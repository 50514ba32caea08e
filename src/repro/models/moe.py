"""Mixture-of-Experts FFN — two TPU-friendly formulations:

* **Grouped GShard dispatch/combine** (train / prefill): tokens are tiled
  into groups of ~1024, each group builds a (tpg, E, capacity) one-hot
  dispatch.  Capacity is per-group, so the dispatch tensor is linear in total
  tokens (not quadratic).  With experts sharded over the `model` mesh axis
  this lowers to the canonical expert-parallel all-to-all.
* **Dense-gather** (decode / tiny batches): every expert runs on every token
  and the router gates the sum.  Exact (no capacity drops), cheap when
  T * E * d_ff is small — the right trade at one-token decode.

* **Held experts, dropless** (:func:`moe_held`): one chip's share of an
  expert-parallel layer.  The router scores all experts; the chip computes
  only its held experts' part of the result, for every token routed to
  them (no capacity, no drops), as a grouped matmul over the assigned
  rows (``lax.ragged_dot``).  The experts are frozen there: the grouped
  matmul carries the input gradient only (split LoRA fine-tuning).

DBRX: 16 routed top-4.  DeepSeek-V2-Lite: 64 routed top-6 + 2 shared.
Aux load-balance loss follows Switch/GShard.
"""
from __future__ import annotations

import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.models import layers as L

Params = Dict[str, Any]

TARGET_TOKENS_PER_GROUP = 1024
DENSE_PATH_MAX_ELEMENTS = 2 ** 27   # T*E*d_ff budget for the dense path


def _expert_ff(cfg: ArchConfig) -> int:
    return cfg.moe.d_ff_expert or cfg.d_ff


def init_moe(key, cfg: ArchConfig, dtype=jnp.float32) -> Params:
    m = cfg.moe
    d, ff, e = cfg.d_model, _expert_ff(cfg), m.n_experts
    k1, k2, k3, k4, k5 = jax.random.split(key, 5)
    std_in, std_out = 1.0 / math.sqrt(d), 1.0 / math.sqrt(ff)
    p = {
        "router": L.trunc_normal(k1, (d, e), std_in, jnp.float32),
        "wi_gate": L.trunc_normal(k2, (e, d, ff), std_in, dtype),
        "wi_up": L.trunc_normal(k3, (e, d, ff), std_in, dtype),
        "wo": L.trunc_normal(k4, (e, ff, d), std_out, dtype),
    }
    if m.n_shared:
        p["shared"] = L.init_mlp(k5, d, m.n_shared * ff, "swiglu", dtype)
    return p


def _route(p: Params, cfg: ArchConfig, xt: jnp.ndarray):
    """(t,d) -> (probs (t,E), gate_vals (t,k), expert_idx (t,k), aux)."""
    m = cfg.moe
    logits = xt.astype(jnp.float32) @ p["router"]
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, expert_idx = jax.lax.top_k(probs, m.top_k)
    if m.norm_topk_prob:
        gate_vals = gate_vals / jnp.maximum(
            jnp.sum(gate_vals, axis=-1, keepdims=True), 1e-9)
    onehot = jax.nn.one_hot(expert_idx, m.n_experts, dtype=jnp.float32)
    frac_tokens = jnp.mean(jnp.sum(onehot, axis=1), axis=0)
    frac_probs = jnp.mean(probs, axis=0)
    aux = (m.n_experts * jnp.sum(frac_tokens / m.top_k * frac_probs)
           * m.aux_loss_weight)
    return probs, gate_vals, expert_idx, onehot, aux


def _experts_dense(p: Params, cfg: ArchConfig, xt, gate_vals, expert_idx):
    """All-experts compute, router-gated sum (decode path)."""
    m = cfg.moe
    w = jax.nn.one_hot(expert_idx, m.n_experts, dtype=jnp.float32)
    w = jnp.sum(w * gate_vals[..., None], axis=1)            # (t, E)
    h = jax.nn.silu(jnp.einsum("td,edf->tef", xt, p["wi_gate"].astype(xt.dtype)))
    h = h * jnp.einsum("td,edf->tef", xt, p["wi_up"].astype(xt.dtype))
    out = jnp.einsum("tef,efd->ted", h, p["wo"].astype(xt.dtype))
    return jnp.einsum("te,ted->td", w.astype(xt.dtype), out)


def _pick_groups(t: int) -> int:
    g = max(t // TARGET_TOKENS_PER_GROUP, 1)
    while g > 1 and t % g:
        g -= 1
    return g


def _experts_grouped(p: Params, cfg: ArchConfig, xt, gate_vals, expert_idx,
                     n_groups: Optional[int]):
    """GShard grouped dispatch/combine (train/prefill path)."""
    m = cfg.moe
    t, d = xt.shape
    e, k = m.n_experts, m.top_k
    g = n_groups or _pick_groups(t)
    tpg = t // g
    cap = max(4, min(int(math.ceil(tpg * k / e * m.capacity_factor)), tpg))

    xg = xt.reshape(g, tpg, d)
    idx = expert_idx.reshape(g, tpg, k)
    gates = gate_vals.reshape(g, tpg, k)

    onehot = jax.nn.one_hot(idx, e, dtype=jnp.int32)          # (g,tpg,k,e)
    flat = onehot.reshape(g, tpg * k, e)
    pos = (jnp.cumsum(flat, axis=1) - flat).reshape(g, tpg, k, e)
    pos = jnp.sum(pos * onehot, axis=-1)                      # (g,tpg,k)
    keep = pos < cap
    gates = jnp.where(keep, gates, 0.0)

    combine = jnp.einsum(
        "gtke,gtkc->gtec",
        (onehot * keep[..., None]).astype(jnp.float32),
        jax.nn.one_hot(pos, cap, dtype=jnp.float32) * gates[..., None])
    dispatch = (combine > 0).astype(xt.dtype)                 # (g,tpg,e,cap)

    expert_in = jnp.einsum("gtec,gtd->gecd", dispatch, xg)    # all-to-all here
    h = jax.nn.silu(jnp.einsum("gecd,edf->gecf", expert_in,
                               p["wi_gate"].astype(xt.dtype)))
    h = h * jnp.einsum("gecd,edf->gecf", expert_in, p["wi_up"].astype(xt.dtype))
    expert_out = jnp.einsum("gecf,efd->gecd", h, p["wo"].astype(xt.dtype))
    y = jnp.einsum("gtec,gecd->gtd", combine.astype(xt.dtype), expert_out)
    return y.reshape(t, d)


def moe_forward(p: Params, cfg: ArchConfig, x: jnp.ndarray,
                n_groups: Optional[int] = None
                ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """x (..., d) -> (y, aux_loss)."""
    m = cfg.moe
    orig_shape = x.shape
    d = orig_shape[-1]
    xt = x.reshape(-1, d)
    t = xt.shape[0]

    _, gate_vals, expert_idx, _, aux = _route(p, cfg, xt)
    if t * m.n_experts * _expert_ff(cfg) <= DENSE_PATH_MAX_ELEMENTS:
        y = _experts_dense(p, cfg, xt, gate_vals, expert_idx)
    else:
        y = _experts_grouped(p, cfg, xt, gate_vals, expert_idx, n_groups)

    if m.n_shared:
        y = y + L.mlp(p["shared"], xt, "swiglu")
    return y.reshape(orig_shape), aux


def capacity(n_tokens: int, cfg: ArchConfig) -> int:
    m = cfg.moe
    g = _pick_groups(n_tokens)
    tpg = n_tokens // g
    return max(4, min(int(math.ceil(tpg * m.top_k / m.n_experts
                                    * m.capacity_factor)), tpg))


def moe_flops(cfg: ArchConfig) -> int:
    """Active matmul FLOPs per token (routed top-k + shared)."""
    m, d, ff = cfg.moe, cfg.d_model, _expert_ff(cfg)
    per_expert = 2 * 3 * d * ff
    return m.top_k * per_expert + m.n_shared * per_expert + 2 * d * m.n_experts


# --------------------------------------------------------------------------
# held experts, dropless (one chip's share of an expert-parallel layer)
# --------------------------------------------------------------------------

@jax.custom_batching.custom_vmap
def _ragged(x, w, sizes):
    return jax.lax.ragged_dot(x, w, sizes)


@_ragged.def_vmap
def _ragged_vmap(axis_size, in_batched, x, w, sizes):
    """Batched rows over shared experts (an RSU axis vmapped over the same
    frozen weights, which are never batched): one grouped matmul per batch
    row, in a loop, instead of ``ragged_dot``'s batching rule, which wants
    the weights batched too (a copy of every held expert per row)."""
    xb, _, sb = in_batched
    bc = lambda a, b: a if b else jnp.broadcast_to(a, (axis_size,) + a.shape)
    return jax.lax.map(lambda a: _ragged(a[0], w, a[1]),
                       (bc(x, xb), bc(sizes, sb))), True


@jax.custom_vjp
def grouped_matmul(x, w, sizes):
    """(m, k) rows sorted by group, (g, k, n) frozen weights, (g,) group
    sizes -> (m, n); rows past ``sum(sizes)`` come out zero.  Only ``x``
    has a gradient: differentiating with respect to ``w`` raises."""
    return _ragged(x, w, sizes)


def _gmm_fwd(x, w, sizes):
    if w.perturbed:
        raise TypeError("grouped_matmul holds frozen expert weights: "
                        "they have no gradient")
    return _ragged(x.value, w.value, sizes.value), (w.value, sizes.value)


def _gmm_bwd(res, dy):
    w, sizes = res
    if isinstance(dy, jax.custom_derivatives.SymbolicZero):
        return None, None, None
    return _ragged(dy, jnp.swapaxes(w, 1, 2), sizes), None, None


grouped_matmul.defvjp(_gmm_fwd, _gmm_bwd, symbolic_zeros=True)


def moe_held(p: Params, cfg: ArchConfig, x: jnp.ndarray, first: int
             ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """x (..., d) -> (y, rows): the part of a routed-expert layer that the
    held experts ``[first, first + n_held)`` give, plus the shared experts.

    ``p["router"]`` scores all ``cfg.moe.n_experts``; ``p["wi_gate"]``,
    ``p["wi_up"]`` and ``p["wo"]`` lead with the ``n_held`` experts held.
    Every (token, expert) pair of the top-k that lands on a held expert is
    computed — no capacity, no dropped token — as one grouped matmul over
    the pairs sorted by expert; pairs on absent experts sort past the last
    group and add nothing.  Gates are the router's softmax probabilities,
    renormalised over the top-k only when ``norm_topk_prob``.  ``rows`` is
    the number of (token, held expert) pairs computed."""
    m = cfg.moe
    k, n_held = m.top_k, p["wi_gate"].shape[0]
    shape = x.shape
    xt = x.reshape(-1, shape[-1])
    with jax.named_scope("asfl.moe_route"):
        _, gates, idx, _, _ = _route(p, cfg, xt)
        local = idx - first
        held = (local >= 0) & (local < n_held)               # (t, k)
        group = jnp.where(held, local, n_held).reshape(-1)   # (t*k,)
        order = jnp.argsort(group, stable=True)
        sizes = jnp.sum(group[None, :] == jnp.arange(n_held)[:, None],
                        axis=1).astype(jnp.int32)
        rows = xt[order // k]
    with jax.named_scope("asfl.moe_experts"):
        h = jax.nn.silu(grouped_matmul(rows, p["wi_gate"], sizes)) \
            * grouped_matmul(rows, p["wi_up"], sizes)
        out = grouped_matmul(h, p["wo"], sizes)
        out = out[jnp.argsort(order)].reshape(idx.shape + (shape[-1],))
        y = jnp.einsum("tk,tkd->td", jnp.where(held, gates, 0.0).astype(
            out.dtype), jnp.where(held[..., None], out, 0.0))
    if m.n_shared:
        with jax.named_scope("asfl.moe_shared"):
            y = y + L.mlp(p["shared"], xt, "swiglu")
    return y.reshape(shape), jnp.sum(held, dtype=jnp.int32)
