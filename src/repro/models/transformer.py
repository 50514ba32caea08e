"""Config-driven assembly of every assigned architecture.

The stack is a list of *segments*; a segment is a repeating pattern of layer
types scanned over ``n_periods`` (stacked params, ``jax.lax.scan``) so compile
time is O(pattern), not O(n_layers).  Cut-layer splitting (repro.core.split)
addresses the stack at *period* granularity via the ``start``/``end``
arguments of :func:`forward_core`.

Modes: ``train`` (full seq, no cache) · ``prefill`` (full seq, returns cache)
· ``decode`` (one token, consumes+returns cache).
"""
from __future__ import annotations

import functools
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import (ATTN, ATTN_LOCAL, ATTN_MOE, MLA_DENSE,
                                MLA_MOE, RGLRU, SSM, ArchConfig)
from repro.models import attention as A
from repro.models import layers as L
from repro.models import mla as M
from repro.models import moe as E
from repro.models import rglru as R
from repro.models import ssm as S

Params = Dict[str, Any]

_ATTN_KINDS = (ATTN, ATTN_LOCAL, ATTN_MOE)
_MLA_KINDS = (MLA_DENSE, MLA_MOE)
_MOE_KINDS = (ATTN_MOE, MLA_MOE)


# --------------------------------------------------------------------------
# per-layer init / apply
# --------------------------------------------------------------------------

def init_layer(key, cfg: ArchConfig, kind: str, dtype) -> Params:
    k1, k2, k3, k4 = jax.random.split(key, 4)
    p: Params = {"norm1": L.init_rmsnorm(cfg.d_model, dtype)}
    if kind in _ATTN_KINDS:
        p["mixer"] = A.init_attn(k1, cfg, dtype)
    elif kind in _MLA_KINDS:
        p["mixer"] = M.init_mla(k1, cfg, dtype)
    elif kind == SSM:
        p["mixer"] = S.init_ssm(k1, cfg, dtype)
        return p  # mamba block has no separate FFN
    elif kind == RGLRU:
        p["mixer"] = R.init_rglru(k1, cfg, dtype)
    else:
        raise ValueError(kind)
    p["norm2"] = L.init_rmsnorm(cfg.d_model, dtype)
    if kind in _MOE_KINDS:
        p["ffn"] = E.init_moe(k2, cfg, dtype)
    else:
        p["ffn"] = L.init_mlp(k2, cfg.d_model, cfg.d_ff, cfg.mlp_variant, dtype)
    return p


def _window(cfg: ArchConfig, kind: str) -> int:
    return cfg.window if kind == ATTN_LOCAL else 0


def apply_layer(p: Params, cfg: ArchConfig, kind: str, x: jnp.ndarray,
                mode: str, positions, cache, capacity: int
                ) -> Tuple[jnp.ndarray, jnp.ndarray, Any]:
    """Returns (x, aux_loss, new_cache)."""
    aux = jnp.zeros((), jnp.float32)
    h = L.rmsnorm(p["norm1"], x)
    new_cache = cache
    if kind in _ATTN_KINDS:
        w = _window(cfg, kind)
        if mode == "train":
            h = A.attn_train(p["mixer"], cfg, h, positions, w)
        elif mode == "prefill":
            h, new_cache = A.attn_prefill(p["mixer"], cfg, h, positions, capacity, w)
        else:
            h, new_cache = A.attn_decode(p["mixer"], cfg, h, cache, w)
    elif kind in _MLA_KINDS:
        if mode == "train":
            h = M.mla_train(p["mixer"], cfg, h, positions)
        elif mode == "prefill":
            h, new_cache = M.mla_prefill(p["mixer"], cfg, h, positions, capacity)
        else:
            h, new_cache = M.mla_decode(p["mixer"], cfg, h, cache)
    elif kind == SSM:
        if mode in ("train",):
            h = S.ssm_train(p["mixer"], cfg, h)
        elif mode == "prefill":
            h, new_cache = S.ssm_prefill(p["mixer"], cfg, h)
        else:
            h, new_cache = S.ssm_decode(p["mixer"], cfg, h, cache)
        return x + h, aux, new_cache
    elif kind == RGLRU:
        if mode == "train":
            h = R.rglru_train(p["mixer"], cfg, h)
        elif mode == "prefill":
            h, new_cache = R.rglru_prefill(p["mixer"], cfg, h)
        else:
            h, new_cache = R.rglru_decode(p["mixer"], cfg, h, cache)
    x = x + h
    h = L.rmsnorm(p["norm2"], x)
    if kind in _MOE_KINDS:
        h, aux = E.moe_forward(p["ffn"], cfg, h)
    else:
        h = L.mlp(p["ffn"], h, cfg.mlp_variant)
    return x + h, aux, new_cache


def init_layer_cache(cfg: ArchConfig, kind: str, batch: int, capacity: int,
                     dtype) -> Any:
    if kind in _ATTN_KINDS:
        return A.init_cache(cfg, batch, capacity, _window(cfg, kind), dtype)
    if kind in _MLA_KINDS:
        return M.init_mla_cache(cfg, batch, capacity, dtype)
    if kind == SSM:
        return S.init_ssm_cache(cfg, batch, dtype)
    if kind == RGLRU:
        return R.init_rglru_cache(cfg, batch, dtype)
    raise ValueError(kind)


# --------------------------------------------------------------------------
# segments
# --------------------------------------------------------------------------

def segments_of(cfg: ArchConfig) -> List[Tuple[Tuple[str, ...], int]]:
    segs = [(tuple(cfg.lead), 1)] if cfg.lead else []
    segs.append((tuple(cfg.pattern), cfg.n_periods))
    if cfg.tail:
        segs.append((tuple(cfg.tail), 1))
    return segs


def total_periods(cfg: ArchConfig) -> int:
    return sum(n for _, n in segments_of(cfg))


def init_segment(key, cfg: ArchConfig, pattern, n_periods: int, dtype):
    def one(k):
        ks = jax.random.split(k, len(pattern))
        return tuple(init_layer(ks[i], cfg, t, dtype) for i, t in enumerate(pattern))
    return jax.vmap(one)(jax.random.split(key, n_periods))


def _slice_leaves(tree, lo: int, hi: int):
    return jax.tree.map(lambda a: a[lo:hi], tree)


# Remat policy for the layer-scan body (perf knob, trace-time switch):
# None = full recompute; "dots" = save dot outputs (cuts the recomputed
# matmuls AND their collectives in the backward pass at the cost of
# activation memory).
REMAT_POLICY = None


def set_remat_policy(name):
    global REMAT_POLICY
    REMAT_POLICY = name


def _scan_segment(seg_params, cfg: ArchConfig, pattern, x, mode: str,
                  positions, caches, capacity: int, remat: bool):
    """Scan the period body over the (already sliced) stacked params."""
    def body(carry, xs):
        xc, auxc = carry
        if mode == "decode":
            pp, cc = xs
        else:
            pp, cc = xs, None
        new_caches = []
        for i, kind in enumerate(pattern):
            layer_cache = cc[i] if cc is not None else None
            xc, aux, ncache = apply_layer(pp[i], cfg, kind, xc, mode,
                                          positions, layer_cache, capacity)
            auxc = auxc + aux
            new_caches.append(ncache)
        return (xc, auxc), tuple(new_caches)

    if remat and mode == "train":
        policy = None
        if REMAT_POLICY == "dots":
            policy = jax.checkpoint_policies.dots_with_no_batch_dims_saveable
        body = jax.checkpoint(body, prevent_cse=False, policy=policy)
    xs = (seg_params, caches) if mode == "decode" else seg_params
    (x, aux), out_caches = jax.lax.scan(body, (x, jnp.zeros((), jnp.float32)), xs)
    return x, aux, out_caches


# --------------------------------------------------------------------------
# model-level params
# --------------------------------------------------------------------------

def init_params(key, cfg: ArchConfig, dtype=None) -> Params:
    dtype = dtype or jnp.dtype(cfg.param_dtype)
    keys = jax.random.split(key, 4 + len(segments_of(cfg)))
    vp, d = cfg.padded_vocab, cfg.d_model
    p: Params = {}
    if cfg.frontend == "audio":
        p["embed"] = L.trunc_normal(keys[0], (cfg.n_codebooks, vp, d), d ** -0.5, dtype)
        p["head"] = L.trunc_normal(keys[1], (d, cfg.n_codebooks, vp), d ** -0.5, dtype)
    else:
        p["embed"] = L.trunc_normal(keys[0], (vp, d), d ** -0.5, dtype)
        p["head"] = L.trunc_normal(keys[1], (d, vp), d ** -0.5, dtype)
    p["final_norm"] = L.init_rmsnorm(d, dtype)
    p["segments"] = tuple(
        init_segment(keys[4 + i], cfg, pat, n, dtype)
        for i, (pat, n) in enumerate(segments_of(cfg)))
    return p


def embed_inputs(p: Params, cfg: ArchConfig, batch: Dict[str, jnp.ndarray],
                 positions: jnp.ndarray) -> jnp.ndarray:
    """batch -> (b, s, d) activations (the vehicle-side input boundary)."""
    if cfg.frontend == "vision" and "patch_embeds" in batch:
        tok = p["embed"][batch["tokens"]]
        x = jnp.concatenate([batch["patch_embeds"].astype(tok.dtype), tok], axis=1)
    elif cfg.frontend == "audio":
        # sum over codebook embeddings (MusicGen interleave collapse)
        codes = batch["codes"]                      # (b, K, s)
        x = jnp.zeros((codes.shape[0], codes.shape[2], cfg.d_model),
                      p["embed"].dtype)
        for k in range(cfg.n_codebooks):
            x = x + p["embed"][k][codes[:, k]]
    else:
        x = p["embed"][batch["tokens"]]
    if cfg.pos == "sinusoidal":
        x = x + L.sinusoidal_pos(positions, cfg.d_model).astype(x.dtype)
    return x


def unembed(p: Params, cfg: ArchConfig, x: jnp.ndarray) -> jnp.ndarray:
    x = L.rmsnorm(p["final_norm"], x)
    if cfg.frontend == "audio":
        logits = jnp.einsum("bsd,dkv->bskv", x, p["head"].astype(x.dtype))
    else:
        logits = jnp.einsum("bsd,dv->bsv", x, p["head"].astype(x.dtype))
    return L.softcap(logits, cfg.logit_softcap)


def forward_core(p: Params, cfg: ArchConfig, x: jnp.ndarray, mode: str,
                 positions=None, caches=None, capacity: int = 0,
                 start: int = 0, end: Optional[int] = None,
                 remat: bool = True):
    """Run periods [start, end) of the stack.  Returns (x, aux, caches)."""
    end = total_periods(cfg) if end is None else end
    aux = jnp.zeros((), jnp.float32)
    out_caches = []
    off = 0
    for si, (pat, n) in enumerate(segments_of(cfg)):
        lo, hi = max(start - off, 0), min(end - off, n)
        if lo < hi:
            seg_p = _slice_leaves(p["segments"][si], lo, hi)
            seg_c = None
            if caches is not None:
                seg_c = _slice_leaves(caches[si], lo, hi)
            x, a, nc = _scan_segment(seg_p, cfg, pat, x, mode, positions,
                                     seg_c, capacity, remat)
            aux = aux + a
            out_caches.append(nc)
        else:
            out_caches.append(None)
        off += n
    return x, aux, tuple(out_caches)


def init_caches(cfg: ArchConfig, batch: int, capacity: int, dtype=jnp.float32,
                start: int = 0, end: Optional[int] = None):
    """Stacked per-segment caches for periods [start, end)."""
    end = total_periods(cfg) if end is None else end
    caches = []
    off = 0
    for pat, n in segments_of(cfg):
        lo, hi = max(start - off, 0), min(end - off, n)
        if lo < hi:
            def one(_):
                return tuple(init_layer_cache(cfg, t, batch, capacity, dtype)
                             for t in pat)
            stacked = jax.tree.map(
                lambda *xs: jnp.stack(xs), *[one(i) for i in range(hi - lo)])
            caches.append(stacked)
        else:
            caches.append(None)
        off += n
    return tuple(caches)


# --------------------------------------------------------------------------
# whole-model convenience (used by fedsim / examples / smoke tests)
# --------------------------------------------------------------------------

def forward(p: Params, cfg: ArchConfig, batch: Dict[str, jnp.ndarray],
            mode: str = "train", caches=None, capacity: int = 0,
            pos_offset=0, remat: bool = False):
    """Full model: embed -> stack -> head.  Returns (logits, aux, caches)."""
    if mode == "decode":
        positions = jnp.asarray([pos_offset], jnp.int32)
        x = embed_inputs(p, cfg, batch, positions)
    else:
        if cfg.frontend == "vision":
            s = batch["tokens"].shape[1] + cfg.n_patches
        elif cfg.frontend == "audio":
            s = batch["codes"].shape[2]
        else:
            s = batch["tokens"].shape[1]
        positions = jnp.arange(s, dtype=jnp.int32)
        x = embed_inputs(p, cfg, batch, positions)
    x, aux, caches = forward_core(p, cfg, x, mode, positions, caches,
                                  capacity, remat=remat)
    return unembed(p, cfg, x), aux, caches


def loss_fn(p: Params, cfg: ArchConfig, batch: Dict[str, jnp.ndarray],
            remat: bool = False) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    logits, aux, _ = forward(p, cfg, batch, "train", remat=remat)
    if cfg.frontend == "audio":
        # next-frame prediction over the K codebooks
        ce = L.cross_entropy(logits, batch["codes"].swapaxes(1, 2), cfg.vocab_size)
    else:
        if cfg.frontend == "vision":
            logits = logits[:, cfg.n_patches:]      # loss on text positions
        ce = L.cross_entropy(logits, batch["labels"], cfg.vocab_size)
    return ce + aux, {"ce": ce, "aux": aux}


# --------------------------------------------------------------------------
# analytic parameter count (roofline MODEL_FLOPS = 6 N D)
# --------------------------------------------------------------------------

def count_params(cfg: ArchConfig, active_only: bool = False) -> int:
    d, ff, vp = cfg.d_model, cfg.d_ff, cfg.padded_vocab
    total = 0
    for kind in cfg.layer_types:
        n = 2 * d  # norms
        if kind in _ATTN_KINDS:
            hd = cfg.head_dim_
            n += d * hd * (cfg.n_heads * 2 + cfg.n_kv_heads * 2)
        elif kind in _MLA_KINDS:
            m = cfg.mla
            qk = m.qk_nope_dim + m.qk_rope_dim
            n += d * (cfg.n_heads * qk + m.kv_lora_rank + m.qk_rope_dim)
            n += m.kv_lora_rank * cfg.n_heads * (m.qk_nope_dim + m.v_head_dim)
            n += cfg.n_heads * m.v_head_dim * d + m.kv_lora_rank
        elif kind == SSM:
            d_inner, n_heads, conv_dim = S.dims(cfg)
            n = d + d * (2 * d_inner + 2 * cfg.ssm.n_groups * cfg.ssm.d_state
                         + n_heads)
            n += cfg.ssm.d_conv * conv_dim + conv_dim + 3 * n_heads
            n += d_inner + d_inner * d
            total += n
            continue
        elif kind == RGLRU:
            dr = cfg.rglru.d_rnn or d
            n += d * dr * 2 + dr * d + 2 * dr * dr + 3 * dr + cfg.rglru.d_conv * dr
        # FFN
        if kind in _MOE_KINDS:
            m = cfg.moe
            eff = m.d_ff_expert or ff
            n_e = m.top_k if active_only else m.n_experts
            n += d * m.n_experts  # router
            n += (n_e + m.n_shared) * 3 * d * eff
        elif kind != SSM:
            mats = 3 if cfg.mlp_variant in ("swiglu", "geglu") else 2
            n += mats * d * ff
        total += n
    emb = vp * d * (cfg.n_codebooks if cfg.frontend == "audio" else 1)
    head = d * vp * (cfg.n_codebooks if cfg.frontend == "audio" else 1)
    return total + emb + head + d
