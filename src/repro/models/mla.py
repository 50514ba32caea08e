"""Multi-head Latent Attention (DeepSeek-V2).  The KV cache stores only the
compressed latent c_kv (kv_lora_rank) + the shared rotary key (qk_rope_dim);
decode uses the absorbed formulation (q_nope absorbed through W_uk so scores
are taken directly against the latent cache) — the actual MLA serving trick.
"""
from __future__ import annotations

import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.models import layers as L
from repro.models.attention import NEG_INF

Params = Dict[str, Any]


def init_mla(key, cfg: ArchConfig, dtype=jnp.float32) -> Params:
    m = cfg.mla
    d, h = cfg.d_model, cfg.n_heads
    qk = m.qk_nope_dim + m.qk_rope_dim
    ks = jax.random.split(key, 6)
    s = 1.0 / math.sqrt(d)
    return {
        "wq": L.trunc_normal(ks[0], (d, h, qk), s, dtype),
        "w_dkv": L.trunc_normal(ks[1], (d, m.kv_lora_rank), s, dtype),
        "w_kr": L.trunc_normal(ks[2], (d, m.qk_rope_dim), s, dtype),
        "kv_norm": L.init_rmsnorm(m.kv_lora_rank, dtype),
        "w_uk": L.trunc_normal(ks[3], (m.kv_lora_rank, h, m.qk_nope_dim),
                               1.0 / math.sqrt(m.kv_lora_rank), dtype),
        "w_uv": L.trunc_normal(ks[4], (m.kv_lora_rank, h, m.v_head_dim),
                               1.0 / math.sqrt(m.kv_lora_rank), dtype),
        "wo": L.trunc_normal(ks[5], (h, m.v_head_dim, d),
                             1.0 / math.sqrt(h * m.v_head_dim), dtype),
    }


LORA_TARGETS = ("q", "kv_a", "kv_b", "o")


def lora_shapes(cfg: ArchConfig) -> Dict[str, Tuple[int, int]]:
    """(in, out) of the four published projections LoRA adapts:
    ``q_proj``, ``kv_a_proj_with_mqa`` (``w_dkv`` and ``w_kr`` side by
    side), ``kv_b_proj`` (per head, ``w_uk`` then ``w_uv``) and
    ``o_proj``."""
    m, d, h = cfg.mla, cfg.d_model, cfg.n_heads
    return {"q": (d, h * (m.qk_nope_dim + m.qk_rope_dim)),
            "kv_a": (d, m.kv_lora_rank + m.qk_rope_dim),
            "kv_b": (m.kv_lora_rank, h * (m.qk_nope_dim + m.v_head_dim)),
            "o": (h * m.v_head_dim, d)}


def _lora(x: jnp.ndarray, ad: Params, scale: float) -> jnp.ndarray:
    """LoRA's low-rank update of one projection: scale * x A B."""
    return scale * ((x @ ad["a"].astype(x.dtype)) @ ad["b"].astype(x.dtype))


def rope(cfg: ArchConfig, x: jnp.ndarray, positions) -> jnp.ndarray:
    """The rotary part of queries and keys: plain ``rope_theta``
    frequencies, or YaRN's when the config publishes ``rope_scaling``."""
    rs = cfg.rope_scaling
    if rs is None:
        return L.apply_rope(x, positions, cfg.rope_theta)
    return L.apply_rope(
        x, positions, cfg.rope_theta,
        inv_freq=L.yarn_inv_freq(cfg.mla.qk_rope_dim, cfg.rope_theta, rs),
        scale=(L.yarn_mscale(rs.factor, rs.mscale)
               / L.yarn_mscale(rs.factor, rs.mscale_all_dim)))


def softmax_scale(cfg: ArchConfig) -> float:
    """1/sqrt(qk head dim), times YaRN's mscale squared when published."""
    m, rs = cfg.mla, cfg.rope_scaling
    scale = 1.0 / math.sqrt(m.qk_nope_dim + m.qk_rope_dim)
    if rs is not None and rs.mscale_all_dim:
        scale *= L.yarn_mscale(rs.factor, rs.mscale_all_dim) ** 2
    return scale


def _latents(p: Params, cfg: ArchConfig, x: jnp.ndarray, positions,
             lora=None, lora_scale: float = 0.0):
    kv = jnp.einsum("bsd,dr->bsr", x, p["w_dkv"].astype(x.dtype))
    k_rope = jnp.einsum("bsd,dr->bsr", x, p["w_kr"].astype(x.dtype))
    if lora is not None:
        delta = _lora(x, lora["kv_a"], lora_scale)
        r = cfg.mla.kv_lora_rank
        kv, k_rope = kv + delta[..., :r], k_rope + delta[..., r:]
    c_kv = L.rmsnorm(p["kv_norm"], kv)
    return c_kv, rope(cfg, k_rope, positions)


def _queries(p: Params, cfg: ArchConfig, x: jnp.ndarray, positions,
             lora=None, lora_scale: float = 0.0):
    m = cfg.mla
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"].astype(x.dtype))
    if lora is not None:
        q = q + _lora(x, lora["q"], lora_scale).reshape(q.shape)
    q_nope, q_rope = q[..., :m.qk_nope_dim], q[..., m.qk_nope_dim:]
    return q_nope, rope(cfg, q_rope, positions)


def mla_train(p: Params, cfg: ArchConfig, x: jnp.ndarray,
              positions: jnp.ndarray, lora: Optional[Params] = None,
              lora_scale: float = 0.0) -> jnp.ndarray:
    """Naive (materialised K/V) path for train/prefill.  ``lora`` holds
    an ``{"a", "b"}`` adapter per :data:`LORA_TARGETS` entry, each acting
    on the published projection matrix, scaled by ``lora_scale``."""
    m = cfg.mla
    b, s, _ = x.shape
    c_kv, k_rope = _latents(p, cfg, x, positions, lora, lora_scale)
    q_nope, q_rope = _queries(p, cfg, x, positions, lora, lora_scale)
    k_nope = jnp.einsum("bsr,rhk->bshk", c_kv, p["w_uk"].astype(x.dtype))
    v = jnp.einsum("bsr,rhk->bshk", c_kv, p["w_uv"].astype(x.dtype))
    if lora is not None:
        kvb = _lora(c_kv, lora["kv_b"], lora_scale).reshape(
            b, s, cfg.n_heads, m.qk_nope_dim + m.v_head_dim)
        k_nope = k_nope + kvb[..., :m.qk_nope_dim]
        v = v + kvb[..., m.qk_nope_dim:]
    scale = softmax_scale(cfg)
    scores = (jnp.einsum("bshk,bthk->bhst", q_nope, k_nope)
              + jnp.einsum("bshk,btk->bhst", q_rope, k_rope)).astype(jnp.float32) * scale
    mask = positions[None, :] <= positions[:, None]      # (s, t)
    scores = jnp.where(mask[None, None], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    o = jnp.einsum("bhst,bthk->bshk", probs, v)
    y = jnp.einsum("bshk,hkd->bsd", o, p["wo"].astype(x.dtype))
    if lora is not None:
        y = y + _lora(o.reshape(b, s, -1), lora["o"], lora_scale)
    return y


def init_mla_cache(cfg: ArchConfig, batch: int, capacity: int,
                   dtype=jnp.float32) -> Params:
    m = cfg.mla
    return {
        "c_kv": jnp.zeros((batch, capacity, m.kv_lora_rank), dtype),
        "k_rope": jnp.zeros((batch, capacity, m.qk_rope_dim), dtype),
        "pos": jnp.zeros((), jnp.int32),
    }


def mla_prefill(p: Params, cfg: ArchConfig, x: jnp.ndarray,
                positions: jnp.ndarray, capacity: int
                ) -> Tuple[jnp.ndarray, Params]:
    b, s, _ = x.shape
    y = mla_train(p, cfg, x, positions)
    c_kv, k_rope = _latents(p, cfg, x, positions)
    cache = init_mla_cache(cfg, b, capacity, c_kv.dtype)
    n = min(s, capacity)
    cache["c_kv"] = jax.lax.dynamic_update_slice_in_dim(cache["c_kv"], c_kv[:, :n], 0, axis=1)
    cache["k_rope"] = jax.lax.dynamic_update_slice_in_dim(cache["k_rope"], k_rope[:, :n], 0, axis=1)
    cache["pos"] = jnp.asarray(s, jnp.int32)
    return y, cache


def mla_decode(p: Params, cfg: ArchConfig, x: jnp.ndarray,
               cache: Params) -> Tuple[jnp.ndarray, Params]:
    """Absorbed decode: scores against the latent cache, O(S * (r + rope))."""
    m = cfg.mla
    b = x.shape[0]
    pos = cache["pos"]
    positions = pos[None].astype(jnp.int32)  # (1,)
    c_new, kr_new = _latents(p, cfg, x, positions)
    size = cache["c_kv"].shape[1]
    slot = jnp.minimum(pos, size - 1)
    c_all = jax.lax.dynamic_update_slice_in_dim(cache["c_kv"], c_new, slot, axis=1)
    kr_all = jax.lax.dynamic_update_slice_in_dim(cache["k_rope"], kr_new, slot, axis=1)

    q_nope, q_rope = _queries(p, cfg, x, positions)
    # absorb: q' = q_nope @ W_uk  -> (b, 1, h, r); scores vs latent directly
    q_abs = jnp.einsum("bshk,rhk->bshr", q_nope, p["w_uk"].astype(x.dtype))
    scale = softmax_scale(cfg)
    scores = (jnp.einsum("bshr,btr->bhst", q_abs, c_all)
              + jnp.einsum("bshk,btk->bhst", q_rope, kr_all)).astype(jnp.float32) * scale
    kpos = jnp.arange(size, dtype=jnp.int32)
    scores = jnp.where((kpos <= pos)[None, None, None, :], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1).astype(x.dtype)
    o_lat = jnp.einsum("bhst,btr->bshr", probs, c_all)       # attend over latents
    o = jnp.einsum("bshr,rhk->bshk", o_lat, p["w_uv"].astype(x.dtype))
    y = jnp.einsum("bshk,hkd->bsd", o, p["wo"].astype(x.dtype))
    return y, {"c_kv": c_all, "k_rope": kr_all, "pos": pos + 1}


def mla_flops(cfg: ArchConfig, seq: int) -> int:
    m, d, h = cfg.mla, cfg.d_model, cfg.n_heads
    qk = m.qk_nope_dim + m.qk_rope_dim
    proj = 2 * d * (h * qk + m.kv_lora_rank + m.qk_rope_dim) \
        + 2 * m.kv_lora_rank * h * (m.qk_nope_dim + m.v_head_dim) \
        + 2 * h * m.v_head_dim * d
    sdpa = 2 * 2 * h * qk * seq
    return proj + sdpa
