"""Shared neural-net building blocks (pure JAX, functional params-as-pytrees)."""
from __future__ import annotations

import math
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

Params = Dict[str, Any]


# --------------------------------------------------------------------------
# initialisers
# --------------------------------------------------------------------------

def trunc_normal(key, shape, stddev, dtype=jnp.float32):
    return stddev * jax.random.truncated_normal(key, -2.0, 2.0, shape, jnp.float32).astype(dtype)


def init_dense(key, d_in: int, d_out: int, dtype=jnp.float32) -> Params:
    """Fan-in scaled dense kernel, no bias (all assigned archs are no-bias)."""
    return {"w": trunc_normal(key, (d_in, d_out), 1.0 / math.sqrt(d_in), dtype)}


def dense(p: Params, x: jnp.ndarray) -> jnp.ndarray:
    return x @ p["w"].astype(x.dtype)


def init_rmsnorm(d: int, dtype=jnp.float32) -> Params:
    return {"scale": jnp.ones((d,), dtype)}


def rmsnorm(p: Params, x: jnp.ndarray, eps: float = 1e-6) -> jnp.ndarray:
    dt = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return (x * jax.lax.rsqrt(var + eps) * p["scale"].astype(jnp.float32)).astype(dt)


def rms_head_norm(scale: jnp.ndarray, x: jnp.ndarray, eps: float = 1e-6) -> jnp.ndarray:
    """qk-norm: RMSNorm over the trailing head_dim of (..., head_dim)."""
    dt = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return (x * jax.lax.rsqrt(var + eps) * scale.astype(jnp.float32)).astype(dt)


# --------------------------------------------------------------------------
# MLPs
# --------------------------------------------------------------------------

def init_mlp(key, d_model: int, d_ff: int, variant: str, dtype=jnp.float32) -> Params:
    k1, k2, k3 = jax.random.split(key, 3)
    if variant in ("swiglu", "geglu"):
        return {
            "wi_gate": init_dense(k1, d_model, d_ff, dtype),
            "wi_up": init_dense(k2, d_model, d_ff, dtype),
            "wo": init_dense(k3, d_ff, d_model, dtype),
        }
    if variant == "gelu":
        return {
            "wi": init_dense(k1, d_model, d_ff, dtype),
            "wo": init_dense(k2, d_ff, d_model, dtype),
        }
    raise ValueError(variant)


def mlp(p: Params, x: jnp.ndarray, variant: str) -> jnp.ndarray:
    if variant == "swiglu":
        h = jax.nn.silu(dense(p["wi_gate"], x)) * dense(p["wi_up"], x)
        return dense(p["wo"], h)
    if variant == "geglu":
        h = jax.nn.gelu(dense(p["wi_gate"], x), approximate=True) * dense(p["wi_up"], x)
        return dense(p["wo"], h)
    if variant == "gelu":
        return dense(p["wo"], jax.nn.gelu(dense(p["wi"], x), approximate=True))
    raise ValueError(variant)


def mlp_flops(d_model: int, d_ff: int, variant: str) -> int:
    """matmul FLOPs per token (multiply-accumulate counted as 2)."""
    n_mats = 3 if variant in ("swiglu", "geglu") else 2
    return 2 * n_mats * d_model * d_ff


# --------------------------------------------------------------------------
# positions
# --------------------------------------------------------------------------

def apply_rope(x: jnp.ndarray, positions: jnp.ndarray, theta: float,
               inv_freq=None, scale: float = 1.0) -> jnp.ndarray:
    """x: (..., seq, n_heads, head_dim) or (..., seq, head_dim);
    positions: broadcastable to (..., seq).  ``inv_freq`` (head_dim // 2,)
    replaces the plain ``theta`` frequencies and ``scale`` multiplies cos
    and sin (YaRN, :func:`yarn_inv_freq`)."""
    head_dim = x.shape[-1]
    half = head_dim // 2
    if inv_freq is None:
        freqs = jnp.exp(-math.log(theta) * jnp.arange(half, dtype=jnp.float32) / half)
    else:
        freqs = jnp.asarray(inv_freq, jnp.float32)
    angles = positions.astype(jnp.float32)[..., None] * freqs  # (..., seq, half)
    if x.ndim == angles.ndim + 2:  # x has a head axis between seq and dim
        angles = angles[..., None, :]
    sin, cos = jnp.sin(angles) * scale, jnp.cos(angles) * scale
    x1, x2 = x[..., :half].astype(jnp.float32), x[..., half:].astype(jnp.float32)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def yarn_mscale(factor: float, mscale: float) -> float:
    """YaRN's attention temperature term: 0.1 * mscale * ln(factor) + 1."""
    return 0.1 * mscale * math.log(factor) + 1.0 if factor > 1 else 1.0


def yarn_inv_freq(dim: int, theta: float, rs) -> np.ndarray:
    """(dim // 2,) YaRN rotary frequencies as DeepSeek-V2 computes them
    (``DeepseekV2YarnRotaryEmbedding``): the extrapolated ``theta``
    frequencies above the correction range, ``factor``-interpolated ones
    below it, and a linear ramp between.  ``rs`` is a
    :class:`~repro.configs.base.YarnConfig`."""
    pos = theta ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    extra, inter = 1.0 / pos, 1.0 / (rs.factor * pos)

    def corr(rotations):
        return (dim * math.log(rs.original_max_position_embeddings
                               / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(corr(rs.beta_fast)), 0)
    high = min(math.ceil(corr(rs.beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0.0, 1.0)
    keep = 1.0 - ramp          # 1 where the frequency is extrapolated
    return (inter * (1.0 - keep) + extra * keep).astype(np.float32)


def sinusoidal_pos(positions: jnp.ndarray, d_model: int) -> jnp.ndarray:
    half = d_model // 2
    freqs = jnp.exp(-math.log(10000.0) * jnp.arange(half, dtype=jnp.float32) / half)
    ang = positions.astype(jnp.float32)[..., None] * freqs
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)


def softcap(x: jnp.ndarray, cap: float) -> jnp.ndarray:
    return cap * jnp.tanh(x / cap) if cap > 0 else x


# --------------------------------------------------------------------------
# losses
# --------------------------------------------------------------------------

def cross_entropy(logits: jnp.ndarray, labels: jnp.ndarray,
                  true_vocab: Optional[int] = None) -> jnp.ndarray:
    """Mean token cross-entropy. logits (..., V_pad), labels (...) int32.
    Padded vocab entries (>= true_vocab) are masked to -inf."""
    logits = logits.astype(jnp.float32)
    if true_vocab is not None and true_vocab < logits.shape[-1]:
        pad = logits.shape[-1] - true_vocab
        mask = jnp.concatenate([
            jnp.zeros((true_vocab,), jnp.float32),
            jnp.full((pad,), -1e9, jnp.float32)])
        logits = logits + mask
    logz = jax.scipy.special.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return jnp.mean(logz - gold)
