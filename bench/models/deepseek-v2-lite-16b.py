"""Plain DeepSeek-V2-Lite (arXiv:2405.04434) under split LoRA fine-tuning,
and its fleet data.

The benchmark's own model: the adapters it hands the engine and the
reference it checks the engine against.  Straightforward ``jax.numpy``
at the published names and shapes of the checkpoint's config.json: MLA
with YaRN rotary, a dense SwiGLU layer 0, then MoE layers (softmax router
over all ``router_experts``, greedy top-k, gates not renormalised, every
held expert computed on every token and weighted by its gate, two shared
experts), final RMSNorm and LM head over the vocabulary slice.  LoRA
(rank and alpha from ``model_kwargs``) adapts ``q_proj``,
``kv_a_proj_with_mqa``, ``kv_b_proj`` and ``o_proj``; everything else is
a frozen base drawn from ``model_kwargs.base_seed`` by the rule the
configuration states, rebuilt inside every ``loss`` so that no base weight
is a compiled constant.  Norm weights are ones and are left out.
"""
from __future__ import annotations

import math
import zlib

import jax
import jax.numpy as jnp
import numpy as np

TARGETS = ("q", "kv_a", "kv_b", "o")
BOS = 0


def _dims(cfg):
    h, r = cfg["num_attention_heads"], cfg["kv_lora_rank"]
    nope, rope, v = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                     cfg["v_head_dim"])
    return h, r, nope, rope, v


def lora_shapes(cfg):
    """(in, out) of each adapted published projection."""
    d = cfg["hidden_size"]
    h, r, nope, rope, v = _dims(cfg)
    return {"q": (d, h * (nope + rope)), "kv_a": (d, r + rope),
            "kv_b": (r, h * (nope + v)), "o": (h * v, d)}


def init(key, cfg, dtype=jnp.float32):
    """(units, head): unit 0 (the embedding) and the head have no
    adapters; each layer unit has A ~ N(0, 1/in) and B = 0 per target."""
    rank = cfg["model_kwargs"]["lora_rank"]
    units = [{}]
    for u in range(1, cfg["num_hidden_layers"] + 1):
        ad = {}
        for i, t in enumerate(TARGETS):
            din, dout = lora_shapes(cfg)[t]
            ka, _ = jax.random.split(jax.random.fold_in(
                jax.random.fold_in(key, u), i))
            ad[t] = {"a": (jax.random.normal(ka, (din, rank))
                           * din ** -0.5).astype(dtype),
                     "b": jnp.zeros((rank, dout), dtype)}
        units.append(ad)
    return units, {}


# ---------------------------------------------------------------- the base

def _weight(seed, unit, name, shape, expert=None):
    k = jax.random.fold_in(jax.random.fold_in(jax.random.PRNGKey(seed), unit),
                           zlib.crc32(name.encode()) & 0x7FFFFFFF)
    if expert is not None:
        k = jax.random.fold_in(k, expert)
    std = 1.0 if name == "embed_tokens" else shape[0] ** -0.5
    return std * jax.random.normal(k, shape, jnp.float32)


def base(cfg, seed):
    """The frozen base at its published names: a dict per unit (0 the
    embedding, 1.. the layers) and the head's ``lm_head``; the routed
    experts held are 0 .. n_routed_experts - 1 of ``router_experts``."""
    d, vocab = cfg["hidden_size"], cfg["vocab_size"]
    h, r, nope, rope, v = _dims(cfg)
    ff, ffe = cfg["intermediate_size"], cfg["moe_intermediate_size"]
    sff = cfg["n_shared_experts"] * ffe
    layers = []
    for l in range(cfg["num_hidden_layers"]):
        u = l + 1
        w = lambda name, shape, e=None: _weight(seed, u, name, shape, e)
        p = {"q_proj": w("q_proj", (d, h * (nope + rope))),
             "kv_a_proj_with_mqa": w("kv_a_proj_with_mqa", (d, r + rope)),
             "kv_b_proj": w("kv_b_proj", (r, h * (nope + v))),
             "o_proj": w("o_proj", (h * v, d))}
        if l < cfg["first_k_dense_replace"]:
            p.update(gate_proj=w("gate_proj", (d, ff)),
                     up_proj=w("up_proj", (d, ff)),
                     down_proj=w("down_proj", (ff, d)))
        else:
            p["gate"] = w("gate", (d, cfg["router_experts"]))
            p["experts"] = [
                {n: w("experts." + n, s, e) for n, s in
                 (("gate_proj", (d, ffe)), ("up_proj", (d, ffe)),
                  ("down_proj", (ffe, d)))}
                for e in range(cfg["n_routed_experts"])]
            p["shared_experts"] = {
                n: w("shared_experts." + n, s) for n, s in
                (("gate_proj", (d, sff)), ("up_proj", (d, sff)),
                 ("down_proj", (sff, d)))}
        layers.append(p)
    top = cfg["num_hidden_layers"] + 1
    return {"embed_tokens": _weight(seed, 0, "embed_tokens", (vocab, d)),
            "layers": layers,
            "lm_head": _weight(seed, top, "lm_head", (d, vocab))}


# ---------------------------------------------------------------- forward

def _rmsnorm(x, eps):
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, -1, keepdims=True) + eps)
    return y.astype(x.dtype)


def _mscale(factor, m):
    return 0.1 * m * math.log(factor) + 1.0 if factor > 1 else 1.0


def _yarn(cfg):
    """(inv_freq, cos/sin scale, softmax scale) from ``rope_scaling``."""
    rs, dim, theta = (cfg["rope_scaling"], cfg["qk_rope_head_dim"],
                      cfg["rope_theta"])
    pos = theta ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    f = rs["factor"]

    def corr(rot):
        return (dim * math.log(rs["original_max_position_embeddings"]
                               / (rot * 2 * math.pi)) / (2 * math.log(theta)))

    lo = max(math.floor(corr(rs["beta_fast"])), 0)
    hi = min(math.ceil(corr(rs["beta_slow"])), dim - 1)
    hi = hi + 0.001 if lo == hi else hi
    extra = 1.0 - np.clip((np.arange(dim // 2) - lo) / (hi - lo), 0, 1)
    inv = (1.0 / (f * pos)) * (1 - extra) + (1.0 / pos) * extra
    cs = _mscale(f, rs["mscale"]) / _mscale(f, rs["mscale_all_dim"])
    sm = (cfg["qk_nope_head_dim"] + dim) ** -0.5
    if rs["mscale_all_dim"]:
        sm *= _mscale(f, rs["mscale_all_dim"]) ** 2
    return inv.astype(np.float32), cs, sm


def _rope(x, inv, cs):
    """Rotate the halves of the last axis of x (b, s, [h,] dim)."""
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * inv
    if x.ndim == 4:
        ang = ang[:, None, :]
    cos, sin = jnp.cos(ang) * cs, jnp.sin(ang) * cs
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half].astype(jnp.float32), x[..., half:].astype(
        jnp.float32)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           -1).astype(x.dtype)


def _proj(x, w, ad, scale):
    """x @ W plus the adapter's scale * x A B (``ad`` None: no adapter)."""
    y = x @ w.astype(x.dtype)
    if ad is not None:
        y = y + scale * ((x @ ad["a"]) @ ad["b"])
    return y


def _swiglu(x, gate, up, down):
    return (jax.nn.silu(x @ gate) * (x @ up)) @ down


def _attention(cfg, p, ad, x, scale):
    b, s, _ = x.shape
    h, r, nope, rope, v = _dims(cfg)
    inv, cs, sm = _yarn(cfg)
    eps = cfg["rms_norm_eps"]
    q = _proj(x, p["q_proj"], ad["q"], scale).reshape(b, s, h, nope + rope)
    kv_a = _proj(x, p["kv_a_proj_with_mqa"], ad["kv_a"], scale)
    c_kv, k_pe = _rmsnorm(kv_a[..., :r], eps), kv_a[..., r:]
    kv = _proj(c_kv, p["kv_b_proj"], ad["kv_b"], scale).reshape(
        b, s, h, nope + v)
    k_nope, val = kv[..., :nope], kv[..., nope:]
    q_pe, k_pe = _rope(q[..., nope:], inv, cs), _rope(k_pe, inv, cs)
    scores = (jnp.einsum("bshk,bthk->bhst", q[..., :nope], k_nope)
              + jnp.einsum("bshk,btk->bhst", q_pe, k_pe)).astype(
                  jnp.float32) * sm
    causal = jnp.tril(jnp.ones((s, s), bool))
    probs = jax.nn.softmax(jnp.where(causal, scores, -1e30), -1).astype(
        x.dtype)
    o = jnp.einsum("bhst,bthk->bshk", probs, val).reshape(b, s, h * v)
    return _proj(o, p["o_proj"], ad["o"], scale)


def _moe(cfg, p, x):
    """Held experts' share plus the shared experts."""
    probs = jax.nn.softmax((x @ p["gate"].astype(x.dtype)).astype(
        jnp.float32), -1)
    top_w, top_i = jax.lax.top_k(probs, cfg["num_experts_per_tok"])
    y = _swiglu(x, *(p["shared_experts"][n].astype(x.dtype)
                     for n in ("gate_proj", "up_proj", "down_proj")))
    for e, ex in enumerate(p["experts"]):
        w = jnp.sum(jnp.where(top_i == e, top_w, 0.0), -1)   # (b, s)
        y = y + w[..., None].astype(x.dtype) * _swiglu(
            x, *(ex[n].astype(x.dtype)
                 for n in ("gate_proj", "up_proj", "down_proj")))
    return y


def logits(units, head, ids, cfg, seed):
    """(b, s, vocab) logits of the whole stack for token ids (b, s)."""
    kw = cfg["model_kwargs"]
    scale = kw["lora_alpha"] / kw["lora_rank"]
    dtype = jax.tree.leaves(units)[0].dtype
    wb = jax.tree.map(lambda a: a.astype(dtype), base(cfg, seed))
    eps = cfg["rms_norm_eps"]
    x = wb["embed_tokens"][ids]
    for l, (p, ad) in enumerate(zip(wb["layers"], units[1:])):
        x = x + _attention(cfg, p, ad, _rmsnorm(x, eps), scale)
        hn = _rmsnorm(x, eps)
        if l < cfg["first_k_dense_replace"]:
            x = x + _swiglu(hn, p["gate_proj"], p["up_proj"], p["down_proj"])
        else:
            x = x + _moe(cfg, p, hn)
    return _rmsnorm(x, eps) @ wb["lm_head"]


def loss(units, head, x, y, cfg):
    """Mean next-token cross-entropy over the slice.  The input ids are
    the sequence's first token and the labels shifted right (the data's
    own ids: the harness casts ``x`` to the precision under test, which
    would round ids in bfloat16).  ``x`` also keys the base: ids are
    non-negative, so the seed is ``base_seed`` exactly, but a traced one,
    and the compiler cannot fold the base into constants."""
    first = x[:, :1].astype(jnp.int32)
    ids = jnp.concatenate([first, y[:, :-1]], axis=1)
    seed = cfg["model_kwargs"]["base_seed"] + jnp.minimum(jnp.min(first), 0)
    lg = logits(units, head, ids, cfg, seed).astype(jnp.float32)
    gold = jnp.take_along_axis(lg, y[..., None], -1)[..., 0]
    return jnp.mean(jax.scipy.special.logsumexp(lg, -1) - gold)


# ---------------------------------------------------------------- data

def make_data(seed: int, n_vehicles: int, per_vehicle: int, n_test: int,
              cfg) -> dict:
    """Token sequences of ``seq_len`` + 1: BOS, then ids drawn Zipf
    (``zipf_exponent``) over a permutation of the vocabulary slice that is
    each vehicle's own (non-IID); the test sequences draw over one more
    permutation.  ``images`` are the inputs, ``labels`` the next ids."""
    vocab, s, a = cfg["vocab_size"], cfg["seq_len"], cfg["zipf_exponent"]
    rng = np.random.default_rng(seed)
    p = np.arange(1, vocab + 1, dtype=np.float64) ** -a
    p /= p.sum()

    def draw(n):
        ids = rng.permutation(vocab)[rng.choice(vocab, size=(n, s), p=p)]
        toks = np.concatenate([np.full((n, 1), BOS), ids], 1).astype(np.int32)
        return toks[:, :-1], toks[:, 1:]

    xs, ys = zip(*[draw(per_vehicle) for _ in range(n_vehicles)])
    xt, yt = draw(n_test)
    return {"images": np.stack(xs), "labels": np.stack(ys),
            "test_images": xt, "test_labels": yt}
