"""Transformer-as-UnitModel adapter: run the federation simulator (fedsim)
over any assigned architecture's reduced config — SFL/ASFL with the paper's
message flow on LM stacks, not just the paper's ResNet18.

Unit granularity: unit 0 = token embedding (always vehicle-side — the raw
tokens never leave the vehicle, the paper's privacy argument); units 1..P =
the stack's periods; the head (final norm + LM head) lives with the RSU.
Batches use the fedsim convention: ``images`` = token ids (b, s),
``labels`` = next-token ids (b, s).

:class:`SplitLoRAUnitModel` is the published-width path: split LoRA
fine-tuning of an MLA stack (DeepSeek-V2-Lite) over a frozen base that the
fused programs take as an argument, with held routed experts.
"""
from __future__ import annotations

import copy
import dataclasses
import zlib
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import MLA_DENSE, MLA_MOE, ArchConfig
from repro.core import cost
from repro.models import layers as L
from repro.models import mla as M
from repro.models import moe as E
from repro.models import transformer as T

Params = Dict[str, Any]


class TransformerUnitModel:
    # matmul-dominated: gradients scan fine on every backend, so the cohort
    # engine may fuse replicas and steps into nested lax.scans on CPU too
    scan_friendly = True

    def __init__(self, cfg: ArchConfig, seq_len: int = 8):
        assert cfg.frontend == "none", "fedsim LM adapter: text archs only"
        self.cfg = cfg
        self.name = cfg.name
        self.seq_len = seq_len          # tokens per sample (fleet data)
        self.vocab_size = cfg.vocab_size
        # (segment index, pattern) per period, in stack order
        self._period_seg: List[Tuple[int, Tuple[str, ...]]] = []
        for si, (pat, n) in enumerate(T.segments_of(cfg)):
            self._period_seg += [(si, pat)] * n
        self.n_units = 1 + len(self._period_seg)

    def init(self, key):
        params = T.init_params(key, self.cfg)
        units: List = [{"embed": params["embed"]}]
        seg_start = {}
        for pi, (si, _) in enumerate(self._period_seg):
            seg_start.setdefault(si, pi)
        for pi, (si, pat) in enumerate(self._period_seg):
            local = pi - seg_start[si]       # period index within its segment
            seg = params["segments"][si]
            units.append(jax.tree.map(lambda a: a[local:local + 1], seg))
        head = {"final_norm": params["final_norm"], "head": params["head"]}
        return units, head

    def apply_units(self, units, x, start: int):
        cfg = self.cfg
        i = start
        for u in units:
            if i == 0:
                positions = jnp.arange(x.shape[1], dtype=jnp.int32)
                x = T.embed_inputs(u, cfg, {"tokens": x}, positions)
                self._positions = positions
            else:
                si, pat = self._period_seg[i - 1]
                positions = jnp.arange(x.shape[1], dtype=jnp.int32)
                x, _, _ = T._scan_segment(u, cfg, pat, x, "train", positions,
                                          None, 0, remat=False)
            i += 1
        return x

    def head_loss(self, head, feats, labels):
        logits = T.unembed(head, self.cfg, feats)
        ce = L.cross_entropy(logits, labels, self.cfg.vocab_size)
        return ce, logits

    def head_predict(self, head, feats):
        return T.unembed(head, self.cfg, feats)

    def profile(self) -> cost.SplitProfile:
        """Per sample of ``seq_len`` tokens.  The embedding unit is a
        gather: its FLOPs count one per element it writes."""
        seq = self.seq_len
        prof = cost.arch_profile(self.cfg, seq=seq, param_bytes_per=4)
        # prepend the embedding unit
        emb_bytes = self.cfg.padded_vocab * self.cfg.d_model * 4
        prof.unit_fwd_flops.insert(0, float(seq * self.cfg.d_model))
        prof.unit_param_bytes.insert(0, emb_bytes)
        prof.smashed_bytes_per_sample.insert(
            0, prof.smashed_bytes_per_sample[0])
        return prof


# --------------------------------------------------------------------------
# split LoRA fine-tuning over a frozen, seeded base
# --------------------------------------------------------------------------

def base_key(seed: int, unit: int, name: str, expert: Optional[int] = None):
    """The key of one frozen base weight: ``PRNGKey(seed)`` folded with the
    unit index, then the CRC-32 of the weight's published name, then (for a
    routed expert's weights) the expert's index among all experts."""
    k = jax.random.fold_in(jax.random.fold_in(jax.random.PRNGKey(seed), unit),
                           zlib.crc32(name.encode()) & 0x7FFFFFFF)
    return k if expert is None else jax.random.fold_in(k, expert)


def base_weight(seed: int, unit: int, name: str, shape, expert=None):
    """A frozen (in, out) matrix: N(0, 1/in), or N(0, 1) for the
    embedding (``embed_tokens``)."""
    std = 1.0 if name == "embed_tokens" else shape[0] ** -0.5
    return std * jax.random.normal(base_key(seed, unit, name, expert), shape,
                                   jnp.float32)


class SplitLoRAUnitModel:
    """Split LoRA fine-tuning (SplitLoRA, Lin et al. 2024; LoRA,
    arXiv:2106.09685) of an MLA stack: the pretrained base is frozen and
    held once, and the vehicle and the RSU train rank-``lora_rank``
    adapters on the four published attention projections of their own
    units (:data:`repro.models.mla.LORA_TARGETS`).

    Units: 0 is the token embedding, 1..``n_layers`` the first layers of
    the published stack in order, and the head (final norm + LM head)
    stays with the RSU.  The trainable ``(units, head)`` are the adapters
    alone (the embedding unit and the head have none); the base is
    :meth:`frozen_params`, which every program takes as an argument and
    binds with :meth:`bind` inside its trace.  A routed-expert layer holds
    its first ``experts_held`` experts (:func:`repro.models.moe.moe_held`);
    the vocabulary is the first
    ``vocab_rows`` rows.  The base is drawn from ``base_seed`` by
    :func:`base_weight`, keyed by published weight names.

    The activation between units is the pair (hidden states, held expert
    rows so far), so the head loss's aux output counts the (token, held
    expert) pairs a batch computed.  Each sublayer's forward (attention,
    then FFN or experts), and the head's, runs again in the backward: only
    the sublayer inputs stay live across it.  At the published widths a
    fused window of four RSUs' slots needs this to fit a 16 GB chip."""

    scan_friendly = True
    aux_counter = "moe_rows_held"

    def __init__(self, cfg: ArchConfig, n_layers: int, experts_held: int,
                 vocab_rows: int, lora_rank: int, lora_alpha: float,
                 base_seed: int, seq_len: int):
        cfg = dataclasses.replace(cfg, n_layers=n_layers)
        kinds = cfg.layer_types
        if set(kinds) - {MLA_DENSE, MLA_MOE}:
            raise ValueError(f"split LoRA adapts MLA layers; {cfg.name} has "
                             f"{sorted(set(kinds))}")
        self.cfg, self.kinds, self.name = cfg, kinds, cfg.name
        self.n_units = 1 + n_layers
        self.experts_held = experts_held
        self.vocab_size = vocab_rows
        self.rank, self.scale = lora_rank, lora_alpha / lora_rank
        self.base_seed, self.seq_len = base_seed, seq_len
        self._frozen = None
        self._bound = None

    # ---- the frozen base ---------------------------------------------
    def _layer_base(self, s, unit: int, kind: str) -> Params:
        cfg, m = self.cfg, self.cfg.mla
        d, h = cfg.d_model, cfg.n_heads
        qk, r = m.qk_nope_dim + m.qk_rope_dim, m.kv_lora_rank
        w = lambda name, shape, e=None: base_weight(s, unit, name, shape, e)
        kv_a = w("kv_a_proj_with_mqa", (d, r + m.qk_rope_dim))
        kv_b = w("kv_b_proj", (r, h * (m.qk_nope_dim + m.v_head_dim))
                 ).reshape(r, h, -1)
        ones = lambda n: {"scale": jnp.ones((n,), jnp.float32)}
        p = {"norm1": ones(d), "norm2": ones(d),
             "mixer": {"wq": w("q_proj", (d, h * qk)).reshape(d, h, qk),
                       "w_dkv": kv_a[:, :r], "w_kr": kv_a[:, r:],
                       "kv_norm": ones(r),
                       "w_uk": kv_b[..., :m.qk_nope_dim],
                       "w_uv": kv_b[..., m.qk_nope_dim:],
                       "wo": w("o_proj", (h * m.v_head_dim, d)
                               ).reshape(h, m.v_head_dim, d)}}
        if kind == MLA_DENSE:
            ff = cfg.d_ff
            p["ffn"] = {"wi_gate": {"w": w("gate_proj", (d, ff))},
                        "wi_up": {"w": w("up_proj", (d, ff))},
                        "wo": {"w": w("down_proj", (ff, d))}}
            return p
        e = cfg.moe
        ff = e.d_ff_expert
        experts = lambda name, shape: jnp.stack(
            [w("experts." + name, shape, i) for i in range(self.experts_held)])
        sff = e.n_shared * ff
        p["ffn"] = {"router": w("gate", (d, e.n_experts)),
                    "wi_gate": experts("gate_proj", (d, ff)),
                    "wi_up": experts("up_proj", (d, ff)),
                    "wo": experts("down_proj", (ff, d)),
                    "shared": {
                        "wi_gate": {"w": w("shared_experts.gate_proj",
                                           (d, sff))},
                        "wi_up": {"w": w("shared_experts.up_proj", (d, sff))},
                        "wo": {"w": w("shared_experts.down_proj",
                                      (sff, d))}}}
        return p

    def make_frozen(self, seed) -> Params:
        """The frozen base drawn from ``seed`` (``base_seed``) as a pytree.
        Traceable: :meth:`frozen_params` builds it on the device in one
        program, from a traced seed, so the compiler cannot fold the base
        into constants."""
        d, v, top = self.cfg.d_model, self.vocab_size, self.n_units
        units = [{"embed": base_weight(seed, 0, "embed_tokens", (v, d))}]
        units += [self._layer_base(seed, u, k)
                  for u, k in enumerate(self.kinds, start=1)]
        head = {"final_norm": {"scale": jnp.ones((d,), jnp.float32)},
                "head": base_weight(seed, top, "lm_head", (d, v))}
        return {"units": units, "head": head}

    def frozen_params(self) -> Params:
        """The device-resident frozen base, built once."""
        if self._frozen is None:
            self._frozen = jax.jit(self.make_frozen)(
                jnp.int32(self.base_seed))
        return self._frozen

    def bind(self, frozen: Params) -> "SplitLoRAUnitModel":
        """A view of this model that reads the base from ``frozen`` (the
        traced argument of the program being built)."""
        m = copy.copy(self)
        m._bound = frozen
        return m

    def _base(self) -> Params:
        return self._bound if self._bound is not None \
            else self.frozen_params()

    # ---- adapters ----------------------------------------------------
    def init(self, key):
        """Adapters: A ~ N(0, 1/in) and B = 0, per target of every layer
        unit."""
        units: List = [{}]
        for u in range(1, self.n_units):
            ad = {}
            for t, (din, dout) in M.lora_shapes(self.cfg).items():
                ka, _ = jax.random.split(
                    jax.random.fold_in(jax.random.fold_in(key, u),
                                       M.LORA_TARGETS.index(t)))
                ad[t] = {"a": jax.random.normal(ka, (din, self.rank))
                         * din ** -0.5,
                         "b": jnp.zeros((self.rank, dout), jnp.float32)}
            units.append(ad)
        return units, {}

    # ---- forward -----------------------------------------------------
    def _attention(self, p: Params, ad: Params, x):
        positions = jnp.arange(x.shape[1], dtype=jnp.int32)
        h = L.rmsnorm(p["norm1"], x)
        with jax.named_scope("asfl.mla"):
            h = M.mla_train(p["mixer"], self.cfg, h, positions, ad,
                            self.scale)
        return x + h

    def _ffn(self, kind: str, p: Params, x):
        h = L.rmsnorm(p["norm2"], x)
        if kind == MLA_DENSE:
            with jax.named_scope("asfl.ffn"):
                h = L.mlp(p["ffn"], h, "swiglu")
            rows = jnp.zeros((), jnp.int32)
        else:
            h, rows = E.moe_held(p["ffn"], self.cfg, h, 0)
        return x + h, rows

    def apply_units(self, units, x, start: int):
        base = self._base()["units"]
        rows = jnp.zeros((), jnp.int32)
        if start > 0:
            x, rows = x
        attn = jax.checkpoint(self._attention)
        ffn = jax.checkpoint(self._ffn, static_argnums=(0,))
        for i, ad in enumerate(units, start=start):
            if i == 0:
                x = base[0]["embed"][x]
                continue
            x, r = ffn(self.kinds[i - 1], base[i], attn(base[i], ad, x))
            rows = rows + r
        return x, rows

    def _logits(self, hb: Params, x):
        with jax.named_scope("asfl.lm_head"):
            return L.rmsnorm(hb["final_norm"], x) @ hb["head"]

    def head_loss(self, head, feats, labels):
        x, rows = feats

        @jax.checkpoint
        def ce(hb, x):
            return L.cross_entropy(self._logits(hb, x), labels)

        return ce(self._base()["head"], x), rows

    def head_predict(self, head, feats):
        return self._logits(self._base()["head"], feats[0])

    # ---- cost --------------------------------------------------------
    def layer_flops(self, kind: str) -> float:
        """Forward FLOPs of one layer per sequence: MLA projections, causal
        attention, the LoRA updates, and the dense FFN or the router, the
        shared experts and the routed experts at the expected share of
        top-k rows that land on the held experts."""
        cfg, s = self.cfg, self.seq_len
        m, d, h = cfg.mla, cfg.d_model, cfg.n_heads
        qk = m.qk_nope_dim + m.qk_rope_dim
        f = sum(2 * i * o for i, o in M.lora_shapes(cfg).values()) * s
        f += sum(2 * self.rank * (i + o)
                 for i, o in M.lora_shapes(cfg).values()) * s
        pairs = s * (s + 1) // 2
        f += 2 * pairs * h * (qk + m.v_head_dim)
        if kind == MLA_DENSE:
            return float(f + 2 * 3 * d * cfg.d_ff * s)
        e = cfg.moe
        ffe = e.d_ff_expert
        f += 2 * d * e.n_experts * s + 2 * 3 * d * e.n_shared * ffe * s
        f += 2 * 3 * d * ffe * s * e.top_k * self.experts_held / e.n_experts
        return float(f)

    def profile(self) -> cost.SplitProfile:
        """Per sequence; parameter bytes are the adapters' (what a vehicle
        uploads and downloads), the base being held on both sides."""
        d, s = self.cfg.d_model, self.seq_len
        ad = sum(self.rank * (i + o) * 4
                 for i, o in M.lora_shapes(self.cfg).values())
        n = self.n_units
        return cost.SplitProfile(
            name=self.name,
            unit_fwd_flops=[float(s * d)] + [self.layer_flops(k)
                                             for k in self.kinds],
            unit_param_bytes=[0] + [ad] * (n - 1),
            smashed_bytes_per_sample=[float(s * d * 4)] * n,
            head_flops=float(2 * d * self.vocab_size * s),
            head_param_bytes=0,
            smashed_trailing_dim=[d] * n)
