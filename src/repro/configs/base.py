"""Architecture + shape configuration for the ASFL framework.

Every assigned architecture is described by one :class:`ArchConfig`. The model
substrate (``repro.models.transformer``) consumes this config to assemble the
layer stack; ``repro.core.split`` consumes it to enumerate valid cut points.
"""
from __future__ import annotations

import dataclasses
import os
from typing import Optional, Sequence, Tuple

# Layer-type ids understood by models/transformer.py
ATTN = "attn"            # global attention + dense MLP
ATTN_LOCAL = "attn_local"  # sliding-window attention + dense MLP
ATTN_MOE = "attn_moe"    # global attention + MoE FFN
MLA_DENSE = "mla_dense"  # multi-head latent attention + dense MLP
MLA_MOE = "mla_moe"      # multi-head latent attention + MoE FFN
SSM = "ssm"              # Mamba2 SSD block (no separate FFN)
RGLRU = "rglru"          # RG-LRU recurrent block + dense MLP

VOCAB_PAD = 2048  # Megatron-style: pad embedding tables to a multiple of this


def pad_vocab(v: int) -> int:
    return ((v + VOCAB_PAD - 1) // VOCAB_PAD) * VOCAB_PAD


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    n_experts: int            # routed experts
    top_k: int
    n_shared: int = 0         # shared (always-on) experts
    d_ff_expert: int = 0      # expert hidden dim (0 -> use arch d_ff)
    capacity_factor: float = 1.25
    aux_loss_weight: float = 0.01
    # renormalise the top-k gates to sum to one (DeepSeek-V2:
    # ``norm_topk_prob: false`` keeps the raw softmax probabilities)
    norm_topk_prob: bool = True


@dataclasses.dataclass(frozen=True)
class MLAConfig:
    kv_lora_rank: int = 512
    qk_nope_dim: int = 128
    qk_rope_dim: int = 64
    v_head_dim: int = 128


@dataclasses.dataclass(frozen=True)
class YarnConfig:
    """YaRN rotary scaling (arXiv:2309.00071) as DeepSeek-V2 publishes it
    (``rope_scaling`` of its config.json)."""
    factor: float
    original_max_position_embeddings: int
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    mscale: float = 1.0
    mscale_all_dim: float = 0.0


@dataclasses.dataclass(frozen=True)
class SSMConfig:
    d_state: int = 128
    head_dim: int = 64
    expand: int = 2
    d_conv: int = 4
    n_groups: int = 1
    chunk: int = 256
    # perf knob (§Perf): split the fused in_proj into per-stream projections
    # (z / xBC / dt) so each output shards cleanly on the model axis instead
    # of crossing shard boundaries at the split offsets.
    fused_proj: bool = True


@dataclasses.dataclass(frozen=True)
class RGLRUConfig:
    d_rnn: int = 0          # 0 -> d_model
    d_conv: int = 4
    c_exponent: float = 8.0


@dataclasses.dataclass(frozen=True)
class ArchConfig:
    name: str
    family: str               # dense | moe | ssm | hybrid | vlm | audio
    source: str               # citation (paper / model card)
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab_size: int
    head_dim: int = 0         # 0 -> d_model // n_heads
    # Layer pattern: tuple of layer-type ids forming one repeating period.
    # The stack = lead + pattern * n_periods + tail.  n_layers must equal
    # len(lead) + len(pattern) * n_periods + len(tail).
    pattern: Tuple[str, ...] = (ATTN,)
    tail: Tuple[str, ...] = ()
    lead: Tuple[str, ...] = ()   # layers before the first period
    # Attention details
    qk_norm: bool = False
    window: int = 0           # sliding window size for ATTN_LOCAL layers
    rope_theta: float = 10000.0
    rope_scaling: Optional[YarnConfig] = None
    pos: str = "rope"         # rope | sinusoidal
    mlp_variant: str = "swiglu"  # swiglu | geglu | gelu
    logit_softcap: float = 0.0
    # Sub-configs
    moe: Optional[MoEConfig] = None
    mla: Optional[MLAConfig] = None
    ssm: Optional[SSMConfig] = None
    rglru: Optional[RGLRUConfig] = None
    # Modality frontend stub ("none" | "vision" | "audio")
    frontend: str = "none"
    n_patches: int = 256      # vision: patch embeddings prepended to text
    n_codebooks: int = 4      # audio: EnCodec codebooks summed at the input
    # SFL defaults
    default_cut: int = 2      # default cut layer (in *period* units; see split.py)
    # Long-context eligibility: sub-quadratic (SSM/hybrid/sliding-window) only
    subquadratic: bool = False
    # dtypes
    param_dtype: str = "float32"

    # ---- derived ----
    @property
    def head_dim_(self) -> int:
        return self.head_dim or self.d_model // self.n_heads

    @property
    def padded_vocab(self) -> int:
        return pad_vocab(self.vocab_size)

    @property
    def layer_types(self) -> Tuple[str, ...]:
        n_periods = self.n_periods
        return (tuple(self.lead) + tuple(self.pattern) * n_periods
                + tuple(self.tail))

    @property
    def n_periods(self) -> int:
        body = self.n_layers - len(self.tail) - len(self.lead)
        assert body % len(self.pattern) == 0, (
            f"{self.name}: {self.n_layers} layers, lead {self.lead}, pattern "
            f"{self.pattern}, tail {self.tail} do not tile")
        return body // len(self.pattern)

    def param_count(self) -> int:
        """Analytic parameter count (for 6ND model-FLOPs in the roofline)."""
        from repro.models.transformer import count_params  # lazy, avoids cycle
        return count_params(self)

    def active_param_count(self) -> int:
        from repro.models.transformer import count_params
        return count_params(self, active_only=True)

    def reduced(self) -> "ArchConfig":
        """Smoke-test variant: <=2 periods, d_model<=256, <=4 experts."""
        d = min(self.d_model, 256)
        n_heads = max(1, min(self.n_heads, 4))
        n_kv = max(1, min(self.n_kv_heads, n_heads))
        while n_heads % n_kv:
            n_kv -= 1
        hd = 32
        moe = None
        if self.moe is not None:
            moe = dataclasses.replace(
                self.moe, n_experts=min(self.moe.n_experts, 4),
                top_k=min(self.moe.top_k, 2),
                n_shared=min(self.moe.n_shared, 1),
                d_ff_expert=min(self.moe.d_ff_expert or 128, 128))
        mla = None
        if self.mla is not None:
            mla = MLAConfig(kv_lora_rank=64, qk_nope_dim=32, qk_rope_dim=16,
                            v_head_dim=32)
        ssm = None
        if self.ssm is not None:
            ssm = dataclasses.replace(self.ssm, d_state=16, head_dim=16,
                                      chunk=32)
        rglru = None
        if self.rglru is not None:
            rglru = dataclasses.replace(self.rglru, d_rnn=0)
        n_tail = len(self.tail) + len(self.lead)
        # keep 1-2 periods so every layer type in the pattern is exercised
        n_layers = len(self.pattern) + n_tail
        return dataclasses.replace(
            self, name=self.name + "-smoke", n_layers=n_layers,
            d_model=d, n_heads=n_heads, n_kv_heads=n_kv, head_dim=hd,
            d_ff=min(self.d_ff, 512) or 0, vocab_size=min(self.vocab_size, 512),
            window=min(self.window, 16) if self.window else 0,
            moe=moe, mla=mla, ssm=ssm, rglru=rglru,
            n_patches=min(self.n_patches, 8), default_cut=1)


@dataclasses.dataclass(frozen=True)
class ShapeConfig:
    name: str
    seq_len: int
    global_batch: int
    kind: str  # train | prefill | decode


INPUT_SHAPES = {
    "train_4k": ShapeConfig("train_4k", 4096, 256, "train"),
    "prefill_32k": ShapeConfig("prefill_32k", 32768, 32, "prefill"),
    "decode_32k": ShapeConfig("decode_32k", 32768, 128, "decode"),
    "long_500k": ShapeConfig("long_500k", 524288, 1, "decode"),
}


# --------------------------------------------------------------------------
# runtime / XLA configuration
# --------------------------------------------------------------------------

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
# <checkout>/.jax_cache (listed in .gitignore): a fixed path, so the cache
# written by one process is found again by the next
CHECKOUT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), ".jax_cache")


def compilation_cache_dir(requested: Optional[str] = None) -> str:
    """Where the persistent compilation cache lives: ``$JAX_COMPILATION_
    CACHE_DIR`` whenever it is set (JAX reads it itself, and nothing here
    overrides it), else ``requested`` (``RuntimeConfig.
    compilation_cache_dir`` / a ``--compilation-cache`` flag), else the
    fixed :data:`CHECKOUT_CACHE_DIR`."""
    return os.environ.get(CACHE_ENV) or requested or CHECKOUT_CACHE_DIR


def enable_compilation_cache(requested: Optional[str] = None,
                             min_compile_time_secs: float = 0.0) -> str:
    """Turn on JAX's persistent compilation cache at
    :func:`compilation_cache_dir` so repeat runs of the same programs
    (fedsim round programs, fused super-steps, kernels) deserialize
    compiled binaries instead of re-invoking XLA.

    Called by the engines when ``compilation_cache_dir`` is configured, and
    by ``chip_smoke.py``, the examples and the benchmarks.
    ``min_compile_time_secs = 0`` caches everything — the federation
    engines compile few, large programs, exactly the shape the cache is
    built for.  Returns the directory (created if missing) so callers can
    log it.

    JAX's cache configuration is **process-global**: this latches the cache
    on for every subsequent compile in the process, and calling it again
    with a different directory repoints everything (last call wins)."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    cache_dir = compilation_cache_dir(requested)
    os.makedirs(cache_dir, exist_ok=True)
    if not os.environ.get(CACHE_ENV):
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    jax.config.update("jax_persistent_cache_min_compile_time_secs",
                      float(min_compile_time_secs))
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    # the cache singleton latches its directory on first use: reset so a
    # dir configured mid-process (engine __init__, bench flags) takes
    # effect for everything compiled afterwards
    compilation_cache.reset_cache()
    return cache_dir


def cache_dir_is_warm(cache_dir) -> bool:
    """True if ``cache_dir`` already holds persistent-cache entries.  Call
    BEFORE running anything that compiles — the run itself populates the
    directory, so probing afterwards always reads warm (the benchmarks'
    ``compile_cache_hit`` key uses this at startup)."""
    return bool(cache_dir and os.path.isdir(cache_dir)
                and os.listdir(cache_dir))
