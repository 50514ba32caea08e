"""Paper-faithful federation simulator: CL / FL / SL / SFL(fixed cut) / ASFL.

This engine reproduces the paper's Fig. 5 case study: ResNet18-class models,
4 vehicles, non-IID (6-of-10 labels, power-law sizes), lr 1e-4, batch 16,
local epochs 5.  The SFL message flow is realised explicitly — vehicle-side
forward, smashed-data upload, RSU-side forward/backward, cut-layer-gradient
download, vehicle-side backward — via jax.vjp, NOT one composite jax.grad,
so the implementation is structurally the paper's Fig. 3 workflow (their
mathematical equality is asserted in tests/test_sfl_math.py).

Scaling design (DESIGN.md §6): a federation round is compiled as ONE jitted
program by the :class:`CohortEngine`.  Clients are bucketed by cut layer and
stacked along a leading replica axis; local steps are driven by `lax.scan`
over pre-staged batch-index tensors (batches are gathered from the on-device
:class:`StackedClients` tensors inside the scan); losses are accumulated
on-device and fetched once per round.  Within a bucket the vehicle-side
compute runs either `jax.vmap`-vectorized across replicas (accelerators) or
as a fused `lax.scan` (CPU, where XLA lowers per-replica-filter convolutions
to slow grouped convs) — both schedules compute the same math.  The seed's
4-client Python loop (one jit dispatch + one `float(loss)` host sync per
client per batch) is gone; the 4-vehicle paper case study is just a small
configuration of the same engine.

The engine is generic over a :class:`UnitModel` (any stack of units with a
head); ResNet18 (the paper's model) and the small transformer wrapper both
implement it.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import (Any, Callable, Dict, List, Optional, Protocol, Sequence,
                    Tuple, Union)

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import PartitionSpec as P

from repro.configs.base import enable_compilation_cache
from repro.core import adaptive, aggregation, channel, compression, cost
from repro.core import faults, fleet_sharding, streaming
from repro.core.fleet_sharding import VEH_AXIS as MESH_AXIS, FLEET_AXES, FleetMesh
from repro.core.superstep import (SERVER_SCHEDULES, SUPERSTEP_LAYOUTS,
                                  SuperStepPrograms)
from repro.data.pipeline import (ClientDataset, DoubleBuffer, StackedClients,
                                 epoch_batch_indices, sample_batch_indices,
                                 stack_clients)
from repro import optim

Params = Any


class UnitModel(Protocol):
    name: str
    n_units: int

    def init(self, key) -> Tuple[List[Params], Params]: ...
    def apply_units(self, units: List[Params], x, start: int): ...
    def head_loss(self, head: Params, feats, labels): ...
    def head_predict(self, head: Params, feats): ...
    def profile(self) -> cost.SplitProfile: ...


class ResNetModel:
    """The paper's ResNet18 over 32x32x3 inputs."""
    name = "resnet18"
    # conv gradients inside lax.scan bodies hit XLA:CPU's slow generic path;
    # the cohort engine unrolls replicas for this model on CPU (DESIGN.md §6)
    scan_friendly = False

    def __init__(self, n_classes: int = 10):
        from repro.models import resnet as R
        self.R = R
        self.n_units = R.N_UNITS
        self.n_classes = n_classes

    def init(self, key):
        p = self.R.init_resnet18(key, self.n_classes)
        return list(p["units"]), p["head"]

    def apply_units(self, units, x, start):
        for j, u in enumerate(units):
            x = self.R._apply_unit(u, x, start + j)
        return x

    def head_loss(self, head, feats, labels):
        logits = jnp.mean(feats, axis=(1, 2)) @ head["w"] + head["b"]
        logz = jax.scipy.special.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, labels[:, None], axis=-1)[:, 0]
        return jnp.mean(logz - gold), logits

    def head_predict(self, head, feats):
        return jnp.mean(feats, axis=(1, 2)) @ head["w"] + head["b"]

    def profile(self):
        return cost.resnet_profile()


# the valid values of every categorical SimConfig field — construction
# rejects anything else (with the allowed values listed) instead of failing
# deep inside engine dispatch.  The api layer (repro.api) re-validates the
# *combinations* per engine at spec-build time.
SCHEMES = ("cl", "fl", "sl", "sfl", "asfl")
ADAPTIVE_STRATEGIES = ("paper", "paper-literal", "latency", "energy",
                       "memory", "residence")
SLOT_CAPACITIES = ("pow2", "tight8")
COHORT_MODES = ("auto", "vmap", "scan", "unroll")
OPTIMIZERS = ("adam", "sgd", "momentum")
WIRE_SCHEMES = compression.WIRE_SCHEMES  # none | int8 | topk_int8

# which adaptive strategies each engine can execute (the fused scenario
# engine runs cut selection on-device; only the traced strategies are wired)
FEDERATION_STRATEGIES = ("paper", "paper-literal", "latency", "energy",
                         "memory")
SCENARIO_STRATEGIES = ("paper", "paper-literal", "residence")


@dataclasses.dataclass
class SimConfig:
    scheme: str = "asfl"          # cl | fl | sl | sfl | asfl
    cut: int = 4                  # fixed cut for sl/sfl
    n_clients: int = 4
    batch_size: int = 16          # paper: 16
    local_epochs: int = 5         # paper: 5
    local_steps: Optional[int] = None  # overrides epochs if set
    lr: float = 1e-4              # paper: 1e-4
    rounds: int = 10
    seed: int = 0
    optimizer: str = "adam"
    # paper | paper-literal | latency | energy | memory
    adaptive_strategy: str = "paper"
    compress_smashed: bool = False
    # wire scheme at the cut boundary (DESIGN.md §11): "none" ships dense
    # fp32 smashed tensors; "int8" per-group quantisation (both directions);
    # "topk_int8" top-k sparsify + int8 pack with per-vehicle error-feedback
    # residuals in the superstep engine (stateless in the cohort engine).
    # compress_smashed=True is the legacy spelling of wire="int8".
    wire: str = "none"
    # keep-fraction per quantisation group for wire="topk_int8"
    wire_k: float = compression.WIRE_K
    server_flops: float = 2e12    # RSU (GPU-class)
    round_interval_s: float = 5.0
    # mobility: vehicles outside RSU coverage at round start skip the round
    # (the paper's §II-C training-interruption challenge).  Legacy spelling
    # of fault_coverage=True — see fault_config()
    mobility_dropout: bool = False
    # fault plane (core/faults.py, DESIGN.md §13): seeded stochastic failure
    # processes.  All-zero defaults are gated out at Python level, so the
    # compiled programs are byte-identical to a no-fault build
    fault_coverage: bool = False      # deterministic §II-C in-range test
    fault_dropout: float = 0.0        # P[vehicle drops mid-round]
    fault_upload_loss: float = 0.0    # P[update lost after full local work]
    fault_straggler: float = 0.0      # >0: deadline factor x residence
    fault_rsu_outage: float = 0.0     # P[RSU misses a round] (scenario only)
    fault_staleness_discount: float = 0.5  # weight for banked late updates
    fault_seed: int = 0
    # streaming plane (core/streaming.py, DESIGN.md §14): seeded presence
    # churn (any schedule) + the buffered-asynchronous streaming schedule's
    # merge policy.  All-defaults are gated out at Python level, so the
    # compiled programs are byte-identical to a no-streaming build
    stream_buffer_size: int = 4       # B pending deltas per RSU per merge
    stream_churn_rate: float = 0.0    # P[vehicle toggles presence per round]
    stream_kernel: str = "constant"   # staleness discount: constant | poly
    stream_alpha: float = 0.5         # poly kernel exponent 1/(1+s)**alpha
    stream_seed: int = 0
    # intra-bucket schedule: "vmap" vectorizes client replicas across the
    # stacked axis (accelerators), "scan" fuses them sequentially (CPU);
    # "auto" picks by platform.  Same math either way (DESIGN.md §6).
    cohort_parallel: str = "auto"
    # evaluate the global model every k rounds (0 = never; test_acc is NaN
    # for skipped rounds).  Evaluation itself is jitted.
    eval_every: int = 1
    # ScenarioEngine server schedule (DESIGN.md §8): "sequential" keeps the
    # source paper's §III-B semantics (the RSU updates its shared server
    # model on every client batch, in cohort order); "parallel" is the
    # companion ASFL paper's parallel server-side execution
    # (arXiv:2405.18707) — one |D_n|-weighted mean-gradient server step per
    # local step, with every matmul batched over the (RSU, vehicle) axes;
    # "streaming" rides the parallel machinery but commits each round's
    # cohort delta into a capacity-B StreamBuffer and advances the edge
    # model only when the buffer fires, via staleness-weighted survivor
    # FedAvg (core/streaming.py, DESIGN.md §14)
    server_schedule: str = "sequential"
    # per-RSU slot-capacity rounding for the fused programs: "pow2" (the
    # bucket-signature scheme — most stable compile cache) or "tight8"
    # (next multiple of 8 — up to ~40% fewer padded slots at fleet scale,
    # a few more signatures under heavy cohort churn)
    slot_capacity: str = "pow2"
    # super-step execution layout (DESIGN.md §12): "ragged" sizes per-slot
    # client planes / optimizer moments / EF wire residuals to the
    # strategy's static max-cut prefix and (parallel schedule) compacts the
    # slot axis to occupied slots with segment-sum per-RSU aggregation;
    # "dense" keeps full-plane masked replicas over per-RSU padded tables.
    # Bit-for-bit identical for sgd on both schedules (tests/test_ragged.py)
    superstep_layout: str = "ragged"
    # rounds fused per ScenarioEngine super-step (DESIGN.md §8): K rounds of
    # mobility, scheduling, training, handover, and edge/cloud aggregation
    # execute as ONE compiled lax.scan with donated carries; 1 = one
    # dispatch per round (same program, scan length 1)
    superstep: int = 1
    # persistent XLA compilation cache directory (None = leave the process
    # config untouched): second runs of the same programs skip compilation
    # entirely.  NOTE: JAX's cache config is PROCESS-GLOBAL — setting it on
    # any engine latches it on for every compile in the process, and the
    # last configured directory wins (configs.base.enable_compilation_cache)
    compilation_cache_dir: Optional[str] = None
    # device mesh over the fleet (core/fleet_sharding.py, DESIGN.md §10,
    # §15): mesh_devices > 1 runs the compiled round / super-step programs
    # under shard_map across that many devices; 1 (the default) is the
    # unsharded single-device path, bit-identical to the pre-mesh engines;
    # "auto" picks 1 vs every addressable device from an occupied-slots-
    # per-device floor, so small fleets never pay the sharding tax
    mesh_devices: Union[int, str] = 1
    # which fleet dimension the mesh partitions: "vehicle" (cohort-engine
    # slot axis), "rsu" (super-step RSU axis), "grid" (2-D rsu x vehicle —
    # the super-step shards BOTH its axes), or "auto" (the engine's
    # natural axis)
    fleet_axis: str = "auto"
    # 2-D mesh factorization (DESIGN.md §15): "auto" derives (rsu, vehicle)
    # device counts from fleet_axis ("vehicle" -> (1, n), "rsu" -> (n, 1),
    # "grid" -> the balanced power-of-2 split), or an explicit "RxV"
    # string whose product must equal mesh_devices
    mesh_shape: str = "auto"
    # slot-capacity paging (DESIGN.md §15): > 0 caps the per-device
    # CONCURRENT slot window of the ragged parallel/streaming super-step —
    # cohorts beyond it page through the compacted axis in fixed windows
    # on the donated carry (more planned slots never raises, and paging
    # churn is data, not a program signature).  0 = unpaged
    page_slots: int = 0
    # presence-churn source (DESIGN.md §15): "markov" is the seeded toggle
    # chain (stream_churn_rate); "mobility" derives departures from the
    # scenario's coverage state (serving_rsu == -1) — a vehicle leaving
    # coverage departs the stream, a vehicle entering it re-registers
    # (synchronous schedules admit it next round; streaming immediately)
    stream_churn_source: str = "markov"

    def __post_init__(self):
        for field, allowed in (("scheme", SCHEMES),
                               ("adaptive_strategy", ADAPTIVE_STRATEGIES),
                               ("server_schedule", SERVER_SCHEDULES),
                               ("slot_capacity", SLOT_CAPACITIES),
                               ("superstep_layout", SUPERSTEP_LAYOUTS),
                               ("cohort_parallel", COHORT_MODES),
                               ("fleet_axis", FLEET_AXES),
                               ("optimizer", OPTIMIZERS),
                               ("wire", WIRE_SCHEMES)):
            value = getattr(self, field)
            if value not in allowed:
                raise ValueError(
                    f"SimConfig.{field}={value!r} is not valid; allowed "
                    f"values: {' | '.join(allowed)}")
        for field, floor in (("n_clients", 1), ("batch_size", 1),
                             ("local_epochs", 1), ("rounds", 1),
                             ("superstep", 1), ("cut", 1), ("eval_every", 0),
                             ("page_slots", 0)):
            value = getattr(self, field)
            if not isinstance(value, int) or value < floor:
                raise ValueError(
                    f"SimConfig.{field}={value!r} is not valid; expected an "
                    f"int >= {floor}")
        md = self.mesh_devices
        if not (md == "auto" or (isinstance(md, int) and md >= 1)):
            raise ValueError(
                f"SimConfig.mesh_devices={md!r} is not valid; expected an "
                f"int >= 1 or 'auto'")
        if self.stream_churn_source not in streaming.CHURN_SOURCES:
            raise ValueError(
                f"SimConfig.stream_churn_source="
                f"{self.stream_churn_source!r} is not valid; allowed "
                f"values: {' | '.join(streaming.CHURN_SOURCES)}")
        if self.mesh_shape != "auto":
            fleet_sharding.parse_shape_spec(self.mesh_shape)
        if self.local_steps is not None and self.local_steps < 1:
            raise ValueError(
                f"SimConfig.local_steps={self.local_steps!r} is not valid; "
                f"expected None (use local_epochs) or an int >= 1")
        if not 0.0 < self.wire_k <= 1.0:
            raise ValueError(
                f"SimConfig.wire_k={self.wire_k!r} is not valid; expected "
                f"a keep-fraction in (0, 1]")
        if self.compress_smashed and self.wire not in ("none", "int8"):
            raise ValueError(
                f"SimConfig.compress_smashed=True conflicts with "
                f"wire={self.wire!r}: compress_smashed is the legacy "
                f"spelling of wire='int8' — set wire alone")
        if self.mobility_dropout and self.fault_coverage:
            raise ValueError(
                "SimConfig.mobility_dropout=True conflicts with "
                "fault_coverage=True: mobility_dropout is the legacy "
                "spelling of fault_coverage — set fault_coverage alone")
        self.fault_config()  # rate/discount validation (FaultConfig raises)
        self.stream_config()  # kernel/rate validation (StreamConfig raises)

    def wire_scheme(self) -> str:
        """The effective cut-boundary wire: compress_smashed=True is kept as
        a working alias for wire="int8" (pre-wire configs still run, with
        identical numerics and now-honest byte accounting)."""
        if self.wire == "none" and self.compress_smashed:
            return "int8"
        return self.wire

    def fault_config(self) -> faults.FaultConfig:
        """The effective fault plane (core/faults.py, DESIGN.md §13).
        ``mobility_dropout=True`` is kept as a working alias for
        ``fault_coverage=True`` — the same shim pattern as
        ``compress_smashed`` → ``wire="int8"``."""
        return faults.FaultConfig(
            dropout_rate=self.fault_dropout,
            upload_loss_rate=self.fault_upload_loss,
            straggler_factor=self.fault_straggler,
            rsu_outage_rate=self.fault_rsu_outage,
            staleness_discount=self.fault_staleness_discount,
            coverage=self.mobility_dropout or self.fault_coverage,
            seed=self.fault_seed)

    def stream_config(self) -> streaming.StreamConfig:
        """The effective streaming plane (core/streaming.py, DESIGN.md
        §14)."""
        return streaming.StreamConfig(
            buffer_size=self.stream_buffer_size,
            churn_rate=self.stream_churn_rate,
            kernel=self.stream_kernel,
            alpha=self.stream_alpha,
            seed=self.stream_seed,
            churn_source=self.stream_churn_source)


@dataclasses.dataclass
class RoundMetrics:
    round: int
    loss: float
    test_acc: float
    comm_bytes: float
    sim_time_s: float
    energy_j: float
    cuts: List[int]
    # fault-plane telemetry (DESIGN.md §13); the defaults are the no-fault
    # values, so pre-fault code paths need no changes
    n_dropout: int = 0
    n_upload_lost: int = 0
    survivor_frac: float = 1.0
    lost_update_bytes: float = 0.0


def _make_opt(cfg: SimConfig):
    return optim.from_name(cfg.optimizer, cfg.lr)


def _wire_transform(cfg: SimConfig, x):
    """The cohort-engine wire site: what a smashed activation (or cut-layer
    gradient) looks like after one trip over the configured wire.  The
    cohort engine is stateless per batch, so topk_int8 runs WITHOUT error
    feedback here; the superstep engine carries the per-vehicle residual
    plane (core/superstep.py).  wire="none" is the identity — no ops are
    added, so pre-wire jaxprs are unchanged."""
    wire = cfg.wire_scheme()
    if wire == "int8":
        return compression.fake_quant(x)
    if wire == "topk_int8":
        return compression.wire_fake(x, cfg.wire_k)
    return x


# --------------------------------------------------------------------------
# jitted single-client batch step (kept as the oracle: tests/test_sfl_math.py
# asserts it computes composite-loss gradients; the parity suite and the
# benchmark replay the seed per-client loop with it against the cohort engine)
# --------------------------------------------------------------------------

def make_sfl_batch_step(model: UnitModel, cfg: SimConfig, cut: int):
    """One SFL batch for one client at a given cut (static).  Returns the
    explicit message-flow step (client fwd -> server fwd/bwd -> client bwd)."""
    opt = _make_opt(cfg)

    @jax.jit
    def step(client_units, server_units, head, c_opt, s_opt, batch):
        x, y = batch["images"], batch["labels"]

        def client_fwd(cu):
            return model.apply_units(cu, x, 0)

        smashed, client_vjp = jax.vjp(client_fwd, client_units)
        sm_in = _wire_transform(cfg, smashed)

        def server_loss(sv, sm):
            feats = model.apply_units(sv["units"], sm, cut)
            loss, logits = model.head_loss(sv["head"], feats, y)
            return loss, logits

        sv_tree = {"units": server_units, "head": head}
        (loss, logits), grads = jax.value_and_grad(
            server_loss, argnums=(0, 1), has_aux=True)(sv_tree, sm_in)
        g_server, g_smashed = grads
        g_smashed = _wire_transform(cfg, g_smashed)  # downlink wire, too
        (g_client,) = client_vjp(g_smashed)

        upd_c, c_opt = opt.update(g_client, c_opt, client_units)
        client_units = optim.apply_updates(client_units, upd_c)
        upd_s, s_opt = opt.update(g_server, s_opt, sv_tree)
        sv_tree = optim.apply_updates(sv_tree, upd_s)
        acc = jnp.mean((jnp.argmax(logits, -1) == y).astype(jnp.float32))
        return client_units, sv_tree["units"], sv_tree["head"], c_opt, s_opt, loss, acc

    return step


# --------------------------------------------------------------------------
# evaluation (jitted; one compiled program per slice shape, cached per model)
# --------------------------------------------------------------------------

def make_eval_fn(model: UnitModel):
    # cached on the model instance (a WeakKeyDictionary would never evict:
    # the jitted fn closes over `model`, pinning its own key; the attribute
    # cycle model -> fn -> model is ordinary gc-collectable garbage)
    fn = getattr(model, "_eval_fn", None)
    if fn is None:
        # a frozen base (split LoRA) is the program's last argument, bound
        # to the model inside the trace; other models take none
        @jax.jit
        def fn(units, head, x, y, *frozen):
            with jax.named_scope("asfl.eval"):
                m = model.bind(*frozen) if frozen else model
                feats = m.apply_units(units, x, 0)
                logits = m.head_predict(head, feats)
                return jnp.sum(jnp.argmax(logits, -1) == y)
        model._eval_fn = fn
    return fn


EVAL_BATCH = 256


def evaluate(model: UnitModel, units, head, test: Dict[str, jnp.ndarray],
             batch: int = EVAL_BATCH) -> float:
    fn = make_eval_fn(model)
    frozen = ((model.frozen_params(),) if hasattr(model, "frozen_params")
              else ())
    n = test["labels"].shape[0]
    correct = []
    total = 0
    for i in range(0, n, batch):
        x = test["images"][i:i + batch]
        y = test["labels"][i:i + batch]
        correct.append(fn(units, head, x, y, *frozen))
        total += int(np.prod(y.shape))
    # host ints: an eager sum of device scalars compiles an add per
    # placement
    return sum(int(c) for c in correct) / max(total, 1)


# --------------------------------------------------------------------------
# cohort engine internals
# --------------------------------------------------------------------------

def _pow2(n: int) -> int:
    return 1 << max(n - 1, 0).bit_length()


def _select(mask, new, old):
    """tree_map(where): pick `new` where mask else `old`.  mask broadcasts
    from the left (a (n,) mask over stacked (n, ...) leaves; a scalar mask
    over whole trees)."""
    mask = jnp.asarray(mask)

    def f(a, b):
        m = mask.reshape(mask.shape + (1,) * (a.ndim - mask.ndim))
        return jnp.where(m, a, b)

    return jax.tree.map(f, new, old)


def _gather_batch(data, idx):
    """data (n, L, ...), idx (n, B) -> (n, B, ...): per-replica batch gather
    inside the scanned round (no host staging per batch)."""
    return jax.vmap(lambda d, i: d[i])(data, idx)


def _suffix_state(state, cut):
    """Slice the RSU optimizer state (whose leaves mirror the full
    {"units": [...], "head": ...} tree) down to the units after `cut`.
    This is static pytree surgery at trace time — the stacked-state
    replacement for the seed's per-batch Python slice_opt/merge_opt."""
    out = {}
    for k, v in state.items():
        if isinstance(v, dict) and "units" in v:
            out[k] = {"units": list(v["units"][cut:]), "head": v["head"]}
        else:
            out[k] = v
    return out


def _merge_state(full, suffix, cut):
    out = {}
    for k, v in full.items():
        if isinstance(v, dict) and "units" in v:
            out[k] = {"units": list(v["units"][:cut]) + list(suffix[k]["units"]),
                      "head": suffix[k]["head"]}
        else:
            out[k] = suffix[k]
    return out


@dataclasses.dataclass
class RoundPlan:
    """Host-side staging of one federation round.  Static fields key the
    compile cache; array fields are the per-round inputs of the compiled
    program (so rounds with the same structure never retrace)."""
    cuts_sig: Tuple[Tuple[int, int], ...]      # ((cut, n_padded), ...) static
    steps: int                                 # static
    bucket_rows: List[np.ndarray]              # (n_pad,) client row per slot
    bucket_idx: List[np.ndarray]               # (steps, n_pad, B)
    bucket_mask: List[np.ndarray]              # (steps, n_pad) bool
    bucket_w: List[np.ndarray]                 # (n_pad,) aggregation weights
    server_unit_w: np.ndarray                  # (n_units,) RSU copy weights


class CohortEngine:
    """Compiles and runs whole federation rounds with one (or a few) jitted
    dispatches instead of a Python loop per client per batch.

    One instance per simulation: it owns the stacked client data (device
    resident, staged once) and a compile cache keyed by round structure
    (bucket cuts/sizes, local steps, batch).  See DESIGN.md §6 for the
    equivalence argument with the seed per-client loop.

    Intra-bucket schedules (same math, different compilation):
      * "vmap"   — vehicle-side compute vectorized across the stacked replica
                   axis, local steps scanned.  The accelerator schedule.
      * "scan"   — replicas AND steps fused into nested lax.scans: one
                   dispatch per round.  The CPU schedule for matmul-dominated
                   models (transformer units, MLPs).
      * "unroll" — replicas unrolled inside a per-step program, Python loop
                   over steps.  XLA:CPU lowers convolution *gradients* inside
                   while-loop bodies (and per-replica-filter convs, i.e.
                   vmapped client backward passes) to a slow generic path —
                   ~20-45x slower than straight-line code — so conv models on
                   CPU keep convs out of while bodies entirely.  Still one
                   dispatch per step (not per client-batch) and zero host
                   syncs inside the round.

    "auto" picks vmap on accelerators; on CPU, scan when the model declares
    ``scan_friendly`` else unroll.

    With a vehicle-axis :class:`~repro.core.fleet_sharding.FleetMesh`
    (``cfg.mesh_devices > 1``, or an explicit ``mesh=``), the split and FL
    round programs run under ``shard_map``: bucket slots are padded to
    device multiples and sharded, client-side compute and optimizer state
    stay shard-local, the shared RSU state is replicated (it consumes the
    all-gathered smashed batches in canonical slot order, preserving paper
    §III-B sequential semantics), and the unit-wise FedAvg is a psum'd
    weighted all-reduce (DESIGN.md §10).  The sharded cohort schedule IS
    the vmap schedule — ``scan``/``unroll`` serialize the very axis the
    mesh partitions and are rejected."""

    def __init__(self, model: UnitModel, cfg: SimConfig,
                 clients: Sequence[ClientDataset],
                 mesh: Optional[FleetMesh] = None):
        self.model = model
        self.cfg = cfg
        self.opt = _make_opt(cfg)
        self.fleet_mesh = mesh if mesh is not None \
            else fleet_sharding.from_config(cfg, "federation",
                                            fleet_size=len(clients))
        if self.fleet_mesh is not None and self.fleet_mesh.axis != "vehicle":
            raise ValueError(
                f"CohortEngine shards the vehicle axis; got a FleetMesh "
                f"over {self.fleet_mesh.axis!r} (fleet_axis='vehicle' or "
                f"'auto')")
        self.stacked: StackedClients = stack_clients(clients)
        if self.fleet_mesh is not None:
            self.stacked = self.fleet_mesh.place_stacked(self.stacked)
        self._programs: Dict[Any, Callable] = {}
        mode = cfg.cohort_parallel
        if self.fleet_mesh is not None:
            if mode in ("scan", "unroll"):
                raise ValueError(
                    f"cohort_parallel={mode!r} serializes the replica axis "
                    f"the mesh shards; with mesh_devices > 1 use 'vmap' "
                    f"(or 'auto')")
            mode = "vmap"
        elif mode == "auto":
            if jax.default_backend() == "cpu":
                mode = "scan" if getattr(model, "scan_friendly", False) \
                    else "unroll"
            else:
                mode = "vmap"
        assert mode in ("vmap", "scan", "unroll"), mode
        self.mode = mode

    def slot_pad(self, n: int) -> int:
        """Bucket slot-count padding: pow2 (the compile-cache signature
        scheme) then up to a device multiple so every shard holds the same
        number of slots.  Padded slots carry zero weight — inert."""
        p = _pow2(n)
        return self.fleet_mesh.pad(p) if self.fleet_mesh is not None else p

    # ---- the shared SFL message-flow math (one client batch) ---------
    def _sfl_client_batch(self, cut, sv, so, cu_i, co_i, x_i, y_i):
        """Explicit message flow for one client batch against the shared
        RSU state: client fwd -> smashed -> server fwd/bwd -> cut-gradient
        -> client bwd.  Returns updated (sv, so, cu, co, loss)."""
        model, opt, cfg = self.model, self.opt, self.cfg

        def client_fwd(c):
            return model.apply_units(c, x_i, 0)

        smashed, cvjp = jax.vjp(client_fwd, cu_i)
        sm_in = _wire_transform(cfg, smashed)

        def server_loss(svt, sm):
            feats = model.apply_units(svt["units"], sm, cut)
            loss, logits = model.head_loss(svt["head"], feats, y_i)
            return loss, logits

        (loss, _), grads = jax.value_and_grad(
            server_loss, argnums=(0, 1), has_aux=True)(sv, sm_in)
        g_sv, g_sm = grads
        g_sm = _wire_transform(cfg, g_sm)
        (g_cu,) = cvjp(g_sm)
        upd_c, co2 = opt.update(g_cu, co_i, cu_i)
        cu2 = optim.apply_updates(cu_i, upd_c)
        upd_s, so2 = opt.update(g_sv, so, sv)
        sv2 = optim.apply_updates(sv, upd_s)
        return sv2, so2, cu2, co2, loss

    def _full_batch(self, tree, ost, x_i, y_i):
        """One full-model (CL / FL local) batch step."""
        model, opt = self.model, self.opt

        def loss_fn(t):
            feats = model.apply_units(t["units"], x_i, 0)
            loss, logits = model.head_loss(t["head"], feats, y_i)
            return loss, logits

        (loss, _), g = jax.value_and_grad(loss_fn, has_aux=True)(tree)
        upd, ost2 = opt.update(g, ost, tree)
        return optim.apply_updates(tree, upd), ost2, loss

    # ---- intra-bucket schedules --------------------------------------
    def _bucket_scan(self, cut, sv, so, cu, co, x, y, msk):
        """Fused sequential schedule: one lax.scan over the bucket's client
        axis; the body is the full message flow.  Exactly the seed loop's
        update order for this bucket."""
        def body(carry, inp):
            sv, so = carry
            cu_i, co_i, x_i, y_i, act = inp
            sv2, so2, cu2, co2, loss = self._sfl_client_batch(
                cut, sv, so, cu_i, co_i, x_i, y_i)
            sv = _select(act, sv2, sv)
            so = _select(act, so2, so)
            cu2 = _select(act, cu2, cu_i)
            co2 = _select(act, co2, co_i)
            return (sv, so), (cu2, co2, jnp.where(act, loss, 0.0))

        (sv, so), (cu, co, losses) = lax.scan(body, (sv, so),
                                              (cu, co, x, y, msk))
        return cu, co, sv, so, losses

    def _bucket_unroll(self, cut, sv, so, cu, co, x, y, msk):
        """Unrolled schedule: same client order and math as _bucket_scan,
        emitted as straight-line code (fast conv grads on XLA:CPU)."""
        n_pad = msk.shape[0]
        cus, cos, losses = [], [], []
        for i in range(n_pad):
            cu_i = jax.tree.map(lambda a: a[i], cu)
            co_i = jax.tree.map(lambda a: a[i], co)
            sv2, so2, cu2, co2, loss = self._sfl_client_batch(
                cut, sv, so, cu_i, co_i, x[i], y[i])
            act = msk[i]
            sv = _select(act, sv2, sv)
            so = _select(act, so2, so)
            cus.append(_select(act, cu2, cu_i))
            cos.append(_select(act, co2, co_i))
            losses.append(jnp.where(act, loss, 0.0))
        cu = jax.tree.map(lambda *a: jnp.stack(a), *cus)
        co = jax.tree.map(lambda *a: jnp.stack(a), *cos)
        return cu, co, sv, so, jnp.stack(losses)

    def _server_scan_body(self, cut):
        """The shared-RSU consume step of the vmap schedule: one smashed
        batch against the shared server state, emitting the cut-layer
        gradient (shared by the sharded and unsharded vmap schedules — the
        sequence of ops must stay identical between them)."""
        model, opt, cfg = self.model, self.opt, self.cfg

        def body(carry, inp):
            sv, so = carry
            sm, y_i, act = inp

            def server_loss(svt, s):
                feats = model.apply_units(svt["units"], s, cut)
                loss, logits = model.head_loss(svt["head"], feats, y_i)
                return loss, logits

            (loss, _), grads = jax.value_and_grad(
                server_loss, argnums=(0, 1), has_aux=True)(sv, sm)
            g_sv, g_sm = grads
            g_sm = _wire_transform(cfg, g_sm)
            upd_s, so2 = opt.update(g_sv, so, sv)
            sv2 = optim.apply_updates(sv, upd_s)
            sv = _select(act, sv2, sv)
            so = _select(act, so2, so)
            g_sm = jnp.where(act, g_sm, jnp.zeros_like(g_sm))
            return (sv, so), (g_sm, jnp.where(act, loss, 0.0))

        return body

    def _bucket_vmap(self, cut, sv, so, cu, co, x, y, msk):
        """Vectorized schedule: vehicle-side fwd/bwd vmapped across the
        stacked replica axis; the shared RSU state still consumes the
        smashed batches sequentially (paper §III-B semantics), via scan."""
        model, cfg = self.model, self.cfg

        def client_fwd(cu_all):
            return jax.vmap(lambda c, xb: model.apply_units(c, xb, 0))(cu_all, x)

        smashed, cvjp = jax.vjp(client_fwd, cu)
        sm_in = _wire_transform(cfg, smashed)

        (sv, so), (g_sm, losses) = lax.scan(self._server_scan_body(cut),
                                            (sv, so), (sm_in, y, msk))
        (g_cu,) = cvjp(g_sm)
        upd, co2 = jax.vmap(self.opt.update)(g_cu, co, cu)
        cu2 = optim.apply_updates(cu, upd)
        cu = _select(msk, cu2, cu)
        co = _select(msk, co2, co)
        return cu, co, sv, so, losses

    def _bucket_vmap_sharded(self, cut, sv, so, cu, co, x, y, msk):
        """The vmap schedule inside a vehicle-axis ``shard_map`` shard:
        client-side fwd/bwd and optimizer updates run on this shard's
        slots only; the smashed batches (and labels/masks) are all-gathered
        so every shard replays the IDENTICAL shared-RSU scan over the full
        cohort in canonical slot order — the server state stays replicated
        by construction, paper §III-B update order survives sharding, and
        each shard slices back exactly its slots' cut-layer gradients.
        Returns full-cohort losses (replicated)."""
        model, cfg = self.model, self.cfg
        n_loc = msk.shape[0]

        def client_fwd(cu_all):
            return jax.vmap(lambda c, xb: model.apply_units(c, xb, 0))(cu_all, x)

        smashed, cvjp = jax.vjp(client_fwd, cu)
        sm_in = _wire_transform(cfg, smashed)
        sm_all = lax.all_gather(sm_in, MESH_AXIS, tiled=True)
        y_all = lax.all_gather(y, MESH_AXIS, tiled=True)
        msk_all = lax.all_gather(msk, MESH_AXIS, tiled=True)

        (sv, so), (g_sm_all, losses) = lax.scan(self._server_scan_body(cut),
                                                (sv, so),
                                                (sm_all, y_all, msk_all))
        g_sm = fleet_sharding.local_slice(g_sm_all, n_loc)
        (g_cu,) = cvjp(g_sm)
        upd, co2 = jax.vmap(self.opt.update)(g_cu, co, cu)
        cu2 = optim.apply_updates(cu, upd)
        cu = _select(msk, cu2, cu)
        co = _select(msk, co2, co)
        return cu, co, sv, so, losses

    def _bucket_fn(self):
        if self.fleet_mesh is not None:
            return self._bucket_vmap_sharded
        return {"scan": self._bucket_scan, "vmap": self._bucket_vmap,
                "unroll": self._bucket_unroll}[self.mode]

    # ---- shared round pieces -----------------------------------------
    def _split_step_body(self, cuts_sig, carry, xs, bdata):
        """One local step across every bucket: client fwd/bwd on all
        (active) replicas, shared RSU state threaded through bucket after
        bucket in ascending-cut order."""
        bucket_fn = self._bucket_fn()
        server, s_opt, bstates = carry
        loss_sum = jnp.zeros((), jnp.float32)
        cnt = jnp.zeros((), jnp.float32)
        new_bstates = []
        for bi, (cut, n_pad) in enumerate(cuts_sig):
            cu, co = bstates[bi]
            idx, msk = xs[bi]
            x = _gather_batch(bdata[bi][0], idx)
            y = _gather_batch(bdata[bi][1], idx)
            sv = {"units": list(server["units"][cut:]),
                  "head": server["head"]}
            so = _suffix_state(s_opt, cut)
            cu, co, sv, so, losses = bucket_fn(cut, sv, so, cu, co, x, y, msk)
            server = {"units": list(server["units"][:cut])
                      + list(sv["units"]), "head": sv["head"]}
            s_opt = _merge_state(s_opt, so, cut)
            new_bstates.append((cu, co))
            loss_sum = loss_sum + jnp.sum(losses)
            c = jnp.sum(msk.astype(jnp.float32))
            if self.fleet_mesh is not None:
                # sharded bucket fns return full-cohort losses (replicated)
                # but the mask here is this shard's slice — complete it
                c = lax.psum(c, MESH_AXIS)
            cnt = cnt + c
        return (server, s_opt, new_bstates), loss_sum, cnt

    def _split_agg(self, cuts_sig, server, bstates, ws, server_unit_w):
        """Unit-wise FedAvg over the stacked axis: vehicle replicas of every
        unit before their cut + the RSU copy of units it served, reduced
        on-device (aggregation.stacked_weighted_sum).  Under a mesh the
        replica axis is sharded, so the bucket reductions become psum'd
        weighted all-reduces (aggregation.sharded_weighted_sum); the RSU
        copy is replicated and contributes locally."""
        n_units = self.model.n_units
        sharded = self.fleet_mesh is not None
        merged = []
        for u in range(n_units):
            swu = server_unit_w[u]
            num = jax.tree.map(
                lambda a: swu * a.astype(jnp.float32), server["units"][u])
            den = swu
            for bi, (cut, n_pad) in enumerate(cuts_sig):
                if cut > u:
                    if sharded:
                        part = aggregation.sharded_weighted_sum(
                            bstates[bi][0][u], ws[bi], MESH_AXIS)
                        den = den + lax.psum(jnp.sum(ws[bi]), MESH_AXIS)
                    else:
                        part = aggregation.stacked_weighted_sum(
                            bstates[bi][0][u], ws[bi])
                        den = den + jnp.sum(ws[bi])
                    num = jax.tree.map(jnp.add, num, part)
            merged.append(jax.tree.map(
                lambda nm, ref: (nm / den).astype(ref.dtype),
                num, server["units"][u]))
        return merged, server["head"]

    def _split_init(self, units, head, rows_list, cuts_sig, data_images,
                    data_labels):
        """Fresh per-round state: shared RSU tree + opt, broadcast client
        replicas + stacked opt states, per-bucket data rows."""
        opt = self.opt
        server = {"units": list(units), "head": head}
        s_opt = opt.init(server)
        bstates, bdata = [], []
        for (cut, n_pad), r in zip(cuts_sig, rows_list):
            cu = jax.tree.map(
                lambda a: jnp.broadcast_to(a, (n_pad,) + a.shape),
                list(units[:cut]))
            co = jax.vmap(opt.init)(cu)
            bstates.append((cu, co))
            bdata.append((data_images[r], data_labels[r]))
        return server, s_opt, bstates, bdata

    # ---- compiled programs -------------------------------------------
    def _split_round_program(self, cuts_sig, steps: int, batch: int):
        """scan/vmap modes: the whole round (init, every local step, the
        aggregation) is ONE jitted program; losses come back as two scalars.
        Under a mesh the same program body runs inside ``shard_map`` with
        every bucket's slot axis sharded (``cuts_sig`` carries the GLOBAL
        padded sizes; each shard traces its 1/D slice)."""
        key = ("split", cuts_sig, steps, batch, self.mode)
        if key in self._programs:
            return self._programs[key]
        fm = self.fleet_mesh
        local_sig = cuts_sig if fm is None else tuple(
            (cut, n_pad // fm.n_devices) for cut, n_pad in cuts_sig)

        def round_fn(units, head, data_images, data_labels, rows, idxs,
                     masks, ws, server_unit_w):
            server, s_opt, bstates, bdata = self._split_init(
                units, head, rows, local_sig, data_images, data_labels)

            def body(carry, xs):
                carry, ls, cs = self._split_step_body(local_sig, carry, xs,
                                                      bdata)
                return carry, (ls, cs)

            (server, s_opt, bstates), (ls, cs) = lax.scan(
                body, (server, s_opt, bstates), tuple(zip(idxs, masks)))
            merged, head2 = self._split_agg(local_sig, server, bstates, ws,
                                            server_unit_w)
            return merged, head2, jnp.sum(ls), jnp.sum(cs)

        if fm is None:
            fn = jax.jit(round_fn)
        else:
            # params/data replicated; slot axes sharded; outputs replicated
            slot = P(MESH_AXIS)
            slab = P(None, MESH_AXIS)        # (steps, n_pad, ...) tensors
            fn = jax.jit(jax.shard_map(
                round_fn, mesh=fm.mesh,
                in_specs=(P(), P(), P(), P(), slot, slab, slab, slot, P()),
                out_specs=(P(), P(), P(), P()), check_vma=False))
        self._programs[key] = fn
        return fn

    def _split_step_program(self, cuts_sig, batch: int):
        """unroll mode: one jitted program per local step (all buckets, all
        replicas, straight-line).  The carry is donated: step s+1 reuses
        step s's buffers."""
        key = ("splitstep", cuts_sig, batch, self.mode)
        if key in self._programs:
            return self._programs[key]

        @functools.partial(jax.jit, donate_argnums=(0,))
        def step_fn(carry, xs, bdata):
            return self._split_step_body(cuts_sig, carry, xs, bdata)

        self._programs[key] = step_fn
        return step_fn

    def _split_agg_program(self, cuts_sig):
        key = ("splitagg", cuts_sig)
        if key in self._programs:
            return self._programs[key]

        @jax.jit
        def agg_fn(server, bstates, ws, server_unit_w):
            return self._split_agg(cuts_sig, server, bstates, ws,
                                   server_unit_w)

        self._programs[key] = agg_fn
        return agg_fn

    def _fl_step_body(self, n_pad, carry, idx_s, msk, bimgs, blabs):
        st, ost = carry
        x = _gather_batch(bimgs, idx_s)
        y = _gather_batch(blabs, idx_s)
        if self.mode == "vmap":
            st2, ost2, losses = jax.vmap(self._full_batch)(st, ost, x, y)
        elif self.mode == "scan":
            def body(_, inp):
                t_i, o_i, x_i, y_i = inp
                t2, o2, loss = self._full_batch(t_i, o_i, x_i, y_i)
                return (), (t2, o2, loss)
            _, (st2, ost2, losses) = lax.scan(body, (), (st, ost, x, y))
        else:
            ts, os_, ls = [], [], []
            for i in range(n_pad):
                t_i = jax.tree.map(lambda a: a[i], st)
                o_i = jax.tree.map(lambda a: a[i], ost)
                t2, o2, loss = self._full_batch(t_i, o_i, x[i], y[i])
                ts.append(t2)
                os_.append(o2)
                ls.append(loss)
            st2 = jax.tree.map(lambda *a: jnp.stack(a), *ts)
            ost2 = jax.tree.map(lambda *a: jnp.stack(a), *os_)
            losses = jnp.stack(ls)
        st = _select(msk, st2, st)
        ost = _select(msk, ost2, ost)
        return (st, ost), (jnp.sum(jnp.where(msk, losses, 0.0)),
                           jnp.sum(msk.astype(jnp.float32)))

    def _fl_round_program(self, n_pad: int, steps: int, batch: int):
        """FL is embarrassingly parallel across clients: under a mesh every
        slot's local steps (model replica, optimizer state, batch gathers)
        are shard-local end to end, and only the closing FedAvg (plus the
        loss/count totals) all-reduce."""
        key = ("fl", n_pad, steps, batch, self.mode)
        if key in self._programs:
            return self._programs[key]
        opt = self.opt
        fm = self.fleet_mesh
        n_loc = n_pad if fm is None else n_pad // fm.n_devices

        def round_fn(units, head, data_images, data_labels, rows, idx,
                     mask, w):
            tree = {"units": list(units), "head": head}
            st = jax.tree.map(
                lambda a: jnp.broadcast_to(a, (n_loc,) + a.shape), tree)
            ost = jax.vmap(opt.init)(st)
            bimgs, blabs = data_images[rows], data_labels[rows]

            def body(carry, xs):
                idx_s, msk = xs
                carry, out = self._fl_step_body(n_loc, carry, idx_s, msk,
                                                bimgs, blabs)
                return carry, out

            (st, ost), (ls, cs) = lax.scan(body, (st, ost), (idx, mask))
            if fm is None:
                avg = aggregation.stacked_fedavg(st, w)
                return avg["units"], avg["head"], jnp.sum(ls), jnp.sum(cs)
            avg = aggregation.sharded_fedavg(st, w, MESH_AXIS)
            return (avg["units"], avg["head"],
                    lax.psum(jnp.sum(ls), MESH_AXIS),
                    lax.psum(jnp.sum(cs), MESH_AXIS))

        if fm is None:
            fn = jax.jit(round_fn)
        else:
            slot, slab = P(MESH_AXIS), P(None, MESH_AXIS)
            fn = jax.jit(jax.shard_map(
                round_fn, mesh=fm.mesh,
                in_specs=(P(), P(), P(), P(), slot, slab, slab, slot),
                out_specs=(P(), P(), P(), P()), check_vma=False))
        self._programs[key] = fn
        return fn

    def _fl_step_program(self, n_pad: int, batch: int):
        key = ("flstep", n_pad, batch, self.mode)
        if key in self._programs:
            return self._programs[key]

        @functools.partial(jax.jit, donate_argnums=(0,))
        def step_fn(carry, idx_s, msk, bimgs, blabs):
            return self._fl_step_body(n_pad, carry, idx_s, msk, bimgs, blabs)

        self._programs[key] = step_fn
        return step_fn

    def _chain_step(self, kind, cut, carry, x_i, y_i):
        if kind == "sl":
            cu, sv, co, so = carry
            sv, so, cu, co, loss = self._sfl_client_batch(
                cut, sv, so, cu, co, x_i, y_i)
            return (cu, sv, co, so), loss
        tree, ost = carry
        tree, ost, loss = self._full_batch(tree, ost, x_i, y_i)
        return (tree, ost), loss

    def _chain_round_program(self, kind: str, cut: int, total_steps: int,
                             batch: int):
        """SL (one traveling vehicle-side model) and CL (centralized) are
        inherently sequential chains; scan/vmap modes fuse the whole chain
        into one scan."""
        key = (kind, cut, total_steps, batch)
        if key in self._programs:
            return self._programs[key]

        @jax.jit
        def round_fn(carry, data_images, data_labels, rows, idx):
            imgs = data_images[rows[:, None], idx]
            labs = data_labels[rows[:, None], idx]

            def body(carry, inp):
                return self._chain_step(kind, cut, carry, *inp)

            carry, losses = lax.scan(body, carry, (imgs, labs))
            return carry, jnp.sum(losses)

        self._programs[key] = round_fn
        return round_fn

    def _chain_step_program(self, kind: str, cut: int, batch: int):
        key = (kind + "step", cut, batch)
        if key in self._programs:
            return self._programs[key]

        @functools.partial(jax.jit, donate_argnums=(0,))
        def step_fn(carry, x_i, y_i):
            return self._chain_step(kind, cut, carry, x_i, y_i)

        self._programs[key] = step_fn
        return step_fn

    # ---- public entry points -----------------------------------------
    def split_round(self, units, head, plan: RoundPlan, batch: int):
        rows = [jnp.asarray(r) for r in plan.bucket_rows]
        ws = tuple(jnp.asarray(w, jnp.float32) for w in plan.bucket_w)
        suw = jnp.asarray(plan.server_unit_w, jnp.float32)
        if self.mode != "unroll":
            fn = self._split_round_program(plan.cuts_sig, plan.steps, batch)
            idxs = tuple(jnp.asarray(i) for i in plan.bucket_idx)
            masks = tuple(jnp.asarray(m) for m in plan.bucket_mask)
            units, head, ls, cnt = fn(units, head, self.stacked.images,
                                      self.stacked.labels, rows, idxs,
                                      masks, ws, suw)
            return list(units), head, ls, cnt
        step_fn = self._split_step_program(plan.cuts_sig, batch)
        agg_fn = self._split_agg_program(plan.cuts_sig)
        server, s_opt, bstates, bdata = self._split_init(
            units, head, rows, plan.cuts_sig, self.stacked.images,
            self.stacked.labels)
        carry = (server, s_opt, bstates)
        ls = cnt = None
        for s in range(plan.steps):
            xs = tuple((jnp.asarray(i[s]), jnp.asarray(m[s]))
                       for i, m in zip(plan.bucket_idx, plan.bucket_mask))
            carry, ls_s, cs_s = step_fn(carry, xs, bdata)
            ls = ls_s if ls is None else ls + ls_s
            cnt = cs_s if cnt is None else cnt + cs_s
        server, s_opt, bstates = carry
        units, head = agg_fn(server, bstates, ws, suw)
        return list(units), head, ls, cnt

    def fl_round(self, units, head, rows, idx, mask, w, batch: int):
        n_pad = len(rows)
        rows = jnp.asarray(rows)
        w = jnp.asarray(w, jnp.float32)
        if self.mode != "unroll":
            fn = self._fl_round_program(n_pad, idx.shape[0], batch)
            units, head, ls, cnt = fn(units, head, self.stacked.images,
                                      self.stacked.labels, rows,
                                      jnp.asarray(idx), jnp.asarray(mask), w)
            return list(units), head, ls, cnt
        step_fn = self._fl_step_program(n_pad, batch)
        tree = {"units": list(units), "head": head}
        st = jax.tree.map(
            lambda a: jnp.broadcast_to(a, (n_pad,) + a.shape), tree)
        ost = jax.vmap(self.opt.init)(st)
        bimgs = self.stacked.images[rows]
        blabs = self.stacked.labels[rows]
        carry, ls, cnt = (st, ost), None, None
        for s in range(idx.shape[0]):
            carry, (ls_s, cs_s) = step_fn(carry, jnp.asarray(idx[s]),
                                          jnp.asarray(mask[s]), bimgs, blabs)
            ls = ls_s if ls is None else ls + ls_s
            cnt = cs_s if cnt is None else cnt + cs_s
        avg = aggregation.stacked_fedavg(carry[0], w)
        return list(avg["units"]), avg["head"], ls, cnt

    def _chain_round(self, kind, cut, carry, rows, idx, batch):
        if self.fleet_mesh is not None:
            raise ValueError(
                f"scheme {kind!r} is an inherently sequential chain (one "
                f"traveling model); the vehicle-axis mesh has nothing to "
                f"shard — run it with mesh_devices=1")
        rows = jnp.asarray(rows)
        idx = jnp.asarray(idx)
        if self.mode == "scan" or self.mode == "vmap":
            fn = self._chain_round_program(kind, cut, idx.shape[0], batch)
            carry, ls = fn(carry, self.stacked.images, self.stacked.labels,
                           rows, idx)
            return carry, ls
        step_fn = self._chain_step_program(kind, cut, batch)
        imgs = self.stacked.images[rows[:, None], idx]
        labs = self.stacked.labels[rows[:, None], idx]
        ls = None
        for s in range(idx.shape[0]):
            carry, loss = step_fn(carry, imgs[s], labs[s])
            ls = loss if ls is None else ls + loss
        return carry, ls

    def sl_round(self, units, head, cut, rows, idx, batch: int):
        carry = (list(units[:cut]),
                 {"units": list(units[cut:]), "head": head},
                 self.opt.init(list(units[:cut])),
                 self.opt.init({"units": list(units[cut:]), "head": head}))
        (cu, sv, co, so), ls = self._chain_round("sl", cut, carry, rows,
                                                 idx, batch)
        return list(cu) + list(sv["units"]), sv["head"], ls

    def cl_round(self, units, head, cl_opt, rows, idx, batch: int):
        carry = ({"units": list(units), "head": head}, cl_opt)
        (tree, cl_opt), ls = self._chain_round("cl", 0, carry, rows, idx,
                                               batch)
        return list(tree["units"]), tree["head"], cl_opt, ls


# --------------------------------------------------------------------------
# the simulator
# --------------------------------------------------------------------------

class FederationSim:
    def __init__(self, model: UnitModel, clients: Sequence[ClientDataset],
                 test: Dict[str, jnp.ndarray], cfg: SimConfig,
                 fleet: Optional[List[channel.VehicleProfile]] = None,
                 ch_cfg: Optional[channel.ChannelConfig] = None,
                 mesh: Optional[FleetMesh] = None):
        if cfg.compilation_cache_dir:
            enable_compilation_cache(cfg.compilation_cache_dir)
        self.model = model
        self.clients = list(clients)
        self.test = test
        self.cfg = cfg
        self.fleet = fleet or channel.make_fleet(len(clients), cfg.seed)
        self.fleet_arr = channel.fleet_arrays(self.fleet)
        self.ch = ch_cfg or channel.ChannelConfig()
        self.profile = model.profile()
        self.engine = CohortEngine(model, cfg, self.clients, mesh=mesh)
        if self.engine.fleet_mesh is not None and cfg.scheme in ("cl", "sl"):
            raise ValueError(
                f"scheme {cfg.scheme!r} is an inherently sequential chain; "
                f"the vehicle-axis mesh shards parallel cohorts only "
                f"(fl | sfl | asfl) — set mesh_devices=1")
        self.faults = cfg.fault_config()
        if (self.faults.straggler_factor > 0.0
                or self.faults.rsu_outage_rate > 0.0):
            raise ValueError(
                "FederationSim is the single-RSU engine: fault_straggler "
                "and fault_rsu_outage need the multi-RSU ScenarioEngine "
                "(residence deadlines and RSU outages are scenario "
                "concepts)")
        if self.faults.stochastic and cfg.scheme not in ("sfl", "asfl"):
            raise ValueError(
                f"fault injection is wired into the split-federation round "
                f"(sfl | asfl); scheme {cfg.scheme!r} does not support it")
        if cfg.server_schedule == "streaming":
            raise ValueError(
                "server_schedule='streaming' needs the multi-RSU "
                "ScenarioEngine (the StreamBuffer is per-RSU super-step "
                "carry state); FederationSim runs the single-RSU "
                "synchronous round loop")
        if cfg.stream_config().churning:
            raise ValueError(
                "presence churn (stream_churn_rate > 0 or "
                "stream_churn_source='mobility') needs the multi-RSU "
                "ScenarioEngine (churn is traced super-step carry state; "
                "the single-RSU engine models coverage via fault_coverage)")
        self.reset()

    def reset(self):
        """Re-initialise parameters and history (compiled round programs and
        staged data are kept — benchmarks time warm re-runs with this)."""
        key = jax.random.PRNGKey(self.cfg.seed)
        self.units, self.head = self.model.init(key)
        self._cl_opt = None
        self.history: List[RoundMetrics] = []

    # ---- helpers -----------------------------------------------------
    def _local_steps(self, client: ClientDataset) -> int:
        if self.cfg.local_steps is not None:
            return self.cfg.local_steps
        nb = max(len(client) // self.cfg.batch_size, 1)
        return nb * self.cfg.local_epochs

    def _round_rates(self, rnd: int) -> np.ndarray:
        t = rnd * self.cfg.round_interval_s
        return channel.sample_round_rates(self.ch, self.fleet_arr, t,
                                          self.cfg.seed * 1000 + rnd)

    def _participants(self, rnd: int) -> List[int]:
        """Vehicle indices in RSU coverage this round (all, unless the
        coverage fault — legacy mobility_dropout — is enabled).  At least
        one vehicle always participates."""
        if not self.faults.coverage:
            return list(range(len(self.clients)))
        t = rnd * self.cfg.round_interval_s
        inr = np.nonzero(channel.in_range_mask(self.ch, self.fleet_arr, t))[0]
        return list(map(int, inr)) or [0]

    def _pick_cuts(self, rates: np.ndarray) -> List[int]:
        c = self.cfg
        if c.scheme == "sfl" or c.scheme == "sl":
            return [c.cut] * len(self.clients)
        strat = c.adaptive_strategy
        if strat not in FEDERATION_STRATEGIES:
            raise ValueError(
                f"adaptive_strategy {strat!r} needs the multi-RSU "
                f"ScenarioEngine; FederationSim supports: "
                f"{' | '.join(FEDERATION_STRATEGIES)}")
        if strat == "paper":
            return adaptive.paper_threshold(rates)
        if strat == "paper-literal":
            return adaptive.paper_threshold(rates, literal_eq3=True)
        if strat == "memory":
            return adaptive.memory_constrained(
                self.profile, self.fleet_arr["memory_budget_bytes"],
                adaptive.paper_threshold, rates)
        flops = self.fleet_arr["compute_flops"]
        nb = max(len(self.clients[0]) // c.batch_size, 1)
        if strat == "latency":
            return adaptive.latency_optimal(self.profile, rates, flops,
                                            c.server_flops, nb, c.batch_size,
                                            c.local_epochs)
        return adaptive.energy_aware(self.profile, rates, flops,
                                     c.server_flops, nb, c.batch_size,
                                     c.local_epochs)

    # ---- schemes -----------------------------------------------------
    def run(self, on_round: Optional[Callable[[RoundMetrics], None]] = None
            ) -> List[RoundMetrics]:
        """Run ``cfg.rounds`` federation rounds.  ``on_round`` (the api
        layer's streaming hook) is invoked with each round's metrics as it
        completes."""
        for rnd in range(self.cfg.rounds):
            fn = getattr(self, f"_round_{self.cfg.scheme}")
            metrics = fn(rnd)
            self.history.append(metrics)
            if on_round is not None:
                on_round(metrics)
        return self.history

    def _metrics(self, rnd, loss, cuts, comm, time_s, energy) -> RoundMetrics:
        ev = self.cfg.eval_every
        if ev and rnd % ev == 0:
            acc = evaluate(self.model, self.units, self.head, self.test)
        else:
            acc = float("nan")
        return RoundMetrics(rnd, float(loss), acc, comm, time_s, energy, cuts)

    def _round_cl(self, rnd: int) -> RoundMetrics:
        # centralized: pool every client's raw data at the RSU (the upper
        # bound the paper argues against — raw-data upload included in comm)
        cfgc = self.cfg
        if self._cl_opt is None:
            self._cl_opt = self.engine.opt.init(
                {"units": self.units, "head": self.head})
        rows_l, idx_l = [], []
        for ci, c in enumerate(self.clients):
            eidx = epoch_batch_indices(len(c), cfgc.batch_size,
                                       cfgc.seed + rnd)
            rows_l += [ci] * len(eidx)
            idx_l.append(eidx)
        rows = np.asarray(rows_l, np.int32)
        idx = np.concatenate(idx_l).astype(np.int32)
        self.units, self.head, self._cl_opt, ls = self.engine.cl_round(
            self.units, self.head, self._cl_opt, rows, idx, cfgc.batch_size)
        comm = sum(c.images.nbytes for c in self.clients) if rnd == 0 else 0.0
        return self._metrics(rnd, float(ls) / max(len(rows), 1), [], comm,
                             0.0, 0.0)

    def _round_fl(self, rnd: int) -> RoundMetrics:
        cfgc = self.cfg
        rates = self._round_rates(rnd)
        part = self._participants(rnd)
        n_pad = self.engine.slot_pad(len(part))
        steps_i = [self._local_steps(self.clients[ci]) for ci in part]
        steps = max(steps_i)
        rows = np.zeros(n_pad, np.int32)
        rows[:len(part)] = part
        idx = np.zeros((steps, n_pad, cfgc.batch_size), np.int32)
        mask = np.zeros((steps, n_pad), bool)
        w = np.zeros(n_pad, np.float64)
        for j, ci in enumerate(part):
            ln = len(self.clients[ci])
            w[j] = ln
            for s in range(steps_i[j]):
                idx[s, j] = sample_batch_indices(ln, cfgc.batch_size,
                                                 cfgc.seed + rnd * 997 + s)
                mask[s, j] = True
        self.units, self.head, ls, cnt = self.engine.fl_round(
            self.units, self.head, rows, idx, mask, w, cfgc.batch_size)

        nb = np.array([max(len(self.clients[ci]) // cfgc.batch_size, 1)
                       for ci in part])
        rc = cost.fl_round_cost_arrays(
            self.profile, nb, cfgc.batch_size, rates[part],
            self.fleet_arr["compute_flops"][part], cfgc.local_epochs,
            self.fleet_arr["tx_power_w"][part],
            self.fleet_arr["compute_power_w"][part])
        return self._metrics(rnd, float(ls) / max(float(cnt), 1.0), [],
                             float(rc.comm_bytes.sum()),
                             float(rc.latency.max()),
                             float(rc.energy_j.sum()))

    def _round_sl(self, rnd: int) -> RoundMetrics:
        """Vanilla sequential SL: the vehicle-side model travels from vehicle
        to vehicle; the RSU-side model trains continuously."""
        cfgc = self.cfg
        cut = cfgc.cut
        rates = self._round_rates(rnd)
        rows_l, idx_l = [], []
        for ci, c in enumerate(self.clients):
            for s in range(self._local_steps(c)):
                rows_l.append(ci)
                idx_l.append(sample_batch_indices(
                    len(c), cfgc.batch_size, cfgc.seed + rnd * 991 + s))
        rows = np.asarray(rows_l, np.int32)
        idx = np.stack(idx_l).astype(np.int32)
        self.units, self.head, ls = self.engine.sl_round(
            self.units, self.head, cut, rows, idx, cfgc.batch_size)
        rc = cost.sl_round_cost(
            self.profile, cut,
            [max(len(c) // cfgc.batch_size, 1) for c in self.clients],
            cfgc.batch_size, rates, self.fleet_arr["compute_flops"],
            cfgc.server_flops, cfgc.local_epochs)
        return self._metrics(rnd, float(ls) / max(len(rows), 1),
                             [cut] * len(self.clients), rc.comm_bytes,
                             rc.latency, rc.energy_j)

    def _round_sfl(self, rnd: int) -> RoundMetrics:
        return self._parallel_split_round(rnd)

    def _round_asfl(self, rnd: int) -> RoundMetrics:
        return self._parallel_split_round(rnd)

    def _plan_split_round(self, rnd: int, cuts: List[int],
                          participants: List[int],
                          performed: Optional[Dict[int, int]] = None,
                          survivors: Optional[Dict[int, bool]] = None
                          ) -> RoundPlan:
        """Stage one SFL/ASFL round: bucket participants by cut (ascending,
        stable by client index), pad buckets to powers of two (bounds the
        compile cache under per-round adaptive cut churn), and pre-draw every
        client's batch-index stream for the whole round.

        Fault plane (DESIGN.md §13): ``performed[ci]`` truncates a mid-round
        dropout's step mask to the steps it actually ran; ``survivors[ci]``
        zeroes the merge weight of any failed vehicle so its client-side
        update folds into the aggregation as an exact ``+0``.  Both are
        *data* (mask and weight tensors) — the compiled round program and
        its signature are untouched, so fault churn never retraces."""
        cfgc = self.cfg
        n_units = self.model.n_units
        buckets: Dict[int, List[int]] = {}
        for ci in participants:
            buckets.setdefault(cuts[ci], []).append(ci)
        steps = max(self._local_steps(self.clients[ci])
                    for ci in participants)
        cuts_sig, rows_l, idx_l, mask_l, w_l = [], [], [], [], []
        for cut in sorted(buckets):
            members = sorted(buckets[cut])
            n_pad = self.engine.slot_pad(len(members))
            rows = np.zeros(n_pad, np.int32)
            rows[:len(members)] = members
            idx = np.zeros((steps, n_pad, cfgc.batch_size), np.int32)
            mask = np.zeros((steps, n_pad), bool)
            w = np.zeros(n_pad, np.float64)
            for j, ci in enumerate(members):
                ln = len(self.clients[ci])
                w[j] = ln if survivors is None or survivors[ci] else 0.0
                n_s = (self._local_steps(self.clients[ci])
                       if performed is None else performed[ci])
                for s in range(n_s):
                    idx[s, j] = sample_batch_indices(
                        ln, cfgc.batch_size,
                        cfgc.seed + rnd * 983 + s * 31 + ci)
                    mask[s, j] = True
            cuts_sig.append((cut, n_pad))
            rows_l.append(rows)
            idx_l.append(idx)
            mask_l.append(mask)
            w_l.append(w)
        server_unit_w = np.array(
            [sum(len(self.clients[ci]) for ci in participants
                 if cuts[ci] <= u) for u in range(n_units)], np.float64)
        return RoundPlan(tuple(cuts_sig), steps, rows_l, idx_l, mask_l, w_l,
                         server_unit_w)

    def _parallel_split_round(self, rnd: int) -> RoundMetrics:
        """SFL/ASFL with SplitFed-V1 semantics: vehicle-side replicas train
        in parallel at (possibly heterogeneous) cuts while the RSU keeps ONE
        shared server-side model that is updated on every client batch (the
        RSU 'sequentially performs forward propagation ... with the received
        smashed data' — paper §III-B).  Round end: vehicle-side units are
        FedAvg'd (|D_n|-weighted) with the RSU copy of any unit it trained.
        The whole round — every bucket, every local step, the aggregation —
        is one compiled CohortEngine program."""
        cfgc = self.cfg
        fc = self.faults
        rates = self._round_rates(rnd)
        participants = self._participants(rnd)
        cuts = [max(1, min(c, self.model.n_units - 1))
                for c in self._pick_cuts(rates)]
        performed = survivors = uploads = None
        if fc.stochastic:
            # host twin of the traced fault plane (DESIGN.md §13): dropouts
            # truncate the step mask, upload losses zero the merge weight —
            # both data, so the compiled round program never retraces
            drop, dfrac, lost = faults.sample_faults_host(
                fc, rnd, len(self.clients))
            lost = lost & ~drop          # dropout precedence (never uploads)
            if all(drop[ci] or lost[ci] for ci in participants):
                # at-least-one-participant guarantee: clear the first
                # scheduled vehicle's failure bits (faults.rescue_mask twin)
                drop[participants[0]] = lost[participants[0]] = False
            performed = {ci: (int(dfrac[ci] * self._local_steps(
                                  self.clients[ci])) if drop[ci]
                              else self._local_steps(self.clients[ci]))
                         for ci in participants}
            survivors = {ci: not (drop[ci] or lost[ci])
                         for ci in participants}
            uploads = {ci: not drop[ci] for ci in participants}
        plan = self._plan_split_round(rnd, cuts, participants, performed,
                                      survivors)
        self.units, self.head, ls, cnt = self.engine.split_round(
            self.units, self.head, plan, cfgc.batch_size)

        part = np.asarray(participants)
        if fc.stochastic:
            # charge only the work performed: a dropout pays its partial
            # smashed traffic and compute but no aggregation upload; an
            # upload loss pays everything (the upload went out and was
            # lost); the straggler latency bound is over merge survivors
            rc = cost.sfl_round_cost_arrays(
                self.profile, np.asarray(cuts)[part],
                np.array([performed[ci] for ci in participants]),
                cfgc.batch_size, rates[part],
                self.fleet_arr["compute_flops"][part], cfgc.server_flops,
                1, self.fleet_arr["tx_power_w"][part],
                self.fleet_arr["compute_power_w"][part],
                wire=cfgc.wire_scheme(), wire_k=cfgc.wire_k,
                model_upload=np.array([uploads[ci]
                                       for ci in participants]))
            surv_arr = np.array([survivors[ci] for ci in participants])
            latency = float(np.max(rc.latency[surv_arr], initial=0.0))
        else:
            rc = cost.sfl_round_cost_arrays(
                self.profile, np.asarray(cuts)[part],
                np.array([max(len(self.clients[ci]) // cfgc.batch_size, 1)
                          for ci in participants]),
                cfgc.batch_size, rates[part],
                self.fleet_arr["compute_flops"][part], cfgc.server_flops,
                cfgc.local_epochs, self.fleet_arr["tx_power_w"][part],
                self.fleet_arr["compute_power_w"][part],
                wire=cfgc.wire_scheme(), wire_k=cfgc.wire_k)
            latency = float(rc.latency.max())
        # cost.effective_comm_bytes charges the wire inside the model: the
        # smashed bytes (both directions) shrink by the per-cut packed-byte
        # ratio while model-transfer bytes stay dense, and latency/energy
        # follow the compressed counts (previously a post-hoc division here
        # wrongly discounted the model bytes and left energy uncompressed)
        m = self._metrics(rnd, float(ls) / max(float(cnt), 1.0), cuts,
                          float(rc.comm_bytes.sum()), latency,
                          float(rc.energy_j.sum()))
        if fc.stochastic:
            bytes_cum = np.concatenate(
                [[0.0], np.cumsum(self.profile.unit_param_bytes)])
            failed = [ci for ci in participants if not survivors[ci]]
            m.n_dropout = int(sum(drop[ci] for ci in participants))
            m.n_upload_lost = int(sum(lost[ci] for ci in participants))
            m.survivor_frac = (float(sum(survivors.values()))
                               / max(len(participants), 1))
            m.lost_update_bytes = float(
                sum(bytes_cum[cuts[ci]] for ci in failed))
        return m


# --------------------------------------------------------------------------
# multi-RSU scenario orchestration (DESIGN.md §7)
# --------------------------------------------------------------------------

@dataclasses.dataclass
class ScenarioRoundMetrics:
    round: int
    loss: float
    test_acc: float          # NaN on rounds without a cloud sync / eval
    comm_bytes: float
    sim_time_s: float        # straggler-bounded round latency
    energy_j: float
    n_scheduled: int         # vehicles that trained this round
    n_skipped: int           # in coverage but residence-infeasible (cut=SKIP)
    n_handover: int          # vehicles that re-associated since last round
    rsu_loads: List[int]     # participants per RSU
    cuts: List[int]          # fleet-wide cuts; 0 = sat the round out
    # fault-plane telemetry (DESIGN.md §13); defaults = no faults
    n_dropout: int = 0       # scheduled vehicles that dropped mid-round
    n_upload_lost: int = 0   # full work done, update lost on the uplink
    n_straggler: int = 0     # deadline-exceeded; update banked, not lost
    n_rsu_down: int = 0      # RSUs that sat the round out
    survivor_frac: float = 1.0   # merged / scheduled (effective participation)
    lost_update_bytes: float = 0.0  # client-side params that never merged
    stale_merged: float = 0.0    # banked straggler weight merged this round
    # streaming-plane telemetry (DESIGN.md §14); defaults = no streaming
    n_present: int = -1          # fleet presence after churn (-1 = no churn)
    n_arrived: int = 0           # vehicles that arrived this round
    absorbed_samples: float = 0.0  # sample weight MERGED into an edge model
    stream_merges: int = 0       # StreamBuffer fires this round
    buffer_occupancy: float = 0.0  # pending deltas across RSUs, post-round
    stream_stale: float = 0.0    # summed slot ages of the merged deltas


class ScenarioEngine:
    """Multi-RSU federation orchestrator over a pluggable mobility
    :class:`~repro.core.scenario.Scenario`, with handover and hierarchical
    edge→cloud aggregation — executed as **fused super-steps**
    (:mod:`repro.core.superstep`, DESIGN.md §8).

    Per round, inside the compiled program:

    1. Fleet state — positions, serving RSU, Shannon rates, residence —
       from the scenario's traced-step path (or staged per super-step for
       scenarios without one, e.g. ``urban_grid``).
    2. Cuts, fleet-wide and on-device: ``paper`` Eq. 3 banding or
       ``residence``-aware deadline feasibility with SKIP.
    3. On-device segment grouping (one sort of (serving, cut, vehicle)
       keys) stacks every RSU's cohort on a leading RSU axis; all RSUs
       train inside the same program with the cut as *data* (per-unit
       client/server parameter masking), then unit-wise FedAvg at the edge.
    4. Every ``cloud_sync_every`` rounds a sample-weighted cloud merge
       across the RSU axis re-seeds every edge model from the global.

    ``cfg.superstep = K`` fuses K such rounds into one ``lax.scan`` with the
    carry (edge stack, sample counters, previous serving, global model)
    donated between dispatches; K = 1 is the per-round dispatch path — the
    *same* program at scan length 1, which is why fused and sequential
    execution agree bit-for-bit (tests/test_superstep.py).  On CPU the
    cut-as-data formulation makes the K=1 path ~2x slower per round than
    PR 2's static-bucket engine; K >= 4 (with ``slot_capacity="tight8"``)
    recovers to at-or-above its throughput — set ``superstep`` accordingly
    when round rate matters (DESIGN.md §8 has the floor analysis).  Dynamic
    membership never retraces: programs are keyed by the rounded per-RSU
    slot capacity (``slot_capacity``: pow2, or tight8 = next multiple of
    8), so join/leave/handover only reshuffles which rows of the
    device-resident :class:`StackedClients` tensors each round gathers.

    Handover semantics: a vehicle's data shard and identity travel with it
    (its rows in the stacked tensors are RSU-agnostic); server-side model
    and optimizer state stay at the RSU.  The handover cost charges the
    vehicle-side sub-model re-download at the new cell.

    What stays in Python: metrics assembly, analytic comm/latency/energy
    accounting, and evaluation — all fed from per-round scan outputs pulled
    once per super-step.
    """

    def __init__(self, model: UnitModel, clients: Sequence[ClientDataset],
                 test: Dict[str, jnp.ndarray], cfg: SimConfig, scenario,
                 cloud_sync_every: int = 1,
                 mesh: Optional[FleetMesh] = None):
        assert len(clients) == scenario.n_vehicles, \
            (len(clients), scenario.n_vehicles)
        if cfg.adaptive_strategy not in SCENARIO_STRATEGIES:
            raise ValueError(
                f"ScenarioEngine supports adaptive_strategy "
                f"{' | '.join(SCENARIO_STRATEGIES)}, got "
                f"{cfg.adaptive_strategy!r} (the single-RSU FederationSim "
                f"strategies latency/energy/memory are not wired here)")
        if cfg.compilation_cache_dir:
            enable_compilation_cache(cfg.compilation_cache_dir)
        self.model = model
        self.clients = list(clients)
        self.test = test
        self.cfg = cfg
        self.scenario = scenario
        self.n_rsus = len(scenario.rsu_positions)
        self.fa = scenario.fleet_arrays
        self.profile = model.profile()
        self.lengths = np.array([len(c) for c in clients], dtype=np.int64)
        self.cloud_sync_every = max(int(cloud_sync_every), 1)
        self.fleet_mesh = mesh if mesh is not None \
            else fleet_sharding.from_config(cfg, "scenario",
                                            fleet_size=scenario.n_vehicles)
        if self.fleet_mesh is not None and \
                self.fleet_mesh.axis not in ("rsu", "grid"):
            raise ValueError(
                f"ScenarioEngine shards the RSU axis (optionally x the "
                f"vehicle slot axis); got a FleetMesh over "
                f"{self.fleet_mesh.axis!r} (fleet_axis='rsu', 'grid' or "
                f"'auto')")
        nb, ep = self._nb_ep()
        self.programs = SuperStepPrograms(
            model, cfg, stack_clients(self.clients), self.lengths, scenario,
            self.n_rsus, self.cloud_sync_every, self.profile, nb, ep,
            mesh=self.fleet_mesh)
        self.mode = ("fused-traced" if self.programs.traced_mobility
                     else "fused-staged")
        self._cohort_counts: Dict[int, int] = {}
        self._covered_totals: Dict[int, int] = {}
        self._state_cache: Dict[int, Any] = {}
        # double-buffered window staging (DESIGN.md §14): the next window's
        # batch/mobility arrays are built while the current one trains
        self._xs_stage = DoubleBuffer()
        self.reset()

    def reset(self):
        """Fresh parameters/history; compiled programs and staged data are
        kept (benchmarks time warm re-runs with this)."""
        units, head = self.model.init(jax.random.PRNGKey(self.cfg.seed))
        self.units, self.head = list(units), head
        # the carry holds its own buffers: the whole carry is DONATED to the
        # next super-step, while self.units/self.head stay valid for
        # callers between (and after) runs
        self._carry = self.programs.make_carry(units, head,
                                               len(self.clients))
        self._sync_count = 0
        self.history: List[ScenarioRoundMetrics] = []
        # sequential schedule: per round run since the last reset, the
        # slot chain's trip count on each RSU-axis shard
        self.slot_trips: List[np.ndarray] = []
        # what the engine did since the last reset, counted where it
        # happens: windows and rounds run, programs enqueued (super-steps,
        # global-model copies or unravels, eval batches), blocking device
        # reads (pulled scan outputs, eval batch counts), evaluations
        # (and the model's own counter, when the program sums one per
        # round: SplitLoRAUnitModel's held expert rows)
        self.counters: Dict[str, int] = dict.fromkeys(
            ("windows", "rounds", "dispatches", "host_syncs",
             "evaluations") + ((self.programs.aux_counter,)
                               if self.programs.aux_counter else ()), 0)

    # ---- staging ------------------------------------------------------
    def _nb_ep(self) -> Tuple[int, int]:
        """(n_batches, epochs) — uniform across the fleet: the scenario
        engine runs every scheduled vehicle for the same number of local
        steps (deadline feasibility is folded into cut selection)."""
        c = self.cfg
        if c.local_steps is not None:
            return c.local_steps, 1
        return max(int(self.lengths.max()) // c.batch_size, 1), c.local_epochs

    def _steps(self) -> int:
        nb, ep = self._nb_ep()
        return nb * ep

    def _host_state(self, rnd: int):
        """Cached host fleet state for round ``rnd`` (fleet_state is a pure
        function of (t, seed), so capacity planning and staged-mobility
        windows share one evaluation per round)."""
        st = self._state_cache.get(rnd)
        if st is None:
            st = self.scenario.fleet_state(rnd * self.cfg.round_interval_s,
                                           self.cfg.seed * 1000 + rnd)
            self._state_cache[rnd] = st
        return st

    def _capacity(self, horizon: int) -> int:
        """pow2 per-RSU slot capacity over rounds [0, horizon): the max
        *covered*-vehicle count of any cell — coverage is deterministic
        geometry, so this upper-bounds every scheduled cohort the traced
        scheduler can form, and the pow2 bucketing keeps the compile-cache
        signature stable under membership churn."""
        for rnd in range(horizon):
            if rnd not in self._cohort_counts:
                s = self._host_state(rnd).serving_rsu
                c = int(np.bincount(s[s >= 0],
                                    minlength=self.n_rsus).max()) \
                    if (s >= 0).any() else 0
                self._cohort_counts[rnd] = c
        mx = max([self._cohort_counts[r] for r in range(horizon)] + [1])
        cap = ((mx + 7) // 8) * 8 \
            if self.cfg.slot_capacity == "tight8" else _pow2(mx)
        if self.fleet_mesh is not None:
            # dense 2-D: each RSU's slot row splits into vehicle-axis
            # column blocks, so the capacity must be a dv multiple
            cap = self.fleet_mesh.pad_slots(cap)
        return cap

    def _total_slots(self, horizon: int) -> int:
        """Capacity of the ragged layout's compacted global slot axis over
        rounds [0, horizon): the max TOTAL covered count of any round,
        rounded like ``slot_capacity`` for compile-cache stability and
        padded to a device multiple under a mesh
        (:meth:`~repro.core.fleet_sharding.FleetMesh.balanced_slots`).
        0 when the engine's layout/schedule has no compacted axis."""
        if not (self.cfg.server_schedule in ("parallel", "streaming")
                and self.programs.layout == "ragged"):
            return 0
        for rnd in range(horizon):
            if rnd not in self._covered_totals:
                s = self._host_state(rnd).serving_rsu
                self._covered_totals[rnd] = int((s >= 0).sum())
        mx = max([self._covered_totals[r] for r in range(horizon)] + [1])
        slots = ((mx + 7) // 8) * 8 \
            if self.cfg.slot_capacity == "tight8" else _pow2(mx)
        if self.fleet_mesh is not None:
            slots = self.fleet_mesh.balanced_slots(slots)
        return slots

    def occupancy_stats(self) -> Dict[str, Any]:
        """Occupancy accounting for bench rows (DESIGN.md §12): how much of
        the compiled layout's slot and plane budget the run actually used.
        ``executed_slots`` is per-round slot-compute the program runs
        (sequential: slot bodies, RSU rows times the slot chain's trip
        count summed over RSU-axis shards, averaged over the rounds run
        since the last reset — the padded grid before any; dense
        parallel/streaming: the padded grid; ragged parallel/streaming:
        the compacted capacity); ``mean_occupied_slots`` averages the
        scheduled counts over the recorded history; ``owned_plane_frac``
        is the client-plane prefix fraction (1.0 dense)."""
        pg = self.programs
        horizon = max(int(self.cfg.rounds), 1)
        cap = self._capacity(horizon)
        if (self.cfg.server_schedule in ("parallel", "streaming")
                and pg.layout == "ragged"):
            executed = self._total_slots(horizon)
        elif pg.schedule == "sequential":
            rows = pg.n_rsus_padded // (1 if pg.mesh is None
                                        else pg.mesh.rsu_devices)
            executed = (rows * float(np.mean(np.sum(self.slot_trips,
                                                    axis=1)))
                        if self.slot_trips else float(pg.n_rsus_padded * cap))
        else:
            executed = pg.n_rsus_padded * cap
        occ = [float(m.n_scheduled) for m in self.history]
        mean_occ = float(np.mean(occ)) if occ else 0.0
        util = (mean_occ / executed) if executed else 0.0
        return {
            "layout": pg.layout,
            "slot_capacity": int(cap),
            "executed_slots": executed,
            "mean_occupied_slots": mean_occ,
            "padded_slot_frac": float(1.0 - util),
            "owned_plane_frac": float(pg.plane_width
                                      / max(pg.n_params, 1)),
        }

    def _window_xs(self, rnd0: int, k: int):
        """Host staging of one super-step window: the round indices, plus —
        only for scenarios without a traced-step path — the per-round fleet
        state arrays, stacked over the window.  Host numpy only: an eager
        jnp op here would compile a small program inside the run window."""
        xs = {"rnd": np.arange(rnd0, rnd0 + k, dtype=np.int32)}
        if not self.programs.traced_mobility:
            states = [self._host_state(rnd) for rnd in range(rnd0, rnd0 + k)]
            xs["serving"] = jnp.asarray(
                np.stack([s.serving_rsu for s in states]), jnp.int32)
            xs["rates"] = jnp.asarray(
                np.stack([s.rates_bps for s in states]), jnp.float32)
            xs["residence"] = jnp.asarray(
                np.stack([s.residence_s for s in states]), jnp.float32)
        return xs

    def _windows(self, rounds: int):
        k = max(int(self.cfg.superstep or 1), 1)
        rnd = 0
        while rnd < rounds:
            kk = min(k, rounds - rnd)
            yield rnd, kk
            rnd += kk

    # ---- warmup -------------------------------------------------------
    def precompile(self, rounds: Optional[int] = None) -> List[Any]:
        """AOT-lower and compile (``.lower().compile()``) every super-step
        signature the run plan for ``rounds`` (default ``cfg.rounds``) will
        request, plus the evaluation program — so the run itself never
        compiles (asserted via ``programs.compile_fallbacks`` in
        tests/test_superstep.py).  With ``cfg.compilation_cache_dir`` set,
        repeat processes deserialize these binaries instead of re-invoking
        XLA.  Returns the compiled signatures."""
        total = int(rounds if rounds is not None else self.cfg.rounds)
        cap = self._capacity(max(total, 1))
        slots = self._total_slots(max(total, 1))
        sigs = []
        for rnd0, kk in self._windows(total):
            sig = self.programs.signature(kk, cap, slots)
            if sig in sigs:
                continue
            # derive the abstract xs from the real staging path so the
            # precompiled pytree spec can never drift from what
            # run_superstep passes (host states are cached, so this is
            # cheap even for staged-mobility scenarios)
            xs = jax.tree.map(
                lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
                self._window_xs(rnd0, kk))
            self.programs.precompile(sig, self._carry, xs)
            sigs.append(sig)
        ev = self.cfg.eval_every
        if ev and any((r + 1) % self.cloud_sync_every == 0
                      for r in range(total)):
            # compile the eval program through its real call path, on the
            # global model as a window hands it over (under a mesh its
            # placement is the carry's, not the initial model's)
            evaluate(self.model, *self.programs.global_model(self._carry),
                     self.test)
        return sigs

    # ---- the rounds ---------------------------------------------------
    def run_superstep(self, rnd0: int, k: int) -> List[ScenarioRoundMetrics]:
        """Execute rounds [rnd0, rnd0+k) as ONE compiled program and return
        their metrics.  The previous carry is donated; per-round arrays come
        back as scan outputs and are pulled to the host once.

        The host work is annotated for JAX's profiler: an ``engine.window``
        span (stat ``rnd0``) around ``engine.stage``, ``engine.dispatch``,
        ``engine.pull``, ``engine.global_model``, ``engine.metrics`` and
        ``engine.evaluate``; ``counters`` counts what the window did."""
        with jax.profiler.TraceAnnotation("engine.window", rnd0=rnd0):
            return self._run_window(rnd0, k)

    def _run_window(self, rnd0: int, k: int) -> List[ScenarioRoundMetrics]:
        horizon = max(self.cfg.rounds, rnd0 + k)
        cap = self._capacity(horizon)
        sig = self.programs.signature(k, cap, self._total_slots(horizon))
        fn = self.programs.get(sig)
        with jax.profiler.TraceAnnotation("engine.stage"):
            xs = self._xs_stage.take((rnd0, k),
                                     lambda: self._window_xs(rnd0, k))
        with jax.profiler.TraceAnnotation("engine.dispatch"):
            carry, ys = fn(self._carry, xs,        # async dispatch
                           *self.programs.frozen_args)
        self.counters["dispatches"] += 1
        # double-buffered staging (DESIGN.md §14): while the dispatched
        # window trains on device, build the NEXT window's batch/mobility
        # arrays and start their transfers — newly arrived vehicles' shards
        # are resident before their first round forms, and the blocking
        # host pull below overlaps the staging instead of serializing it
        nxt = rnd0 + k
        if nxt < self.cfg.rounds:
            kk = min(max(int(self.cfg.superstep or 1), 1),
                     self.cfg.rounds - nxt)
            with jax.profiler.TraceAnnotation("engine.stage"):
                self._xs_stage.stage((nxt, kk),
                                     lambda: self._window_xs(nxt, kk))
        with jax.profiler.TraceAnnotation("engine.pull"):
            ys = jax.tree.map(np.asarray, ys)      # ONE host sync per window
            self.counters["host_syncs"] += len(jax.tree.leaves(ys))
            if int(ys["counts"].max(initial=0)) > cap:
                # raise BEFORE committing the window: the window silently
                # dropped overflow vehicles, so its carry must not become
                # engine state (the donated previous carry is gone — the
                # engine needs reset() — but nothing masquerades as valid
                # training)
                raise RuntimeError(
                    f"per-RSU cohort exceeded slot capacity {cap}; traced "
                    f"vs host association disagree — raise the capacity "
                    f"margin and reset() the engine")
            if sig.slots and int(ys["counts"].sum(axis=-1).max(initial=0)) \
                    > sig.slots:
                # the ragged layout's compacted axis silently truncates the
                # sorted slot order past its capacity — same contract as
                # the per-RSU check above
                raise RuntimeError(
                    f"fleet-wide occupied slots exceeded the compacted "
                    f"capacity {sig.slots}; traced vs host association "
                    f"disagree — raise the capacity margin and reset() the "
                    f"engine")
        self._carry = carry
        if self.programs.aux_counter:
            self.counters[self.programs.aux_counter] += int(
                ys[self.programs.aux_counter].sum())
        if self.programs.schedule == "sequential":
            self.slot_trips.extend(self.programs.slot_trips(ys["counts"],
                                                            cap))
        with jax.profiler.TraceAnnotation("engine.global_model"):
            self.units, self.head = self.programs.global_model(carry)
        self.counters["dispatches"] += self.programs.global_model_programs
        out = []
        eval_due, last_synced = False, None
        with jax.profiler.TraceAnnotation("engine.metrics"):
            for i in range(k):
                out.append(self._round_metrics(rnd0 + i, i, ys))
                if (rnd0 + i + 1) % self.cloud_sync_every == 0:
                    # evaluate every eval_every-th cloud sync (the global
                    # model only changes at syncs) — counted here on the
                    # host, since the fused window keeps no per-round model
                    # snapshots
                    ev = self.cfg.eval_every
                    if ev and self._sync_count % ev == 0:
                        eval_due = True
                    self._sync_count += 1
                    last_synced = i
        self.counters["windows"] += 1
        self.counters["rounds"] += k
        if eval_due and last_synced is not None:
            # the current global IS the last synced round's model (later
            # rounds trained edges but did not merge), so attaching the
            # score there is exact; K=1 reproduces the per-round schedule
            with jax.profiler.TraceAnnotation("engine.evaluate"):
                out[last_synced].test_acc = evaluate(
                    self.model, self.units, self.head, self.test)
            # one program and one host read per eval batch
            batches = -(-int(self.test["labels"].shape[0]) // EVAL_BATCH)
            self.counters["dispatches"] += batches
            self.counters["host_syncs"] += batches
            self.counters["evaluations"] += 1
        return out

    def _round_metrics(self, rnd: int, i: int, ys) -> ScenarioRoundMetrics:
        cuts = ys["cuts"][i].astype(np.int64)
        serving = ys["serving"][i]
        sched = cuts > 0
        active = serving >= 0
        handover = np.asarray(ys["handover"][i], bool)
        fault = None
        if self.programs.fz:
            # drop/lost/strag come out of the program already scheduled-
            # masked, precedence-ordered, and rescue-cleared
            fault = (np.asarray(ys["dstep"][i], np.int64),
                     np.asarray(ys["drop"][i], bool),
                     np.asarray(ys["lost"][i], bool),
                     np.asarray(ys["strag"][i], bool))
        comm, lat, energy = self._accounting(ys["rates"][i], cuts, sched,
                                             handover, fault)
        loss = float(ys["loss"][i]) / max(float(ys["cnt"][i]), 1.0)
        m = ScenarioRoundMetrics(
            rnd, loss, float("nan"), comm, lat, energy,
            n_scheduled=int(sched.sum()),
            n_skipped=int((active & ~sched).sum()),
            n_handover=int(handover.sum()),
            # the program may pad the RSU axis to a device multiple; padded
            # cells never receive members — report the real cells only
            rsu_loads=[int(c) for c in ys["counts"][i][:self.n_rsus]],
            cuts=[int(c) for c in cuts])
        if fault is not None:
            _, drop, lost, strag = fault
            bytes_cum = np.concatenate(
                [[0.0], np.cumsum(self.profile.unit_param_bytes)])
            surv = sched & ~drop & ~lost & ~strag
            m.n_dropout = int(drop.sum())
            m.n_upload_lost = int(lost.sum())
            m.n_straggler = int(strag.sum())
            m.n_rsu_down = int(
                np.asarray(ys["rsu_down"][i], bool)[:self.n_rsus].sum())
            m.survivor_frac = float(surv.sum()) / max(int(sched.sum()), 1)
            # stragglers are banked, not lost — only drop/lost updates die
            m.lost_update_bytes = float(bytes_cum[cuts[drop | lost]].sum())
            m.stale_merged = float(ys["stale_w"][i])
        if self.programs.cz:
            m.n_present = int(ys["present"][i])
            m.n_arrived = int(ys["arrived"][i])
        if self.programs.sz:
            # streaming: absorption happens at buffer fires, measured
            # in-program (DESIGN.md §14)
            m.absorbed_samples = float(ys["absorbed"][i])
            m.stream_merges = int(ys["stream_fires"][i])
            m.buffer_occupancy = float(ys["buf_occ"][i])
            m.stream_stale = float(ys["stream_stale"][i])
        else:
            # synchronous schedules absorb every merge-surviving update the
            # round it trained — the goodput baseline streaming is compared
            # against (host arithmetic over the same scan outputs)
            if fault is not None:
                _, drop, lost, strag = fault
                merged = sched & ~drop & ~lost & ~strag
            else:
                merged = sched
            m.absorbed_samples = float(self.lengths[merged].sum())
        return m

    def run_round(self, rnd: int) -> ScenarioRoundMetrics:
        return self.run_superstep(rnd, 1)[0]

    def run(self,
            on_round: Optional[Callable[[ScenarioRoundMetrics],
                                        None]] = None,
            on_cloud_merge: Optional[Callable[[int, "ScenarioEngine"],
                                              None]] = None,
            on_stream_merge: Optional[Callable[[ScenarioRoundMetrics,
                                                "ScenarioEngine"],
                                               None]] = None
            ) -> List[ScenarioRoundMetrics]:
        """Run ``cfg.rounds`` rounds as fused super-step windows.

        Streaming hooks (the api layer's callbacks): ``on_round(metrics)``
        fires for every completed round, ``on_cloud_merge(rnd, engine)``
        after every cloud sync, and ``on_stream_merge(metrics, engine)``
        after every round in which at least one StreamBuffer fired
        (``metrics.stream_merges > 0`` — streaming schedule only) — all
        AFTER each fused window completes, fed from the window's single
        host pull, so none adds a host sync to the fused path.  Consequence
        for ``superstep`` K > 1: the fused window keeps no per-round model
        snapshots, so every ``on_cloud_merge`` / ``on_stream_merge`` in a
        window observes ``engine.units/head`` as of the window end (exactly
        the eval semantics above); run with K = 1 if a callback needs the
        global model at each individual sync."""
        for rnd0, kk in self._windows(self.cfg.rounds):
            window = self.run_superstep(rnd0, kk)
            self.history.extend(window)
            for m in window:
                if on_round is not None:
                    on_round(m)
                if (on_cloud_merge is not None
                        and (m.round + 1) % self.cloud_sync_every == 0):
                    on_cloud_merge(m.round, self)
                if on_stream_merge is not None and m.stream_merges > 0:
                    on_stream_merge(m, self)
        return self.history

    def _accounting(self, rates, cuts, sched, handover, fault=None):
        """Analytic per-round comm/latency/energy over the scheduled set +
        the handover model-migration bytes (vehicle-side sub-model
        re-download at the new cell).  Pure numpy over arrays the super-step
        emitted — part of the Python accounting tier by design.

        With ``fault = (dstep, drop, lost, strag)`` (DESIGN.md §13) each
        vehicle is charged the work it performed: dropouts pay their partial
        smashed traffic and compute but no aggregation upload; upload losses
        pay in full (the upload went out and was lost); the straggler bound
        on round latency is over merge survivors — a dropout's partial work
        and a deadline straggler's banked upload do not extend the round."""
        cfgc = self.cfg
        act = np.nonzero(sched)[0]
        bytes_cum = np.concatenate(
            [[0.0], np.cumsum(self.profile.unit_param_bytes)])
        ho_bytes = float(bytes_cum[cuts[handover]].sum())
        if not len(act):
            return ho_bytes, 0.0, 0.0
        nb, ep = self._nb_ep()
        if fault is None:
            rc = cost.sfl_round_cost_arrays(
                self.profile, cuts[act], nb, cfgc.batch_size,
                np.maximum(np.asarray(rates, np.float64)[act], 1.0),
                self.fa["compute_flops"][act], cfgc.server_flops, ep,
                self.fa["tx_power_w"][act], self.fa["compute_power_w"][act],
                wire=cfgc.wire_scheme(), wire_k=cfgc.wire_k)
            lat = float(rc.latency.max())
        else:
            dstep, drop, lost, strag = fault
            rc = cost.sfl_round_cost_arrays(
                self.profile, cuts[act], dstep[act], cfgc.batch_size,
                np.maximum(np.asarray(rates, np.float64)[act], 1.0),
                self.fa["compute_flops"][act], cfgc.server_flops, 1,
                self.fa["tx_power_w"][act], self.fa["compute_power_w"][act],
                wire=cfgc.wire_scheme(), wire_k=cfgc.wire_k,
                model_upload=~drop[act])
            surv = ~(drop | lost | strag)[act]
            lat = float(np.max(rc.latency[surv], initial=0.0))
        # wire bytes charged inside the cost model (smashed both directions;
        # model transfer and handover migration stay dense) — see cost.py
        return (float(rc.comm_bytes.sum()) + ho_bytes, lat,
                float(rc.energy_j.sum()))
