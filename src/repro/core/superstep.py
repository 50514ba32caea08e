"""Fused multi-RSU super-steps (DESIGN.md §8).

PR 2's :class:`~repro.core.fedsim.ScenarioEngine` ran one compiled
CohortEngine cohort **per RSU per round** from a Python loop: an
``np.unique(serving[sched])`` host sync, per-RSU boolean indexing and numpy
staging, one jit dispatch plus a blocking ``float(loss)`` pull per RSU, and
a host-side Python FedAvg at every cloud sync.  At 256 vehicles that Python
orbit bounded round throughput, and warmup compiled one program per (bucket
signature, RSU cohort structure) pair: ~53-58 s before the first round
(BENCH_scenarios.json).

This module restructures the hot path around four ideas:

* **All RSUs execute inside one jitted program.**  Per-RSU cohorts are
  stacked on a leading RSU axis and ``vmap``-ed; membership grouping is one
  on-device segment sort of (serving, cut, vehicle) keys — replacing
  ``np.unique`` + per-RSU boolean indexing while preserving the engine's
  canonical server-update order (ascending cut, then vehicle index, per
  RSU).  The pow2 per-RSU slot capacity plays the role of PR 1's pow2
  bucket signatures: membership churn from mobility/handover only
  reshuffles gather indices, never the compiled program.

* **The cut layer is data, on a flat parameter plane.**  The whole
  ``{units, head}`` pytree is ravelled once into a single (P,) vector with
  a static ``unit_ids`` position→unit map
  (``jax.flatten_util.ravel_pytree``).  A vehicle at cut c owns the
  positions with ``unit_ids < c``; the RSU owns the rest.  Heterogeneous
  cuts, gradient routing, masked optimizer updates, and the unit-wise
  FedAvg become a few fused vector ops, so dynamic cut churn (residence-
  aware SKIP, rate banding) never retraces anything.

* **Two server schedules, one engine.**  ``sequential`` keeps the source
  paper's §III-B semantics — the RSU updates its shared server-side model
  on every client batch, in cohort order — as a loop over slots that
  stops at the round's fullest RSU (client-replica optimizer updates are
  deferred out of that loop and applied vmapped per local step, which is
  the identical math since each replica is touched once per step).
  ``parallel`` implements the companion paper's parallel server-side
  execution (arXiv:2405.18707, "Adaptive and Parallel Split Federated
  Learning in Vehicular Edge Computing"): the RSU consumes the whole
  cohort's smashed batches at once and takes one |D_n|-weighted
  mean-gradient step per local step.  The
  parallel schedule has no sequential inner loop at all — every matmul in
  the round batches across the (RSU, slot) axes, which is what lets fleet-
  scale rounds run at the hardware's batched-matmul throughput instead of
  the tiny-matmul scan throughput (~10x apart on CPU; see DESIGN.md §8).

* **K rounds fuse into one super-step** via ``lax.scan`` over rounds:
  mobility (scenario traced-step path), rate sampling, cut selection,
  batch staging, training, handover tracking, edge aggregation, and the
  periodic cloud merge all live in the scanned round body, with the carry
  (edge-model stack, edge sample counters, previous serving cells, global
  model) donated between super-steps.  The per-round dispatch path is the
  K=1 special case of the same program, which is why K-fused and
  K-sequential execution agree bit-for-bit (tests/test_superstep.py).

Warmup collapses with it: :meth:`SuperStepPrograms.precompile` AOT-lowers
(``.lower().compile()``) every signature a run plan will request, and the
engine wires JAX's persistent compilation cache so warm starts skip XLA
entirely.

What stays in Python, by design: logging, round-metrics assembly, and the
analytic comm/latency/energy accounting — all consume the per-round arrays
the super-step emits as scan outputs, pulled to the host **once per
super-step** instead of several times per round.

**Ragged layout** (DESIGN.md §12, the default): the dense formulation
above pays as if every vehicle held the whole model — full (P,) replicas
plus moments per slot, all client math masked by ``keep``, and pow2/tight8
capacity padding burning full-plane FLOPs on phantom slots.  Because the
plane serializes the head first and then units in ascending order, every
position a vehicle can own at any cut ``c <= c_max`` lives in ONE static
contiguous window of the plane (:func:`owned_window`), where ``c_max`` is
the strategy's static cut bound (:func:`repro.core.adaptive.
strategy_max_cut`) pow2-bucketed into the program signature
(:func:`cut_prefix_bucket` — cut churn stays retrace-free).  With
``superstep_layout="ragged"`` client replicas, client moments, and EF wire
residuals shrink to that prefix window; the sequential schedule truncates
its per-unit replica lists to the bucket; and the parallel schedule
replaces the per-RSU (R, C) padded slot table with a globally compacted
(segment-id, slot) layout from the same on-device sort — client fwd/bwd
vmaps over *occupied* slots only and per-RSU aggregation becomes
segment-sums (scatter-adds into an R+1-row table whose overflow row drops
phantom work).  Segment scatter-adds are left-folds, so a padded slot
contributes an exact ±0 in any position: compacted and dense execution
stay bit-for-bit for sgd on both schedules (tests/test_ragged.py).
``superstep_layout="dense"`` keeps the full-plane masked path.

Caveats: the flat plane requires a uniform parameter dtype (the current
UnitModels are float32 throughout), and a replica is materialized per slot
— the price of making the cut a runtime value.  Memory is
``O(n_rsus * capacity * P)`` for the dense layout, and
``O(occupied_slots * P_prefix)`` for the ragged one.

Wire schemes (DESIGN.md §11): ``cfg.wire`` inserts a compression boundary
at the runtime cut inside the fused forward — ``"int8"`` is the stateless
fake-quant round trip, ``"topk_int8"`` adds per-vehicle error-feedback
residuals carried as two extra slot-table planes (``wire_res``,
``wire_cut``) in the donated scan carry.  Residuals follow the vehicle
(the planes are fleet-indexed and replicated under a mesh), so they
migrate on handover exactly like the data shards; a residual is zeroed
only when the vehicle's cut changes, because the buffer's layout is the
smashed-tensor shape at that cut.  Because the cut is a runtime value,
every unit boundary computes its compressed candidate and a ``where``
selects the one at the cut — under the RSU/slot vmaps a ``lax.cond``
would execute both branches anyway, so the select form is the honest
spelling of that cost (see DESIGN.md §11 for the CPU-interpret numbers).
``wire="none"`` stays byte-identical to the pre-wire engine: every hook
below is gated at Python level, so the traced program is unchanged.

Fault plane (DESIGN.md §13): ``cfg.fault_*`` turns on seeded, fully traced
failure processes from :mod:`repro.core.faults` — mid-round dropout, upload
loss, deadline stragglers (analytic latency at the chosen cut vs
``straggler_factor x residence``), and whole-RSU outages.  Consequences are
computed in-round: outages zero the cohort's cuts before slot grouping;
per-step activity masks stop a dropout's batches after its drop step
(server-side gradients it contributed before dropping stand — they already
landed on the RSU); the unit-wise FedAvg renormalizes over *survivors*
(``aggregation.survivor_weighted_sum`` — failed slots fold in as exact +0);
and straggler client updates land in a staleness bank on the donated carry
(``stale_num``/``stale_den``) that merges next round at a
``staleness_discount``.  Every hook is gated at Python level on
``FaultConfig.stochastic`` (the ``wire="none"`` precedent), so the
zero-fault program is byte-identical and trains bit-for-bit vs a build
without the fault plane — on both schedules, both layouts, and under a
mesh (tests/test_faults.py).

Streaming plane (DESIGN.md §14): ``cfg.stream_*`` adds a seeded presence
process (a per-vehicle Markov toggle chain on the donated carry — see
:mod:`repro.core.streaming`) that gates cut selection on any schedule, and
a third server schedule ``"streaming"`` that rides the parallel machinery
but commits its round update through a ``StreamBuffer`` carry plane: each
RSU's survivor-aggregated cohort delta is pushed into a capacity-B slot
ring (``sbuf``/``sbuf_w``/``sbuf_age``/``sbuf_cnt``), and the edge model
advances only when the buffer reaches B pending deltas, via a
staleness-weighted survivor FedAvg (``streaming.staleness_kernel`` over
slot ages — the FedBuff policy).  Both planes are gated at Python level on
``StreamConfig.churning`` / the schedule flag, so the zero-streaming
program is byte-identical, and all state is carry — presence/buffer churn
is data, never a program signature (tests/test_streaming.py).

Frozen base (split LoRA fine-tuning, ``SplitLoRAUnitModel``): a model with
``frozen_params()`` trains only its adapters on the flat plane, FedAvg and
Adam; its frozen base enters every program as a third, non-donated
argument and is bound to the model (``model.bind``) inside the trace.
Closed over, a multi-GB base would be compiled in as a constant.  Models
without one trace the identical two-argument program.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.flatten_util import ravel_pytree
from jax.sharding import PartitionSpec as PSpec

from repro.core import (adaptive, aggregation, compression, faults,
                        fleet_sharding, streaming)
from repro.core.fleet_sharding import (ALL_AXES, RSU_AXIS, VEH_AXIS,
                                       FleetMesh)
from repro.data.pipeline import StackedClients, fleet_batch_indices_traced
from repro import optim

SERVER_SCHEDULES = ("sequential", "parallel", "streaming")
SUPERSTEP_LAYOUTS = ("ragged", "dense")


def cut_prefix_bucket(c_max: int, n_units: int) -> int:
    """pow2-bucket the strategy's static max cut into the program-signature
    dimension that sizes prefix planes: the smallest power of two >= c_max,
    clipped to U-1 (no vehicle can own the last unit).  Bucketing keeps the
    signature — and therefore the compile cache — stable when a strategy's
    candidate set changes without crossing a power of two."""
    c = max(int(c_max), 1)
    b = 1
    while b < c:
        b *= 2
    return min(b, max(int(n_units) - 1, 1))


def owned_window(unit_ids: np.ndarray, bucket: int):
    """(offset, width) of the contiguous flat-plane window holding every
    position with ``unit_ids < bucket`` — all positions a vehicle can own
    at any cut <= bucket.  Contiguity is a property of the ravel order
    (``ravel_pytree`` sorts dict keys: "head" serializes before "units",
    units ascend), asserted here rather than assumed."""
    ids = np.asarray(unit_ids)
    owned = np.nonzero(ids < int(bucket))[0]
    if owned.size == 0:
        return 0, 0
    off, width = int(owned[0]), int(owned.size)
    if not np.array_equal(owned, np.arange(off, off + width)):
        raise AssertionError(
            "owned plane positions are not contiguous; the ragged layout "
            "requires the ravel order to keep units < bucket adjacent")
    return off, width


def tree_copy(tree):
    """Deep copy device buffers (public views of donated carries must not
    alias buffers a later super-step will consume)."""
    return jax.tree.map(lambda a: jnp.array(a, copy=True), tree)


def _select(mask, new, old):
    """tree_map(where): pick ``new`` where mask else ``old``; the mask
    broadcasts from the left (scalar masks select whole trees)."""
    mask = jnp.asarray(mask)

    def f(a, b):
        m = mask.reshape(mask.shape + (1,) * (a.ndim - mask.ndim))
        return jnp.where(m, a, b)

    return jax.tree.map(f, new, old)


def _sel_list_state(new: Dict, old: Dict, keep_units, act):
    """Per-unit select over an optimizer state whose array collections are
    *lists* mirroring a client replica's unit list (bookkeeping leaves —
    step counts — follow the per-replica ``act`` mask)."""
    out = {}
    for k, v in new.items():
        if isinstance(v, list):
            out[k] = [_select(keep_units[u], v[u], old[k][u])
                      for u in range(len(v))]
        else:
            out[k] = _select(act, v, old[k])
    return out


def _sel_server_state(new: Dict, old: Dict, keep_units, act):
    """Per-unit select over the server optimizer state (leaves mirror the
    ``{"units": [...], "head": ...}`` tree)."""
    out = {}
    for k, v in new.items():
        if isinstance(v, dict) and "units" in v:
            out[k] = {"units": [_select(keep_units[u], v["units"][u],
                                        old[k]["units"][u])
                                for u in range(len(v["units"]))],
                      "head": _select(act, v["head"], old[k]["head"])}
        else:
            out[k] = _select(act, v, old[k])
    return out


def _sel_flat_state(keep, act, new, old, params_shape):
    """Select a flat-plane optimizer state: leaves shaped like the (flat)
    parameters follow the per-position ``keep`` mask, bookkeeping leaves
    (step counts) follow ``act``."""
    def f(a, b):
        if a.shape == tuple(params_shape):
            return jnp.where(keep, a, b)
        return jnp.where(act, a, b)

    return jax.tree.map(f, new, old)


@dataclasses.dataclass(frozen=True)
class SuperStepSignature:
    """Static compile-cache key of one fused program."""
    k: int            # rounds fused into the scan
    capacity: int     # pow2 per-RSU slot capacity
    staged: bool      # True: mobility staged per-window on the host
    # compacted global slot capacity (ragged layout + parallel schedule:
    # bucketed max TOTAL covered count; 0 = dense per-RSU padded tables)
    slots: int = 0
    # pow2-bucketed static max cut sizing the prefix planes (0 = dense
    # layout, full plane)
    max_cut: int = 0


class SuperStepPrograms:
    """Builds, caches, and AOT-precompiles fused super-step programs for one
    (model, config, fleet, scenario) tuple.  ``ScenarioEngine`` owns one.

    ``compile_fallbacks`` counts programs that had to be built outside
    :meth:`precompile` — zero after a covering precompile means no silent
    mid-run recompiles (asserted in tests/test_superstep.py)."""

    def __init__(self, model, cfg, stacked: StackedClients,
                 lengths: np.ndarray, scenario, n_rsus: int,
                 cloud_sync_every: int, profile, nb: int, ep: int,
                 mesh: Optional[FleetMesh] = None):
        self.model = model
        self.cfg = cfg
        self.opt = optim.from_name(cfg.optimizer, cfg.lr)
        self.schedule = getattr(cfg, "server_schedule", "sequential")
        if self.schedule not in SERVER_SCHEDULES:
            raise ValueError(f"server_schedule must be one of "
                             f"{SERVER_SCHEDULES}, got {self.schedule!r}")
        # RSU-axis mesh (core/fleet_sharding.py, DESIGN.md §10): the RSU
        # axis is padded to a device multiple (phantom cells no vehicle is
        # served by — inert, they never accumulate samples) and sharded;
        # the master client tensors replicate (handover makes per-round
        # gathers cross-shard by design); everything fleet-wide (mobility,
        # cuts, the slot table, the global model) is computed replicated
        self.mesh = mesh
        self.n_rsus_padded = mesh.pad(n_rsus) if mesh is not None else n_rsus
        self.stacked = stacked if mesh is None else mesh.place_stacked(stacked)
        self.lengths = np.asarray(lengths, np.int64)
        self.scenario = scenario
        self.n_rsus = n_rsus
        self.n_vehicles = int(len(lengths))
        self.sync_every = cloud_sync_every
        self.profile = profile
        self.nb, self.ep = nb, ep
        self.steps = nb * ep
        self.fa = scenario.fleet_arrays
        self._programs: Dict[SuperStepSignature, Callable] = {}
        self._unravel: Optional[Callable] = None   # flat global -> tree
        self.compile_fallbacks = 0
        self.traced_mobility = hasattr(scenario, "traced_fleet_state")
        # the flat parameter plane: one (P,) vector for {units, head}, plus
        # the static position->unit map that makes the cut a runtime value
        units, head = model.init(jax.random.PRNGKey(cfg.seed))
        template = {"units": list(units), "head": head}
        flat, self.unravel = ravel_pytree(template)
        if flat.dtype != jnp.float32:
            raise TypeError(
                f"superstep engine requires uniform float32 params, got "
                f"{flat.dtype} after ravel")
        self.n_params = int(flat.size)
        # programs global_model() enqueues per call: a copy per leaf of the
        # global tree (sequential) or one unravel of the flat plane
        self.global_model_programs = (len(jax.tree.leaves(template))
                                      if self.schedule == "sequential"
                                      else 1)
        ids = {"units": [jax.tree.map(
            lambda a, _u=u: np.full(np.shape(a), _u, np.int32), ut)
            for u, ut in enumerate(units)],
            "head": jax.tree.map(
                lambda a: np.full(np.shape(a), model.n_units, np.int32),
                head)}
        self.unit_ids = ravel_pytree(ids)[0].astype(jnp.int32)
        self.unit_ids_np = np.asarray(self.unit_ids)
        # ragged layout (DESIGN.md §12): client planes/moments/EF residuals
        # are sized to the static max-cut prefix — the pow2 bucket of the
        # strategy's cut bound — which is one contiguous window of the
        # plane (head serializes first, then units ascending)
        self.layout = getattr(cfg, "superstep_layout", "ragged")
        if self.layout not in SUPERSTEP_LAYOUTS:
            raise ValueError(f"superstep_layout must be one of "
                             f"{SUPERSTEP_LAYOUTS}, got {self.layout!r}")
        if self.layout == "ragged":
            c_max = adaptive.strategy_max_cut(cfg.adaptive_strategy,
                                              model.n_units)
            self.max_cut_bucket = cut_prefix_bucket(c_max, model.n_units)
            self.plane_offset, self.plane_width = owned_window(
                self.unit_ids_np, self.max_cut_bucket)
            self.client_units = self.max_cut_bucket
        else:
            self.max_cut_bucket = 0
            self.plane_offset, self.plane_width = 0, self.n_params
            self.client_units = model.n_units
        # wire boundary geometry: the smashed-tensor shape at every cut
        # (1..U-1), from one eval_shape of the per-unit forward.  The EF
        # residual plane holds the LARGEST boundary flattened — one slot
        # per vehicle, reinterpreted in the shape of its current cut.
        # Ragged layout: cuts never exceed the bucket, so only boundaries
        # below it ever carry a residual — the plane shrinks accordingly
        self.wire = getattr(cfg, "wire", "none")
        self.wire_k = float(getattr(cfg, "wire_k", compression.WIRE_K))
        self.ef = self.wire == "topk_int8"
        if self.wire != "none":
            x_sds = jax.ShapeDtypeStruct(
                (cfg.batch_size,) + tuple(self.stacked.images.shape[2:]),
                self.stacked.images.dtype)

            def _stack_shapes(x):
                h, outs = x, []
                for u in range(model.n_units - 1):
                    h = model.apply_units([units[u]], h, u)
                    outs.append(h)
                return outs

            sds = jax.eval_shape(_stack_shapes, x_sds)
            self.boundary_shapes = [tuple(s.shape) for s in sds]
            self.wire_units = (min(model.n_units - 1, self.max_cut_bucket)
                               if self.layout == "ragged"
                               else model.n_units - 1)
            self.res_size = max(int(np.prod(s))
                                for s in self.boundary_shapes
                                [:self.wire_units])
        else:
            self.boundary_shapes, self.res_size = None, 0
            self.wire_units = 0
        # fault plane (DESIGN.md §13): every hook below is gated at Python
        # level on `fz`, so a zero-fault config traces the identical program
        self.faults = (cfg.fault_config() if hasattr(cfg, "fault_config")
                       else faults.FaultConfig())
        if self.faults.coverage:
            raise ValueError(
                "fault coverage (the legacy single-RSU mobility_dropout "
                "in-range test) does not apply to the multi-RSU super-step "
                "engine: scenarios model coverage through serving_rsu == -1")
        self.fz = self.faults.stochastic
        # streaming plane (DESIGN.md §14): presence churn (`cz`) gates any
        # schedule; the StreamBuffer (`sz`) belongs to schedule="streaming"
        self.stream = (cfg.stream_config() if hasattr(cfg, "stream_config")
                       else streaming.StreamConfig())
        self.cz = self.stream.churning
        self.sz = self.schedule == "streaming"
        # frozen base (split LoRA fine-tuning): a pytree every program
        # takes as a non-donated argument, bound to the model inside the
        # trace — as a closed-over constant a multi-GB base would be
        # compiled into the program.  None for models without one: their
        # programs take no such argument
        self.frozen = (model.frozen_params()
                       if hasattr(model, "frozen_params") else None)
        if self.frozen is not None:
            if self.wire != "none":
                raise ValueError("a frozen-base model trains without a "
                                 "wire scheme (wire='none')")
            if mesh is not None:
                self.frozen = mesh.replicate(self.frozen)
        self.frozen_args = () if self.frozen is None else (self.frozen,)
        # a model's per-batch counter (its head loss's aux output), summed
        # per round into the outputs by the sequential schedule
        self.aux_counter = (getattr(model, "aux_counter", None)
                            if self.schedule == "sequential" else None)

    def flatten(self, units, head) -> jnp.ndarray:
        return ravel_pytree({"units": list(units), "head": head})[0]

    def make_carry(self, units, head, n_vehicles: int):
        """Fresh super-step carry for the engine's schedule.  Every buffer
        belongs to the carry alone (the whole carry is donated to each
        dispatch); the sequential schedule keeps pytree edges, the parallel
        schedule keeps the flat plane.  Under a mesh the edge stack is
        placed sharded over the RSU axis and the rest replicated, matching
        the ``shard_map`` specs so donation reuses the sharded buffers."""
        R = self.n_rsus_padded
        if self.schedule == "sequential":
            stackR = lambda t: jax.tree.map(
                lambda a: jnp.broadcast_to(a, (R,) + a.shape), t)
            edge = {"units": [stackR(u) for u in units],
                    "head": stackR(head)}
            glob = tree_copy({"units": list(units), "head": head})
        else:
            flat = self.flatten(units, head)
            edge = jnp.broadcast_to(flat, (R, self.n_params))
            glob = jnp.array(flat, copy=True)
        carry = {"edge": edge,
                 "samples": jnp.zeros((R,), jnp.float32),
                 "prev": jnp.full((n_vehicles,), -1, jnp.int32),
                 "global": glob}
        if self.ef:
            # error-feedback planes (wire="topk_int8"): per-vehicle
            # residual buffer + the cut it was accumulated at (-1 = never
            # trained; a cut change invalidates the buffer's layout)
            carry["wire_res"] = jnp.zeros((n_vehicles, self.res_size),
                                          jnp.float32)
            carry["wire_cut"] = jnp.full((n_vehicles,), -1, jnp.int32)
        if self.fz:
            # staleness bank (DESIGN.md §13): last round's deadline-
            # straggler client updates, banked per RSU as a weighted
            # numerator (sequential: per-unit trees; parallel: the owned
            # prefix window of the flat plane) plus the per-unit banked
            # weight, merged next round at the staleness discount
            CU = self.client_units
            if self.schedule == "sequential":
                carry["stale_num"] = [
                    jax.tree.map(
                        lambda a: jnp.zeros((R,) + a.shape, jnp.float32),
                        units[u])
                    for u in range(CU)]
            else:
                carry["stale_num"] = jnp.zeros((R, self.plane_width),
                                               jnp.float32)
            carry["stale_den"] = jnp.zeros((R, CU), jnp.float32)
        if self.cz:
            # presence plane (DESIGN.md §14): the Markov toggle chain's
            # state — all vehicles start present; churn flips bits in-round
            carry["present"] = jnp.ones((n_vehicles,), bool)
        if self.sz:
            # StreamBuffer (DESIGN.md §14): per-RSU ring of B pending
            # cohort deltas on the flat plane, their merge weights, their
            # ages in rounds, and the fill count.  Per-RSU state: it shards
            # with the edge stack (and replicates when the edge does)
            B = int(self.stream.buffer_size)
            carry["sbuf"] = jnp.zeros((R, B, self.n_params), jnp.float32)
            carry["sbuf_w"] = jnp.zeros((R, B), jnp.float32)
            carry["sbuf_age"] = jnp.zeros((R, B), jnp.int32)
            carry["sbuf_cnt"] = jnp.zeros((R,), jnp.int32)
        if self.mesh is not None:
            if self.schedule != "sequential" and self.layout == "ragged":
                # ragged + parallel/streaming shards the compacted SLOT
                # axis, not the RSU axis: every device owns a block of
                # occupied slots of arbitrary RSUs, so the edge stack must
                # be replicated (the per-RSU segment-sums come home via
                # psum)
                carry = {k: self.mesh.replicate(v) for k, v in carry.items()}
            else:
                # the staleness bank and stream buffer are per-RSU state
                # and shard with the edge stack
                for k in carry:
                    if k in ("edge", "stale_num", "stale_den", "sbuf",
                             "sbuf_w", "sbuf_age", "sbuf_cnt"):
                        carry[k] = self.mesh.shard_leading(carry[k])
                    else:
                        carry[k] = self.mesh.replicate(carry[k])
        return carry

    def global_model(self, carry):
        """(units, head) view of the carry's global model, in fresh buffers
        callers may hold across the next (donating) dispatch.  The flat
        plane's unravel runs as the program :meth:`precompile` built, so a
        window's host side compiles nothing."""
        if self.schedule == "sequential":
            g = tree_copy(carry["global"])
        else:
            if self._unravel is None:
                self.compile_fallbacks += 1
                self._unravel = jax.jit(self.unravel)
            g = self._unravel(carry["global"])
        return list(g["units"]), g["head"]

    def slot_trips(self, counts, capacity: int) -> np.ndarray:
        """(rounds, RSU-axis shards) trip counts of the sequential slot
        chain, from a window's pulled per-RSU ``counts``: each shard runs
        its slot loop for its fullest RSU's load, at most ``capacity``
        (the value the program computes from the same counts)."""
        c = np.minimum(np.asarray(counts), int(capacity))
        shards = 1 if self.mesh is None else self.mesh.rsu_devices
        return c.reshape(c.shape[0], shards, -1).max(axis=-1)

    # ---- program construction ----------------------------------------
    def _build(self, sig: SuperStepSignature):
        model, cfg, opt = self.model, self.cfg, self.opt
        U = model.n_units
        R, C, n = self.n_rsus_padded, sig.capacity, self.n_vehicles
        fm = self.mesh
        R_loc = R if fm is None else R // fm.rsu_devices
        dv = 1 if fm is None else fm.veh_devices
        P = self.n_params
        steps, batch = self.steps, cfg.batch_size
        interval = float(cfg.round_interval_s)
        sync_every = self.sync_every
        nb, ep = self.nb, self.ep
        sc = self.scenario
        unravel = self.unravel
        unit_ids = self.unit_ids
        images, labels = self.stacked.images, self.stacked.labels
        lengths_dev = jnp.asarray(self.lengths, jnp.int32)
        lengths_f = jnp.asarray(self.lengths, jnp.float32)
        flops = jnp.asarray(self.fa["compute_flops"], jnp.float32)
        base_key = jax.random.PRNGKey(cfg.seed)
        fading_key = jax.random.PRNGKey(cfg.seed ^ 0x5EED5EED)
        strategy = cfg.adaptive_strategy
        slot_ids = jnp.arange(C, dtype=jnp.int32)
        wire, ef, wire_k = self.wire, self.ef, self.wire_k
        bshapes, res_size = self.boundary_shapes, self.res_size
        wire_units = self.wire_units
        cr = self.aux_counter is not None
        # fault-plane statics (DESIGN.md §13): gated at Python level on
        # `fz` throughout — zero-fault configs trace the identical program
        fc, fz = self.faults, self.fz
        disc = float(fc.staleness_discount)
        # streaming-plane statics (DESIGN.md §14): gated at Python level on
        # `cz` (presence churn) and `sz` (the streaming schedule's buffer)
        stc, cz, sz = self.stream, self.cz, self.sz
        B = int(stc.buffer_size)
        # ragged layout statics (DESIGN.md §12): the owned-prefix window of
        # the plane, the per-replica unit count (sequential), and the flat
        # slot-axis geometry (parallel).  Dense: window = whole plane,
        # CU = U, and the flat axis is the flattened (R, C) table
        layout = self.layout
        ragged_par = self.schedule != "sequential" and layout == "ragged"
        O, W = self.plane_offset, self.plane_width
        CU = self.client_units
        unit_ids_w = unit_ids[O:O + W]
        S = sig.slots if ragged_par else R * C
        C_loc = C if fm is None else C // dv
        # dense2d: the grid mesh splits each RSU's dense slot row into
        # vehicle-axis column blocks; segment-sums regroup through an
        # order-restoring all-gather (DESIGN.md §15)
        dense2d = (fm is not None and layout == "dense"
                   and self.schedule != "sequential" and dv > 1)
        if fm is not None and layout == "dense" and C % dv != 0:
            raise ValueError(
                f"dense slot capacity {C} must divide over the vehicle "
                f"sub-axis ({dv} devices); pad it with "
                f"FleetMesh.pad_slots upstream")
        paged, n_pages, page = False, 1, int(getattr(cfg, "page_slots", 0))
        if self.schedule != "sequential":
            if fm is None:
                S_loc, R_srv, psum_out = S, R, False
            elif layout == "dense":
                # RSU-aligned slot blocks: device (i, j)'s slots are its
                # R_loc RSU rows x its C_loc slot columns.  With dv == 1
                # segment-sums stay shard-local and the PR 5 bit-for-bit
                # all-gather combine applies unchanged; with dv > 1 the
                # per-RSU sums regroup over the vehicle axis first
                S_loc, R_srv, psum_out = R_loc * C_loc, R_loc, False
            else:
                # compacted slots shard by occupancy over the WHOLE device
                # grid: blocks of occupied slots, RSUs interleaved —
                # per-RSU sums are psum'd partials
                S_loc, R_srv, psum_out = S // fm.n_devices, R, True
            # slot-capacity paging (DESIGN.md §15): when the planned
            # compacted block exceeds the per-device concurrent window,
            # each local step sweeps the slots in fixed `page`-slot
            # windows instead of one S_loc-wide vmap — peak activation
            # memory is set by page_slots, while the slot axis (and the
            # program signature) tracks the planned capacity.  Ragged
            # parallel/streaming only: the dense grid's bit-exact regroup
            # needs the whole (R_loc, C) row in flight
            if ragged_par and page > 0 and S_loc > page:
                if S_loc % page:
                    raise ValueError(
                        f"page_slots={page} must divide the per-device "
                        f"compacted slot block {S_loc} (signature() pads "
                        f"planned slots to a page multiple — pass slots "
                        f"through SuperStepPrograms.signature)")
                paged, n_pages = True, S_loc // page

        def pick_cuts(serving, rates, residence):
            """(n,) int32 cuts, 0 = SKIP/uncovered (traced twin of the PR 2
            host strategy dispatch)."""
            if strategy in ("paper", "paper-literal"):
                cuts = adaptive.paper_threshold_traced(
                    rates, literal_eq3=(strategy == "paper-literal"))
            else:  # "residence" (validated by ScenarioEngine.__init__)
                cuts = adaptive.residence_aware_traced(
                    self.profile, jnp.maximum(rates, 1.0), flops,
                    cfg.server_flops, nb, batch, ep, residence)
            sched = cuts > 0
            cuts = jnp.where(sched, jnp.clip(cuts, 1, U - 1), 0)
            return jnp.where(serving >= 0, cuts, 0).astype(jnp.int32)

        def slot_sort(serving, cuts):
            """On-device segment grouping: one sort of (serving, cut,
            vehicle) keys.  Replaces the host-side ``np.unique`` + boolean
            indexing, preserving the ascending (cut, vehicle) server-update
            order per RSU.  Unscheduled vehicles get segment R (past every
            real RSU), so they sort to the tail."""
            sched = cuts > 0
            seg = jnp.where(sched, serving, R).astype(jnp.int32)
            key = seg * (U * n) + cuts * n + jnp.arange(n, dtype=jnp.int32)
            order = jnp.argsort(key).astype(jnp.int32)
            counts = jnp.sum(seg[None, :] == jnp.arange(R, dtype=jnp.int32)
                             [:, None], axis=1).astype(jnp.int32)
            return order, seg, counts

        def slot_table_seq(order, counts):
            """Per-RSU (R, C) member slots for the sequential schedule."""
            starts = jnp.concatenate(
                [jnp.zeros((1,), jnp.int32), jnp.cumsum(counts)[:-1]])
            flat = jnp.clip(starts[:, None] + slot_ids[None, :], 0, n - 1)
            members = order[flat]                        # (R, C)
            mask = slot_ids[None, :] < counts[:, None]   # (R, C)
            return members, mask

        def slot_table_flat(order, seg, counts):
            """Flat (S,) slot table for the parallel schedule: ``members``
            (vehicle per slot) and ``slot_seg`` (serving RSU per slot, R =
            phantom/parked — scatter contributions to row R are dropped).

            Ragged: slots are the sorted order's prefix — globally
            compacted, RSU-major, zero phantom slots between cohorts.
            Dense: the flattened (R, C) padded table, so the occupied slots
            appear in the IDENTICAL global order as the ragged table and
            the two layouts differ only by exact-zero phantom
            contributions (the bit-for-bit parity argument)."""
            if ragged_par:
                seg_sorted = seg[order]
                if S <= n:
                    return order[:S], seg_sorted[:S]
                pad = S - n
                members = jnp.concatenate(
                    [order, jnp.zeros((pad,), jnp.int32)])
                slot_seg = jnp.concatenate(
                    [seg_sorted, jnp.full((pad,), R, jnp.int32)])
                return members, slot_seg
            members2d, mask2d = slot_table_seq(order, counts)
            rows = jnp.repeat(jnp.arange(R, dtype=jnp.int32), C)
            slot_seg = jnp.where(mask2d.reshape(-1), rows,
                                 R).astype(jnp.int32)
            return members2d.reshape(-1), slot_seg

        def loss_fn(units, head, x, y):
            feats = model.apply_units(units, x, 0)
            loss, logits = model.head_loss(head, feats, y)
            return loss, logits

        def wire_loss(units, head, x, y, cut_j, res_j):
            """Forward with the wire boundary at the runtime cut ``cut_j``.

            The cut is data, so every unit boundary computes its compressed
            candidate and a ``where`` keeps the one at the cut (under the
            RSU/slot vmaps a cond would run both branches anyway).  For
            ``topk_int8`` the slot's residual buffer ``res_j`` is
            reinterpreted in the active boundary's smashed shape, added
            before top-k (error feedback), and the un-sent remainder comes
            back as the aux output; gradients cross the boundary through
            the scheme's custom_vjp (the compressed downlink)."""
            h, r = x, res_j
            for u in range(U - 1):
                h = model.apply_units([units[u]], h, u)
                if u >= wire_units:
                    # ragged layout: cuts never exceed the bucket, so
                    # boundaries at or past it can never be selected —
                    # skipping their candidates changes no selected value
                    continue
                is_b = cut_j == (u + 1)
                with jax.named_scope("asfl.wire"):
                    if ef:
                        sz = int(np.prod(bshapes[u]))
                        yb, r2 = compression.wire_boundary(
                            h, res_j[:sz].reshape(bshapes[u]), wire_k)
                        r = jnp.where(is_b,
                                      jnp.pad(r2.reshape(-1),
                                              (0, res_size - sz)), r)
                    else:
                        yb = compression.quant_boundary(h)
                    h = jnp.where(is_b, yb, h)
            feats = model.apply_units([units[U - 1]], h, U - 1)
            loss, _ = model.head_loss(head, feats, y)
            return loss, (r if ef else jnp.zeros((0,), jnp.float32))

        # ---- sequential schedule (paper §III-B: the RSU consumes the
        # cohort's smashed batches one at a time, in slot order) ---------
        def seq_slot_body(carry, inp):
            """One client batch at one slot: the full unit stack, with the
            units before the slot's cut taken from its replica and the rest
            from the RSU copy.  Only the RSU state mutates here; client
            gradients stream out as loop outputs and are applied vmapped
            after the slot loop (each replica is touched once per step, so
            deferring its update out of the sequential body is identical
            math at a fraction of the op count)."""
            sv, so = carry
            if ef:
                cu_j, m_j, cut_j, act, idx_j, res_j = inp
            else:
                cu_j, m_j, cut_j, act, idx_j = inp
            with jax.named_scope("asfl.batch"):
                x = images[m_j][idx_j]
                y = labels[m_j][idx_j]
            # units at or past the max-cut bucket have no client replica in
            # the ragged layout (CU < U): no cut can reach them, so the
            # server copy is the effective parameter unconditionally — the
            # same value the dense select produces (its replica is never
            # updated there), hence bit-for-bit across layouts
            with jax.named_scope("asfl.server_update"):
                eff = [_select(u < cut_j, cu_j[u], sv["units"][u])
                       for u in range(CU)] \
                    + [sv["units"][u] for u in range(CU, U)]
            with jax.named_scope("asfl.grad"):
                if wire == "none":
                    (loss, aux), (g_units, g_head) = jax.value_and_grad(
                        loss_fn, argnums=(0, 1), has_aux=True)(
                            eff, sv["head"], x, y)
                else:
                    (loss, res_new), (g_units, g_head) = jax.value_and_grad(
                        wire_loss, argnums=(0, 1), has_aux=True)(
                            eff, sv["head"], x, y, cut_j,
                            res_j if ef else None)
            with jax.named_scope("asfl.server_update"):
                keep_s = [act & (u >= cut_j) for u in range(U)]
                g_sv = {"units": [_select(u >= cut_j, g_units[u],
                                          jax.tree.map(jnp.zeros_like,
                                                       g_units[u]))
                                  for u in range(U)],
                        "head": g_head}
                upd, so2 = opt.update(g_sv, so, sv)
                sv2 = optim.apply_updates(sv, upd)
                sv3 = {"units": [_select(keep_s[u], sv2["units"][u],
                                         sv["units"][u]) for u in range(U)],
                       "head": _select(act, sv2["head"], sv["head"])}
                so3 = _sel_server_state(so2, so, keep_s, act)
                ys = (list(g_units[:CU]), jnp.where(act, loss, 0.0))
                if cr:
                    ys = ys + (jnp.where(act, aux, 0),)
            if ef:
                with jax.named_scope("asfl.wire"):
                    ys = ys + (jnp.where(act, res_new, res_j),)
            return (sv3, so3), ys

        def slot_loop(trip, sv, so, xs):
            """The slot chain of one local step over this shard's RSUs (the
            leading axis of ``sv``/``so``, then of ``xs`` ahead of the slot
            axis): ``seq_slot_body``, vmapped over the RSUs, for slots
            ``[0, trip)`` in order, each slot's outputs written into
            buffers of the whole slot axis.  ``trip`` is the fullest RSU's
            load; a slot at or past it is padding in every RSU, and its
            rows keep what a masked slot would emit — a zero client
            gradient (discarded by the keep-selects), a zero loss, a zero
            count, and the incoming EF residual."""
            ys0 = (jax.tree.map(jnp.zeros_like, xs[0]),
                   jnp.zeros(xs[3].shape, jnp.float32))
            if cr:
                ys0 = ys0 + (jnp.zeros(xs[3].shape, jnp.int32),)
            if ef:
                ys0 = ys0 + (xs[-1],)
            body_r = jax.vmap(seq_slot_body)

            def body(j, c):
                st, ys = c
                st, y = body_r(st, jax.tree.map(
                    lambda a: lax.dynamic_index_in_dim(a, j, 1, False), xs))
                return st, jax.tree.map(
                    lambda b, v: lax.dynamic_update_index_in_dim(b, v, j, 1),
                    ys, y)

            return lax.fori_loop(0, trip, body, ((sv, so), ys0))

        def seq_merge(edge_tree, sv, cu, members, cut_slots, w_slots,
                      *fz_planes):
            """One RSU's unit-wise FedAvg at the end of its round — vmapped
            across the RSU axis.  ``fz_planes`` (when ``fz``): survivor
            slots, straggler slots, and the incoming staleness bank."""
            if fz:
                surv_slots, strag_slots, st_num_in, st_den_in = fz_planes
                # survivor weights (DESIGN.md §13): a dropped / lost /
                # straggling slot's client update folds into the FedAvg as
                # an exact +0 and the denominator renormalizes over the
                # survivors.  Server-side contributions stand for every
                # in-round-active slot — those gradients already landed on
                # the RSU's own copy
                w_merge = lengths_f[members] * surv_slots
                w_bank = lengths_f[members] * strag_slots
            else:
                w_merge = w_slots
            w_total = jnp.sum(w_merge)
            den = jnp.maximum(w_total, 1.0)
            merged, st_num_out, st_den_out = [], [], []
            for u in range(U):
                if u >= CU:
                    # no replica exists past the bucket: every slot's
                    # weight lands on the server copy, so the unit-wise
                    # FedAvg collapses to (w_total * sv) / den — the value
                    # the dense path computes through its all-zero client
                    # weights
                    merged.append(jax.tree.map(
                        lambda s, ref: jnp.where(
                            w_total > 0.0,
                            ((w_total * s.astype(jnp.float32))
                             / den).astype(ref.dtype), ref),
                        sv["units"][u], edge_tree["units"][u]))
                    continue
                w_u = w_slots * (cut_slots > u)
                if fz:
                    # survivor-weighted numerator + last round's staleness
                    # bank at the discount; den_u can sit in (0, 1) when
                    # only discounted bank weight remains, so the guard is
                    # a where, not a max
                    num = aggregation.survivor_weighted_sum(
                        cu[u], w_u, surv_slots)
                    swu = w_total - jnp.sum(w_merge * (cut_slots > u))
                    den_u = w_total + disc * st_den_in[u]
                    den_safe = jnp.where(den_u > 0.0, den_u, 1.0)
                    num = jax.tree.map(
                        lambda nm, s, st: (nm + swu * s.astype(jnp.float32)
                                           + disc * st),
                        num, sv["units"][u], st_num_in[u])
                    merged.append(jax.tree.map(
                        lambda nm, ref: jnp.where(
                            den_u > 0.0,
                            (nm / den_safe).astype(ref.dtype), ref),
                        num, edge_tree["units"][u]))
                    # this round's bank: straggler replicas fold with the
                    # same exact-+0 masking and merge NEXT round
                    st_num_out.append(aggregation.survivor_weighted_sum(
                        cu[u], w_u, strag_slots))
                    st_den_out.append(jnp.sum(w_bank * (cut_slots > u)))
                    continue
                swu = w_total - jnp.sum(w_u)
                num = aggregation.stacked_weighted_sum(cu[u], w_u)
                num = jax.tree.map(
                    lambda nm, s: nm + swu * s.astype(jnp.float32),
                    num, sv["units"][u])
                merged.append(jax.tree.map(
                    lambda nm, ref: jnp.where(
                        w_total > 0.0, (nm / den).astype(ref.dtype), ref),
                    num, edge_tree["units"][u]))
            out = {"units": merged, "head": sv["head"]}
            if fz:
                return out, w_total, st_num_out, jnp.stack(st_den_out)
            return out, w_total

        def rsu_round_seq(trip, edge_tree, members, mask, cut_slots,
                          idx_steps, *extra):
            """The whole round (replica init, every local step, unit-wise
            FedAvg) of this shard's RSUs with the sequential server
            schedule.  Every argument leads with the RSU axis, except
            ``idx_steps`` (steps, R, C, batch) and the per-step activity,
            which lead with the local step; ``trip`` is the slot chain's
            trip count, shared by the RSUs.  The per-RSU pieces are
            vmapped, and the slot loop runs outside them, so it indexes a
            slot of the (RSU, slot) arrays directly.  Params stay in pytree
            form here: the sequential slot loop is dominated by per-slot
            tree math, and ravelling in/out of the flat plane per round
            measurably loses to plain trees on CPU.

            ``extra`` packs the statically gated optional planes, in order:
            the EF residual slots (when ``ef``), then the fault planes
            (when ``fz``): per-step slot activity (steps, R, C), survivor
            slots, straggler slots, and the incoming staleness bank."""
            i = 0
            if ef:
                res_slots = extra[0]
                i = 1
            if fz:
                act_steps, *fz_planes = extra[i:]
            sv = {"units": list(edge_tree["units"]),
                  "head": edge_tree["head"]}
            with jax.named_scope("asfl.server_update"):
                so = jax.vmap(opt.init)(sv)
            # ragged layout: replicas exist only for the CU units a cut can
            # reach — the per-slot memory and deferred-update math shrink
            # to the owned prefix
            with jax.named_scope("asfl.client_update"):
                cu = [jax.tree.map(
                    lambda a: jnp.broadcast_to(
                        a[:, None], (a.shape[0], C) + a.shape[1:]), u)
                    for u in edge_tree["units"][:CU]]
                co = jax.vmap(jax.vmap(opt.init))(cu)
            with jax.named_scope("asfl.merge"):
                w_slots = lengths_f[members] * mask      # (R, C)
            if not fz:
                with jax.named_scope("asfl.client_update"):
                    keep_cu = [mask & (cut_slots > u) for u in range(CU)]

            def step_body(carry, x_s):
                if fz:
                    idx_s, act_s = x_s
                else:
                    idx_s, act_s = x_s, mask
                if ef:
                    sv, so, cu, co, res = carry
                    xs = (cu, members, cut_slots, act_s, idx_s, res)
                else:
                    sv, so, cu, co = carry
                    xs = (cu, members, cut_slots, act_s, idx_s)
                # the loop's own work (each slot's replica sliced in, its
                # client gradient stacked out) is the deferred client update
                with jax.named_scope("asfl.client_update"):
                    (sv, so), ys = slot_loop(trip, sv, so, xs)
                g_cu, losses = ys[:2]
                if ef:
                    res = ys[-1]
                with jax.named_scope("asfl.client_update"):
                    upd_c, co2 = jax.vmap(jax.vmap(opt.update))(g_cu, co, cu)
                    cu2 = optim.apply_updates(cu, upd_c)
                    # a dropout's replica stops updating at its drop step
                    # (per-step keep); the zero-fault path keeps the
                    # hoisted masks
                    keep_s = ([act_s & (cut_slots > u) for u in range(CU)]
                              if fz else keep_cu)
                    cu = [_select(keep_s[u], cu2[u], cu[u])
                          for u in range(CU)]
                    co = _sel_list_state(co2, co, keep_s,
                                         jnp.asarray(act_s))
                    out = (sv, so, cu, co, res) if ef else (sv, so, cu, co)
                    per_step = (jnp.sum(losses, axis=1),
                                jnp.sum(act_s.astype(jnp.float32), axis=1))
                    if cr:
                        per_step = per_step + (jnp.sum(ys[2]),)
                    return out, per_step

            init = (sv, so, cu, co, res_slots) if ef else (sv, so, cu, co)
            xs_steps = (idx_steps, act_steps) if fz else idx_steps
            (sv, so, cu, co, *res_t), (ls, cs, *counted) = lax.scan(
                step_body, init, xs_steps,
                unroll=min(steps, 2))
            with jax.named_scope("asfl.merge"):
                merged = jax.vmap(seq_merge)(
                    edge_tree, sv, cu, members, cut_slots, w_slots,
                    *(fz_planes if fz else ()))
                rets = [merged[0], jnp.sum(ls, axis=0), jnp.sum(cs, axis=0),
                        merged[1]]
                if ef:
                    rets.append(res_t[0])
                rets += list(merged[2:])
                if cr:
                    rets.append(jnp.sum(counted[0]))
                return tuple(rets)

        # ---- parallel schedule (arXiv:2405.18707: the RSUs execute the
        # cohorts' server-side passes in parallel and take one weighted
        # mean-gradient step per local step) ------------------------------
        def par_slot_grad(cu_j, cut_j, m_j, idx_j, sv_j, res_j=None):
            with jax.named_scope("asfl.batch"):
                x = images[m_j][idx_j]
                y = labels[m_j][idx_j]
            # the effective plane: the slot's prefix replica where owned,
            # the serving RSU's plane elsewhere.  Dense layout: O = 0 and
            # W = P, so this is the old full-plane select verbatim
            with jax.named_scope("asfl.server_update"):
                own = jnp.where(unit_ids_w < cut_j, cu_j, sv_j[O:O + W])
                if O > 0 or O + W < P:
                    plane = jnp.concatenate([sv_j[:O], own, sv_j[O + W:]])
                else:
                    plane = own
                eff = unravel(plane)
            with jax.named_scope("asfl.grad"):
                if wire == "none":
                    (loss, _), (g_units, g_head) = jax.value_and_grad(
                        loss_fn, argnums=(0, 1), has_aux=True)(
                            eff["units"], eff["head"], x, y)
                else:
                    (loss, res_new), (g_units, g_head) = jax.value_and_grad(
                        wire_loss, argnums=(0, 1), has_aux=True)(
                            eff["units"], eff["head"], x, y, cut_j, res_j)
                g = ravel_pytree({"units": list(g_units),
                                  "head": g_head})[0]
            if ef:
                return g, loss, res_new
            return g, loss

        def fleet_round_par(edge_stack_in, cuts, members_l, slot_seg_l,
                            idx_slots_l, *extra):
            """The whole fleet's round over ONE flat slot axis: vmapped
            client fwd/bwd over this shard's ``S_loc`` slots, per-RSU
            aggregation as segment-sums.  Both layouts run this code — they
            differ only in the slot table handed in (compacted occupied
            slots vs the flattened padded (R, C) grid).  Segment scatter-
            adds fold left from +0, so the dense table's phantom slots
            (segment R, dropped row; exact-zero weights) are bitwise
            neutral — the bit-for-bit layout-parity argument
            (tests/test_ragged.py).

            ``extra`` packs the statically gated optional planes, in order:
            the EF residual slots (when ``ef``), then the fault planes
            (when ``fz``): per-step slot activity (steps, S_loc), survivor
            slots, straggler slots, and the incoming staleness bank
            ((R_srv, W) numerator plane + (R_srv, CU) per-unit weight)."""
            i = 0
            if ef:
                res_slots_l = extra[0]
                i = 1
            if fz:
                (act_slots_l, surv_sl, strag_sl,
                 st_num_in, st_den_in) = extra[i:]
            with jax.named_scope("asfl.control"):
                slot_mask_l = slot_seg_l < R_srv         # (S_loc,)
                seg_gather = jnp.minimum(slot_seg_l, R_srv - 1)
                cut_slots_l = cuts[members_l]
                w_slots_l = lengths_f[members_l] * slot_mask_l

            def regroup(vals):
                """Order-restoring gather over the vehicle sub-axis
                (dense grid mesh): this device's (R_loc, C_loc) column
                block rejoins its row's other blocks, so per-RSU
                reductions see the full C columns in the single-device
                slot order — the bit-for-bit combine, where a psum of
                column-block partials would reassociate the fp adds."""
                v = lax.all_gather(vals, VEH_AXIS)      # (dv, S_loc, ...)
                v = v.reshape((dv, R_loc, C_loc) + vals.shape[1:])
                v = jnp.moveaxis(v, 0, 1)               # (R_loc, dv, C_loc)
                return v.reshape((R_loc * C,) + vals.shape[1:])

            with jax.named_scope("asfl.control"):
                seg_full = regroup(slot_seg_l) if dense2d else slot_seg_l

            def seg_sum(vals):
                if dense2d:
                    vals = regroup(vals)
                out = jnp.zeros((R_srv + 1,) + vals.shape[1:],
                                vals.dtype).at[seg_full].add(vals)[:R_srv]
                return lax.psum(out, ALL_AXES) if psum_out else out

            with jax.named_scope("asfl.control"):
                w_seg = seg_sum(w_slots_l)               # (R_srv,)
                den = jnp.maximum(w_seg, 1.0)
                any_active = w_seg > 0.0
                gw = w_slots_l / den[seg_gather]         # (S_loc,)
                # (S_loc, P) / (S_loc, W): positions each slot's replica
                # owns
                keep_full = slot_mask_l[:, None] \
                    & (unit_ids[None, :] < cut_slots_l[:, None])
                keep_w = keep_full[:, O:O + W]
            sv0 = edge_stack_in                          # (R_srv, P)
            with jax.named_scope("asfl.client_update"):
                cu = sv0[:, O:O + W][seg_gather]         # (S_loc, W)
                co = jax.vmap(opt.init)(cu)
            with jax.named_scope("asfl.server_update"):
                so = jax.vmap(opt.init)(sv0)

            def paged_sweep(sv_stack, cu, idx_s, amask, gw_s, res):
                """One local step's fwd/bwd in fixed slot windows
                (DESIGN.md §15): each page vmaps ``page`` slots, scatters
                its weighted gradient share into an (R_srv + 1, P)
                accumulator (row R_srv drops the phantoms), and emits only
                its owned-window gradient columns — the full-width
                (S_loc, P) gradient and the S_loc-wide activations never
                materialize, so peak memory is set by ``page_slots``, not
                the planned compacted capacity.  Pages are a lax.scan of
                static length: paging churn is data, never a signature."""
                pg = lambda a: a.reshape((n_pages, page) + a.shape[1:])
                xs = {"cu": pg(cu), "cut": pg(cut_slots_l),
                      "m": pg(members_l), "idx": pg(idx_s),
                      "seg": pg(slot_seg_l), "mask": pg(slot_mask_l),
                      "amask": pg(amask), "gw": pg(gw_s)}
                if ef:
                    xs["res"] = pg(res)

                def page_fn(accs, xp):
                    g_acc, l_acc = accs
                    with jax.named_scope("asfl.server_update"):
                        sv_g = sv_stack[jnp.minimum(xp["seg"], R_srv - 1)]
                    if ef:
                        g, losses, res_n = jax.vmap(
                            par_slot_grad, in_axes=(0, 0, 0, 0, 0, 0))(
                                xp["cu"], xp["cut"], xp["m"], xp["idx"],
                                sv_g, xp["res"])
                    else:
                        g, losses = jax.vmap(
                            par_slot_grad, in_axes=(0, 0, 0, 0, 0))(
                                xp["cu"], xp["cut"], xp["m"], xp["idx"],
                                sv_g)
                    with jax.named_scope("asfl.server_update"):
                        keep_p = xp["mask"][:, None] \
                            & (unit_ids[None, :] < xp["cut"][:, None])
                        contrib = jnp.where(keep_p, 0.0, g) \
                            * xp["gw"][:, None]
                        g_acc = g_acc.at[xp["seg"]].add(contrib)
                        l_acc = l_acc.at[xp["seg"]].add(
                            jnp.where(xp["amask"], losses, 0.0))
                    with jax.named_scope("asfl.client_update"):
                        ys = (g[:, O:O + W], res_n) if ef \
                            else g[:, O:O + W]
                    return (g_acc, l_acc), ys

                with jax.named_scope("asfl.server_update"):
                    accs0 = (jnp.zeros((R_srv + 1, P), jnp.float32),
                             jnp.zeros((R_srv + 1,), jnp.float32))
                (g_acc, l_acc), ys = lax.scan(page_fn, accs0, xs)
                with jax.named_scope("asfl.server_update"):
                    if ef:
                        g_w = ys[0].reshape(S_loc, W)
                        res_new = ys[1].reshape(S_loc, res_size)
                    else:
                        g_w, res_new = ys.reshape(S_loc, W), None
                    g_srv, ls_seg = g_acc[:R_srv], l_acc[:R_srv]
                    if psum_out:
                        g_srv = lax.psum(g_srv, ALL_AXES)
                        ls_seg = lax.psum(ls_seg, ALL_AXES)
                return g_w, g_srv, ls_seg, res_new

            def step_body(carry, x_s):
                if fz:
                    idx_s, act_s = x_s
                else:
                    idx_s = x_s
                if ef:
                    sv_stack, so, cu, co, res = carry
                else:
                    sv_stack, so, cu, co = carry
                    res = None
                if fz:
                    # per-step survivorship: a dropped slot stops
                    # contributing weight (and gradient) after its drop
                    # step, so the server's |D_n|-weighted mean-gradient
                    # renormalizes per step over the still-active slots
                    with jax.named_scope("asfl.control"):
                        amask = slot_mask_l & act_s
                        w_act = w_slots_l * act_s
                        w_seg_s = seg_sum(w_act)
                        den_s = jnp.maximum(w_seg_s, 1.0)
                        gw_s = w_act / den_s[seg_gather]
                        any_s = w_seg_s > 0.0
                else:
                    amask, gw_s, any_s = slot_mask_l, gw, any_active
                if paged:
                    g_w, g_srv, ls_seg, res_new = paged_sweep(
                        sv_stack, cu, idx_s, amask, gw_s, res)
                else:
                    with jax.named_scope("asfl.server_update"):
                        sv_g = sv_stack[seg_gather]
                    if ef:
                        g, losses, res_new = jax.vmap(
                            par_slot_grad, in_axes=(0, 0, 0, 0, 0, 0))(
                                cu, cut_slots_l, members_l, idx_s,
                                sv_g, res)
                    else:
                        g, losses = jax.vmap(
                            par_slot_grad, in_axes=(0, 0, 0, 0, 0))(
                                cu, cut_slots_l, members_l, idx_s,
                                sv_g)
                        res_new = None
                    # RSUs: one |D_n|-weighted mean-gradient step each
                    # over their cohorts' server-side gradient shares
                    with jax.named_scope("asfl.server_update"):
                        contrib = jnp.where(keep_full, 0.0, g) \
                            * gw_s[:, None]
                        g_srv = seg_sum(contrib)         # (R_srv, P)
                        ls_seg = seg_sum(jnp.where(amask, losses, 0.0))
                    with jax.named_scope("asfl.client_update"):
                        g_w = g[:, O:O + W]
                if ef:
                    with jax.named_scope("asfl.wire"):
                        res = jnp.where(amask[:, None], res_new, res)
                with jax.named_scope("asfl.server_update"):
                    upd_s, so2 = jax.vmap(opt.update)(g_srv, so, sv_stack)
                    sv2 = optim.apply_updates(sv_stack, upd_s)
                    sv_stack = jnp.where(any_s[:, None], sv2, sv_stack)
                    so = _sel_flat_state(any_s[:, None], any_s,
                                         so2, so, sv_stack.shape)
                # vehicles: per-replica prefix updates over the slot axis
                with jax.named_scope("asfl.client_update"):
                    upd_c, co2 = jax.vmap(opt.update)(g_w, co, cu)
                    keep_w_s = keep_w & act_s[:, None] if fz else keep_w
                    cu = jnp.where(keep_w_s,
                                   optim.apply_updates(cu, upd_c), cu)
                    co = _sel_flat_state(keep_w_s, amask, co2, co,
                                         cu.shape)
                out = (sv_stack, so, cu, co, res) if ef \
                    else (sv_stack, so, cu, co)
                return out, ls_seg

            init = (sv0, so, cu, co, res_slots_l) if ef \
                else (sv0, so, cu, co)
            xs_steps = (idx_slots_l, act_slots_l) if fz else idx_slots_l
            (sv_stack, so, cu, co, *res_t), ls_steps = lax.scan(
                step_body, init, xs_steps,
                unroll=min(steps, 4))
            with jax.named_scope("asfl.merge"):
                ls_rows = jnp.sum(ls_steps, axis=0)          # (R_srv,)
                if fz:
                    # survivor-weighted unit-wise FedAvg (DESIGN.md §13): the
                    # merge weight is the SURVIVING slot weight — dropped /
                    # lost / straggling slots fold in as exact +0 — plus last
                    # round's staleness bank at the discount.  The per-position
                    # denominator can sit in (0, 1) when only discounted bank
                    # weight remains, so the guards are wheres, not maxes
                    w_surv = w_slots_l * surv_sl.astype(jnp.float32)
                    w_seg_m = seg_sum(w_surv)                # (R_srv,)
                    wk = w_surv[:, None] * keep_w            # (S_loc, W)
                    num = seg_sum(wk * cu)                   # (R_srv, W)
                    w_srv = w_seg_m[:, None] - seg_sum(wk)
                    svw = sv_stack[:, O:O + W]
                    st_den_pos = st_den_in[:, unit_ids_w]    # (R_srv, W)
                    den_pos = w_seg_m[:, None] + disc * st_den_pos
                    den_pos_safe = jnp.where(den_pos > 0.0, den_pos, 1.0)
                    merged_w = jnp.where(
                        den_pos > 0.0,
                        (num + w_srv * svw + disc * st_num_in) / den_pos_safe,
                        edge_stack_in[:, O:O + W])
                    row_act = w_seg_m > 0.0
                    den_row = jnp.maximum(w_seg_m, 1.0)
                    if O > 0 or O + W < P:
                        edge_new = jnp.concatenate(
                            [jnp.where(row_act[:, None],
                                       (w_seg_m[:, None] * sv_stack[:, :O])
                                       / den_row[:, None],
                                       edge_stack_in[:, :O]),
                             merged_w,
                             jnp.where(row_act[:, None],
                                       (w_seg_m[:, None] * sv_stack[:, O + W:])
                                       / den_row[:, None],
                                       edge_stack_in[:, O + W:])],
                            axis=1)
                    else:
                        edge_new = merged_w
                    # this round's bank: straggler replicas scattered into the
                    # same per-RSU segment rows, merged NEXT round
                    w_st = w_slots_l * strag_sl.astype(jnp.float32)
                    st_num_out = seg_sum((w_st[:, None] * keep_w) * cu)
                    unit_own = (cut_slots_l[:, None]
                                > jnp.arange(CU, dtype=jnp.int32)[None, :])
                    st_den_out = seg_sum(w_st[:, None] * unit_own)
                    rets = [edge_new, ls_rows, w_seg_m, slot_mask_l]
                    if ef:
                        rets.append(res_t[0])
                    rets += [st_num_out, st_den_out]
                    return tuple(rets)
                # unit-wise FedAvg: segment-sums over the owned window, the
                # untouched remainder of the plane merges as (w_seg * sv) / den
                # (its client weight is identically zero)
                wk = w_slots_l[:, None] * keep_w             # (S_loc, W)
                num = seg_sum(wk * cu)                       # (R_srv, W)
                w_srv = w_seg[:, None] - seg_sum(wk)
                merged_w = (num + w_srv * sv_stack[:, O:O + W]) / den[:, None]
                if O > 0 or O + W < P:
                    merged = jnp.concatenate(
                        [(w_seg[:, None] * sv_stack[:, :O]) / den[:, None],
                         merged_w,
                         (w_seg[:, None] * sv_stack[:, O + W:])
                         / den[:, None]],
                        axis=1)
                else:
                    merged = merged_w
                edge_new = jnp.where(any_active[:, None], merged,
                                     edge_stack_in)
                if ef:
                    return edge_new, ls_rows, w_seg, slot_mask_l, res_t[0]
                return edge_new, ls_rows, w_seg, slot_mask_l

        def round_body(carry, x):
            with jax.named_scope("asfl.control"):
                rnd = x["rnd"]
                if sig.staged:
                    serving = x["serving"]
                    rates = x["rates"]
                    residence = x["residence"]
                else:
                    t = rnd.astype(jnp.float32) * interval
                    fkey = jax.random.fold_in(fading_key, rnd)
                    st = sc.traced_fleet_state(t, fkey)
                    serving, rates, residence = (st.serving_rsu, st.rates_bps,
                                                 st.residence_s)
                if cz:
                    # presence churn (DESIGN.md §14): each vehicle flips its
                    # presence bit with P[churn_rate], round-keyed so a K-fused
                    # window samples identically to K single rounds.  A vehicle
                    # not admitted this round becomes indistinguishable from
                    # one outside coverage before cut selection.  Synchronous
                    # schedules admit a fresh arrival only NEXT round (it still
                    # has to register and download the cohort model after the
                    # round has formed); the streaming schedule admits it
                    # immediately — its shard is already staged on device by
                    # the double-buffered pipeline, and the buffered merge
                    # never waits on cohort formation
                    if stc.churn_source == "mobility":
                        # mobility-coupled stream (DESIGN.md §15): departures
                        # ARE the coverage state — a vehicle whose serving
                        # cell is -1 has left the stream, one re-entering
                        # coverage re-registers.  Same admission contract as
                        # the sampled chain: synchronous schedules admit the
                        # re-arrival next round, streaming immediately
                        present2 = serving >= 0
                    else:
                        toggle = streaming.sample_toggles_traced(stc, rnd, n)
                        present2 = carry["present"] ^ toggle
                    arrived = present2 & ~carry["present"]
                    admit = present2 if sz else (present2 & ~arrived)
                    serving, rates, residence = streaming.gate_presence(
                        serving, rates, residence, admit)
                cuts = pick_cuts(serving, rates, residence)
                if fz:
                    drop, dfrac, lost, rsu_down = faults.sample_faults_traced(
                        fc, rnd, n, R)
                    rsu_down = faults.ensure_rsu_up(rsu_down)
                    # whole-RSU outage: the cohort's cuts drop to SKIP before
                    # slot grouping — the cell trains nothing and accrues no
                    # samples this round, so the cloud merge reweights around
                    # it by construction
                    down_v = rsu_down[jnp.clip(serving, 0, R - 1)] \
                        & (serving >= 0)
                    cuts = jnp.where(down_v, 0, cuts).astype(jnp.int32)
                order, seg_v, counts = slot_sort(serving, cuts)
                idx_all = fleet_batch_indices_traced(
                    jax.random.fold_in(base_key, rnd), lengths_dev, steps,
                    batch)
                sched = cuts > 0
                if fz:
                    # failure precedence: a mid-round dropout has nothing left
                    # to upload; an upload loss discards what a straggler
                    # would have banked
                    drop = drop & sched
                    lost = lost & sched & ~drop
                    if fc.straggler_factor > 0.0:
                        # deadline stragglers are derived, not sampled: the
                        # analytic round latency at the CHOSEN cut against the
                        # scaled residence budget
                        lat_m = adaptive.latency_matrix_traced(
                            self.profile, jnp.maximum(rates, 1.0), flops,
                            cfg.server_flops, nb, batch, ep, range(1, U))
                        lat = lat_m[jnp.arange(n),
                                    jnp.clip(cuts - 1, 0, U - 2)]
                        strag = sched & (lat > fc.straggler_factor * residence)
                    else:
                        strag = jnp.zeros_like(sched)
                    strag = strag & ~drop & ~lost
                    rescue = faults.rescue_mask(sched, drop | lost | strag)
                    drop = drop & ~rescue
                    lost = lost & ~rescue
                    strag = strag & ~rescue
                    surv = sched & ~drop & ~lost & ~strag
                    dstep = faults.drop_steps(drop, dfrac, steps)
                    # (steps, n) per-step activity: a dropout runs only its
                    # first dstep local batches; everyone else runs them all
                    act_v = (jnp.arange(steps, dtype=jnp.int32)[:, None]
                             < dstep[None, :]) & sched[None, :]
                    # banked weight merging THIS round (telemetry)
                    stale_w = jnp.sum(carry["stale_den"])
                    if fm is not None and not ragged_par:
                        # the bank is per-RSU state, sharded over the RSU axis
                        # and replicated across the vehicle sub-axis — psum
                        # over the RSU axis only (both would multiply by dv)
                        stale_w = lax.psum(stale_w, RSU_AXIS)
            with jax.named_scope("asfl.wire"):
                if ef:
                    # residuals follow the vehicle (the plane is fleet-indexed
                    # and replicated): zero where this round's cut differs from
                    # the one the buffer was accumulated at, then gather each
                    # shard's slot view
                    stale = sched & (cuts != carry["wire_cut"])
                    res_base = jnp.where(stale[:, None], 0.0,
                                         carry["wire_res"])
            if self.schedule == "sequential":
                with jax.named_scope("asfl.control"):
                    members, mask = slot_table_seq(order, counts)
                    if fm is not None:
                        # the slot table is fleet-wide and replicated; each
                        # RSU-axis shard trains its contiguous block of RSU
                        # rows.  The sequential schedule is a per-RSU slot
                        # CHAIN (slot i+1's server pass consumes slot i's
                        # updated state), so the vehicle sub-axis has nothing
                        # to split — it replicates the chain (DESIGN.md §15)
                        members_l = fleet_sharding.local_slice(
                            members, R_loc, axes=(RSU_AXIS,))
                        mask_l = fleet_sharding.local_slice(
                            mask, R_loc, axes=(RSU_AXIS,))
                    else:
                        members_l, mask_l = members, mask
                    # the slot chain runs for this shard's fullest RSU
                    # (its occupied slots, at most C): slots past it are
                    # padding in every local RSU row
                    trip = jnp.max(jnp.sum(mask_l, axis=1, dtype=jnp.int32))
                    cut_slots = cuts[members_l]            # (R_loc, C)
                    args = [carry["edge"], members_l, mask_l, cut_slots,
                            idx_all[:, members_l]]  # (steps, R_loc, C, b)
                    if ef:
                        res_slots = res_base[members_l]    # (R_loc, C, res)
                        args.append(res_slots)
                    if fz:
                        args += [act_v[:, members_l] & mask_l,
                                 surv[members_l] & mask_l,
                                 strag[members_l] & mask_l,
                                 carry["stale_num"], carry["stale_den"]]
                outs = rsu_round_seq(trip, *args)
                if cr:
                    n_counted = outs[-1]
                    if fm is not None:
                        n_counted = lax.psum(n_counted, RSU_AXIS)
                    outs = outs[:-1]
                if fz:
                    st_num2, st_den2 = outs[-2], outs[-1]
                    outs = outs[:-2]
                if ef:
                    edge, ls, cs, w_tot, res_out = outs
                else:
                    edge, ls, cs, w_tot = outs
                ef_mask, ef_members = mask_l, members_l
                with jax.named_scope("asfl.merge"):
                    cnt = jnp.sum(cs)
                    if fm is not None:
                        # per-RSU results come home via all_gather so every
                        # total (loss/count sums, the sample counters, the
                        # cloud merge) reduces the full (R,) stack in the SAME
                        # order as the single-device program — gather-then-
                        # reduce is the order-preserving form of the weighted
                        # all-reduce, which is what keeps sharded sgd
                        # bit-for-bit (a psum of per-shard partials would
                        # reassociate the fp additions)
                        ls = lax.all_gather(ls, RSU_AXIS, tiled=True)
                        cnt = jnp.sum(lax.all_gather(cs, RSU_AXIS,
                                                     tiled=True))
                        w_tot = lax.all_gather(w_tot, RSU_AXIS, tiled=True)
                        edge_stack = aggregation.gathered_stack(edge,
                                                                RSU_AXIS)
                    else:
                        edge_stack = edge
            else:
                with jax.named_scope("asfl.control"):
                    members, slot_seg = slot_table_flat(order, seg_v, counts)
                    if fm is None:
                        members_l, slot_seg_l = members, slot_seg
                    elif layout == "dense":
                        # RSU-aligned tiles: device (i, j)'s slots are its
                        # R_loc rows x C_loc columns of the padded (R, C)
                        # grid (with dv == 1 that is exactly the old R_loc-row
                        # block); localize segment ids and clip the phantom
                        # segment R onto the local drop row
                        members_l = fleet_sharding.local_block2d(
                            members.reshape(R, C), R_loc, C_loc).reshape(-1)
                        seg = fleet_sharding.local_block2d(
                            slot_seg.reshape(R, C), R_loc, C_loc).reshape(-1)
                        r0 = lax.axis_index(RSU_AXIS) * R_loc
                        slot_seg_l = jnp.minimum(seg - r0,
                                                 R_loc).astype(jnp.int32)
                    else:
                        # occupancy-balanced blocks of the compacted axis,
                        # split over the WHOLE (rsu, vehicle) device grid
                        members_l = fleet_sharding.local_slice(members, S_loc)
                        slot_seg_l = fleet_sharding.local_slice(slot_seg,
                                                                S_loc)
                    idx_slots = idx_all[:, members_l]      # (steps, S_loc, b)
                    args = [carry["edge"], cuts, members_l, slot_seg_l,
                            idx_slots]
                    if ef:
                        res_slots = res_base[members_l]    # (S_loc, res)
                        args.append(res_slots)
                    if fz:
                        args += [act_v[:, members_l], surv[members_l],
                                 strag[members_l],
                                 carry["stale_num"], carry["stale_den"]]
                outs = fleet_round_par(*args)
                if fz:
                    st_num2, st_den2 = outs[-2], outs[-1]
                    outs = outs[:-2]
                if ef:
                    edge, ls, w_tot, slot_mask_l, res_out = outs
                else:
                    edge, ls, w_tot, slot_mask_l = outs
                ef_mask, ef_members = slot_mask_l, members_l
                with jax.named_scope("asfl.merge"):
                    if fz:
                        # dropouts run only their dstep-batch prefix
                        cnt = jnp.sum(
                            jnp.where(sched, dstep, 0)).astype(jnp.float32)
                    else:
                        # every occupied slot runs exactly `steps` batches
                        cnt = (jnp.sum(counts) * steps).astype(jnp.float32)
                    if sz:
                        # StreamBuffer commit (DESIGN.md §14): the cohort's
                        # round update becomes a PENDING delta in the RSU's
                        # next free buffer slot; the edge model advances only
                        # when the buffer holds B deltas and the staleness-
                        # weighted survivor FedAvg fires.  Runs on the LOCAL
                        # edge rows (before any gather), so the committed edge
                        # is what the mesh combine sees.  All state is carry:
                        # buffer churn is data, never a program signature
                        edge_old = carry["edge"]
                        delta = edge - edge_old               # (R_srv, P)
                        pushed = w_tot > 0.0                  # (R_srv,)
                        cnt_b = carry["sbuf_cnt"]
                        slot_oh = (jnp.arange(B, dtype=jnp.int32)[None, :]
                                   == cnt_b[:, None]) & pushed[:, None]
                        sb = jnp.where(slot_oh[:, :, None], delta[:, None, :],
                                       carry["sbuf"])
                        sbw = jnp.where(slot_oh, w_tot[:, None],
                                        carry["sbuf_w"])
                        sba = jnp.where(slot_oh, 0, carry["sbuf_age"])
                        cnt2 = cnt_b + pushed.astype(jnp.int32)
                        fire = cnt2 >= B                      # (R_srv,)
                        valid = (jnp.arange(B, dtype=jnp.int32)[None, :]
                                 < cnt2[:, None])
                        # staleness-weighted survivor FedAvg over the pending
                        # deltas: weights are merge weight x kernel(age), and
                        # empty slots fold in as exact +0 through their zero
                        # weights.  The denominator can sit in (0, 1) under
                        # polynomial discounts, so the guard is a where
                        kw = (sbw * streaming.staleness_kernel(
                            stc.kernel, stc.alpha, sba)
                            * valid.astype(jnp.float32))
                        tot_b = jnp.sum(kw, axis=1)           # (R_srv,)
                        den_b = jnp.where(tot_b > 0.0, tot_b, 1.0)
                        merged_b = edge_old + jnp.einsum(
                            "rb,rbp->rp", kw, sb) / den_b[:, None]
                        edge = jnp.where(fire[:, None], merged_b, edge_old)
                        # merge telemetry, read BEFORE the post-fire reset
                        absorbed = jnp.sum(jnp.where(
                            fire[:, None], sbw * valid, 0.0))
                        st_stream = jnp.sum(jnp.where(
                            fire[:, None],
                            sba.astype(jnp.float32) * valid, 0.0))
                        fires = jnp.sum(fire.astype(jnp.int32))
                        occ = jnp.sum(jnp.where(fire, 0, cnt2))
                        # post-fire: fired buffers clear; survivors age one
                        # round.  The delta plane itself needs no clear — its
                        # weights are zero, the exact-+0 convention
                        sbuf2 = sb
                        sbw2 = jnp.where(fire[:, None], 0.0, sbw)
                        sba2 = jnp.where(fire[:, None], 0,
                                         jnp.where(valid, sba + 1, sba))
                        cnt3 = jnp.where(fire, 0, cnt2)
                        if fm is not None and not ragged_par:
                            # per-RSU scalars sharded over the RSU axis (and
                            # replicated across the vehicle sub-axis): sum
                            # home across the RSU shards only
                            rsu_only = (RSU_AXIS,)
                            absorbed = fleet_sharding.scalar_allsum(absorbed,
                                                                    rsu_only)
                            st_stream = fleet_sharding.scalar_allsum(st_stream,
                                                                     rsu_only)
                            fires = fleet_sharding.scalar_allsum(fires,
                                                                 rsu_only)
                            occ = fleet_sharding.scalar_allsum(occ, rsu_only)
                    if fm is not None and layout == "dense":
                        # per-RSU rows are vehicle-replicated after the
                        # regrouped segment-sums, so the combine is the same
                        # RSU-axis gather as the 1-D mesh
                        ls = lax.all_gather(ls, RSU_AXIS, tiled=True)
                        w_tot = lax.all_gather(w_tot, RSU_AXIS, tiled=True)
                        edge_stack = aggregation.gathered_stack(edge,
                                                                RSU_AXIS)
                    else:
                        # single device, or ragged mesh: segment-sums were
                        # already psum'd full-width and the edge is replicated
                        edge_stack = edge
            with jax.named_scope("asfl.merge"):
                samples = carry["samples"] + w_tot
            with jax.named_scope("asfl.wire"):
                if ef:
                    # masked scatter-ADD of the residual deltas back onto the
                    # fleet plane: padded slots carry a zero delta (their
                    # member index is a clipped duplicate), active slots are
                    # unique per round (a vehicle is served by one RSU), and
                    # under a mesh the psum of per-shard deltas reassembles
                    # the replicated plane — other shards contribute zeros
                    delta = jnp.where(ef_mask[..., None], res_out - res_slots,
                                      0.0)
                    upd = jnp.zeros_like(res_base).at[
                        ef_members.reshape(-1)].add(
                            delta.reshape(-1, delta.shape[-1]))
                    if fm is not None:
                        # sequential: slots live on RSU-axis shards and the
                        # vehicle sub-axis replicates them (psum over both
                        # would multiply by dv); flat schedules: every slot
                        # lives on exactly one (rsu, vehicle) device
                        upd = lax.psum(upd,
                                       (RSU_AXIS,)
                                       if self.schedule == "sequential"
                                       else ALL_AXES)
                    wire_res2 = res_base + upd
                    wire_cut2 = jnp.where(sched, cuts,
                                          carry["wire_cut"]).astype(jnp.int32)
            with jax.named_scope("asfl.control"):
                handover = sched & (carry["prev"] >= 0) \
                    & (carry["prev"] != serving)
                prev = jnp.where(serving >= 0, serving, -1).astype(jnp.int32)
            with jax.named_scope("asfl.merge"):
                synced = (rnd + 1) % sync_every == 0
                merged_global = aggregation.stacked_cloud_merge(
                    edge_stack, samples, carry["global"])
                carry2 = {
                    "edge": jax.tree.map(
                        lambda stacked, g: jnp.where(
                            synced, jnp.broadcast_to(g, stacked.shape),
                            stacked),
                        edge, merged_global),
                    "samples": jnp.where(synced, jnp.zeros_like(samples),
                                         samples),
                    "prev": prev,
                    "global": jax.tree.map(
                        lambda g, old: jnp.where(synced, g, old),
                        merged_global, carry["global"]),
                }
            if ef:
                carry2["wire_res"] = wire_res2
                carry2["wire_cut"] = wire_cut2
            if fz:
                # the staleness bank drains every round: this round's
                # straggler captures replace last round's (now-merged) bank
                carry2["stale_num"] = st_num2
                carry2["stale_den"] = st_den2
            if cz:
                carry2["present"] = present2
            if sz:
                carry2["sbuf"] = sbuf2
                carry2["sbuf_w"] = sbw2
                carry2["sbuf_age"] = sba2
                carry2["sbuf_cnt"] = cnt3
            with jax.named_scope("asfl.control"):
                ys = {"loss": jnp.sum(ls), "cnt": cnt, "cuts": cuts,
                      "serving": serving.astype(jnp.int32),
                      "rates": rates.astype(jnp.float32),
                      "handover": handover, "counts": counts}
                if cr:
                    ys[self.aux_counter] = n_counted
                if fz:
                    ys.update({"drop": drop, "lost": lost, "strag": strag,
                               "rsu_down": rsu_down, "dstep": dstep,
                               "stale_w": stale_w})
                if cz:
                    ys.update({
                        "present": jnp.sum(present2.astype(jnp.int32)),
                        "arrived": jnp.sum(arrived.astype(jnp.int32))})
                if sz:
                    ys.update({"absorbed": absorbed, "stream_fires": fires,
                               "buf_occ": occ, "stream_stale": st_stream})
            return carry2, ys

        def superstep(carry, xs):
            return lax.scan(round_body, carry, xs)

        if self.frozen is not None:
            scan_rounds = superstep

            def superstep(carry, xs, frozen):
                # every closure above reads `model`: bind it to this
                # trace's frozen argument before the rounds are traced
                nonlocal model
                model = self.model.bind(frozen)
                return scan_rounds(carry, xs)

        if fm is not None:
            # ragged + parallel replicates the edge stack (the mesh splits
            # the compacted slot axis, not the RSU axis); every other
            # combination shards the edge's leading RSU axis as before
            edge_spec = PSpec() if ragged_par else PSpec(RSU_AXIS)
            carry_spec = {"edge": edge_spec, "samples": PSpec(),
                          "prev": PSpec(), "global": PSpec()}
            if ef:
                carry_spec["wire_res"] = PSpec()
                carry_spec["wire_cut"] = PSpec()
            if fz:
                # the staleness bank is per-RSU state: it shards with the
                # edge stack (and replicates when the edge does)
                carry_spec["stale_num"] = edge_spec
                carry_spec["stale_den"] = edge_spec
            if cz:
                # presence is fleet-wide state, replicated like the slot
                # table it gates
                carry_spec["present"] = PSpec()
            if sz:
                # the stream buffer is per-RSU state: it shards with the
                # edge stack (and replicates when the edge does)
                for k in ("sbuf", "sbuf_w", "sbuf_age", "sbuf_cnt"):
                    carry_spec[k] = edge_spec
            superstep = jax.shard_map(superstep, mesh=fm.mesh,
                                      in_specs=(carry_spec, PSpec())
                                      + (PSpec(),) * len(self.frozen_args),
                                      out_specs=(carry_spec, PSpec()),
                                      check_vma=False)
        return jax.jit(superstep, donate_argnums=(0,))

    # ---- cache / AOT --------------------------------------------------
    def signature(self, k: int, capacity: int,
                  slots: int = 0) -> SuperStepSignature:
        """The compile-cache key for a K-window at per-RSU capacity
        ``capacity``.  ``slots`` (the bucketed max TOTAL covered count) is
        honored only by the ragged layout's parallel schedule; callers that
        do not plan it fall back to ``R * capacity`` — always sufficient,
        merely uncompacted."""
        if self.layout == "ragged" and self.schedule != "sequential":
            s = int(slots) if slots and int(slots) > 0 \
                else self.n_rsus_padded * int(capacity)
            if self.mesh is not None:
                s = self.mesh.balanced_slots(s)
            page = int(getattr(self.cfg, "page_slots", 0))
            if page > 0:
                # pad each device's block to a page multiple so the paged
                # sweep's fixed windows tile it exactly (padding is
                # phantom slots — inert by the exact-+0 convention)
                nd = 1 if self.mesh is None else self.mesh.n_devices
                per = -(-s // nd)
                if per > page:
                    per = -(-per // page) * page
                s = per * nd
        else:
            s = 0
        return SuperStepSignature(k, capacity, not self.traced_mobility,
                                  s, self.max_cut_bucket)

    def get(self, sig: SuperStepSignature):
        """The program for ``sig``; builds one (a counted compile fallback)
        if :meth:`precompile` did not cover it."""
        fn = self._programs.get(sig)
        if fn is None:
            self.compile_fallbacks += 1
            fn = self._build(sig)
            self._programs[sig] = fn
        return fn

    def precompile(self, sig: SuperStepSignature, carry, xs) -> None:
        """AOT-lower and compile the program for ``sig`` against the
        abstract shapes of (carry, xs) — leaves may be arrays or
        ``ShapeDtypeStruct``s."""
        if sig in self._programs:
            return

        def sds(a):
            if isinstance(a, jax.ShapeDtypeStruct):
                return a
            if self.mesh is not None and isinstance(a, jax.Array):
                # AOT-compiled executables check input shardings: keep the
                # carry's mesh placement in the abstract signature so the
                # run's (sharded, donated) carry matches what was compiled
                return jax.ShapeDtypeStruct(a.shape, a.dtype,
                                            sharding=a.sharding)
            a = jnp.asarray(a)
            return jax.ShapeDtypeStruct(a.shape, a.dtype)

        compiled = self._build(sig).lower(
            jax.tree.map(sds, carry), jax.tree.map(sds, xs),
            *jax.tree.map(sds, self.frozen_args)).compile()
        self._programs[sig] = compiled
        if self.schedule != "sequential" and self._unravel is None:
            self._unravel = jax.jit(self.unravel).lower(
                sds(carry["global"])).compile()
