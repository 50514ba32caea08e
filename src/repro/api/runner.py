"""``run(spec)``: one front door over the federation engines.

The router inspects :attr:`ExperimentSpec.engine_kind` and drives the right
engine — :class:`~repro.core.fedsim.FederationSim` (single-RSU cohort
rounds) or :class:`~repro.core.fedsim.ScenarioEngine` (multi-RSU fused
super-steps, honoring ``runtime.superstep``/``precompile``/compilation
cache) — then returns a :class:`RunResult`: the full round-metrics history,
aggregate cost accounting, wall-clock timing, and ``save``/``load``.

Streaming: ``on_round(metrics)`` fires for every completed round,
``on_cloud_merge(rnd, engine)`` after every multi-RSU cloud sync, and
``on_stream_merge(metrics, engine)`` after every round in which a
StreamBuffer fired (``train.server_schedule="streaming"``).  On the fused
path all fire after each K-round window from the window's single host
pull, so callbacks never add host syncs to the compiled program
(DESIGN.md §8/§9/§14).

``timeit=True`` runs the benchmark protocol: one warmup run (compiles every
program), ``reset()``, then the timed re-run — ``timing["round_s"]`` is the
steady-state per-round cost the benchmarks report (and compare against a
direct engine call for the ``api_overhead_s`` key).
"""
from __future__ import annotations

import dataclasses
import json
import time
from typing import Any, Callable, Dict, List, Optional, Union

import jax
import numpy as np

from repro.api import registry
from repro.api.spec import ExperimentSpec
from repro.core import fleet_sharding
from repro.core.fedsim import (FederationSim, RoundMetrics, ScenarioEngine,
                               ScenarioRoundMetrics)

__all__ = ["RunResult", "run", "build_engine"]


def _json_default(o):
    """Type-faithful JSON fallback: numpy ints stay ints (a loaded
    RunResult's cuts/loads must compare like a live run's)."""
    if isinstance(o, np.integer):
        return int(o)
    if isinstance(o, np.ndarray):
        return o.tolist()
    return float(o)


@dataclasses.dataclass
class RunResult:
    """Everything one experiment produced.

    ``history`` rows are :class:`RoundMetrics` (federation) or
    :class:`ScenarioRoundMetrics` (scenario).  ``final_params`` is the
    trained global model ``(units, head)``, gathered to host numpy arrays
    (mesh-independent); not serialized by :meth:`save`."""
    spec: ExperimentSpec
    engine_kind: str
    history: List[Any]
    totals: Dict[str, float]
    timing: Dict[str, float]
    diagnostics: Dict[str, Any]
    final_params: Any = dataclasses.field(default=None, repr=False)

    def to_dict(self) -> Dict[str, Any]:
        return {
            "spec": self.spec.to_dict(),
            "engine_kind": self.engine_kind,
            "history": [dataclasses.asdict(m) for m in self.history],
            "totals": self.totals,
            "timing": self.timing,
            "diagnostics": self.diagnostics,
        }

    def save(self, path: str) -> str:
        with open(path, "w") as f:
            json.dump(self.to_dict(), f, indent=1, default=_json_default)
        return path

    @classmethod
    def load(cls, path: str) -> "RunResult":
        with open(path) as f:
            d = json.load(f)
        metrics_cls = (ScenarioRoundMetrics
                       if d["engine_kind"] == registry.SCENARIO
                       else RoundMetrics)
        return cls(spec=ExperimentSpec.from_dict(d["spec"]),
                   engine_kind=d["engine_kind"],
                   history=[metrics_cls(**m) for m in d["history"]],
                   totals=d["totals"], timing=d["timing"],
                   diagnostics=d["diagnostics"])


def build_engine(spec: ExperimentSpec):
    """Instantiate the engine a spec routes to (model + fleet data + config
    assembled from the registries).  ``run`` uses this; benchmarks and
    parity tests may call it directly to hold an engine across re-runs.

    The device mesh is built HERE (``runtime.mesh_devices > 1`` —
    core/fleet_sharding.py), so a machine with too few devices fails with
    the ``--xla_force_host_platform_device_count`` recipe before any data
    is staged."""
    rt = spec.runtime
    # multi-host rendezvous first (DESIGN.md §15): jax.distributed must
    # initialize before the first backend touch so the mesh below spans
    # every process's devices.  No-op for the single-process default
    fleet_sharding.maybe_init_distributed(rt.coordinator_address,
                                          rt.num_processes, rt.process_id)
    entry = registry.model_entry(spec.model)
    model = entry.build(**spec.model_kwargs)
    f = spec.fleet
    clients, test = entry.make_data(f.n_vehicles, f.per_vehicle_samples,
                                    f.test_samples, f.data_seed, model=model)
    cfg = spec.to_sim_config()
    mesh = fleet_sharding.from_config(cfg, spec.engine_kind,
                                      fleet_size=f.n_vehicles)
    if spec.engine_kind == registry.SCENARIO:
        kw = dict(f.scenario_kwargs)
        kw.setdefault("seed", spec.runtime.seed)
        sc = registry.build_scenario(f.scenario, f.n_vehicles, **kw)
        return ScenarioEngine(model, clients, test, cfg, sc,
                              cloud_sync_every=f.cloud_sync_every,
                              mesh=mesh)
    fleet = None
    if f.memory_budget_bytes is not None:
        from repro.core import channel
        fleet = channel.make_fleet(f.n_vehicles, cfg.seed,
                                   memory_budget_bytes=f.memory_budget_bytes)
    return FederationSim(model, clients, test, cfg, fleet=fleet, mesh=mesh)


def _drive(engine, on_round, on_cloud_merge, on_stream_merge=None):
    if isinstance(engine, ScenarioEngine):
        return engine.run(on_round=on_round, on_cloud_merge=on_cloud_merge,
                          on_stream_merge=on_stream_merge)
    return engine.run(on_round=on_round)


def _totals(history) -> Dict[str, float]:
    accs = [m.test_acc for m in history if np.isfinite(m.test_acc)]
    totals = {
        "rounds": len(history),
        "comm_bytes": float(sum(m.comm_bytes for m in history)),
        "energy_j": float(sum(m.energy_j for m in history)),
        "sim_time_s": float(sum(m.sim_time_s for m in history)),
        "final_loss": float(history[-1].loss) if history else float("nan"),
        "final_acc": float(accs[-1]) if accs else float("nan"),
    }
    if history:
        # fault-plane robustness telemetry (DESIGN.md §13): effective
        # participation and the update mass that never merged.  getattr
        # defaults keep loaded pre-fault histories working
        totals["survivor_frac"] = float(np.mean(
            [getattr(m, "survivor_frac", 1.0) for m in history]))
        totals["lost_update_bytes"] = float(sum(
            getattr(m, "lost_update_bytes", 0.0) for m in history))
        totals["n_dropout"] = int(sum(
            getattr(m, "n_dropout", 0) for m in history))
        totals["n_upload_lost"] = int(sum(
            getattr(m, "n_upload_lost", 0) for m in history))
        totals["n_straggler"] = int(sum(
            getattr(m, "n_straggler", 0) for m in history))
        # streaming-plane telemetry (DESIGN.md §14): sample mass absorbed
        # into the global model (the goodput numerator), buffered-merge
        # count, and continuous-arrival volume
        totals["absorbed_samples"] = float(sum(
            getattr(m, "absorbed_samples", 0.0) for m in history))
        totals["stream_merges"] = int(sum(
            getattr(m, "stream_merges", 0) for m in history))
        totals["n_arrived"] = int(sum(
            getattr(m, "n_arrived", 0) for m in history))
    return totals


def run(spec: ExperimentSpec, *,
        on_round: Optional[Callable[[Any], None]] = None,
        on_cloud_merge: Optional[Callable[[int, Any], None]] = None,
        on_stream_merge: Optional[Callable[[Any, Any], None]] = None,
        timeit: Union[bool, int] = False) -> RunResult:
    """Execute an :class:`ExperimentSpec` end to end and return a
    :class:`RunResult`.

    ``on_round``/``on_cloud_merge``/``on_stream_merge`` stream progress
    (see module docstring);
    ``timeit`` truthy adds a warmup run plus ``int(timeit)`` timed
    **callback-free** re-runs (reset between; min wins) before the final
    callback-visible run, so ``round_s``/``rounds_per_s`` report
    compile-free engine steady state regardless of callback cost — an int
    > 1 strips scheduler noise on small containers."""
    engine = build_engine(spec)
    timing: Dict[str, float] = {}
    warmup = 0.0
    if isinstance(engine, ScenarioEngine) and spec.runtime.precompile:
        t0 = time.perf_counter()
        engine.precompile()
        warmup += time.perf_counter() - t0
    best = None
    if timeit:
        t0 = time.perf_counter()
        _drive(engine, None, None)
        warmup += time.perf_counter() - t0
        # timed samples are always callback-free, so round_s reports pure
        # engine steady state even when on_round does expensive work
        for _ in range(max(int(timeit), 1)):
            engine.reset()
            t0 = time.perf_counter()
            _drive(engine, None, None)
            rep = time.perf_counter() - t0
            best = rep if best is None else min(best, rep)
        engine.reset()
    # compile events inside the final run's window (the jax.monitoring
    # harness of the zero-fallback tests): 0 when precompile() (or the
    # timeit warmup) covered every program the run dispatched
    compiles = []

    def on_event(name, *args, **kwargs):
        if "compile" in name:
            compiles.append(name)

    jax.monitoring.register_event_duration_secs_listener(on_event)
    try:
        t0 = time.perf_counter()
        history = _drive(engine, on_round, on_cloud_merge, on_stream_merge)
        run_s = time.perf_counter() - t0
    finally:
        jax.monitoring.unregister_event_duration_listener(on_event)
    fastest = best if best is not None else run_s
    timing["warmup_s"] = warmup
    timing["run_s"] = run_s
    timing["round_s"] = fastest / max(len(history), 1)
    timing["rounds_per_s"] = (max(len(history), 1) / fastest
                              if fastest else 0.0)

    diagnostics: Dict[str, Any] = {"model": spec.model,
                                   "wire": spec.train.wire,
                                   "window_compiles": len(compiles)}
    if isinstance(engine, ScenarioEngine):
        diagnostics.update(
            mode=engine.mode, n_rsus=engine.n_rsus,
            compile_fallbacks=engine.programs.compile_fallbacks,
            superstep_layout=engine.programs.layout,
            occupancy=engine.occupancy_stats(),
            counters=dict(engine.counters))
        mesh = engine.fleet_mesh
    else:
        diagnostics.update(mode=engine.engine.mode, n_rsus=1)
        mesh = engine.engine.fleet_mesh
    diagnostics.update(
        mesh_devices=(mesh.n_devices if mesh is not None else 1),
        fleet_axis=(mesh.axis if mesh is not None else None),
        mesh_shape=([mesh.rsu_devices, mesh.veh_devices]
                    if mesh is not None else None),
        n_processes=jax.process_count())
    if spec.runtime.mesh_devices == "auto":
        # the mesh_devices="auto" decision (core/fleet_sharding.py):
        # chosen device count, the slots-per-device floor that drove it,
        # and what was available — None mesh means auto chose 1
        diagnostics["mesh_auto"] = (
            mesh.auto_info if mesh is not None
            else fleet_sharding.resolve_mesh_devices(
                "auto", spec.fleet.n_vehicles)[1])
    if spec.runtime.page_slots > 0:
        diagnostics["page_slots"] = spec.runtime.page_slots
    if spec.faults.straggler_factor > 0.0:
        # staleness histogram (DESIGN.md §13): distribution of the banked
        # straggler weight merged per round across the run
        stale = [float(getattr(m, "stale_merged", 0.0)) for m in history]
        counts, edges = np.histogram(stale, bins=8)
        diagnostics["staleness_hist"] = {"counts": counts.tolist(),
                                         "edges": edges.tolist()}
    elif spec.train.server_schedule == "streaming":
        # streaming twin (DESIGN.md §14): distribution of the buffered
        # slot-age mass discharged per round by StreamBuffer merges
        stale = [float(getattr(m, "stream_stale", 0.0)) for m in history]
        counts, edges = np.histogram(stale, bins=8)
        diagnostics["staleness_hist"] = {"counts": counts.tolist(),
                                         "edges": edges.tolist()}
    totals = _totals(history)
    # goodput (DESIGN.md §14): sample mass the global model absorbed per
    # steady-state second — the continuous-fleet throughput metric
    # BENCH_streaming sweeps against churn
    totals["goodput_samples_per_s"] = (
        totals.get("absorbed_samples", 0.0) / fastest if fastest else 0.0)
    # final_params come home to host numpy: results must not pin (or be
    # stranded on) mesh device buffers after the run
    return RunResult(spec=spec, engine_kind=spec.engine_kind,
                     history=list(history), totals=totals,
                     timing=timing, diagnostics=diagnostics,
                     final_params=fleet_sharding.host_fetch(
                         (list(engine.units), engine.head)))
