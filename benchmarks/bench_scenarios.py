"""Scenario-layer benchmark: rounds/s per mobility scenario at fleet scale.

Runs the multi-RSU fused super-step engine (DESIGN.md §8) through the
declarative front door: every row is one ``repro.api.run(ExperimentSpec)``
call with ``timeit=True`` — AOT ``precompile()`` + a warmup run, a reset,
then the timed compile-free re-run.  ``--superstep K`` fuses K rounds into
one ``lax.scan`` dispatch with donated carries; ``--compilation-cache DIR``
wires JAX's persistent compilation cache so a second invocation skips XLA
entirely (the ``compile_cache_hit`` key records whether this run started
warm).  The ``api_overhead_s`` key compares the API-routed per-round time
against a direct ``ScenarioEngine`` call at fleet 64 — the front door adds
no measurable per-round cost.

  PYTHONPATH=src python benchmarks/bench_scenarios.py
  -> BENCH_scenarios.json (repo root) + benchmarks/out/BENCH_scenarios.json

``--check-baseline BASELINE.json [--max-regress 0.30]`` compares this run's
rounds/s against a committed baseline and exits non-zero on a >30%
regression (the CI perf smoke); rows missing from the baseline are skipped
gracefully.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from bench_devices import parse_devices_early

# --devices N[,M,...]: per-device-count rows; the host device count must be
# forced BEFORE the first jax import (jax locks it on backend init)
DEVICE_COUNTS = parse_devices_early()

import jax
import numpy as np

from bench_io import device_row_key, write_bench
from bench_timing import interleaved_overhead
from repro import api
from repro.configs.base import cache_dir_is_warm, compilation_cache_dir
from repro.core.fedsim import ScenarioEngine


def _spec(name: str, n: int, args, devices: int = 1,
          fault_dropout: float = None,
          fault_upload_loss: float = None) -> api.ExperimentSpec:
    fd = args.fault_dropout if fault_dropout is None else fault_dropout
    fu = args.fault_upload_loss if fault_upload_loss is None else fault_upload_loss
    return api.ExperimentSpec(
        model="mlp9",
        train=api.TrainConfig(scheme="asfl", rounds=args.rounds,
                              local_steps=args.local_steps,
                              batch_size=args.batch, lr=1e-3, eval_every=0,
                              server_schedule=args.schedule,
                              wire=args.wire, wire_k=args.wire_k),
        faults=api.FaultsConfig(dropout_rate=fd, upload_loss_rate=fu,
                                seed=args.fault_seed),
        adaptive=api.AdaptiveConfig(strategy=args.strategy),
        fleet=api.FleetConfig(n_vehicles=n, scenario=name,
                              scenario_kwargs={"seed": n},
                              cloud_sync_every=args.sync,
                              round_interval_s=10.0,
                              per_vehicle_samples=64, data_seed=n),
        runtime=api.RuntimeConfig(superstep=args.superstep,
                                  slot_capacity=args.slot_capacity,
                                  superstep_layout=args.layout,
                                  precompile=True,
                                  mesh_devices=devices,
                                  compilation_cache_dir=args.compilation_cache))


def bench_one(name: str, n: int, args, devices: int = 1,
              fault_dropout: float = None,
              fault_upload_loss: float = None) -> dict:
    spec = _spec(name, n, args, devices, fault_dropout, fault_upload_loss)
    res = api.run(spec, timeit=args.timeit)
    assert all(np.isfinite(m.loss) for m in res.history)
    # zero retraces even under fault churn (DESIGN.md §13): fault masks are
    # data on the carry, never part of a program signature
    assert res.diagnostics["compile_fallbacks"] == 0
    occ = res.diagnostics["occupancy"]
    row = {
        "scenario": name, "n_vehicles": n, "devices": devices,
        # fault plane: rates + robustness telemetry (zero-fault rows report
        # the trivial values, keeping the row schema uniform)
        "fault_dropout": spec.faults.dropout_rate,
        "fault_upload_loss": spec.faults.upload_loss_rate,
        "survivor_frac": res.totals["survivor_frac"],
        "lost_update_bytes": res.totals["lost_update_bytes"],
        "n_dropout": res.totals["n_dropout"],
        "n_upload_lost": res.totals["n_upload_lost"],
        "n_rsus": res.diagnostics["n_rsus"],
        "mode": res.diagnostics["mode"], "schedule": args.schedule,
        "superstep": args.superstep, "rounds": args.rounds,
        "superstep_layout": res.diagnostics["superstep_layout"],
        "round_s": res.timing["round_s"],
        "rounds_per_s": res.timing["rounds_per_s"],
        "warmup_s": res.timing["warmup_s"],
        # occupancy accounting (DESIGN.md §12): how much of the executed
        # slot table / parameter plane was real work
        "padded_slot_frac": occ["padded_slot_frac"],
        "owned_plane_frac": occ["owned_plane_frac"],
        "scheduled_per_round": [m.n_scheduled for m in res.history],
        "handovers": int(sum(m.n_handover for m in res.history)),
        "final_loss": float(res.history[-1].loss),
    }
    if "staleness_hist" in res.diagnostics:
        row["staleness_hist"] = res.diagnostics["staleness_hist"]
    return row


def measure_api_overhead(args, fleet: int = 64,
                         scenario: str = "highway_corridor",
                         repeats: int = 3) -> dict:
    """Per-round cost of the front door: an engine built by
    ``api.build_engine(spec)`` and driven exactly as ``api.run`` drives it
    vs a hand-constructed ScenarioEngine with the same model, data,
    scenario, and config.  Both AOT-precompile and warm up once, then
    timed re-runs INTERLEAVE (min wins per side) so container scheduler
    drift hits both sides equally instead of masquerading as overhead."""
    spec = _spec(scenario, fleet, args)
    api_eng = api.build_engine(spec)
    entry = api.model_entry(spec.model)
    f = spec.fleet
    clients, test = entry.make_data(f.n_vehicles, f.per_vehicle_samples,
                                    f.test_samples, f.data_seed)
    sc = api.build_scenario(f.scenario, f.n_vehicles, **f.scenario_kwargs)
    direct = ScenarioEngine(entry.build(), clients, test,
                            spec.to_sim_config(), sc,
                            cloud_sync_every=f.cloud_sync_every)
    api_eng.precompile()
    direct.precompile()
    out = interleaved_overhead(
        (api_eng, lambda: api_eng.run(on_round=None, on_cloud_merge=None)),
        (direct, direct.run), repeats)
    return {"fleet": fleet, "scenario": scenario, **out}


def check_baseline(out: dict, baseline_path: str, max_regress: float) -> int:
    """Exit status for the CI perf smoke: 1 if any matching row's rounds/s
    dropped more than ``max_regress`` below the baseline."""
    if not os.path.exists(baseline_path):
        print(f"baseline {baseline_path} missing; skipping perf check")
        return 0
    with open(baseline_path) as f:
        base = json.load(f)
    # rounds/s is only comparable when the per-round work matches: skip
    # (don't spuriously fail) if the bench config drifted from the
    # committed baseline's — that means the baseline needs regenerating
    keys = ("local_steps", "batch", "strategy", "cloud_sync_every",
            "superstep", "schedule", "slot_capacity", "wire",
            "superstep_layout", "fault_dropout", "fault_upload_loss")
    mismatch = {k: (base.get("config", {}).get(k), out["config"].get(k))
                for k in keys
                if base.get("config", {}).get(k) != out["config"].get(k)}
    if mismatch:
        print(f"baseline config mismatch {mismatch}; skipping perf check "
              f"(regenerate {baseline_path})")
        return 0
    def _perf_key(r):
        # the chaos row times different work than its zero-fault twin —
        # give it its own baseline slot
        faulted = bool(r.get("fault_dropout") or r.get("fault_upload_loss"))
        return (r["scenario"], r["n_vehicles"], r.get("devices", 1),
                "faulted" if faulted else "clean")

    base_rows = {_perf_key(r): r["rounds_per_s"]
                 for r in base.get("results", [])}
    failures = []
    for row in out["results"]:
        key = _perf_key(row)
        if key not in base_rows:
            print(f"no baseline row for {key}; skipping")
            continue
        floor = base_rows[key] * (1.0 - max_regress)
        status = "OK" if row["rounds_per_s"] >= floor else "REGRESSION"
        print(f"perf {key}: {row['rounds_per_s']:.2f} r/s vs baseline "
              f"{base_rows[key]:.2f} (floor {floor:.2f}) {status}")
        if row["rounds_per_s"] < floor:
            failures.append(key)
    if failures:
        print(f"perf regression >{max_regress:.0%} in rows: {failures}")
        return 1
    return 0


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--sizes", default="64,256")
    ap.add_argument("--scenarios",
                    default=",".join(sorted(n for n, b in api.SCENARIOS.items()
                                            if b is not None)))
    ap.add_argument("--rounds", type=int, default=4)
    ap.add_argument("--local-steps", type=int, default=2)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--strategy", default="paper",
                    help="cut strategy (paper | residence | ...)")
    ap.add_argument("--sync", type=int, default=1)
    ap.add_argument("--superstep", type=int, default=8,
                    help="rounds fused per dispatch (1 = per-round); the "
                         "default benchmarks the engine's recommended "
                         "fused operating point")
    ap.add_argument("--schedule", default="sequential",
                    choices=sorted(api.SCHEDULES))
    ap.add_argument("--slot-capacity", default="tight8",
                    choices=["pow2", "tight8"])
    ap.add_argument("--layout", default="ragged",
                    choices=["ragged", "dense"],
                    help="super-step slot layout (DESIGN.md §12): ragged "
                         "compacts occupied slots + cut-prefix planes")
    ap.add_argument("--wire", default="none", choices=sorted(api.WIRES),
                    help="cut-boundary wire scheme (kernels/wire.py)")
    ap.add_argument("--wire-k", type=float, default=0.25,
                    help="topk_int8 keep fraction per group")
    ap.add_argument("--fault-dropout", type=float, default=0.0,
                    help="P[vehicle drops mid-round] applied to EVERY row "
                         "(core/faults.py; 0 = clean rows + one dedicated "
                         "chaos row)")
    ap.add_argument("--fault-upload-loss", type=float, default=0.0,
                    help="P[update lost after full local work], every row")
    ap.add_argument("--fault-seed", type=int, default=0)
    ap.add_argument("--no-fault-row", action="store_true",
                    help="skip the dedicated seeded-chaos row (dropout 0.2 "
                         "+ upload loss 0.1 on the first scenario) that the "
                         "CI perf gate tracks")
    ap.add_argument("--compilation-cache", default=None, metavar="DIR",
                    help="persistent XLA compilation cache directory")
    ap.add_argument("--devices", default="1", metavar="N[,M...]",
                    help="device counts to bench (RSU-axis mesh rows; on "
                         "CPU the host device count is forced pre-import "
                         "— parsed by bench_devices before jax loads)")
    ap.add_argument("--timeit", type=int, default=3,
                    help="timed compile-free re-runs per row (min wins); "
                         ">1 strips scheduler noise on small containers")
    ap.add_argument("--check-baseline", default=None, metavar="JSON",
                    help="compare rounds/s against a committed baseline")
    ap.add_argument("--max-regress", type=float, default=0.30)
    ap.add_argument("--skip-api-overhead", action="store_true",
                    help="skip the api-vs-direct overhead measurement")
    ap.add_argument("--no-write", action="store_true",
                    help="don't overwrite BENCH_scenarios.json")
    args = ap.parse_args()

    # the persistent cache always runs, at $JAX_COMPILATION_CACHE_DIR or
    # the fixed <checkout>/.jax_cache unless --compilation-cache says else
    args.compilation_cache = compilation_cache_dir(args.compilation_cache)
    cache_hit = cache_dir_is_warm(args.compilation_cache)
    results = []
    for devices in DEVICE_COUNTS:
        for name in args.scenarios.split(","):
            for n in (int(s) for s in args.sizes.split(",")):
                # drop the previous row's engine, staged data, and compiled
                # programs before timing: later rows must not inherit the
                # sweep's accumulated memory pressure (2-core containers)
                gc.collect()
                row = bench_one(name, n, args, devices)
                results.append(row)
                print(f"{name:17s} n={n:4d} dev={devices} "
                      f"rsus={row['n_rsus']} "
                      f"mode={row['mode']:12s} K={args.superstep} "
                      f"warmup={row['warmup_s']:6.1f}s "
                      f"round={row['round_s']*1e3:9.1f} ms "
                      f"({row['rounds_per_s']:.2f} rounds/s) "
                      f"handovers={row['handovers']}", flush=True)

    if (args.fault_dropout == 0.0 and args.fault_upload_loss == 0.0
            and not args.no_fault_row):
        # dedicated chaos row (DESIGN.md §13): seeded 20% dropout + 10%
        # upload loss on the first scenario at the smallest fleet, so the
        # perf gate tracks the survivor-weighted merge path too
        name = args.scenarios.split(",")[0]
        n = min(int(s) for s in args.sizes.split(","))
        gc.collect()
        row = bench_one(name, n, args, DEVICE_COUNTS[0],
                        fault_dropout=0.2, fault_upload_loss=0.1)
        results.append(row)
        print(f"{name:17s} n={n:4d} CHAOS drop=0.20 loss=0.10 "
              f"survivor_frac={row['survivor_frac']:.2f} "
              f"lost={row['lost_update_bytes']/1e6:.2f} MB "
              f"round={row['round_s']*1e3:9.1f} ms "
              f"({row['rounds_per_s']:.2f} rounds/s)", flush=True)

    api_overhead = None
    if not args.skip_api_overhead:
        fleet = (64 if 64 in [int(s) for s in args.sizes.split(",")]
                 else max(int(s) for s in args.sizes.split(",")))
        api_overhead = measure_api_overhead(args, fleet=fleet)
        print(f"api overhead @ fleet {fleet}: "
              f"{api_overhead['api_overhead_s']*1e3:+.2f} ms/round "
              f"(api {api_overhead['api_round_s']*1e3:.1f} vs direct "
              f"{api_overhead['direct_round_s']*1e3:.1f})", flush=True)

    def row_key(r):
        key = device_row_key(f"{r['scenario']}@{r['n_vehicles']}",
                             r["devices"])
        if r.get("fault_dropout") or r.get("fault_upload_loss"):
            key += "+faults"
        return key

    out = {
        "config": {"local_steps": args.local_steps, "batch": args.batch,
                   "rounds": args.rounds, "strategy": args.strategy,
                   "cloud_sync_every": args.sync,
                   "superstep": args.superstep, "schedule": args.schedule,
                   "slot_capacity": args.slot_capacity,
                   "superstep_layout": args.layout,
                   "timeit": args.timeit,
                   "wire": args.wire, "wire_k": args.wire_k,
                   "fault_dropout": args.fault_dropout,
                   "fault_upload_loss": args.fault_upload_loss,
                   "devices": list(DEVICE_COUNTS),
                   "compilation_cache": args.compilation_cache,
                   "backend": jax.default_backend(),
                   "driver": "repro.api.run"},
        "warmup_total_s": float(sum(r["warmup_s"] for r in results)),
        "compile_cache_hit": cache_hit,
        "rounds_per_s": {row_key(r): r["rounds_per_s"] for r in results},
        "api_overhead_s": (api_overhead["api_overhead_s"]
                           if api_overhead else None),
        "api_overhead": api_overhead,
        "results": results,
    }
    if not args.no_write:
        write_bench("BENCH_scenarios", out, "benchmarks/bench_scenarios.py")
        print(f"(warmup_total_s={out['warmup_total_s']:.1f}, "
              f"cache_hit={cache_hit})")

    if args.check_baseline:
        sys.exit(check_baseline(out, args.check_baseline, args.max_regress))


if __name__ == "__main__":
    main()
