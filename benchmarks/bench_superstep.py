"""Fused super-step benchmark (ISSUE 3 acceptance): highway_corridor fleet
rounds with K-round fusion, both server schedules, AOT precompile, and the
persistent compilation cache — compared against the per-round dispatch
baseline committed in BENCH_scenarios.json.

Three questions, three measurements per fleet size:

* steady-state rounds/s — fused K-round ``lax.scan`` dispatches (both the
  paper-faithful ``sequential`` server schedule and the companion paper's
  ``parallel`` schedule, arXiv:2405.18707) vs the engine's K=1 per-round
  dispatch path (the BENCH_scenarios.json configuration);
* warmup — AOT ``precompile()`` cold, then again on a **warm persistent
  compilation cache** (a fresh engine whose ``.lower().compile()`` calls
  deserialize from disk instead of invoking XLA);
* effective rounds/s — rounds / (warmup + run), the metric the issue's
  motivation frames ("the warmup costs the equivalent of ~150 simulated
  rounds"): short fleet simulations are warmup-dominated, and the super-step
  engine's collapsed signature set + persistent cache is what moves it.

  PYTHONPATH=src python benchmarks/bench_superstep.py
  -> BENCH_superstep.json (repo root) + benchmarks/out/BENCH_superstep.json
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import jax
import numpy as np

from bench_io import write_bench
from repro.configs.base import cache_dir_is_warm, compilation_cache_dir
from repro.core import scenario
from repro.core.fedsim import ScenarioEngine, SimConfig
from repro.models.mlp_unit import MLPUnitModel, make_mlp_fleet_data

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
SCENARIO = "highway_corridor"


def _engine(n, args, superstep, schedule, slot_capacity, cache_dir,
            name=SCENARIO):
    sc = scenario.make_scenario(name, n, seed=n)
    clients, test = make_mlp_fleet_data(n, 64, 48, seed=n)
    cfg = SimConfig(scheme="asfl", adaptive_strategy="paper",
                    rounds=args.rounds, local_steps=args.local_steps,
                    batch_size=args.batch, lr=1e-3, eval_every=0,
                    round_interval_s=10.0, superstep=superstep,
                    server_schedule=schedule, slot_capacity=slot_capacity,
                    superstep_layout=args.layout,
                    compilation_cache_dir=cache_dir)
    return ScenarioEngine(MLPUnitModel(), clients, test, cfg, sc,
                          cloud_sync_every=1)


def bench_variant(n, args, superstep, schedule, slot_capacity,
                  cache_dir, name=SCENARIO) -> dict:
    """Cold precompile, warm-cache precompile (fresh engine, same disk
    cache), then a timed steady-state run with zero compile fallbacks."""
    # time precompile() alone (not engine construction / data staging) so
    # the warmup numbers are commensurable with bench_scenarios' warmup_s
    eng = _engine(n, args, superstep, schedule, slot_capacity, cache_dir,
                  name)
    t0 = time.perf_counter()
    eng.precompile()
    warmup_cold = time.perf_counter() - t0
    # a fresh engine AOT-compiles the same programs; with the persistent
    # cache populated, .lower().compile() deserializes instead of compiling
    eng = _engine(n, args, superstep, schedule, slot_capacity, cache_dir,
                  name)
    t0 = time.perf_counter()
    eng.precompile()
    warmup_warm = time.perf_counter() - t0
    eng.run()                               # staging warm-up (no compiles)
    dt = None
    for _ in range(max(args.timeit, 1)):    # min of N strips CPU noise
        eng.reset()
        t0 = time.perf_counter()
        hist = eng.run()
        rep = time.perf_counter() - t0
        dt = rep if dt is None else min(dt, rep)
    assert all(np.isfinite(m.loss) for m in hist)
    assert eng.programs.compile_fallbacks == 0
    occ = eng.occupancy_stats()
    return {
        "scenario": name, "n_vehicles": n, "superstep": superstep,
        "schedule": schedule, "slot_capacity": slot_capacity,
        "superstep_layout": occ["layout"],
        "rounds": args.rounds,
        "round_s": dt / args.rounds,
        "rounds_per_s": args.rounds / dt,
        "warmup_cold_s": warmup_cold,
        "warmup_warm_cache_s": warmup_warm,
        "effective_rounds_per_s_cold": args.rounds / (warmup_cold + dt),
        "effective_rounds_per_s_warm": args.rounds / (warmup_warm + dt),
        # occupancy accounting (DESIGN.md §12)
        "padded_slot_frac": occ["padded_slot_frac"],
        "owned_plane_frac": occ["owned_plane_frac"],
        "handovers": int(sum(m.n_handover for m in hist)),
        "final_loss": float(hist[-1].loss),
    }


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--sizes", default="64,256")
    ap.add_argument("--rounds", type=int, default=8)
    ap.add_argument("--superstep", type=int, default=8)
    ap.add_argument("--local-steps", type=int, default=2)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--schedules", default="sequential,parallel")
    ap.add_argument("--slot-capacity", default="tight8",
                    choices=["pow2", "tight8"])
    ap.add_argument("--layout", default="ragged",
                    choices=["ragged", "dense"],
                    help="super-step slot layout (DESIGN.md §12): ragged "
                         "compacts occupied slots + cut-prefix planes")
    ap.add_argument("--timeit", type=int, default=3,
                    help="timed steady-state runs per row (min wins)")
    ap.add_argument("--compilation-cache", default=None, metavar="DIR",
                    help="persistent cache dir (default: "
                         "$JAX_COMPILATION_CACHE_DIR, else <checkout>/"
                         ".jax_cache); warmup_cold_s is cold only when it "
                         "starts empty (config.compile_cache_hit)")
    ap.add_argument("--baseline", default=os.path.join(
        ROOT, "BENCH_scenarios.json"))
    args = ap.parse_args()
    assert args.superstep >= 4, "acceptance asks for super-step K>=4"

    cache_dir = compilation_cache_dir(args.compilation_cache)
    cache_hit = cache_dir_is_warm(cache_dir)
    baseline, baseline_cfg = {}, {}
    if os.path.exists(args.baseline):
        with open(args.baseline) as f:
            b = json.load(f)
        baseline = {(r["scenario"], r["n_vehicles"]): r
                    for r in b.get("results", [])
                    if r.get("devices", 1) == 1}    # single-device reference
        baseline_cfg = b.get("config", {})

    results = []
    for n in (int(s) for s in args.sizes.split(",")):
        # the K=1 per-round dispatch reference (BENCH_scenarios.json config)
        rows = [bench_variant(n, args, 1, "sequential", "pow2", cache_dir)]
        for sched in args.schedules.split(","):
            rows.append(bench_variant(n, args, args.superstep, sched,
                                      args.slot_capacity, cache_dir))
        # the skewed-load stress row (one crowded cell, sparse tail): where
        # occupancy compaction pays most — a dense table pads every RSU to
        # the crowded cell's cohort
        rows.append(bench_variant(n, args, args.superstep, "parallel",
                                  args.slot_capacity, cache_dir,
                                  name="highway_zipf"))
        dispatch = rows[0]                     # the K=1 per-round reference
        for row in rows:
            base = baseline.get((row["scenario"], n))
            row["speedup_vs_per_round_dispatch"] = \
                row["rounds_per_s"] / dispatch["rounds_per_s"]
            if base:
                row["baseline_rounds_per_s"] = base["rounds_per_s"]
                row["baseline_warmup_s"] = base["warmup_s"]
                row["speedup_rounds_per_s_vs_baseline"] = \
                    row["rounds_per_s"] / base["rounds_per_s"]
                row["warmup_reduction_vs_baseline"] = \
                    base["warmup_s"] / row["warmup_warm_cache_s"]
                row["effective_speedup_vs_baseline"] = (
                    row["effective_rounds_per_s_warm"]
                    / (base["rounds"] / (base["warmup_s"]
                                         + base["rounds"] * base["round_s"])))
            results.append(row)
            print(f"{row['scenario']} n={n:4d} K={row['superstep']} "
                  f"{row['schedule']:10s}: {row['rounds_per_s']:6.2f} r/s "
                  f"({row['speedup_vs_per_round_dispatch']:.2f}x vs K=1)  "
                  f"warmup cold {row['warmup_cold_s']:5.1f}s / warm "
                  f"{row['warmup_warm_cache_s']:5.1f}s"
                  + (f"  [{row['speedup_rounds_per_s_vs_baseline']:.2f}x r/s,"
                     f" {row['warmup_reduction_vs_baseline']:.1f}x warmup,"
                     f" {row['effective_speedup_vs_baseline']:.1f}x "
                     f"effective vs baseline]" if base else ""), flush=True)

    # acceptance summary at the largest fleet.  The committed
    # BENCH_scenarios.json baseline is itself the fused recommended
    # operating point (its config block records the superstep), so the
    # K-fusion benefit is measured against this bench's own K=1 per-round
    # dispatch row; ratios vs the baseline file are reported alongside,
    # unmasked.
    n_max = max(int(s) for s in args.sizes.split(","))
    fused = [r for r in results
             if r["n_vehicles"] == n_max and r["superstep"] >= 4]
    acceptance = {}
    if fused:
        best_tp = max(fused, key=lambda r: r["rounds_per_s"])
        acceptance = {
            "fleet": n_max,
            "rounds_per_s_ratio_vs_per_round_dispatch": {
                "value": best_tp["speedup_vs_per_round_dispatch"],
                "schedule": best_tp["schedule"], "target": 3.0},
        }
        with_base = [r for r in fused
                     if "speedup_rounds_per_s_vs_baseline" in r]
        if with_base:
            best_fb = max(with_base,
                          key=lambda r:
                          r["speedup_rounds_per_s_vs_baseline"])
            best_wu = max(with_base,
                          key=lambda r: r["warmup_reduction_vs_baseline"])
            best_ef = max(with_base,
                          key=lambda r: r["effective_speedup_vs_baseline"])
            acceptance.update({
                "rounds_per_s_ratio_vs_baseline_file": {
                    "value": best_fb["speedup_rounds_per_s_vs_baseline"],
                    "schedule": best_fb["schedule"], "target": 3.0,
                    "note": "baseline file already runs fused superstep="
                            f"{baseline_cfg.get('superstep')}"},
                "warm_warmup_reduction_vs_baseline": {
                    "value": best_wu["warmup_reduction_vs_baseline"],
                    "schedule": best_wu["schedule"], "target": 5.0},
                # rounds/(warmup+run): the amortized metric the issue's
                # motivation frames warmup in ("~150 simulated rounds")
                "effective_rounds_per_s_ratio_vs_baseline": {
                    "value": best_ef["effective_speedup_vs_baseline"],
                    "schedule": best_ef["schedule"], "target": 3.0},
            })
    def row_key(r):
        return (f"{r['scenario']}@{r['n_vehicles']}/K{r['superstep']}/"
                f"{r['schedule']}")

    out = {
        "config": {"local_steps": args.local_steps, "batch": args.batch,
                   "rounds": args.rounds, "superstep": args.superstep,
                   "slot_capacity": args.slot_capacity,
                   "superstep_layout": args.layout,
                   "timeit": args.timeit,
                   "strategy": "paper", "cloud_sync_every": 1,
                   "baseline_file": os.path.basename(args.baseline),
                   "compile_cache_hit": cache_hit,
                   "backend": jax.default_backend()},
        # top-level summary keys, schema-aligned with BENCH_scenarios.json
        # (tooling reads the same two keys off either file)
        "warmup_total_s": float(sum(r["warmup_cold_s"] for r in results)),
        "rounds_per_s": {row_key(r): r["rounds_per_s"] for r in results},
        "acceptance": acceptance,
        "results": results,
    }
    write_bench("BENCH_superstep", out, "benchmarks/bench_superstep.py")


if __name__ == "__main__":
    main()
