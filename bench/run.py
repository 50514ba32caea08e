"""One run of one benchmark cell on the chips of this machine.

  python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell (``BENCHMARK.json``) names a configuration and a traffic mix; the
run makes the fleet data and the initial weights from ``--seed``, builds
the program's ``ScenarioEngine`` from them, precompiles the planned
horizon and runs one warm window (all of that is ``setup_s``), then runs
super-step windows back to back for ``--seconds`` (``rounds_per_s``).
After the window it reads the peak device memory, frees the engine, and
replays the warm window with the plain reference to decide ``correct``.

With ``--trace 1`` the window runs under the profiler (for at most the
traffic's ``trace_seconds``) and the result carries the per-layer metrics
instead of the end-to-end ones.  The last line of stdout is the result;
the numbers compared, each with its limit, are the last lines of stderr.
Without a TPU, or with fewer chips than the cell asks for, the run exits 2
before doing anything and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

import numpy as np  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, os.path.join(ROOT, "src"))
sys.path.insert(0, BENCH)

from harness import layout  # noqa: E402


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def enable_cache(jax, root: str = ROOT) -> str:
    """JAX's persistent compilation cache, as the program sets it up:
    ``$JAX_COMPILATION_CACHE_DIR`` when set, else the fixed
    ``<checkout>/.jax_cache``; with no cap on its size."""
    from jax.experimental.compilation_cache import compilation_cache
    from repro.configs.base import enable_compilation_cache
    d = enable_compilation_cache(os.path.join(root, ".jax_cache"))
    # The fused resnet18 window is one executable of about 1.07 GB, over
    # the chip machines' default cap of 192 MiB.  Any cap turns on JAX's
    # eviction, which reads an access-time file beside every entry and
    # fails each write in a directory whose entries have none, as a cache
    # shared with uncapped writers has: then every run compiles again.
    # Uncapped, the cache grows only by what compiles: one window
    # executable per cell, since the traffic fixes the data it embeds.
    jax.config.update("jax_compilation_cache_max_size", -1)
    compilation_cache.reset_cache()
    return d


def dir_bytes(d: str) -> int:
    return sum(os.path.getsize(os.path.join(p, f))
               for p, _, fs in os.walk(d) for f in fs)


def prepare(cell, seed: int):
    """The fleet data, the initial weights from the seed, and the engine
    built on them.  The data comes from the traffic's ``data_seed``: the
    program compiles the fleet data into its fused window as a constant,
    so data drawn per run would compile (and cache) a new program in
    every run."""
    import jax
    from harness import fleet
    cfg, tr = cell.config, cell.traffic
    model = cell.module("models", cfg["model"])
    data = model.make_data(tr["data_seed"], tr["n_vehicles"],
                           tr["per_vehicle_samples"], tr["test_samples"], cfg)
    key = jax.random.fold_in(jax.random.PRNGKey(seed % 2 ** 31), 1)
    units, head = jax.jit(lambda k: model.init(k, cfg))(key)
    p0 = fleet.host_tree(units, head)
    engine = fleet.build_engine(fleet.make_spec(cell), data, units, head)
    return data, p0, engine


def warm_window(engine, k: int):
    """Precompile the planned horizon, then the first window: the rounds
    the reference replays."""
    from harness import fleet
    t0 = time.perf_counter()
    sigs = engine.precompile()
    compile_s = time.perf_counter() - t0
    records = [fleet.round_record(m) for m in engine.run_superstep(0, k)]
    return records, fleet.host_tree(engine.units, engine.head), compile_s, \
        sigs


def reference_numbers(cell, data, p0, records, prog_params, seed: int,
                      dtype=None, half_batch: bool = False):
    """The reference replay of the warm window, compared with what the
    program produced (or, for the control, with what ``dtype`` gives)."""
    import jax.numpy as jnp
    from harness import check, fleet, reference
    k = cell.traffic["superstep"]
    pseed = fleet.program_seed(cell)
    ref = reference.run(cell, data, p0[0], p0[1], pseed, k)
    if dtype is None and not half_batch:
        return check.compare(records, ref, p0, prog_params), ref
    alt = reference.run(cell, data, p0[0], p0[1], pseed, k,
                        dtype=dtype or jnp.float32, half_batch=half_batch)
    alt_records = [{"loss": l, "cuts": st["cuts"],
                    "rsu_loads": list(np.bincount(
                        st["serving"][st["cuts"] > 0],
                        minlength=ref["n_rsus"])),
                    "n_handover": st["n_handover"]}
                   for l, st in zip(alt["losses"], alt["states"])]
    return check.compare(alt_records, ref, p0,
                         (alt["units"], alt["head"])), ref


def trace_metrics(cell, tdir, win, occ, peak):
    from harness import trace as T
    path = T.latest_xplane(tdir)
    tr = T.load(path)
    T.save(tr, os.path.join(tdir, "trace.json.gz"))
    shutil.rmtree(os.path.join(tdir, "plugins"), ignore_errors=True)
    cfg = cell.config
    flops = cell.module("flops", cfg["model"])
    n_windows = len(win["window_s"])
    ctx = {"trace": tr, "records": win["records"],
           "executed_slots": occ["executed_slots"],
           "forward_flops_per_sample": flops.forward_flops_per_sample(cfg),
           "samples_per_vehicle": (cfg["local_steps"]
                                   * cfg["train"]["batch_size"]),
           "eval_samples": (cell.traffic["test_samples"] * n_windows
                            if cfg["train"]["eval_every"] else 0),
           "chips": cell.chips, "peak_flops": peak["bf16_flops"]}
    metrics = {}
    for m in cell.per_layer:
        v = cell.module("metrics", m["name"]).read(ctx)
        if v is not None:
            metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    times = T.device_times(tr)
    breakdown = {"device_ops": T.top_ops(tr), "idle_gaps": T.idle_gaps(tr)}
    return metrics, times, breakdown


def run_cell(cell, args, info, peak) -> dict:
    import jax
    from harness import check, fleet
    tr = cell.traffic
    k, horizon = tr["superstep"], tr["horizon_rounds"]
    data, p0, engine = prepare(cell, args.seed)
    records, prog_params, compile_s, sigs = warm_window(engine, k)
    setup_s = time.perf_counter() - T_START
    print(f"set-up: {setup_s:.3f} s (precompile {compile_s:.3f} s, "
          f"{len(sigs)} signature(s): {sigs})", flush=True)
    tdir = os.path.join(cell.bench_dir, "out", cell.name, "trace")
    seconds = args.seconds
    if args.trace:
        shutil.rmtree(tdir, ignore_errors=True)
        os.makedirs(tdir)
        seconds = min(seconds, float(tr["trace_seconds"]))
        jax.profiler.start_trace(tdir)
    try:
        win = fleet.timed_window(engine, k, k, horizon, seconds,
                                 annotate=bool(args.trace))
    finally:
        if args.trace:
            jax.profiler.stop_trace()
    mem = fleet.peak_bytes()
    occ = engine.occupancy_stats()
    fallbacks = engine.programs.compile_fallbacks
    done = len(win["records"])
    print(f"window: {done} rounds in {win['elapsed_s']:.6f} s over "
          f"{len(win['window_s'])} windows; compile events in the window: "
          f"{win['compiles']}; compile fallbacks: {fallbacks}; horizon "
          f"reached: {win['horizon_reached']} (planned {horizon})",
          flush=True)
    print(f"window seconds: {win['window_s']}", flush=True)
    sched = [r["n_scheduled"] for r in win["records"]]
    print(f"occupancy: executed slots {occ['executed_slots']}, slot "
          f"capacity {occ['slot_capacity']}, scheduled per round "
          f"min {min(sched, default=0)} max {max(sched, default=0)}",
          flush=True)
    print(f"warm-window losses: {[r['loss'] for r in records]}")
    print(f"window losses: {[r['loss'] for r in win['records']]}",
          flush=True)
    del engine
    gc.collect()
    metrics, times, breakdown = {}, None, None
    if args.trace:
        t0 = time.perf_counter()
        metrics, times, breakdown = trace_metrics(cell, tdir, win, occ, peak)
        print(f"trace read and reduced in {time.perf_counter() - t0:.3f} s",
              flush=True)
    else:
        e2e = {"rounds_per_s": done / win["elapsed_s"] if done else 0.0,
               "peak_hbm_gb": mem / 1e9, "setup_s": setup_s}
        for m in cell.end_to_end:
            metrics[m["name"]] = {"value": float(e2e[m["name"]]),
                                  "unit": m["unit"]}
    t0 = time.perf_counter()
    numbers, _ = reference_numbers(cell, data, p0, records, prog_params,
                                   args.seed)
    print(f"reference: {time.perf_counter() - t0:.3f} s for {k} rounds",
          flush=True)
    correct = check.judge(numbers, cell.limits)
    device = dict(info, memory_peak_bytes=mem)
    if times is not None:
        device["busy_s"], device["window_s"] = times
    out = {"correct": bool(correct), "attempted": win["attempted"],
           "failed": win["failed"], "metrics": metrics, "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = {n: {"value": numbers[n], "limit": cell.limits[n]}
                     for n in cell.limits}
    return out


def main(argv=None, require_chip: bool = True, root: str = ROOT) -> int:
    """``require_chip=False`` and ``root`` let the CPU tests drive a run
    of a small cell."""
    args = parse(argv)
    try:
        cell = layout.find_cell(args.workload, root)
    except Exception as e:  # no BENCHMARK.json, unknown cell, missing file
        print(f"bench: {type(e).__name__}: {e}", file=sys.stderr)
        return 2
    import jax
    devs = jax.devices()
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
    print(f"platform={info['platform']} device_kind={info['kind']} "
          f"device_count={info['count']} jax={jax.__version__}", flush=True)
    if require_chip and (info["platform"] != "tpu"
                         or info["count"] < cell.chips):
        print(f"bench: cell {cell.name} needs {cell.chips} TPU chip(s); "
              f"found {info}; nothing was run", file=sys.stderr)
        return 2
    from harness import peaks
    try:
        peak = peaks.lookup(info["kind"])
    except KeyError as e:
        if require_chip:
            print(f"bench: {e}", file=sys.stderr)
            return 2
        peak = {"bf16_flops": float("nan")}
    cache = enable_cache(jax, root)
    print(f"compilation cache: {cache} ({dir_bytes(cache)} bytes before "
          f"the run)", flush=True)
    out = run_cell(cell, args, info, peak)
    print(f"compilation cache: {dir_bytes(cache)} bytes after the run")
    print(f"correct: {out['correct']}", file=sys.stderr)
    for name, c in out["checks"].items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
