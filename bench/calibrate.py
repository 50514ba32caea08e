"""Readings that the correctness limits of a cell are set from.

  python3 bench/calibrate.py --workload <cell> --seeds 1,2,3 \
      [--control] [--faults half_batch,no_exchange]

For each seed, in one process: the program's warm window against the
reference (the lower reading), and optionally the control (the reference
in bfloat16, in the program's place) and planted faults against the
reference (the upper readings).  Faults: ``half_batch`` is the reference
with half of every batch left out; ``no_exchange`` is the program with the
cross-chip sums of its fused step taken out (mesh cells).  One JSON line
per reading goes to stdout and to ``bench/out/<cell>/calibrate.jsonl``.
"""
from __future__ import annotations

import argparse
import contextlib
import gc
import json
import os
import sys
import time
import types

import run  # noqa: E402  (puts bench/ and src/ on the path)

from harness import layout  # noqa: E402


@contextlib.contextmanager
def no_exchange():
    """The fused step's cross-chip sums (``psum``) return the local
    partials."""
    from repro.core import superstep
    real = superstep.lax
    fake = types.SimpleNamespace(**{k: getattr(real, k) for k in dir(real)
                                    if not k.startswith("__")})
    fake.psum = lambda x, axis_name, **kw: x
    superstep.lax = fake
    try:
        yield
    finally:
        superstep.lax = real


def program_reading(cell, seed, plant=None):
    ctx = plant() if plant else contextlib.nullcontext()
    with ctx:
        data, p0, engine = run.prepare(cell, seed)
        records, prog, _, _ = run.warm_window(engine, cell.traffic[
            "superstep"])
    del engine
    gc.collect()
    numbers, _ = run.reference_numbers(cell, data, p0, records, prog, seed)
    return numbers, data, p0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control", action="store_true")
    ap.add_argument("--faults", default="")
    args = ap.parse_args(argv)
    import jax
    cell = layout.find_cell(args.workload, run.ROOT)
    if jax.devices()[0].platform != "tpu" \
            or len(jax.devices()) < cell.chips:
        print(f"calibrate: {cell.name} needs {cell.chips} TPU chip(s)",
              file=sys.stderr)
        return 2
    run.enable_cache(jax)
    faults = [f for f in args.faults.split(",") if f]
    out_dir = os.path.join(run.BENCH, "out", cell.name)
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, "calibrate.jsonl"), "a") as log:
        for seed in [int(s) for s in args.seeds.split(",")]:
            readings(cell, seed, args.control, faults, log)
    return 0


def readings(cell, seed, control, faults, log):
    import jax.numpy as jnp

    def emit(kind, numbers, t0):
        line = json.dumps({"cell": cell.name, "kind": kind, "seed": seed,
                           "seconds": time.perf_counter() - t0, **numbers})
        print(line, flush=True)
        log.write(line + "\n")
        log.flush()

    t0 = time.perf_counter()
    numbers, data, p0 = program_reading(cell, seed)
    emit("program", numbers, t0)
    if control:
        t0 = time.perf_counter()
        numbers, _ = run.reference_numbers(cell, data, p0, None, None, seed,
                                           dtype=jnp.bfloat16)
        emit("control_bf16", numbers, t0)
    if "half_batch" in faults:
        t0 = time.perf_counter()
        numbers, _ = run.reference_numbers(cell, data, p0, None, None, seed,
                                           half_batch=True)
        emit("fault_half_batch", numbers, t0)
    if "no_exchange" in faults:
        t0 = time.perf_counter()
        numbers, _, _ = program_reading(cell, seed, plant=no_exchange)
        emit("fault_no_exchange", numbers, t0)
    del data
    gc.collect()


if __name__ == "__main__":
    sys.exit(main())
