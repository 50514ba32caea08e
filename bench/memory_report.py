"""Device memory of a cell's programs beside the allocator's peak.

  python3 bench/memory_report.py --workload <cell> --seed <n>

Builds the cell as ``bench/run.py`` does, precompiles its horizon and runs
the warm window, then prints, for each compiled window program,
``memory_analysis()`` (argument, output, alias, temporary and generated
code bytes), and the fullest device's ``memory_stats()``.  The allocator's
``peak_bytes_in_use`` is what ``peak_hbm_gb`` reports; the analysis is what
the compiler planned for one window.  One JSON line per program and one for
the device go to stdout.  Needs the chip, like ``bench/run.py``.
"""
from __future__ import annotations

import argparse
import json
import sys

import run  # noqa: E402  (puts bench/ and src/ on the path)

from harness import layout  # noqa: E402

FIELDS = ("argument_size_in_bytes", "output_size_in_bytes",
          "alias_size_in_bytes", "temp_size_in_bytes",
          "generated_code_size_in_bytes", "peak_memory_in_bytes")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    args = ap.parse_args(argv)
    import jax
    cell = layout.find_cell(args.workload, run.ROOT)
    if jax.devices()[0].platform != "tpu" \
            or len(jax.devices()) < cell.chips:
        print(f"memory_report: {cell.name} needs {cell.chips} TPU chip(s)",
              file=sys.stderr)
        return 2
    run.enable_cache(jax)
    _, _, engine = run.prepare(cell, args.seed)
    _, _, _, sigs = run.warm_window(engine, cell.traffic["superstep"])
    for sig in sigs:
        ma = engine.programs.get(sig).memory_analysis()
        print(json.dumps({"cell": cell.name, "program": repr(sig),
                          **{f: getattr(ma, f, None) for f in FIELDS}}),
              flush=True)
    stats = [d.memory_stats() or {} for d in jax.local_devices()]
    full = max(stats, key=lambda s: s.get("peak_bytes_in_use", 0))
    print(json.dumps({"cell": cell.name, "memory_stats": full}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
