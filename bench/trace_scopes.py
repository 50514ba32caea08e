"""One traced run of a cell that also says where the window's time went by
the program's own names.

  python3 bench/trace_scopes.py --workload <cell> --seed <n> --seconds <s>

It is ``bench/run.py --trace 1`` with the same result line, plus two
lines before it: ``scopes:`` the fullest device's op time per window, its
share of the op time and the op count per window of every ``asfl.*`` scope
(``(no scope)`` for ops that carry none), with the six device groups of
``harness/scopes.py``; and ``counters:`` the engine's counters over the
timed window, per window (``null`` for a program without them).
``bench/run.py`` reads its trace and then deletes the profile, so this
command reads the op scopes from the profile before that.
"""
from __future__ import annotations

import json
import os
import sys

BENCH = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, BENCH)

import run  # noqa: E402
from harness import fleet, scopes, trace as T  # noqa: E402


def scope_line(trace) -> str:
    rows = scopes.device_ns_per_window(trace) or {}
    total = sum(r["ns"] for r in rows.values())
    table = {k: {"ms": r["ns"] / 1e6, "share_pct": 100 * r["ns"] / total,
                 "ops": r["ops"]}
             for k, r in sorted(rows.items(), key=lambda kv: -kv[1]["ns"])}
    return json.dumps({"per_window": table,
                       "groups_ms": scopes.device_ms(trace)})


def main(argv=None, **kw) -> int:
    argv = list(sys.argv[1:] if argv is None else argv)
    load, timed_window = T.load, fleet.timed_window

    def annotated(path):
        tr = load(path)
        print(f"scopes: {scope_line(scopes.annotate(tr, path))}",
              flush=True)
        return tr

    def counted(engine, *a, **k):
        # a program without engine counters prints ``counters: null``
        before = dict(getattr(engine, "counters", {}))
        win = timed_window(engine, *a, **k)
        n = max(len(win["window_s"]), 1)
        after = getattr(engine, "counters", None)
        print("counters: " + json.dumps(
            None if after is None else
            {c: (v - before[c]) / n for c, v in after.items()}), flush=True)
        return win

    T.load, fleet.timed_window = annotated, counted
    try:
        return run.main(argv + ["--trace", "1"], **kw)
    finally:
        T.load, fleet.timed_window = load, timed_window


if __name__ == "__main__":
    sys.exit(main())
