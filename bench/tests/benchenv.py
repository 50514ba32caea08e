"""Small cells for the CPU tests: a checkout-like directory with its own
``BENCHMARK.json`` and a copy of the benchmark, plus tiny traffic mixes."""
from __future__ import annotations

import json
import os
import shutil
import sys

TESTS = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(TESTS)
ROOT = os.path.dirname(BENCH)
DATA = os.path.join(TESTS, "data")
for p in (BENCH, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

# the small cells' limits, from CPU readings on seeds 1-5 of the highway
# cell: the program reads loss_gap <= 1.5e-7 and delta_gap <= 5.3e-6; the
# bf16 control 3.4e-3..9.9e-3 and 0.15..0.24; half the batch left out
# 0.038..0.10 and 0.19..0.26
TINY_LIMITS = {"route_mismatch": 0, "loss_gap": 1e-3, "delta_gap": 5e-2}


TINY_CONFIG = "mlp9-tiny"


def make_root(tmp: str, cells, limits=None) -> str:
    """A root holding ``BENCHMARK.json`` with ``cells`` ((name, traffic
    file under tests/data, chips)) over the small mlp9 configuration, whose
    model and FLOP count are test fixtures under tests/data."""
    bench = os.path.join(tmp, "bench")
    shutil.copytree(BENCH, bench,
                    ignore=shutil.ignore_patterns("tests", "out",
                                                  "__pycache__"))
    for kind in ("models", "flops"):
        shutil.copytree(os.path.join(DATA, kind), os.path.join(bench, kind),
                        dirs_exist_ok=True,
                        ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(DATA, TINY_CONFIG + ".config.json"),
                os.path.join(bench, "configs", TINY_CONFIG + ".json"))
    spec = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    spec["configs"].append({"name": TINY_CONFIG,
                            "file": f"bench/configs/{TINY_CONFIG}.json"})
    spec["workloads"] = []
    for name, traffic, chips in cells:
        shutil.copy(os.path.join(DATA, traffic + ".traffic.json"),
                    os.path.join(bench, "traffic", traffic + ".json"))
        with open(os.path.join(bench, "limits", name + ".json"), "w") as f:
            json.dump(limits or TINY_LIMITS, f)
        spec["workloads"].append({"name": name, "config": TINY_CONFIG,
                                  "traffic": traffic, "chips": chips,
                                  "why": "a small cell for the CPU tests"})
    with open(os.path.join(tmp, "BENCHMARK.json"), "w") as f:
        json.dump(spec, f)
    return tmp
