"""Whole runs of small cells on the CPU, with the chip check skipped: the
result line, the correctness check against the plain reference, and the
bfloat16 control that check has to refuse."""
import json
import os

import jax.numpy as jnp
import pytest

import benchenv
import run
from harness import check, layout, reference

CELLS = [("tiny-hwy", "tiny-hwy-seq", 1), ("tiny-city", "tiny-city-par", 1)]


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return benchenv.make_root(str(tmp_path_factory.mktemp("bench")), CELLS)


def run_cell(root, capsys, name, seed, trace=0):
    rc = run.main(["--workload", name, "--seed", str(seed), "--seconds",
                   "1", "--trace", str(trace)], require_chip=False,
                  root=root)
    out = capsys.readouterr().out.strip().splitlines()
    return rc, json.loads(out[-1])


@pytest.mark.parametrize("name", [c[0] for c in CELLS])
def test_small_cell_is_correct(root, capsys, name):
    rc, res = run_cell(root, capsys, name, 2 ** 31 + 12345)
    assert rc == 0 and res["correct"] is True
    assert list(res)[:5] == ["correct", "attempted", "failed", "metrics",
                             "device"]
    assert list(res)[-1] == "checks"
    assert set(res["metrics"]) == {"rounds_per_s", "peak_hbm_gb",
                                   "setup_s"}
    assert res["attempted"] > 0 and res["failed"] == 0
    assert res["checks"]["route_mismatch"]["value"] == 0


@pytest.mark.parametrize("name", [c[0] for c in CELLS])
def test_bf16_control_fails(root, name):
    cell = layout.find_cell(name, root)
    seed = 7
    data, p0, engine = run.prepare(cell, seed)
    del engine
    numbers, _ = run.reference_numbers(cell, data, p0, None, None, seed,
                                       dtype=jnp.bfloat16)
    assert not check.judge(numbers, cell.limits)


def test_added_cell_and_metric_need_no_edit(tmp_path, capsys):
    """A new traffic mix, limits file and per-layer metric, each a new
    file plus a BENCHMARK.json entry, are found by name."""
    root = benchenv.make_root(str(tmp_path), [("added", "tiny-hwy-seq", 1)])
    with open(os.path.join(root, "bench", "metrics",
                           "rounds_traced.py"), "w") as f:
        f.write("def read(ctx):\n    return float(len(ctx['records']))\n")
    spec = json.load(open(os.path.join(root, "BENCHMARK.json")))
    spec["per_layer"].append({"name": "rounds_traced", "unit": "rounds",
                              "better": "higher", "source": "host_clock",
                              "layer": "engine host",
                              "moves": "rounds_per_s"})
    json.dump(spec, open(os.path.join(root, "BENCHMARK.json"), "w"))
    rc, res = run_cell(root, capsys, "added", 5, trace=1)
    assert rc == 0 and res["correct"] is True
    assert res["metrics"]["rounds_traced"]["value"] > 0
    assert "rounds_per_s" not in res["metrics"]


@pytest.mark.parametrize("dtype,expect", [(jnp.float32, "highest"),
                                          (jnp.bfloat16, None)])
def test_reference_precision(root, monkeypatch, dtype, expect):
    """The float32 reference traces its matmuls and convolutions at the
    highest precision; the bfloat16 control at the default."""
    import jax
    cell = layout.find_cell("tiny-hwy", root)
    model = cell.module("models", cell.config["model"])
    seen = []

    def loss(*a):
        seen.append(jax.config.jax_default_matmul_precision)
        return model.loss(*a)

    monkeypatch.setattr(cell, "module", lambda kind, name: (
        type("M", (), {"loss": staticmethod(loss)}) if kind == "models"
        else layout.load_module(kind, name, cell.bench_dir)))
    data = model.make_data(0, cell.traffic["n_vehicles"],
                           cell.traffic["per_vehicle_samples"], 8,
                           cell.config)
    units, head = model.init(jax.random.PRNGKey(0), cell.config)
    reference.run(cell, data, units, head, 0, 1, dtype=dtype)
    assert seen and set(seen) == {expect}
