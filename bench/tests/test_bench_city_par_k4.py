"""The parallel-schedule city cell: a small copy of its traffic run whole
on the CPU, the real cell's traffic as the table sets it, and which
per-layer metrics the cell reports."""
import json
import re

import pytest

import benchenv
import run
from harness import layout

CELL = "resnet18-city-par"
TINY = "tiny-city-par-k4"

# what the real cell's traffic mix (bench/traffic/par-city-k4.json) sets:
# the paper's parallel schedule over a 4 x 4 city, 56 vehicles, since the
# window program of 128, 96 or 64 over this city needs more than one chip's
# memory
TRAFFIC = {
    "scenario": "city",
    "scenario_kwargs": {"seed": 4, "grid_x": 4, "grid_y": 4},
    "n_vehicles": 56, "per_vehicle_samples": 892,
    "server_schedule": "parallel", "superstep": 4,
    "superstep_layout": "ragged", "slot_capacity": "tight8",
    "churn_source": "mobility",
}


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    """A root whose cell of this name runs the small traffic over the
    small mlp9 configuration, so that the per-layer metrics that list the
    real cell apply to it."""
    return benchenv.make_root(str(tmp_path_factory.mktemp("bench")),
                              [(CELL, TINY, 1)])


def run_cell(root, capsys, seed, trace):
    rc = run.main(["--workload", CELL, "--seed", str(seed), "--seconds",
                   "1", "--trace", str(trace)], require_chip=False,
                  root=root)
    out = capsys.readouterr().out.strip().splitlines()
    compiles = [int(m.group(1)) for line in out for m in [re.search(
        r"compile events in the window: (\d+)", line)] if m]
    return rc, json.loads(out[-1]), compiles


@pytest.mark.parametrize("trace", [0, 1])
def test_small_city_par_cell_is_correct(root, capsys, trace):
    rc, res, compiles = run_cell(root, capsys, 2 ** 31 + 4242 + trace,
                                 trace)
    assert rc == 0 and res["correct"] is True
    assert compiles == [0]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert res["checks"]["route_mismatch"]["value"] == 0
    cell = layout.find_cell(CELL, root)
    if trace:
        # the CPU profile has no device plane, so the device readers find
        # nothing and leave their metrics out; the slot counter reads the
        # compacted slot axis of the parallel schedule
        assert set(res["metrics"]) <= {m["name"] for m in cell.per_layer}
        occ = res["metrics"]["slot_occupancy_pct"]["value"]
        assert 0 < occ <= 100
    else:
        assert set(res["metrics"]) == {m["name"] for m in cell.end_to_end}


def test_real_cell_traffic():
    cell = layout.find_cell(CELL)
    assert cell.chips == 1
    assert cell.config["name"] == "resnet18-highway64"
    assert {k: cell.traffic[k] for k in TRAFFIC} == TRAFFIC
    # nearly the highway cell's data: 49,952 of CIFAR-10's 50,000 images
    assert TRAFFIC["n_vehicles"] * TRAFFIC["per_vehicle_samples"] == 49952


def test_per_layer_metrics_of_each_cell():
    spec = json.load(open(benchenv.ROOT + "/BENCHMARK.json"))
    lists = {m["name"]: m.get("workloads") for m in spec["per_layer"]}
    both = ["resnet18-hwy64-seq", CELL]
    # the ragged parallel schedule executes its compacted slot axis, the
    # engine spans run on every schedule, and the trace has the device
    for name in ("device_idle_pct", "window_gap_ms", "superstep_mfu",
                 "slot_occupancy_pct", "pull_idle_ms", "metrics_idle_ms",
                 "eval_idle_ms", "other_idle_ms"):
        assert lists[name] == both, name
    for w in spec["workloads"]:
        assert any(w["name"] in (ws or [w["name"]])
                   for ws in lists.values())


def test_bf16_control_fails(root):
    import jax.numpy as jnp
    from harness import check
    cell = layout.find_cell(CELL, root)
    data, p0, engine = run.prepare(cell, 8)
    del engine
    numbers, _ = run.reference_numbers(cell, data, p0, None, None, 8,
                                       dtype=jnp.bfloat16)
    assert not check.judge(numbers, cell.limits)


def _unchanged(monkeypatch):
    from repro import optim
    monkeypatch.setattr(optim, "apply_updates", lambda params, upd: params)


def _half_batch(monkeypatch):
    from repro.models.mlp_unit import MLPUnitModel
    full = MLPUnitModel.head_loss

    def half(self, head, feats, labels):
        n = feats.shape[0] // 2
        return full(self, head, feats[:n], labels[:n])

    monkeypatch.setattr(MLPUnitModel, "head_loss", half)


@pytest.mark.parametrize("plant", [_unchanged, _half_batch],
                         ids=["state_left_unchanged", "half_batch_left_out"])
def test_planted_fault_is_not_correct(root, capsys, monkeypatch, plant):
    plant(monkeypatch)
    rc, res, _ = run_cell(root, capsys, 13, 0)
    assert rc == 0 and res["correct"] is False
