"""The trace reducers, on a hand-built trace whose answers are known and
on a trace recorded on a TPU v5e."""
import os

import pytest

import benchenv
from harness import layout
from harness import trace as T

MS = 1_000_000


def _synthetic():
    # two windows of 10 ms on two devices; TPU:0 is the busier one
    host = [[0, 10 * MS, T.WINDOW], [12 * MS, 10 * MS, T.WINDOW],
            [9 * MS, 4 * MS, "host pull"], [10 * MS, 1 * MS, "metrics"]]
    dev0 = [[1 * MS, 3 * MS, "while.1"],            # spans the ops below
            [1 * MS, 2 * MS, "fusion.1 kOutput"],
            [3 * MS, 1 * MS, "all-reduce.2"],
            [5 * MS, 4 * MS, "convolution.3"],
            [13 * MS, 6 * MS, "fusion.1 kOutput"]]
    dev1 = [[2 * MS, 1 * MS, "fusion.1 kOutput"]]
    return {"devices": {"TPU:0": dev0, "TPU:1": dev1}, "host": host}


def _ctx(tr):
    return {"trace": tr}


def test_bounds_and_busy():
    tr = _synthetic()
    assert T.bounds(tr) == (0, 22 * MS)
    assert T.fullest(tr) == "TPU:0"
    busy, window = T.device_times(tr)
    assert window == pytest.approx(0.022)
    assert busy == pytest.approx((13 + 1) / 2 / 1e3)


def test_device_idle_pct():
    read = layout.load_module("metrics", "device_idle_pct").read
    assert read(_ctx(_synthetic())) == pytest.approx(100 * (1 - 13 / 22))


def test_window_gap_ms():
    read = layout.load_module("metrics", "window_gap_ms").read
    # TPU:0 idles 22 - 13 = 9 ms over the two windows
    assert read(_ctx(_synthetic())) == pytest.approx(4.5)


def test_collective_pct():
    read = layout.load_module("metrics", "collective_pct").read
    assert read(_ctx(_synthetic())) == pytest.approx(100 * 1 / 22)
    tr = _synthetic()
    tr["devices"]["TPU:0"] = [e for e in tr["devices"]["TPU:0"]
                              if "all-reduce" not in e[2]]
    assert read(_ctx(tr)) is None


def test_breakdown():
    tr = _synthetic()
    ops = dict(T.top_ops(tr))
    assert "while.1" not in ops
    assert ops["fusion.1 kOutput"] == pytest.approx(0.008)
    gaps = T.idle_gaps(tr)
    # the longest gap, 9-13 ms, falls in the host pull; then 19-22, 0-1
    assert gaps[0] == ["host pull", pytest.approx(0.004)]
    assert [g[1] for g in gaps] == pytest.approx([0.004, 0.003, 0.001,
                                                  0.001])


def test_readers_find_nothing_without_a_device():
    tr = {"devices": {}, "host": [[0, MS, T.WINDOW]]}
    for name in ("device_idle_pct", "window_gap_ms", "collective_pct"):
        assert layout.load_module("metrics", name).read(_ctx(tr)) is None
    ctx = {"trace": tr, "records": [{"n_scheduled": 4}],
           "forward_flops_per_sample": 1, "samples_per_vehicle": 1,
           "eval_samples": 0, "chips": 1, "peak_flops": 1.0}
    assert layout.load_module("metrics", "superstep_mfu").read(ctx) is None


# two windows of the resnet18 cell on a TPU v5e (its operations of 0.1 ms
# or more, and its host spans of 0.2 ms or more)
RECORDED = os.path.join(benchenv.DATA, "tpu_v5e_trace.json.gz")


def test_recorded_trace():
    tr = T.read(RECORDED)
    busy, window = T.device_times(tr)
    assert 0 < busy <= window
    idle = layout.load_module("metrics", "device_idle_pct").read(_ctx(tr))
    assert 0 <= idle < 100
    gap = layout.load_module("metrics", "window_gap_ms").read(_ctx(tr))
    assert 0 < gap < 1e3 * window / len(T.windows(tr))
    assert len(T.top_ops(tr)) == 10 and len(T.idle_gaps(tr)) == 10
