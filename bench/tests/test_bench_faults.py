"""The correctness check sees a broken timed path: each fault that a
training cell can have, planted in the program underneath a whole run
(chip check skipped), turns ``correct`` false."""
import json
import os
import subprocess
import sys

import pytest

import benchenv
import run

CELLS = [("tiny-hwy", "tiny-hwy-seq", 1)]


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return benchenv.make_root(str(tmp_path_factory.mktemp("bench")), CELLS)


def run_cell(root, capsys):
    rc = run.main(["--workload", "tiny-hwy", "--seed", "11", "--seconds",
                   "1", "--trace", "0"], require_chip=False, root=root)
    return rc, json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def test_state_left_unchanged(root, capsys, monkeypatch):
    from repro import optim
    monkeypatch.setattr(optim, "apply_updates", lambda params, upd: params)
    rc, res = run_cell(root, capsys)
    assert rc == 0 and res["correct"] is False
    assert res["checks"]["delta_gap"]["value"] > 0.9


def test_half_batch_left_out(root, capsys, monkeypatch):
    from repro.models.mlp_unit import MLPUnitModel
    full = MLPUnitModel.head_loss

    def half(self, head, feats, labels):
        n = feats.shape[0] // 2
        return full(self, head, feats[:n], labels[:n])

    monkeypatch.setattr(MLPUnitModel, "head_loss", half)
    rc, res = run_cell(root, capsys)
    assert rc == 0 and res["correct"] is False


MESH = """
import json, os, sys
sys.path.insert(0, {tests!r})
import benchenv, run, calibrate
root = benchenv.make_root({tmp!r}, [("tiny-grid", "tiny-city-grid4", 4)])
args = ["--workload", "tiny-grid", "--seed", "4", "--seconds", "1",
        "--trace", "0"]
run.main(args, require_chip=False, root=root)
with calibrate.no_exchange():
    run.main(args, require_chip=False, root=root)
"""


def test_exchange_between_chips_left_out(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    code = MESH.format(tests=benchenv.TESTS, tmp=str(tmp_path))
    p = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, env=env, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    sound, broken = [json.loads(l) for l in p.stdout.splitlines()
                     if l.startswith("{")]
    assert sound["correct"] is True and sound["device"]["count"] == 4
    assert broken["correct"] is False
