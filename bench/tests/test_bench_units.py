"""The benchmark's arithmetic: FLOP counters against XLA's cost analysis,
the peaks table, and the command's refusal to run without a chip."""
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

import benchenv
from harness import layout, peaks

RESNET = layout.load_json(os.path.join(benchenv.BENCH, "configs",
                                        "resnet18-highway64.json"))
MLP = layout.load_json(os.path.join(benchenv.DATA, "mlp9-tiny.config.json"))


def _xla_flops(fn, *args):
    ca = jax.jit(fn).lower(*args).compile().cost_analysis()
    return (ca[0] if isinstance(ca, list) else ca)["flops"]


def test_resnet18_forward_flops_from_shapes():
    f = layout.load_module("flops", "resnet18")
    assert f.forward_flops_per_sample(RESNET) == 1_110_845_440


def test_resnet18_flops_agree_with_cost_analysis():
    m = layout.load_module("models", "resnet18")
    f = layout.load_module("flops", "resnet18")
    units, head = m.init(jax.random.PRNGKey(0), RESNET)

    def forward(units, head, x):
        for i, u in enumerate(units):
            x = m.apply_unit(u, x, i, RESNET)
        return jnp.mean(x, axis=(1, 2)) @ head["w"] + head["b"]

    xla = _xla_flops(forward, units, head, jnp.zeros((2, 32, 32, 3))) / 2
    inside = f.forward_flops_per_sample(RESNET, count_padding=False)
    # XLA counts the taps inside the image, plus BatchNorm/ReLU/pooling
    assert inside <= xla <= 1.015 * inside


def test_mlp9_flops_agree_with_cost_analysis():
    m = layout.load_module("models", "mlp9", benchenv.DATA)
    f = layout.load_module("flops", "mlp9", benchenv.DATA)
    units, head = m.init(jax.random.PRNGKey(0), MLP)

    def forward(units, head, x):
        for i, u in enumerate(units):
            x = m.apply_unit(u, x, i, MLP)
        return x @ head["w"] + head["b"]

    xla = _xla_flops(forward, units, head, jnp.zeros((4, 48))) / 4
    ours = f.forward_flops_per_sample(MLP)
    assert ours <= xla <= 1.02 * ours


def test_peaks_known_device():
    p = peaks.lookup("TPU v5 lite")
    assert p["bf16_flops"] == 197e12 and p["hbm_bytes_per_s"] == 819e9


@pytest.mark.parametrize("kind", ["cpu", "TPU v4", "TPU v5"])
def test_peaks_refuse_unknown_device(kind):
    with pytest.raises(KeyError):
        peaks.lookup(kind)


@pytest.mark.parametrize("workload", ["resnet18-hwy64-seq", "no-such-cell"])
def test_command_refuses_without_tpu(workload):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(benchenv.BENCH, "run.py"),
         "--workload", workload, "--seed", "3000000000", "--seconds", "1",
         "--trace", "0"], capture_output=True, text=True, env=env,
        cwd=benchenv.ROOT, timeout=120)
    assert p.returncode != 0
    assert not any(l.startswith("{") for l in p.stdout.splitlines())


def test_benchmark_json_names_every_piece():
    spec = json.load(open(os.path.join(benchenv.ROOT, "BENCHMARK.json")))
    for w in spec["workloads"]:
        cell = layout.find_cell(w["name"])
        cell.module("models", cell.config["model"])
        cell.module("flops", cell.config["model"])
        cell.module("scenarios", cell.traffic["scenario"])
        cell.module("schedules", cell.traffic["server_schedule"])
        for m in cell.per_layer:
            assert callable(cell.module("metrics", m["name"]).read)
        assert set(cell.limits) == {"route_mismatch", "loss_gap",
                                    "delta_gap"}


def test_cache_writes_beside_entries_without_access_times(tmp_path,
                                                          monkeypatch):
    """The benchmark's cache writes its programs in a directory that
    already holds entries written without a size cap (no access-time
    file beside them), as a shared cache directory does."""
    import run
    cache = tmp_path / "jax"
    cache.mkdir()
    (cache / "jit_other-0123-cache").write_bytes(b"x" * 64)
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(cache))
    jax.config.update("jax_compilation_cache_dir", str(cache))
    run.enable_cache(jax, str(tmp_path))
    jax.jit(lambda a: a * 3.0 + 1.0)(jnp.arange(7.0)).block_until_ready()
    written = [p.name for p in cache.glob("*-cache")
               if p.name != "jit_other-0123-cache"]
    assert written
