"""The DeepSeek-V2-Lite split LoRA cell: a small copy of it run whole on the
CPU, its window-share metric on a synthetic trace, and the real cell's
configuration and traffic as the table sets them."""
import json
import os
import re
import shutil

import pytest

import benchenv
import run
from harness import layout

CELL = "dsv2lite-lora-hwy16-seq"
CONFIG = "dsv2-lite-splitlora"
TINY_CONFIG, TINY_TRAFFIC = "dsv2-tiny", "tiny-lm-hwy-seq"
# the small cell's limits, from CPU readings on seeds 1-2: the program
# reads loss_gap <= 6.6e-8 and delta_gap <= 2.8e-6; the bf16 control
# 6.5e-5..2.2e-4 and 0.0037..0.0069; half of each batch left out
# 0.0069..0.0072 and 0.086..0.122
TINY_LIMITS = {"route_mismatch": 0, "loss_gap": 1e-5, "delta_gap": 1e-3}

TRAFFIC = {
    "scenario": "highway_corridor", "scenario_kwargs": {"seed": 4},
    "n_vehicles": 16, "per_vehicle_samples": 32, "test_samples": 8,
    "server_schedule": "sequential", "superstep": 2,
    "superstep_layout": "ragged", "slot_capacity": "tight8",
    "churn_source": "markov", "horizon_rounds": 256, "trace_seconds": 5,
}


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    """A root whose cell of this name runs the small traffic over the
    small configuration (the program's reduced widths)."""
    tmp = str(tmp_path_factory.mktemp("bench"))
    bench = os.path.join(tmp, "bench")
    shutil.copytree(benchenv.BENCH, bench, ignore=shutil.ignore_patterns(
        "tests", "out", "__pycache__"))
    shutil.copy(os.path.join(benchenv.DATA, TINY_CONFIG + ".config.json"),
                os.path.join(bench, "configs", TINY_CONFIG + ".json"))
    shutil.copy(os.path.join(benchenv.DATA, TINY_TRAFFIC + ".traffic.json"),
                os.path.join(bench, "traffic", TINY_TRAFFIC + ".json"))
    with open(os.path.join(bench, "limits", CELL + ".json"), "w") as f:
        json.dump(TINY_LIMITS, f)
    spec = json.load(open(os.path.join(benchenv.ROOT, "BENCHMARK.json")))
    spec["configs"].append({"name": TINY_CONFIG,
                            "file": f"bench/configs/{TINY_CONFIG}.json"})
    spec["workloads"] = [{"name": CELL, "config": TINY_CONFIG,
                          "traffic": TINY_TRAFFIC, "chips": 1,
                          "why": "a small cell for the CPU tests"}]
    with open(os.path.join(tmp, "BENCHMARK.json"), "w") as f:
        json.dump(spec, f)
    return tmp


@pytest.mark.parametrize("trace", [0, 1])
def test_small_lm_cell_is_correct(root, capsys, trace):
    rc = run.main(["--workload", CELL, "--seed", str(2 ** 31 + 77 + trace),
                   "--seconds", "1", "--trace", str(trace)],
                  require_chip=False, root=root)
    out = capsys.readouterr().out.strip().splitlines()
    res = json.loads(out[-1])
    compiles = [int(m.group(1)) for line in out for m in [re.search(
        r"compile events in the window: (\d+)", line)] if m]
    assert rc == 0 and res["correct"] is True, res["checks"]
    assert compiles == [0]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert res["checks"]["route_mismatch"]["value"] == 0
    cell = layout.find_cell(CELL, root)
    names = {m["name"] for m in (cell.per_layer if trace
                                 else cell.end_to_end)}
    # the CPU profile has no device plane: the device readers find nothing
    assert set(res["metrics"]) <= names if trace \
        else set(res["metrics"]) == names


def test_lora_window_mfu_reads_a_synthetic_trace():
    mfu = layout.load_module("metrics", "lora_window_mfu")
    trace = {"devices": {"TPU:0": [[0, 10, "fusion.1 kLoop"]]},
             "host": [[0, 2_000_000_000, "bench.window"]]}
    recs = [{"n_scheduled": 10}, {"n_scheduled": 6}]
    ctx = {"trace": trace, "records": recs, "executed_slots": 24,
           "forward_flops_per_sample": 1e12, "samples_per_vehicle": 8,
           "eval_samples": 8, "chips": 1, "peak_flops": 197e12}
    # 2 x 1e12 x 16 vehicles x 8 sequences + 1e12 x 8, over 2 s at peak
    assert mfu.read(ctx) == pytest.approx(100 * 264e12 / (2 * 197e12))
    assert mfu.read(dict(ctx, trace={"devices": {}, "host": []})) is None


def test_real_cell():
    cell = layout.find_cell(CELL)
    assert cell.chips == 1
    cfg = cell.config
    assert cfg["name"] == CONFIG
    assert {k: cell.traffic[k] for k in TRAFFIC} == TRAFFIC
    assert cfg["train"]["batch_size"] == 4 and cfg["local_steps"] == 2
    spec = json.load(open(os.path.join(benchenv.ROOT, "BENCHMARK.json")))
    entry = [c for c in spec["configs"] if c["name"] == CONFIG][0]
    assert set(entry["reduced"]) == set(cfg["reduced"]) == {
        "num_hidden_layers", "n_routed_experts", "vocab_size",
        "local_steps"}
    lists = {m["name"]: m.get("workloads") for m in spec["per_layer"]}
    assert lists["lora_window_mfu"] == [CELL]
    assert CELL not in lists["superstep_mfu"]


def test_config_agrees_with_the_program_build():
    """The program's build reads ``model_kwargs``, the reference the
    published keys: they name the same cut, and the FLOP count agrees with
    the program's analytic profile."""
    from repro.api import registry
    cfg = layout.load_json(os.path.join(benchenv.BENCH, "configs",
                                        CONFIG + ".json"))
    kw = cfg["model_kwargs"]
    assert kw["n_layers"] == cfg["num_hidden_layers"] == 5
    assert kw["experts_held"] == cfg["n_routed_experts"] == 8
    assert kw["vocab_rows"] == cfg["vocab_size"] == 102400 // 8
    assert kw["seq_len"] == cfg["seq_len"] == 1024
    assert cfg["n_units"] == kw["n_layers"] + 1
    assert cfg["router_experts"] == cfg["published"]["n_routed_experts"]
    model = registry.build_model(cfg["model"], **kw)
    m = model.cfg
    assert (m.d_model, m.n_heads, m.d_ff, m.moe.d_ff_expert, m.moe.top_k,
            m.moe.n_experts, m.mla.kv_lora_rank) == (
        cfg["hidden_size"], cfg["num_attention_heads"],
        cfg["intermediate_size"], cfg["moe_intermediate_size"],
        cfg["num_experts_per_tok"], cfg["router_experts"],
        cfg["kv_lora_rank"])
    prof = model.profile()
    flops = layout.load_module("flops", cfg["model"])
    prog = sum(prof.unit_fwd_flops[1:]) + prof.head_flops
    assert flops.forward_flops_per_sample(cfg) == pytest.approx(prog,
                                                                rel=1e-12)


@pytest.mark.parametrize("control", ["bf16", "half_batch"])
def test_controls_fail(root, control):
    """The reference in bfloat16, or with half of every batch left out, in
    the program's place is not correct."""
    import jax.numpy as jnp
    from harness import check
    cell = layout.find_cell(CELL, root)
    data, p0, engine = run.prepare(cell, 5)
    del engine
    kw = ({"dtype": jnp.bfloat16} if control == "bf16"
          else {"half_batch": True})
    numbers, _ = run.reference_numbers(cell, data, p0, None, None, 5, **kw)
    assert numbers["route_mismatch"] == 0
    assert not check.judge(numbers, cell.limits)


def test_a_left_unchanged_escapes_the_comparison(root):
    """A planted fault: the program's final A leaves put back to their
    start, what a program that never updates A hands back (up to A's second
    order effect on the second round's loss).  B starts at zero, so every
    A leaf's first reference gradient is zero and ``delta_gap`` leaves it
    out; the loss does not see it either.  The comparison passes the fault:
    a gap of the cell's limits that PERF.md section 7 records."""
    import numpy as np
    from harness import check
    cell = layout.find_cell(CELL, root)
    data, p0, engine = run.prepare(cell, 5)
    records, (units, head), _, _ = run.warm_window(
        engine, cell.traffic["superstep"])
    del engine
    numbers, ref = run.reference_numbers(cell, data, p0, records,
                                         (units, head), 5)
    assert check.judge(numbers, cell.limits)
    moved = lambda us: [float(np.linalg.norm(np.asarray(ad["a"])
                                             - np.asarray(p0[0][u][t]["a"])))
                        for u, unit in enumerate(us) for t, ad in unit.items()]
    assert min(moved(units)) > 0 and min(moved(ref["units"])) > 0
    frozen_a = [{t: dict(ad, a=p0[0][u][t]["a"]) for t, ad in unit.items()}
                for u, unit in enumerate(units)]
    faulty = check.compare(records, ref, p0, (frozen_a, head))
    assert faulty == numbers and check.judge(faulty, cell.limits)
