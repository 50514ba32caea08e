"""The program's own names in a trace: the engine's host spans split the
idle time, and each device op's ``asfl.*`` scope splits the op time; on
hand-built traces, a hand-built and a real profile, and a trace recorded
on a TPU v5e."""
import os

import pytest

import benchenv
from harness import layout
from harness import trace as T
from test_bench_trace import MS, RECORDED, _ctx, _synthetic


def _engine_synthetic():
    """The two windows of ``_synthetic`` with the engine's spans: TPU:0
    idles 0-1, 4-5, 9-13 and 19-22 ms; its ops carry scopes."""
    tr = _synthetic()
    us = MS // 1000
    tr["host"] = sorted(tr["host"][:2] + [
        [500 * us, 9300 * us, "engine.window"],
        [500 * us, 500 * us, "engine.dispatch"],
        [1 * MS, 8500 * us, "engine.pull"],
        [9500 * us, 300 * us, "engine.metrics"],
        [12200 * us, 9700 * us, "engine.window"],
        [12200 * us, 800 * us, "engine.dispatch"],
        [13 * MS, 7 * MS, "engine.pull"],
        [20 * MS, 1 * MS, "engine.metrics"],
        [21 * MS, 800 * us, "engine.evaluate"]])
    tr["devices"]["TPU:0"].sort()
    scope = {"fusion.1 kOutput": "asfl.grad", "all-reduce.2": "asfl.merge",
             "convolution.3": "asfl.server_update"}
    tr["op_meta"] = {d: [[scope.get(n, "") if s < 10 * MS else "",
                          "jit_superstep"] for s, _, n in evs]
                     for d, evs in tr["devices"].items()}
    return tr


IDLE = ("pull_idle_ms", "metrics_idle_ms", "eval_idle_ms", "other_idle_ms")
DEVICE = ("grad_ms", "server_update_ms", "client_update_ms", "batch_ms",
          "merge_ms", "other_device_ms")


def test_idle_by_engine_span():
    """Each idle instant goes to the innermost engine span over it: the
    dispatch, the window span itself and no span at all fall to
    ``other_idle_ms``; the four sum to ``window_gap_ms``."""
    tr = _engine_synthetic()
    got = {m: layout.load_module("metrics", m).read(_ctx(tr)) for m in IDLE}
    assert got == pytest.approx({"pull_idle_ms": 1.25,
                                 "metrics_idle_ms": 0.65,
                                 "eval_idle_ms": 0.4,
                                 "other_idle_ms": 2.2})
    gap = layout.load_module("metrics", "window_gap_ms").read(_ctx(tr))
    assert sum(got.values()) == pytest.approx(gap)


def test_device_time_by_scope():
    """Every op of the fullest device (loops left out) goes to one scope;
    the six groups sum to the op time per window."""
    from harness import scopes
    tr = _engine_synthetic()
    got = scopes.device_ms(tr)
    assert got == pytest.approx({"grad_ms": 1.0, "server_update_ms": 2.0,
                                 "client_update_ms": 0.0, "batch_ms": 0.0,
                                 "merge_ms": 0.5, "other_device_ms": 3.0})
    rows = scopes.device_ns_per_window(tr)
    assert rows[scopes.NO_SCOPE]["ops"] == 0.5     # one op over two windows
    assert sum(dict(T.top_ops(tr, 100)).values()) / 2 * 1e3 == \
        pytest.approx(sum(got.values()))


def test_scope_of_takes_the_innermost():
    from harness import scopes
    path = ("jit(superstep)/while/body/asfl.grad/transpose(jvp(asfl.wire))"
            "/mul")
    assert scopes.scope_of(path) == "asfl.wire"
    assert scopes.scope_of("jit(copy)/copy") == ""


def test_spans_and_scopes_find_nothing_in_a_program_without_them():
    """On a trace of a program with no engine spans and no op scopes (the
    parent of this instrumentation), the new readers return nothing."""
    from harness import scopes
    tr = T.read(RECORDED)
    for name in IDLE:
        assert layout.load_module("metrics", name).read(_ctx(tr)) is None
    assert scopes.device_ms(tr) is None


def test_recorded_trace_keeps_its_values():
    """The readers of the four accepted metrics give, on the recorded
    trace, exactly what they gave before the engine had spans."""
    tr = T.read(RECORDED)
    ctx = {"trace": tr, "executed_slots": 96,
           "records": [{"n_scheduled": n}
                       for n in (64, 63, 64, 62, 64, 64, 61, 64)],
           "forward_flops_per_sample": 1.11e9, "samples_per_vehicle": 32,
           "eval_samples": 512, "chips": 1, "peak_flops": 197e12}
    want = {"device_idle_pct": 2.6529375937276223,
            "window_gap_ms": 32.6720305,
            "superstep_mfu": 11.229308236146544,
            "slot_occupancy_pct": 65.88541666666667}
    for name, value in want.items():
        assert layout.load_module("metrics", name).read(ctx) == value


def _pb(*fields) -> bytes:
    """Protobuf bytes of (field, value) pairs: an int as a varint, str
    and bytes length-delimited."""
    out = bytearray()

    def varint(n):
        while True:
            out.append((n & 0x7F) | (0x80 if n >> 7 else 0))
            n >>= 7
            if not n:
                return

    for f, v in fields:
        if isinstance(v, int):
            varint(f << 3)
            varint(v)
        else:
            v = v.encode() if isinstance(v, str) else v
            varint(f << 3 | 2)
            varint(len(v))
            out.extend(v)
    return bytes(out)


def _xspace() -> bytes:
    """An XSpace with one TPU plane (a program event and three ops) and
    the ``/host:metadata`` plane holding the program's ``HloProto``, whose
    instructions carry their op_name, as the profiler writes them."""
    def instruction(name, op_name=None):
        meta = () if op_name is None else ((7, _pb((2, op_name))),)
        return (2, _pb((1, name), (2, "fusion"), *meta))

    hlo = _pb((1, _pb((1, "jit_superstep"), (3, _pb(
        (1, "main"),
        instruction("fusion.1", "jit(superstep)/while/body/asfl.grad/mul"),
        instruction("copy.2", "jit(superstep)/asfl.merge/copy"),
        instruction("add.3"))))))
    metadata = _pb((1, 1), (2, "/host:metadata"), (4, _pb(
        (1, 9), (2, _pb((1, 9), (2, "jit_superstep(9)"),
                        (5, _pb((1, 1), (6, hlo))))))))

    def line(name, *events):
        return _pb((1, 1), (2, name), (3, 1000),
                   *((4, _pb((1, m), (2, off), (3, dur)))
                     for m, off, dur in events))

    def event_meta(i, name):
        return _pb((1, i), (2, _pb((1, i), (2, name))))

    device = _pb(
        (1, 7), (2, "/device:TPU:0"),
        (3, line("XLA Modules", (10, 0, 10_000_000))),
        (3, line("XLA Ops", (11, 1_000_000, 2_000_000),
                 (12, 4_000_000, 1_000_000), (13, 6_000_000, 500_000))),
        (4, event_meta(10, "jit_superstep(9)")),
        (4, event_meta(11, "%fusion.1 = f32[] fusion(), kind=kLoop")),
        (4, event_meta(12, "%copy.2 = f32[] copy()")),
        (4, event_meta(13, "%add.3 = f32[] add()")))
    return _pb((1, metadata), (1, device))


def test_annotate_reads_op_scopes_from_the_xplane(tmp_path):
    """Each device op gets the program it ran in and the innermost asfl
    scope of its instruction's op_name in that program's HloProto; the
    normalized keys are left as ``load`` wrote them."""
    from harness import scopes
    path = str(tmp_path / "t.xplane.pb")
    with open(path, "wb") as f:
        f.write(_xspace())
    tr = T.load(path)
    ann = scopes.annotate(tr, path)
    assert ann["devices"] == tr["devices"] and ann["host"] == tr["host"]
    assert [op[2] for op in tr["devices"]["TPU:0"]] == [
        "fusion.1 kLoop", "copy.2", "add.3"]
    assert ann["op_meta"] == {"TPU:0": [["asfl.grad", "jit_superstep"],
                                        ["asfl.merge", "jit_superstep"],
                                        ["", "jit_superstep"]]}


def test_program_scopes_of_a_real_profile(tmp_path):
    """A profile the CPU writes holds each program's optimized HloProto:
    the instructions that ran (the ``hlo_op`` of its XLA op events) map
    to the scopes they were traced in."""
    import jax
    import jax.numpy as jnp
    from jax.profiler import ProfileData
    from harness import scopes

    @jax.jit
    def f(x):
        with jax.named_scope("asfl.grad"):
            y = jnp.sin(x) @ x
        with jax.named_scope("asfl.merge"):
            return jnp.cos(y).sum()

    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    with jax.profiler.trace(str(tmp_path)):
        f(x).block_until_ready()
    path = str(next(tmp_path.glob("plugins/profile/*/*.xplane.pb")))
    ran = {(dict(e.stats)["hlo_module"], dict(e.stats)["program_id"],
            dict(e.stats)["hlo_op"])
           for plane in ProfileData.from_file(path).planes
           for line in plane.lines for e in line.events
           if "hlo_op" in dict(e.stats)}
    programs = scopes.program_scopes(path)
    got = {op: programs[f"{m}({i})"][op] for m, i, op in ran
           if m == "jit_f"}
    assert {"asfl.grad", "asfl.merge"} <= set(got.values())


# two windows of the resnet18 cell on a TPU v5e with the engine's spans
# and each op's scope and program (ops of 0.1 ms or more; host spans of
# 0.2 ms or more, and every engine and benchmark span)
SCOPED = os.path.join(benchenv.DATA, "tpu_v5e_scoped_trace.json.gz")


def test_recorded_scoped_trace_sums():
    """On a recorded trace of the instrumented program: the six device
    groups sum to the op time per window and the four idle metrics to
    ``window_gap_ms``; every phase of the fused program has op time."""
    from harness import scopes
    tr = T.read(SCOPED)
    n = len(T.windows(tr))
    assert n == 2
    dev = scopes.device_ms(tr)
    op_ms = sum(ns for _, ns in T.top_ops(tr, 10 ** 6)) * 1e3 / n
    assert sum(dev.values()) == pytest.approx(op_ms, abs=1e-6)
    for name in DEVICE[:-1]:
        assert dev[name] > 0, name
    idle = {m: layout.load_module("metrics", m).read(_ctx(tr)) for m in IDLE}
    gap = layout.load_module("metrics", "window_gap_ms").read(_ctx(tr))
    assert sum(idle.values()) == pytest.approx(gap, abs=1e-6)
    assert all(v >= 0 for v in idle.values()) and idle["pull_idle_ms"] > 0
    rows = scopes.device_ns_per_window(tr)
    fused = sum(r["programs"].get("jit_superstep", 0) for r in rows.values())
    assert rows["asfl.eval"]["programs"] == {
        "jit_fn": pytest.approx(rows["asfl.eval"]["ns"])}
    assert 0 < fused <= sum(r["ns"] for r in rows.values())
