"""A whole traced run of a small cell through ``bench/trace_scopes.py`` on
the CPU, with the chip check skipped: its scope and counter lines."""
import json

import pytest

import benchenv


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return benchenv.make_root(str(tmp_path_factory.mktemp("bench")),
                              [("tiny-hwy", "tiny-hwy-seq", 1)])


def test_trace_scopes_prints_scopes_and_counters(root, capsys):
    """``trace_scopes.py`` is a traced run with the same result line and
    two lines before it: the per-scope op time (none on the CPU, which
    has no device plane) and the engine's counters per window."""
    import trace_scopes
    rc = trace_scopes.main(["--workload", "tiny-hwy", "--seed",
                            str(2 ** 31 + 7), "--seconds", "1"],
                           require_chip=False, root=root)
    out = capsys.readouterr().out.strip().splitlines()
    res = json.loads(out[-1])
    assert rc == 0 and res["correct"] is True
    assert "rounds_per_s" not in res["metrics"]
    scopes = json.loads(next(l for l in out if l.startswith("scopes: "))[8:])
    assert scopes == {"per_window": {}, "groups_ms": None}
    counters = json.loads(
        next(l for l in out if l.startswith("counters: "))[10:])
    # K=2 windows of the 8-vehicle mlp9 fleet: a super-step and 20
    # global-model copies, 7 scan outputs, and one eval batch per window
    assert counters == {"windows": 1.0, "rounds": 2.0, "dispatches": 22.0,
                        "host_syncs": 8.0, "evaluations": 1.0}
