"""Ragged super-steps (ISSUE 7 acceptance tests, DESIGN.md §12):
cut-prefix parameter planes + occupancy-compacted slot scheduling.

The load-bearing claims:

* ragged == dense bit-for-bit for sgd on BOTH server schedules, through a
  window containing a handover, a cloud merge, and a cut change (the
  two-cell trace) — with and without the EF wire carry planes;
* zero compile fallbacks / zero backend compiles across cut churn (the
  prefix bucket and compacted slot count are part of the static program
  signature, so retracing would be a bug, not a slowdown);
* the compacted layout's compiled program needs less temp memory than the
  dense one (the peak-device-memory smoke CI runs via ``-k memory``);
* occupancy accounting is honest: the bench columns derive from
  ``ScenarioEngine.occupancy_stats()`` asserted here.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import scenario as S
from repro.core.fedsim import ScenarioEngine, SimConfig
from repro.core.superstep import (SUPERSTEP_LAYOUTS, cut_prefix_bucket,
                                  owned_window)

from test_scenario import TinyMLP, _two_cell_trace, _vector_clients

ROUNDS, INTERVAL = 6, 5.0


def _cfg(layout, **kw):
    base = dict(scheme="asfl", adaptive_strategy="paper", rounds=ROUNDS,
                local_steps=2, batch_size=8, lr=1e-2, optimizer="sgd",
                round_interval_s=INTERVAL, eval_every=0, superstep=3,
                superstep_layout=layout)
    base.update(kw)
    return SimConfig(**base)


def _engine(layout, **kw):
    sc = _two_cell_trace(ROUNDS, INTERVAL)
    clients, test = _vector_clients(2)
    return ScenarioEngine(TinyMLP(), clients, test, _cfg(layout, **kw), sc,
                          cloud_sync_every=2)


def _params(eng):
    return jax.tree.map(np.asarray, {"units": eng.units, "head": eng.head})


# ------------------------------------------------- ragged == dense, sgd
@pytest.mark.parametrize("wire", ["none", "topk_int8"])
@pytest.mark.parametrize("schedule", ["sequential", "parallel"])
def test_ragged_matches_dense_bitforbit(schedule, wire):
    """The compacted layout is a pure re-layout: sgd training through a
    handover, a mid-window cloud merge, and the trace's cut churn is
    bit-identical to the dense masked path — including the EF residual
    planes (their prefix sizing covers every reachable boundary)."""
    er = _engine("ragged", server_schedule=schedule, wire=wire)
    ed = _engine("dense", server_schedule=schedule, wire=wire)
    hr, hd = er.run(), ed.run()
    assert sum(m.n_handover for m in hr) >= 1
    assert [m.cuts for m in hr] == [m.cuts for m in hd]
    assert [m.rsu_loads for m in hr] == [m.rsu_loads for m in hd]
    np.testing.assert_array_equal([m.loss for m in hr],
                                  [m.loss for m in hd])
    jax.tree.map(np.testing.assert_array_equal, _params(er), _params(ed))
    if wire == "topk_int8":
        np.testing.assert_array_equal(np.asarray(er._carry["wire_res"]),
                                      np.asarray(ed._carry["wire_res"]))
        np.testing.assert_array_equal(np.asarray(er._carry["wire_cut"]),
                                      np.asarray(ed._carry["wire_cut"]))


@pytest.mark.parametrize("schedule", ["sequential", "parallel"])
def test_ragged_matches_dense_adam_tolerance(schedule):
    """adam re-associates nothing extra in the ragged layout, but moment
    planes live on the prefix window; parity within the engine-parity fp
    tolerance (acceptance wording)."""
    er = _engine("ragged", server_schedule=schedule, optimizer="adam")
    ed = _engine("dense", server_schedule=schedule, optimizer="adam")
    hr, hd = er.run(), ed.run()
    np.testing.assert_allclose([m.loss for m in hr], [m.loss for m in hd],
                               rtol=1e-5, atol=1e-5)
    jax.tree.map(lambda a, b: np.testing.assert_allclose(
        a, b, atol=1e-5, rtol=1e-5), _params(er), _params(ed))


# ------------------------------------------- static signatures, no churn
def test_zero_fallbacks_across_cut_churn():
    """The prefix bucket / compacted slot count are pow2-bucketed STATICS:
    precompile covers the whole run and no backend compile fires mid-run
    even as cuts and membership churn (jax.monitoring listener — the same
    harness as tests/test_superstep.py)."""
    for schedule in ("sequential", "parallel"):
        eng = _engine("ragged", server_schedule=schedule, wire="topk_int8")
        eng.precompile()
        events = []
        jax.monitoring.register_event_duration_secs_listener(
            lambda name, *a, **kw: events.append(name))
        baseline = len([e for e in events if "compile" in e])
        hist = eng.run()
        assert eng.programs.compile_fallbacks == 0
        assert not [e for e in events[baseline:] if "compile" in e]
        assert len(hist) == ROUNDS


def test_signature_carries_slots_and_max_cut():
    """The compile-cache key: ragged+parallel signatures carry the planned
    compacted slot capacity; everything else keys slots=0.  max_cut is the
    strategy's pow2 prefix bucket (TinyMLP, paper thresholds: 4 of 5
    units)."""
    ep = _engine("ragged", server_schedule="parallel")
    sig = ep.programs.signature(3, 2, 8)
    assert sig.slots == 8 and sig.max_cut == 4
    # unplanned callers fall back to the uncompacted R*capacity bound
    assert ep.programs.signature(3, 2).slots == \
        ep.programs.n_rsus_padded * 2
    es = _engine("ragged", server_schedule="sequential")
    assert es.programs.signature(3, 2, 8).slots == 0
    ed = _engine("dense", server_schedule="parallel")
    assert ed.programs.signature(3, 2, 8).slots == 0
    assert ed.programs.signature(3, 2, 8).max_cut == 0


def test_prefix_plane_window():
    """TinyMLP under paper thresholds: bucket 4 of 5 units, so the client
    plane window owns units 0..3 (head + unit 4 excluded) and the EF wire
    sizing still covers every reachable boundary (== dense here: unit ids
    below the bucket include every candidate cut)."""
    er = _engine("ragged", wire="topk_int8")
    ed = _engine("dense", wire="topk_int8")
    pg = er.programs
    assert pg.max_cut_bucket == 4
    ids = pg.unit_ids_np
    assert pg.plane_width == int((ids < 4).sum())
    o, w = pg.plane_offset, pg.plane_width
    assert (np.sort(np.flatnonzero(ids < 4)) == np.arange(o, o + w)).all()
    assert pg.wire_units == min(pg.model.n_units - 1, pg.max_cut_bucket)
    assert pg.res_size == ed.programs.res_size


def test_layout_validation_and_spec_wiring():
    with pytest.raises(ValueError, match="superstep_layout"):
        SimConfig(superstep_layout="diagonal")
    assert set(SUPERSTEP_LAYOUTS) == {"ragged", "dense"}
    from repro import api
    spec = api.ExperimentSpec(
        fleet=api.FleetConfig(n_vehicles=4, scenario="trace_replay"),
        runtime=api.RuntimeConfig(superstep_layout="dense"))
    assert spec.to_sim_config().superstep_layout == "dense"
    assert api.ExperimentSpec().to_sim_config().superstep_layout == "ragged"


# -------------------------------------------------- occupancy accounting
def test_occupancy_stats_are_honest():
    """The bench columns' source of truth: executed slots, padded fraction,
    prefix plane fraction.  On the two-cell trace the dense layout pads
    2 RSUs x capacity while the compacted one executes the bucketed total
    covered count."""
    er = _engine("ragged", server_schedule="parallel")
    ed = _engine("dense", server_schedule="parallel")
    hr, hd = er.run(), ed.run()
    occ_r, occ_d = er.occupancy_stats(), ed.occupancy_stats()
    assert occ_r["layout"] == "ragged" and occ_d["layout"] == "dense"
    mean = float(np.mean([m.n_scheduled for m in hr]))
    assert occ_r["mean_occupied_slots"] == mean
    assert occ_d["mean_occupied_slots"] == float(
        np.mean([m.n_scheduled for m in hd]))
    for occ in (occ_r, occ_d):
        # the padded share is exactly what the occupied slots leave over
        assert 0.0 <= occ["padded_slot_frac"] < 1.0
        assert occ["padded_slot_frac"] == pytest.approx(
            1.0 - occ["mean_occupied_slots"] / occ["executed_slots"],
            abs=1e-12)
    assert occ_r["executed_slots"] <= occ_d["executed_slots"]
    assert occ_r["owned_plane_frac"] < 1.0 == occ_d["owned_plane_frac"]


def test_sequential_occupancy_counts_slot_bodies():
    """Sequential schedule: executed slots are the slot bodies the program
    ran — RSU rows times the slot chain's trip count, the fullest RSU's
    load of each round — so a fleet with padding executes fewer than the
    padded (R, C) grid."""
    n = 8
    sc = S.make_scenario("highway_corridor", n, seed=2)
    clients, test = _vector_clients(n)
    cfg = _cfg("ragged", server_schedule="sequential", rounds=4, superstep=2)
    eng = ScenarioEngine(TinyMLP(), clients, test, cfg, sc,
                         cloud_sync_every=2)
    hist = eng.run()
    trips = [int(t[0]) for t in eng.slot_trips]   # one RSU-axis shard
    assert trips == [max(m.rsu_loads) for m in hist]
    occ = eng.occupancy_stats()
    rows = eng.programs.n_rsus_padded
    assert occ["executed_slots"] == rows * float(np.mean(trips))
    assert occ["executed_slots"] < rows * eng._capacity(4)
    assert occ["padded_slot_frac"] == pytest.approx(
        1.0 - occ["mean_occupied_slots"] / occ["executed_slots"], abs=1e-12)
    eng.reset()
    assert eng.slot_trips == []


def test_compacted_overflow_raises():
    """A signature planned for fewer slots than the fleet occupies must
    fail loudly (truncated cohorts would train silently wrong)."""
    eng = _engine("ragged", server_schedule="parallel")
    eng._covered_totals = {r: 0 for r in range(ROUNDS)}

    def fake(horizon):
        return 1                                # plan 1 slot, serve 2
    eng._total_slots = fake
    with pytest.raises(RuntimeError, match="compacted"):
        eng.run_superstep(0, 3)


# -------------------------------------------------- zipf skewed arrivals
def test_zipf_load_skew_biases_initial_cells():
    """load_skew="zipf" piles initial arrivals onto the low-index cells;
    kinematics are untouched (same speeds as the uniform twin)."""
    uni = S.make_scenario("highway_corridor", 64, seed=7)
    zip_ = S.make_scenario("highway_zipf", 64, seed=7)
    assert zip_.name == "highway_zipf"
    np.testing.assert_array_equal(uni._speed, zip_._speed)
    s = zip_.fleet_state(0.0, seed=0).serving_rsu
    loads = np.bincount(s[s >= 0], minlength=zip_.n_rsus)
    # zipf mass ~ 1/(k+1): cell 0 clearly dominates the tail cell
    assert loads[0] > 2 * max(loads[-1], 1)
    with pytest.raises(ValueError, match="load_skew"):
        S.HighwayCorridor(n_vehicles=4, load_skew="bogus")


def test_zipf_runs_ragged_parallel():
    """The skewed scenario trains end-to-end on the compacted layout with
    zero fallbacks — and compaction beats the dense grid where it matters:
    fewer executed slots than n_rsus_padded * capacity."""
    n = 16
    sc = S.make_scenario("highway_zipf", n, seed=3)
    clients, test = _vector_clients(n)
    cfg = _cfg("ragged", server_schedule="parallel", rounds=4, superstep=4)
    eng = ScenarioEngine(TinyMLP(), clients, test, cfg, sc,
                         cloud_sync_every=2)
    eng.precompile()
    hist = eng.run()
    assert eng.programs.compile_fallbacks == 0
    assert all(np.isfinite(m.loss) for m in hist)
    occ = eng.occupancy_stats()
    cap = eng._capacity(4)
    assert occ["executed_slots"] < eng.programs.n_rsus_padded * cap


# --------------------------------------------- peak-device-memory smoke
def test_memory_compacted_below_dense():
    """The CI peak-device-memory smoke (``-k memory``): on the skewed
    fleet the ragged+parallel executable's temp allocation stays below the
    dense one's — the compacted slot axis and prefix planes are where the
    O(n_rsus * capacity * P) dense working set goes."""
    n = 32
    engines = {}
    for layout in ("ragged", "dense"):
        sc = S.make_scenario("highway_zipf", n, seed=5)
        clients, test = _vector_clients(n)
        cfg = _cfg(layout, server_schedule="parallel", rounds=4,
                   superstep=4)
        eng = ScenarioEngine(TinyMLP(), clients, test, cfg, sc,
                             cloud_sync_every=2)
        eng.precompile()
        engines[layout] = eng

    def temp_bytes(eng):
        tots = []
        for prog in eng.programs._programs.values():
            ma = getattr(prog, "memory_analysis", None)
            if ma is None:
                pytest.skip("compiled memory_analysis unavailable "
                            "on this backend")
            tots.append(int(ma().temp_size_in_bytes))
        return max(tots)

    ragged, dense = temp_bytes(engines["ragged"]), \
        temp_bytes(engines["dense"])
    assert ragged < dense, (ragged, dense)
