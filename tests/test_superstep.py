"""Fused super-step engine (ISSUE 3 acceptance tests, DESIGN.md §8):
K-fused == K-sequential equivalence (with a handover and a cloud merge
inside the fused window), donation safety, precompile coverage (no silent
mid-run recompiles), capacity-padding invariance, and traced-twin parity
for the on-device schedulers."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import adaptive, channel, cost
from repro.core import scenario as S
from repro.core.fedsim import ScenarioEngine, SimConfig
from repro.data.pipeline import fleet_batch_indices_traced

from test_scenario import TinyMLP, _two_cell_trace, _vector_clients

ROUNDS, INTERVAL = 4, 5.0


def _cfg(**kw):
    base = dict(scheme="asfl", adaptive_strategy="paper", rounds=ROUNDS,
                local_steps=2, batch_size=8, lr=1e-2, optimizer="sgd",
                round_interval_s=INTERVAL, eval_every=0, superstep=1)
    base.update(kw)
    return SimConfig(**base)


def _engines(cfg1, sync=2):
    """(K=1 engine, K=4 engine) over the canonical two-cell handover trace:
    the 4-round window contains vehicle 0's handover AND (sync=2) a cloud
    merge strictly inside the fused window."""
    sc = _two_cell_trace(ROUNDS, INTERVAL)
    clients, test = _vector_clients(2)
    cfgK = dataclasses.replace(cfg1, superstep=ROUNDS)
    e1 = ScenarioEngine(TinyMLP(), clients, test, cfg1, sc,
                        cloud_sync_every=sync)
    eK = ScenarioEngine(TinyMLP(), clients, test, cfgK, sc,
                        cloud_sync_every=sync)
    return e1, eK


def _params(eng):
    return jax.tree.map(np.asarray, {"units": eng.units, "head": eng.head})


# ---------------------------------------------------- fused == sequential
@pytest.mark.parametrize("schedule", ["sequential", "parallel"])
@pytest.mark.parametrize("optimizer,exact", [("sgd", True), ("adam", False)])
def test_superstep_matches_sequential_rounds(schedule, optimizer, exact):
    """K fused rounds == K per-round dispatches: same program body, so sgd
    is bit-for-bit; adam stays within the engine-parity fp tolerance.  The
    window covers a handover and a mid-window cloud merge."""
    e1, eK = _engines(_cfg(optimizer=optimizer, server_schedule=schedule))
    h1, hK = e1.run(), eK.run()
    # the fused window really contained the interesting events
    assert sum(m.n_handover for m in h1) >= 1
    assert [m.n_handover for m in h1] == [m.n_handover for m in hK]
    assert [m.n_scheduled for m in h1] == [m.n_scheduled for m in hK]
    assert [m.cuts for m in h1] == [m.cuts for m in hK]
    p1, pK = _params(e1), _params(eK)
    if exact:
        jax.tree.map(np.testing.assert_array_equal, p1, pK)
        np.testing.assert_array_equal([m.loss for m in h1],
                                      [m.loss for m in hK])
    else:
        jax.tree.map(lambda a, b: np.testing.assert_allclose(
            a, b, atol=1e-5, rtol=1e-5), p1, pK)
        np.testing.assert_allclose([m.loss for m in h1],
                                   [m.loss for m in hK],
                                   rtol=1e-5, atol=1e-5)
    # training progressed across the handover in both paths
    assert h1[-1].loss < h1[0].loss
    assert hK[-1].loss < hK[0].loss


def test_superstep_tail_window():
    """rounds not divisible by K: the tail window (smaller K) matches the
    per-round path bit-for-bit too."""
    e1, eK = _engines(_cfg())
    eK.cfg.superstep = 3                       # windows of 3 + tail of 1
    h1, hK = e1.run(), eK.run()
    jax.tree.map(np.testing.assert_array_equal, _params(e1), _params(eK))
    np.testing.assert_array_equal([m.loss for m in h1],
                                  [m.loss for m in hK])


def test_capacity_padding_is_inert():
    """pow2 vs tight8 slot capacity: padded slots are exact no-ops, so the
    trained model is bit-identical."""
    ea, eb = (_engines(_cfg(slot_capacity=cap))[1]
              for cap in ("pow2", "tight8"))
    ha, hb = ea.run(), eb.run()
    jax.tree.map(np.testing.assert_array_equal, _params(ea), _params(eb))
    np.testing.assert_array_equal([m.loss for m in ha],
                                  [m.loss for m in hb])


def test_rsu_loads_follow_the_trace():
    """On-device segment grouping reproduces the known two-cell membership:
    both vehicles start in cell 0; after the crossing, one per cell."""
    _, eK = _engines(_cfg())
    hist = eK.run()
    assert hist[0].rsu_loads == [2, 0]
    assert hist[-1].rsu_loads == [1, 1]
    assert all(sum(m.rsu_loads) == m.n_scheduled for m in hist)


# ------------------------------------------- the slot chain's trip count
def _uneven_engines(**kw):
    """{(slot_capacity, K): engine} over an 8-vehicle highway fleet on 4
    RSUs whose fullest RSU holds 3 or 4 vehicles by round: the sequential
    slot chain stops short of both capacities (pow2 4, tight8 8), at a
    trip count that changes between rounds."""
    sc = S.make_scenario("highway_corridor", 8, seed=2)
    clients, test = _vector_clients(8)
    return {(cap, k): ScenarioEngine(
        TinyMLP(), clients, test, _cfg(slot_capacity=cap, superstep=k, **kw),
        sc, cloud_sync_every=2)
        for cap in ("pow2", "tight8") for k in (1, ROUNDS)}


@pytest.mark.parametrize("planes", [
    dict(wire="topk_int8"),
    dict(fault_dropout=0.2, fault_upload_loss=0.1, fault_straggler=1e-7,
         fault_rsu_outage=0.3, fault_seed=1)], ids=["ef_wire", "faults"])
def test_skipped_slots_are_exact_noops(planes):
    """Slots past the fullest RSU's load are skipped, not computed and
    masked: pow2 and tight8 capacity, K-fused and per-round, train
    bit-identical params, losses and EF residual planes."""
    engines = _uneven_engines(**planes)
    hists = {key: eng.run() for key, eng in engines.items()}
    ref = engines["tight8", ROUNDS]
    trips = [int(t[0]) for t in ref.slot_trips]
    assert max(trips) < ref._capacity(ROUNDS)
    assert min(trips) < engines["pow2", ROUNDS]._capacity(ROUNDS)
    assert len(set(trips)) > 1
    for key, eng in engines.items():
        assert [[int(x) for x in t] for t in eng.slot_trips] == \
            [[t] for t in trips], key
        jax.tree.map(np.testing.assert_array_equal, _params(ref),
                     _params(eng))
        np.testing.assert_array_equal(
            [m.loss for m in hists["tight8", ROUNDS]],
            [m.loss for m in hists[key]])
        if "wire_res" in ref._carry:
            for plane in ("wire_res", "wire_cut"):
                np.testing.assert_array_equal(np.asarray(ref._carry[plane]),
                                              np.asarray(eng._carry[plane]))


def test_slot_chain_runs_every_occupied_slot():
    """With lr = 0 the model stays at its initial weights, so each round's
    loss is the initial model's mean loss over every scheduled vehicle's
    batches, computed here on the host: a chain that stopped before the
    fullest RSU's last vehicle would leave that vehicle's terms out."""
    eng = _uneven_engines(lr=0.0)["tight8", ROUNDS]
    hist = eng.run()
    model = TinyMLP()
    units, head = model.init(jax.random.PRNGKey(eng.cfg.seed))
    steps = eng.cfg.local_steps
    for m in hist:
        idx = np.asarray(fleet_batch_indices_traced(
            jax.random.fold_in(jax.random.PRNGKey(eng.cfg.seed), m.round),
            eng.lengths, steps, eng.cfg.batch_size))
        losses = [model.head_loss(head, model.apply_units(
            units, c.images[idx[s, v]], 0), c.labels[idx[s, v]])[0]
            for v, c in enumerate(eng.clients) if m.cuts[v] > 0
            for s in range(steps)]
        np.testing.assert_allclose(m.loss, np.mean(losses), rtol=1e-5)


# ------------------------------------------------------- donation safety
def test_donated_carries_never_reused():
    """The super-step donates its carry: old carry buffers must be deleted,
    the engine must keep working across windows/resets, and the public
    units/head handed to callers must survive later (donating)
    dispatches."""
    e1, eK = _engines(_cfg(superstep=2))
    carry0_leaves = jax.tree.leaves(eK._carry)
    hist = eK.run()
    assert len(hist) == ROUNDS
    # the initial carry was consumed by donation...
    assert all(leaf.is_deleted() for leaf in carry0_leaves)
    # ...but caller-facing views are fresh buffers: still readable after
    # further donating dispatches and a reset
    held = jax.tree.map(lambda a: a, {"units": eK.units, "head": eK.head})
    eK.reset()
    eK.run()
    first = jax.tree.leaves(held)[0]
    assert not first.is_deleted()
    _ = jax.tree.map(np.asarray, held)         # materializes without error
    assert np.isfinite(hist[-1].loss)


# ----------------------------------------------- precompile / warm start
def test_precompile_covers_every_signature():
    """After precompile(), a full run must not build (or XLA-compile)
    anything: the engine's fallback counter stays at zero and no backend
    compile events fire during the run (jax.monitoring)."""
    sc = _two_cell_trace(ROUNDS, INTERVAL)
    clients, test = _vector_clients(2)
    cfg = _cfg(superstep=3, eval_every=1)      # windows 3 + 1, plus eval
    eng = ScenarioEngine(TinyMLP(), clients, test, cfg, sc,
                         cloud_sync_every=2)
    sigs = eng.precompile()
    assert len(sigs) == 2                      # K=3 and the K=1 tail
    assert eng.programs.compile_fallbacks == 0

    events = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda name, *a, **kw: events.append(name))
    baseline = len([e for e in events if "compile" in e])
    hist = eng.run()
    compiles = [e for e in events[baseline:] if "compile" in e]
    assert eng.programs.compile_fallbacks == 0, \
        "run requested a signature precompile() did not cover"
    assert not compiles, f"silent mid-run recompiles: {compiles}"
    assert len(hist) == ROUNDS
    # eval ran through its precompiled path too (sync rounds 2 and 4)
    assert np.isfinite(hist[-1].test_acc)


def test_fused_eval_cadence_fires():
    """eval_every must keep firing in fused mode even when the due sync
    never lands on a window-end round (K=2, sync=1, eval_every=2: syncs 0
    and 2 are due, both mid/at-window — regression test)."""
    sc = _two_cell_trace(ROUNDS, INTERVAL)
    clients, test = _vector_clients(2)
    cfg = _cfg(superstep=2, eval_every=2)
    eng = ScenarioEngine(TinyMLP(), clients, test, cfg, sc,
                         cloud_sync_every=1)
    hist = eng.run()
    accs = [m.test_acc for m in hist]
    assert any(np.isfinite(a) for a in accs), \
        f"eval never fired in fused mode: {accs}"
    # the score lands on the last synced round of an eval-due window, whose
    # global model is exactly the one evaluated
    assert all(0.0 <= a <= 1.0 for a in accs if np.isfinite(a))


def test_compilation_cache_dir_is_wired(tmp_path):
    """SimConfig.compilation_cache_dir turns on JAX's persistent cache:
    compiled super-step programs land on disk."""
    cache = tmp_path / "xla-cache"
    sc = _two_cell_trace(2, INTERVAL)
    clients, test = _vector_clients(2)
    cfg = _cfg(rounds=2, superstep=2, compilation_cache_dir=str(cache))
    eng = ScenarioEngine(TinyMLP(), clients, test, cfg, sc)
    eng.run()
    entries = list(cache.iterdir())
    assert entries, "persistent compilation cache wrote nothing"


# ------------------------------------------------- traced-twin schedulers
def test_paper_threshold_traced_matches_numpy():
    rng = np.random.default_rng(0)
    rates = rng.uniform(1e6, 4e8, 256)
    # keep clear of the band edges (fp32 vs fp64 digitize)
    for thr in adaptive.DEFAULT_THRESHOLDS:
        rates = np.where(np.abs(rates - thr) < 0.01 * thr, rates * 1.05,
                         rates)
    for literal in (False, True):
        ref = adaptive.paper_threshold(rates, literal_eq3=literal)
        got = np.asarray(adaptive.paper_threshold_traced(
            jnp.asarray(rates, jnp.float32), literal_eq3=literal))
        np.testing.assert_array_equal(ref, got)


def test_residence_aware_traced_matches_numpy():
    rng = np.random.default_rng(1)
    prof = cost.resnet_profile()
    n = 64
    rates = rng.uniform(2e6, 3e8, n)
    flops = rng.uniform(5e9, 5e10, n)
    residence = rng.uniform(0.05, 60.0, n)
    ref = np.asarray(adaptive.residence_aware(prof, rates, flops, 2e12, 4,
                                              16, 1, residence))
    got = np.asarray(adaptive.residence_aware_traced(
        prof, jnp.asarray(rates, jnp.float32),
        jnp.asarray(flops, jnp.float32), 2e12, 4, 16, 1,
        jnp.asarray(residence, jnp.float32)))
    # fp32 cost evaluation may flip knife-edge vehicles; decisions must
    # agree almost everywhere and SKIPs must agree exactly on clear cases
    assert (ref == got).mean() > 0.95
    clear = np.abs(residence - 1.0) > 0.5      # away from typical latencies
    assert ((ref == 0) == (got == 0))[clear].mean() > 0.95


def test_traced_fleet_state_matches_host_for_traces():
    """TraceReplay's traced-step path indexes the same precomputed tables
    the host path serves (fading-free: exactly)."""
    sc = S.crossing_trace(8, n_rsus=3, seed=5)
    for t in (0.0, 30.0, 77.5):
        host = sc.fleet_state(t, seed=0)
        traced = jax.jit(lambda tt: sc.traced_fleet_state(tt, None))(
            jnp.float32(t))
        np.testing.assert_array_equal(host.serving_rsu,
                                      np.asarray(traced.serving_rsu))
        np.testing.assert_allclose(host.residence_s,
                                   np.asarray(traced.residence_s),
                                   rtol=1e-5, atol=1e-4)
        np.testing.assert_allclose(host.rates_bps,
                                   np.asarray(traced.rates_bps),
                                   rtol=2e-5)


def test_traced_highway_state_consistent():
    """Highway's traced-step path reproduces the host kinematics and cell
    association (rates differ only by the fading stream)."""
    sc = S.highway_corridor(16, seed=3,
                            ch=channel.ChannelConfig(fading_std_db=0.0))
    for t in (0.0, 12.5, 60.0):
        host = sc.fleet_state(t, seed=0)
        traced = jax.jit(lambda tt: sc.traced_fleet_state(tt, None))(
            jnp.float32(t))
        np.testing.assert_array_equal(host.serving_rsu,
                                      np.asarray(traced.serving_rsu))
        np.testing.assert_allclose(host.positions,
                                   np.asarray(traced.positions),
                                   rtol=1e-5, atol=1e-2)
        np.testing.assert_allclose(host.rates_bps,
                                   np.asarray(traced.rates_bps), rtol=2e-5)


def test_fleet_batch_indices_traced_bounds():
    lengths = np.array([5, 64, 17, 1])
    idx = np.asarray(fleet_batch_indices_traced(
        jax.random.PRNGKey(0), lengths, steps=3, batch_size=8))
    assert idx.shape == (3, 4, 8)
    assert (idx >= 0).all()
    assert (idx < lengths[None, :, None]).all()


# ------------------------------------------------------- wire boundaries
@pytest.mark.parametrize("schedule", ["sequential", "parallel"])
@pytest.mark.parametrize("wire", ["int8", "topk_int8"])
def test_superstep_wire_fused_matches_sequential(schedule, wire):
    """K-fused == K per-round dispatches stays bit-for-bit with a wire
    boundary in the forward — including the error-feedback carry planes
    for topk_int8 (same program body, sgd)."""
    e1, eK = _engines(_cfg(server_schedule=schedule, wire=wire))
    h1, hK = e1.run(), eK.run()
    jax.tree.map(np.testing.assert_array_equal, _params(e1), _params(eK))
    np.testing.assert_array_equal([m.loss for m in h1],
                                  [m.loss for m in hK])
    assert all(np.isfinite(m.loss) for m in h1)


def test_wire_precompile_covers_across_cut_churn():
    """With wire="topk_int8" the EF planes are part of the carry signature:
    precompile must still cover the whole run (zero fallbacks, zero
    backend compiles) across the trace's handover/cut churn."""
    sc = _two_cell_trace(ROUNDS, INTERVAL)
    clients, test = _vector_clients(2)
    cfg = _cfg(superstep=2, wire="topk_int8")
    eng = ScenarioEngine(TinyMLP(), clients, test, cfg, sc,
                         cloud_sync_every=2)
    eng.precompile()
    events = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda name, *a, **kw: events.append(name))
    baseline = len([e for e in events if "compile" in e])
    hist = eng.run()
    assert eng.programs.compile_fallbacks == 0
    assert not [e for e in events[baseline:] if "compile" in e]
    assert len(hist) == ROUNDS


def test_wire_residual_plane_persists_and_tracks_cuts():
    """The EF residual is a real carry plane: nonzero after training,
    sized to the largest boundary, and wire_cut records the cut each
    vehicle's buffer was accumulated at (it migrates with the vehicle on
    handover — the plane is fleet-indexed, not RSU-indexed)."""
    sc = _two_cell_trace(ROUNDS, INTERVAL)
    clients, test = _vector_clients(2)
    cfg = _cfg(wire="topk_int8")
    eng = ScenarioEngine(TinyMLP(), clients, test, cfg, sc,
                         cloud_sync_every=2)
    w = TinyMLP().width
    assert eng.programs.res_size == cfg.batch_size * w
    hist = eng.run()
    res = np.asarray(eng._carry["wire_res"])
    wcut = np.asarray(eng._carry["wire_cut"])
    assert res.shape == (2, eng.programs.res_size)
    # both vehicles trained (incl. vehicle 0 after its handover), so both
    # rows hold live residuals and their last cut
    assert (np.abs(res).sum(axis=1) > 0).all()
    assert (wcut == np.asarray(hist[-1].cuts)).all()
    # reset() rebuilds zeroed planes
    eng.reset()
    assert not np.asarray(eng._carry["wire_res"]).any()
    assert (np.asarray(eng._carry["wire_cut"]) == -1).all()


def test_wire_reduces_scenario_comm():
    """The accounting charges packed wire bytes: topk_int8 rounds move
    strictly fewer bytes than the dense fp32 baseline, which moves fewer
    than nothing changes elsewhere (identical schedule/cuts)."""
    hists = {}
    for wire in ("none", "topk_int8"):
        e1, _ = _engines(_cfg(wire=wire))
        hists[wire] = e1.run()
    assert [m.cuts for m in hists["none"]] == \
        [m.cuts for m in hists["topk_int8"]]
    for mn, mt in zip(hists["none"], hists["topk_int8"]):
        assert mt.comm_bytes < mn.comm_bytes


def test_staged_mobility_scenarios_run_fused():
    """urban_grid has no traced-step path: the engine stages its fleet
    state per window and still fuses K rounds into one program."""
    n = 8
    sc = S.urban_grid(n, seed=2, grid_size=4, block_m=120.0)
    clients, test = _vector_clients(n)
    cfg = _cfg(rounds=3, superstep=3)
    eng = ScenarioEngine(TinyMLP(), clients, test, cfg, sc)
    assert eng.mode == "fused-staged"
    hist = eng.run()
    assert len(hist) == 3
    assert all(np.isfinite(m.loss) for m in hist)


# ------------------------------------------- spans, scopes and counters
def _mlp9_engine(schedule="sequential", wire="none", eval_every=4):
    """A tiny mlp9 fleet over two K=2 windows; with ``eval_every=4`` only
    the first window evaluates (cloud syncs every round)."""
    from repro import api
    spec = api.ExperimentSpec(
        model="mlp9",
        train=api.TrainConfig(rounds=4, local_steps=1, batch_size=8,
                              optimizer="sgd", lr=1e-2,
                              eval_every=eval_every,
                              server_schedule=schedule, wire=wire),
        fleet=api.FleetConfig(n_vehicles=8, scenario="highway_corridor",
                              per_vehicle_samples=16, test_samples=300),
        runtime=api.RuntimeConfig(superstep=2, slot_capacity="tight8"))
    return api.build_engine(spec)


def test_engine_spans_nest_in_the_profiler_trace(tmp_path):
    """Two windows under jax.profiler: one engine.window per window with
    its first round as the rnd0 stat, and the engine's host spans nested
    inside it; engine.evaluate only where an evaluation is due."""
    from jax.profiler import ProfileData
    eng = _mlp9_engine()
    eng.precompile()
    with jax.profiler.trace(str(tmp_path)):
        eng.run()
    path, = tmp_path.glob("plugins/profile/*/*.xplane.pb")
    spans = [(e.start_ns, e.start_ns + e.duration_ns, e.name,
              dict(e.stats))
             for plane in ProfileData.from_file(str(path)).planes
             if plane.name.startswith("/host:")
             for line in plane.lines for e in line.events
             if e.name.startswith("engine.")]
    windows = sorted(s for s in spans if s[2] == "engine.window")
    assert [w[3].get("rnd0") for w in windows] == [0, 2]
    children = ("engine.stage", "engine.dispatch", "engine.pull",
                "engine.global_model", "engine.metrics")
    for i, (a, b, _, _) in enumerate(windows):
        inside = {n for s, e, n, _ in spans
                  if a <= s and e <= b and n != "engine.window"}
        due = {"engine.evaluate"} if i == 0 else set()
        assert inside == set(children) | due
    assert sum(n == "engine.evaluate" for _, _, n, _ in spans) == 1


@pytest.mark.parametrize("schedule", ["sequential", "parallel"])
def test_engine_counters_match_a_hand_count(schedule):
    """After two windows the counters are what the engine did: one
    super-step per window, the global model's copies (a copy per leaf) or
    unravel (one program), two eval batches of the 300 test samples in
    the window that evaluates; a host read per scan output and per eval
    batch.  reset() zeroes them."""
    eng = _mlp9_engine(schedule)
    eng.precompile()
    assert set(eng.counters.values()) == {0}
    eng.run()
    leaves = len(jax.tree.leaves({"units": eng.units, "head": eng.head}))
    per_window = 1 + (leaves if schedule == "sequential" else 1)
    ys_leaves = 7       # loss, cnt, cuts, serving, rates, handover, counts
    assert eng.counters == {
        "windows": 2, "rounds": 4, "dispatches": 2 * per_window + 2,
        "host_syncs": 2 * ys_leaves + 2, "evaluations": 1}
    eng.reset()
    assert set(eng.counters.values()) == {0}


@pytest.mark.parametrize("schedule", ["sequential", "parallel"])
def test_named_scopes_in_the_lowered_program(schedule):
    """Every phase scope names ops of the fused window (the wire's in a
    wire cell), and the eval program carries its own; without debug info
    the scopes leave no trace in the program text."""
    from repro.core.fedsim import make_eval_fn
    eng = _mlp9_engine(schedule, wire="int8")
    cap = eng._capacity(4)
    sig = eng.programs.signature(2, cap, eng._total_slots(4))
    low = eng.programs._build(sig).lower(eng._carry, eng._window_xs(0, 2))
    text = low.as_text(debug_info=True)
    for scope in ("asfl.control", "asfl.batch", "asfl.grad", "asfl.wire",
                  "asfl.server_update", "asfl.client_update",
                  "asfl.merge"):
        assert scope in text, scope
    assert "asfl." not in low.as_text()
    ev = make_eval_fn(eng.model).lower(
        eng.units, eng.head, eng.test["images"][:8], eng.test["labels"][:8])
    assert "asfl.eval" in ev.as_text(debug_info=True)


# sha256 of the lowered StableHLO, without debug info, of the parallel and
# streaming windows of ``_mlp9_engine``: these schedules never run the
# sequential slot chain, so a change to it leaves their programs as they
# were
FLAT_SCHEDULE_HLO = {
    "parallel":
        "738d0a78b7f5577dec5af0642c245cdddf520476a048ffbcca7e8caf0d76d1ab",
    "streaming":
        "502eba971d0ecc7ba2b5c28fed3efcbb3a98d72d300040c61113b835aa654a7b",
}


def _window_program(eng):
    cap = eng._capacity(4)
    sig = eng.programs.signature(2, cap, eng._total_slots(4))
    return eng.programs._build(sig), (eng._carry, eng._window_xs(0, 2)), cap


@pytest.mark.parametrize("schedule", sorted(FLAT_SCHEDULE_HLO))
def test_flat_schedules_lower_to_pinned_programs(schedule):
    import hashlib
    fn, args, _ = _window_program(_mlp9_engine(schedule))
    text = fn.lower(*args).as_text()
    assert hashlib.sha256(text.encode()).hexdigest() == \
        FLAT_SCHEDULE_HLO[schedule]


def test_sequential_slot_loop_has_no_static_trip_count():
    """The slot chain is a while loop bounded by the round's fullest RSU,
    not a scan over the slot capacity C (C = 8 here, distinct from the
    window's K = 2 rounds and its single local step)."""
    fn, args, cap = _window_program(_mlp9_engine("sequential"))

    def eqns(jaxpr):
        for e in jaxpr.eqns:
            yield e
            for p in e.params.values():
                for sub in (p if isinstance(p, (list, tuple)) else [p]):
                    sub = getattr(sub, "jaxpr", sub)     # ClosedJaxpr
                    if hasattr(sub, "eqns"):
                        yield from eqns(sub)

    prims = list(eqns(jax.make_jaxpr(fn)(*args).jaxpr))
    scan_lengths = [e.params["length"] for e in prims
                    if e.primitive.name == "scan"]
    assert cap == 8 and 8 not in scan_lengths, scan_lengths
    assert any(e.primitive.name == "while" for e in prims)
