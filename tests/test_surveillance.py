"""Surveillance engine invariants: the batched fleet tick must equal the
per-job scalar pipeline exactly (periods/profiles) and to float tolerance
(confidences); staleness epochs must cache and invalidate correctly; empty
fleets and short windows must be graceful; the fleet SoA telemetry must
agree with per-job ring buffers."""
import numpy as np
import pytest

from repro.core import characterize, cycles, postpone as pp
from repro.core.fleetsim import WorkloadTrace, make_training_nb, table3_traces
from repro.core.surveillance import SurveillanceEngine
from repro.core.telemetry import FleetTelemetry, TelemetryBuffer

WINDOW = 128


def _record_steps(fleet, traces, t0s, rng, start, count):
    fields = fleet.fields
    for s in range(start, start + count):
        vals = np.asarray([[tr.sample_indexes(t0 + s, rng)[f] for f in fields]
                           for tr, t0 in zip(traces, t0s)])
        fleet.record_fleet(s, vals)


def _fill_fleet(seed=0):
    """Fleet of table3-style traces (phase_s=4 -> short cycles that fit a
    128-sample window) in one FleetTelemetry store."""
    rng = np.random.default_rng(seed)
    traces = list(table3_traces(phase_s=4.0).values())
    traces.append(WorkloadTrace([("CPU", 4)], 3600))        # acyclic job
    fleet = FleetTelemetry(len(traces), capacity=4 * WINDOW)
    t0s = [rng.uniform(0, tr.cycle_s) for tr in traces]
    _record_steps(fleet, traces, t0s, rng, 0, WINDOW)
    return fleet, traces, t0s, rng


def _scalar_pipeline(nb, buf, window, now_step, folded=False):
    """The seed per-job path: classify -> fit_cycle -> postpone."""
    w = buf.window(window)
    _, lm, _ = characterize.classify_series(nb, w)
    model = cycles.fit_cycle(lm, folded=folded)
    origin = buf.latest_step() - len(w) + 1
    return model, pp.postpone(model, now_step - origin)


def _register_all(engine, nb, fleet):
    for i, v in enumerate(fleet.views()):
        engine.register(f"j{i}", v, nb, window=WINDOW)


@pytest.fixture(scope="module")
def nb():
    return make_training_nb()


def test_tick_matches_scalar_pipeline(nb):
    fleet, traces, _, _ = _fill_fleet()
    eng = SurveillanceEngine()
    _register_all(eng, nb, fleet)
    now_step = WINDOW - 1
    res = eng.tick(now_step)
    assert res.fleet == len(traces)
    for i in range(len(traces)):
        job = eng.jobs[f"j{i}"]
        model, remain = _scalar_pipeline(nb, fleet.view(i), WINDOW, now_step)
        assert job.model.period == model.period, i
        np.testing.assert_array_equal(job.model.profile_lm, model.profile_lm)
        np.testing.assert_array_equal(job.model.array_lm, model.array_lm)
        np.testing.assert_allclose(job.model.confidence, model.confidence,
                                   atol=1e-6)
        if model.cyclic:
            assert res.remain[f"j{i}"] == remain, i


def test_incremental_refresh_matches_full_reclassify(nb):
    """Sliding the window and refitting through the staleness-epoch splice
    path must equal classifying the full window from scratch."""
    fleet, traces, t0s, rng = _fill_fleet()
    eng = SurveillanceEngine()
    _register_all(eng, nb, fleet)
    eng.refresh(force=True)                    # full-window first fit
    _record_steps(fleet, traces, t0s, rng, WINDOW, 37)
    eng.refresh(force=True)                    # delta=37 -> incremental path
    for i in range(len(traces)):
        job = eng.jobs[f"j{i}"]
        w = fleet.view(i).window(WINDOW)
        _, lm_full, _ = characterize.classify_series(nb, w)
        np.testing.assert_array_equal(job.lm_series, lm_full)
        model = cycles.fit_cycle(lm_full)
        assert job.model.period == model.period
        np.testing.assert_array_equal(job.model.profile_lm, model.profile_lm)
        np.testing.assert_allclose(job.model.confidence, model.confidence,
                                   atol=1e-6)
        assert job.origin_step == fleet.latest_step(i) - WINDOW + 1


def test_blocked_classify_matches_one_piece(nb, monkeypatch):
    """A fleet window spanning several classify row blocks (and a ragged
    last block) must tick exactly like the classify in one piece."""
    fleet, traces, t0s, rng = _fill_fleet()
    whole = SurveillanceEngine()
    _register_all(whole, nb, fleet)
    res_whole = whole.tick(WINDOW - 1)
    monkeypatch.setattr(characterize, "CLASSIFY_BLOCK", 200)
    blocked = SurveillanceEngine()
    _register_all(blocked, nb, fleet)
    res_blocked = blocked.tick(WINDOW - 1)
    assert len(traces) * WINDOW > 3 * characterize.CLASSIFY_BLOCK
    assert res_blocked.remain == res_whole.remain
    for jid, job in whole.jobs.items():
        np.testing.assert_array_equal(blocked.jobs[jid].lm_series,
                                      job.lm_series)
        assert blocked.jobs[jid].model.period == job.model.period


def test_staleness_epochs(nb):
    fleet, traces, _, _ = _fill_fleet()
    eng = SurveillanceEngine()
    _register_all(eng, nb, fleet)
    assert eng.refresh() == len(traces)        # first fit: everything stale
    assert eng.refresh() == 0                  # nothing moved: all cached
    job = eng.jobs["j1"]                       # vm02_C: MEM phases -> cyclic
    model0 = job.model
    period = model0.period
    assert period > 1
    rng = np.random.default_rng(5)
    fields = fleet.fields
    # advance fewer than period//4 samples: fit must stay cached
    few = max(1, period // 4 - 2)
    for s in range(few):
        fleet.record_fleet(WINDOW + s, rng.random((len(traces), len(fields))))
    eng.refresh()
    assert eng.jobs["j1"].model is model0
    # cross the epoch boundary: fit must be recomputed
    for s in range(few, period // 4 + 1):
        fleet.record_fleet(WINDOW + s, rng.random((len(traces), len(fields))))
    eng.refresh()
    assert eng.jobs["j1"].model is not model0
    assert eng.jobs["j1"].fitted_step == fleet.latest_step(1)


def test_empty_fleet_and_short_window(nb):
    eng = SurveillanceEngine()
    res = eng.tick(0)                          # no jobs registered at all
    assert res.remain == {} and res.fleet == 0 and res.refitted == 0
    buf = TelemetryBuffer(capacity=64)
    eng.register("tiny", buf, nb, window=WINDOW)
    assert eng.tick(0).fleet == 0              # no samples yet
    for s in range(4):                         # below min_samples
        buf.record(s, compute_util=0.5)
    assert eng.refresh() == 0
    assert eng.refresh_model("tiny") is None
    for s in range(4, 16):                     # crosses min_samples
        buf.record(s, compute_util=0.5)
    assert eng.refresh_model("tiny") is not None
    assert eng.tick(15).fleet == 1


def test_fresh_tick_zero_perjob_work(nb, monkeypatch):
    """Decide-plane cache regression guard: a tick over an all-fresh fleet
    (nothing stale) must not repack Algorithm 2's operands — no pack_fleet
    call, i.e. zero per-job Python work beyond the staleness scan — and
    must still return the correct remains via the cached operands."""
    fleet, traces, _, _ = _fill_fleet()
    eng = SurveillanceEngine()
    _register_all(eng, nb, fleet)
    want = eng.tick(WINDOW - 1).remain          # first tick builds the cache

    calls = []
    orig = pp.pack_fleet

    def counting(*a, **k):
        calls.append(1)
        return orig(*a, **k)

    monkeypatch.setattr(pp, "pack_fleet", counting)
    res = eng.tick(WINDOW - 1)                  # all fresh: cache hit
    assert not calls, "fresh tick repacked the fleet (per-job Python work)"
    assert res.remain == want and res.refitted == 0

    # registration invalidates: the new job must repack on the next tick
    lone = TelemetryBuffer(capacity=WINDOW)
    for s in range(WINDOW):
        lone.record(s, compute_util=0.5)
    eng.register("late", lone, nb, window=WINDOW)
    res = eng.tick(WINDOW - 1)
    assert calls and "late" in res.remain

    # unregister invalidates too: the job must vanish from the decide
    calls.clear()
    eng.unregister("late")
    res = eng.tick(WINDOW - 1)
    assert calls and "late" not in res.remain
    assert res.remain == want


def test_mixed_backing_stores_one_gather(nb):
    """window_matrix must agree across fleet views and foreign buffers."""
    fleet, traces, _, _ = _fill_fleet()
    lone = TelemetryBuffer(capacity=256)
    rng = np.random.default_rng(9)
    tr = traces[0]
    for s in range(WINDOW):
        lone.record(s, **tr.sample_indexes(s * 1.0, rng))
    bufs = [fleet.view(0), lone, fleet.view(2)]
    W, lens = TelemetryBuffer.window_matrix(bufs, WINDOW)
    assert W.shape == (3, WINDOW, len(fleet.fields))
    assert lens.tolist() == [WINDOW] * 3
    for k, b in enumerate(bufs):
        np.testing.assert_allclose(W[k], b.window(WINDOW))


def test_fleet_telemetry_wraps_like_scalar_buffers():
    J, cap, steps = 3, 16, 41
    fleet = FleetTelemetry(J, capacity=cap)
    bufs = [TelemetryBuffer(capacity=cap) for _ in range(J)]
    rng = np.random.default_rng(0)
    for s in range(steps):
        vals = rng.random((J, len(fleet.fields)))
        fleet.record_fleet(s, vals)
        for j, b in enumerate(bufs):
            b.record(s, **dict(zip(fleet.fields, vals[j])))
    for n in (4, cap, cap + 5):
        W, m = fleet.window_matrix(n)
        for j in range(J):
            w = bufs[j].window(n)
            assert m[j] == len(w)
            np.testing.assert_allclose(W[j, n - len(w):], w)
    assert fleet.latest_steps().tolist() == [steps - 1] * J
