"""The program's own spans (``alma.*``, ``src/repro/spans.py``) as the
benchmark reads them (``bench/program_spans.py``).

On the CPU, under ``jax.profiler.start_trace`` and through
``bench/trace.reduce``: a 64-job decide plane with the Pallas kernels in
interpret mode, its numpy path with the decide overlapped, and small live
pre-copies. On synthetic reduced traces: the idle gaps by the innermost span
of either kind, and the ten per-layer numbers. On a trace recorded on one
TPU v5e (``scripts/record_spans_trace.py``): every decide span, and the
device's programs placed against the host's dispatches and waits.
"""
import pathlib
import sys

import numpy as np
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT / "src"))

from bench import program_spans as ps  # noqa: E402
from bench import trace  # noqa: E402
from bench.gen import fleet as gen  # noqa: E402
from repro import spans  # noqa: E402

SEED = 3141592653
DECIDE = [n for n in spans.NAMES if not n.startswith("precopy.")]
PRECOPY = [n for n in spans.NAMES if n.startswith("precopy.")]
RECORDED = ROOT / "bench" / "testdata" / "tpu_v5e_spans.xplane.pb"
SMALL = ROOT / "bench" / "testdata" / "tpu_v5e_small.xplane.pb"


def traced(tmp_path, body):
    """Run ``body()`` inside a ``bench.window`` span under the profiler;
    returns (its result, the reduced trace, the program's spans)."""
    import jax
    jax.profiler.start_trace(str(tmp_path))
    try:
        with jax.profiler.TraceAnnotation("bench.window"):
            out = body()
    finally:
        jax.profiler.stop_trace()
    path = next(tmp_path.glob("plugins/profile/*/*.xplane.pb"))
    red = trace.reduce(path)
    return out, red, ps.read(path, red.window)


def decide_run(use_kernel, overlap, jobs=64, window=128, steps=3):
    """A decide plane over the Table 3 load with 4 s phases: a tick that
    fits half the jobs, then ``steps`` ticks of one new sample each. The
    other half joins at the last tick, where the first half's cyclic jobs
    refit through the splice path: two groups in one tick. Returns the
    body that runs the ticks, and a list it fills with the number of
    batched refits each tick ran."""
    from repro.core import characterize
    from repro.core.surveillance import SurveillanceEngine
    from repro.core.telemetry import FleetTelemetry
    rows = gen.make_load("table3", jobs, window + steps, seed=SEED,
                         phase_s=4.0).transpose(1, 0, 2)
    nb = characterize.fit(*gen.nb_training_set(SEED, 400))
    store = FleetTelemetry(jobs, capacity=window, fields=gen.FIELDS)
    for s in range(window):
        store.record_fleet(s, rows[s])
    engine = SurveillanceEngine(use_kernel=use_kernel, overlap=overlap)
    views = store.views()

    def join(rows):
        for i in rows:
            engine.register(f"j{i}", views[i], nb, window=window)
    join(range(jobs // 2))
    calls = []
    refresh_group = engine._refresh_group

    def counted(*a, **k):
        calls[-1] += 1
        return refresh_group(*a, **k)
    engine._refresh_group = counted

    def body():
        results = []
        for s in range(window - 1, window + steps):
            if s >= window:
                store.record_fleet(s, rows[s])
            if s == window + steps - 1:
                join(range(jobs // 2, jobs))
            calls.append(0)
            res = engine.tick(s)
            res.remain
            results.append(res)
        return results
    return body, calls


@pytest.fixture(scope="module")
def kernel_ticks(tmp_path_factory):
    body, calls = decide_run(use_kernel=True, overlap=False)
    results, red, program = traced(tmp_path_factory.mktemp("kernel"), body)
    return results, calls, program


def named(program, name):
    return [s for s in program if s.name == ps.PREFIX + name]


@pytest.mark.parametrize("name", DECIDE)
def test_every_decide_span_appears(kernel_ticks, name):
    _, _, program = kernel_ticks
    assert named(program, name)


@pytest.mark.parametrize("sync,stage", [
    ("sync.classify", "surveil.classify"), ("sync.spectrum", "cycles.spectrum"),
    ("sync.refine", "cycles.refine"), ("sync.remain", "surveil.remain")])
def test_each_sync_nests_in_its_stage(kernel_ticks, sync, stage):
    _, _, program = kernel_ticks
    syncs, stages = named(program, sync), named(program, stage)
    assert len(syncs) == len(stages)
    for s, outer in zip(syncs, stages):
        assert outer.start <= s.start < s.end <= outer.end


def test_tick_args_count_the_refits(kernel_ticks):
    results, calls, program = kernel_ticks
    ticks = named(program, "surveil.tick")
    assert [t.args["refitted"] for t in ticks] == [r.refitted
                                                  for r in results]
    assert [t.args["groups"] for t in ticks] == calls
    assert [t.args["jobs"] for t in ticks] == [32, 32, 32, 64]
    assert [t.args["packed"] for t in ticks] == [1, 0, 0, 1]
    assert calls[-1] > 1
    refits = named(program, "surveil.refit")
    assert len(refits) == sum(calls)
    assert sum(r.args["rows"] for r in refits) == sum(r.refitted
                                                     for r in results)
    assert any(r.args["tail"] < r.args["window"] for r in refits)


def test_numpy_path_with_overlap_keeps_its_stages(tmp_path):
    body, calls = decide_run(use_kernel=False, overlap=True, steps=1)
    results, _, program = traced(tmp_path, body)
    seen = {s.name[len(ps.PREFIX):] for s in program}
    assert seen == set(DECIDE) - {"sync.spectrum", "sync.refine"}
    # the overlapped decide is read after the tick has returned
    for tick, remain in zip(named(program, "surveil.tick"),
                            named(program, "surveil.remain")):
        assert remain.start >= tick.end


def precopy_run(step_every_round):
    import jax.numpy as jnp
    from repro.core import precopy
    rng = np.random.default_rng(0)
    state = {"w": jnp.asarray(rng.standard_normal((64, 128)), jnp.float32),
             "b": jnp.asarray(rng.standard_normal(300), jnp.bfloat16),
             "step": jnp.asarray(0, jnp.int32)}

    def step():
        state["w"] = state["w"].at[0].add(1.0)
        state["step"] = state["step"] + 1

    cfg = precopy.PrecopyConfig(block_elems=256, max_rounds=4,
                                stop_dirty_blocks=0)
    return lambda: precopy.migrate(lambda: dict(state),
                                   step if step_every_round else None, cfg)


@pytest.mark.parametrize("live", [True, False], ids=["max_rounds", "idle"])
def test_precopy_spans_follow_the_rounds(tmp_path, live):
    (dest, report), _, program = traced(tmp_path, precopy_run(live))
    rounds = report.outcome.rounds
    assert report.outcome.stop_reason == ("max_rounds" if live
                                          else "dirty_low")
    assert {s.name[len(ps.PREFIX):] for s in program} == set(PRECOPY)
    (mig,) = named(program, "precopy.migrate")
    assert mig.args == {"state_bytes": float(report.v_mem), "leaves": 3.0,
                        "rounds": float(rounds)}
    assert [s.args["round"] for s in named(program, "precopy.round")] == \
        list(range(1, rounds + 1))
    (stop,) = named(program, "precopy.stop_copy")
    scans = named(program, "precopy.scan")
    assert len(scans) == rounds + 1
    assert all(s.args["syncs"] == s.args["leaves"] == 3.0 for s in scans)
    assert len(named(program, "precopy.merge")) == rounds
    assert stop.start <= scans[-1].start and scans[-1].end <= stop.end


# -- the reader, on synthetic traces ------------------------------------------
def S(name, a, b, **args):
    return trace.Span(name, a, b, {k: float(v) for k, v in args.items()})


def reduced(busy, bench_spans, window=(0, 100)):
    return trace.Reduced(window=window, busy={0: busy}, modules={0: []},
                         spans=[S("bench.window", *window)] + bench_spans)


def precopy_trace():
    """One migration: a round with a decode, a scan and a merge, then the
    stop-and-copy's scan and merge. Idle: 10-20 in the decode, 25-30 in the
    round outside its children, 32-40 in the scan, 50-55 in the merge,
    62-66 in the stop scan, 70-80 in the stop merge, 90-100 outside."""
    bench = [S("bench.migrate", 0, 85, leaves=3), S("bench.decode", 8, 22)]
    program = [S("alma.precopy.migrate", 2, 84, leaves=3, rounds=1),
               S("alma.precopy.round", 5, 57, round=1),
               S("alma.precopy.scan", 31, 45, leaves=3, syncs=3),
               S("alma.precopy.merge", 46, 56, leaves=3),
               S("alma.precopy.stop_copy", 60, 83),
               S("alma.precopy.scan", 61, 67, leaves=3, syncs=3),
               S("alma.precopy.merge", 68, 82, leaves=3)]
    busy = [(0, 10), (20, 25), (30, 32), (40, 50), (55, 62), (66, 70),
            (80, 90)]
    return reduced(busy, bench), program


def test_program_gaps_go_to_the_innermost_span_of_either_kind():
    red, program = precopy_trace()
    got = ps.idle_gaps(red, program)
    assert got == pytest.approx({
        "bench.decode": 10e-9, "alma.precopy.round": 5e-9,
        "alma.precopy.scan": 12e-9, "alma.precopy.merge": 15e-9,
        "outside bench spans": 10e-9})
    assert sum(got.values()) + red.busy_s == pytest.approx(red.window_s)


def test_program_spans_leave_idle_gaps_and_breakdown_as_they_were():
    red, program = precopy_trace()
    gaps, breakdown, spans = red.idle_gaps(), red.breakdown(), list(red.spans)
    ps.idle_gaps(red, program)
    assert red.idle_gaps() == gaps
    assert red.breakdown() == breakdown
    assert red.spans == spans
    assert gaps == pytest.approx({"bench.decode": 10e-9,
                                  "bench.migrate": 32e-9,
                                  "outside bench spans": 10e-9})


def test_no_program_spans_give_the_benchmarks_gaps_on_a_v5e_trace():
    red = trace.reduce(SMALL)
    assert ps.idle_gaps(red, []) == red.idle_gaps()
    assert ps.decide(red, [], ticks=3) == {}
    assert ps.precopy(red, []) == {}


def decide_trace():
    """Two ticks of 40 ns. Tick one: stale scan 2, one refit of 20 with
    syncs of 1 + 3 + 2 inside, pack_fleet 5, decide 1, remain 4 (its sync
    2). Tick two: stale scan 4, no refit, decide 3, remain 2 (sync 1)."""
    spans = [S("bench.tick", 0, 40), S("bench.tick", 50, 90)]
    program = [S("alma.surveil.tick", 1, 38),
               S("alma.surveil.stale_scan", 1, 3),
               S("alma.surveil.refit", 3, 23, rows=8),
               S("alma.sync.classify", 5, 6), S("alma.sync.spectrum", 8, 11),
               S("alma.sync.refine", 15, 17),
               S("alma.surveil.pack_fleet", 24, 29),
               S("alma.surveil.decide", 30, 31),
               S("alma.surveil.remain", 32, 36), S("alma.sync.remain", 32, 34),
               S("alma.surveil.tick", 51, 70),
               S("alma.surveil.stale_scan", 51, 55),
               S("alma.surveil.decide", 56, 59),
               S("alma.surveil.remain", 60, 62), S("alma.sync.remain", 60, 61)]
    return reduced([], spans), program


@pytest.mark.parametrize("metric,want", [
    ("stale_scan_ms_per_tick", 1e-6 * 6 / 2),
    ("refit_host_ms_per_tick", 1e-6 * (20 - 6) / 2),
    ("refit_sync_ms_per_tick", 1e-6 * 6 / 2),
    ("pack_fleet_ms_per_tick", 1e-6 * 5 / 2),
    ("remain_ms_per_tick", 1e-6 * (1 + 4 + 3 + 2) / 2),
    ("host_syncs_per_tick", 5 / 2)])
def test_decide_reader(metric, want):
    red, program = decide_trace()
    assert ps.decide(red, program, ticks=2)[metric] == pytest.approx(want)


@pytest.mark.parametrize("metric,want", [
    ("scan_idle_ms_per_round", 1e-6 * (8 + 4) / 2),
    ("merge_idle_ms_per_round", 1e-6 * (5 + 10) / 2),
    ("dirty_syncs_per_round", 3.0),
    ("stop_copy_ms_per_migration", 1e-6 * 23)])
def test_precopy_reader(metric, want):
    red, program = precopy_trace()
    assert ps.precopy(red, program)[metric] == pytest.approx(want)


def test_readers_find_nothing_without_their_spans():
    red, program = decide_trace()
    assert ps.decide(red, program, ticks=0) == {}
    assert ps.precopy(red, program) == {}
    red, program = precopy_trace()
    assert ps.decide(red, program, ticks=2) == {}


# -- a trace recorded on one TPU v5e --------------------------------------------
@pytest.fixture(scope="module")
def recorded():
    red = trace.reduce(RECORDED)
    return red, ps.read(RECORDED, red.window)


@pytest.mark.parametrize("name", DECIDE)
def test_recorded_trace_has_every_decide_span(recorded, name):
    _, program = recorded
    assert named(program, name)


WAITS = [("jit__dft_power", "cycles.spectrum", "sync.spectrum"),
         ("jit__autocorr_score", "cycles.refine", "sync.refine"),
         ("jit__nb_predict_lm", "surveil.classify", "sync.classify"),
         ("jit_postpone_batch", "surveil.decide", "sync.remain")]


def runs_of(red, module):
    return [(s, s + d) for n, s, d in red.modules[0] if n == module]


@pytest.mark.parametrize("module,stage,sync", WAITS[:2])
def test_a_sync_ends_after_the_program_it_waits_for(recorded, module,
                                                    stage, sync):
    red, program = recorded
    runs, syncs = runs_of(red, module), named(program, sync)
    assert len(runs) == len(syncs) >= 2
    for (_, end), s in zip(runs, syncs):
        assert s.end >= end


def test_one_shift_of_the_device_clock_orders_dispatch_and_wait(recorded):
    """Every program of a tick starts after the host span that dispatched
    it began and ends before the host's wait on it ended, once the device's
    events are shifted by one constant; on this v5e trace that shift lies
    between 0.83 and 1.79 ms (the device's events sit early), so the two
    clocks agree to within 2 ms, not to the microsecond."""
    red, program = recorded
    lo, hi = -np.inf, np.inf
    for module, stage, sync in WAITS:
        runs = runs_of(red, module)
        stages, syncs = named(program, stage), named(program, sync)
        assert len(runs) == len(stages) == len(syncs) >= 2, module
        for (start, end), st, sy in zip(runs, stages, syncs):
            lo, hi = max(lo, st.start - start), min(hi, sy.end - end)
    assert lo <= hi
    assert max(lo, 0.0) < 2e6                     # ns


def test_recorded_trace_reads_its_decide_numbers(recorded):
    red, program = recorded
    ticks = len(named(program, "surveil.tick"))
    got = ps.decide(red, program, ticks)
    assert set(got) == {"stale_scan_ms_per_tick", "refit_host_ms_per_tick",
                        "refit_sync_ms_per_tick", "pack_fleet_ms_per_tick",
                        "remain_ms_per_tick", "host_syncs_per_tick"}
    assert all(v > 0 for v in got.values())
