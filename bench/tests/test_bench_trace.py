"""The trace reducer, on a trace recorded on one TPU v5e.

``bench/testdata/tpu_v5e_small.xplane.pb`` holds three host spans
``bench.step`` (args ``i`` = 0, 1, 2), each running a bf16 matmul-sum
(``jit__lambda``), the spectrum and autocorrelation kernels and the dirty
kernel, one after another with a host sync between them.
"""
import pathlib
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import trace  # noqa: E402

TRACE = ROOT / "bench" / "testdata" / "tpu_v5e_small.xplane.pb"


@pytest.fixture(scope="module")
def red():
    return trace.reduce(TRACE)


def test_programs_are_named_without_their_hash(red):
    for name in ("jit__dft_power", "jit__autocorr_score",
                 "jit__max_abs_delta"):
        secs, runs = red.module_time(name)
        assert runs == 3, name
        assert 0 < secs < 1e-3, name
    assert red.module_time("jit__no_such_program") == (0.0, 0)


def test_busy_lies_inside_the_window(red):
    assert 0 < red.busy_s < red.window_s
    assert 0 < red.idle_share < 1
    for a, b in red.busy[0]:
        assert red.window[0] <= a < b <= red.window[1]


def test_spans_keep_their_args(red):
    steps = red.spans_named("bench.step")
    assert [s.args["i"] for s in steps] == [0.0, 1.0, 2.0]
    assert all(s.end > s.start for s in steps)


def test_idle_gaps_and_busy_fill_the_window(red):
    gaps = red.idle_gaps()
    assert set(gaps) <= {"bench.step", "outside bench spans"}
    assert sum(gaps.values()) + red.busy_s == pytest.approx(red.window_s,
                                                           rel=1e-9)


def test_breakdown_is_short_and_sorted(red):
    b = red.breakdown()
    assert set(b) == {"device_ops", "idle_gaps"}
    for key in b:
        assert len(b[key]) <= 10
        secs = [s for _, s in b[key]]
        assert secs == sorted(secs, reverse=True)
    assert b["device_ops"][0][0] == "jit__max_abs_delta"


def test_union_merges_overlaps():
    assert trace._union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3), (5, 8)]


def test_gaps_go_to_the_innermost_open_span():
    spans = [trace.Span("bench.window", 0, 100),
             trace.Span("bench.tick", 0, 50),
             trace.Span("bench.decide", 10, 40),
             trace.Span("bench.tick", 50, 100)]
    red = trace.Reduced(window=(0, 100), busy={0: [(0, 10), (40, 60)]},
                        modules={0: []}, spans=spans)
    gaps = red.idle_gaps()
    assert gaps == pytest.approx({"bench.decide": 30e-9,
                                  "bench.tick": 40e-9})
