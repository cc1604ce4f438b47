"""The program's own spans in a profiler trace, and the per-layer numbers
they give.

The program names its host spans ``alma.<stage>`` (``src/repro/spans.py``)
and writes them into the same trace as the benchmark's ``bench.*`` spans.
The trace puts the device's operations within 2 ms of them on one clock (on
a v5e 0.83-1.79 ms early: ``bench/tests/test_program_spans.py``), so a gap
much shorter than that may land in the span beside it.
``bench/trace.reduce`` keeps the ``bench.*`` spans; ``read`` keeps the
``alma.*`` spans of the same file that lie in the same window.

``idle_gaps`` runs the sweep of ``Reduced.idle_gaps`` over the spans of both
kinds together: each idle gap of the chip goes to the innermost span of
either kind open at its midpoint, so a gap inside ``bench.decode`` within a
pre-copy round stays with ``bench.decode``. The ``Reduced`` is not changed.

``decide`` and ``precopy`` give the per-layer numbers of the two system
paths from a reduced trace and the program's spans; a trace without the
program's spans gives an empty dict.
"""
from __future__ import annotations

import bisect
import dataclasses
from typing import Dict, Iterable, List, Tuple

from bench.trace import Reduced, Span, _number

PREFIX = "alma."
SYNC = PREFIX + "sync."
#: the host waits on the device inside a refit
REFIT_SYNCS = (SYNC + "classify", SYNC + "spectrum", SYNC + "refine")


def read(path, window: Tuple[float, float]) -> List[Span]:
    """The program's spans in the trace at ``path`` that overlap
    ``window`` (ns, the window of ``bench.trace.reduce``)."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(str(path))
    lo, hi = window
    out: List[Span] = []
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if not e.name.startswith(PREFIX):
                    continue
                start, end = e.start_ns, e.start_ns + e.duration_ns
                if end > lo and start < hi:
                    args = {k: _number(v) for k, v in e.stats}
                    out.append(Span(e.name, start, end,
                                    {k: v for k, v in args.items()
                                     if v is not None}))
    return sorted(out, key=lambda s: (s.start, -s.end))


def idle_gaps(red: Reduced, program: List[Span]) -> Dict[str, float]:
    """Idle seconds of the first chip inside the window, by the innermost
    span of the benchmark or of the program open at each gap's midpoint."""
    return dataclasses.replace(red, spans=red.spans + program).idle_gaps()


def _named(spans: List[Span], names: Iterable[str]) -> List[Span]:
    names = set(names)
    return [s for s in spans if s.name in names]


def _seconds(spans: List[Span], window: Tuple[float, float]) -> float:
    """Summed duration of ``spans`` inside ``window``."""
    lo, hi = window
    return sum(max(0.0, min(s.end, hi) - max(s.start, lo))
               for s in spans) * 1e-9


def _inside(inner: List[Span], outer: List[Span]) -> List[Span]:
    """The spans of ``inner`` that lie within a span of ``outer``; the
    spans of ``outer`` do not overlap each other."""
    outer = sorted(outer, key=lambda s: s.start)
    starts = [s.start for s in outer]
    out = []
    for s in inner:
        i = bisect.bisect_right(starts, s.start) - 1
        if i >= 0 and s.end <= outer[i].end:
            out.append(s)
    return out


def decide(red: Reduced, program: List[Span], ticks: int
           ) -> Dict[str, float]:
    """Per tick of the decide plane: host milliseconds of the staleness
    scan, of the refits without their host waits on the device, of those
    waits, of the Algorithm 2 operands' rebuild, of Algorithm 2's dispatch
    and RemainTime dict; and the host waits on the device."""
    if not ticks or not _named(program, [PREFIX + "surveil.tick"]):
        return {}
    win = red.window
    refits = _named(program, [PREFIX + "surveil.refit"])
    syncs = [s for s in program if s.name.startswith(SYNC)]
    refit_syncs = _named(syncs, REFIT_SYNCS)
    per_tick = 1e3 / ticks
    return {
        "stale_scan_ms_per_tick": per_tick * _seconds(
            _named(program, [PREFIX + "surveil.stale_scan"]), win),
        "refit_host_ms_per_tick": per_tick * (
            _seconds(refits, win)
            - _seconds(_inside(refit_syncs, refits), win)),
        "refit_sync_ms_per_tick": per_tick * _seconds(refit_syncs, win),
        "pack_fleet_ms_per_tick": per_tick * _seconds(
            _named(program, [PREFIX + "surveil.pack_fleet"]), win),
        "remain_ms_per_tick": per_tick * _seconds(
            _named(program, [PREFIX + "surveil.decide",
                             PREFIX + "surveil.remain"]), win),
        "host_syncs_per_tick": len(syncs) / ticks,
    }


def precopy(red: Reduced, program: List[Span]) -> Dict[str, float]:
    """Per dirty scan and per merge, the chip's idle milliseconds inside
    it; the host syncs per scan; and the mean stop-and-copy, in ms."""
    scans = _named(program, [PREFIX + "precopy.scan"])
    merges = _named(program, [PREFIX + "precopy.merge"])
    stops = _named(program, [PREFIX + "precopy.stop_copy"])
    if not scans or not merges or not stops:
        return {}
    gaps = idle_gaps(red, program)
    return {
        "scan_idle_ms_per_round":
            1e3 * gaps.get(PREFIX + "precopy.scan", 0.0) / len(scans),
        "merge_idle_ms_per_round":
            1e3 * gaps.get(PREFIX + "precopy.merge", 0.0) / len(merges),
        "dirty_syncs_per_round":
            sum(s.args.get("syncs", 0.0) for s in scans) / len(scans),
        "stop_copy_ms_per_migration":
            1e3 * _seconds(stops, red.window) / len(stops),
    }
