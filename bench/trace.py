"""Reduce a profiler trace (``.xplane.pb``) to the benchmark's device numbers.

On a TPU the trace holds, for each chip, a plane ``/device:TPU:<n>`` whose
``XLA Modules`` line has one event per program run (``jit_<fn>(<hash>)``)
and whose ``XLA Ops`` line has one event per operation. The host planes
hold the benchmark's own spans (``jax.profiler.TraceAnnotation`` named
``bench.*``) on the same clock. From these:

- the traced window: the ``bench.window`` span, else the device events'
  extent;
- busy time: the union of the operations' intervals inside the window,
  per chip; ``busy_s`` is its mean over the chips used;
- device time and run count of each program, by name without its hash;
- idle gaps: the window minus the busy union on the first chip, each gap
  attributed to the innermost benchmark span around its midpoint, that is
  to what the host was doing while the chip waited.
"""
from __future__ import annotations

import re
from collections import defaultdict
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
HASH_SUFFIX = re.compile(r"\(\d+\)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"


@dataclass
class Span:
    name: str
    start: float                 # ns, profile clock
    end: float
    args: Dict[str, float] = field(default_factory=dict)


def _number(v) -> Optional[float]:
    try:
        return float(v)
    except (TypeError, ValueError):
        return None


def _union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[Tuple[float, float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


@dataclass
class Reduced:
    window: Tuple[float, float]                       # ns
    busy: Dict[int, List[Tuple[float, float]]]        # chip -> union, ns
    modules: Dict[int, List[Tuple[str, float, float]]]  # chip -> (name, start, dur)
    spans: List[Span]

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    @property
    def busy_s(self) -> float:
        per_chip = [sum(b - a for a, b in u) * 1e-9
                    for u in self.busy.values()]
        return sum(per_chip) / max(len(per_chip), 1)

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def module_time(self, name: str) -> Tuple[float, int]:
        """(device seconds, runs) of program ``name`` on the first chip."""
        chip = min(self.modules) if self.modules else None
        runs = [d for n, _, d in self.modules.get(chip, []) if n == name]
        return sum(runs) * 1e-9, len(runs)

    def spans_named(self, name: str) -> List[Span]:
        return [s for s in self.spans if s.name == name]

    def idle_gaps(self) -> Dict[str, float]:
        """Idle seconds of the first chip inside the window, by the
        innermost benchmark span open at each gap's midpoint."""
        if not self.busy:
            return {}
        busy = self.busy[min(self.busy)]
        lo, hi = self.window
        gaps, t = [], lo
        for a, b in busy:
            if a > t:
                gaps.append((t, a))
            t = max(t, b)
        if hi > t:
            gaps.append((t, hi))
        # the benchmark's spans come from one thread and nest, so a stack
        # swept forward in time holds the spans open at each gap's midpoint
        inner = sorted((s for s in self.spans if s.name != WINDOW_SPAN),
                       key=lambda s: (s.start, -s.end))
        out: Dict[str, float] = defaultdict(float)
        stack: List[Span] = []
        i = 0
        for a, b in gaps:
            mid = 0.5 * (a + b)
            while i < len(inner) and inner[i].start <= mid:
                while stack and stack[-1].end < inner[i].start:
                    stack.pop()
                stack.append(inner[i])
                i += 1
            while stack and stack[-1].end < mid:
                stack.pop()
            name = stack[-1].name if stack else "outside bench spans"
            out[name] += (b - a) * 1e-9
        return dict(out)

    def breakdown(self, k: int = 10) -> Dict[str, list]:
        by_module: Dict[str, float] = defaultdict(float)
        if self.modules:
            for n, _, d in self.modules[min(self.modules)]:
                by_module[n] += d * 1e-9
        top = sorted(by_module.items(), key=lambda e: -e[1])[:k]
        gaps = sorted(self.idle_gaps().items(), key=lambda e: -e[1])[:k]
        return {"device_ops": [[n, s] for n, s in top],
                "idle_gaps": [[n, s] for n, s in gaps]}


def reduce(path, devices: int = 1) -> Reduced:
    """Read ``path`` and keep what lies in the traced window of the first
    ``devices`` chips."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(str(path))
    ops: Dict[int, List[Tuple[float, float]]] = {}
    modules: Dict[int, List[Tuple[str, float, float]]] = {}
    spans: List[Span] = []
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            chip = int(m.group(1))
            if chip >= devices:
                continue
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops[chip] = [(e.start_ns, e.start_ns + e.duration_ns)
                                 for e in line.events]
                elif line.name == MODULES_LINE:
                    modules[chip] = [(HASH_SUFFIX.sub("", e.name), e.start_ns,
                                      e.duration_ns) for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        args = {k: _number(v) for k, v in e.stats}
                        spans.append(Span(e.name, e.start_ns,
                                          e.start_ns + e.duration_ns,
                                          {k: v for k, v in args.items()
                                           if v is not None}))
    win = [s for s in spans if s.name == WINDOW_SPAN]
    if win:
        window = (win[0].start, win[0].end)
    else:
        edges = [t for iv in ops.values() for a, b in iv for t in (a, b)]
        window = (min(edges), max(edges)) if edges else (0.0, 0.0)
    lo, hi = window
    busy = {c: _union([(max(a, lo), min(b, hi)) for a, b in iv
                       if b > lo and a < hi]) for c, iv in ops.items()}
    modules = {c: [(n, s, d) for n, s, d in evs if lo <= s < hi]
               for c, evs in modules.items()}
    spans = [s for s in spans if s.end > lo and s.start < hi]
    return Reduced(window, busy, modules, spans)
