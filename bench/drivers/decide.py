"""The decide plane: a fleet's surveillance ticks, back to back.

One tick is what an operator's control plane runs every sampling period:
one new telemetry row for every job (``FleetTelemetry.record_fleet``), then
``SurveillanceEngine.tick`` (staleness scan, classify, cycle fit of the
stale jobs, Algorithm 2 for the fleet), then ``.remain`` on the host. The
loop is closed: the next tick starts when the last one has returned.

Set-up generates the whole load from the seed (``bench/gen/fleet.py``) and
fills every job's window. Jobs then join the control plane over the
traffic's ``arrival_ticks`` ticks, in an order drawn from the seed and in
equal numbers each tick, so that their first fits, and the staleness epochs
that follow from them, are spread over the ticks as in a fleet that did not
start all at once. Set-up runs those ticks and the traffic's warm-up ticks.
The window replays the load's ``replay_steps`` rows cyclically.

The check replays the same telemetry through the plain reference
(``bench/ref/decide.py``) for ``check_jobs`` jobs drawn from the seed and
compares their RemainTime at every tick of the window and their final LM
series, period and confidence.
"""
from __future__ import annotations

import contextlib
import operator
import time
from typing import Dict, List, Tuple

import numpy as np

from bench.gen import fleet as gen
from bench.ref import decide as ref


def job_id(i: int) -> str:
    return f"job{i:05d}"


class DecideCell:
    def __init__(self, config: dict, traffic: dict, seed: int, ctx):
        from repro.core import characterize
        from repro.core.surveillance import SurveillanceEngine
        from repro.core.telemetry import FleetTelemetry

        self.ctx = ctx
        self.config = config
        W = self.W = int(config["window"])
        J = int(config["jobs"])
        self.replay = int(traffic["replay_steps"])
        feats, labels = gen.nb_training_set(
            seed, int(config["nb_training_samples"]))
        self.nb_program = characterize.fit(feats, labels)
        self.nb_ref = ref.fit_nb(feats, labels)
        load = gen.make_load(traffic["load"], J, W + self.replay, seed=seed,
                             **traffic.get("load_params", {}))
        # (steps, jobs, fields): one contiguous row per recorded step
        self.rows = np.ascontiguousarray(load.transpose(1, 0, 2))
        del load
        self.fleet = FleetTelemetry(J, capacity=W, fields=gen.FIELDS)
        for s in range(W):
            self.fleet.record_fleet(s, self.rows[s])
        self.engine = SurveillanceEngine(**config.get("engine", {}))
        rng = np.random.default_rng([seed, 1])
        k = min(int(traffic["check_jobs"]), J)
        self.check_rows = np.sort(rng.choice(J, k, replace=False))
        self._get = operator.itemgetter(*[job_id(int(r))
                                          for r in self.check_rows])
        # job i joins at tick W - 1 + arrival[i]: a seeded order, dealt out
        # round-robin so that every seed has the same number per tick
        ticks = int(traffic["arrival_ticks"])
        arrival = np.empty(J, np.int64)
        arrival[rng.permutation(J)] = np.arange(J) % ticks
        self.first_steps = W - 1 + arrival[self.check_rows]
        views = self.fleet.views()
        self.step = W - 1
        for t in range(ticks):
            for i in np.flatnonzero(arrival == t):
                self.engine.register(job_id(int(i)), views[i],
                                     self.nb_program, window=W)
            self.tick()
        for _ in range(int(traffic["warm_ticks"])):
            self.tick()
        self.latency: List[float] = []
        self.answers: List[Tuple] = []
        self.steps: List[int] = []
        self.window_s = 0.0
        self._want = None

    def col(self, step: int) -> int:
        """Row of the load recorded at ``step``: the first W fill the
        windows, then ``replay_steps`` rows repeat."""
        W = self.W
        return step if step < W else W + (step - W) % self.replay

    def tick(self) -> Dict[str, int]:
        span = self.ctx.span
        s = self.step
        with span("bench.tick"):
            if s >= self.W:
                with span("bench.record"):
                    self.fleet.record_fleet(s, self.rows[self.col(s)])
            with span("bench.decide"):
                res = self.engine.tick(s)
            with span("bench.remain"):
                remain = res.remain
        self.step += 1
        return remain

    @contextlib.contextmanager
    def kernel_spans(self):
        """In a traced run, a span with its shapes around every call of the
        two cycle-fit kernels, for the roofline readers."""
        if not self.ctx.tracing:
            yield
            return
        from repro.kernels import ops
        span = self.ctx.span
        spectrum, autocorr = ops.power_spectrum, ops.autocorr_score

        def traced_spectrum(x, *a, **k):
            with span("bench.power_spectrum", rows=x.shape[0], n=x.shape[1]):
                return spectrum(x, *a, **k)

        def traced_autocorr(x, lags, *a, **k):
            host = np.asarray(lags)
            with span("bench.autocorr_score", rows=x.shape[0], n=x.shape[1],
                      lag_lo=int(host[0]), lag_hi=int(host[-1])):
                return autocorr(x, lags, *a, **k)

        ops.power_spectrum, ops.autocorr_score = (traced_spectrum,
                                                  traced_autocorr)
        try:
            yield
        finally:
            ops.power_spectrum, ops.autocorr_score = spectrum, autocorr

    def window(self, seconds: float) -> None:
        with self.kernel_spans():
            self._window(seconds)

    def _window(self, seconds: float) -> None:
        from bench.harness import HostClock
        clock = time.perf_counter
        host: List[Tuple[float, ...]] = []
        with HostClock() as hc:
            t0 = clock()
            t_end = t0 + seconds
            while True:
                before = hc.read()
                a = clock()
                remain = self.tick()
                b = clock()
                host.append(tuple(y - x for x, y in zip(before, hc.read())))
                self.latency.append(b - a)
                self.steps.append(self.step - 1)
                self.answers.append(self._get(remain))
                if b >= t_end:
                    break
        self.window_s = b - t0
        self.ctx.log(HostClock.describe("ticks", self.latency, host))
        self.final = {
            "lm": np.stack([self.engine.jobs[job_id(int(r))].lm_series
                            for r in self.check_rows]),
            "period": np.array([self.engine.jobs[job_id(int(r))].model.period
                                for r in self.check_rows]),
            "confidence": np.array(
                [self.engine.jobs[job_id(int(r))].model.confidence
                 for r in self.check_rows]),
        }

    def end_to_end(self) -> Dict[str, float]:
        lat = np.asarray(self.latency)
        return {"tick_s": self.window_s / len(lat),
                "tick_p95_s": float(np.percentile(lat, 95))}

    def counters(self) -> Dict[str, float]:
        return {"ticks": len(self.latency)}

    def release(self) -> None:
        self.engine = None
        self.fleet = None

    def window_of(self, rows: np.ndarray, step: int) -> np.ndarray:
        cols = np.array([self.col(s)
                         for s in range(step - self.W + 1, step + 1)])
        return self.rows[cols[:, None], rows[None, :]].transpose(1, 0, 2)

    def reference(self, prec: str = "f64") -> Dict[str, np.ndarray]:
        return ref.simulate(self.window_of, self.check_rows, self.first_steps,
                            self.steps[-1], self.W, self.nb_ref,
                            np.asarray(self.steps), prec)

    def program_answers(self) -> Dict[str, np.ndarray]:
        return dict(self.final, remain=np.asarray(self.answers, np.int64))

    def readings(self, control: bool = False) -> Dict[str, float]:
        """The compared numbers of the program's answers, or with
        ``control`` of the reference computed in bfloat16."""
        if self._want is None:
            self._want = self.reference()
        got = self.reference("bf16") if control else self.program_answers()
        return ref.compare(got, self._want)

    def tally(self) -> Tuple[int, int]:
        if self._want is None:
            self._want = self.reference()
        failed = int(np.any(self.program_answers()["remain"]
                            != self._want["remain"], axis=1).sum())
        return len(self.steps), failed


def setup(config: dict, traffic: dict, seed: int, ctx) -> DecideCell:
    return DecideCell(config, traffic, seed, ctx)
