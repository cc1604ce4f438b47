"""Fleet surveillance engine — one batched tick for the whole LMCM fleet.

The paper's LMCM (§5) surveils every VM continuously: classify the latest
telemetry window (NB, §4.1), recognize the workload cycle (FFT, §4.2 +
Alg. 1), and answer migration requests with Alg. 2 postponements. The seed
ran that pipeline one job at a time from ``LMCM.refresh_job`` — a Python
dispatch per job whose cost capped Fig. 10 scalability near 1k jobs at a
1 s sampling period. This module replaces the per-job loop with ONE batched
computation over the registered fleet:

  1. gather     — every job's telemetry window in one SoA ``window_matrix``
                  call (``telemetry.FleetTelemetry`` fast path; generic
                  per-buffer fallback for foreign stores);
  2. classify   — one jitted Naive Bayes call over (J, T, F)
                  (``characterize.classify_series_batch``); classification
                  is *incremental*: NB is stateless per sample, so a slid
                  window only classifies its new tail and splices the
                  cached lm series for the overlap (telemetry steps are
                  assumed dense — one sample per step);
  3. recognize  — one batched power spectrum (Pallas MXU matmul-DFT with a
                  fused mean-removal prologue on TPU) + one vectorized
                  candidate-lag autocorrelation refinement
                  (``cycles.fit_cycle_batch`` / ``kernels/autocorr.py``);
  4. decide     — the already-vectorized Algorithm 2 applied fleet-wide
                  (``postpone.postpone_batch``).

Staleness epochs make the tick incremental: a job's cycle fit is only
recomputed once its window has advanced >= period/4 samples since the last
fit (``acyclic_refit`` samples while no cycle is known), so a steady-state
tick touches only the jobs whose phase estimate could actually have
drifted. ``LMCM`` consumes the engine for both its per-request decisions
and its per-step surveillance; ``FleetSim`` and
``benchmarks/fig10_scalability.py`` drive ``tick`` directly.

Batch shapes are bucketed to powers of two before entering jitted code so
a fleet whose stale subset fluctuates does not retrace XLA programs every
tick.

100k-job extensions (all default-off / bit-identical):

  * sharding   — ``shards=k`` partitions every job-row stage (classify,
                 spectrum, refinement, Alg. 2) across the first k local
                 devices via shard_map (``core/shard.py``). No stage mixes
                 rows, so sharded ticks are BIT-IDENTICAL to the
                 single-device reference path (``shards=None``).
  * overlap    — ``overlap=True`` returns ``TickResult`` while Algorithm 2
                 is still executing under jax's async dispatch; the
                 job->RemainTime dict materializes on first ``.remain``
                 access, so the caller's next record/gather/classify
                 overlaps the decide. ``overlap=False`` restores the
                 synchronous schedule; values are bit-identical either way
                 (the decide's operands are captured at dispatch).
  * decide cache — the packed Alg. 2 operands (profiles/periods/origins/
                 ids) are cached and invalidated only by register/
                 unregister/refit, so a tick over an all-fresh fleet does
                 ZERO per-job Python work beyond the staleness scan:
                 ``m_now`` is one vectorized subtraction.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional, Tuple

import numpy as np

from repro.core import characterize, cycles, postpone as pp
from repro.core import shard as shardlib
from repro.core.telemetry import TelemetryBuffer
from repro.spans import enabled, span


def _pow2(n: int) -> int:
    return 1 << max(0, int(n - 1).bit_length())


@dataclass
class SurveilledJob:
    """Per-job surveillance state (the LMCM's job registry entry)."""
    job_id: str
    telemetry: TelemetryBuffer          # or any buffer with its interface
    nb: characterize.NaiveBayes
    window: int = 512
    dirty_rate_fn: Optional[Callable[[float], float]] = None
    model: Optional[cycles.CycleModel] = None
    lm_series: np.ndarray = field(default_factory=lambda: np.zeros(0, np.int8))
    # step index of the first sample in the characterized window: Alg.1's
    # profile is indexed from here, so Alg.2's M_current must be too
    origin_step: int = 0
    fitted_step: int = -1               # latest step at last fit (-1 = never)
    # misprediction feedback (core/guard.py): decayed by each guard abort
    # of this job's migrations, floor-clamped by the guard's policy. The
    # receding-horizon controller gates trough pricing on
    # confidence x trust, so a burned fit stops deferring launches to
    # troughs the model hallucinated until refits re-earn it.
    trust: float = 1.0


class TickResult:
    """One surveillance tick's outcome: ``remain`` (job -> Alg.2 RemainTime
    in samples), ``refitted`` (cycle fits recomputed), ``fleet`` (jobs with
    a current model), ``confidence`` (job -> spectral confidence of its
    current fit — the guard layer's gating input, shared with the packed
    Alg. 2 cache so surfacing it costs no per-tick Python).

    With ``overlap=True`` the engine constructs this while Algorithm 2 is
    still executing on device (jax async dispatch); the ``remain`` dict is
    built on first access from operands captured at dispatch time, so the
    values are bit-identical to the synchronous schedule — only the host
    sync moves.
    """
    __slots__ = ("_remain", "refitted", "fleet", "confidence", "_thunk")

    def __init__(self, remain: Optional[Dict[str, int]], refitted: int,
                 fleet: int, confidence: Optional[Dict[str, float]] = None,
                 _thunk: Optional[Callable] = None):
        self._remain = remain
        self.refitted = refitted
        self.fleet = fleet
        self.confidence = confidence if confidence is not None else {}
        self._thunk = _thunk

    @property
    def remain(self) -> Dict[str, int]:
        if self._thunk is not None:
            self._remain = self._thunk()
            self._thunk = None
        return self._remain

    @property
    def pending(self) -> bool:
        """True while the decide has not been synced to host yet."""
        return self._thunk is not None

    def __repr__(self) -> str:
        body = "<pending>" if self.pending else repr(self._remain)
        return (f"TickResult(remain={body}, refitted={self.refitted}, "
                f"fleet={self.fleet})")


class SurveillanceEngine:
    """Batched NB -> FFT -> Alg.2 surveillance over a registered fleet."""

    def __init__(self, *, folded: bool = False, min_samples: int = 8,
                 acyclic_refit: int = 8,
                 use_kernel: Optional[bool] = None,
                 shards: Optional[int] = None,
                 overlap: bool = False,
                 min_coverage: float = 0.5):
        self.folded = folded
        self.min_samples = min_samples
        self.acyclic_refit = acyclic_refit
        # degraded-telemetry gate: fraction of a job's gathered window that
        # must be valid (recorded AND finite — NaN samples are sensor
        # dropout) for its cycle fit to be trusted; rows below it demote to
        # an acyclic model instead of fitting a cycle to zero-filled holes.
        # Clean telemetry always has coverage 1.0, so the gate is inert
        # until NaNs appear.
        self.min_coverage = float(min_coverage)
        self.use_kernel = use_kernel
        self.shards = shards
        self.overlap = overlap
        self.mesh = shardlib.decide_mesh(shards)
        self.jobs: Dict[str, SurveilledJob] = {}
        self._decide_cache: Optional[Tuple] = None
        #: batched refit groups run (``_refresh_group`` calls), ever
        self.groups_refit = 0

    # -- registration -------------------------------------------------------
    def register(self, job_id: str, telemetry, nb: characterize.NaiveBayes,
                 *, window: int = 512, dirty_rate_fn=None) -> SurveilledJob:
        job = SurveilledJob(job_id, telemetry, nb, window=window,
                            dirty_rate_fn=dirty_rate_fn)
        self.jobs[job_id] = job
        self._decide_cache = None
        return job

    def unregister(self, job_id: str) -> None:
        if self.jobs.pop(job_id, None) is not None:
            self._decide_cache = None

    # -- staleness epochs ---------------------------------------------------
    def _latest_steps(self, jobs: List[SurveilledJob]) -> np.ndarray:
        """(J,) latest telemetry step per job; one call on the fleet-SoA
        fast path, per-buffer otherwise."""
        out = np.full(len(jobs), -1, np.int64)
        by_fleet: Dict[int, List[int]] = {}
        for i, job in enumerate(jobs):
            fleet = getattr(job.telemetry, "fleet", None)
            if fleet is not None:
                by_fleet.setdefault(id(fleet), []).append(i)
            else:
                out[i] = job.telemetry.latest_step()
        for idxs in by_fleet.values():
            fleet = jobs[idxs[0]].telemetry.fleet
            latest = fleet.latest_steps()
            for i in idxs:
                out[i] = latest[jobs[i].telemetry.index]
        return out

    def _stale(self, job: SurveilledJob, latest: int) -> bool:
        if latest < 0 or len(job.telemetry) < self.min_samples:
            return False                        # not enough history yet
        if job.fitted_step < 0:
            return True
        advanced = latest - job.fitted_step
        if job.model is not None and job.model.period > 1:
            return advanced >= max(1, job.model.period // 4)
        return advanced >= self.acyclic_refit

    def next_refresh_step(self, now_step: int) -> float:
        """Earliest telemetry step at which ANY registered job's cycle fit
        becomes stale, assuming telemetry stays dense (one sample per
        step) — the event-skipping simulator's surveillance horizon: a
        per-step ``refresh()`` is a pure no-op strictly before this step,
        so the simulator may jump straight to it without changing any
        fit (``inf`` when no job will ever go stale, e.g. an empty
        fleet). Jobs with no samples yet are assumed to record their
        FIRST sample at ``now_step`` (callers pass the step about to be
        recorded), so they reach ``min_samples`` at
        ``now_step + min_samples - 1``."""
        nxt = np.inf
        if not self.jobs:
            return nxt
        jobs = list(self.jobs.values())
        for job, latest in zip(jobs, self._latest_steps(jobs)):
            base = int(latest) if latest >= 0 else now_step - 1
            ready = base + max(0, self.min_samples - len(job.telemetry))
            if job.fitted_step < 0:
                cand = ready                    # stale on first full window
            else:
                if job.model is not None and job.model.period > 1:
                    thresh = max(1, job.model.period // 4)
                else:
                    thresh = self.acyclic_refit
                cand = max(ready, job.fitted_step + thresh)
            nxt = min(nxt, cand)
        return nxt

    # -- the batched pipeline ----------------------------------------------
    def refresh(self, job_ids: Optional[List[str]] = None,
                *, force: bool = False) -> int:
        """Recompute the cycle fit of every stale (or ``force``d) job in
        one batched pipeline per (classifier, window-length) group.
        Returns the number of jobs refit."""
        with span("surveil.stale_scan") as s:
            jobs = ([self.jobs[i] for i in job_ids] if job_ids is not None
                    else list(self.jobs.values()))
            latest = self._latest_steps(jobs)
            todo = [(job, ls) for job, ls in zip(jobs, latest)
                    if (force and ls >= 0
                        and len(job.telemetry) >= self.min_samples)
                    or (not force and self._stale(job, ls))]
            groups: Dict[tuple, List[tuple]] = {}
            for job, ls in todo:
                m = min(job.window, len(job.telemetry))
                delta = int(ls) - job.fitted_step
                # incremental classification: NB is stateless per sample,
                # so a slid window only needs its NEW tail classified — the
                # cached lm_series supplies the overlap (telemetry steps
                # are assumed dense, one sample per step, as the recorder
                # produces them)
                splice = (job.fitted_step >= 0 and len(job.lm_series) == m
                          and 0 <= delta < m)
                tail = min(m, _pow2(max(delta, 1))) if splice else m
                groups.setdefault((id(job.nb), m, tail), []).append((job, ls))
            if enabled():
                s.set_metadata(jobs=len(jobs), stale=len(todo))
        for (_, m, tail), entries in groups.items():
            with span("surveil.refit", rows=len(entries),
                      rows_padded=_pow2(len(entries)), tail=tail, window=m):
                self._refresh_group([j for j, _ in entries],
                                    np.asarray([ls for _, ls in entries]),
                                    m, tail)
            self.groups_refit += 1
        return len(todo)

    def _refresh_group(self, jobs: List[SurveilledJob],
                       latest: np.ndarray, m: int, tail: int) -> None:
        G = len(jobs)
        with span("surveil.gather"):
            # masked gather: NaN dropout samples come back zero-filled (the
            # batched NB/FFT stays finite) with their invalidity recorded,
            # so starved rows can be demoted instead of fit to hole-filled
            # data
            W, counts, valid = TelemetryBuffer.window_matrix(
                [j.telemetry for j in jobs], tail,
                return_mask=True)                           # (G, tail, F)
            coverage = valid.sum(axis=1) / np.maximum(counts, 1)
            # bucket BOTH batch axes so the jitted NB doesn't retrace per
            # stale subset (job axis) or per history length (time axis —
            # zero rows at the front classify to garbage and are sliced
            # off; NB is per-sample)
            G_p, T_p = _pow2(G), _pow2(tail)
            if G_p != G or T_p != tail:
                Wp = np.zeros((G_p, T_p, W.shape[2]))
                Wp[:G, T_p - tail:] = W
                W = Wp
        with span("surveil.classify"):
            # lm-only classify: same jitted argmax as classify_series_batch
            # (bit-identical lm), no (G, T, C) posterior — optionally
            # sharded
            lm_tail = shardlib.classify_lm(jobs[0].nb, W, self.mesh)
            lm_tail = lm_tail[:G, T_p - tail:]
        with span("surveil.splice"):
            if tail == m:
                LM = lm_tail
            else:
                LM = np.empty((G, m), np.int8)
                for i, (job, ls) in enumerate(zip(jobs, latest)):
                    d = int(ls) - job.fitted_step
                    LM[i, : m - d] = job.lm_series[d:]
                    if d:
                        LM[i, m - d:] = lm_tail[i, tail - d:]
        models = cycles.fit_cycle_batch(LM, folded=self.folded,
                                        use_kernel=self.use_kernel,
                                        mesh=self.mesh)
        with span("surveil.assign"):
            for i, (job, model, lm_row, ls) in enumerate(
                    zip(jobs, models, LM, latest)):
                if coverage[i] < self.min_coverage:
                    # blackout-starved window: a cycle fit over zero-filled
                    # holes is noise — demote to acyclic (same shape as the
                    # not-found branch of fit_cycle_batch) until telemetry
                    # recovers and a later refit sees real samples again
                    model = cycles.CycleModel(0, 0.0, np.asarray(
                        [1 if lm_row.mean() >= 0.5 else 0], np.int8))
                job.model = model
                job.lm_series = lm_row
                job.origin_step = int(ls) - m + 1
                job.fitted_step = int(ls)
        self._decide_cache = None       # packed Alg.2 operands went stale

    def refresh_model(self, job_id: str, *, force: bool = False
                      ) -> Optional[cycles.CycleModel]:
        """Single-job view of ``refresh``: recompute if stale, then return
        the (possibly cached) model. None while history is too short."""
        self.refresh([job_id], force=force)
        return self.jobs[job_id].model

    # -- the batched tick ---------------------------------------------------
    def _packed_fleet(self) -> Tuple:
        """(ids, origins, profiles, periods, confidence) for the fitted
        fleet, padded/bucketed for Alg. 2 — cached between ticks and
        invalidated only by register/unregister/refit, so an all-fresh
        tick does no per-job Python work past the staleness scan."""
        if self._decide_cache is None:
            with span("surveil.pack_fleet") as s:
                self._decide_cache = self._pack()
                if enabled():
                    s.set_metadata(rows=len(self._decide_cache[0]))
        return self._decide_cache

    def _pack(self) -> Tuple:
        fitted = [j for j in self.jobs.values() if j.model is not None]
        if not fitted:
            return ((), None, None, None, {})
        p_max = max((j.model.period for j in fitted
                     if j.model.period > 1), default=1)
        # bucket both axes: jit cache stays O(log J * log P)
        J_p, P_p = _pow2(len(fitted)), _pow2(max(p_max, 1))
        profiles, periods = pp.pack_fleet(
            [j.model for j in fitted], n_jobs=J_p, p_max=P_p)
        origins = np.zeros(J_p, np.int64)
        origins[: len(fitted)] = [j.origin_step for j in fitted]
        return (tuple(j.job_id for j in fitted), origins, profiles, periods,
                {j.job_id: float(j.model.confidence) for j in fitted})

    def next_trough(self, job_ids: List[str], now_step: int
                    ) -> Dict[str, Optional[int]]:
        """Samples until each job's next predicted LM trough — Algorithm
        2's RemainTime read off the CURRENT cycle fits (no refit: admission
        decisions ride whatever the last tick fitted, so pricing a
        candidate does not perturb the surveillance schedule). ``None``
        for unregistered jobs and for jobs without a cyclic model — there
        is no trough to time against, and the receding-horizon controller
        falls back to its myopic one-period deferral for them."""
        out: Dict[str, Optional[int]] = {}
        for jid in job_ids:
            job = self.jobs.get(jid)
            model = job.model if job is not None else None
            if model is None or not model.cyclic:
                out[jid] = None
            else:
                out[jid] = int(pp.postpone(
                    model, int(now_step) - job.origin_step))
        return out

    def tick(self, now_step: int) -> TickResult:
        """One fleet surveillance tick: refresh every stale cycle fit, then
        answer Algorithm 2 for the whole fleet in one vectorized call.

        With ``overlap=True`` the returned ``TickResult`` is constructed
        before the decide's host sync: Alg. 2 runs under jax async dispatch
        while the caller records/gathers the next tick, and ``.remain``
        materializes on first access (bit-identical values — the operands
        are captured at dispatch). Padding rows (period 0) decide to 0 and
        are sliced off before the dict is built.
        """
        with span("surveil.tick") as s:
            groups = self.groups_refit
            refitted = self.refresh()
            packed = self._decide_cache is None
            res = self._decide(now_step, refitted)
            if enabled():
                s.set_metadata(jobs=len(self.jobs), refitted=refitted,
                               groups=self.groups_refit - groups,
                               packed=int(packed))
        return res

    def _decide(self, now_step: int, refitted: int) -> TickResult:
        ids, origins, profiles, periods, conf = self._packed_fleet()
        if not ids:
            return TickResult({}, refitted, 0)
        with span("surveil.decide"):
            m_now = (now_step - origins).astype(np.int32)   # one vector op
            remain_dev = shardlib.postpone_rows(profiles, periods, m_now,
                                                self.mesh)
        J = len(ids)

        def materialize(ids=ids, dev=remain_dev, J=J) -> Dict[str, int]:
            with span("surveil.remain"):
                with span("sync.remain"):
                    remain = np.asarray(dev)
                return dict(zip(ids, remain[:J].tolist()))

        if self.overlap:
            return TickResult(None, refitted, J, conf, _thunk=materialize)
        return TickResult(materialize(), refitted, J, conf)
