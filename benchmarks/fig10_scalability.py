"""Figure 10 — LMCM scalability: orchestration overhead vs fleet size.

The paper measures kernel-compile slowdown while LMCM analyzes traces from 5
to 1,000 VMs, finds a linear trend (~0.21% per 5 VMs) and a saturation point
around 1,800 VMs. Here: wall-time of one SurveillanceEngine tick — SoA
window gather + batched NB classification + batched FFT cycle fit (fused
mean removal) + vectorized candidate-lag refinement + fleet-wide Algorithm 2
— at fleet sizes 5..100,000, against the seed's per-job ``refresh_job`` loop
(one Python-dispatched pipeline per job), a linear fit, and two saturation
estimates against the 1 s sampling period.

Three batched-tick flavors are reported: ``tick_cold_s`` is the first-ever
fleet fit (full-window classification for every job); ``tick_full_s``
force-refits every job's cycle each tick (the seed-comparable decision
recompute — classification is incremental over the slid window, FFT +
refinement + Alg. 2 rerun for the whole fleet); ``tick_steady_s`` is the
amortized production tick (record one sample per job, tick) where staleness
epochs skip jobs whose window advanced < period/4 samples since the last
fit, and the decide-plane cache turns the Alg. 2 repack into one vector op.

Saturation is reported twice:

  * ``saturation_jobs`` — the ``tick_steady_s`` extrapolation (linear fit
    with the measured-regime fallback), kept for the cross-PR trajectory;
  * ``knee`` — the MEASURED saturation of the seed-comparable decision
    recompute: the fleet size where ``tick_full_s`` crosses the 1 s
    sampling period, interpolated between two bracketing MEASURED sizes
    (``knee_measured=True`` only when a bracket exists — a 10k/25k sweep
    brackets the knee on one CPU core; extrapolation is labelled as such).

Shard scaling (``shard_scaling``): the same 10k-job force-refit tick is
re-run with ``SurveillanceEngine(shards=k)``. On a CPU host each cell is a
SUBPROCESS with ``XLA_FLAGS=--xla_force_host_platform_device_count=k``
(the flag must be set before jax initializes); on an accelerator the cells
run in-process over the first k chips. Every cell
also emits a digest of its end-to-end decide output, so cross-shard
bit-parity is checked on the exact benchmark workload, not just in unit
tests. On a multi-core host the 2-device cell must beat the 1-device cell;
on a single-core host (this container: ``os.cpu_count() == 1``) the
parallel speedup is physically unattainable, so the quick gate enforces
parity + bounded overhead there and records ``multicore_host`` so the
criterion is honest about what it measured.

CLI:
  python -m benchmarks.fig10_scalability --shard-cell N K [REPS]
  python -m benchmarks.fig10_scalability [--load table3|heavy_tail|
      correlated] [--sizes 5,100,1000,10000]
"""
from __future__ import annotations

import hashlib
import json
import os
import pathlib
import subprocess
import sys
import time
from typing import Dict, List, Optional, Sequence

import numpy as np

from repro.core import characterize, cycles, postpone as pp
from repro.core.fleetsim import PHASES, WorkloadTrace, make_training_nb, \
    table3_traces
from repro.core.surveillance import SurveillanceEngine
from repro.core.telemetry import DEFAULT_FIELDS, FleetTelemetry
from repro.data import synthetic

WINDOW = 512
ROOT = pathlib.Path(__file__).resolve().parents[1]

#: fleet generators selectable with ``load=`` (table3 = the paper's traces)
LOADS = ("table3", "heavy_tail", "correlated")


def _sample_matrix(trace: WorkloadTrace, t0: np.ndarray, steps: int,
                   rng: np.random.Generator) -> np.ndarray:
    """Vectorized ``trace.sample_indexes`` over jobs x steps: ``t0`` is the
    (J,) per-job phase offset; returns (J, steps, F) load-index rows
    ordered like ``DEFAULT_FIELDS``."""
    t0 = np.atleast_1d(np.asarray(t0, np.float64))
    tc = (t0[:, None] + np.arange(steps, dtype=np.float64)) % trace.cycle_s
    cum = np.cumsum([d for _, d in trace.phases])
    pi = np.searchsorted(cum, tc.ravel(), side="right").reshape(tc.shape)
    names = [n for n, _ in trace.phases]
    cu = np.asarray([PHASES[n]["compute_util"] for n in names])[pi]
    hb = np.asarray([PHASES[n]["hbm_util"] for n in names])[pi]
    dr = np.asarray([PHASES[n]["dirty_rate"] for n in names])[pi]
    base = np.stack([0.5 / np.maximum(cu, 0.02), dr,
                     np.minimum(1.0, dr / 200e6), cu * 1e9, cu, hb], axis=2)
    jit = 1.0 + trace.jitter * rng.standard_normal(base.shape)
    return np.maximum(0.0, base * jit)


def _make_fleet(n: int, steps: int, seed: int = 0, load: str = "table3"):
    """Fleet SoA store pre-filled with WINDOW samples + ``steps`` further
    sample rows to replay during the rolling steady-state measurement.
    Fully vectorized (the old per-job Python loop took ~15 s just to BUILD
    a 25k fleet): table3 groups jobs by trace, the synthetic generators
    are (J, steps, F) tensors outright."""
    total = WINDOW + steps
    fleet = FleetTelemetry(n, capacity=WINDOW, fields=DEFAULT_FIELDS)
    if load == "table3":
        rng = np.random.default_rng(seed)
        base = list(table3_traces().values())
        vals = np.empty((n, total, len(DEFAULT_FIELDS)))
        idx = np.arange(n)
        for k, tr in enumerate(base):
            rows = idx[idx % len(base) == k]
            if rows.size:
                t0 = rng.uniform(0, tr.cycle_s, rows.size)
                vals[rows] = _sample_matrix(tr, t0, total, rng)
    elif load == "heavy_tail":
        vals = synthetic.heavy_tail_load(n, total, seed=seed)
    elif load == "correlated":
        vals = synthetic.correlated_tenant_load(n, total, seed=seed)
    else:
        raise ValueError(f"unknown load {load!r} (want one of {LOADS})")
    for s in range(WINDOW):
        fleet.record_fleet(s, vals[:, s])
    return fleet, vals[:, WINDOW:]


def _make_engine(nb, fleet: FleetTelemetry, *, shards: Optional[int] = None,
                 overlap: bool = False) -> SurveillanceEngine:
    eng = SurveillanceEngine(shards=shards, overlap=overlap)
    for i, view in enumerate(fleet.views()):
        eng.register(f"job{i:05d}", view, nb, window=WINDOW)
    return eng


def _tick_perjob(nb, views, m_now: int) -> np.ndarray:
    """The seed surveillance loop: one Python-dispatched NB -> FFT -> Alg.2
    pipeline per job (kept as the benchmark baseline)."""
    remain = np.empty(len(views))
    for i, buf in enumerate(views):
        w = buf.window(WINDOW)
        _, lm, _ = characterize.classify_series(nb, w)
        model = cycles.fit_cycle(lm)
        remain[i] = pp.postpone(model, m_now)
    return remain


def _remain_digest(res) -> str:
    """Digest of a tick's end-to-end decide output (job -> RemainTime, in
    sorted job order, plus the fleet/refit counters) — the cross-shard
    parity check runs on exactly the benchmark's workload."""
    h = hashlib.sha256()
    for job_id, r in sorted(res.remain.items()):
        h.update(f"{job_id}={int(r)};".encode())
    h.update(f"fleet={res.fleet};refitted={res.refitted}".encode())
    return h.hexdigest()[:16]


def _knee(per_size_full: List[tuple], period_s: float = 1.0) -> Dict:
    """Measured saturation knee of the seed-comparable full-refit tick:
    the fleet size where ``tick_full_s`` crosses the sampling period,
    interpolated between the two bracketing MEASURED sizes. Falls back to
    marginal-slope extrapolation from the two largest measurements (and
    says so) only when no measured bracket exists."""
    xs = [(int(n), float(t)) for n, t in per_size_full]
    for (n1, t1), (n2, t2) in zip(xs, xs[1:]):
        if t1 < period_s <= t2:
            frac = (period_s - t1) / max(t2 - t1, 1e-12)
            return {"knee_jobs": int(round(n1 + frac * (n2 - n1))),
                    "knee_measured": True, "knee_basis": "tick_full_s",
                    "knee_bracket": [n1, n2]}
    if xs and xs[0][1] >= period_s:            # already saturated at min n
        return {"knee_jobs": xs[0][0], "knee_measured": True,
                "knee_basis": "tick_full_s",
                "knee_bracket": [xs[0][0], xs[0][0]]}
    (n1, t1), (n2, t2) = xs[-2], xs[-1]
    marginal = (t2 - t1) / max(n2 - n1, 1)
    knee = (n2 + (period_s - t2) / marginal if marginal > 0
            else n2 * period_s / max(t2, 1e-9))
    return {"knee_jobs": int(min(knee, 1e9)), "knee_measured": False,
            "knee_basis": "tick_full_s", "knee_bracket": [n1, n2]}


def run(sizes: Optional[Sequence[int]] = None, *, reps: int = 3,
        steady_steps: int = 32, perjob_cap: int = 1000,
        load: str = "table3"):
    nb = make_training_nb()
    sizes = list(sizes or [5, 10, 25, 50, 100, 250, 500, 1000])
    rows: List[Dict] = []
    per_size = []
    per_size_full = []
    speedup_at = {}
    warm = 12
    for n in sizes:
        fleet, replay = _make_fleet(n, steady_steps + reps + warm, load=load)
        eng = _make_engine(nb, fleet)
        t0 = time.perf_counter()
        eng.tick(WINDOW - 1)                 # first fleet fit: full windows
        t_cold = time.perf_counter() - t0    # includes the XLA compiles
        step = WINDOW
        for k in range(warm):                # populate jit caches for the
            fleet.record_fleet(step, replay[:, step - WINDOW])
            if k % 3 == 0:                   # tail/G bucket shapes the timed
                eng.refresh(force=True)      # loops will hit
            eng.tick(step)
            step += 1
        # seed-comparable decision recompute: every tick advances the fleet
        # one sample and force-refits every job's cycle
        t0 = time.perf_counter()
        for k in range(reps):
            fleet.record_fleet(step, replay[:, step - WINDOW])
            eng.refresh(force=True)
            res = eng.tick(step)
            step += 1
        t_full = (time.perf_counter() - t0) / reps
        # production steady state: staleness epochs skip unmoved fits
        t0 = time.perf_counter()
        for k in range(steady_steps):
            fleet.record_fleet(step, replay[:, step - WINDOW])
            res = eng.tick(step)
            step += 1
        t_steady = (time.perf_counter() - t0) / steady_steps
        t_perjob = None
        if n <= perjob_cap:
            views = [eng.jobs[j].telemetry for j in eng.jobs]
            _tick_perjob(nb, views[:1], 100)   # warm the (W, F) jit trace
            t0 = time.perf_counter()
            _tick_perjob(nb, views, 100)
            t_perjob = time.perf_counter() - t0
            speedup_at[n] = t_perjob / t_full
        per_size.append((n, t_steady))
        per_size_full.append((n, t_full))
        rows.append({"n_jobs": n, "tick_cold_s": round(t_cold, 4),
                     "tick_full_s": round(t_full, 4),
                     "tick_steady_s": round(t_steady, 5),
                     "perjob_s": round(t_perjob, 4) if t_perjob else None,
                     "speedup": round(t_perjob / t_full, 1) if t_perjob
                     else None,
                     "per_job_us": round(t_steady / n * 1e6, 1),
                     "fleet_with_model": res.fleet})
    ns = np.array([p[0] for p in per_size], float)
    ts = np.array([p[1] for p in per_size], float)
    slope, intercept = np.polyfit(ns, ts, 1)
    r2 = float(np.corrcoef(ns, ts)[0, 1] ** 2)
    # the global least-squares line is only trusted when it actually
    # explains the measurements: at small fleets the tick is dominated by
    # fixed overhead and timer noise, and a noise-fitted slope used to
    # extrapolate absurd saturations (~1e9 jobs at r^2 ~ 0.25). When the
    # fit is degenerate, extrapolate from the MEASURED large-n regime
    # instead: the marginal per-job cost between the two largest fleets
    # (falling back to through-origin scaling at the largest measurement
    # if even that slope is noise-negative).
    fit_ok = bool(slope > 0 and r2 >= 0.9)
    if fit_ok:
        saturation = (1.0 - intercept) / slope
        fit_method = "linear_fit"
    else:
        (n1, t1), (n2, t2) = per_size[-2], per_size[-1]
        marginal = (t2 - t1) / (n2 - n1)
        saturation = (n2 + (1.0 - t2) / marginal if marginal > 0
                      else n2 / t2)
        fit_method = "measured_regime"
    knee = _knee(per_size_full)
    rows.append({"n_jobs": "FIT",
                 "per_job_us": round(slope * 1e6, 2),
                 "linear_r2": round(r2, 4),
                 "fit_ok": fit_ok,
                 "fit_method": fit_method,
                 "saturation_jobs": int(min(saturation, 1e9)),
                 "speedup_at_max": round(speedup_at.get(max(speedup_at), 0.0),
                                         1) if speedup_at else None,
                 **knee})
    summary = [{"name": "fig10_scalability",
                "us_per_call": round(slope * 1e6, 2),
                "derived": f"saturation~{int(min(saturation, 1e9))}jobs,"
                           f"fit={fit_method},"
                           f"knee~{knee['knee_jobs']}jobs"
                           f"({'measured' if knee['knee_measured'] else 'extrapolated'}),"
                           f"speedup~{rows[-1]['speedup_at_max']}x"}]
    return summary, rows


# -- shard scaling ----------------------------------------------------------
def shard_cell(n: int, shards: int, reps: int = 3, *, warm: int = 4,
               load: str = "table3") -> Dict:
    """One shard-scaling measurement IN THIS PROCESS: a ``shards``-way
    engine (1 = the single-device reference path) timing the force-refit
    decide tick over a deterministic ``n``-job fleet, plus the end-to-end
    decide digest for cross-shard parity. On a CPU host, callers must have
    set the device count (XLA_FLAGS) before jax initialized — use
    ``shard_scaling`` for the subprocess plumbing."""
    import jax
    nb = make_training_nb()
    fleet, replay = _make_fleet(n, warm + reps, seed=0, load=load)
    eng = _make_engine(nb, fleet, shards=None if shards <= 1 else shards,
                       overlap=True)
    eng.tick(WINDOW - 1)
    step = WINDOW
    for k in range(warm):
        fleet.record_fleet(step, replay[:, step - WINDOW])
        eng.refresh(force=True)
        res = eng.tick(step)
        res.remain       # materialize: warm includes the host-sync path
        step += 1
    t0 = time.perf_counter()
    for k in range(reps):
        fleet.record_fleet(step, replay[:, step - WINDOW])
        eng.refresh(force=True)
        res = eng.tick(step)
        digest = _remain_digest(res)       # forces the host sync
        step += 1
    t_full = (time.perf_counter() - t0) / reps
    return {"n_jobs": n, "shards": shards, "devices": jax.device_count(),
            "tick_full_s": round(t_full, 4), "digest": digest}


def shard_scaling(n: int = 10_000, shard_counts: Sequence[int] = (1, 2),
                  reps: int = 3, load: str = "table3") -> List[Dict]:
    """Run one ``shard_cell`` per shard count. On an accelerator the cells
    run in this process over ``jax.devices()[:k]``: the process holds the
    chips, so a child could not reach them. On a CPU host each cell runs in
    a fresh SUBPROCESS with ``XLA_FLAGS=--xla_force_host_platform_device_
    count=k`` (the flag only takes effect before jax initializes, and the
    parent process must keep its single real device so co-resident timing
    gates stay undisturbed). Returns the cells in ``shard_counts`` order."""
    import jax
    if jax.default_backend() != "cpu":
        return [shard_cell(n, k, reps, load=load) for k in shard_counts]
    cells = []
    for k in shard_counts:
        env = dict(os.environ)
        flags = env.get("XLA_FLAGS", "")
        flags = " ".join(f for f in flags.split()
                         if "host_platform_device_count" not in f)
        env["XLA_FLAGS"] = (flags + " "
                            f"--xla_force_host_platform_device_count={k}"
                            ).strip()
        env["PYTHONPATH"] = os.pathsep.join(
            p for p in [str(ROOT / "src"), env.get("PYTHONPATH")] if p)
        out = subprocess.run(
            [sys.executable, "-m", "benchmarks.fig10_scalability",
             "--shard-cell", str(n), str(k), str(reps), load],
            cwd=ROOT, env=env, capture_output=True, text=True, check=True)
        cells.append(json.loads(out.stdout.strip().splitlines()[-1]))
    return cells


def main(argv: Sequence[str]) -> None:
    if argv and argv[0] == "--shard-cell":
        n, k, reps = int(argv[1]), int(argv[2]), int(argv[3] if len(argv)
                                                    > 3 else 3)
        load = argv[4] if len(argv) > 4 else "table3"
        print(json.dumps(shard_cell(n, k, reps, load=load)))
        return
    sizes = None
    load = "table3"
    it = iter(argv)
    for a in it:
        if a == "--sizes":
            sizes = [int(s) for s in next(it).split(",")]
        elif a == "--load":
            load = next(it)
    summary, rows = run(sizes=sizes, load=load)
    print(json.dumps(rows, indent=1, default=str))
    for s in summary:
        print(f"{s['name']},{s['us_per_call']},{s['derived']}")


if __name__ == "__main__":
    main(sys.argv[1:])
