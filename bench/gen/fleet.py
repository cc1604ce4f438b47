"""Fleet load generators of the benchmark, copied from the program.

Each is a pure function of its arguments and the seed, and returns
(jobs, steps, 6) load-index rows in the field order of the telemetry store
(step_time, dirty_bytes, dirty_fraction, collective_bytes, compute_util,
hbm_util). Copied so that no later change to the program can change the
load it is measured on:

- ``table3``: the paper's four Table 3 cycles, jobs spread over them
  round-robin, each at a random phase (``benchmarks/fig10_scalability.py``
  ``_make_fleet`` and ``_sample_matrix``; phase tables from
  ``core/fleetsim.py`` ``PHASES`` and ``table3_traces``);
- ``heavy_tail`` and ``correlated``: ``data/synthetic.py``
  ``heavy_tail_load`` and ``correlated_tenant_load``;
- ``nb_training_set``: the labelled samples that ``core/fleetsim.py``
  ``make_training_nb`` trains the classifier on.
"""
from __future__ import annotations

from typing import List, Tuple

import numpy as np

FIELDS = ("step_time", "dirty_bytes", "dirty_fraction", "collective_bytes",
          "compute_util", "hbm_util")

# workload classes and the load each phase kind puts on a job
CPU, MEM, IO, IDLE = range(4)
PHASES = {
    "CPU": dict(compute_util=0.95, hbm_util=0.30, dirty_rate=3e6, label=CPU),
    "MEM": dict(compute_util=0.55, hbm_util=0.95, dirty_rate=150e6,
                label=MEM),
    "IO": dict(compute_util=0.25, hbm_util=0.45, dirty_rate=12e6, label=IO),
    "IDLE": dict(compute_util=0.03, hbm_util=0.05, dirty_rate=0.3e6,
                 label=IDLE),
}

# paper Table 3: the phase sequence of each artificial cycle
TABLE3 = {
    "vm03_A": ["IO", "CPU", "CPU", "IO", "CPU", "CPU", "IO", "CPU", "CPU"],
    "vm02_C": ["MEM", "IDLE", "CPU", "MEM", "IDLE", "CPU", "MEM", "IDLE",
               "CPU"],
    "vm02_A": ["MEM", "CPU", "CPU", "MEM", "CPU", "CPU", "MEM", "CPU", "CPU",
               "MEM", "CPU", "CPU"],
    "vm01_C": ["MEM", "IDLE", "CPU", "MEM", "IDLE", "CPU"],
}


def _phase_means(name: str) -> Tuple[float, ...]:
    ph = PHASES[name]
    return (0.5 / max(ph["compute_util"], 0.02), ph["dirty_rate"],
            min(1.0, ph["dirty_rate"] / 200e6), ph["compute_util"] * 1e9,
            ph["compute_util"], ph["hbm_util"])


def sample_matrix(phases: List[Tuple[str, float]], jitter: float,
                  t0: np.ndarray, steps: int,
                  rng: np.random.Generator) -> np.ndarray:
    """One cyclic phase trace sampled for len(t0) jobs over ``steps``
    one-second samples; ``t0`` is each job's phase offset in seconds."""
    cycle_s = float(np.sum([d for _, d in phases]))
    t0 = np.atleast_1d(np.asarray(t0, np.float64))
    tc = (t0[:, None] + np.arange(steps, dtype=np.float64)) % cycle_s
    cum = np.cumsum([d for _, d in phases])
    pi = np.searchsorted(cum, tc.ravel(), side="right").reshape(tc.shape)
    names = [n for n, _ in phases]
    cu = np.asarray([PHASES[n]["compute_util"] for n in names])[pi]
    hb = np.asarray([PHASES[n]["hbm_util"] for n in names])[pi]
    dr = np.asarray([PHASES[n]["dirty_rate"] for n in names])[pi]
    base = np.stack([0.5 / np.maximum(cu, 0.02), dr,
                     np.minimum(1.0, dr / 200e6), cu * 1e9, cu, hb], axis=2)
    jit = 1.0 + jitter * rng.standard_normal(base.shape)
    return np.maximum(0.0, base * jit)


def table3_load(n_jobs: int, steps: int, *, seed: int, phase_s: float = 60.0,
                jitter: float = 0.05) -> np.ndarray:
    """Job j runs Table 3 cycle j mod 4 from a random phase."""
    rng = np.random.default_rng(seed)
    traces = [[(n, phase_s) for n in names] for names in TABLE3.values()]
    vals = np.empty((n_jobs, steps, len(FIELDS)))
    idx = np.arange(n_jobs)
    for k, phases in enumerate(traces):
        rows = idx[idx % len(traces) == k]
        if rows.size:
            t0 = rng.uniform(0, phase_s * len(phases), rows.size)
            vals[rows] = sample_matrix(phases, jitter, t0, steps, rng)
    return vals


def _load_indexes(cu, hb, dr) -> np.ndarray:
    return np.stack([0.5 / np.maximum(cu, 0.02), dr,
                     np.minimum(1.0, dr / 200e6), cu * 1e9, cu, hb], axis=-1)


def _square_wave(rng, n, steps, cycle_range, duty) -> np.ndarray:
    lo, hi = cycle_range
    periods = rng.integers(lo, hi + 1, n)
    phases = rng.integers(0, periods)
    t = np.arange(steps, dtype=np.int64)
    frac = ((t[None, :] + phases[:, None]) % periods[:, None]) \
        / periods[:, None]
    return (frac < duty).astype(np.float64)


def heavy_tail_load(n_jobs: int, steps: int, *, seed: int,
                    alpha: float = 1.6, burst_rate: float = 0.02,
                    cycle_range=(64, 256), duty: float = 0.5,
                    jitter: float = 0.05) -> np.ndarray:
    """Square-wave busy/idle cycles with Pareto dirty-rate bursts."""
    rng = np.random.default_rng(
        np.random.SeedSequence([seed, n_jobs, steps]))
    busy = _square_wave(rng, n_jobs, steps, tuple(cycle_range), duty)
    cu = 0.15 + 0.75 * busy
    hb = 0.30 + 0.50 * busy
    dr = 5e6 + 395e6 * busy
    burst = rng.random((n_jobs, steps)) < burst_rate
    mag = (1.0 + rng.pareto(alpha, (n_jobs, steps))) * burst
    dr = dr * (1.0 + mag)
    cu = np.minimum(1.0, cu * (1.0 + 0.2 * mag))
    noise = 1.0 + jitter * rng.standard_normal((n_jobs, steps, 1))
    return np.maximum(0.0, _load_indexes(cu, hb, dr) * noise)


def correlated_load(n_jobs: int, steps: int, *, seed: int,
                    n_tenants: int = 8, rho: float = 0.8,
                    cycle_range=(64, 256), jitter: float = 0.05
                    ) -> np.ndarray:
    """Each job mixes its tenant's shared cycle with one of its own."""
    rng = np.random.default_rng(
        np.random.SeedSequence([seed, n_jobs, n_tenants]))
    tenant = rng.integers(0, n_tenants, n_jobs)
    shared = _square_wave(rng, n_tenants, steps, tuple(cycle_range),
                          0.5)[tenant]
    idio = _square_wave(rng, n_jobs, steps, tuple(cycle_range), 0.5)
    busy = rho * shared + (1.0 - rho) * idio
    cu = 0.15 + 0.75 * busy
    hb = 0.25 + 0.55 * busy
    dr = 5e6 + 395e6 * busy
    noise = 1.0 + jitter * rng.standard_normal((n_jobs, steps, 1))
    return np.maximum(0.0, _load_indexes(cu, hb, dr) * noise)


LOADS = {"table3": table3_load, "heavy_tail": heavy_tail_load,
         "correlated": correlated_load}


def make_load(kind: str, n_jobs: int, steps: int, *, seed: int,
              **params) -> np.ndarray:
    """(n_jobs, steps, 6) load rows of the named generator."""
    if kind not in LOADS:
        raise ValueError(f"unknown load {kind!r}; known: {sorted(LOADS)}")
    return LOADS[kind](n_jobs, steps, seed=seed, **params)


def nb_training_set(seed: int, n: int = 4000,
                    jitter: float = 0.05) -> Tuple[np.ndarray, np.ndarray]:
    """(n, 6) float32 samples and (n,) labels: one second of each class in
    a 4 s cycle, sampled at uniform random times, one normal draw per field
    in field order (the classifier's training step, paper §4.1)."""
    rng = np.random.default_rng(seed)
    names = ["CPU", "MEM", "IO", "IDLE"]
    feats, labels = [], []
    for _ in range(n):
        t = rng.uniform(0, 4.0)
        name = names[min(int(np.searchsorted(np.arange(1.0, 5.0), t,
                                             side="right")), 3)]
        means = _phase_means(name)
        feats.append([max(0.0, v * (1 + jitter * rng.standard_normal()))
                      for v in means])
        labels.append(PHASES[name]["label"])
    return np.asarray(feats, np.float32), np.asarray(labels)
