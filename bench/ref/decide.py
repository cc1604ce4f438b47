"""Plain reference of the decide plane: classify, cycle fit, Algorithm 2.

Written from the paper (arXiv:1607.07846 §4.1 binned naive Bayes, §4.2 FFT
cycle recognition with Algorithm 1, §5.2 Algorithm 2) and from the
surveillance rules the engine documents (a job's fit is refreshed once its
window has advanced a quarter of its period, or 8 samples while it has no
cycle). It imports nothing of the program and takes nothing the program
made: the classifier's tables are fitted here from the same labelled
samples.

Every stage computes in float64 on float32 inputs, the precision the
configuration states. ``prec="bf16"`` is the control: each stage's inputs,
tables and outputs rounded to bfloat16, with float32 sums, as a program
that dropped to bfloat16 would compute them.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Callable, Dict

import ml_dtypes
import numpy as np

LM_OF_CLASS = np.array([1, 0, 1, 1], np.int8)     # CPU, MEM, IO, IDLE: MEM is NLM
MIN_PERIOD = 2
ACYCLIC_REFIT = 8
DEGENERATE_MASS = 1e-9


def _q(x: np.ndarray, prec: str) -> np.ndarray:
    """Round to the working precision (float64 values out)."""
    if prec == "bf16":
        return np.asarray(x, np.float32).astype(ml_dtypes.bfloat16
                                                ).astype(np.float64)
    return np.asarray(x, np.float64)


@dataclass
class NB:
    edges: np.ndarray        # (F, bins-1) float32 quantile edges
    loglik: np.ndarray       # (C, F, bins) float32
    logprior: np.ndarray     # (C,) float32


def fit_nb(features: np.ndarray, labels: np.ndarray, n_bins: int = 16,
           n_classes: int = 4, alpha: float = 1.0) -> NB:
    """Quantile-binned naive Bayes with Laplace smoothing."""
    F = features.shape[1]
    qs = np.linspace(0, 1, n_bins + 1)[1:-1]
    edges = np.quantile(features, qs, axis=0).T.astype(np.float32)
    edges = np.maximum.accumulate(edges, axis=1)
    edges = edges + (np.arange(edges.shape[1], dtype=np.float32)
                     * np.float32(1e-9))[None, :]
    bins = np.stack([np.searchsorted(edges[f], features[:, f])
                     for f in range(F)], axis=1)
    counts = np.zeros((n_classes, F, n_bins))
    for c in range(n_classes):
        for f in range(F):
            counts[c, f] = np.bincount(bins[labels == c, f],
                                       minlength=n_bins)
    loglik = np.log((counts + alpha)
                    / (counts.sum(axis=2, keepdims=True) + alpha * n_bins))
    prior = np.bincount(labels, minlength=n_classes).astype(np.float64)
    logprior = np.log((prior + alpha) / (prior.sum() + alpha * n_classes))
    return NB(edges, loglik.astype(np.float32), logprior.astype(np.float32))


def classify_lm(nb: NB, x: np.ndarray, prec: str = "f64") -> np.ndarray:
    """(..., F) telemetry samples -> (...) int8 LM (1) / NLM (0)."""
    x = np.asarray(x, np.float32)
    edges = nb.edges
    if prec == "bf16":
        x = _q(x, prec).astype(np.float32)
        edges = _q(edges, prec).astype(np.float32)
    F = x.shape[-1]
    bins = np.stack([np.searchsorted(edges[f], x[..., f]) for f in range(F)],
                    axis=-1)                                   # (..., F)
    ll = _q(nb.loglik, prec)
    score = _q(nb.logprior, prec).copy()
    score = np.broadcast_to(score, x.shape[:-1] + score.shape).copy()
    for f in range(F):
        score += ll[:, f, :][:, bins[..., f]].transpose(
            *range(1, bins.ndim), 0)
    if prec == "bf16":
        score = _q(score.astype(np.float32), prec)
    return LM_OF_CLASS[np.argmax(score, axis=-1)]


def _spectrum(xc: np.ndarray, prec: str) -> np.ndarray:
    """(J, n) centred rows -> (J, n//2+1) one-sided power |DFT|^2."""
    if prec != "bf16":
        F = np.fft.rfft(xc, axis=1)
        return F.real ** 2 + F.imag ** 2
    n = xc.shape[1]
    k = np.arange(n // 2 + 1)
    ang = 2.0 * np.pi * np.outer(np.arange(n), k) / n
    xb = _q(xc, prec).astype(np.float32)
    re = xb @ _q(np.cos(ang), prec).astype(np.float32)
    im = xb @ _q(np.sin(ang), prec).astype(np.float32)
    return _q(re.astype(np.float64) ** 2 + im.astype(np.float64) ** 2, prec)


def _autocorr(xc: np.ndarray, lags: np.ndarray, prec: str) -> np.ndarray:
    """(J, n) centred rows -> (J, L) sums x[t] x[t+lag] over t < n - lag."""
    n = xc.shape[1]
    x = _q(xc, prec)
    out = np.empty((xc.shape[0], len(lags)))
    for i, p in enumerate(lags):
        prod = x[:, : n - p] * x[:, p:]
        out[:, i] = (prod.astype(np.float32).sum(axis=1) if prec == "bf16"
                     else prod.sum(axis=1))
    return _q(out, prec)


@dataclass
class Fit:
    period: np.ndarray        # (J,) 0 = no cycle
    confidence: np.ndarray    # (J,)
    profile: list             # per job: (period,) int8 LM profile, or (1,)


def fit_cycles(lm: np.ndarray, prec: str = "f64") -> Fit:
    """(J, n) LM series -> dominant period by the power spectrum's peak,
    de-quantized by the autocorrelation over +/- one bin width, and the
    first cycle's LM profile (Algorithm 1)."""
    X = np.asarray(lm, np.float64)
    J, n = X.shape
    max_p = n // 2
    xc = X - X.mean(axis=1, keepdims=True)
    P = _spectrum(xc, prec)
    ks = np.arange(P.shape[1])
    with np.errstate(divide="ignore"):
        per_k = np.where(ks > 0, n / np.maximum(ks, 1), np.inf)
    ok_k = (per_k >= MIN_PERIOD) & (per_k <= max_p) & (ks > 0)
    Pv = np.where(ok_k[None, :], P, -1.0)
    k_star = np.argmax(Pv, axis=1)
    rows = np.arange(J)
    mass = P[:, 1:].sum(axis=1)
    conf = P[rows, k_star] / np.maximum(mass, 1e-12)
    degenerate = mass <= DEGENERATE_MASS * (X * X).sum(axis=1)
    found = (Pv[rows, k_star] > 0) & ~degenerate
    conf = np.where(degenerate, 0.0, conf)
    period = np.zeros(J, np.int64)
    profiles = []
    for j in range(J):
        if not found[j]:
            profiles.append(np.array([1 if X[j].mean() >= 0.5 else 0],
                                     np.int8))
            conf[j] = 0.0
            continue
        p0 = int(round(n / k_star[j]))
        span = max(2, int(np.ceil(p0 * p0 / n)) + 1)
        lo, hi = max(MIN_PERIOD, p0 - span), min(max_p, n - 1, p0 + span)
        p = p0
        if hi >= lo:
            lags = np.arange(lo, hi + 1)
            p = int(lags[np.argmax(_autocorr(xc[j:j + 1], lags, prec)[0])])
        period[j] = p
        profiles.append(np.asarray(lm[j, :p], np.int8))
    return Fit(period, conf, profiles)


def remain_table(period: int, profile: np.ndarray) -> np.ndarray:
    """Algorithm 2 at every phase of the cycle: samples until the next LM
    moment; 0 in an LM moment; a whole period when the cycle has none.
    A job with no cycle migrates now: its table is [0]."""
    if period <= 1:
        return np.zeros(1, np.int64)
    r = np.arange(period)
    lm_idx = np.flatnonzero(profile[:period] == 1)
    if lm_idx.size == 0:
        return np.full(period, period, np.int64)
    return np.min((lm_idx[None, :] - r[:, None]) % period, axis=1)


def simulate(window_of: Callable[[np.ndarray, int], np.ndarray],
             rows: np.ndarray, first_steps: np.ndarray, last_step: int,
             window: int, nb: NB, record_steps: np.ndarray, prec: str = "f64"
             ) -> Dict[str, np.ndarray]:
    """Run the surveillance of jobs ``rows`` tick by tick, each from its
    ``first_steps`` entry (the tick it joins and is first fit at) to
    ``last_step``: a job's fit is refreshed when its window has advanced a
    quarter of its period (8 samples with no cycle) since the last.
    ``window_of(rows, step)`` gives the (K, window, F) telemetry each job
    holds at ``step``. Returns the RemainTime at ``record_steps`` (S, K),
    each after every job has joined, and each job's final LM series,
    period and confidence."""
    K = len(rows)
    first_steps = np.asarray(first_steps, np.int64)
    period = np.zeros(K, np.int64)
    conf = np.zeros(K)
    fitted = np.full(K, -1, np.int64)
    origin = np.zeros(K, np.int64)
    tables = np.zeros((K, window), np.int64)
    lm_last = np.zeros((K, window), np.int8)
    record = {int(s): i for i, s in enumerate(record_steps)}
    remain = np.zeros((len(record_steps), K), np.int64)
    ks = np.arange(K)
    for step in range(int(first_steps.min()), last_step + 1):
        need = np.where(period > 1, np.maximum(1, period // 4),
                        ACYCLIC_REFIT)
        todo = np.flatnonzero((first_steps <= step)
                              & ((fitted < 0) | (step - fitted >= need)))
        if todo.size:
            lm = classify_lm(nb, window_of(rows[todo], step), prec)
            fit = fit_cycles(lm, prec)
            for i, k in enumerate(todo):
                period[k] = fit.period[i]
                conf[k] = fit.confidence[i]
                t = remain_table(int(period[k]), fit.profile[i])
                tables[k, : len(t)] = t
                lm_last[k] = lm[i]
            fitted[todo] = step
            origin[todo] = step - window + 1
        i = record.get(step)
        if i is not None:
            phase = (step - origin) % np.maximum(period, 1)
            remain[i] = np.where(period > 1, tables[ks, phase], 0)
    return {"remain": remain, "lm": lm_last, "period": period,
            "confidence": conf}


def compare(got: Dict[str, np.ndarray], ref: Dict[str, np.ndarray]
            ) -> Dict[str, float]:
    """The numbers ``correct`` is decided on, each a share or a gap of
    ``got`` (the program's answers, or the control's) against ``ref``."""
    cyc = ref["period"] > 1
    gap = np.abs(got["confidence"] - ref["confidence"])[cyc] / np.maximum(
        ref["confidence"][cyc], 1e-12)
    return {
        "lm_mismatch_share": float(np.mean(got["lm"] != ref["lm"])),
        "period_mismatch_share": float(np.mean(got["period"]
                                               != ref["period"])),
        "remain_mismatch_share": float(np.mean(got["remain"]
                                               != ref["remain"])),
        "confidence_max_rel_gap": float(gap.max()) if gap.size else 0.0,
    }
