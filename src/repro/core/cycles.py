"""FFT cycle recognition and cycle decomposition (paper §4.2, Algorithm 1).

Input is the chronologically ordered LM/NLM classification series from the
characterizer. ``cycle_length`` extracts the dominant period via the power
spectrum (O(n log n), exactly the paper's tool); ``decompose`` is Algorithm 1:
one cycle window is split into the suitable (ArrayLM) and unsuitable
(ArrayNLM) moment sets. Simple and complex (multi-interval) cycles both fall
out of the same machinery.

Beyond the paper ('alma-plus'): ``fold_profile`` replaces the first-window
slice with a phase-folded majority vote over *all* observed cycles (more
robust to classifier noise), and a confidence score (peak power / DC-removed
spectral mass) gates orchestration decisions.

Fleet scale: the scalar path (``fit_cycle``) is a J=1 view of the batched
path (``fit_cycle_batch``) — one shared spectrum routine, one shared peak
pick, one shared autocorrelation refinement — so both produce bit-identical
periods/profiles and confidences for the same series by construction. The
batched refinement scores the whole fleet against a shared candidate-lag
grid in one vectorized pass (Pallas ``autocorr_score`` on TPU, f64 einsum
off-TPU) instead of the per-job Python lag loop that used to dominate
surveillance ticks beyond ~100 jobs (see ``core/surveillance.py``).
"""
from __future__ import annotations

import warnings
from dataclasses import dataclass, field
from typing import List, Optional, Tuple

import numpy as np

from repro.kernels import ops as kops
from repro.spans import enabled, span


@dataclass
class CycleModel:
    period: int                    # samples per cycle (0 = acyclic)
    confidence: float              # spectral peak share in (0, 1]
    profile_lm: np.ndarray         # (period,) int8: 1 = LM at this phase
    array_lm: np.ndarray = field(default_factory=lambda: np.zeros(0, np.int64))
    array_nlm: np.ndarray = field(default_factory=lambda: np.zeros(0, np.int64))

    @property
    def cyclic(self) -> bool:
        return self.period > 1 and 0 < self.profile_lm.sum() < self.period


def _resolve_kernel(use_kernel: Optional[bool]) -> bool:
    # interpret-mode Pallas is for lowering validation, not CPU throughput:
    # off the TPU the default is the pocketfft/numpy path.
    return kops.on_tpu() if use_kernel is None else use_kernel


def _warn_host_fallback(stage: str, n: int) -> None:
    """The kernel path was asked for but this window length is off its
    tiling, so ``stage`` runs on the host. Python's default warning filter
    reports each stage and window length once per call site."""
    warnings.warn(f"{stage}: window length {n} is off the kernel tiling; "
                  "computing it with numpy on the host", RuntimeWarning,
                  stacklevel=3)


def _spectra(X: np.ndarray, use_kernel: Optional[bool],
             mesh=None) -> np.ndarray:
    """(J, n) f32 -> (J, n//2+1) one-sided power of the mean-removed rows.

    ``mesh`` row-shards the kernel path across devices (bit-identical: the
    spectrum is per-row). The numpy fallback ignores it — pocketfft rows
    are already independent and host-resident.
    """
    n = X.shape[1]
    with span("cycles.spectrum", rows=X.shape[0], n=n):
        if _resolve_kernel(use_kernel):
            if kops.dft_supported(n):
                P = kops.power_spectrum(X, center=True, mesh=mesh)
                with span("sync.spectrum"):
                    return np.asarray(P)
            _warn_host_fallback("power spectrum", n)
        F = np.fft.rfft(X - X.mean(axis=1, keepdims=True), axis=1)
        return (F.real ** 2 + F.imag ** 2).astype(np.float32)


def power_spectrum(series: np.ndarray, use_kernel: Optional[bool] = None
                   ) -> np.ndarray:
    """One-sided |FFT|^2 of the mean-removed series. Uses the Pallas MXU
    matmul-DFT kernel (fused mean removal) for the sizes it tiles well;
    falls back to numpy's pocketfft otherwise."""
    return _spectra(np.asarray(series, np.float32)[None], use_kernel)[0]


# A near-constant window leaves only float rounding residue after mean
# removal; relative to the raw signal power that residue is ~eps(f32)^2
# (~1e-14). Real 0/1 classification series with any structure carry
# DC-removed mass >= ~1e-2 of total power, so 1e-9 cleanly separates
# "all noise floor" from "has a cycle to score".
_DEGENERATE_MASS_FRAC = 1e-9


def _total_power(X: np.ndarray) -> np.ndarray:
    """(J, n) -> (J,) raw per-row signal power (DC included), the
    reference scale for the degenerate-window confidence clamp."""
    X = np.asarray(X, np.float64)
    return (X * X).sum(axis=1)


def _peak_pick(P: np.ndarray, n: int, min_period: int, max_period: int,
               total_power: Optional[np.ndarray] = None
               ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Fleet peak pick. P: (J, n//2+1) one-sided power. Returns
    (k_star (J,), confidence (J,), found (J,) bool)."""
    ks = np.arange(P.shape[1])
    with np.errstate(divide="ignore"):
        periods = np.where(ks > 0, n / np.maximum(ks, 1), np.inf)
    valid = (periods >= min_period) & (periods <= max_period)
    Pv = np.where(valid[None, :], P, -1.0)
    Pv[:, 0] = -1.0                                # drop DC
    k_star = np.argmax(Pv, axis=1)
    rows = np.arange(P.shape[0])
    found = Pv[rows, k_star] > 0
    # confidence: peak bin's share of the DC-removed one-sided spectral
    # mass — the single normalization shared by the scalar and batch paths
    mass = P[:, 1:].sum(axis=1)
    conf = P[rows, k_star] / np.maximum(mass, 1e-12)
    if total_power is not None:
        # degenerate-window clamp: when the whole DC-removed mass is float
        # noise (mass hits the 1e-12 floor relative to raw power), the
        # "peak share" is 1.0-of-nothing — report confidence 0 so gates on
        # confidence fall back instead of trusting pure noise.
        # Nor is there a peak to find: the matmul-DFT leaves rounding
        # residue where pocketfft returns exact zeros, and a "period" fit
        # to that residue would differ by backend.
        degen = mass <= _DEGENERATE_MASS_FRAC * np.asarray(total_power)
        conf = np.where(degen, 0.0, conf)
        found = found & ~degen
    return k_star, conf, found


def _refine_period_batch(X: np.ndarray, p0: np.ndarray, min_period: int,
                         max_period: int, mesh=None,
                         use_kernel: Optional[bool] = None) -> np.ndarray:
    """Sharpen FFT bin estimates with a local autocorrelation search, for
    the whole fleet at once.

    FFT periods are quantized to n/k (a 512-sample window puts a true
    120-sample cycle into the 128 bin — enough drift to break Algorithm 2's
    modular indexing four cycles out). The spectral peak still *finds* the
    cycle (the paper's tool); the lag search just de-quantizes it within
    +/- one bin width. All jobs score one shared candidate-lag grid (the
    union of their per-job windows) in a single vectorized pass; each job's
    argmax is masked to its own window.
    """
    J, n = X.shape
    with span("cycles.refine", rows=J, n=n) as s:
        X = np.asarray(X, np.float64)
        Xc = X - X.mean(axis=1, keepdims=True)
        p0 = np.asarray(p0, np.int64)
        width = np.maximum(2, np.ceil(p0 * p0 / n).astype(np.int64) + 1)
        lo = np.maximum(min_period, p0 - width)
        hi = np.minimum(np.minimum(max_period, n - 1), p0 + width)
        ok = hi >= lo
        if not ok.any():
            return p0.copy()
        lag_lo, lag_hi = int(lo[ok].min()), int(hi[ok].max())
        if enabled():
            s.set_metadata(lag_lo=lag_lo, lag_hi=lag_hi)
        kernel = _resolve_kernel(use_kernel)
        if kernel and not kops.autocorr_supported(n):
            _warn_host_fallback("period refinement", n)
            kernel = False
        if kernel:
            # Pallas kernel (TPU row of the dispatch table): fleet x
            # shared candidate-lag grid in one call, optionally row-sharded
            import jax.numpy as jnp
            lags = np.arange(lag_lo, lag_hi + 1)
            scores = kops.autocorr_score(jnp.asarray(Xc, jnp.float32),
                                         jnp.asarray(lags, jnp.int32),
                                         mesh=mesh)
            with span("sync.refine"):
                R = np.asarray(scores)
            R = R.astype(np.float64)
        else:
            # off the TPU: Wiener-Khinchin on the zero-padded rows
            # gives the exact linear autocorrelation R[j, p] = sum_t x[t]
            # x[t+p] at EVERY lag in one vectorized pocketfft pass
            # (interpret-mode Pallas is not a CPU hot path)
            F = np.fft.rfft(Xc, 2 * n, axis=1)
            R = np.fft.irfft(F.real ** 2 + F.imag ** 2, 2 * n, axis=1)[:, :n]
            lags = np.arange(n)
        valid = ((lags[None, :] >= lo[:, None])
                 & (lags[None, :] <= hi[:, None]))
        best = lags[np.argmax(np.where(valid, R, -np.inf), axis=1)]
        return np.where(ok, best, p0)


def _refine_period(x: np.ndarray, p0: int, min_period: int,
                   max_period: int, use_kernel: Optional[bool] = None) -> int:
    """Scalar view of ``_refine_period_batch`` (kept for API compat)."""
    return int(_refine_period_batch(np.asarray(x, np.float64)[None],
                                    np.asarray([p0]), min_period,
                                    max_period, use_kernel=use_kernel)[0])


def cycle_length(series: np.ndarray, *, min_period: int = 2,
                 max_period: Optional[int] = None,
                 use_kernel: Optional[bool] = None) -> Tuple[int, float]:
    """Dominant cycle length of a series. Returns (period, confidence).

    period = round(N / k*) with k* the argmax power bin whose implied period
    lies in [min_period, max_period], de-quantized by the autocorrelation
    refinement; confidence is that bin's share of the DC-removed spectral
    mass.
    """
    x = np.asarray(series, np.float32)
    n = len(x)
    if n < 2 * min_period:
        return 0, 0.0
    max_p = min(max_period or n // 2, n // 2)
    P = _spectra(x[None], use_kernel)
    k_star, conf, found = _peak_pick(P, n, min_period, max_p,
                                     total_power=_total_power(x[None]))
    if not found[0]:
        return 0, 0.0
    p0 = int(round(n / k_star[0]))
    return _refine_period(np.asarray(series, np.float64), p0,
                          min_period, max_p, use_kernel), float(conf[0])


def decompose(classes: np.ndarray, period: int
              ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Algorithm 1 (verbatim): split the first cycle window of the LM/NLM
    series into (ArrayLM, ArrayNLM) moment-index arrays; also returns the
    (period,) LM profile used by Algorithm 2."""
    c = np.asarray(classes[:period], np.int8)
    idx = np.arange(len(c))
    array_lm = idx[c == 1]
    array_nlm = idx[c != 1]
    return array_lm, array_nlm, c


def fold_profile(classes: np.ndarray, period: int) -> np.ndarray:
    """'alma-plus': phase-folded majority vote across all observed cycles."""
    n = (len(classes) // period) * period
    if n == 0:
        return np.asarray(classes[:period], np.int8)
    folded = np.asarray(classes[:n]).reshape(-1, period)
    return (folded.mean(axis=0) >= 0.5).astype(np.int8)


def fit_cycle_batch(classes_batch: np.ndarray, *, min_period: int = 2,
                    max_period: Optional[int] = None,
                    folded: bool = False,
                    use_kernel: Optional[bool] = None,
                    mesh=None) -> List[CycleModel]:
    """Fleet-scale cycle recognition: one batched (Pallas MXU-DFT) power
    spectrum, one batched peak pick, one batched autocorrelation refinement
    for all jobs. This is the surveillance-tick hot path (Fig. 10) — the
    seed's per-job Python dispatch dominated beyond ~100 jobs.

    ``mesh`` row-shards the kernel-path stages across devices; every stage
    is per-row, so sharded output is bit-identical to unsharded.
    """
    X = np.asarray(classes_batch, np.float32)
    J, n = X.shape
    if J == 0:
        return []
    max_p = min(max_period or n // 2, n // 2)
    if n < 2 * min_period:
        return [CycleModel(0, 0.0, np.asarray(
            [1 if X[j].mean() >= 0.5 else 0], np.int8)) for j in range(J)]
    P = _spectra(X, use_kernel, mesh=mesh)
    with span("cycles.peak_pick"):
        k_star, conf, found = _peak_pick(P, n, min_period, max_p,
                                         total_power=_total_power(X))
        p0 = np.round(n / np.maximum(k_star, 1)).astype(np.int64)
        periods = np.where(found, p0, 1)
    if found.any():
        refined = _refine_period_batch(X[found].astype(np.float64),
                                       p0[found], min_period, max_p,
                                       mesh=mesh, use_kernel=use_kernel)
        periods = periods.copy()
        periods[found] = refined
    out: List[CycleModel] = []
    with span("cycles.models"):
        for j in range(J):
            if not found[j]:
                out.append(CycleModel(0, 0.0, np.asarray(
                    [1 if X[j].mean() >= 0.5 else 0], np.int8)))
                continue
            period = int(periods[j])
            cls = np.asarray(classes_batch[j], np.int8)
            array_lm, array_nlm, profile = decompose(cls, period)
            if folded:
                profile = fold_profile(cls, period)
                idx = np.arange(period)
                array_lm, array_nlm = idx[profile == 1], idx[profile != 1]
            out.append(CycleModel(period, float(conf[j]), profile, array_lm,
                                  array_nlm))
    return out


def fit_cycle(classes: np.ndarray, *, min_period: int = 2,
              max_period: Optional[int] = None, folded: bool = False,
              use_kernel: Optional[bool] = None) -> CycleModel:
    """Characterized series -> CycleModel (the paper pipeline in one call).

    A J=1 view of ``fit_cycle_batch`` — scalar/batch parity is structural,
    not coincidental.
    """
    return fit_cycle_batch(np.asarray(classes)[None], min_period=min_period,
                           max_period=max_period, folded=folded,
                           use_kernel=use_kernel)[0]
