"""Naive Bayes workload characterization (paper §4.1, Table 5).

Discretized (binned) NB exactly as the paper sketches: load indexes are
quantile-discretized, per-class likelihood tables are learned with Laplace
smoothing, and prediction is a table lookup + sum of logs — Θ(n + k) per
sample (n = number of classes, k = number of indexes), which is the
linear-cost property the paper leans on for 1,000+ VM scalability.

Classes follow the paper: primary workload kinds (CPU / MEM / IO / IDLE)
that collapse onto the binary LM / NLM suitability signal — memory-dirty
workloads are NLM (pre-copy is dirty-rate bound, §3.2), everything else LM.
The posterior probabilities are kept (the paper highlights NB's quantitative
output as an optimization hook) and drive the 'alma-plus' policy.

The predict path is pure JAX (jit + vmap) so a fleet of series can be
classified in one batched call.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

# canonical workload classes (paper §6.2)
CLASSES = ("CPU", "MEM", "IO", "IDLE")
CPU, MEM, IO, IDLE = range(4)
# suitability collapse: pre-copy cost tracks the memory dirty rate
LM_SUITABLE = np.array([True, False, True, True])   # MEM -> NLM


@dataclass
class NaiveBayes:
    """Binned NB model. Arrays are device-ready; predict is jittable."""

    bin_edges: jnp.ndarray      # (F, n_bins-1) quantile edges per feature
    log_likelihood: jnp.ndarray  # (C, F, n_bins)
    log_prior: jnp.ndarray      # (C,)

    @property
    def n_classes(self) -> int:
        return self.log_prior.shape[0]

    def predict_logprob(self, x: jnp.ndarray) -> jnp.ndarray:
        """x: (..., F) -> log-posterior (..., C) (unnormalized)."""
        return _nb_logprob(self.bin_edges, self.log_likelihood,
                           self.log_prior, x)

    def predict(self, x: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
        """Returns (class (...,) int32, posterior (..., C))."""
        return _nb_predict(self.bin_edges, self.log_likelihood,
                           self.log_prior, x)


def _nb_logprob(edges, ll, prior, x):
    bins = jax.vmap(jnp.searchsorted, in_axes=(0, -1), out_axes=-1)(
        edges, x)                                    # (..., F)
    lp = jnp.take_along_axis(
        ll[None], bins[..., None, :, None], axis=-1)[..., 0]  # (..., C, F)
    return jnp.sum(lp, axis=-1) + prior


@jax.jit
def _nb_predict(edges, ll, prior, x):
    lead = x.shape[:-1]
    lp = _nb_logprob(edges, ll, prior, x.reshape(-1, x.shape[-1]))
    lp = lp.reshape(*lead, -1)
    post = jax.nn.softmax(lp, axis=-1)
    return jnp.argmax(lp, axis=-1).astype(jnp.int32), post


#: samples classified per step of the blocked LM classify. The (rows, C, F)
#: likelihood gather pads its small minor dimensions to the TPU's (8, 128)
#: tile, about 3 KB of HBM per sample, so one unblocked call over a 16k-job
#: x 512-sample window needs ~26 GB; a block bounds it near 1 GB.
CLASSIFY_BLOCK = 1 << 18


@functools.partial(jax.jit, static_argnames=("block",))
def _nb_predict_lm(edges, ll, prior, x, *, block: int):
    """LM/NLM signal only: same argmax as ``_nb_predict`` (bit-identical
    class decisions) but skips the softmax posterior — the decide-plane
    tick only consumes the binary suitability series, and this is also the
    shard_map body of the sharded classify (``core/shard.py``): no
    cross-row reduction anywhere, so row-partitioning is exact.

    Samples are classified ``block`` at a time (a sequential
    ``lax.map`` over zero-padded row blocks), so device memory is bounded
    by the block, not the fleet; each block runs the same per-sample ops,
    so the series is bit-identical to one call over all rows."""
    lead = x.shape[:-1]
    rows = x.reshape(-1, x.shape[-1])
    n = rows.shape[0]

    def classify(r):
        return jnp.argmax(_nb_logprob(edges, ll, prior, r), axis=-1)

    if n <= block:
        cls = classify(rows)
    else:
        n_p = -(-n // block) * block
        rows = jnp.pad(rows, ((0, n_p - n), (0, 0)))
        cls = jax.lax.map(classify, rows.reshape(
            n_p // block, block, -1)).reshape(-1)[:n]
    lm = jnp.asarray(LM_SUITABLE, jnp.int8)[
        jnp.clip(cls, 0, len(LM_SUITABLE) - 1)]
    return lm.reshape(lead)


def fit(features: np.ndarray, labels: np.ndarray, *, n_bins: int = 16,
        n_classes: int = len(CLASSES), alpha: float = 1.0) -> NaiveBayes:
    """features: (N, F) f32; labels: (N,) int in [0, n_classes)."""
    N, F = features.shape
    qs = np.linspace(0, 1, n_bins + 1)[1:-1]
    edges = np.quantile(features, qs, axis=0).T.astype(np.float32)  # (F, nb-1)
    # enforce strictly increasing edges (constant features -> tiny ramp)
    edges = np.maximum.accumulate(edges, axis=1)
    bump = np.arange(edges.shape[1], dtype=np.float32) * 1e-9
    edges = edges + bump[None, :]

    bins = np.stack([np.searchsorted(edges[f], features[:, f])
                     for f in range(F)], axis=1)     # (N, F)
    counts = np.zeros((n_classes, F, n_bins), np.float64)
    for c in range(n_classes):
        sel = bins[labels == c]
        for f in range(F):
            counts[c, f] = np.bincount(sel[:, f], minlength=n_bins)
    ll = np.log((counts + alpha)
                / (counts.sum(axis=2, keepdims=True) + alpha * n_bins))
    prior = np.bincount(labels, minlength=n_classes).astype(np.float64)
    log_prior = np.log((prior + alpha) / (prior.sum() + alpha * n_classes))
    return NaiveBayes(jnp.asarray(edges), jnp.asarray(ll, dtype=jnp.float32),
                      jnp.asarray(log_prior, dtype=jnp.float32))


def classify_series(nb: NaiveBayes, window: np.ndarray,
                    ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Classify a telemetry window (T, F) sample-by-sample.

    Returns (classes (T,), lm_binary (T,) {0=NLM,1=LM}, posterior (T, C)).
    """
    cls, post = nb.predict(jnp.asarray(window, jnp.float32))
    cls = np.asarray(cls)
    lm = LM_SUITABLE[np.clip(cls, 0, len(LM_SUITABLE) - 1)].astype(np.int8)
    return cls, lm, np.asarray(post)


def classify_series_batch(nb: NaiveBayes, windows: np.ndarray,
                          ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Classify a fleet of telemetry windows (J, T, F) in ONE jitted call —
    the surveillance-tick entry point (``core/surveillance.py``). Per-row
    results are identical to ``classify_series`` on each window (the jitted
    predict flattens leading axes, so reductions stay per-sample).

    Returns (classes (J, T), lm_binary (J, T) {0=NLM,1=LM},
    posterior (J, T, C)).
    """
    return classify_series(nb, windows)     # predict flattens leading axes


def classify_lm_batch(nb: NaiveBayes, windows: np.ndarray) -> np.ndarray:
    """LM-only fleet classification: (J, T, F) -> (J, T) int8 {0=NLM,1=LM}.

    Bit-identical to ``classify_series_batch``'s lm output (same jitted
    argmax, same suitability table) but never materializes the (J, T, C)
    posterior — the surveillance tick's classify stage.
    """
    return np.asarray(_nb_predict_lm(nb.bin_edges, nb.log_likelihood,
                                     nb.log_prior,
                                     jnp.asarray(windows, jnp.float32),
                                     block=CLASSIFY_BLOCK))


def primary_secondary(classes: np.ndarray) -> Tuple[int, Optional[int]]:
    """Paper Table 5 reporting: the dominant and runner-up workload class."""
    counts = np.bincount(classes, minlength=len(CLASSES))
    order = np.argsort(-counts)
    primary = int(order[0])
    secondary = int(order[1]) if counts[order[1]] > 0.1 * counts.sum() else None
    return primary, secondary
