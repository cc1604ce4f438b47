"""Batched autocorrelation scoring — the period-refinement hot spot.

FFT bin periods are quantized to N/k; `cycles` de-quantizes them with a
local lag search maximizing the (mean-removed) autocorrelation. At fleet
scale the seed ran that search as a scalar Python loop per job — the single
largest CPU cost of a surveillance tick beyond ~100 jobs. Here the whole
fleet scores one shared grid of candidate lags in a single Pallas call:

    R[j, l] = sum_t x[j, t] * x[j, t + lag_l]        (t + lag_l < N)

Grid: (job_tiles, lag_tiles). The candidate lags are scalar-prefetched into
SMEM whole. Each kernel instance keeps its block's full rows resident in
VMEM (bt x N f32, <= 256 KB at N=2048) and walks its tile of lags with a
fori_loop: the shifted row ``x[:, t + lag]`` is a lane rotation
(``pltpu.roll`` by ``N - lag``) whose wrapped-around tail is masked off by
``t < N - lag``, and the row sum lands in the lag's column of a (bt, LT)
accumulator through a lane select — every access stays on the (8, 128)
tiling. The products are VPU work — no MXU — but one kernel launch
replaces J Python-dispatched dot-product loops, and rows are streamed once
per lag *tile* instead of once per lag.

Callers (``cycles._refine_period_batch``) pick each job's argmax over its
own valid lag window; invalid/padding lags are masked host-side.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import backend as kb
from repro.spans import scope

B_TILE = 32
L_TILE = 128
MAX_N = 2048


def _kernel(lags_ref, x_ref, out_ref):
    x = x_ref[...]                                         # (bt, N)
    n = x.shape[1]
    lt = out_ref.shape[1]
    t = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    col = jax.lax.broadcasted_iota(jnp.int32, out_ref.shape, 1)
    base = pl.program_id(1) * lt

    def body(l, acc):
        p = jnp.clip(lags_ref[base + l], 0, n)
        sh = pltpu.roll(x, (n - p) % n, 1)                 # sh[:, t] = x[:, t+p]
        s = jnp.sum(jnp.where(t < n - p, x * sh, 0.0), axis=1, keepdims=True)
        return jnp.where(col == l, s, acc)

    out_ref[...] = jax.lax.fori_loop(
        0, lt, body, jnp.zeros(out_ref.shape, jnp.float32))


@functools.partial(jax.jit, static_argnames=("interpret",))
def _autocorr_score(x: jnp.ndarray, lags: jnp.ndarray, *,
                    interpret: bool) -> jnp.ndarray:
    J, N = x.shape
    L = lags.shape[0]
    bt = min(B_TILE, J)
    J_p = -(-J // bt) * bt
    # one lag tile of the full width when it fits, else 128-lane tiles
    lt = L if L <= L_TILE else L_TILE
    L_p = -(-L // lt) * lt
    with scope("autocorr"):
        if J_p != J:
            x = jnp.pad(x, ((0, J_p - J), (0, 0)))
        if L_p != L:
            lags = jnp.pad(lags, (0, L_p - L))
        out = pl.pallas_call(
            _kernel,
            out_shape=jax.ShapeDtypeStruct((J_p, L_p), jnp.float32),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=1,
                grid=(J_p // bt, L_p // lt),
                in_specs=[pl.BlockSpec((bt, N),
                                       lambda ji, li, lags: (ji, 0))],
                out_specs=pl.BlockSpec((bt, lt),
                                       lambda ji, li, lags: (ji, li)),
            ),
            interpret=interpret,
            name="autocorr_score",
        )(lags.astype(jnp.int32), x.astype(jnp.float32))
        return out[:J, :L]


def autocorr_score(x: jnp.ndarray, lags: jnp.ndarray, *,
                   interpret=None) -> jnp.ndarray:
    """x: (J, N) f32 mean-removed rows; lags: (L,) int32 shared candidates.

    Returns (J, L) f32 unnormalized autocorrelation scores. Lags outside
    [0, N] are clamped (callers mask their scores out). ``interpret=None``
    auto-detects: compiled on TPU, interpret mode (lowering validation)
    everywhere else.
    """
    return _autocorr_score(x, lags, interpret=kb.resolve_interpret(interpret))


def autocorr_score_ref(x: np.ndarray, lags: np.ndarray) -> np.ndarray:
    """Numpy oracle: same contract as ``autocorr_score`` (f64 accumulate)."""
    x = np.asarray(x, np.float64)
    J, N = x.shape
    out = np.zeros((J, len(lags)), np.float64)
    for li, p in enumerate(np.clip(lags, 0, N)):
        if p < N:
            out[:, li] = np.einsum("jt,jt->j", x[:, : N - p], x[:, p:])
    return out.astype(np.float32)
