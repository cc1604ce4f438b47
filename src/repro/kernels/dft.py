"""MXU matmul-DFT power spectrum — the cycle-recognition hot spot.

The paper's FFT runs per VM over short classification series; a fleet of
1,000+ jobs classifies thousands of series at once. On TPU a radix-2
butterfly wastes the MXU, so we *adapt* (DESIGN.md §5): the DFT of a batch
of length-N real series is two N x N matmuls against precomputed cos/sin
weight matrices with a fused square-add epilogue:

    P[b, f] = (x_b . cos_f)^2 + (x_b . sin_f)^2

O(N^2) per series instead of O(N log N), but N <= 2048 here and the MXU
turns the batch into dense 128-aligned tiles — for series batches this beats
a scalar butterfly on TPU by a wide margin (the classic FFT-vs-matmul
crossover argument). Grid: (batch_tiles, freq_tiles, time_tiles), time
innermost with two f32 accumulators in VMEM scratch.

Mean removal is fused (``center=True``): a third running accumulator holds
the per-row sum, and the epilogue applies the exact rank-1 correction

    (x - m 1) . W_f = x . W_f - m (1 . W_f)

against the precomputed column sums of the weight matrices, so the host
never materializes the ``X - X.mean()`` copy the surveillance tick used to
pay per fleet scan.

Weight memory: instead of pinning two N x N f32 matrices per cached N
(268 MB worst case at the old ``lru_cache(maxsize=8)``), the cache holds one
length-N cosine table plus an int16 phase-index matrix per N (capacity 2);
``sin`` is the same table read a quarter period earlier. Matrices are
materialized only transiently at trace time (they live on as jit-cache
constants, not host arrays).
"""
from __future__ import annotations

import functools
from collections import OrderedDict
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import backend as kb
from repro.spans import scope

B_TILE = 8
F_TILE = 128
T_TILE = 128
MAX_N = 2048

_TABLE_CACHE_MAX = 2
_TABLE_CACHE: "OrderedDict[int, Tuple[np.ndarray, np.ndarray]]" = OrderedDict()


def _dft_tables(n: int) -> Tuple[np.ndarray, np.ndarray]:
    """Cached (cos table (n,) f32, phase-index matrix (n, n) int16).

    ``idx[t, f] = (t * f) % n`` indexes the shared cosine table; int16 is
    exact because the kernel caps n at ``MAX_N`` = 2048 < 2**15. Footprint
    per entry is 2 n^2 + 4 n bytes — a quarter of one f32 weight matrix.
    """
    if n in _TABLE_CACHE:
        _TABLE_CACHE.move_to_end(n)
        return _TABLE_CACHE[n]
    k = np.arange(n, dtype=np.int64)
    table = np.cos(2.0 * np.pi * k / n).astype(np.float32)
    idx = (np.outer(k, k) % n).astype(np.int16)
    _TABLE_CACHE[n] = (table, idx)
    while len(_TABLE_CACHE) > _TABLE_CACHE_MAX:
        _TABLE_CACHE.popitem(last=False)
    return table, idx


def dft_cache_nbytes() -> int:
    """Resident bytes pinned by the DFT weight cache (regression-tested)."""
    return sum(t.nbytes + i.nbytes for t, i in _TABLE_CACHE.values())


def dft_weights(n: int) -> Tuple[np.ndarray, np.ndarray]:
    """(cos, sin) n x n f32 DFT weight matrices.

    Materialized on demand from the cached tables: sin(2 pi t f / n) is the
    cosine table read a quarter period back (n % 4 == 0 on every kernel-
    supported n; other n fall back to direct evaluation, uncached).
    """
    if n > MAX_N or n % 4:
        t = np.arange(n)[:, None] * np.arange(n)[None, :]
        ang = 2.0 * np.pi * t / n
        return np.cos(ang).astype(np.float32), np.sin(ang).astype(np.float32)
    table, idx = _dft_tables(n)
    cos = table[idx]
    sin = table[(idx.astype(np.int32) - n // 4) % n]
    return cos, sin


def _kernel(x_ref, cos_ref, sin_ref, csum_ref, ssum_ref, out_ref,
            acc_re, acc_im, acc_sum, *, n: int, center: bool):
    ti = pl.program_id(2)
    nt = pl.num_programs(2)

    @pl.when(ti == 0)
    def _init():
        acc_re[...] = jnp.zeros_like(acc_re)
        acc_im[...] = jnp.zeros_like(acc_im)
        acc_sum[...] = jnp.zeros_like(acc_sum)

    x = x_ref[...]
    acc_re[...] += jax.lax.dot(x, cos_ref[...],
                               preferred_element_type=jnp.float32)
    acc_im[...] += jax.lax.dot(x, sin_ref[...],
                               preferred_element_type=jnp.float32)
    if center:
        acc_sum[...] += jnp.sum(x, axis=1, keepdims=True)

    @pl.when(ti == nt - 1)
    def _emit():
        re, im = acc_re[...], acc_im[...]
        if center:
            mean = acc_sum[...] * (1.0 / n)            # (bt, 1)
            re = re - mean * csum_ref[...]
            im = im - mean * ssum_ref[...]
        out_ref[...] = re ** 2 + im ** 2


@functools.partial(jax.jit, static_argnames=("center", "interpret"))
def _dft_power(x: jnp.ndarray, *, center: bool,
               interpret: bool) -> jnp.ndarray:
    B, N = x.shape
    cos_np, sin_np = dft_weights(N)
    cos_w, sin_w = jnp.asarray(cos_np), jnp.asarray(sin_np)
    # column sums of the weights for the mean-removal rank-1 correction
    csum = jnp.asarray(cos_np.sum(axis=0, dtype=np.float64)
                       .astype(np.float32)[None, :])
    ssum = jnp.asarray(sin_np.sum(axis=0, dtype=np.float64)
                       .astype(np.float32)[None, :])
    bt = min(B_TILE, B)
    B_p = -(-B // bt) * bt
    with scope("spectrum"):
        if B_p != B:
            x = jnp.pad(x, ((0, B_p - B), (0, 0)))
        out = pl.pallas_call(
            functools.partial(_kernel, n=N, center=center),
            out_shape=jax.ShapeDtypeStruct((B_p, N), jnp.float32),
            grid=(B_p // bt, N // F_TILE, N // T_TILE),
            in_specs=[
                pl.BlockSpec((bt, T_TILE), lambda bi, fi, ti: (bi, ti)),
                pl.BlockSpec((T_TILE, F_TILE), lambda bi, fi, ti: (ti, fi)),
                pl.BlockSpec((T_TILE, F_TILE), lambda bi, fi, ti: (ti, fi)),
                pl.BlockSpec((1, F_TILE), lambda bi, fi, ti: (0, fi)),
                pl.BlockSpec((1, F_TILE), lambda bi, fi, ti: (0, fi)),
            ],
            out_specs=pl.BlockSpec((bt, F_TILE),
                                   lambda bi, fi, ti: (bi, fi)),
            scratch_shapes=[pltpu.VMEM((bt, F_TILE), jnp.float32),
                            pltpu.VMEM((bt, F_TILE), jnp.float32),
                            pltpu.VMEM((bt, 1), jnp.float32)],
            interpret=interpret,
            name="dft_power",
        )(x, cos_w, sin_w, csum, ssum)
        return out[:B]


def dft_power(x: jnp.ndarray, *, center: bool = False,
              interpret=None) -> jnp.ndarray:
    """x: (B, N) f32, N % 128 == 0 -> (B, N) power spectrum (all N bins).

    ``center=True`` removes each row's mean inside the kernel (fused
    prologue/epilogue) — equivalent to ``dft_power(x - x.mean(-1, kd))``.
    ``interpret=None`` auto-detects: compiled on TPU, interpret mode
    (lowering validation) everywhere else — callers no longer pay
    interpret-mode dispatch by default on the platform the kernel targets.
    """
    return _dft_power(x, center=center,
                      interpret=kb.resolve_interpret(interpret))
