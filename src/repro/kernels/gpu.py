"""Pallas Triton lowerings of the cycle-recognition kernels (GPU row of the
``kernels/ops.py`` dispatch table).

The TPU kernels in ``dft.py`` / ``autocorr.py`` lean on TPU-specific Pallas
features (VMEM scratch accumulators across an inner grid axis, SMEM scalar
blocks) that the Triton backend does not provide. These lowerings keep the
same math and the same tiling *contract* (callers pad/slice identically) but
restructure for a GPU:

  * ``dft_power``: grid (batch_tiles, freq_tiles); each program keeps its
    block's full rows resident ((bt, N) f32, N <= 2048 -> 64 KB) and runs the
    whole time reduction as one dot per weight tile — no cross-program
    accumulator, so no scratch. Mean removal uses the same rank-1
    column-sum correction as the TPU epilogue.
  * ``autocorr_score``: the same per-lag row products as the TPU kernel,
    but shifted by a dynamic slice of the zero-extended rows and with the
    candidate-lag tile read from regular memory (the TPU kernel instead
    lane-rotates rows by lags scalar-prefetched into SMEM).

Both share the TPU module's weight/table caches and numerics, and both run
under interpret mode on non-GPU hosts — parity against ``kernels/ref.py``
is tested per backend in ``tests/test_kernels.py``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from repro.kernels import backend as kb
from repro.kernels.dft import dft_weights

B_TILE = 8
F_TILE = 128
L_TILE = 8
MAX_N = 2048


# ---------------------------------------------------------------------------
# matmul-DFT power spectrum
# ---------------------------------------------------------------------------
def _dft_kernel(x_ref, cos_ref, sin_ref, csum_ref, ssum_ref, out_ref,
                *, n: int, center: bool):
    x = x_ref[...]                                          # (bt, N)
    re = jnp.dot(x, cos_ref[...], preferred_element_type=jnp.float32)
    im = jnp.dot(x, sin_ref[...], preferred_element_type=jnp.float32)
    if center:
        mean = jnp.sum(x, axis=1, keepdims=True) * (1.0 / n)
        re = re - mean * csum_ref[...]
        im = im - mean * ssum_ref[...]
    out_ref[...] = re ** 2 + im ** 2


@functools.partial(jax.jit, static_argnames=("center", "interpret"))
def _dft_power(x: jnp.ndarray, *, center: bool, interpret: bool
               ) -> jnp.ndarray:
    B, N = x.shape
    cos_np, sin_np = dft_weights(N)
    csum = jnp.asarray(cos_np.sum(axis=0, dtype=np.float64)
                       .astype(np.float32)[None, :])
    ssum = jnp.asarray(sin_np.sum(axis=0, dtype=np.float64)
                       .astype(np.float32)[None, :])
    bt = min(B_TILE, B)
    B_p = -(-B // bt) * bt
    if B_p != B:
        x = jnp.pad(x, ((0, B_p - B), (0, 0)))
    out = pl.pallas_call(
        functools.partial(_dft_kernel, n=N, center=center),
        out_shape=jax.ShapeDtypeStruct((B_p, N), jnp.float32),
        grid=(B_p // bt, N // F_TILE),
        in_specs=[
            pl.BlockSpec((bt, N), lambda bi, fi: (bi, 0)),
            pl.BlockSpec((N, F_TILE), lambda bi, fi: (0, fi)),
            pl.BlockSpec((N, F_TILE), lambda bi, fi: (0, fi)),
            pl.BlockSpec((1, F_TILE), lambda bi, fi: (0, fi)),
            pl.BlockSpec((1, F_TILE), lambda bi, fi: (0, fi)),
        ],
        out_specs=pl.BlockSpec((bt, F_TILE), lambda bi, fi: (bi, fi)),
        interpret=interpret,
    )(x, jnp.asarray(cos_np), jnp.asarray(sin_np), csum, ssum)
    return out[:B]


def dft_power(x: jnp.ndarray, *, center: bool = False,
              interpret=None) -> jnp.ndarray:
    """x: (B, N) f32, N % 128 == 0, N <= 2048 -> (B, N) power spectrum.

    Same contract as ``dft.dft_power``; ``interpret=None`` auto-detects
    (compiled on GPU, interpret elsewhere).
    """
    return _dft_power(x, center=center,
                      interpret=kb.resolve_interpret("gpu", interpret))


# ---------------------------------------------------------------------------
# autocorrelation scoring
# ---------------------------------------------------------------------------
def _ac_kernel(x_ref, lags_ref, out_ref):
    x = x_ref[...]                                          # (bt, N)
    xp = jnp.concatenate([x, jnp.zeros_like(x)], axis=1)    # zero tail = mask

    def body(l, acc):
        p = jnp.clip(lags_ref[l], 0, x.shape[1])
        sh = jax.lax.dynamic_slice(xp, (0, p), x.shape)
        return acc.at[:, l].set(jnp.sum(x * sh, axis=1))

    out_ref[...] = jax.lax.fori_loop(
        0, lags_ref.shape[0], body,
        jnp.zeros(out_ref.shape, jnp.float32))


@functools.partial(jax.jit, static_argnames=("interpret",))
def _autocorr_score(x: jnp.ndarray, lags: jnp.ndarray, *,
                    interpret: bool) -> jnp.ndarray:
    J, N = x.shape
    L = lags.shape[0]
    bt = min(B_TILE, J)
    J_p = -(-J // bt) * bt
    L_p = -(-L // L_TILE) * L_TILE
    if J_p != J:
        x = jnp.pad(x, ((0, J_p - J), (0, 0)))
    if L_p != L:
        lags = jnp.pad(lags, (0, L_p - L))
    out = pl.pallas_call(
        _ac_kernel,
        out_shape=jax.ShapeDtypeStruct((J_p, L_p), jnp.float32),
        grid=(J_p // bt, L_p // L_TILE),
        in_specs=[
            pl.BlockSpec((bt, N), lambda ji, li: (ji, 0)),
            pl.BlockSpec((L_TILE,), lambda ji, li: (li,)),
        ],
        out_specs=pl.BlockSpec((bt, L_TILE), lambda ji, li: (ji, li)),
        interpret=interpret,
    )(x.astype(jnp.float32), lags.astype(jnp.int32))
    return out[:J, :L]


def autocorr_score(x: jnp.ndarray, lags: jnp.ndarray, *,
                   interpret=None) -> jnp.ndarray:
    """x: (J, N) f32 rows x (L,) int32 shared lags -> (J, L) f32 scores.

    Same contract as ``autocorr.autocorr_score``; ``interpret=None``
    auto-detects (compiled on GPU, interpret elsewhere).
    """
    return _autocorr_score(x, lags,
                           interpret=kb.resolve_interpret("gpu", interpret))
