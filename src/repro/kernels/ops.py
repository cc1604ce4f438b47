"""Public wrappers for the accelerated ops — one backend-dispatch table.

Each cycle-recognition op has two lowerings, selected per process by
``backend.kernel_backend()`` (overridable with ``backend.force_backend`` so
tests can run the Pallas row, in interpret mode, on a CPU host):

  ==================  ===========================  ============================
  op                  tpu                          xla (fallback)
  ==================  ===========================  ============================
  power_spectrum      dft.dft_power                ref.dft_power_ref
                      (Pallas MXU matmul-DFT,      (jnp complex FFT)
                      fused mean removal)
  autocorr_score      autocorr.autocorr_score      ref.autocorr_score_ref_xla
                      (VMEM rows, lane-roll        (vmap slices)
                      per prefetched lag)
  ==================  ===========================  ============================

The Pallas row auto-detects ``interpret``: compiled on a TPU, interpret mode
elsewhere (validation). Shapes outside a kernel's tiling contract always
fall back to the xla row, so callers never care.

Both table ops accept an optional ``mesh``: rows are then partitioned across
the mesh devices with ``shard_map`` (every lowering is embarrassingly
parallel per row, so sharded results are bit-identical to unsharded) — the
kernel half of the sharded surveillance plane (``core/shard.py``).

The training-side kernels (flash attention, ssm scan) keep their
TPU-or-reference dispatch: they are not on the decide-plane hot path. The
pre-copy calls its kernels' own modules (``dirty_delta``, ``dirty_copy``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import autocorr as _ac
from repro.kernels import backend as kb
from repro.kernels import dft as _dft
from repro.kernels import flash_attention as _fa
from repro.kernels import ref
from repro.kernels import ssm_scan as _ssm
from repro.kernels.backend import (  # noqa: F401  (re-exported API)
    force_backend, kernel_backend, on_tpu)


def _interpret() -> bool:
    """Interpret flag for the TPU-only training kernels."""
    return kb.resolve_interpret(None)


def _row_sharded(fn, mesh, x: jnp.ndarray) -> jnp.ndarray:
    """Run ``fn`` with the rows of ``x`` partitioned across ``mesh`` via
    shard_map (1-D mesh, axis name taken from the mesh). Rows are padded to
    a multiple of the device count and sliced back; since every lowering is
    per-row, the result is bit-identical to ``fn(x)``."""
    from jax.sharding import PartitionSpec as P
    n = int(mesh.devices.size)
    axis = mesh.axis_names[0]
    B = x.shape[0]
    B_p = -(-B // n) * n
    if B_p != B:
        x = jnp.pad(x, ((0, B_p - B),) + ((0, 0),) * (x.ndim - 1))
    out = jax.shard_map(fn, mesh=mesh, in_specs=(P(axis),),
                        out_specs=P(axis), check_vma=False)(x)
    return out[:B]


# ---------------------------------------------------------------------------
# DFT power spectrum (cycle recognition)
# ---------------------------------------------------------------------------
def _power_tpu(x: jnp.ndarray, *, center: bool) -> jnp.ndarray:
    return _dft.dft_power(x.astype(jnp.float32), center=center)


def _power_xla(x: jnp.ndarray, *, center: bool) -> jnp.ndarray:
    if center:
        x = x - jnp.mean(x, axis=-1, keepdims=True)
    return ref.dft_power_ref(x)


POWER_SPECTRUM = {"tpu": _power_tpu, "xla": _power_xla}


def dft_supported(n: int) -> bool:
    return n % _dft.T_TILE == 0 and 0 < n <= _dft.MAX_N


def power_spectrum(x: jnp.ndarray, *, center: bool = False,
                   mesh=None) -> jnp.ndarray:
    """x: (B, N) -> (B, N//2+1) one-sided power spectrum.

    ``center=True`` removes each row's mean (fused into the kernel prologue
    on the Pallas rows). ``mesh`` partitions the batch rows across devices.
    """
    B, N = x.shape
    row = kernel_backend() if dft_supported(N) else "xla"
    fn = functools.partial(POWER_SPECTRUM[row], center=center)
    p = _row_sharded(fn, mesh, x) if mesh is not None else fn(x)
    return p[:, : N // 2 + 1]


# ---------------------------------------------------------------------------
# autocorrelation scoring (period refinement)
# ---------------------------------------------------------------------------
def _autocorr_tpu(x, lags):
    return _ac.autocorr_score(x, lags)


def _autocorr_xla(x, lags):
    return ref.autocorr_score_ref_xla(x, lags)


AUTOCORR_SCORE = {"tpu": _autocorr_tpu, "xla": _autocorr_xla}


def autocorr_supported(n: int) -> bool:
    return 0 < n <= _ac.MAX_N


def autocorr_score(x: jnp.ndarray, lags: jnp.ndarray, *,
                   mesh=None) -> jnp.ndarray:
    """(J, N) rows x (L,) shared candidate lags -> (J, L) scores.

    The Pallas kernel on a TPU, the jnp fallback elsewhere.
    Note the decide plane's CPU hot path does not come through here at all
    — off-accelerator ``cycles._refine_period_batch`` uses a Wiener-
    Khinchin pocketfft pass, which beats any per-lag scoring on host.
    ``mesh`` partitions the job rows across devices.
    """
    row = kernel_backend() if autocorr_supported(x.shape[1]) else "xla"
    fn = AUTOCORR_SCORE[row]
    if mesh is not None:
        return _row_sharded(lambda v: fn(v, lags), mesh, x)
    return fn(x, lags)


def kernel_table() -> dict:
    """Introspection: op -> {backend row -> implementing callable}. The
    README's dispatch table and the per-backend parity tests iterate this
    so a silently added/renamed row cannot escape coverage."""
    return {"power_spectrum": dict(POWER_SPECTRUM),
            "autocorr_score": dict(AUTOCORR_SCORE)}


# ---------------------------------------------------------------------------
# flash attention (prefill hot path)
# ---------------------------------------------------------------------------
def flash_attention(q, k, v, *, window: int = 0) -> jnp.ndarray:
    S = q.shape[2]
    if S % _fa.DEFAULT_BQ == 0:
        return _fa.flash_attention(q, k, v, window=window,
                                   interpret=_interpret())
    return ref.attention_ref(q, k, v, window=window)


# ---------------------------------------------------------------------------
# ssm scan (Mamba2/RWKV6)
# ---------------------------------------------------------------------------
def ssm_scan(q, k, v, log_decay, *, bonus=None, ssd: bool = True):
    S = q.shape[2]
    if S % _ssm.DEFAULT_CHUNK == 0:
        return _ssm.ssm_scan(q, k, v, log_decay, bonus=bonus, ssd=ssd,
                             interpret=_interpret())
    return ref.gla_chunked(q, k, v, log_decay,
                           bonus=bonus if not ssd else None)
