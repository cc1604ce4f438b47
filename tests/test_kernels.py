"""Per-kernel shape/dtype sweeps against the pure-jnp oracles (ref.py).

All kernels run in interpret mode on CPU (the TPU lowering is the target;
interpret executes the same kernel body)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import dirty_delta, ops, ref
from repro.kernels.dft import dft_power
from repro.kernels.flash_attention import flash_attention
from repro.kernels.ssm_scan import ssm_scan
from repro.models.gla import gla_chunked

RNG = np.random.default_rng(42)


def randn(*shape, dtype=jnp.float32):
    return jnp.asarray(RNG.standard_normal(shape), dtype)


# ---------------------------------------------------------------------------
# dirty_delta
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("nb,blk", [(1, 64), (7, 129), (32, 2048), (65, 300)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_dirty_delta_sweep(nb, blk, dtype):
    """The padded block view of ``dirty_mask`` (blocks that are no
    multiple of 128 padded to whole lane chunks) against the oracle."""
    new = randn(nb, blk, dtype=dtype)
    old = randn(nb, blk, dtype=dtype)
    got = dirty_delta._view_max(new, old, blk)
    want = ref.max_abs_delta_ref(new, old)[:, 0]
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-6, atol=1e-6)


def test_dirty_blocks_exact_detection():
    new = randn(16, 512, dtype=jnp.bfloat16)
    old = jnp.array(new)
    old = old.at[3, 100].add(jnp.bfloat16(0.5)).at[12, 0].add(jnp.bfloat16(-1))
    d = dirty_delta.dirty_mask(new, old, 512)
    assert set(np.flatnonzero(np.asarray(d))) == {3, 12}


def test_dirty_blocks_int_dtype():
    new = jnp.arange(4 * 64, dtype=jnp.int32).reshape(4, 64)
    old = new.at[2, 5].add(1)
    d = dirty_delta.dirty_mask(new, old, 64)
    assert set(np.flatnonzero(np.asarray(d))) == {2}


# ---------------------------------------------------------------------------
# dft
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("b,n", [(1, 128), (3, 256), (9, 512), (2, 1024)])
def test_dft_power_sweep(b, n):
    x = randn(b, n)
    got = dft_power(x)
    want = ref.dft_power_ref(x)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-2)


@pytest.mark.parametrize("b,n", [(3, 128), (5, 512)])
def test_dft_fused_mean_removal(b, n):
    """center=True (in-kernel prologue) == host-side x - x.mean()."""
    x = randn(b, n) + 3.0                      # big DC so the fusion matters
    got = dft_power(x, center=True)
    want = dft_power(x - jnp.mean(x, axis=-1, keepdims=True))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-2)


def test_dft_weights_quarter_shift_exact():
    """sin derived from the shared cosine table == direct evaluation."""
    from repro.kernels.dft import dft_weights
    for n in (128, 256):
        cos_w, sin_w = dft_weights(n)
        t = np.arange(n)[:, None] * np.arange(n)[None, :]
        ang = 2.0 * np.pi * t / n
        np.testing.assert_allclose(cos_w, np.cos(ang), atol=1e-6)
        np.testing.assert_allclose(sin_w, np.sin(ang), atol=1e-6)


def test_dft_weight_cache_capped():
    """Regression: the weight cache must stay bounded (the seed pinned up
    to 8 pairs of N x N f32 matrices — 268 MB at N=2048)."""
    from repro.kernels.dft import (MAX_N, _TABLE_CACHE_MAX, dft_cache_nbytes,
                                   dft_weights)
    for n in (128, 256, 512, 1024, 2048, 512, 128):
        dft_weights(n)
    # capacity entries of (int16 phase matrix + f32 table) at worst-case N
    bound = _TABLE_CACHE_MAX * (2 * MAX_N * MAX_N + 4 * MAX_N)
    assert dft_cache_nbytes() <= bound
    assert dft_cache_nbytes() < 268e6 / 10


# ---------------------------------------------------------------------------
# autocorr (period refinement)
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("j,n,nl", [(1, 128, 3), (7, 256, 8), (12, 512, 17)])
def test_autocorr_score_sweep(j, n, nl):
    from repro.kernels.autocorr import autocorr_score, autocorr_score_ref
    x = randn(j, n)
    x = x - jnp.mean(x, axis=1, keepdims=True)
    lags = jnp.asarray(RNG.integers(0, n + 10, nl), jnp.int32)
    got = autocorr_score(x, lags)
    want = autocorr_score_ref(np.asarray(x), np.asarray(lags))
    np.testing.assert_allclose(np.asarray(got), want, rtol=2e-4, atol=2e-3)


def test_dft_finds_planted_period():
    n = 512
    t = np.arange(n)
    x = jnp.asarray(np.sin(2 * np.pi * t / 32)[None, :], jnp.float32)
    p = np.asarray(ops.power_spectrum(x))[0]
    assert int(np.argmax(p[1:])) + 1 == n // 32


# ---------------------------------------------------------------------------
# backend dispatch table (tpu / xla rows)
# ---------------------------------------------------------------------------
def test_kernel_backend_detection_and_override():
    assert ops.kernel_backend() == "xla"        # CPU container
    assert not ops.on_tpu()
    with ops.force_backend("tpu"):
        assert ops.kernel_backend() == "tpu" and ops.on_tpu()
    assert ops.kernel_backend() == "xla"        # override scoped
    with pytest.raises(ValueError):
        with ops.force_backend("gpu"):
            pass


def test_interpret_autodetect_off_target():
    """interpret=None must resolve to interpret mode on a foreign host
    (CPU here), and an explicit flag must win."""
    from repro.kernels import backend as kb
    assert kb.resolve_interpret(None) is True
    assert kb.resolve_interpret(False) is False
    # force_backend routes DISPATCH only — the physical platform still
    # decides interpret, so a forced row never tries to compile on CPU
    with ops.force_backend("tpu"):
        assert kb.resolve_interpret(None) is True


def test_kernel_table_covers_every_row():
    table = ops.kernel_table()
    assert set(table) == {"power_spectrum", "autocorr_score"}
    for op, rows in table.items():
        assert set(rows) == {"tpu", "xla"}, op


@pytest.mark.parametrize("row", ["tpu", "xla"])
def test_power_spectrum_rows_parity(row):
    """Every dispatch row against the f64 numpy oracle (the Pallas row runs
    in interpret mode on this host — the same kernel body as on the TPU)."""
    x = randn(5, 256) + 1.5                     # DC offset: center matters
    with ops.force_backend(row):
        got = np.asarray(ops.power_spectrum(x, center=True))
    xc = np.asarray(x, np.float64)
    xc -= xc.mean(axis=1, keepdims=True)
    F = np.fft.fft(xc, axis=1)[:, : 129]
    want = F.real ** 2 + F.imag ** 2
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-2)


@pytest.mark.parametrize("row", ["tpu", "xla"])
def test_autocorr_rows_parity(row):
    from repro.kernels.autocorr import autocorr_score_ref
    x = randn(6, 256)
    x = x - jnp.mean(x, axis=1, keepdims=True)
    lags = jnp.asarray(RNG.integers(0, 270, 13), jnp.int32)
    with ops.force_backend(row):
        got = np.asarray(ops.autocorr_score(x, lags))
    want = autocorr_score_ref(np.asarray(x), np.asarray(lags))
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-3)


def test_dispatch_falls_back_off_tile_shapes():
    """Shapes outside the Pallas tiling contract must take the xla row even
    when an accelerator row is forced — callers never see a tiling error."""
    x = randn(3, 200)                           # 200 not a T_TILE multiple
    assert not ops.dft_supported(200)
    with ops.force_backend("tpu"):
        got = np.asarray(ops.power_spectrum(x, center=True))
    xc = np.asarray(x, np.float64)
    xc -= xc.mean(axis=1, keepdims=True)
    F = np.fft.fft(xc, axis=1)[:, : 101]
    np.testing.assert_allclose(got, F.real ** 2 + F.imag ** 2,
                               rtol=2e-4, atol=2e-2)


def test_cycle_fit_warns_when_accelerator_falls_back_to_host():
    """A window length off the kernel tiling still fits on the host, but on
    an accelerator row it says so; an explicit host fit stays silent."""
    import warnings

    from repro.core import cycles
    t = np.arange(200)
    lm = (np.sin(2 * np.pi * t / 20) > 0).astype(np.int8)[None]
    with ops.force_backend("tpu"), pytest.warns(RuntimeWarning,
                                                match="power spectrum"):
        fitted = cycles.fit_cycle_batch(lm)
    assert fitted[0].period == 20
    with ops.force_backend("tpu"), warnings.catch_warnings():
        warnings.simplefilter("error")
        assert cycles.fit_cycle_batch(lm, use_kernel=False)[0].period == 20


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("s,hkv,g,d", [(128, 1, 1, 64), (256, 2, 2, 64),
                                       (384, 2, 4, 128), (256, 4, 1, 32)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_sweep(s, hkv, g, d, dtype):
    q = randn(2, hkv * g, s, d, dtype=dtype)
    k = randn(2, hkv, s, d, dtype=dtype)
    v = randn(2, hkv, s, d, dtype=dtype)
    got = flash_attention(q, k, v, bq=128, bk=128)
    want = ref.attention_ref(q, k, v)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol)


@pytest.mark.parametrize("window", [64, 128, 500])
def test_flash_attention_swa(window):
    q = randn(1, 4, 256, 64)
    k = randn(1, 2, 256, 64)
    v = randn(1, 2, 256, 64)
    got = flash_attention(q, k, v, window=window)
    want = ref.attention_ref(q, k, v, window=window)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# ssm scan
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("s,dk,dv", [(64, 16, 16), (128, 64, 32),
                                     (96, 32, 64)])
@pytest.mark.parametrize("ssd", [True, False])
def test_ssm_scan_sweep(s, dk, dv, ssd):
    B, H = 2, 3
    q = randn(B, H, s, dk)
    k = randn(B, H, s, dk)
    v = randn(B, H, s, dv)
    lw = -jnp.abs(randn(B, H, s, dk)) * 0.3
    u = randn(H, dk) if not ssd else None
    y_k, st_k = ssm_scan(q, k, v, lw, bonus=u if not ssd else None, ssd=ssd)
    y_r, st_r = ref.ssm_scan_ref(q, k, v, lw, bonus=u, ssd=ssd)
    np.testing.assert_allclose(np.asarray(y_k), np.asarray(y_r),
                               rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(st_k), np.asarray(st_r),
                               rtol=2e-4, atol=2e-4)


def test_ssm_scan_matches_gla_chunked():
    """Kernel and the model's XLA path share the algorithm bit-for-bit-ish."""
    B, H, S, Dk, Dv = 1, 2, 256, 32, 48
    q, k = randn(B, H, S, Dk), randn(B, H, S, Dk)
    v = randn(B, H, S, Dv)
    lw = -jnp.abs(randn(B, H, S, Dk))
    y_k, st_k = ssm_scan(q, k, v, lw, ssd=True, chunk=32)
    y_c, st_c = gla_chunked(q, k, v, lw, chunk=32)
    np.testing.assert_allclose(np.asarray(y_k), np.asarray(y_c),
                               rtol=5e-4, atol=5e-4)
    np.testing.assert_allclose(np.asarray(st_k), np.asarray(st_c),
                               rtol=5e-4, atol=5e-4)
