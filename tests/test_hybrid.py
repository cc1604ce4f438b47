"""The interleaved wiring (granite: Mamba2 and attention layers in a
per-layer order) and the exact Mamba2 scan it runs.

- The chunked SSD scan of a prefill gives the state and outputs of the
  step-by-step recurrence, at decays strong enough that a clamped or
  overflowing chunk would show.
- The wirings that were there trace to the same jaxpr as before the
  interleaved wiring was added.
"""
import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import lm, mamba2
from repro.train import make_decode_step, make_prefill_step

M, A = "mamba_mlp", "attn"


def _sequential(x, dt, A_, Bm, Cm):
    """S_t = exp(dt_t A) S_t-1 + dt_t B_t (x) x_t, y_t = C_t S_t, in
    float64, one token at a time."""
    Bsz, S, H, P = x.shape
    state = np.zeros((Bsz, H, Bm.shape[-1], P))
    ys = []
    for t in range(S):
        decay = np.exp(dt[:, t] * A_)[..., None, None]
        state = decay * state + (dt[:, t, :, None, None] * Bm[:, t, None, :, None]
                                 * x[:, t, :, None, :])
        ys.append(np.einsum("bn,bhnp->bhp", Cm[:, t], state))
    return np.stack(ys, 1), state


@pytest.mark.parametrize("S,chunk", [(40, 16), (32, 8), (5, 16)],
                         ids=["ragged", "whole", "short"])
def test_chunked_ssd_is_the_recurrence(S, chunk):
    """Steps with dt A down to -40 (a clamp at -4, or a chunk factored
    around its middle, would read them wrong) and up to 0."""
    rng = np.random.default_rng(0)
    Bsz, H, P, N = 2, 3, 4, 8
    x = rng.standard_normal((Bsz, S, H, P))
    dt = rng.uniform(0.0, 2.5, (Bsz, S, H))
    A_ = -np.array([1.0, 4.0, 16.0])
    Bm, Cm = rng.standard_normal((2, Bsz, S, N))
    y, state = mamba2.ssd_chunked(*(jnp.asarray(a, jnp.float32)
                                    for a in (x, dt, A_, Bm, Cm)), chunk)
    y_ref, state_ref = _sequential(x, dt, A_, Bm, Cm)
    # float32 sums of float64 inputs, states of order 10
    np.testing.assert_allclose(np.asarray(state), state_ref, rtol=1e-5,
                               atol=1e-5)
    np.testing.assert_allclose(np.asarray(y), y_ref, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("chunk", [4, mamba2.CHUNK], ids=["ragged", "one"])
def test_prefill_state_is_the_decode_recurrence(monkeypatch, chunk):
    """The prefill's final SSM state of each Mamba layer, over 11 tokens in
    chunks of 4 or in one chunk, equals the state reached by decoding the
    same tokens one at a time from an empty cache."""
    monkeypatch.setattr(mamba2, "CHUNK", chunk)
    cfg = get_config("granite_4_0_h_micro").smoke().replace(
        num_layers=4, block_pattern=(M, M, A, M), param_dtype="float32")
    params = lm.init_params(cfg, jax.random.key(1))
    tokens = jnp.asarray(np.random.default_rng(1).integers(
        0, cfg.vocab_size, (2, 11)), jnp.int32)
    _, cache = make_prefill_step(cfg, cache_len=16)(params,
                                                    {"tokens": tokens})
    step = jax.jit(make_decode_step(cfg))
    dec = lm.init_cache(cfg, 2, 16)
    for t in range(tokens.shape[1]):
        _, _, dec = step(params, tokens[:, t: t + 1], dec)
    for got, want in zip(cache[M], dec[M]):
        np.testing.assert_allclose(np.asarray(got[1]), np.asarray(want[1]),
                                   rtol=2e-4, atol=2e-5)
    assert int(cache["pos"]) == int(dec["pos"]) == tokens.shape[1]


def test_runs_scan_each_stretch_of_same_kind_layers():
    cfg = get_config("granite_4_0_h_micro").replace(num_layers=20)
    assert lm.wiring_mode(cfg) == "interleaved"
    assert lm._runs(cfg) == [(M, 0, 5), (A, 0, 1), (M, 5, 9), (A, 1, 1),
                             (M, 14, 4)]


def test_granite_parameter_count():
    """3.19 B at 40 layers, as the analytic count of the config says (which
    leaves out the final norm's gains)."""
    cfg = get_config("granite_4_0_h_micro")
    n = lm.param_count(cfg)
    assert n == cfg.param_count() + cfg.d_model
    assert 3.1e9 < n < 3.3e9


#: sha256 (first 16 hex) of the jaxprs of prefill (2 x 16 tokens, cache 32)
#: and decode, smoke sizes, as traced before the interleaved wiring
WIRING_JAXPRS = {
    "internlm2_1p8b": ("90063255f6c0698a", "4ba04f34db05fe25"),   # uniform
    "kimi_k2_1t_a32b": ("a3d77f7ca68a47ae", "adec4cb825337ebb"),  # prefix_dense
}


@pytest.mark.parametrize("arch", sorted(WIRING_JAXPRS))
def test_existing_wirings_trace_as_before(arch):
    cfg = get_config(arch).smoke()
    params = jax.eval_shape(lambda: lm.init_params(cfg, jax.random.key(0)))
    tokens = jax.ShapeDtypeStruct((2, 16), jnp.int32)
    prefill = jax.make_jaxpr(make_prefill_step(cfg, cache_len=32))(
        params, {"tokens": tokens})
    cache = jax.eval_shape(lambda: lm.init_cache(cfg, 2, 32))
    decode = jax.make_jaxpr(make_decode_step(cfg))(
        params, jax.ShapeDtypeStruct((2, 1), jnp.int32), cache)
    digest = tuple(hashlib.sha256(str(j).encode()).hexdigest()[:16]
                   for j in (prefill, decode))
    assert digest == WIRING_JAXPRS[arch]
