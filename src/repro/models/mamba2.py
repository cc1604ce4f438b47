"""Mamba2 (SSD) block — the mixer of zamba2 and granite's Mamba layers.

Faithful to the Mamba2 structure: fused in-projection -> short causal
depthwise conv over (x, B, C) -> SSD scan (exact and chunked, one group of
B/C shared by all heads) -> gated RMSNorm -> out-projection. Per-head
scalar decay a_t = exp(dt_t * A_h), never clamped.
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.configs.base import ArchConfig
from repro.models.blocks import dense_init, rmsnorm, rmsnorm_init

Params = Dict[str, jnp.ndarray]

#: SSD chunk of a full-sequence scan: Mamba2's published ``chunk_size``
CHUNK = 256


def dims(cfg: ArchConfig) -> Tuple[int, int, int, int]:
    s = cfg.ssm
    d_in = s.expand * cfg.d_model
    nheads = d_in // s.head_dim
    conv_ch = d_in + 2 * s.state_dim
    return d_in, nheads, s.state_dim, conv_ch


def mamba2_init(rng, cfg: ArchConfig) -> Params:
    s = cfg.ssm
    d = cfg.d_model
    d_in, H, N, conv_ch = dims(cfg)
    ks = jax.random.split(rng, 4)
    proj_out = 2 * d_in + 2 * N + H          # [z, xBC..., dt]
    return {
        "in_proj": dense_init(ks[0], (d, proj_out), cfg.dtype),
        "conv_w": dense_init(ks[1], (s.conv_width, conv_ch), cfg.dtype, scale=2.0),
        "conv_b": jnp.zeros((conv_ch,), cfg.dtype),
        "A_log": jnp.log(jnp.linspace(1.0, 16.0, H, dtype=jnp.float32)),
        "D": jnp.ones((H,), jnp.float32),
        "dt_bias": jnp.log(jnp.expm1(                       # softplus^-1 of dt
            jnp.exp(jax.random.uniform(ks[2], (H,), jnp.float32,
                                       jnp.log(1e-3), jnp.log(1e-1))))),
        "norm": rmsnorm_init(d_in, cfg.dtype),
        "out_proj": dense_init(ks[3], (d_in, d), cfg.dtype,
                               scale=1.0 / (2 * cfg.num_layers) ** 0.5),
    }


def _split_proj(cfg: ArchConfig, proj: jnp.ndarray):
    d_in, H, N, _ = dims(cfg)
    z = proj[..., :d_in]
    xBC = proj[..., d_in: 2 * d_in + 2 * N]
    dt = proj[..., 2 * d_in + 2 * N:]
    return z, xBC, dt


def _causal_depthwise_conv(xBC: jnp.ndarray, w: jnp.ndarray, b: jnp.ndarray,
                           prev: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """Width-W causal depthwise conv via shifted adds (width is 4: cheaper and
    simpler than lax.conv at these widths). ``prev``: (B, W-1, C) carry for
    decode continuation."""
    W = w.shape[0]
    if prev is not None:
        xBC = jnp.concatenate([prev.astype(xBC.dtype), xBC], axis=1)
    pad = W - 1 if prev is None else 0
    xp = jnp.pad(xBC, ((0, 0), (pad, 0), (0, 0)))
    S_out = xBC.shape[1] - (0 if prev is None else W - 1)
    out = sum(xp[:, i: i + S_out] * w[i] for i in range(W))
    return out + b


def _ssd_inputs(params: Params, cfg: ArchConfig, xBC: jnp.ndarray,
                dt_raw: jnp.ndarray):
    """Conv'd xBC + raw dt -> (x heads (..., H, P), B (..., N), C (..., N),
    dt (..., H) f32, A (H,) f32)."""
    d_in, H, N, _ = dims(cfg)
    P = cfg.ssm.head_dim
    xBC = jax.nn.silu(xBC)
    x = xBC[..., :d_in]
    Bm = xBC[..., d_in: d_in + N]
    Cm = xBC[..., d_in + N:]
    dt = jax.nn.softplus(dt_raw.astype(jnp.float32) + params["dt_bias"])  # (..., H)
    A = -jnp.exp(params["A_log"])                                          # (H,)
    xh = x.reshape(*x.shape[:-1], H, P)     # B/C shared across heads (n_groups=1)
    return xh, Bm, Cm, dt, A


def ssd_chunked(x: jnp.ndarray, dt: jnp.ndarray, A: jnp.ndarray,
                Bm: jnp.ndarray, Cm: jnp.ndarray, chunk: int
                ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """The SSD scan over a whole sequence, exactly, in chunks of ``chunk``::

        S_t = exp(dt_t A) S_{t-1} + dt_t B_t (x) x_t      # S: (N, P) per head
        y_t = C_t S_t

    x: (B, S, H, P); dt: (B, S, H); A: (H,); Bm, Cm: (B, S, N), one group.
    Returns (y (B, S, H, P), final state (B, H, N, P)), float32, from a
    zero state. Inside a chunk the decay from position s to t >= s is the
    exponential of a difference of cumulative sums of dt A, never above 0,
    so no decay is clamped and none overflows; across chunks a short scan
    carries the state."""
    f32 = jnp.float32
    Bsz, S, H, P = x.shape
    N = Bm.shape[-1]
    Q = min(chunk, S)
    pad = -S % Q
    if pad:
        # dt = 0 past the end: decay 1 and no input, so padding is exact
        x, dt, Bm, Cm = (jnp.pad(a, [(0, 0), (0, pad)] + [(0, 0)] * (a.ndim - 2))
                         for a in (x, dt, Bm, Cm))
    nc = (S + pad) // Q
    dt = dt.astype(f32).reshape(Bsz, nc, Q, H)
    u = x.astype(f32).reshape(Bsz, nc, Q, H, P) * dt[..., None]
    Bc = Bm.astype(f32).reshape(Bsz, nc, Q, N)
    Cc = Cm.astype(f32).reshape(Bsz, nc, Q, N)
    acs = jnp.cumsum(jnp.moveaxis(dt * A, 3, 1), axis=-1)        # (B,H,nc,Q)

    # within a chunk: y_t += sum_{s<=t} (C_t . B_s) exp(acs_t - acs_s) u_s
    causal = jnp.tril(jnp.ones((Q, Q), bool))
    decay = jnp.exp(jnp.where(causal, acs[..., :, None] - acs[..., None, :],
                              -jnp.inf))                          # (B,H,nc,Q,Q)
    cb = jnp.einsum("bctn,bcsn->bcts", Cc, Bc)
    y = jnp.einsum("bcts,bhcts,bcshp->bcthp", cb, decay, u)

    # each chunk's input, carried to the chunk's end; then chunk to chunk
    to_end = jnp.exp(acs[..., -1:] - acs)                         # (B,H,nc,Q)
    states = jnp.einsum("bcsn,bhcs,bcshp->cbhnp", Bc, to_end, u)
    chunk_decay = jnp.moveaxis(jnp.exp(acs[..., -1]), 2, 0)       # (nc,B,H)

    def step(s_prev, xs):
        d_c, st_c = xs
        return d_c[..., None, None] * s_prev + st_c, s_prev

    final, entering = lax.scan(step, jnp.zeros((Bsz, H, N, P), f32),
                               (chunk_decay, states))
    y = y + jnp.einsum("bctn,cbhnp,bhct->bcthp", Cc, entering, jnp.exp(acs))
    return y.reshape(Bsz, nc * Q, H, P)[:, :S], final


def ssd_step(x: jnp.ndarray, dt: jnp.ndarray, A: jnp.ndarray,
             Bm: jnp.ndarray, Cm: jnp.ndarray, state: jnp.ndarray
             ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """One token of the same recurrence. x: (B, H, P); dt: (B, H); Bm, Cm:
    (B, N); state: (B, H, N, P) float32. Returns (y (B, H, P), state)."""
    f32 = jnp.float32
    dt = dt.astype(f32)
    u = x.astype(f32) * dt[..., None]
    state = (jnp.exp(dt * A)[..., None, None] * state
             + Bm.astype(f32)[:, None, :, None] * u[:, :, None, :])
    return jnp.einsum("bn,bhnp->bhp", Cm.astype(f32), state), state


def mamba2_forward(params: Params, cfg: ArchConfig, x: jnp.ndarray
                   ) -> Tuple[jnp.ndarray, Tuple[jnp.ndarray, jnp.ndarray]]:
    """Full-sequence forward. Returns (y, (conv_state, ssd_state)) so prefill
    can hand off to decode."""
    B, S, _ = x.shape
    d_in = dims(cfg)[0]
    Wc = cfg.ssm.conv_width
    z, xBC_raw, dt_raw = _split_proj(cfg, x @ params["in_proj"])
    xBC = _causal_depthwise_conv(xBC_raw, params["conv_w"], params["conv_b"])
    xh, Bm, Cm, dt, A = _ssd_inputs(params, cfg, xBC, dt_raw)
    y, state = ssd_chunked(xh, dt, A, Bm, Cm, CHUNK)
    y = y + params["D"][:, None] * xh                  # D*x skip
    y = y.reshape(B, S, d_in).astype(x.dtype)
    y = rmsnorm(params["norm"], y * jax.nn.silu(z), cfg.norm_eps)
    conv_state = xBC_raw[:, -(Wc - 1):, :]             # pre-activation carry
    return y @ params["out_proj"], (conv_state, state)


def mamba2_decode(params: Params, cfg: ArchConfig, x: jnp.ndarray,
                  cache: Tuple[jnp.ndarray, jnp.ndarray]
                  ) -> Tuple[jnp.ndarray, Tuple[jnp.ndarray, jnp.ndarray]]:
    """Single-token step. x: (B, 1, d); cache = (conv_state, ssd_state)."""
    conv_state, ssd_state = cache
    B = x.shape[0]
    d_in = dims(cfg)[0]
    z, xBC_raw, dt_raw = _split_proj(cfg, x @ params["in_proj"])
    xBC = _causal_depthwise_conv(xBC_raw, params["conv_w"], params["conv_b"],
                                 prev=conv_state)
    new_conv = jnp.concatenate([conv_state[:, 1:], xBC_raw], axis=1)
    xh, Bm, Cm, dt, A = _ssd_inputs(params, cfg, xBC, dt_raw)
    y, new_state = ssd_step(xh[:, 0], dt[:, 0], A, Bm[:, 0], Cm[:, 0],
                            ssd_state)
    y = y + params["D"][:, None] * xh[:, 0]
    y = y.reshape(B, 1, d_in).astype(x.dtype)
    y = rmsnorm(params["norm"], y * jax.nn.silu(z), cfg.norm_eps)
    return y @ params["out_proj"], (new_conv, new_state)


def init_cache(cfg: ArchConfig, batch: int, dtype) -> Tuple[jnp.ndarray, jnp.ndarray]:
    d_in, H, N, conv_ch = dims(cfg)
    P = cfg.ssm.head_dim
    return (jnp.zeros((batch, cfg.ssm.conv_width - 1, conv_ch), dtype),
            jnp.zeros((batch, H, N, P), jnp.float32))
