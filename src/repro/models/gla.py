"""Chunked gated linear attention — the scan core of RWKV6 (Finch), and in
SSD mode of the ``ssm_scan`` kernel's oracle. (Mamba2 runs its own exact
scan, ``models.mamba2.ssd_chunked``: it needs no decay clamp.)

Both modes are linear recurrences over an outer-product state::

    S_t = diag(w_t) S_{t-1} + k_t v_t^T          # S: (Dk, Dv) per head
    y_t = q_t S_t            (+ bonus (q_t . u . k_t) v_t   for RWKV)

We evaluate them chunk-parallel (chunk Q tokens): the intra-chunk term is a
masked (Q, Q) matmul — MXU-shaped — and the inter-chunk term is a short scan
carrying S. This is the standard SSD/GLA decomposition; the Pallas kernel in
``repro.kernels.ssm_scan`` implements the identical algorithm with explicit
VMEM tiling, and ``repro.kernels.ref`` re-exports this function as its oracle.

Numerics: all decay math in f32 log-space. Per-step log-decay is clamped to
[-LOG_DECAY_CLAMP, 0]; within a chunk, exponents are shifted by the mid-chunk
cumulative decay so both factors of the factored pairwise term stay inside
f32 range (documented trade-off in DESIGN.md §5 — a per-step decay below
exp(-4) zeroes state within a couple of tokens anyway).
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

LOG_DECAY_CLAMP = 4.0
CHUNK = 32


def clamp_log_decay(logw: jnp.ndarray) -> jnp.ndarray:
    return jnp.clip(logw, -LOG_DECAY_CLAMP, 0.0)


def gla_chunked(
    q: jnp.ndarray,            # (B, H, S, Dk)
    k: jnp.ndarray,            # (B, H, S, Dk)
    v: jnp.ndarray,            # (B, H, S, Dv)
    log_decay: jnp.ndarray,    # (B, H, S, Dk) per-channel log decay (<= 0)
    *,
    bonus: Optional[jnp.ndarray] = None,   # (H, Dk): RWKV 'u'; None -> SSD mode
    initial_state: Optional[jnp.ndarray] = None,   # (B, H, Dk, Dv)
    chunk: int = CHUNK,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Returns (y: (B,H,S,Dv), final_state: (B,H,Dk,Dv)).

    ``bonus is None`` selects SSD semantics (current token enters the state
    *before* readout: mask j<=t, no bonus). Otherwise RWKV semantics (readout
    sees only the past: mask j<t, current token contributes via ``bonus``).
    """
    from repro.models import dist
    q, k, v, log_decay = (dist.constrain_heads(a)
                          for a in (q, k, v, log_decay))
    B, H, S, Dk = q.shape
    Dv = v.shape[-1]
    S_orig = S
    if S % chunk:
        # zero-pad to a chunk multiple: k=v=0 adds nothing to the state and
        # log_decay=0 leaves it untouched, so padding is exact.
        pad = chunk - S % chunk
        padw = ((0, 0), (0, 0), (0, pad), (0, 0))
        q, k, v = (jnp.pad(a, padw) for a in (q, k, v))
        log_decay = jnp.pad(log_decay, padw)
        S += pad
    nc, Q = S // chunk, chunk
    f32 = jnp.float32

    qc = q.reshape(B, H, nc, Q, Dk).astype(f32)
    kc = k.reshape(B, H, nc, Q, Dk).astype(f32)
    vc = v.reshape(B, H, nc, Q, Dv).astype(f32)
    lw = clamp_log_decay(log_decay.reshape(B, H, nc, Q, Dk).astype(f32))

    ssd = bonus is None
    L = jnp.cumsum(lw, axis=3)                       # inclusive cumsum
    L_q = L if ssd else L - lw                       # RWKV reads pre-decay
    L_total = L[:, :, :, -1, :]                      # (B,H,nc,Dk)
    shift = L[:, :, :, Q // 2, :][:, :, :, None, :]  # mid-chunk exponent shift

    q_in = qc * jnp.exp(L_q - shift)                 # (B,H,nc,Q,Dk)
    k_in = kc * jnp.exp(shift - L)
    scores = jnp.einsum("bhcqd,bhckd->bhcqk", q_in, k_in)
    pos = jnp.arange(Q)
    mask = pos[:, None] >= pos[None, :] if ssd else pos[:, None] > pos[None, :]
    scores = jnp.where(mask[None, None, None], scores, 0.0)
    if not ssd:
        diag = jnp.einsum("bhcqd,hd,bhcqd->bhcq", qc, bonus.astype(f32), kc)
        scores = scores + diag[..., None] * jnp.eye(Q)[None, None, None]
    y_intra = jnp.einsum("bhcqk,bhckv->bhcqv", scores, vc)

    # ---- inter-chunk: scan the per-chunk state summaries --------------------
    k_out = kc * jnp.exp(L_total[:, :, :, None, :] - L)   # weight to chunk end
    chunk_states = jnp.einsum("bhcqd,bhcqv->bhcdv", k_out, vc)
    decay_c = jnp.exp(L_total)                             # (B,H,nc,Dk)

    def step(S_prev, xs):
        d_c, st_c = xs                                     # (B,H,Dk), (B,H,Dk,Dv)
        S_new = d_c[..., None] * S_prev + st_c
        return S_new, S_prev                               # emit state *entering* chunk

    S0 = (jnp.zeros((B, H, Dk, Dv), f32) if initial_state is None
          else initial_state.astype(f32))
    d_sc = jnp.moveaxis(decay_c, 2, 0)                     # (nc,B,H,Dk)
    st_sc = jnp.moveaxis(chunk_states, 2, 0)               # (nc,B,H,Dk,Dv)
    final_state, entering = jax.lax.scan(step, S0, (d_sc, st_sc))
    entering = jnp.moveaxis(entering, 0, 2)                # (B,H,nc,Dk,Dv)

    q_inter = qc * jnp.exp(L_q)
    y_inter = jnp.einsum("bhcqd,bhcdv->bhcqv", q_inter, entering)

    y = (y_intra + y_inter).reshape(B, H, S, Dv)[:, :, :S_orig]
    return y, final_state


def gla_decode_step(
    q: jnp.ndarray,            # (B, H, Dk)
    k: jnp.ndarray,            # (B, H, Dk)
    v: jnp.ndarray,            # (B, H, Dv)
    log_decay: jnp.ndarray,    # (B, H, Dk)
    state: jnp.ndarray,        # (B, H, Dk, Dv)
    *,
    bonus: Optional[jnp.ndarray] = None,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Single-token exact recurrence (decode path). Matches gla_chunked."""
    f32 = jnp.float32
    qf, kf, vf = q.astype(f32), k.astype(f32), v.astype(f32)
    w = jnp.exp(clamp_log_decay(log_decay.astype(f32)))
    kv = kf[..., :, None] * vf[..., None, :]               # (B,H,Dk,Dv)
    if bonus is None:                                      # SSD: state first
        state = w[..., None] * state + kv
        y = jnp.einsum("bhd,bhdv->bhv", qf, state)
    else:                                                  # RWKV: read, bonus, then update
        y = jnp.einsum("bhd,bhdv->bhv", qf, state)
        y = y + jnp.einsum("bhd,hd,bhd->bh", qf, bonus.astype(f32), kf)[..., None] * vf
        state = w[..., None] * state + kv
    return y, state
