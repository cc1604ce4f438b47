"""Plain reference of the granite 4.0-H hybrid decoder (``model_type``
granitemoehybrid), from its ``config.json`` keys.

Per layer ``l`` of ``layer_types``, with r = ``residual_multiplier``::

    x0 = embedding_multiplier * E[tok]
    h  = x + r * Mixer_l(RMSNorm_in(x))
    x' = h + r * W_down(silu(u W_gate) * (u W_up)),   u = RMSNorm_post(h)
    logits = RMSNorm_f(x) E^T / logits_scaling           (tied head)

- attention: grouped-query heads, no position embedding, full causal
  softmax(attention_multiplier * q k^T) v, then W_o;
- mamba: [z | xBC | dt] = u W_in; xBC = silu(depthwise causal conv of
  ``mamba_d_conv`` taps + bias); x | B | C split from xBC (one group);
  dt = softplus(dt + dt_bias); A = -exp(A_log); per head h
  S_t = exp(dt_t,h A_h) S_t-1 + dt_t,h B_t (x) x_t,h and
  y_t,h = C_t S_t + D_h x_t,h; out = RMSNorm(y * silu(z)) W_out, the norm
  over all of the inner width.

No biases but the conv's. Everything runs in float32 at the highest matmul
precision: the Mamba layers as a sequential ``lax.scan`` over time, attention
without a cache, in blocks of queries, a few requests at a time, on the
weights that ``bench/gen/hybrid_weights.py`` makes from the seed (made again
here: nothing of the program is used).

Departures from the published model: the weights are random; the depth is
the configuration's (its ``layer_types``); and the published Mamba kernel
scans in chunks of ``mamba_chunk_size``, which changes only the rounding of
the same recurrence, computed here step by step.

``quant=True`` is the control: every matmul with a weight (the projections,
the MLP and the head) takes its two operands rounded to float8 e4m3 with one
scale per tensor, the step below the bfloat16 the configuration serves in.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from bench.gen import hybrid_weights as gen
from bench.ref.internlm2 import _mm, _rms, widest_gap

HIGHEST = jax.lax.Precision.HIGHEST
#: requests computed together, and queries per attention block
REQUESTS, QUERY_BLOCK = 4, 256


def _mlp(x, p, *, r, eps, quant):
    u = _rms(x, p["ln2"], eps)
    ff = jax.nn.silu(_mm(u, p["w_gate"], quant)) * _mm(u, p["w_up"], quant)
    return x + r * _mm(ff, p["w_down"], quant)


@partial(jax.jit, static_argnames=("heads", "kv_heads", "scale", "r", "eps",
                                   "quant"))
def _attn_layer(x, p, *, heads, kv_heads, scale, r, eps, quant):
    B, S, d = x.shape
    hd = d // heads
    u = _rms(x, p["ln1"], eps)
    q = _mm(u, p["wq"], quant).reshape(B, S, heads, hd)
    k = _mm(u, p["wk"], quant).reshape(B, S, kv_heads, hd)
    v = _mm(u, p["wv"], quant).reshape(B, S, kv_heads, hd)
    k = jnp.repeat(k, heads // kv_heads, axis=2)
    v = jnp.repeat(v, heads // kv_heads, axis=2)
    out = []
    for lo in range(0, S, QUERY_BLOCK):
        hi = min(lo + QUERY_BLOCK, S)
        s = jnp.einsum("bqhd,bkhd->bhqk", q[:, lo:hi], k[:, :hi],
                       precision=HIGHEST) * scale
        causal = np.arange(lo, hi)[:, None] >= np.arange(hi)[None, :]
        a = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
        out.append(jnp.einsum("bhqk,bkhd->bqhd", a, v[:, :hi],
                              precision=HIGHEST))
    o = jnp.concatenate(out, axis=1).reshape(B, S, heads * hd)
    return _mlp(x + r * _mm(o, p["wo"], quant), p, r=r, eps=eps, quant=quant)


@partial(jax.jit, static_argnames=("heads", "head_dim", "d_state", "r", "eps",
                                   "quant"))
def _mamba_layer(x, p, *, heads, head_dim, d_state, r, eps, quant):
    B, S, _ = x.shape
    d_in = heads * head_dim
    u = _rms(x, p["ln1"], eps)
    proj = _mm(u, p["in_proj"], quant)
    z = proj[..., :d_in]
    xbc = proj[..., d_in: 2 * d_in + 2 * d_state]
    dt = proj[..., 2 * d_in + 2 * d_state:]
    taps = p["conv_w"].shape[0]
    xp = jnp.pad(xbc, ((0, 0), (taps - 1, 0), (0, 0)))
    xbc = jax.nn.silu(sum(xp[:, i: i + S] * p["conv_w"][i]
                          for i in range(taps)) + p["conv_b"])
    xs = xbc[..., :d_in].reshape(B, S, heads, head_dim)
    Bm = xbc[..., d_in: d_in + d_state]
    Cm = xbc[..., d_in + d_state:]
    dt = jax.nn.softplus(dt + p["dt_bias"])                # (B, S, H)
    A = -jnp.exp(p["A_log"])

    def step(state, inp):                                  # state (B,H,N,P)
        x_t, dt_t, b_t, c_t = inp
        state = (jnp.exp(dt_t * A)[..., None, None] * state
                 + dt_t[..., None, None] * b_t[:, None, :, None]
                 * x_t[:, :, None, :])
        return state, jnp.einsum("bn,bhnp->bhp", c_t, state,
                                 precision=HIGHEST)

    time_major = lambda a: jnp.moveaxis(a, 1, 0)           # noqa: E731
    _, y = jax.lax.scan(step, jnp.zeros((B, heads, d_state, head_dim)),
                        tuple(map(time_major, (xs, dt, Bm, Cm))))
    y = time_major(y) + p["D"][:, None] * xs
    y = _rms(y.reshape(B, S, d_in) * jax.nn.silu(z), p["norm"], eps)
    return _mlp(x + r * _mm(y, p["out_proj"], quant), p, r=r, eps=eps,
                quant=quant)


@partial(jax.jit, static_argnames=("eps", "scaling", "quant"))
def _head(x, g, emb, *, eps, scaling, quant):
    return _mm(_rms(x, g, eps), emb.T, quant) / scaling


def _layer_params(params: dict, kind: str, j: int) -> dict:
    """Layer ``j`` of the ``kind`` stack, flat and in float32."""
    f32 = lambda a: a[j].astype(jnp.float32)              # noqa: E731
    stack = params[kind]
    mixer = stack["attn" if kind == "attn" else "mixer"]
    p = {"ln1": f32(stack["ln1"]["scale"]), "ln2": f32(stack["ln2"]["scale"]),
         **{k: f32(v) for k, v in stack["mlp"].items()},
         **{k: f32(v) for k, v in mixer.items() if k != "norm"}}
    if kind != "attn":
        p["norm"] = f32(mixer["norm"]["scale"])
    return p


def logits(c: dict, params: dict, tokens: np.ndarray, start: int,
           quant: bool = False) -> jax.Array:
    """(B, S) token ids -> (B, S - start, vocab) float32 logits of the
    positions from ``start`` on."""
    eps, r = c["rms_norm_eps"], c["residual_multiplier"]
    emb = params["embed"].astype(jnp.float32)
    x = c["embedding_multiplier"] * emb[jnp.asarray(tokens)]
    seen = {"attn": 0, "mamba_mlp": 0}
    for kind in gen.kinds(c):
        p = _layer_params(params, kind, seen[kind])
        seen[kind] += 1
        if kind == "attn":
            x = _attn_layer(x, p, heads=c["num_attention_heads"],
                            kv_heads=c["num_key_value_heads"],
                            scale=c["attention_multiplier"], r=r, eps=eps,
                            quant=quant)
        else:
            x = _mamba_layer(x, p, heads=c["mamba_n_heads"],
                             head_dim=c["mamba_d_head"],
                             d_state=c["mamba_d_state"], r=r, eps=eps,
                             quant=quant)
    return _head(x[:, start:], params["final_ln"]["scale"].astype(jnp.float32),
                 emb, eps=eps, scaling=c["logits_scaling"], quant=quant)


def served_gap(c: dict, seed: int, prompt: np.ndarray, served: np.ndarray,
               control: bool = False) -> float:
    """Widest gap of the served tokens (B, N) after ``prompt`` (B, P), as
    ``bench/ref/internlm2.served_gap`` reads it, ``REQUESTS`` requests at a
    time. With ``control``, of the tokens the float8 forward puts first at
    the same positions instead."""
    params = gen.make_params(c, seed)
    P = prompt.shape[1]
    seq = np.concatenate([prompt, served[:, :-1]], axis=1)
    gaps = []
    for lo in range(0, seq.shape[0], REQUESTS):
        rows = slice(lo, lo + REQUESTS)
        ref = logits(c, params, seq[rows], P - 1)
        chosen = jnp.asarray(served[rows])
        if control:
            chosen = jnp.argmax(logits(c, params, seq[rows], P - 1,
                                       quant=True), -1)
        gaps.append(widest_gap(ref, chosen))
    return max(gaps)
