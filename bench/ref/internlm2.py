"""Plain reference of the InternLM2 decoder (arXiv:2403.17297).

Pre-norm decoder layers: RMSNorm, grouped-query attention with rotary
position embeddings (rotate-half, base ``rope_theta``) and a causal mask,
RMSNorm, SwiGLU feed-forward; a final RMSNorm and an untied output head.
No biases. The whole sequence runs in float32 at the highest matmul
precision, layer by layer, on the weights that ``bench/gen/weights.py``
makes from the seed (made again here: nothing of the program is used).

``quant=True`` is the control: every matmul of the layers and the head
takes its two operands rounded to float8 e4m3 with one scale per tensor,
the step below the bfloat16 the configuration serves in.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from bench.gen import weights

E4M3_MAX = 448.0


def _fp8(x: jax.Array) -> jax.Array:
    amax = jnp.max(jnp.abs(x))
    s = jnp.where(amax > 0, amax / E4M3_MAX, 1.0)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _mm(x, w, quant: bool):
    if quant:
        x, w = _fp8(x), _fp8(w)
    return jnp.matmul(x, w, precision=jax.lax.Precision.HIGHEST)


def _rms(x, g, eps: float):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g


def _rope(x, theta: float):
    """x: (B, S, H, D), positions 0..S-1."""
    S, D = x.shape[1], x.shape[-1]
    half = D // 2
    inv = 1.0 / theta ** (jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


@partial(jax.jit, static_argnames=("heads", "kv_heads", "eps", "theta",
                                   "quant"))
def _layer(x, p, *, heads, kv_heads, eps, theta, quant):
    B, S, d = x.shape
    hd = d // heads
    h = _rms(x, p["ln1"], eps)
    q = _rope(_mm(h, p["wq"], quant).reshape(B, S, heads, hd), theta)
    k = _rope(_mm(h, p["wk"], quant).reshape(B, S, kv_heads, hd), theta)
    v = _mm(h, p["wv"], quant).reshape(B, S, kv_heads, hd)
    k = jnp.repeat(k, heads // kv_heads, axis=2)
    v = jnp.repeat(v, heads // kv_heads, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                   precision=jax.lax.Precision.HIGHEST) * hd ** -0.5
    causal = jnp.tril(jnp.ones((S, S), bool))
    a = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
    o = jnp.einsum("bhqk,bkhd->bqhd", a, v,
                   precision=jax.lax.Precision.HIGHEST).reshape(B, S, d)
    x = x + _mm(o, p["wo"], quant)
    h = _rms(x, p["ln2"], eps)
    ff = jax.nn.silu(_mm(h, p["w_gate"], quant)) * _mm(h, p["w_up"], quant)
    return x + _mm(ff, p["w_down"], quant)


@partial(jax.jit, static_argnames=("eps", "quant"))
def _head(x, g, w, *, eps, quant):
    return _mm(_rms(x, g, eps), w, quant)


def logits(c: dict, seed: int, tokens: np.ndarray, start: int,
           quant: bool = False) -> jax.Array:
    """(B, S) token ids -> (B, S - start, vocab) float32 logits of the
    positions from ``start`` on."""
    params = weights.make_params(c, seed)
    f32 = lambda a: a.astype(jnp.float32)            # noqa: E731
    x = f32(params["embed"][jnp.asarray(tokens)])
    blocks = params["blocks"]
    for layer in range(c["num_layers"]):
        p = {"ln1": f32(blocks["ln1"]["scale"][layer]),
             "ln2": f32(blocks["ln2"]["scale"][layer]),
             **{k: f32(v[layer]) for k, v in blocks["attn"].items()},
             **{k: f32(v[layer]) for k, v in blocks["mlp"].items()}}
        x = _layer(x, p, heads=c["num_heads"], kv_heads=c["num_kv_heads"],
                   eps=c["norm_eps"], theta=c["rope_theta"], quant=quant)
    return _head(x[:, start:], f32(params["final_ln"]["scale"]),
                 f32(params["head"]), eps=c["norm_eps"], quant=quant)


def widest_gap(ref_logits: jax.Array, chosen: jax.Array) -> float:
    """Largest amount by which a chosen token's reference logit lies below
    the reference's best at its position. ``chosen``: (B, N) ids."""
    best = jnp.max(ref_logits, axis=-1)
    got = jnp.take_along_axis(ref_logits, chosen[..., None], axis=-1)[..., 0]
    return float(jnp.max(best - got))


def served_gap(c: dict, seed: int, prompt: np.ndarray, served: np.ndarray,
               control: bool = False) -> float:
    """Widest gap of the served tokens (B, N) after ``prompt`` (B, P).
    With ``control``, of the tokens the float8 forward puts first at the
    same positions instead."""
    P = prompt.shape[1]
    seq = np.concatenate([prompt, served[:, :-1]], axis=1)
    ref = logits(c, seed, seq, P - 1)
    chosen = jnp.asarray(served)
    if control:
        chosen = jnp.argmax(logits(c, seed, seq, P - 1, quant=True), -1)
    return widest_gap(ref, chosen)
