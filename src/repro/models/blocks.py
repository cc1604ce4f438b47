"""Core transformer building blocks: norms, rotary (incl. M-RoPE), GQA
attention (full / sliding-window / cached decode), SwiGLU MLP and the
sort-based MoE layer.

All blocks are pure functions over parameter pytrees (nested dicts of
``jnp.ndarray``). Matmuls run in the config dtype (bf16 on TPU, MXU f32
accumulation); softmax/norm statistics and the router always run in f32.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.configs.base import ArchConfig, MoEConfig

Params = Dict[str, Any]


# ---------------------------------------------------------------------------
# initialization helpers
# ---------------------------------------------------------------------------
def dense_init(rng, shape, dtype, scale: float = 1.0) -> jnp.ndarray:
    """Truncated-normal fan-in init (the LM-standard 1/sqrt(fan_in))."""
    fan_in = shape[0] if len(shape) <= 2 else shape[-2]
    std = scale / max(1.0, fan_in) ** 0.5
    return (jax.random.truncated_normal(rng, -3.0, 3.0, shape, jnp.float32)
            * std).astype(dtype)


# ---------------------------------------------------------------------------
# RMSNorm
# ---------------------------------------------------------------------------
def rmsnorm_init(d: int, dtype) -> Params:
    return {"scale": jnp.ones((d,), dtype=dtype)}


def rmsnorm(params: Params, x: jnp.ndarray, eps: float) -> jnp.ndarray:
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    y = xf * lax.rsqrt(var + eps)
    return (y * params["scale"].astype(jnp.float32)).astype(x.dtype)


# ---------------------------------------------------------------------------
# Rotary embeddings (standard + 3-axis M-RoPE)
# ---------------------------------------------------------------------------
def rope_angles(cfg: ArchConfig, positions: jnp.ndarray) -> jnp.ndarray:
    """Rotation angles per (batch, seq, d_head/2).

    ``positions``: (B, S) int32 for standard RoPE, or (3, B, S) for M-RoPE
    where axis 0 indexes the temporal/height/width position streams and
    ``cfg.mrope_sections`` partitions the frequency bands between them.
    """
    half = cfg.head_dim // 2
    inv_freq = 1.0 / (cfg.rope_theta ** (jnp.arange(0, half, dtype=jnp.float32) / half))
    if cfg.mrope:
        sections = cfg.mrope_sections
        assert sum(sections) == half, (sections, half)
        # frequency band i takes its position stream from axis sec(i)
        axis_of_band = jnp.repeat(jnp.arange(3), jnp.array(sections),
                                  total_repeat_length=half)
        pos = positions.astype(jnp.float32)              # (3, B, S)
        pos_per_band = jnp.take(pos, axis_of_band, axis=0)   # (half, B, S)
        return jnp.einsum("hbs,h->bsh", pos_per_band, inv_freq)
    pos = positions.astype(jnp.float32)                  # (B, S)
    return pos[..., None] * inv_freq                     # (B, S, half)


def apply_rope(x: jnp.ndarray, angles: jnp.ndarray) -> jnp.ndarray:
    """x: (B, S, H, D); angles: (B, S, D/2). Rotate-half convention."""
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    cos = jnp.cos(angles)[:, :, None, :].astype(x.dtype)
    sin = jnp.sin(angles)[:, :, None, :].astype(x.dtype)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


# ---------------------------------------------------------------------------
# GQA attention
# ---------------------------------------------------------------------------
def attention_init(rng, cfg: ArchConfig) -> Params:
    d, hd = cfg.d_model, cfg.head_dim
    ks = jax.random.split(rng, 4)
    p = {
        "wq": dense_init(ks[0], (d, cfg.num_heads * hd), cfg.dtype),
        "wk": dense_init(ks[1], (d, cfg.num_kv_heads * hd), cfg.dtype),
        "wv": dense_init(ks[2], (d, cfg.num_kv_heads * hd), cfg.dtype),
        "wo": dense_init(ks[3], (cfg.num_heads * hd, d), cfg.dtype,
                         scale=1.0 / (2 * cfg.num_layers) ** 0.5),
    }
    if cfg.qk_norm:
        p["q_norm"] = rmsnorm_init(hd, cfg.dtype)
        p["k_norm"] = rmsnorm_init(hd, cfg.dtype)
    return p


def _attn_scores_mask(q_pos, k_pos, window: int):
    """Causal (+ optional sliding window) mask. q_pos/k_pos: (Sq,), (Sk,)."""
    causal = q_pos[:, None] >= k_pos[None, :]
    if window > 0:
        causal &= q_pos[:, None] - k_pos[None, :] < window
    return causal


ATTN_CHUNK = 512


def _chunked_causal_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                              window: int, chunk: int = ATTN_CHUNK,
                              scale: float = 0.0) -> jnp.ndarray:
    """Memory-O(S·chunk) causal attention (online softmax over KV chunks).

    This is the XLA-path equivalent of the Pallas flash-attention kernel
    (``repro.kernels.flash_attention``): outer python loop over query chunks
    (static triangular structure — no wasted masked-out FLOPs), inner
    ``lax.scan`` over the causal KV range with running (m, l, acc). Each query
    chunk is rematerialized on backward so the S² probabilities never coexist.

    q: (B, S, Hkv, G, hd); k, v: (B, S, Hkv, hd) -> (B, S, Hkv, G, hd).
    ``scale`` 0 -> hd ** -0.5.
    """
    B, S, Hkv, G, hd = q.shape
    scale = scale or hd ** -0.5
    if S <= chunk:
        logits = jnp.einsum("bqhgd,bkhd->bhgqk", q, k).astype(jnp.float32) * scale
        pos = jnp.arange(S)
        mask = _attn_scores_mask(pos, pos, window)
        logits = jnp.where(mask[None, None, None], logits, -1e30)
        probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
        return jnp.einsum("bhgqk,bkhd->bqhgd", probs, v)

    assert S % chunk == 0, (S, chunk)
    nq = S // chunk
    kc = k.reshape(B, nq, chunk, Hkv, hd)
    vc = v.reshape(B, nq, chunk, Hkv, hd)
    pos = jnp.arange(chunk)

    def one_q_chunk(qi: int, q_blk: jnp.ndarray) -> jnp.ndarray:
        # causal range: kv chunks [lo, qi]; SWA trims lo to the window
        lo = 0 if window <= 0 else max(0, qi - (window + chunk - 1) // chunk)
        q_pos = qi * chunk + pos

        def kv_step(carry, xs):
            m, l, acc = carry
            k_blk, v_blk, kj = xs
            s = jnp.einsum("bqhgd,bkhd->bhgqk", q_blk, k_blk
                           ).astype(jnp.float32) * scale
            k_pos = kj * chunk + pos
            mask = _attn_scores_mask(q_pos, k_pos, window)
            s = jnp.where(mask[None, None, None], s, -1e30)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l_new = l * corr + jnp.sum(p, axis=-1)
            pv = jnp.einsum("bhgqk,bkhd->bhgqd", p.astype(v_blk.dtype), v_blk)
            acc_new = acc * corr[..., None].astype(acc.dtype) + pv
            return (m_new, l_new, acc_new), None

        m0 = jnp.full((B, Hkv, G, chunk), -jnp.inf, jnp.float32)
        l0 = jnp.zeros((B, Hkv, G, chunk), jnp.float32)
        a0 = jnp.zeros((B, Hkv, G, chunk, hd), q.dtype)
        ks_ = jnp.moveaxis(kc[:, lo: qi + 1], 1, 0)
        vs_ = jnp.moveaxis(vc[:, lo: qi + 1], 1, 0)
        (m, l, acc), _ = lax.scan(kv_step, (m0, l0, a0),
                                  (ks_, vs_, jnp.arange(lo, qi + 1)))
        out = acc / jnp.maximum(l, 1e-30)[..., None].astype(acc.dtype)
        return jnp.moveaxis(out, 3, 1)                 # (B, chunk, Hkv, G, hd)

    qcs = q.reshape(B, nq, chunk, Hkv, G, hd)
    blocks = [jax.checkpoint(one_q_chunk, static_argnums=0)(i, qcs[:, i])
              for i in range(nq)]
    return jnp.concatenate(blocks, axis=1)


def multihead_attention(
    params: Params,
    cfg: ArchConfig,
    x: jnp.ndarray,                         # (B, S, d)
    angles: jnp.ndarray,                    # (B, S, hd/2)
    *,
    kv_cache: Optional[Tuple[jnp.ndarray, jnp.ndarray]] = None,
    cache_pos: Optional[jnp.ndarray] = None,     # scalar: tokens already cached
) -> Tuple[jnp.ndarray, Optional[Tuple[jnp.ndarray, jnp.ndarray]]]:
    """Full-sequence (train/prefill) or single-token cached (decode) attention.

    Decode: ``x`` is (B, 1, d); ``kv_cache`` = (k, v) each (B, W, Hkv, hd)
    where W is the cache window (ring-indexed when SWA is on). Returns the
    updated cache.
    """
    B, S, _ = x.shape
    H, Hkv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    q = (x @ params["wq"]).reshape(B, S, H, hd)
    k = (x @ params["wk"]).reshape(B, S, Hkv, hd)
    v = (x @ params["wv"]).reshape(B, S, Hkv, hd)
    if cfg.qk_norm:
        q = rmsnorm(params["q_norm"], q, cfg.norm_eps)
        k = rmsnorm(params["k_norm"], k, cfg.norm_eps)
    if cfg.use_rope:
        q = apply_rope(q, angles)
        k = apply_rope(k, angles)
    scale = cfg.attn_scale or hd ** -0.5

    if kv_cache is None:
        # ---- train / prefill: chunked causal (+SWA) attention ---------------
        g = H // Hkv
        qh = q.reshape(B, S, Hkv, g, hd)
        out = _chunked_causal_attention(qh, k, v, cfg.sliding_window,
                                        chunk=min(cfg.attn_chunk, S),
                                        scale=cfg.attn_scale)
        out = out.reshape(B, S, H * hd)
        new_cache = (k, v)
    else:
        # ---- decode: append one token to the (ring) cache ------------------
        ck, cv = kv_cache
        W = ck.shape[1]
        slot = (cache_pos % W).astype(jnp.int32)
        ck = lax.dynamic_update_slice(ck, k.astype(ck.dtype), (0, slot, 0, 0))
        cv = lax.dynamic_update_slice(cv, v.astype(cv.dtype), (0, slot, 0, 0))
        g = H // Hkv
        qh = q.reshape(B, 1, Hkv, g, hd)
        logits = jnp.einsum("bqhgd,bkhd->bhgqk", qh, ck).astype(jnp.float32) * scale
        # valid cache entries: absolute position of slot i in the ring
        idx = jnp.arange(W)
        n_seen = cache_pos + 1                       # tokens seen incl. current
        if cfg.sliding_window > 0:
            abs_pos = jnp.where(idx <= slot, cache_pos - slot + idx,
                                cache_pos - slot + idx - W)
            valid = (abs_pos >= 0) & (abs_pos > cache_pos - cfg.sliding_window)
        else:
            valid = idx < n_seen
        logits = jnp.where(valid[None, None, None, None, :], logits, -1e30)
        probs = jax.nn.softmax(logits, axis=-1).astype(x.dtype)
        out = jnp.einsum("bhgqk,bkhd->bqhgd", probs, cv).reshape(B, 1, H * hd)
        new_cache = (ck, cv)

    return out @ params["wo"], new_cache


# ---------------------------------------------------------------------------
# SwiGLU MLP
# ---------------------------------------------------------------------------
def mlp_init(rng, cfg: ArchConfig, d_ff: Optional[int] = None) -> Params:
    d, f = cfg.d_model, d_ff or cfg.d_ff
    ks = jax.random.split(rng, 3)
    p = {
        "w_up": dense_init(ks[1], (d, f), cfg.dtype),
        "w_down": dense_init(ks[2], (f, d), cfg.dtype,
                             scale=1.0 / (2 * cfg.num_layers) ** 0.5),
    }
    if cfg.gated_mlp:
        p["w_gate"] = dense_init(ks[0], (d, f), cfg.dtype)
    return p


def mlp(params: Params, x: jnp.ndarray) -> jnp.ndarray:
    if "w_gate" in params:             # SwiGLU
        return (jax.nn.silu(x @ params["w_gate"])
                * (x @ params["w_up"])) @ params["w_down"]
    return jax.nn.gelu(x @ params["w_up"]) @ params["w_down"]


# ---------------------------------------------------------------------------
# Mixture of Experts (sort-based dispatch; EP-shardable over the expert axis)
# ---------------------------------------------------------------------------
def moe_init(rng, cfg: ArchConfig) -> Params:
    m = cfg.moe
    d, f, E = cfg.d_model, m.d_ff_expert, m.num_experts
    ks = jax.random.split(rng, 5)
    p = {
        "router": dense_init(ks[0], (d, E), jnp.float32),
        "w_gate": dense_init(ks[1], (E, d, f), cfg.dtype),
        "w_up": dense_init(ks[2], (E, d, f), cfg.dtype),
        "w_down": dense_init(ks[3], (E, f, d), cfg.dtype,
                             scale=1.0 / (2 * cfg.num_layers) ** 0.5),
    }
    if m.num_shared_experts:
        p["shared"] = mlp_init(ks[4], cfg, d_ff=m.num_shared_experts * f)
    return p


def moe_capacity(m: MoEConfig, num_tokens: int) -> int:
    cap = int(num_tokens * m.top_k * m.capacity_factor / m.num_experts) + 1
    return max(8, -(-cap // 8) * 8)          # round up to 8 for TPU tiling


def _route(params: Params, m: MoEConfig, xt: jnp.ndarray,
           logits: Optional[jnp.ndarray] = None):
    """Router: (T, d) -> (gate (T,K) f32, expert (T,K) i32, aux loss terms).
    ``logits`` may be precomputed (EP path: expert-sharded router matmul +
    logit all-gather)."""
    T, E = xt.shape[0], m.num_experts
    if logits is None:
        logits = xt.astype(jnp.float32) @ params["router"]        # (T, E)
    probs = jax.nn.softmax(logits, axis=-1)
    gate, expert = lax.top_k(probs, m.top_k)
    gate = gate / jnp.sum(gate, axis=-1, keepdims=True)           # renormalize
    # load-balance terms (Switch): sums so they psum across shards cleanly
    p_sum = jnp.sum(probs, axis=0)                                # (E,)
    c_sum = jnp.zeros((E,), jnp.float32).at[expert.reshape(-1)].add(1.0)
    return gate, expert.astype(jnp.int32), p_sum, c_sum


def _fill_buffer(xt: jnp.ndarray, expert: jnp.ndarray, E: int, C: int):
    """Sort-based dispatch: rank tokens within their expert (stable argsort),
    scatter into an (E, C, d) capacity buffer (overflow drops, Switch-style).
    O(Tk log Tk) with no (T, E, C) one-hot. Returns (buffer, slot (T*K,))."""
    TK = expert.size
    d = xt.shape[-1]
    K = TK // xt.shape[0]
    flat_e = expert.reshape(-1)
    order = jnp.argsort(flat_e, stable=True)
    counts = jnp.zeros((E,), jnp.int32).at[flat_e].add(1)
    starts = jnp.cumsum(counts) - counts
    pos_sorted = jnp.arange(TK, dtype=jnp.int32) - starts[flat_e[order]]
    pos = jnp.zeros((TK,), jnp.int32).at[order].set(pos_sorted)
    slot = jnp.where(pos < C, flat_e * C + pos, E * C)            # OOB -> drop
    x_rep = jnp.repeat(xt, K, axis=0)
    buf = jnp.zeros((E * C + 1, d), xt.dtype).at[slot].set(x_rep, mode="drop")
    return buf[: E * C].reshape(E, C, d), slot


def _expert_swiglu(h: jnp.ndarray, wg, wu, wd) -> jnp.ndarray:
    a = jax.nn.silu(jnp.einsum("ecd,edf->ecf", h, wg))
    b = jnp.einsum("ecd,edf->ecf", h, wu)
    return jnp.einsum("ecf,efd->ecd", a * b, wd)


def _combine(y: jnp.ndarray, slot: jnp.ndarray, gate: jnp.ndarray,
             T: int) -> jnp.ndarray:
    E_C, d = y.shape[0] * y.shape[1], y.shape[-1]
    K = slot.size // T
    y_flat = jnp.concatenate([y.reshape(E_C, d),
                              jnp.zeros((1, d), y.dtype)], axis=0)
    gathered = y_flat[jnp.minimum(slot, E_C)]                     # (T*K, d)
    weighted = gathered * gate.reshape(-1, 1).astype(y.dtype)
    return jnp.sum(weighted.reshape(T, K, d), axis=1)


def moe_ffn(params: Params, cfg: ArchConfig, x: jnp.ndarray
            ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Top-k expert layer. x: (B, S, d) -> (out, aux_loss).

    With an active distribution context this is the explicit expert-parallel
    path (shard_map + all-to-all; see ``_moe_ffn_sharded``) — GSPMD cannot
    partition the data-dependent dispatch gathers without replicating them
    (measured: 51 TB/step collectives on kimi-k2, EXPERIMENTS.md §Perf).
    Without a mesh it is the same math locally.
    """
    from repro.models import dist
    ctx = dist.current()
    if ctx is not None:
        return _moe_ffn_sharded(params, cfg, x, ctx)

    m = cfg.moe
    B, S, d = x.shape
    T, E = B * S, m.num_experts
    xt = x.reshape(T, d)
    gate, expert, p_sum, c_sum = _route(params, m, xt)
    aux = (E * jnp.sum((p_sum / T) * (c_sum / (T * m.top_k)))
           * m.aux_loss_weight)
    C = moe_capacity(m, T)
    buf, slot = _fill_buffer(xt, expert, E, C)
    y = _expert_swiglu(buf, params["w_gate"], params["w_up"],
                       params["w_down"])
    out = _combine(y, slot, gate, T)
    if m.num_shared_experts:
        out = out + mlp(params["shared"], xt)
    return out.reshape(B, S, d), aux


def _moe_ffn_sharded(params: Params, cfg: ArchConfig, x: jnp.ndarray,
                     ctx) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Expert-parallel MoE: tokens sharded over (batch x model) axes, experts
    over 'model', FSDP ZeRO-3 expert weights over 'data'.

    Per shard: route local tokens -> capacity buffer (E, C, d) -> all-to-all
    over 'model' (tokens travel to their experts' owners) -> expert SwiGLU ->
    all-to-all back -> weighted combine. With ``expert_inner_shard`` the
    expert FFN inner dim is 'data'-sharded (Megatron row/col) and the ZeRO-3
    all-gather is replaced by a psum of the expert outputs (§Perf iteration).
    """
    from jax.sharding import PartitionSpec as P

    m = cfg.moe
    mesh, bd, tp = ctx.mesh, ctx.batch_axes, ctx.tp_axis
    tp_n = mesh.shape[tp]
    nb = int(np.prod([mesh.shape[a] for a in bd]))
    B, S, d = x.shape
    E, K = m.num_experts, m.top_k

    B_loc = B // nb if B % nb == 0 else B
    x_bspec = bd if B % nb == 0 else None
    seq_sharded = ctx.seq_shard and S % tp_n == 0 and S // tp_n > 0
    S_loc = S // tp_n if seq_sharded else S
    T_loc = B_loc * S_loc
    if seq_sharded:
        sp_mode = "seq"
        T_tp = T_loc
    elif T_loc % tp_n == 0:
        sp_mode = "slice"
        T_tp = T_loc // tp_n
    else:
        sp_mode = "dup"                 # tiny-token decode: dup work, exact
        T_tp = T_loc
    C = moe_capacity(m, T_tp)
    E_loc = E // tp_n

    # NOTE (§Perf, refuted): 'expert_inner_shard' (Megatron row/col inside
    # each expert, f over 'data') is INVALID on this mesh — 'data' is also
    # the token-shard axis, so the output psum over 'data' would mix
    # different tokens' partial results. A correct version needs either a
    # dedicated mesh axis for the f-split or a token all-gather whose
    # traffic exceeds the ZeRO-3 weight gather it replaces. ZeRO-3 it is.
    zero3 = True
    w_specs = (P(tp, "data", None), P(tp, "data", None),
               P(tp, None, "data"))

    def body(xl, router, wg, wu, wd):
        Bq, Sq, _ = xl.shape
        xt = xl.reshape(Bq * Sq, d)
        if sp_mode == "slice":
            r = lax.axis_index(tp)
            xt = lax.dynamic_slice_in_dim(xt, r * T_tp, T_tp, axis=0)
        # router is expert-sharded (d, E/tp): local matmul, tiny logit gather
        loc_logits = xt.astype(jnp.float32) @ router          # (T_tp, E/tp)
        logits = lax.all_gather(loc_logits, tp, axis=1, tiled=True)
        gate, expert, p_sum, c_sum = _route({}, m, xt, logits=logits)
        T_tot = T_tp * (1 if sp_mode == "dup" else tp_n) * nb
        p_tot = lax.psum(lax.psum(p_sum, bd), tp) if sp_mode != "dup" \
            else lax.psum(p_sum, bd)
        c_tot = lax.psum(lax.psum(c_sum, bd), tp) if sp_mode != "dup" \
            else lax.psum(c_sum, bd)
        aux = (E * jnp.sum((p_tot / T_tot) * (c_tot / (T_tot * K)))
               * m.aux_loss_weight)

        buf, slot = _fill_buffer(xt, expert, E, C)        # (E, C, d)
        recv = lax.all_to_all(buf, tp, split_axis=0, concat_axis=1,
                              tiled=True)                 # (E_loc, C*tp, d)
        if zero3:
            wg_f = lax.all_gather(wg, "data", axis=1, tiled=True)
            wu_f = lax.all_gather(wu, "data", axis=1, tiled=True)
            wd_f = lax.all_gather(wd, "data", axis=2, tiled=True)
            h = _expert_swiglu(recv, wg_f, wu_f, wd_f)
        else:
            # inner-sharded: contraction over local f-slice, psum outputs
            h = _expert_swiglu(recv, wg, wu, wd)
            h = lax.psum(h, "data")
        back = lax.all_to_all(h, tp, split_axis=1, concat_axis=0,
                              tiled=True)                 # (E, C, d)
        y = _combine(back, slot, gate, T_tp)              # (T_tp, d)
        if sp_mode == "slice":
            y = lax.all_gather(y, tp, axis=0, tiled=True)
        return y.reshape(Bq, Sq, d).astype(xl.dtype), aux

    x_spec = P(x_bspec, tp if seq_sharded else None, None)
    out, aux = jax.shard_map(
        body, mesh=mesh,
        in_specs=(x_spec, P(None, tp)) + w_specs,
        out_specs=(x_spec, P()),
        check_vma=False,
    )(x, params["router"], params["w_gate"], params["w_up"],
      params["w_down"])

    if m.num_shared_experts:
        out = out + mlp(params["shared"], x.reshape(B * S, d)
                        ).reshape(B, S, d)
    return out, aux
