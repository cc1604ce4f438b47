"""Full language model: embedding -> scanned block stack -> head, for every
assigned architecture family.

Wiring modes (chosen from the config's block pattern):

* ``uniform``       — all layers one kind; single ``lax.scan`` over stacked params.
* ``hybrid_shared`` — zamba2: groups of Mamba2 layers with a *shared-weight*
                      attention block applied after each group.
* ``prefix_dense``  — kimi-k2: a leading dense layer, then a scanned MoE stack.
* ``interleaved``   — granite: layers of two kinds ('mamba_mlp', 'attn') in a
                      per-layer order, each with its own weights. Each kind's
                      layers are stacked in one pytree; each maximal run of
                      same-kind layers is one ``lax.scan`` over its stretch of
                      that stack, with a stacked cache of its own (a list per
                      kind, in depth order), so HLO size is O(runs).

Params are nested dicts; layer stacks are stacked pytrees scanned with
``jax.lax.scan`` so HLO size is O(1) in depth. ``remat='block'`` checkpoints
each scanned body. ``constrain`` is an optional residual-stream sharding hook
installed by the train-step builder (Megatron-style sequence sharding).
"""
from __future__ import annotations

from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.configs.base import ArchConfig
from repro.models import blocks as B
from repro.models import mamba2, rwkv6
from repro.spans import scope

Params = Dict[str, Any]
Batch = Dict[str, jnp.ndarray]
Identity = lambda x: x  # noqa: E731


# ---------------------------------------------------------------------------
# wiring
# ---------------------------------------------------------------------------
def wiring_mode(cfg: ArchConfig) -> str:
    if "shared_attn" in cfg.block_pattern:
        return "hybrid_shared"
    if cfg.first_k_dense > 0:
        return "prefix_dense"
    if set(cfg.block_pattern) == {"mamba_mlp", "attn"}:
        return "interleaved"
    assert len(set(cfg.block_pattern)) == 1, cfg.block_pattern
    return "uniform"


def _runs(cfg: ArchConfig) -> List[Tuple[str, int, int]]:
    """interleaved: (kind, first index in the kind's stack, length) of each
    maximal run of same-kind layers, in depth order."""
    runs: List[Tuple[str, int, int]] = []
    seen: Dict[str, int] = {}
    for kind in cfg.pattern_for_depth():
        if runs and runs[-1][0] == kind:
            runs[-1] = (kind, runs[-1][1], runs[-1][2] + 1)
        else:
            runs.append((kind, seen.get(kind, 0), 1))
        seen[kind] = seen.get(kind, 0) + 1
    return runs


#: device-side scope of each interleaved layer kind
_SCOPE = {"mamba_mlp": "mamba", "attn": "attn"}


def _layer(stack, i):
    """Layer ``i`` (traced) of a stacked pytree."""
    return jax.tree.map(lambda a: a[i], stack)


def _group_shape(cfg: ArchConfig) -> Tuple[int, int]:
    """hybrid_shared: (n_groups, mamba_per_group)."""
    per = sum(1 for k in cfg.block_pattern if k == "mamba")
    n_groups = cfg.num_layers // len(cfg.block_pattern)
    return n_groups, per


# ---------------------------------------------------------------------------
# per-kind block init / apply
# ---------------------------------------------------------------------------
def _attn_block_init(rng, cfg: ArchConfig) -> Params:
    k1, k2 = jax.random.split(rng)
    return {
        "ln1": B.rmsnorm_init(cfg.d_model, cfg.dtype),
        "attn": B.attention_init(k1, cfg),
        "ln2": B.rmsnorm_init(cfg.d_model, cfg.dtype),
        "mlp": B.mlp_init(k2, cfg),
    }


def _moe_block_init(rng, cfg: ArchConfig) -> Params:
    k1, k2 = jax.random.split(rng)
    return {
        "ln1": B.rmsnorm_init(cfg.d_model, cfg.dtype),
        "attn": B.attention_init(k1, cfg),
        "ln2": B.rmsnorm_init(cfg.d_model, cfg.dtype),
        "moe": B.moe_init(k2, cfg),
    }


def _mamba_block_init(rng, cfg: ArchConfig) -> Params:
    return {
        "ln": B.rmsnorm_init(cfg.d_model, cfg.dtype),
        "mixer": mamba2.mamba2_init(rng, cfg),
    }


def _mamba_mlp_block_init(rng, cfg: ArchConfig) -> Params:
    k1, k2 = jax.random.split(rng)
    return {
        "ln1": B.rmsnorm_init(cfg.d_model, cfg.dtype),
        "mixer": mamba2.mamba2_init(k1, cfg),
        "ln2": B.rmsnorm_init(cfg.d_model, cfg.dtype),
        "mlp": B.mlp_init(k2, cfg),
    }


BLOCK_INIT = {
    "attn": _attn_block_init,
    "shared_attn": _attn_block_init,
    "moe": _moe_block_init,
    "mamba": _mamba_block_init,
    "mamba_mlp": _mamba_mlp_block_init,
    "rwkv": rwkv6.rwkv6_init,
}


def _residual(cfg: ArchConfig, x: jnp.ndarray, h: jnp.ndarray) -> jnp.ndarray:
    m = cfg.residual_multiplier
    return x + h if m == 1.0 else x + m * h


def apply_block(kind: str, params: Params, cfg: ArchConfig, x: jnp.ndarray,
                angles: jnp.ndarray, cache: Any, cache_pos,
                constrain: Callable = Identity):
    """Returns (x, new_cache, aux_loss). cache=None -> train path (no cache out
    is consumed); still returns prefill-style cache pieces."""
    aux = jnp.zeros((), jnp.float32)
    if kind in ("attn", "shared_attn", "moe"):
        h, new_kv = B.multihead_attention(
            params["attn"], cfg, B.rmsnorm(params["ln1"], x, cfg.norm_eps),
            angles, kv_cache=cache, cache_pos=cache_pos)
        x = constrain(_residual(cfg, x, h))
        h2 = B.rmsnorm(params["ln2"], x, cfg.norm_eps)
        if kind == "moe":
            mo, aux = B.moe_ffn(params["moe"], cfg, h2)
            x = constrain(x + mo)
        else:
            x = constrain(_residual(cfg, x, B.mlp(params["mlp"], h2)))
        return x, new_kv, aux
    if kind in ("mamba", "mamba_mlp"):
        norm = params["ln" if kind == "mamba" else "ln1"]
        xn = B.rmsnorm(norm, x, cfg.norm_eps)
        if cache is None:
            h, new_c = mamba2.mamba2_forward(params["mixer"], cfg, xn)
        else:
            h, new_c = mamba2.mamba2_decode(params["mixer"], cfg, xn, cache)
        if kind == "mamba":
            return constrain(x + h), new_c, aux
        x = constrain(_residual(cfg, x, h))
        h2 = B.rmsnorm(params["ln2"], x, cfg.norm_eps)
        x = constrain(_residual(cfg, x, B.mlp(params["mlp"], h2)))
        return x, new_c, aux
    if kind == "rwkv":
        x, new_c = rwkv6.rwkv6_block(params, cfg, x, cache)
        return constrain(x), new_c, aux
    raise ValueError(kind)


# ---------------------------------------------------------------------------
# model init
# ---------------------------------------------------------------------------
def init_params(cfg: ArchConfig, rng) -> Params:
    mode = wiring_mode(cfg)
    k_embed, k_head, k_blocks, k_extra = jax.random.split(rng, 4)
    p: Params = {
        "embed": (jax.random.normal(k_embed, (cfg.vocab_size, cfg.d_model),
                                    jnp.float32)
                  * cfg.d_model ** -0.5).astype(cfg.dtype),
        "final_ln": B.rmsnorm_init(cfg.d_model, cfg.dtype),
    }
    if not cfg.tie_embeddings:
        p["head"] = B.dense_init(k_head, (cfg.d_model, cfg.vocab_size), cfg.dtype)

    def stacked(kind: str, n: int, key) -> Params:
        return jax.vmap(lambda k: BLOCK_INIT[kind](k, cfg))(jax.random.split(key, n))

    if mode == "uniform":
        kind = cfg.block_pattern[0]
        p["blocks"] = stacked(kind, cfg.num_layers, k_blocks)
    elif mode == "prefix_dense":
        p["dense0"] = _attn_block_init(k_extra, cfg)
        p["blocks"] = stacked("moe", cfg.num_layers - cfg.first_k_dense, k_blocks)
    elif mode == "interleaved":
        kinds = cfg.pattern_for_depth()
        for i, kind in enumerate(dict.fromkeys(kinds)):
            p[kind] = stacked(kind, kinds.count(kind),
                              jax.random.fold_in(k_blocks, i))
    else:  # hybrid_shared
        n_groups, per = _group_shape(cfg)
        flat = stacked("mamba", n_groups * per, k_blocks)
        p["mamba"] = jax.tree.map(
            lambda a: a.reshape(n_groups, per, *a.shape[1:]), flat)
        p["shared_attn"] = _attn_block_init(k_extra, cfg)
    return p


def param_count(cfg: ArchConfig) -> int:
    """Exact parameter count via shape-only tracing (no allocation)."""
    import math
    spec = jax.eval_shape(lambda: init_params(cfg, jax.random.key(0)))
    return sum(math.prod(l.shape) for l in jax.tree.leaves(spec))


# ---------------------------------------------------------------------------
# embedding / positions
# ---------------------------------------------------------------------------
def _positions(cfg: ArchConfig, batch: Batch, Bsz: int, S: int,
               offset=0) -> jnp.ndarray:
    if "positions" in batch:
        return batch["positions"]
    pos = jnp.arange(S)[None, :] + offset                 # (B, S) broadcastable
    pos = jnp.broadcast_to(pos, (Bsz, S))
    if cfg.mrope:
        return jnp.broadcast_to(pos[None], (3, Bsz, S))   # stub: t=h=w stream
    return pos


def _lookup(cfg: ArchConfig, params: Params, tokens: jnp.ndarray) -> jnp.ndarray:
    x = jnp.take(params["embed"], tokens, axis=0)
    m = cfg.embedding_multiplier
    return x if m == 1.0 else x * m


def _embed(cfg: ArchConfig, params: Params, batch: Batch) -> jnp.ndarray:
    x = _lookup(cfg, params, batch["tokens"])
    if cfg.frontend_prefix and "prefix_embeds" in batch:
        pe = batch["prefix_embeds"].astype(x.dtype)       # (B, P, d) stub frontend
        x = lax.dynamic_update_slice(x, pe, (0, 0, 0))
    return x


def _head(cfg: ArchConfig, params: Params, x: jnp.ndarray,
          constrain_logits: Callable = Identity) -> jnp.ndarray:
    x = B.rmsnorm(params["final_ln"], x, cfg.norm_eps)
    w = params["embed"].T if cfg.tie_embeddings else params["head"]
    logits = x @ w
    if cfg.logits_scaling != 1.0:
        logits = logits / cfg.logits_scaling
    return constrain_logits(logits)


# ---------------------------------------------------------------------------
# forward (train / prefill)
# ---------------------------------------------------------------------------
def _maybe_remat(cfg: ArchConfig, fn: Callable) -> Callable:
    return jax.checkpoint(fn) if cfg.remat in ("block", "full") else fn


def forward(params: Params, cfg: ArchConfig, batch: Batch, *,
            constrain: Callable = Identity, want_cache: bool = False,
            cache_len: int = 0):
    """Full-sequence forward. Returns (hidden, aux_loss, cache-or-None).

    ``want_cache`` (prefill): also build the decode cache with capacity
    ``cache_len`` (>= S; SWA archs use min(cache_len, window))."""
    mode = wiring_mode(cfg)
    Bsz, S = batch["tokens"].shape
    x = constrain(_embed(cfg, params, batch))
    angles = (B.rope_angles(cfg, _positions(cfg, batch, Bsz, S))
              if not cfg.attention_free and cfg.use_rope
              else jnp.zeros((Bsz, S, 1)))
    aux_total = jnp.zeros((), jnp.float32)
    cache = {"pos": jnp.asarray(S, jnp.int32)} if want_cache else None

    def ring_kv(kv: jnp.ndarray, W: int) -> jnp.ndarray:
        """Arrange prefill K/V (B,S,...) into the decode ring layout (B,W,...)."""
        if S <= W:
            pad = [(0, 0)] * kv.ndim
            pad[1] = (0, W - S)
            return jnp.pad(kv, pad)
        s_idx = jnp.arange(W)
        src = S - 1 - ((S - 1 - s_idx) % W)
        return jnp.take(kv, src, axis=1)

    kv_W = (min(cache_len, cfg.sliding_window) if cfg.sliding_window > 0
            else cache_len)

    if mode == "uniform":
        kind = cfg.block_pattern[0]

        def body(carry, layer_params):
            x, aux = carry
            x, c, a = apply_block(kind, layer_params, cfg, x, angles, None,
                                  None, constrain)
            return (x, aux + a), (c if want_cache else 0)

        (x, aux_total), caches = lax.scan(
            _maybe_remat(cfg, body), (x, aux_total), params["blocks"])
        if want_cache:
            cache[kind] = _pack_cache(kind, caches, ring_kv, kv_W)
    elif mode == "prefix_dense":
        x, c0, a0 = apply_block("attn", params["dense0"], cfg, x, angles,
                                None, None, constrain)
        aux_total += a0

        def body(carry, layer_params):
            x, aux = carry
            x, c, a = apply_block("moe", layer_params, cfg, x, angles, None,
                                  None, constrain)
            return (x, aux + a), (c if want_cache else 0)

        (x, aux_total), caches = lax.scan(
            _maybe_remat(cfg, body), (x, aux_total), params["blocks"])
        if want_cache:
            cache["dense0"] = _pack_cache(
                "attn", jax.tree.map(lambda a: a[None], c0), ring_kv, kv_W)
            cache["moe"] = _pack_cache("moe", caches, ring_kv, kv_W)
    elif mode == "interleaved":
        for kind, lo, n in _runs(cfg):

            def body(carry, i, kind=kind):
                x, aux = carry
                with scope(_SCOPE[kind]):
                    x, c, a = apply_block(kind, _layer(params[kind], i), cfg,
                                          x, angles, None, None, constrain)
                return (x, aux + a), (c if want_cache else 0)

            (x, aux_total), caches = lax.scan(
                _maybe_remat(cfg, body), (x, aux_total),
                jnp.arange(lo, lo + n))
            if want_cache:
                cache.setdefault(kind, []).append(
                    _pack_cache(kind, caches, ring_kv, kv_W))
    else:  # hybrid_shared
        n_groups, per = _group_shape(cfg)

        def group_body(carry, group_params):
            x, aux = carry

            def inner(carry2, lp):
                x2, aux2 = carry2
                x2, c, a = apply_block("mamba", lp, cfg, x2, angles, None,
                                       None, constrain)
                return (x2, aux2 + a), (c if want_cache else 0)

            (x, aux), m_caches = lax.scan(inner, (x, aux), group_params)
            x, a_cache, a = apply_block("shared_attn", params["shared_attn"],
                                        cfg, x, angles, None, None, constrain)
            return (x, aux + a), ((m_caches, a_cache) if want_cache else 0)

        (x, aux_total), caches = lax.scan(
            _maybe_remat(cfg, group_body), (x, aux_total), params["mamba"])
        if want_cache:
            m_caches, a_caches = caches
            # mamba caches come out (n_groups, per, ...) -> flatten layer axes
            m_flat = jax.tree.map(
                lambda a: a.reshape(n_groups * per, *a.shape[2:]), m_caches)
            cache["mamba"] = m_flat
            cache["shared_attn"] = _pack_cache("shared_attn", a_caches,
                                               ring_kv, kv_W)
    return x, aux_total, cache


def _pack_cache(kind: str, caches, ring_kv: Callable, kv_W: int):
    if kind in ("attn", "shared_attn", "moe"):
        k, v = caches
        return {"k": jax.vmap(lambda a: ring_kv(a, kv_W))(k)
                if k.ndim == 5 else ring_kv(k, kv_W),
                "v": jax.vmap(lambda a: ring_kv(a, kv_W))(v)
                if v.ndim == 5 else ring_kv(v, kv_W)}
    return caches


# ---------------------------------------------------------------------------
# decode (one token)
# ---------------------------------------------------------------------------
def decode_step(params: Params, cfg: ArchConfig, token: jnp.ndarray,
                cache: Dict[str, Any], *, constrain: Callable = Identity):
    """token: (B, 1) int32. Returns (logits (B, V), new_cache)."""
    mode = wiring_mode(cfg)
    Bsz = token.shape[0]
    pos = cache["pos"]
    x = _lookup(cfg, params, token)
    if not cfg.attention_free and cfg.use_rope:
        positions = jnp.broadcast_to(jnp.asarray(pos)[None, None], (Bsz, 1))
        if cfg.mrope:
            positions = jnp.broadcast_to(positions[None], (3, Bsz, 1))
        angles = B.rope_angles(cfg, positions)
    else:
        angles = jnp.zeros((Bsz, 1, 1))
    new_cache = {"pos": pos + 1}

    if mode == "uniform":
        kind = cfg.block_pattern[0]

        def body(x, xs):
            layer_params, layer_cache = xs
            x, c, _ = apply_block(kind, layer_params, cfg, x, angles,
                                  _unpack(kind, layer_cache), pos, constrain)
            return x, _repack(kind, c)

        x, new_lc = lax.scan(body, x, (params["blocks"], cache[kind]))
        new_cache[kind] = new_lc
    elif mode == "prefix_dense":
        x, c0, _ = apply_block("attn", params["dense0"], cfg, x, angles,
                               _unpack("attn", jax.tree.map(lambda a: a[0],
                                                            cache["dense0"])),
                               pos, constrain)
        new_cache["dense0"] = jax.tree.map(lambda a: a[None], _repack("attn", c0))

        def body(x, xs):
            layer_params, layer_cache = xs
            x, c, _ = apply_block("moe", layer_params, cfg, x, angles,
                                  _unpack("moe", layer_cache), pos, constrain)
            return x, _repack("moe", c)

        x, new_lc = lax.scan(body, x, (params["blocks"], cache["moe"]))
        new_cache["moe"] = new_lc
    elif mode == "interleaved":
        run_caches = {kind: iter(cache[kind]) for kind in _SCOPE}
        for kind, lo, n in _runs(cfg):

            def body(x, xs, kind=kind):
                i, layer_cache = xs
                with scope(_SCOPE[kind]):
                    x, c, _ = apply_block(kind, _layer(params[kind], i), cfg,
                                          x, angles, _unpack(kind, layer_cache),
                                          pos, constrain)
                return x, _repack(kind, c)

            x, c = lax.scan(body, x, (jnp.arange(lo, lo + n),
                                      next(run_caches[kind])))
            new_cache.setdefault(kind, []).append(c)
    else:  # hybrid_shared
        n_groups, per = _group_shape(cfg)
        m_cache = jax.tree.map(
            lambda a: a.reshape(n_groups, per, *a.shape[1:]), cache["mamba"])

        def group_body(x, xs):
            group_params, g_mcache, g_acache = xs

            def inner(x2, xs2):
                lp, lc = xs2
                x2, c, _ = apply_block("mamba", lp, cfg, x2, angles, lc, pos,
                                       constrain)
                return x2, c

            x, new_mc = lax.scan(inner, x, (group_params, g_mcache))
            x, ac, _ = apply_block("shared_attn", params["shared_attn"], cfg,
                                   x, angles, _unpack("attn", g_acache), pos,
                                   constrain)
            return x, (new_mc, _repack("attn", ac))

        x, (new_mc, new_ac) = lax.scan(
            group_body, x, (params["mamba"], m_cache, cache["shared_attn"]))
        new_cache["mamba"] = jax.tree.map(
            lambda a: a.reshape(n_groups * per, *a.shape[2:]), new_mc)
        new_cache["shared_attn"] = new_ac

    logits = _head(cfg, params, x)[:, 0]                  # (B, V)
    return logits, new_cache


def _unpack(kind: str, layer_cache):
    if kind in ("attn", "shared_attn", "moe"):
        return (layer_cache["k"], layer_cache["v"])
    return layer_cache


def _repack(kind: str, c):
    if kind in ("attn", "shared_attn", "moe"):
        return {"k": c[0], "v": c[1]}
    return c


# ---------------------------------------------------------------------------
# cache init (decode from scratch, e.g. dry-run serve_step input specs)
# ---------------------------------------------------------------------------
def init_cache(cfg: ArchConfig, batch: int, cache_len: int) -> Dict[str, Any]:
    mode = wiring_mode(cfg)
    W = (min(cache_len, cfg.sliding_window) if cfg.sliding_window > 0
         else cache_len)
    hd, Hkv = cfg.head_dim, cfg.num_kv_heads
    kv = lambda n: {"k": jnp.zeros((n, batch, W, Hkv, hd), cfg.dtype),
                    "v": jnp.zeros((n, batch, W, Hkv, hd), cfg.dtype)}
    cache: Dict[str, Any] = {"pos": jnp.asarray(0, jnp.int32)}
    if mode == "uniform":
        kind = cfg.block_pattern[0]
        if kind in ("attn", "moe"):
            cache[kind] = kv(cfg.num_layers)
        elif kind == "mamba":
            cache["mamba"] = jax.tree.map(
                lambda a: jnp.broadcast_to(a, (cfg.num_layers, *a.shape)),
                mamba2.init_cache(cfg, batch, cfg.dtype))
        else:
            cache["rwkv"] = jax.tree.map(
                lambda a: jnp.broadcast_to(a, (cfg.num_layers, *a.shape)),
                rwkv6.init_cache(cfg, batch, cfg.dtype))
    elif mode == "prefix_dense":
        cache["dense0"] = kv(1)
        cache["moe"] = kv(cfg.num_layers - cfg.first_k_dense)
    elif mode == "interleaved":
        for kind, _, n in _runs(cfg):
            cache.setdefault(kind, []).append(kv(n) if kind == "attn" else
                jax.tree.map(lambda a: jnp.broadcast_to(a, (n, *a.shape)),
                             mamba2.init_cache(cfg, batch, cfg.dtype)))
    else:
        n_groups, per = _group_shape(cfg)
        cache["mamba"] = jax.tree.map(
            lambda a: jnp.broadcast_to(a, (n_groups * per, *a.shape)),
            mamba2.init_cache(cfg, batch, cfg.dtype))
        cache["shared_attn"] = kv(n_groups)
    return jax.tree.map(jnp.asarray, cache)


# ---------------------------------------------------------------------------
# loss
# ---------------------------------------------------------------------------
def lm_loss(params: Params, cfg: ArchConfig, batch: Batch, *,
            constrain: Callable = Identity,
            constrain_logits: Callable = Identity):
    """Next-token cross entropy (+ z-loss + MoE aux). Returns (loss, metrics)."""
    x, aux, _ = forward(params, cfg, batch, constrain=constrain)
    logits = _head(cfg, params, x, constrain_logits)      # (B, S, V)
    targets = batch["targets"]
    mask = (targets >= 0).astype(jnp.float32)
    tgt = jnp.maximum(targets, 0)

    logits = logits.astype(jnp.float32)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, tgt[..., None], axis=-1)[..., 0]
    nll = (logz - gold) * mask
    denom = jnp.maximum(jnp.sum(mask), 1.0)
    ce = jnp.sum(nll) / denom
    zl = cfg.z_loss * jnp.sum(jnp.square(logz) * mask) / denom
    loss = ce + zl + aux
    return loss, {"ce": ce, "z_loss": zl, "aux_loss": aux,
                  "tokens": jnp.sum(mask)}
