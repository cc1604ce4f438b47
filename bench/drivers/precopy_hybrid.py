"""Live pre-copy of a Mamba2 / attention hybrid serving replica (granite
4.0-H), back to back.

The replica is the configuration's model (its ``config.json`` keys) serving
a batch of requests: weights from ``bench/gen/hybrid_weights.py``, and a
cache of two kinds, filled by a prefill of the prompts, ``prefill_batch``
requests a call: a ring of K/V per attention layer and, per Mamba layer, a
conv window and a float32 SSM state that every decode step rewrites whole.
Everything else is ``bench/drivers/precopy.py``'s ``PrecopyCell``: one decode
step per pre-copy round, every token fetched to the host, the same window,
pause and leaf-by-leaf check. The check's reference is
``bench/ref/granite_hybrid.py``.

Each ``bench.decode`` span carries the bytes a decode step must move at the
least (``bench/layers/serve_step_roofline.py``), and the window's migrations
report their rounds and bytes sent as counters.
"""
from __future__ import annotations

import time
from typing import Dict, List

import numpy as np

from bench.drivers.precopy import PrecopyCell
from bench.gen import hybrid_weights as gen
from bench.gen.weights import prompt_tokens
from bench.ref import granite_hybrid as ref


def arch_of(c: dict):
    """The program's ``ArchConfig`` of config ``c``, refusing a model this
    path does not compute (biases, experts, position embedding, an SSD
    chunk other than the program's)."""
    from repro.configs.base import ArchConfig, SSMConfig
    from repro.models import mamba2
    if (c["attention_bias"] or c["mamba_proj_bias"] or not c["mamba_conv_bias"]
            or c["num_local_experts"] or c["position_embedding_type"] != "nope"
            or c["hidden_act"] != "silu" or not c["tie_word_embeddings"]
            or c["mamba_chunk_size"] != mamba2.CHUNK):
        raise ValueError(f"{c['name']}: not a model this driver computes")
    gen.mamba_dims(c)
    return ArchConfig(
        name=c["name"], family="hybrid", num_layers=c["num_hidden_layers"],
        d_model=c["hidden_size"], num_heads=c["num_attention_heads"],
        num_kv_heads=c["num_key_value_heads"], d_ff=c["intermediate_size"],
        vocab_size=c["vocab_size"], norm_eps=c["rms_norm_eps"],
        tie_embeddings=True, use_rope=False,
        attn_scale=c["attention_multiplier"],
        embedding_multiplier=c["embedding_multiplier"],
        residual_multiplier=c["residual_multiplier"],
        logits_scaling=c["logits_scaling"], block_pattern=gen.kinds(c),
        ssm=SSMConfig(kind="mamba2", state_dim=c["mamba_d_state"],
                      head_dim=c["mamba_d_head"], expand=c["mamba_expand"],
                      conv_width=c["mamba_d_conv"]),
        param_dtype=c["param_dtype"])


def _nbytes(tree) -> int:
    import jax
    return sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(tree))


class HybridCell(PrecopyCell):
    def __init__(self, config: dict, traffic: dict, seed: int, ctx):
        import jax
        import jax.numpy as jnp
        from repro.core import precopy
        from repro.train import make_decode_step, make_prefill_step

        self.ctx, self.config, self.seed = ctx, config, seed
        self.model = config
        serve = config["serving"]
        self.cache_len = serve["cache_len"]
        arch = arch_of(config)
        self.reports: List = []

        def migrate(get_state, step_fn, cfg):
            dest, report = precopy.migrate(get_state, step_fn, cfg)
            self.reports.append(report)
            return dest, report

        self.migrate = migrate
        self.pc = precopy.PrecopyConfig(
            **config["precopy"], steps_per_round=traffic["steps_per_round"])
        self.prompt = prompt_tokens(config, serve["batch"], serve["prompt"],
                                    seed)
        params = gen.make_params(config, seed)
        prefill = jax.jit(make_prefill_step(arch, cache_len=self.cache_len))
        self.decode = jax.jit(make_decode_step(arch))
        logits, caches = [], []
        for lo in range(0, serve["batch"], serve["prefill_batch"]):
            lg, c = prefill(params, {"tokens": jnp.asarray(
                self.prompt[lo: lo + serve["prefill_batch"]])})
            logits.append(lg)
            caches.append(c)
        # requests on axis 1 of every stacked cache leaf; "pos" is shared
        cache = jax.tree.map(
            lambda *a: jnp.concatenate(a, axis=1) if a[0].ndim else a[0],
            *caches)
        del caches
        tok = jnp.argmax(jnp.concatenate(logits), -1)[:, None].astype(
            jnp.int32)
        self.tokens: List[np.ndarray] = [np.asarray(tok)[:, 0]]
        self.box = {"params": params, "cache": cache, "tok": tok}
        self.state_bytes = precopy.total_bytes(self.state())
        self.leaves = len(jax.tree.leaves(self.state()))
        # least bytes of one decode step: weights, recurrent state read and
        # written, and per filled position the K and V of every attention layer
        self.step_bytes = {
            "weight_bytes": _nbytes(params),
            "recurrent_bytes": _nbytes(cache["mamba_mlp"]),
            "kv_position_bytes": _nbytes(cache["attn"]) // self.cache_len}
        self.equal = jax.jit(lambda a, b: jnp.all(jnp.stack(
            [jnp.array_equal(x, y) for x, y in
             zip(jax.tree.leaves(a), jax.tree.leaves(b))])))
        self.t_token = time.perf_counter()
        self.same: List[bool] = []
        self.warm = int(traffic["warm_migrations"])
        for _ in range(self.warm):
            self.migrate_once()
        self.migration_s: List[float] = []
        self.pause_s: List[float] = []
        self.window_s = 0.0

    def step(self) -> None:
        """One decode step of the job, its tokens fetched to the host."""
        positions = self.prompt.shape[1] + len(self.tokens)
        with self.ctx.span("bench.decode", positions=positions,
                           **self.step_bytes):
            b = self.box
            b["tok"], _, b["cache"] = self.decode(b["params"], b["tok"],
                                                  b["cache"])
            self.tokens.append(np.asarray(b["tok"])[:, 0])
        self.t_token = time.perf_counter()

    def counters(self) -> Dict[str, float]:
        window = [r.outcome for r in self.reports[self.warm:]]
        out = {"migrations": len(window)}
        if window:
            out["rounds_per_migration"] = float(
                np.mean([o.rounds for o in window]))
            out["sent_gb_per_migration"] = float(
                np.mean([o.bytes_sent for o in window])) / 1e9
        return out

    def readings(self, control: bool = False) -> Dict[str, float]:
        """The compared numbers of the program's run; with ``control``, the
        gap of the tokens the float8 reference puts first instead."""
        gap = ref.served_gap(self.model, self.seed, self.prompt,
                             self.served(), control=control)
        if control:
            return {"served_logit_gap": gap}
        return {"leaf_mismatch_migrations": float(self.same.count(False)),
                "served_logit_gap": gap}


def setup(config: dict, traffic: dict, seed: int, ctx) -> HybridCell:
    return HybridCell(config, traffic, seed, ctx)
