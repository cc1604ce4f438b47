"""Live pre-copy of a serving replica's device state, back to back.

The replica is a dense decoder serving a batch of requests: its weights
(``bench/gen/weights.py``, from the seed, in the served dtype) and its KV
cache, filled by a prefill of the prompts. It decodes one step per
pre-copy round (``steps_per_round``), and every step fetches its tokens to
the host, as a server returning tokens does. One migration is
``precopy.migrate`` of that state while it decodes; then decoding goes on
from the destination and the old source is dropped, so the chip holds one
state and one shadow. Set-up warms ``warm_migrations`` whole migrations.

Per migration the window records its wall time, from the ``migrate`` call
to the destination ready, and the pause in the token stream, from the host
receiving the last token decoded on the source to it receiving the first
decoded on the destination. Every destination is compared with its source
at the final copy, leaf by leaf on the chip, after that first token. The
check then runs the plain reference (``bench/ref/internlm2.py``) over each
prompt with all its served tokens.
"""
from __future__ import annotations

import time
from typing import Dict, List, Tuple

import numpy as np

from bench.gen import weights
from bench.ref import internlm2 as ref


def arch_of(name: str, m: dict):
    from repro.configs.base import ArchConfig
    return ArchConfig(name=name, family="dense",
                      num_layers=m["num_layers"], d_model=m["d_model"],
                      num_heads=m["num_heads"],
                      num_kv_heads=m["num_kv_heads"], d_ff=m["d_ff"],
                      vocab_size=m["vocab_size"], rope_theta=m["rope_theta"],
                      norm_eps=m["norm_eps"], param_dtype=m["param_dtype"],
                      tie_embeddings=False)


class PrecopyCell:
    def __init__(self, config: dict, traffic: dict, seed: int, ctx):
        import jax
        import jax.numpy as jnp
        from repro.core import precopy
        from repro.train import make_decode_step, make_prefill_step

        self.ctx, self.config, self.seed = ctx, config, seed
        self.model = m = config["model"]
        serve = config["serving"]
        self.cache_len = serve["cache_len"]
        arch = arch_of(config["name"], m)
        self.migrate = precopy.migrate
        self.pc = precopy.PrecopyConfig(
            **config["precopy"], steps_per_round=traffic["steps_per_round"])
        self.prompt = weights.prompt_tokens(m, serve["batch"],
                                            serve["prompt"], seed)
        params = weights.make_params(m, seed)
        prefill = jax.jit(make_prefill_step(arch, cache_len=self.cache_len))
        self.decode = jax.jit(make_decode_step(arch))
        logits, cache = prefill(params, {"tokens": jnp.asarray(self.prompt)})
        tok = jnp.argmax(logits, -1)[:, None].astype(jnp.int32)
        self.tokens: List[np.ndarray] = [np.asarray(tok)[:, 0]]
        self.box = {"params": params, "cache": cache, "tok": tok}
        self.state_bytes = precopy.total_bytes(self.state())
        self.leaves = len(jax.tree.leaves(self.state()))
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

    def state(self) -> Dict:
        return {"params": self.box["params"], "cache": self.box["cache"]}

    def step(self) -> None:
        """One decode step of the job, its tokens fetched to the host."""
        with self.ctx.span("bench.decode"):
            b = self.box
            b["tok"], _, b["cache"] = self.decode(b["params"], b["tok"],
                                                  b["cache"])
            self.tokens.append(np.asarray(b["tok"])[:, 0])
        self.t_token = time.perf_counter()

    def migrate_once(self) -> Tuple[float, float]:
        if len(self.tokens) + self.pc.max_rounds + 2 + self.prompt.shape[1] \
                > self.cache_len:
            raise RuntimeError("the requests would outgrow the KV cache")
        with self.ctx.span("bench.migrate", state_bytes=self.state_bytes,
                           leaves=self.leaves):
            t0 = time.perf_counter()
            dest, _ = self.migrate(self.state, self.step, self.pc)
            t1 = time.perf_counter()
        last_on_source = self.t_token
        source = self.state()
        self.box["params"], self.box["cache"] = dest["params"], dest["cache"]
        self.step()                                  # first on the destination
        pause = self.t_token - last_on_source
        with self.ctx.span("bench.check"):
            self.same.append(bool(self.equal(source, dest)))
        return t1 - t0, pause

    def window(self, seconds: float) -> None:
        from bench.harness import HostClock
        host: List[Tuple[float, ...]] = []
        with HostClock() as clock:
            t0 = time.perf_counter()
            t_end = t0 + seconds
            while True:
                before = clock.read()
                mig, pause = self.migrate_once()
                host.append(tuple(b - a for a, b in zip(before,
                                                        clock.read())))
                self.migration_s.append(mig)
                self.pause_s.append(pause)
                if time.perf_counter() >= t_end:
                    break
            self.window_s = time.perf_counter() - t0
        self.ctx.log(HostClock.describe("migrations", self.migration_s, host)
                     + f" longest_pause_s={max(self.pause_s)} at "
                     f"{int(np.argmax(self.pause_s))}")

    def end_to_end(self) -> Dict[str, float]:
        return {"migration_s": float(np.mean(self.migration_s)),
                "pause_s": float(np.mean(self.pause_s))}

    def counters(self) -> Dict[str, float]:
        return {"migrations": len(self.migration_s)}

    def release(self) -> None:
        self.box = None

    def served(self) -> np.ndarray:
        """(batch, tokens) every token served, prefill's first included."""
        return np.stack(self.tokens, axis=1)

    def readings(self, control: bool = False) -> Dict[str, float]:
        """The compared numbers of the program's run; with ``control``, the
        gap of the tokens the float8 reference puts first instead."""
        gap = ref.served_gap(self.model, self.seed, self.prompt,
                             self.served(), control=control)
        if control:
            return {"served_logit_gap": gap}
        return {"leaf_mismatch_migrations": float(self.same.count(False)),
                "served_logit_gap": gap}

    def tally(self) -> Tuple[int, int]:
        return len(self.migration_s), self.same[self.warm:].count(False)


def setup(config: dict, traffic: dict, seed: int, ctx) -> PrecopyCell:
    return PrecopyCell(config, traffic, seed, ctx)
