"""Random weights of a dense decoder, made on the device from the seed.

Every leaf is drawn from its own key, ``fold_in(key(seed), leaf index)``,
so one jitted call makes the whole tree in the served dtype, the same
for the program and for the reference that remakes it. Leaves
are named by their path in the serving program's parameter tree:

- ``embed``: N(0, 1) / sqrt(d_model);
- ``wq``, ``wk``, ``wv``, ``w_gate``, ``w_up``, ``head``: N(0, 1) / sqrt(fan in);
- ``wo``, ``w_down``: the same, scaled by 1 / sqrt(2 * layers);
- ``scale`` (RMSNorm gains): 1 + 0.1 N(0, 1), so that a norm that drops its
  gain does not go unseen.
"""
from __future__ import annotations

from functools import partial
from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

Shape = Tuple[int, ...]


def tree_spec(c: dict) -> Dict[str, Shape]:
    """Path -> shape of every leaf, for the sizes of config ``c``, in the
    order of the flattened tree (sorted paths)."""
    L, d, f, V = c["num_layers"], c["d_model"], c["d_ff"], c["vocab_size"]
    hd = d // c["num_heads"]
    q, kv = c["num_heads"] * hd, c["num_kv_heads"] * hd
    spec = {
        "blocks/attn/wk": (L, d, kv), "blocks/attn/wo": (L, q, d),
        "blocks/attn/wq": (L, d, q), "blocks/attn/wv": (L, d, kv),
        "blocks/ln1/scale": (L, d), "blocks/ln2/scale": (L, d),
        "blocks/mlp/w_down": (L, f, d), "blocks/mlp/w_gate": (L, d, f),
        "blocks/mlp/w_up": (L, d, f), "embed": (V, d),
        "final_ln/scale": (d,), "head": (d, V),
    }
    return dict(sorted(spec.items()))


def seed_key(seed: int) -> jax.Array:
    """A JAX key from any whole number, however large."""
    words = np.random.SeedSequence(seed).generate_state(2) >> 1
    return jax.random.fold_in(jax.random.key(int(words[0])), int(words[1]))


def _leaf(key, index: int, path: str, shape: Shape, layers: int, dtype):
    k = jax.random.fold_in(key, index)
    z = jax.random.normal(k, shape, jnp.float32)
    name = path.rsplit("/", 1)[-1]
    if name == "scale":
        w = 1.0 + 0.1 * z
    elif name == "embed":
        w = z * shape[-1] ** -0.5
    else:
        w = z * shape[-2] ** -0.5
        if name in ("wo", "w_down"):
            w = w * (2.0 * layers) ** -0.5
    return w.astype(dtype)


@partial(jax.jit, static_argnames=("paths", "shapes", "layers", "dtype"))
def _make(key, *, paths: Tuple[str, ...], shapes: Tuple[Shape, ...],
          layers: int, dtype) -> List[jax.Array]:
    return [_leaf(key, i, p, s, layers, dtype)
            for i, (p, s) in enumerate(zip(paths, shapes))]


def make_params(c: dict, seed: int) -> dict:
    """The whole nested parameter tree of config ``c``, in one jitted call."""
    spec = tree_spec(c)
    leaves = _make(seed_key(seed), paths=tuple(spec),
                   shapes=tuple(spec.values()), layers=c["num_layers"],
                   dtype=jnp.dtype(c["param_dtype"]))
    tree: dict = {}
    for path, leaf in zip(spec, leaves):
        node = tree
        *parents, last = path.split("/")
        for p in parents:
            node = node.setdefault(p, {})
        node[last] = leaf
    return tree


def prompt_tokens(c: dict, batch: int, length: int, seed: int) -> np.ndarray:
    """(batch, length) int32 prompt ids drawn uniformly from the vocabulary."""
    rng = np.random.default_rng([seed, 2])
    return rng.integers(0, c["vocab_size"], (batch, length), dtype=np.int32)
