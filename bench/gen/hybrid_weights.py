"""Random weights of a Mamba2 / attention hybrid decoder (granite 4.0-H),
made on the device from the seed.

The configuration is the model's own ``config.json`` keys
(``bench/configs/replica-granite-4h-micro.json``). Every leaf is drawn from
its own key, ``fold_in(key(seed), leaf index)``, so one jitted call makes the
whole tree, the same for the program and for the reference that remakes it.
Layers of one kind are stacked on a leading axis, in depth order: ``attn``
holds the attention layers, ``mamba_mlp`` the Mamba2 ones, as the serving
program's parameter tree has them. Leaves, by name:

- ``in_proj``, ``out_proj``, ``wq``, ``wk``, ``wv``, ``wo``, ``w_gate``,
  ``w_up``, ``w_down``, ``conv_w``: N(0, 1 / fan in), fan in being the
  second-to-last axis (the conv's 4 taps for ``conv_w``);
- ``embed`` (tied with the output head): N(0, 1 / width) over
  ``embedding_multiplier`` squared, so that the embedding the layers see,
  ``embedding_multiplier * embed``, is N(0, 1 / width). Drawn at 1 / width,
  a token's scaled embedding would outweigh what the layers add by about
  ``embedding_multiplier`` and the tied head would serve each request its
  own last token again, whatever the layers compute;
- ``scale`` (RMSNorm gains, the gated norm's among them) and ``D`` (the skip
  of each Mamba head): 1 + 0.1 N(0, 1), so that a dropped gain or skip shows;
- ``conv_b``: 0.1 N(0, 1);
- ``A_log``: log U(1, 16), and ``dt_bias``: softplus^-1 of exp U(log 1e-3,
  log 1e-1), Mamba2's own initialisation of its decay and step.

``A_log``, ``D`` and ``dt_bias`` are float32, as the program keeps them; the
rest is in the served dtype.
"""
from __future__ import annotations

import math
from functools import partial
from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp

from bench.gen.weights import seed_key

Shape = Tuple[int, ...]
F32_LEAVES = ("A_log", "D", "dt_bias")


def kinds(c: dict) -> Tuple[str, ...]:
    """Per-layer kind in the program's names: 'attn' or 'mamba_mlp'."""
    assert len(c["layer_types"]) == c["num_hidden_layers"]
    return tuple("attn" if t == "attention" else "mamba_mlp"
                 for t in c["layer_types"])


def mamba_dims(c: dict) -> Tuple[int, int, int, int]:
    """(d_inner, heads, d_state, conv channels) of a Mamba2 layer."""
    d_in = c["mamba_expand"] * c["hidden_size"]
    assert d_in == c["mamba_n_heads"] * c["mamba_d_head"]
    assert c["mamba_n_groups"] == 1
    return (d_in, c["mamba_n_heads"], c["mamba_d_state"],
            d_in + 2 * c["mamba_d_state"])


def tree_spec(c: dict) -> Dict[str, Shape]:
    """Path -> shape of every leaf, in the order of the flattened tree."""
    d, f, V = c["hidden_size"], c["intermediate_size"], c["vocab_size"]
    hd = d // c["num_attention_heads"]
    q, kv = c["num_attention_heads"] * hd, c["num_key_value_heads"] * hd
    d_in, H, N, conv = mamba_dims(c)
    ks = kinds(c)
    spec = {"embed": (V, d), "final_ln/scale": (d,)}
    for kind, per_layer in (
            ("attn", {"attn/wq": (d, q), "attn/wk": (d, kv),
                      "attn/wv": (d, kv), "attn/wo": (q, d)}),
            ("mamba_mlp", {
                "mixer/in_proj": (d, 2 * d_in + 2 * N + H),
                "mixer/conv_w": (c["mamba_d_conv"], conv),
                "mixer/conv_b": (conv,), "mixer/A_log": (H,),
                "mixer/D": (H,), "mixer/dt_bias": (H,),
                "mixer/norm/scale": (d_in,), "mixer/out_proj": (d_in, d)})):
        n = ks.count(kind)
        if not n:
            continue
        per_layer.update({"ln1/scale": (d,), "ln2/scale": (d,),
                          "mlp/w_gate": (d, f), "mlp/w_up": (d, f),
                          "mlp/w_down": (f, d)})
        spec.update({f"{kind}/{p}": (n, *s) for p, s in per_layer.items()})
    return dict(sorted(spec.items()))


def dtype_of(path: str, c: dict):
    name = path.rsplit("/", 1)[-1]
    return jnp.dtype("float32" if name in F32_LEAVES else c["param_dtype"])


def _leaf(key, index: int, path: str, shape: Shape, dtype, embed_mult: float):
    k = jax.random.fold_in(key, index)
    name = path.rsplit("/", 1)[-1]
    if name == "A_log":
        w = jnp.log(jax.random.uniform(k, shape, jnp.float32, 1.0, 16.0))
    elif name == "dt_bias":
        dt = jnp.exp(jax.random.uniform(k, shape, jnp.float32,
                                        math.log(1e-3), math.log(1e-1)))
        w = dt + jnp.log(-jnp.expm1(-dt))              # softplus^-1
    else:
        z = jax.random.normal(k, shape, jnp.float32)
        if name in ("scale", "D"):
            w = 1.0 + 0.1 * z
        elif name == "conv_b":
            w = 0.1 * z
        elif name == "embed":
            w = z * shape[-1] ** -0.5 / embed_mult
        else:
            w = z * shape[-2] ** -0.5
    return w.astype(dtype)


@partial(jax.jit, static_argnames=("paths", "shapes", "dtypes", "embed_mult"))
def _make(key, *, paths: Tuple[str, ...], shapes: Tuple[Shape, ...],
          dtypes: tuple, embed_mult: float) -> List[jax.Array]:
    return [_leaf(key, i, p, s, t, embed_mult)
            for i, (p, s, t) in enumerate(zip(paths, shapes, dtypes))]


def make_params(c: dict, seed: int) -> dict:
    """The whole nested parameter tree of config ``c``, in one jitted call."""
    spec = tree_spec(c)
    leaves = _make(seed_key(seed), paths=tuple(spec),
                   shapes=tuple(spec.values()),
                   dtypes=tuple(dtype_of(p, c) for p in spec),
                   embed_mult=float(c["embedding_multiplier"]))
    tree: dict = {}
    for path, leaf in zip(spec, leaves):
        node = tree
        *parents, last = path.split("/")
        for p in parents:
            node = node.setdefault(p, {})
        node[last] = leaf
    return tree
