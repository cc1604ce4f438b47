"""Dirty-block scan kernel — the pre-copy inner loop (DESIGN.md §5).

Given the live view and the shadow (last-copied) view of a state shard as
(n_blocks, block) tiles, emit the per-block max |delta| so the migration
engine can mark dirty "pages". Purely memory-bound (2 streaming reads, tiny
write): the Pallas value is the explicit HBM->VMEM pipeline; block tiles are
sized so two input tiles + accumulator fit comfortably in VMEM.

Grid: (row_tiles, col_tiles); col dim innermost so the row accumulator lives
in VMEM scratch across the column sweep and the (n_blocks, 1) result is
written once per row tile.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import backend as kb

ROW_TILE = 8          # blocks per program
COL_TILE = 2048       # elements of the block dim per program (lane-aligned)


def _kernel(new_ref, old_ref, out_ref, acc):
    ci = pl.program_id(1)
    nc = pl.num_programs(1)

    @pl.when(ci == 0)
    def _init():
        acc[...] = jnp.zeros_like(acc)

    d = jnp.abs(new_ref[...].astype(jnp.float32)
                - old_ref[...].astype(jnp.float32))
    acc[...] = jnp.maximum(acc[...], jnp.max(d, axis=1, keepdims=True))

    @pl.when(ci == nc - 1)
    def _emit():
        out_ref[...] = acc[...]


@functools.partial(jax.jit, static_argnames=("interpret",))
def _max_abs_delta(new: jnp.ndarray, old: jnp.ndarray, *,
                   interpret: bool) -> jnp.ndarray:
    nb, blk = new.shape
    rt = min(ROW_TILE, nb)
    ct = min(COL_TILE, blk)
    # pad to tile multiples (padding contributes |0-0| = 0)
    nb_p = -(-nb // rt) * rt
    blk_p = -(-blk // ct) * ct
    if (nb_p, blk_p) != (nb, blk):
        new = jnp.pad(new, ((0, nb_p - nb), (0, blk_p - blk)))
        old = jnp.pad(old, ((0, nb_p - nb), (0, blk_p - blk)))
    out = pl.pallas_call(
        _kernel,
        out_shape=jax.ShapeDtypeStruct((nb_p, 1), jnp.float32),
        grid=(nb_p // rt, blk_p // ct),
        in_specs=[pl.BlockSpec((rt, ct), lambda ri, ci: (ri, ci)),
                  pl.BlockSpec((rt, ct), lambda ri, ci: (ri, ci))],
        out_specs=pl.BlockSpec((rt, 1), lambda ri, ci: (ri, 0)),
        scratch_shapes=[pltpu.VMEM((rt, 1), jnp.float32)],
        interpret=interpret,
        name="max_abs_delta",
    )(new, old)
    return out[:nb]


def max_abs_delta(new: jnp.ndarray, old: jnp.ndarray, *,
                  interpret=None) -> jnp.ndarray:
    """(n_blocks, block) x2 -> (n_blocks, 1) f32 max |new - old| per block.

    ``interpret=None`` auto-detects: compiled on TPU, interpret mode
    (lowering validation) everywhere else.
    """
    return _max_abs_delta(new, old,
                          interpret=kb.resolve_interpret("tpu", interpret))
