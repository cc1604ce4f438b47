"""Dirty-block scan kernel — the pre-copy inner loop (DESIGN.md §5).

A block of a leaf is dirty when any of its flat elements changed since the
last copy: max |new - old| over the block, in float32, above 0. The scan
is memory-bound (two streaming reads, a small write), so the kernel reads
the leaf and its shadow where they lie, through a view that is a bitcast
of the leaf in the chip's tiled layout: the minor dim (``L`` lanes) and the
second-minor dim stay, the leading dims collapse into rows, or into slabs
where the second-minor dim is below one sublane tile (a KV cache's 8 heads
in bfloat16). No copy of a leaf is made, flattened or padded.

Each grid step reads a tile of about ``TILE_BYTES`` of each input and
writes float32 partial maxima, the same number for every flat block, in
the flat order of the data; a block's max is the max of its partials. With
``k = L / 128`` lane chunks in a row and ``c = block / 128`` in a block:

- ``rows``: a block holds whole rows (``k`` divides ``c``). Every chunk of
  a row, and ``group`` consecutive rows, fold elementwise into one
  128-lane partial; no lane moves.
- ``segments``: a row is longer than a block, or blocks straddle rows (the
  vocabulary-wide minor dim of an LM head). Each chunk reduces across its
  128 lanes to one segment max, laid along the lanes of the output in the
  data's order, and a block is the max of its ``c`` segments.
- ``slabs``: a block holds whole (second-minor, minor) slabs; ``group``
  slabs fold elementwise, then their rows.

``dirty_mask`` is the pre-copy's one entry: the dirty mask of any leaf
pair. A float leaf with a ``plan`` is read in place; a float leaf without
one (a minor dim or a block that is no multiple of 128) goes into the same
kernel through a zero-padded ``(n_blocks, block)`` view of its flat data,
each block padded to whole lane chunks; an integer leaf is compared
exactly on that view (a float32 cast could alias distinct int32 values).
"""
from __future__ import annotations

import functools
import math
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import backend as kb

LANES = 128
#: bytes of each input one grid step reads; two inputs, double-buffered,
#: stay well inside the default scoped VMEM
TILE_BYTES = 1 << 20
#: lane chunks one grid step reduces to segment maxima: a short unrolled
#: body (the LM head's 723 chunks, unrolled, took seconds to trace in
#: every process's set-up; a loop over them read a third as fast)
SEGMENT_CHUNKS = 16


class Plan(NamedTuple):
    mode: str                  # rows | segments | slabs
    view: Tuple[int, ...]      # (R, L) rows, or (P, S, L) slabs
    tile: Tuple[int, ...]      # the view's block one grid step reads
    group: int                 # rows (slabs) folded into one output row
    per_value: int             # flat elements one output value covers
    out_cols: int              # lanes of the output array


def _fit(total: int, unit: int, unit_bytes: int) -> int:
    """Rows of a tile: all ``total`` if they fit ``TILE_BYTES``, else the
    largest multiple of ``unit`` that fits and divides ``total`` (or, when
    none divides, that fits; the last tile is then ragged)."""
    if total * unit_bytes <= TILE_BYTES:
        return total
    most = max(1, TILE_BYTES // (unit * unit_bytes))
    if total % unit == 0:
        n = total // unit
        return unit * max(d for d in range(1, most + 1) if n % d == 0)
    return unit * most


def plan(shape: Tuple[int, ...], dtype, block: int) -> Optional[Plan]:
    """How the kernel reads a leaf of ``shape`` in place, or None."""
    if block % LANES or not shape or shape[-1] % LANES:
        return None
    L = shape[-1]
    k, c = L // LANES, block // LANES
    item = jnp.dtype(dtype).itemsize
    sub = max(8, 32 // item)                  # rows of one sublane tile
    if len(shape) > 2 and shape[-2] % sub:
        P, S = math.prod(shape[:-2]), shape[-2]
        if block % (S * L):
            return None
        tp = _fit(P, 1, S * L * item)
        g = math.gcd(block // (S * L), tp)    # slabs a block holds
        return Plan("slabs", (P, S, L), (tp, S, L), g, g * S * k, LANES)
    R = math.prod(shape[:-1])
    if c % k == 0:
        ts = _fit(R, sub, L * item)
        h = math.gcd(c // k, ts)              # rows a block holds
        return Plan("rows", (R, L), (ts, L), h, h * k, LANES)
    tc = min(k, SEGMENT_CHUNKS) * LANES
    return Plan("segments", (R, L), (_fit(R, sub, tc * item), tc), 1,
                LANES, min(k, LANES) * -(-k // LANES))


def _kernel(new_ref, old_ref, out_ref, *, p: Plan, per_block: int):
    rows, tile = p.view[0], p.tile
    i = pl.program_id(0)

    def diff(j):
        """|new - old| of lane chunk ``j`` of the tile, in float32, with
        the rows past the view's end (a ragged last tile) at 0."""
        cols = slice(j * LANES, (j + 1) * LANES)
        d = jnp.abs(new_ref[..., cols].astype(jnp.float32)
                    - old_ref[..., cols].astype(jnp.float32))
        if rows % tile[0]:
            r = jax.lax.broadcasted_iota(jnp.int32, d.shape, 0)
            d = jnp.where(r < rows - i * tile[0], d, 0.0)
        return d

    if p.mode == "segments":
        # ``per_block`` column steps fill one output block, ``m`` lanes
        # each; chunks past the row's end (a ragged last column tile)
        # land in lanes the fold drops, or in none
        m = tile[-1] // LANES
        step = pl.program_id(1) % per_block
        lane = jax.lax.broadcasted_iota(jnp.int32, out_ref.shape[1:], 1)
        acc = jnp.where(step == 0, 0.0, out_ref[0])
        for j in range(m):
            acc = jnp.where(lane == step * m + j,
                            jnp.max(diff(j), axis=1, keepdims=True), acc)
        out_ref[0] = acc
        return
    acc = diff(0)
    for j in range(1, tile[-1] // LANES):
        acc = jnp.maximum(acc, diff(j))
    # ``group`` consecutive rows (or slabs, with their rows) fold into one
    acc = acc.reshape(-1, p.group, *acc.shape[1:])
    out_ref[0] = jnp.max(acc, axis=tuple(range(1, acc.ndim - 1)))


@functools.partial(jax.jit, static_argnames=("p", "interpret"))
def _partials(new: jnp.ndarray, old: jnp.ndarray, *, p: Plan,
              interpret: bool) -> jnp.ndarray:
    """The plan's float32 partial maxima, (grid steps, rows, lanes) in the
    flat order of the data, as the kernel writes them."""
    tile = p.tile
    steps = -(-p.view[0] // tile[0])
    cols = -(-p.view[-1] // tile[-1])         # > 1 for segments of long rows
    width = min(p.out_cols, LANES)            # lanes of one output block
    per_block = -(-width // (tile[-1] // LANES))   # column steps that fill it
    out_rows = tile[0] // p.group
    in_map = (lambda i, j: (i, j)) if len(tile) == 2 else \
        (lambda i, j: (i, 0, j))
    return pl.pallas_call(
        functools.partial(_kernel, p=p, per_block=per_block),
        out_shape=jax.ShapeDtypeStruct((steps, out_rows, p.out_cols),
                                       jnp.float32),
        grid=(steps, cols),
        in_specs=[pl.BlockSpec(tile, in_map), pl.BlockSpec(tile, in_map)],
        out_specs=pl.BlockSpec((1, out_rows, width),
                               lambda i, j: (i, 0, j // per_block)),
        interpret=interpret,
        name="max_abs_delta",
    )(new.reshape(p.view), old.reshape(p.view))


def block_max(new: jnp.ndarray, old: jnp.ndarray, block: int, *,
              interpret=None) -> jnp.ndarray:
    """Leaf pair (same shape and dtype, with a ``plan``) -> (n_blocks,)
    float32 max |new - old| over each flat block of ``block`` elements,
    the tail block padded with zeros. Traceable inside a jitted caller.

    ``interpret=None`` auto-detects: compiled on TPU, interpret mode
    (lowering validation) everywhere else.
    """
    p = plan(new.shape, new.dtype, block)
    q = _partials(new, old, p=p, interpret=kb.resolve_interpret(interpret))
    if p.mode == "segments":                  # drop the ragged tile's lanes
        q = q[..., :p.view[-1] // LANES]
    per_block = block // p.per_value
    nb = -(-new.size // block)
    q = q.reshape(-1)[:nb * per_block]
    q = jnp.pad(q, (0, nb * per_block - q.size))
    return jnp.max(q.reshape(nb, per_block), axis=1)


def reads_in_place(leaf: jnp.ndarray, block: int) -> bool:
    """Whether ``dirty_mask`` reads ``leaf`` where it lies: a float leaf
    with a ``plan``."""
    return (jnp.issubdtype(leaf.dtype, jnp.floating)
            and plan(leaf.shape, leaf.dtype, block) is not None)


def _block_view(leaf: jnp.ndarray, block: int) -> jnp.ndarray:
    """(any shape) leaf -> (n_blocks, block) zero-padded view of its flat
    data. Traced inside the jitted scan, so no flattened copy of the whole
    state is ever materialized at once: that copy would double the
    state's HBM footprint, beyond one chip for a 4.6 GB replica."""
    flat = leaf.reshape(-1)
    nb = -(-flat.shape[0] // block)
    return jnp.pad(flat, (0, nb * block - flat.shape[0])).reshape(nb, block)


def _view_max(new: jnp.ndarray, old: jnp.ndarray,
              block: int) -> jnp.ndarray:
    """``block_max`` of a leaf pair without a ``plan``, through its
    ``_block_view`` with each block padded with zeros to whole lane
    chunks."""
    new, old = _block_view(new, block), _block_view(old, block)
    lanes = -(-block // LANES) * LANES
    if lanes != block:
        pad = ((0, 0), (0, lanes - block))
        new, old = jnp.pad(new, pad), jnp.pad(old, pad)
    return block_max(new, old, lanes)


def dirty_mask(new: jnp.ndarray, old: jnp.ndarray,
               block: int) -> jnp.ndarray:
    """Leaf pair (same shape and dtype) -> (n_blocks,) bool mask of the
    flat blocks of ``block`` elements in which any element differs.
    Traceable inside a jitted caller."""
    if reads_in_place(new, block):
        return block_max(new, old, block) > 0
    if jnp.issubdtype(new.dtype, jnp.floating):
        return _view_max(new, old, block) > 0
    return jnp.any(_block_view(new, block) != _block_view(old, block),
                   axis=1)
