"""Dirty-box copy kernel — the pre-copy merge, in place.

The merge writes the dirty blocks of a live leaf into its shadow. Copying
more than the dirty blocks is always correct (a clean block of the live
leaf equals the shadow's), so each dirty block is rounded out to a *box*
that a DMA can copy in the leaf's own device layout, and only the boxes
are written: into the shadow's donated buffer, through an alias, from the
live leaf where it lies. Time and bytes follow the dirty bytes: a clean
leaf costs a dispatch, a wholly dirty leaf one copy of the live leaf.

The leaf is read in its physical order (``layout``: the device's dim
order, a transpose that is a bitcast, and the rows of one tile). The
physical array is viewed as ``(units, rows, lanes)``, leading dims
collapsed, and a box is a run of whole units:

- a unit is a whole (second-minor, minor) slab, or ``tile`` rows of it
  where the blocks split the second-minor dim;
- a box is one unit, or ``group`` consecutive units where the blocks span
  several slabs (a KV cache's 16 positions x 8 heads x 128);
- where the layout puts a dim that the blocks split as the minor dim (a
  KV cache with its positions minor), a box is also cut to one 128-lane
  tile of it, and the boxes form ``lanes`` columns.

A leaf a DMA cannot slice this way (rank under 2, a minor dim that is no
multiple of 128, a second-minor dim that is no multiple of a tile) is one
box: copied whole when any of its blocks is dirty.

The kernel walks, column by column, a bitmap of where the box mask turns
on and off (32 boxes a word, clean columns and words skipped), so each run
of dirty boxes costs two bits and a few copies: a run of ``n`` units is
copied as ``n``'s digits in base ``RADIX``, a copy of ``RADIX**j`` units
for each unit of digit ``j``, all of a run's copies in flight at once.
"""
from __future__ import annotations

import functools
import math
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import backend as kb

LANES = 128
#: boxes one word of the edge bitmap holds
BITS = 32
#: copy sizes grow by this factor: a run is copied as its digits in this
#: base, a copy of ``RADIX**j`` units for each unit of digit ``j``
RADIX = 16
#: words of edge bitmap a leaf may hold in SMEM (256 KB of its 1 MB)
MAX_WORDS = 1 << 16


def layout(shape: Tuple[int, ...], dtype) -> Tuple[Tuple[int, ...], int]:
    """The default device layout of a ``shape``/``dtype`` array on the
    default backend: its dims major to minor, and the rows of one tile (8
    where the layout has no 2-D tile)."""
    from jax.experimental.layout import Layout
    dev = jax.devices()[0]
    got = Layout.from_pjrt_layout(dev.client.get_default_layout(
        np.dtype(dtype), tuple(shape), dev))
    tiles = [t for t in got.tiling if len(t) == 2]
    return tuple(got.major_to_minor), tiles[0][0] if tiles else 8


class Plan(NamedTuple):
    perm: Tuple[int, ...]       # the physical dims, major to minor
    view: Optional[Tuple[int, int, int]]   # (units, rows, minor); None: whole
    group: int                  # units a box holds
    lanes: int                  # 128-lane columns of boxes (1: whole rows)
    cells: Tuple[int, ...]      # each logical dim's extent in one box
    fine: Tuple[int, int]       # (t, k) of ``_fine``
    box_elems: int              # elements of one dirty block's boxes, at most


def _fine(shape: Tuple[int, ...], block: int) -> Tuple[int, int]:
    """The mask's finest product grid: ``(t, k)`` such that flat groups of
    ``k * prod(shape[t:])`` elements (a divisor of ``block``) are runs of
    ``k`` indices of dim ``t - 1`` with every index of the dims after it;
    ``t == 0`` when the leaf lies within one block."""
    for t in range(len(shape) + 1):
        inner = math.prod(shape[t:])
        if block % inner == 0:
            return (t, 1) if t == 0 else (t, math.gcd(block // inner,
                                                      shape[t - 1]))
    raise AssertionError("unreachable: the empty product divides")


def _extent(fine: Tuple[int, int], d: int) -> Optional[int]:
    """Indices of dim ``d`` that one entry of the fine grid spans: 1, or
    ``k`` for dim ``t - 1``; None for the dims after it (every index)."""
    t, k = fine
    if d >= t:
        return None
    return k if d == t - 1 else 1


def _reach(shape, fine, block: int, q) -> int:
    """Boxes one dirty block reaches, at most: exact where a block is one
    entry of the fine grid, a bound where blocks straddle its rows."""
    t, k = fine
    fine_shape = shape[:t - 1] + (shape[t - 1] // k,) if t else ()
    entries = block // (k * math.prod(shape[t:]))  # fine entries a block
    boxes = 1
    for d, s in enumerate(shape):
        e, n = _extent(fine, d), -(-s // q[d])
        if e is None:
            boxes *= n
        elif e % q[d] == 0:        # each entry fills e / q whole boxes
            span = 1 if entries == 1 else min(
                fine_shape[d], (entries - 1) // math.prod(
                    fine_shape[d + 1:]) + 2)
            boxes *= min(n, span * e // q[d])
        elif entries == 1:         # one entry, aligned, inside a box
            boxes *= 1 if q[d] % e == 0 else min(n, -(-e // q[d]) + 1)
        else:                      # a run that may wrap: two ranges
            span = min(fine_shape[d], (entries - 1) // math.prod(
                fine_shape[d + 1:]) + 2)
            boxes *= min(n, -(-span * e // q[d]) + 2)
    return boxes


def plan(shape: Tuple[int, ...], dtype, block: int) -> Plan:
    """How the kernel copies the dirty boxes of a leaf of ``shape`` in the
    default device layout."""
    shape = tuple(shape) or (1,)
    return _plan(shape, block, *layout(shape, dtype))


@functools.lru_cache(maxsize=1024)
def _plan(shape: Tuple[int, ...], block: int, perm: Tuple[int, ...],
          tile: int) -> Plan:
    n = len(shape)
    p = tuple(shape[d] for d in perm)
    fine = _fine(shape, block)
    size = math.prod(shape)
    whole = Plan(perm, None, 1, 1, shape, fine, size)
    if n < 2 or p[-1] % LANES or p[-2] % tile:
        return whole

    def split(d):                  # the blocks cut dim ``d``
        e = _extent(fine, d)
        return e is not None and e < shape[d]

    m, r = perm[-1], perm[-2]
    lanes = p[-1] // LANES if m != n - 1 and split(m) else 1
    rows = split(r)
    q = [1] * n
    q[m] = LANES if lanes > 1 else shape[m]
    q[r] = tile if rows else shape[r]
    whole_inside = not rows        # boxes stay contiguous over the units
    for d in reversed(perm[:-2]):
        if not whole_inside:
            break
        q[d] = _extent(fine, d) if split(d) else shape[d]
        whole_inside = q[d] == shape[d]
    units = math.prod(p[:-2]) * (p[-2] // tile if rows else 1)
    group = 1 if rows else math.prod(q[d] for d in perm[:-2])
    if lanes * -(-units // group // BITS) > MAX_WORDS:
        return whole
    box = math.prod(q)
    return Plan(perm, (units, tile if rows else p[-2], p[-1]), group, lanes,
                tuple(q), fine,
                min(size, box * _reach(shape, fine, block, q)))


def _box_mask(dirty: jnp.ndarray, shape, block: int,
              p: Plan) -> jnp.ndarray:
    """(lanes, boxes) bool: the boxes any dirty block reaches."""
    shape = tuple(shape) or (1,)
    t, k = p.fine
    h = k * math.prod(shape[t:])
    if t == 0:
        grid = dirty[:1].reshape(())
    else:
        fine_shape = shape[:t - 1] + (shape[t - 1] // k,)
        e = dirty if h == block else jnp.broadcast_to(
            dirty[:, None], (dirty.shape[0], block // h)).reshape(-1)
        grid = e[:math.prod(fine_shape)].reshape(fine_shape)
    # each dim of the fine grid from its entries to its boxes
    for d in range(t):
        f, q = (k if d == t - 1 else 1), p.cells[d]
        if f == q:
            continue
        g = math.gcd(f, q)
        boxes = -(-shape[d] // q)
        x = jnp.moveaxis(grid, d, -1)
        if f > g:
            x = jnp.repeat(x, f // g, axis=-1)
        if boxes * q // g > x.shape[-1]:
            x = jnp.pad(x, [(0, 0)] * (x.ndim - 1)
                        + [(0, boxes * q // g - x.shape[-1])])
        x = x.reshape(x.shape[:-1] + (boxes, q // g)).any(-1)
        grid = jnp.moveaxis(x, -1, d)
    # the dims after it (each entry spans them whole) in the device order
    grid = grid.reshape(grid.shape + (1,) * (len(shape) - t))
    grid = jnp.transpose(grid, p.perm)
    cells = tuple(-(-shape[d] // p.cells[d]) for d in p.perm)
    grid = jnp.broadcast_to(grid, cells)
    return grid.reshape(-1, cells[-1]).T


def _edges(mask: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """(lanes, boxes) mask -> the words of its edge bitmap (a bit where a
    run of dirty boxes starts, and one past where it ends), flattened
    column by column, and each column's any."""
    c, nb = mask.shape
    ext = jnp.pad(mask, ((0, 0), (1, 1)))
    e = ext[:, 1:] != ext[:, :-1]             # boxes + 1 positions
    w = -(-(nb + 1) // BITS)
    e = jnp.pad(e, ((0, 0), (0, w * BITS - nb - 1))).reshape(c, w, BITS)
    bits = (e.astype(jnp.uint32) << jnp.arange(BITS, dtype=jnp.uint32)
            ).sum(-1, dtype=jnp.uint32)
    return (lax.bitcast_convert_type(bits, jnp.int32).reshape(-1),
            mask.any(-1).astype(jnp.int32))


def _kernel(edges_ref, cols_ref, new_ref, old_ref, out_ref, run, sems, *,
            boxes: int, group: int, units: int, lanes: int):
    del old_ref                           # the same buffer as ``out_ref``
    words = -(-(boxes + 1) // BITS)
    classes = _classes(units)

    def piece(j, at, c):
        """The copy of ``RADIX**j`` units from unit ``at`` (column ``c``)."""
        size = RADIX ** j
        if lanes > 1:
            cols = pl.ds(pl.multiple_of(c * LANES, LANES), LANES)
            return pltpu.make_async_copy(
                new_ref.at[pl.ds(at, size), :, cols],
                out_ref.at[pl.ds(at, size), :, cols], sems.at[j])
        return pltpu.make_async_copy(new_ref.at[pl.ds(at, size)],
                                     out_ref.at[pl.ds(at, size)],
                                     sems.at[j])

    def emit(b0, b1, c):
        """Copy boxes [b0, b1) of column ``c``: every copy started, then
        waited for."""
        at = b0 * group
        n = jnp.minimum(b1 * group, units) - at
        digits = [n // RADIX ** j % RADIX if j < classes - 1
                  else n // RADIX ** j for j in range(classes)]
        for j in reversed(range(classes)):
            def start(i, at, j=j):
                piece(j, at, c).start()
                return at + RADIX ** j
            at = lax.fori_loop(0, digits[j], start, at)
        for j in range(classes):
            def wait(i, carry, j=j):
                piece(j, 0, 0).wait()
                return carry
            lax.fori_loop(0, digits[j], wait, 0)

    def column(c, carry):
        run[0] = -1                       # first box of the open run

        def word(w, carry):
            def edge(x):
                at = w * BITS + 31 - lax.clz(x & -x)
                start = run[0]

                @pl.when(start >= 0)
                def _():
                    emit(start, at, c)

                run[0] = jnp.where(start >= 0, -1, at)
                return x & (x - 1)

            lax.while_loop(lambda x: x != 0, edge, edges_ref[c * words + w])
            return carry

        @pl.when(cols_ref[c] != 0)
        def _():
            lax.fori_loop(0, words, word, 0)
        return carry

    if lanes > 1:
        lax.fori_loop(0, lanes, column, 0)
    else:
        column(0, 0)


def _classes(units: int) -> int:
    """Copy sizes: ``RADIX**j`` units for ``j`` below this, the largest at
    most ``units``."""
    classes = 1
    while RADIX ** classes <= units:
        classes += 1
    return classes


def _whole_kernel(any_ref, new_ref, old_ref, out_ref, sem):
    del old_ref

    @pl.when(any_ref[0] != 0)
    def _():
        copy = pltpu.make_async_copy(new_ref, out_ref, sem)
        copy.start()
        copy.wait()


def copy_boxes(new: jnp.ndarray, old: jnp.ndarray, dirty: jnp.ndarray,
               block: int, *, interpret=None) -> jnp.ndarray:
    """``old`` with every box of ``new`` that a dirty block reaches copied
    over it, written in place where ``old``'s buffer is donated (same
    shape and dtype; ``dirty`` the ``(n_blocks,)`` mask over flat blocks of
    ``block`` elements). Traceable inside a jitted caller.

    ``interpret=None`` auto-detects: compiled on TPU, interpret mode
    everywhere else."""
    shape = old.shape
    p = plan(shape, old.dtype, block)
    phys = tuple((shape or (1,))[d] for d in p.perm)
    inverse = tuple(np.argsort(p.perm))

    def physical(x):
        return jnp.transpose(x.reshape(shape or (1,)), p.perm)

    interpret = kb.resolve_interpret(interpret)
    if p.view is None:
        out = pl.pallas_call(
            _whole_kernel,
            out_shape=jax.ShapeDtypeStruct(phys, old.dtype),
            in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM),
                      pl.BlockSpec(memory_space=pl.ANY),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec(memory_space=pl.ANY),
            scratch_shapes=[pltpu.SemaphoreType.DMA(())],
            input_output_aliases={2: 0},
            interpret=interpret,
            name="copy_dirty_boxes",
        )(jnp.any(dirty).astype(jnp.int32).reshape(1), physical(new),
          physical(old))
        return jnp.transpose(out, inverse).reshape(shape)
    units = p.view[0]
    edges, cols = _edges(_box_mask(dirty, shape, block, p))
    boxes = -(-units // p.group)
    classes = _classes(units)
    out = pl.pallas_call(
        functools.partial(_kernel, boxes=boxes, group=p.group, units=units,
                          lanes=p.lanes),
        out_shape=jax.ShapeDtypeStruct(p.view, old.dtype),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM),
                  pl.BlockSpec(memory_space=pltpu.SMEM),
                  pl.BlockSpec(memory_space=pl.ANY),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        scratch_shapes=[pltpu.SMEM((1,), jnp.int32),
                        pltpu.SemaphoreType.DMA((classes,))],
        input_output_aliases={3: 0},
        interpret=interpret,
        name="copy_dirty_boxes",
    )(edges, cols, physical(new).reshape(p.view),
      physical(old).reshape(p.view))
    return jnp.transpose(out.reshape(phys), inverse).reshape(shape)


def copied_bytes(shape, dtype, block: int, dirty_blocks: int) -> int:
    """Bytes the merge writes for ``dirty_blocks`` dirty blocks of a leaf,
    boxes and rounding included: a box per dirty block, at most the
    leaf (a bound where a leaf's blocks straddle its rows)."""
    if not dirty_blocks:
        return 0
    p = plan(shape, dtype, block)
    size = math.prod(shape)
    return jnp.dtype(dtype).itemsize * min(size, dirty_blocks * p.box_elems)
