"""Block-level pre-copy live migration of a *live* sharded pytree.

This is the paper's migration algorithm (§3.2) re-targeted at TPU job state
(params + optimizer + caches): while the job keeps stepping, state blocks
that changed since the last round ("dirty pages") are re-copied to the
destination buffer; Xen's three stop conditions end the iterative phase and
a final stop-and-copy (the only pause the job sees) transfers the last dirty
set. The result is bit-exact: the destination pytree equals the source at
the moment of the final copy (tested in tests/test_precopy.py).

Block diffing is the memory-bound hot loop -> Pallas kernel
(``repro.kernels.dirty_delta``), which reads each float leaf and its shadow
in place, in the leaf's own layout. The merge is a Pallas kernel too
(``repro.kernels.dirty_copy``): it writes only the dirty blocks, rounded
out to boxes a DMA copies, into the donated shadow.

Time accounting is dual: wall-clock (real copies) and a bandwidth model
(bytes / link-bandwidth) so fleet-scale costs can be projected from smoke
runs — the same separation the paper uses between testbed runs and the
1,000-VM trace analysis.
"""
from __future__ import annotations

import dataclasses
import time
from dataclasses import dataclass
from functools import lru_cache, partial
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import AxisType, NamedSharding, PartitionSpec as P

from repro.core.strunk import (MigrationOutcome, XEN_MAX_ROUNDS,
                               XEN_STOP_DIRTY_PAGES, XEN_STOP_TOTAL_FACTOR)
from repro.kernels import dirty_copy, dirty_delta
from repro.spans import enabled, scope, span


@dataclass(frozen=True)
class PrecopyConfig:
    block_elems: int = 1 << 14                 # "page" size, in elements
    max_rounds: int = XEN_MAX_ROUNDS
    stop_dirty_blocks: int = XEN_STOP_DIRTY_PAGES
    stop_total_factor: float = XEN_STOP_TOTAL_FACTOR
    bandwidth: float = 50e9                    # modeled ICI link, bytes/s
    steps_per_round: int = 1                   # job steps overlapped per round


@partial(jax.jit, static_argnums=(2,))
def _leaf_dirty(new: jnp.ndarray, old: jnp.ndarray, block: int) -> jnp.ndarray:
    """Leaf pair (same shape) -> (nb,) bool dirty mask over its flat blocks
    (``kernels/dirty_delta.py``): a float leaf the kernel can view as a
    bitcast of itself is read where it lies."""
    with scope("dirty_scan"):
        return dirty_delta.dirty_mask(new, old.astype(new.dtype), block)


@partial(jax.jit, static_argnums=(3,), donate_argnums=(1,))
def _leaf_merge(new: jnp.ndarray, old: jnp.ndarray, dirty: jnp.ndarray,
                block: int) -> jnp.ndarray:
    """Copy dirty blocks of ``new`` over ``old`` (the 'network transfer').
    ``old``'s buffer is donated to the result and only the boxes the dirty
    blocks round out to are written into it, in place
    (``kernels/dirty_copy.py``): a round holds one shadow copy of the
    state, and a merge costs what is dirty, not what is held."""
    with scope("merge"):
        return dirty_copy.copy_boxes(new.astype(old.dtype), old, dirty, block)


def _leaf_merge_spread(new: jnp.ndarray, old: jnp.ndarray,
                       dirty: jnp.ndarray, block: int) -> jnp.ndarray:
    """``_leaf_merge`` of a leaf spread over several devices, which the
    kernel cannot take: XLA writes the dirty blocks one by one into the
    donated shadow (the last, partial, block as the leaf's last
    ``block`` elements). The flat view it writes through is XLA's
    relayout of the sharded leaf, so the bytes moved follow the leaf; the
    result keeps ``old``'s sharding and buffers. A mesh's Explicit axes
    refuse that flat view, so the leaf passes through the same placement
    with every axis Auto (the same buffers, no copy)."""
    dst = old.sharding
    auto, flags = dst, dirty
    if isinstance(dst, NamedSharding):
        mesh = dst.mesh.update(
            axis_types=(AxisType.Auto,) * len(dst.mesh.axis_names))
        auto = NamedSharding(mesh, dst.spec)
        flags = jax.device_put(dirty, NamedSharding(mesh, P()))
    out = _spread_merge(auto, block)(
        jax.device_put(new, auto), jax.device_put(old, auto, donate=True),
        flags)
    return jax.device_put(out, dst, donate=True)


@lru_cache(maxsize=None)
def _spread_merge(sharding, block: int):
    """The jitted loop of ``_leaf_merge_spread``, its result pinned to
    ``sharding`` so that the donated shadow's buffers take it."""

    def merge(new, old, dirty):
        with scope("merge"):
            src = new.astype(old.dtype).reshape(-1)
            n = min(block, src.shape[0])
            at = jnp.nonzero(dirty, size=dirty.shape[0])[0] * block

            def put(i, flat):
                return lax.dynamic_update_slice(
                    flat, lax.dynamic_slice(src, (at[i],), (n,)), (at[i],))

            return lax.fori_loop(0, jnp.sum(dirty), put,
                                 old.reshape(-1)).reshape(old.shape)

    return jax.jit(merge, out_shardings=sharding, donate_argnums=(1,))


def dirty_scan(live, shadow, block: int
               ) -> Tuple[List[jnp.ndarray], int, int, List[int]]:
    """Per-leaf dirty masks + (dirty_blocks, dirty_bytes) totals + each
    leaf's dirty blocks."""
    masks, counts, n_bytes, syncs = [], [], 0, 0
    leaves = jax.tree.leaves(live)
    with span("precopy.scan", leaves=len(leaves)) as s:
        for new, old in zip(leaves, jax.tree.leaves(shadow)):
            m = _leaf_dirty(new, old, block)
            masks.append(m)
            d = int(jnp.sum(m))
            syncs += 1
            counts.append(d)
            n_bytes += d * block * new.dtype.itemsize
        if enabled():
            inplace = sum(dirty_delta.reads_in_place(leaf, block)
                          for leaf in leaves)
            s.set_metadata(syncs=syncs, dirty_blocks=sum(counts),
                           inplace=inplace)
    return masks, sum(counts), n_bytes, counts


def merge_dirty(live, shadow, masks: List[jnp.ndarray], block: int,
                counts: Optional[List[int]] = None):
    """Shadow with every dirty block of ``live`` copied over it. Consumes
    ``shadow``: its leaves are donated to the result. ``counts``, each
    leaf's dirty blocks, give the span's ``copied_bytes`` when tracing."""

    def align(n, o):
        """The cross-placement transfer: move live data onto the destination
        sharding before merging (this IS the network copy)."""
        if getattr(n, "sharding", None) != getattr(o, "sharding", None):
            n = jax.device_put(n, o.sharding)
        return n

    def merge(o):
        spread = len(o.sharding.device_set) > 1
        return _leaf_merge_spread if spread else _leaf_merge

    leaves = jax.tree.leaves(shadow)
    with span("precopy.merge", leaves=len(leaves)) as s:
        merged = [merge(o)(align(n, o), o, m, block)
                  for n, o, m in zip(jax.tree.leaves(live), leaves, masks)]
        if enabled() and counts is not None:
            s.set_metadata(copied_bytes=sum(
                dirty_copy.copied_bytes(o.shape, o.dtype, block, d)
                for o, d in zip(leaves, counts)))
    return jax.tree.unflatten(jax.tree.structure(shadow), merged)


def total_bytes(state) -> int:
    return sum(l.size * l.dtype.itemsize for l in jax.tree.leaves(state))


# ---------------------------------------------------------------------------
# the engine
# ---------------------------------------------------------------------------
@dataclass
class PrecopyReport:
    outcome: MigrationOutcome
    wall_time: float
    per_round_dirty_bytes: List[int]
    v_mem: int


def migrate(get_state: Callable[[], Any],
            step_fn: Optional[Callable[[], None]],
            cfg: PrecopyConfig = PrecopyConfig(),
            *, placement: Optional[Callable[[Any], Any]] = None
            ) -> Tuple[Any, PrecopyReport]:
    """Pre-copy migrate the state returned by ``get_state`` while ``step_fn``
    keeps mutating it between rounds (the 'live' in live migration).

    ``placement`` optionally maps the destination pytree onto its new
    sharding/devices (e.g. ``lambda t: jax.device_put(t, dst_sharding)``).
    Returns (destination_state, report).
    """
    t0 = time.monotonic()
    place = placement or (lambda t: t)
    live = get_state()
    v_mem = total_bytes(live)
    with span("precopy.migrate", state_bytes=v_mem,
              leaves=len(jax.tree.leaves(live))) as s:
        # round 0: full copy (iterative-copy stage, first iteration)
        shadow = place(jax.tree.map(jnp.array, live))
        sent = v_mem
        sim_t = v_mem / cfg.bandwidth
        per_round = [v_mem]
        rounds = 1
        reason = "max_rounds"

        while True:
            with span("precopy.round", round=rounds):
                if step_fn is not None:    # job keeps running during the copy
                    for _ in range(cfg.steps_per_round):
                        step_fn()
                live = get_state()
                masks, n_dirty, n_bytes, counts = dirty_scan(
                    live, shadow, cfg.block_elems)
                if n_dirty <= cfg.stop_dirty_blocks:
                    reason = "dirty_low"
                    break
                if rounds >= cfg.max_rounds:
                    reason = "max_rounds"
                    break
                if sent + n_bytes > cfg.stop_total_factor * v_mem:
                    reason = "total_cap"
                    break
                shadow = merge_dirty(live, shadow, masks, cfg.block_elems,
                                     counts)
                sent += n_bytes
                sim_t += n_bytes / cfg.bandwidth
                per_round.append(n_bytes)
                rounds += 1

        # stop-and-copy: job paused; transfer the final dirty set
        with span("precopy.stop_copy") as sc:
            live = get_state()
            masks, n_dirty, n_bytes, counts = dirty_scan(
                live, shadow, cfg.block_elems)
            shadow = merge_dirty(live, shadow, masks, cfg.block_elems,
                                 counts)
            shadow = jax.block_until_ready(shadow)
            sent += n_bytes
            if enabled():
                sc.set_metadata(stop=reason, sent_bytes=sent)
        if enabled():
            s.set_metadata(rounds=rounds)
    downtime = n_bytes / cfg.bandwidth
    sim_t += downtime
    per_round.append(n_bytes)

    outcome = MigrationOutcome(total_time=sim_t, downtime=downtime,
                               bytes_sent=float(sent), rounds=rounds,
                               stop_reason=reason)
    report = PrecopyReport(outcome=outcome, wall_time=time.monotonic() - t0,
                           per_round_dirty_bytes=per_round, v_mem=v_mem)
    return shadow, report
