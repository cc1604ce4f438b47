"""Block-level pre-copy live migration of a *live* sharded pytree.

This is the paper's migration algorithm (§3.2) re-targeted at TPU job state
(params + optimizer + caches): while the job keeps stepping, state blocks
that changed since the last round ("dirty pages") are re-copied to the
destination buffer; Xen's three stop conditions end the iterative phase and
a final stop-and-copy (the only pause the job sees) transfers the last dirty
set. The result is bit-exact: the destination pytree equals the source at
the moment of the final copy (tested in tests/test_precopy.py).

Block diffing is the memory-bound hot loop -> Pallas kernel
(``repro.kernels.dirty_delta``), with a jnp fallback on hosts without it.

Time accounting is dual: wall-clock (real copies) and a bandwidth model
(bytes / link-bandwidth) so fleet-scale costs can be projected from smoke
runs — the same separation the paper uses between testbed runs and the
1,000-VM trace analysis.
"""
from __future__ import annotations

import dataclasses
import time
from dataclasses import dataclass
from functools import partial
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.strunk import (MigrationOutcome, XEN_MAX_ROUNDS,
                               XEN_STOP_DIRTY_PAGES, XEN_STOP_TOTAL_FACTOR)
from repro.kernels import ops as kops


@dataclass(frozen=True)
class PrecopyConfig:
    block_elems: int = 1 << 14                 # "page" size, in elements
    max_rounds: int = XEN_MAX_ROUNDS
    stop_dirty_blocks: int = XEN_STOP_DIRTY_PAGES
    stop_total_factor: float = XEN_STOP_TOTAL_FACTOR
    bandwidth: float = 50e9                    # modeled ICI link, bytes/s
    steps_per_round: int = 1                   # job steps overlapped per round


# ---------------------------------------------------------------------------
# flat block view of a pytree
# ---------------------------------------------------------------------------
def _blocks(leaf: jnp.ndarray, nb: int, block: int) -> jnp.ndarray:
    """(any shape) leaf -> (nb, block) zero-padded view of its flat data.
    Traced inside the jitted scan/merge, so no flattened copy of the whole
    state is ever materialized at once: that copy would double the state's
    HBM footprint, beyond one chip for a 4.6 GB replica."""
    flat = leaf.reshape(-1)
    return jnp.pad(flat, (0, nb * block - flat.shape[0])).reshape(nb, block)


@partial(jax.jit, static_argnums=(2,))
def _leaf_dirty(new: jnp.ndarray, old: jnp.ndarray, block: int) -> jnp.ndarray:
    """Leaf pair (same shape) -> (nb,) bool dirty mask over its flat blocks."""
    nb = -(-new.size // block)
    return kops.dirty_blocks(_blocks(new, nb, block),
                             _blocks(old.astype(new.dtype), nb, block))


@partial(jax.jit, static_argnums=(3,), donate_argnums=(1,))
def _leaf_merge(new: jnp.ndarray, old: jnp.ndarray, dirty: jnp.ndarray,
                block: int) -> jnp.ndarray:
    """Copy dirty blocks of ``new`` over ``old`` (the 'network transfer').
    ``old``'s buffer is donated to the result, so a round holds one shadow
    copy of the state, not two."""
    nb = dirty.shape[0]
    out = jnp.where(dirty[:, None], _blocks(new, nb, block),
                    _blocks(old.astype(new.dtype), nb, block))
    return out.reshape(-1)[: new.size].reshape(old.shape).astype(old.dtype)


def dirty_scan(live, shadow, block: int) -> Tuple[List[jnp.ndarray], int, int]:
    """Per-leaf dirty masks + (dirty_blocks, dirty_bytes) totals."""
    masks, n_dirty, n_bytes = [], 0, 0
    for new, old in zip(jax.tree.leaves(live), jax.tree.leaves(shadow)):
        m = _leaf_dirty(new, old, block)
        masks.append(m)
        d = int(jnp.sum(m))
        n_dirty += d
        n_bytes += d * block * new.dtype.itemsize
    return masks, n_dirty, n_bytes


def merge_dirty(live, shadow, masks: List[jnp.ndarray], block: int):
    """Shadow with every dirty block of ``live`` copied over it. Consumes
    ``shadow``: its leaves are donated to the result."""

    def align(n, o):
        """The cross-placement transfer: move live data onto the destination
        sharding before merging (this IS the network copy)."""
        if getattr(n, "sharding", None) != getattr(o, "sharding", None):
            n = jax.device_put(n, o.sharding)
        return n

    leaves = jax.tree.leaves(shadow)
    merged = [_leaf_merge(align(n, o), o, m, block)
              for n, o, m in zip(jax.tree.leaves(live), leaves, masks)]
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

    # round 0: full copy (iterative-copy stage, first iteration)
    shadow = place(jax.tree.map(jnp.array, live))
    sent = v_mem
    sim_t = v_mem / cfg.bandwidth
    per_round = [v_mem]
    rounds = 1
    reason = "max_rounds"

    while True:
        if step_fn is not None:            # job keeps running during the copy
            for _ in range(cfg.steps_per_round):
                step_fn()
        live = get_state()
        masks, n_dirty, n_bytes = dirty_scan(live, shadow, cfg.block_elems)
        if n_dirty <= cfg.stop_dirty_blocks:
            reason = "dirty_low"
            break
        if rounds >= cfg.max_rounds:
            reason = "max_rounds"
            break
        if sent + n_bytes > cfg.stop_total_factor * v_mem:
            reason = "total_cap"
            break
        shadow = merge_dirty(live, shadow, masks, cfg.block_elems)
        sent += n_bytes
        sim_t += n_bytes / cfg.bandwidth
        per_round.append(n_bytes)
        rounds += 1

    # stop-and-copy: job paused; transfer the final dirty set
    live = get_state()
    masks, n_dirty, n_bytes = dirty_scan(live, shadow, cfg.block_elems)
    shadow = merge_dirty(live, shadow, masks, cfg.block_elems)
    shadow = jax.block_until_ready(shadow)
    downtime = n_bytes / cfg.bandwidth
    sent += n_bytes
    sim_t += downtime
    per_round.append(n_bytes)

    outcome = MigrationOutcome(total_time=sim_t, downtime=downtime,
                               bytes_sent=float(sent), rounds=rounds,
                               stop_reason=reason)
    report = PrecopyReport(outcome=outcome, wall_time=time.monotonic() - t0,
                           per_round_dirty_bytes=per_round, v_mem=v_mem)
    return shadow, report
