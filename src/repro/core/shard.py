"""Sharded decide plane — row-partitioning surveillance across devices.

Every stage of the surveillance pipeline (NB classify, matmul-DFT spectrum,
autocorrelation refinement, Algorithm 2 postponement) is embarrassingly
parallel per job row: no stage reduces across jobs. That makes the scaling
story trivial to state and strong to test — partitioning the job axis over
a 1-D device mesh with ``shard_map`` produces BIT-IDENTICAL results to the
single-device path, which stays in the tree as the parity reference.

This module owns the mesh plumbing so the engine and the kernels never
repeat it:

  * ``decide_mesh(shards)`` — build the 1-D ``('shard',)`` mesh over the
    first ``shards`` local devices (``None``/``<=1`` -> no mesh, i.e. the
    single-device reference path). On a CPU host, virtual devices come from
    ``XLA_FLAGS=--xla_force_host_platform_device_count=N`` (set before jax
    initializes — see ``scripts/verify.sh`` and the fig10 shard cells).
  * ``classify_lm(nb, W, mesh)`` — NB arrays replicated, window rows
    partitioned; the shard_map body is the same jitted
    ``characterize._nb_predict_lm`` the unsharded path runs.
  * ``postpone_rows(profiles, periods, m_now, mesh)`` — Algorithm 2 with
    all three row-aligned operands partitioned. Returns the DEVICE array
    unmaterialized so overlapped ticks can defer the host sync
    (``surveillance.TickResult``).

The kernel stages (spectrum/autocorr) take the mesh directly via
``kernels.ops`` (``cycles.fit_cycle_batch(..., mesh=...)``); padding there
follows the same rows-to-multiple-of-mesh rule as ``_pad_rows`` here.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import characterize
from repro.core import postpone as pp


def device_count() -> int:
    """Visible local device count (virtual CPU devices included)."""
    return len(jax.devices())


def decide_mesh(shards: Optional[int] = None):
    """1-D ``('shard',)`` mesh over the first ``shards`` local devices.

    ``None`` or ``<= 1`` returns ``None`` — callers then take the
    single-device reference path unchanged. Asking for more shards than
    visible devices is an error (forcing virtual devices is an env-level
    decision, not something to guess at here).
    """
    if shards is None or shards <= 1:
        return None
    devs = jax.devices()
    if shards > len(devs):
        if devs[0].platform == "cpu":
            hint = ("set XLA_FLAGS=--xla_force_host_platform_device_count="
                    f"{shards} before jax initializes to fake them on CPU")
        else:
            hint = (f"this {devs[0].platform} host has {len(devs)}; ask for "
                    "at most that many shards")
        raise ValueError(f"requested {shards} shards but only {len(devs)} "
                         f"devices are visible: {hint}")
    from jax.sharding import Mesh
    return Mesh(np.asarray(devs[:shards]), ("shard",))


def _pad_rows(x: jnp.ndarray, n: int) -> Tuple[jnp.ndarray, int]:
    """Pad axis 0 to a multiple of ``n``; returns (padded, original_rows).
    Row stages never mix rows, so zero padding cannot perturb real rows."""
    B = x.shape[0]
    B_p = -(-B // n) * n
    if B_p != B:
        x = jnp.pad(x, ((0, B_p - B),) + ((0, 0),) * (x.ndim - 1))
    return x, B


def classify_lm(nb: characterize.NaiveBayes, windows, mesh=None) -> np.ndarray:
    """(J, T, F) windows -> (J, T) int8 LM series, optionally row-sharded.

    ``mesh=None`` is the single-device reference; with a mesh the NB tables
    are replicated and the job rows partitioned. Bit-identical either way —
    NB decisions are per-sample.
    """
    if mesh is None:
        return characterize.classify_lm_batch(nb, windows)
    from jax.sharding import PartitionSpec as P
    axis = mesh.axis_names[0]
    x, J = _pad_rows(jnp.asarray(windows, jnp.float32),
                     int(mesh.devices.size))
    body = functools.partial(characterize._nb_predict_lm,
                             block=characterize.CLASSIFY_BLOCK)
    fn = jax.shard_map(body, mesh=mesh, in_specs=(P(), P(), P(), P(axis)),
                       out_specs=P(axis), check_vma=False)
    return np.asarray(fn(nb.bin_edges, nb.log_likelihood, nb.log_prior,
                         x))[:J]


def postpone_rows(profiles, periods, m_now, mesh=None) -> jnp.ndarray:
    """Algorithm 2 over the packed fleet, optionally row-sharded.

    Returns the device array WITHOUT a host sync: with jax's async
    dispatch the decide of tick t executes while the caller records/
    gathers tick t+1 (``SurveillanceEngine`` materializes lazily).
    Padding rows carry period 0, which Algorithm 2 maps to RemainTime 0
    independent of ``m_now``.
    """
    m_now = jnp.asarray(m_now)
    if mesh is None:
        return pp.postpone_batch_jit(profiles, periods, m_now)
    from jax.sharding import PartitionSpec as P
    axis = mesh.axis_names[0]
    n = int(mesh.devices.size)
    prof, J = _pad_rows(jnp.asarray(profiles), n)
    per, _ = _pad_rows(jnp.asarray(periods), n)
    m, _ = _pad_rows(m_now, n)
    out = jax.shard_map(pp.postpone_batch_jit, mesh=mesh,
                        in_specs=(P(axis), P(axis), P(axis)),
                        out_specs=P(axis), check_vma=False)(prof, per, m)
    return out[:J]
