"""Algorithm 2 — identification of the live-migration moment (paper §5.2).

``postpone(model, m_current)`` computes the paper's ``RemainTime``: zero when
the workload's current relative moment sits in ArrayLM, otherwise the
distance to the first suitable moment. We also handle the wrap-around case
the paper leaves implicit (current moment past the last LM instant of the
cycle -> wait into the next cycle) and an all-NLM guard (returns ``period``
as a one-full-cycle backoff).

A vectorized jit variant classifies a whole fleet in one call (used by the
Fig. 10 scalability benchmark).

Two consumers, one algorithm: the LMCM's per-request decide path calls
``postpone`` directly (defer the request, re-decide at the trough), and the
receding-horizon admission controller reads the same RemainTime through
``SurveillanceEngine.next_trough`` — there it is a PRICE, not a verdict:
"launch now" and "launch at the trough T+RemainTime" are two columns of one
scored what-if batch, so Alg. 2's timing and the fabric's contention are
weighed in the same currency (predicted bytes) instead of in sequence.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.cycles import CycleModel
from repro.spans import scope


def postpone(model: CycleModel, m_current: int) -> int:
    """RemainTime in samples until the next suitable (LM) moment."""
    if model.period <= 1:
        return 0 if model.profile_lm.any() else int(model.period or 1)
    m_rel = int(m_current) % model.period
    if model.profile_lm[m_rel] == 1:
        return 0                                     # already suitable
    if len(model.array_lm) == 0:
        return model.period                          # acyclically busy: back off
    greater = model.array_lm[model.array_lm > m_rel]
    nxt = int(greater[0]) if len(greater) else int(model.array_lm[0]) + model.period
    return nxt - m_rel


def postpone_batch(profiles: jnp.ndarray, periods: jnp.ndarray,
                   m_current: jnp.ndarray) -> jnp.ndarray:
    """Vectorized Algorithm 2 over a fleet.

    profiles: (J, P_max) int8 (1=LM), padded with -1 beyond each period;
    periods: (J,) int32; m_current: (J,) int32. Returns (J,) RemainTime.
    """
    J, P_max = profiles.shape
    with scope("postpone"):
        m_rel = m_current % jnp.maximum(periods, 1)

        idx = jnp.arange(P_max)[None, :]
        valid = idx < periods[:, None]
        is_lm = (profiles == 1) & valid
        # distance from m_rel to each LM phase, wrapping within the period
        dist = (idx - m_rel[:, None]) % jnp.maximum(periods, 1)[:, None]
        dist = jnp.where(is_lm, dist, jnp.iinfo(jnp.int32).max)
        remain = jnp.min(dist, axis=1)
        none_lm = ~jnp.any(is_lm, axis=1)
        remain = jnp.where(none_lm, periods, remain)       # all-NLM backoff
        return jnp.where(periods <= 1, 0, remain).astype(jnp.int32)


postpone_batch_jit = jax.jit(postpone_batch)


def pack_fleet(models, *, n_jobs=None, p_max=None) -> tuple:
    """CycleModels -> padded arrays for ``postpone_batch``.

    ``n_jobs``/``p_max`` optionally pad the job/period axes beyond the
    fleet's own extent (the surveillance engine buckets both to powers of
    two so the jit cache stays bounded); padding rows have period 0 and
    all-(-1) profiles, which ``postpone_batch`` maps to RemainTime 0.
    """
    p_req = max((m.period for m in models if m.period > 1), default=1)
    p_max = max(p_max or 1, p_req, 1)
    n_jobs = max(n_jobs or len(models), len(models))
    profiles = np.full((n_jobs, p_max), -1, np.int8)
    periods = np.zeros(n_jobs, np.int32)
    for j, m in enumerate(models):
        periods[j] = m.period
        if m.period > 1:
            profiles[j, : m.period] = m.profile_lm
    return jnp.asarray(profiles), jnp.asarray(periods)
