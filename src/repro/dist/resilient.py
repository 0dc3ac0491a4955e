"""Straggler/shard-loss degradation for the mesh halo exchange.

``halo_aggregate`` is the efficient collective (cut-edge rows only), but it
is also the fragile one: it needs every shard of the ``all_to_all`` to show
up.  :func:`resilient_halo_aggregate` is the drop-in wrapper that degrades
instead of hanging — but no longer in one shot: a faulted exchange walks the
:class:`repro.dist.elastic.RetryPolicy` ladder (seeded, bounded exponential
backoff + jitter charged to a :class:`~repro.dist.elastic.ModeledClock`)
before the *affected step* is recomputed through ``allgather_aggregate``,
which ships the full feature table and depends on no per-shard send tables.
A transient fault therefore recovers on the halo path at retry cost; only a
fault that outlives the ladder (or the ``budget_s`` delay budget) degrades
the step.  Persistent faults are the membership state machine's business:
:class:`repro.dist.elastic.ElasticAggregator` evicts and repartitions.

``timeout_s`` survives as the legacy alias for the ladder's delay budget.
The old implementation force-materialized the halo result
(``block_until_ready``), compared wall clock against the budget, and on
overrun *discarded the finished compute* and ran a full allgather on top —
one straggler cost two collectives plus a sync, and the wall-clock read made
chaos drills nondeterministic.  The ladder charges stragglers to the modeled
clock instead: no double compute, no wall-time in the deterministic path.

Every retry counts ``dist.halo_retry{kind=...}``; every degraded step counts
``dist.halo_fallback{reason=...}`` and drops a trace instant, so a drill (or
production) can audit exactly which steps retried and which degraded.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax

from .. import obs
from ..chaos import inject as chaos
from .elastic import FAULT_KINDS, ModeledClock, RetryPolicy
from .halo import allgather_aggregate, halo_aggregate


def _fallback(mesh, x, plan, local_n, axis_name, reason: str) -> jax.Array:
    obs.counter("dist.halo_fallback", reason=reason).inc()
    obs.instant("dist.halo_fallback", cat="dist", reason=reason)
    return allgather_aggregate(mesh, x, plan, local_n, axis_name)


def resilient_halo_aggregate(mesh, x, plan, send, local_n,
                             axis_name: Optional[str] = None,
                             timeout_s: Optional[float] = None, *,
                             policy: Optional[RetryPolicy] = None,
                             clock: Optional[ModeledClock] = None,
                             step: int = 0) -> jax.Array:
    """``halo_aggregate`` with a deterministic retry ladder and per-step
    fallback to ``allgather_aggregate``.

    A ``dist.halo`` fault (shard loss or straggler) is retried up to
    ``policy.max_retries`` times with seeded exponential backoff charged to
    ``clock`` (modeled time — never wall time); if the fault persists
    through the ladder, or the accumulated backoff would exceed
    ``policy.budget_s``, the step degrades to the all-gather path.  A real
    exchange exception degrades immediately (it already burned the
    attempt).  ``timeout_s`` is the legacy alias for ``budget_s``.
    """
    if policy is None:
        policy = RetryPolicy(budget_s=timeout_s)
    elif timeout_s is not None and policy.budget_s is None:
        policy = dataclasses.replace(policy, budget_s=timeout_s)
    clock = clock or ModeledClock()
    waited = 0.0
    for attempt in range(policy.max_retries + 1):
        f = chaos.fire("dist.halo")
        if f is not None and f.kind in FAULT_KINDS:
            if attempt == policy.max_retries:
                return _fallback(mesh, x, plan, local_n, axis_name, f.kind)
            delay = policy.backoff(step, attempt)
            if (policy.budget_s is not None
                    and waited + delay > policy.budget_s):
                return _fallback(mesh, x, plan, local_n, axis_name, f.kind)
            waited += delay
            clock.advance(delay)
            obs.counter("dist.halo_retry", kind=f.kind).inc()
            continue
        try:
            return halo_aggregate(mesh, x, plan, send, local_n, axis_name)
        except Exception:
            return _fallback(mesh, x, plan, local_n, axis_name,
                             "exchange_error")
    return _fallback(mesh, x, plan, local_n, axis_name, "retries_exhausted")
