"""The program's own trace: host spans and device-side names.

``span(name, **args)`` is a ``jax.profiler.TraceAnnotation`` named
``alma.<name>``. It writes to the profiler's host plane, on the clock of the
device's ``XLA Ops``, so a trace shows what the host was doing while the
chip waited. With no profiler session running it does nothing (about a
microsecond per ``with``). Args are numbers, or a short word such as a stop
reason. A count known only when a span ends is added with ``set_metadata``,
under ``enabled()``, so that tracing off builds no argument dicts::

    with span("precopy.scan", leaves=n) as s:
        ...
        if enabled():
            s.set_metadata(dirty_blocks=d)

``scope(name)`` is the ``jax.named_scope`` ``alma.<name>`` inside each jitted
program that a trace is read by, so its device operations keep one name
whatever the Python function is called.
"""
from __future__ import annotations

import jax

#: every span the program emits, without the ``alma.`` prefix
NAMES = (
    # the decide plane: one surveillance tick (core/surveillance.py)
    "surveil.tick",
    "surveil.stale_scan",
    "surveil.refit",
    "surveil.gather",
    "surveil.classify",
    "surveil.splice",
    "cycles.spectrum",
    "cycles.peak_pick",
    "cycles.refine",
    "cycles.models",
    "surveil.assign",
    "surveil.pack_fleet",
    "surveil.decide",
    "surveil.remain",
    # each host wait on the device: the wait and the copy back
    "sync.classify",
    "sync.spectrum",
    "sync.refine",
    "sync.remain",
    # the live pre-copy (core/precopy.py)
    "precopy.migrate",
    "precopy.round",
    "precopy.scan",
    "precopy.merge",
    "precopy.stop_copy",
)

PREFIX = "alma."

enabled = jax.profiler.TraceAnnotation.is_enabled


def span(name: str, **args) -> jax.profiler.TraceAnnotation:
    return jax.profiler.TraceAnnotation(PREFIX + name, **args)


def scope(name: str):
    return jax.named_scope(PREFIX + name)
