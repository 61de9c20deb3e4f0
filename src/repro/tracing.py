"""Spans on the profiler's clock: the served path's only tracing.

``span(name, **meta)`` is a :class:`jax.profiler.TraceAnnotation` named
``repro:<name>``; ``step(name, step_num)`` is the
:class:`jax.profiler.StepTraceAnnotation` of one cluster-loop
iteration.  They record nothing unless a profiler session is active
(``jax.profiler.trace(dir)`` or ``jax.profiler.start_trace``).  Then
each span lands in that session's trace, on the same clock as the
device's operations, so an idle gap on the device can be put down to
the phase of the program the host was in.  ``meta`` becomes the
event's stats (a request's ``rid``, a replica's id, a chunk's offset).
Nothing is kept in memory: the trace is the record.

No span name, or program name, may contain ``paged_decode``,
``paged_prefill`` or ``expert_ffn``: the chip benchmark finds the
kernels by those substrings in the trace.

Counters are read by method, outside any span; :data:`COUNTERS` lists
them.
"""

from __future__ import annotations

from jax.profiler import StepTraceAnnotation, TraceAnnotation

PREFIX = "repro:"

#: Every span the program emits, outermost first within each layer.
SPANS = (
    # cluster loop (serving/cluster.py)
    "loop",
    "cluster.events",
    "cluster.view",
    "cluster.dispatch",
    "cluster.migrate",
    "cluster.idle",
    "cluster.finish",
    # scheduler: the call, then Algorithm 1's phases (core/scheduler.py)
    "sched.schedule",
    "sched.ready",
    "sched.bounds",
    "sched.srtf",
    "sched.uncertainty",
    "sched.merge",
    "sched.place",
    # paged engine (serving/paged_engine.py)
    "engine.admit",
    "engine.step",
    "engine.readmit",
    "engine.prefill",
    "engine.prefill.call",
    "engine.prefill.sync",
    "engine.grow",
    "engine.decode.prep",
    "engine.decode.call",
    "engine.decode.sync",
    "engine.sample",
)


#: Every counter the program keeps, by the method that reads it.
COUNTERS = (
    # lookups and hits of the four per-job caches (core/profiler.py)
    "ProfileStore.cache_counts",
    # tokens routed to each held expert, then to experts held elsewhere,
    # counted on the device by the step programs (serving/paged_engine.py)
    "PagedLLMEngine.expert_counts",
)


def span(name: str, **meta) -> TraceAnnotation:
    """A ``repro:<name>`` span; use it as a context manager."""
    return TraceAnnotation(PREFIX + name, **meta)


def step(name: str, step_num: int) -> StepTraceAnnotation:
    """The ``repro:<name>`` span of one loop iteration, numbered."""
    return StepTraceAnnotation(PREFIX + name, step_num=step_num)
