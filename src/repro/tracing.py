"""The program's marks in a ``jax.profiler`` trace, and its compile clock.

One tracing system, ``jax.profiler``: the spans and scopes below land in the
trace a profiler session records, on the same clock as the device's ops, and
cost next to nothing when no session records.

* :func:`span` — a host span (``TraceAnnotation``) named ``knn.<name>``,
  carrying ``tick=<τ>`` so the spans of one tick share an identifier.  The
  session layer marks its boundaries and the points where the host blocks:
  ``session.ingest``, ``session.update_queries``, ``session.submit`` (with
  ``session.finalize``, ``session.rebuild`` and ``session.dispatch``, which
  holds ``session.delta``: the maintenance decision and the delta's
  assembly), ``tick.wait``, ``tick.result`` (with ``tick.collect``).
* :func:`stage` — a ``jax.named_scope`` named ``knn.<stage>`` for one stage
  of the tick program (:data:`STAGES`).  It is trace-time metadata: the
  compiled ops carry it in their ``op_name``, and no device work is added.
  A trace reduction gives each device op the innermost ``knn.`` scope of
  its ``op_name`` path.
* :func:`compile_time` — the seconds JAX spent tracing, lowering and
  compiling (its ``/jax/core/compile/*`` events) on this thread inside a
  block, from ``jax.monitoring``.
"""
from __future__ import annotations

import contextlib
import functools
import threading
import time

import jax

__all__ = ["PREFIX", "STAGES", "span", "stage", "compile_time"]

PREFIX = "knn."

# The stages of the tick program, outermost first: the index refresh, the
# Morton sort of the queries and the unsort of their results, the chunked
# sweep with its window gathers, SCAN and NAV inside, the cross-shard merge
# of the object-axis plans, the drift compare, and the on-device sink.
STAGES = ("reindex", "order", "sweep", "gather", "scan", "nav", "merge",
          "drift", "sink")

_COMPILE_EVENTS = "/jax/core/compile/"


def span(name: str, **args):
    """A host span ``knn.<name>`` in the profiler's trace; ``args`` become
    its stats.  Records only while a profiler session is active."""
    return jax.profiler.TraceAnnotation(PREFIX + name, **args)


def stage(name: str):
    """``jax.named_scope`` for one of :data:`STAGES` (a context manager that
    also decorates a function traced under ``jit``)."""
    if name not in STAGES:
        raise ValueError(f"unknown stage {name!r}; stages: {STAGES}")
    return jax.named_scope(PREFIX + name)


_local = threading.local()


def _on_event(event: str, duration: float, **kw):
    spans = getattr(_local, "spans", None)
    if spans is not None and event.startswith(_COMPILE_EVENTS):
        end = time.perf_counter()
        spans.append((end - duration, end))


@functools.cache
def _listen():
    jax.monitoring.register_event_duration_secs_listener(_on_event)


class _Clock:
    seconds = 0.0


@contextlib.contextmanager
def compile_time():
    """Yields a holder whose ``seconds``, once the block has ended, is the
    wall time this thread spent in JAX's tracing, lowering and compiling
    inside the block (0.0 where every program came from JAX's cache).

    The events nest (a jitted function traced inside another reports its
    own trace time), so the holder counts the union of their intervals.
    """
    _listen()
    outer = getattr(_local, "spans", None)
    _local.spans = spans = []
    clock = _Clock()
    try:
        yield clock
    finally:
        _local.spans = outer
        if outer is not None:
            outer.extend(spans)
        total, reach = 0.0, float("-inf")
        for s, e in sorted(spans):
            if e > reach:
                total += e - max(s, reach)
                reach = e
        clock.seconds = total
