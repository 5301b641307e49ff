"""Test helpers: a blocking tick loop and a fallback for `hypothesis`.

:func:`run_ticks` drives a session with whole frames of a
:class:`~repro.data.MovingObjectWorkload`, one blocking
``KnnSession.process_tick`` per tick.

The rest is a deterministic fallback for the tiny slice of `hypothesis` the
tests use.

The CI container has no ``hypothesis`` wheel and the tier-1 suite must not
depend on network installs, so property tests import it through a guard::

    try:
        from hypothesis import given, settings, strategies as st
    except ModuleNotFoundError:
        from repro.testing import given, settings, strategies as st

Semantics here are a strict subset: ``@given`` draws ``max_examples`` examples
from the strategies with a seed derived from the test name (stable across
runs — failures reproduce), with no shrinking and no example database.  When
real hypothesis is available it wins, shrinking and all.
"""
from __future__ import annotations

import dataclasses
import zlib
from typing import Any, Callable

import numpy as np

__all__ = ["run_ticks", "given", "settings", "strategies"]

DEFAULT_MAX_EXAMPLES = 20


@dataclasses.dataclass(frozen=True)
class _Strategy:
    draw: Callable[[np.random.Generator], Any]

    def sample(self, rng: np.random.Generator):
        return self.draw(rng)


class strategies:
    """Namespace mirroring ``hypothesis.strategies`` (the used subset)."""

    @staticmethod
    def floats(min_value: float, max_value: float) -> _Strategy:
        return _Strategy(lambda rng: float(rng.uniform(min_value, max_value)))

    @staticmethod
    def integers(min_value: int, max_value: int) -> _Strategy:
        # hypothesis bounds are inclusive
        return _Strategy(lambda rng: int(rng.integers(min_value, max_value + 1)))

    @staticmethod
    def tuples(*elems: _Strategy) -> _Strategy:
        return _Strategy(lambda rng: tuple(e.sample(rng) for e in elems))

    @staticmethod
    def lists(elem: _Strategy, *, min_size: int = 0, max_size: int = 10) -> _Strategy:
        def draw(rng):
            n = int(rng.integers(min_size, max_size + 1))
            return [elem.sample(rng) for _ in range(n)]

        return _Strategy(draw)


def settings(max_examples: int = DEFAULT_MAX_EXAMPLES, deadline=None):
    """Decorator setting the example count on a ``@given``-wrapped test."""

    def deco(fn):
        fn._max_examples = max_examples
        return fn

    return deco


def given(*strats: _Strategy):
    """Run the test once per drawn example (deterministic per-test seed)."""

    def deco(fn):
        def run(*args, **kw):
            # @settings may sit above @given (stamps `run`) or below (stamps `fn`)
            n = getattr(
                run, "_max_examples", getattr(fn, "_max_examples", DEFAULT_MAX_EXAMPLES)
            )
            rng = np.random.default_rng(zlib.crc32(fn.__name__.encode()))
            for _ in range(n):
                fn(*args, *(s.sample(rng) for s in strats), **kw)

        # NOT functools.wraps: copying __wrapped__ would make pytest inspect
        # the original signature and treat the drawn arguments as fixtures.
        run.__name__ = fn.__name__
        run.__doc__ = fn.__doc__
        run.__module__ = fn.__module__
        return run

    return deco


def run_ticks(session, workload, ticks: int, query_rate: float = 1.0):
    """``ticks`` blocking ticks of ``workload`` through ``session``.

    Each tick draws the query batch, serves it against the workload's
    current positions with ``session.process_tick`` and advances the
    workload.  Returns the list of ``TickResult``.
    """
    out = []
    for _ in range(ticks):
        qpos, qid = workload.query_batch(query_rate)
        out.append(session.process_tick(workload.positions(), qpos, qid))
        workload.advance()
    return out
