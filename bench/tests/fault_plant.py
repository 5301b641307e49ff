"""Faults planted under the window, each of which the comparison must see.

Each is a context manager that breaks the program underneath the timed
path for as long as it is open:

* ``stale``: the step keeps its state, so new object reports never reach it;
* ``half``: half of each tick's rows are left out (never computed);
* ``altered``: one answer is changed where it is produced;
* ``exchange``: the query shards of the mesh plans exchange nothing, so
  only the first chip's rows come back (four devices only).
"""
from __future__ import annotations

import contextlib
import dataclasses
from unittest import mock

import numpy as np


def _results(change):
    from repro.api.handles import TickHandle

    orig = TickHandle.result

    def result(self, materialize=True):
        res = orig(self, materialize)
        if res.nn_idx is None:
            return res
        idx, dist = np.array(res.nn_idx), np.array(res.nn_dist)
        change(idx, dist)
        return dataclasses.replace(res, nn_idx=idx, nn_dist=dist)

    return mock.patch.object(TickHandle, "result", result)


@contextlib.contextmanager
def stale():
    from repro.api.session import KnnSession

    with mock.patch.object(KnnSession, "ingest_objects",
                           lambda self, positions: None), \
            mock.patch.object(KnnSession, "update_objects",
                              lambda self, ids, positions: None):
        yield


def half():
    def change(idx, dist):
        h = idx.shape[0] // 2
        idx[h:] = -1
        dist[h:] = np.inf

    return _results(change)


def altered():
    def change(idx, dist):
        idx[0, -1] += 1

    return _results(change)


@contextlib.contextmanager
def exchange():
    import jax
    import jax.numpy as jnp
    from repro.core import plan

    orig = plan.shard_map_compat

    def broken(fn, **kw):
        def local(*args):
            idx, d2, *rest = fn(*args)
            first = jax.lax.axis_index(kw["axis_names"].copy().pop()) == 0
            return (jnp.where(first, idx, -1), jnp.where(first, d2, jnp.inf),
                    *rest)

        return orig(local, **kw)

    with mock.patch.object(plan, "shard_map_compat", broken):
        jax.clear_caches()
        yield
    jax.clear_caches()


FAULTS = {"stale": stale, "half": half, "altered": altered,
          "exchange": exchange}
