"""ResultSink — on-device per-tick result consumers (DESIGN.md §14).

The steady-state serving bottleneck measured on a CPU host was never
the sweep: it was ``result()`` draining the dispatch queue and shipping the
``(Q, k)`` neighbour lists to the host every tick.  Most monitoring consumers
do not need the lists — they need *aggregates*: how much did each query's
k-th distance drift, how much did the neighbour sets churn, which object
shards served the hits.  A :class:`ResultSink` computes those aggregates in
a jitted device program that consumes ``(nn_idx, nn_dist)`` right where the
tick produced them, so under ``ServiceSpec(collect="stats")`` only O(Q)
scalars — and under ``collect="none"`` nothing beyond the two drift-policy
scalars the session already reads — ever cross the host boundary.

The sink update is dispatched by ``KnnSession.submit()`` immediately after
the tick step, *asynchronously* (no donation, same reasoning as
``_tick_step``): tick τ+1's host staging overlaps τ's aggregation exactly as
it overlaps τ's sweep.  Sink state (previous tick's neighbour ids + k-th
distances) is device-resident and carries the usual sentinel discipline:
``prev_kth = -1`` marks rows with no previous observation (first tick, or a
registry row-set change), for which drift reports 0 and churn reports 1.
"""
from __future__ import annotations

from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.tracing import stage

__all__ = ["TickAggregates", "SinkState", "ResultSink", "StatsSink"]


class TickAggregates(NamedTuple):
    """O(Q)/O(1) per-tick aggregates, computed on device.

    ``kth_dist`` is padded to the registry batch (rows >= ``n_live`` are
    garbage — slice before use, as ``TickHandle.result`` does); every other
    field is already reduced over live rows only.
    """

    kth_dist: jnp.ndarray  # (Qp,) f32 — Euclidean k-th distance per query
    # (same units as nn_dist; the serve cache squares it at insert time)
    kth_drift_mean: jnp.ndarray  # () f32 — mean |kth - prev_kth|, live+finite
    kth_drift_max: jnp.ndarray  # () f32
    churn_mean: jnp.ndarray  # () f32 — mean fraction of new neighbour ids
    churn_max: jnp.ndarray  # () f32
    shard_hits: jnp.ndarray  # (R_o,) f32 — reported hits per object shard
    n_live: jnp.ndarray  # () i32 — live rows the reductions covered


class SinkState(NamedTuple):
    """Device-resident cross-tick sink memory (previous tick's results)."""

    prev_idx: jnp.ndarray  # (Qp, k) i32; -1 = no entry
    prev_kth: jnp.ndarray  # (Qp,) f32; -1 = row has no previous observation


def init_sink_state(qp: int, k: int) -> SinkState:
    return SinkState(
        prev_idx=jnp.full((qp, k), -1, jnp.int32),
        prev_kth=jnp.full((qp,), -1.0, jnp.float32),
    )


@partial(jax.jit, static_argnames=("num_shards", "use_bounds"))
@stage("sink")
def _stats_update(
    state: SinkState,
    nn_idx,
    nn_dist,
    index,
    bounds,
    n_live,
    *,
    num_shards: int,
    use_bounds: bool,
):
    """(state, R_tau) -> (state', TickAggregates), entirely on device.

    * **k-th drift** — |kth - prev_kth| over live rows where both are finite
      (under-full queries carry kth = inf; sentinel rows carry prev = -1).
    * **churn** — per live row, the fraction of current neighbour ids absent
      from the row's previous list (padding entries ``-1`` never match); 1.0
      for rows with no previous observation, 0.0 for empty result rows.
      The (Qp, k, k) id comparison is tiny next to the sweep (k² ≪ N).
    * **shard hits** — histogram of reported neighbour ids over their owning
      object shard under the SAME ownership rule delta routing uses
      (Morton rank // capacity, or the boundary intervals the tick actually
      used when ``use_bounds``); scatter-add with ``mode="drop"`` discards
      padding entries.
    """
    qp, k = nn_idx.shape
    live = jnp.arange(qp) < n_live
    valid = nn_idx >= 0

    kth = nn_dist[:, k - 1]
    has_prev = state.prev_kth >= 0.0
    drift_ok = live & has_prev & jnp.isfinite(kth) & jnp.isfinite(state.prev_kth)
    drift = jnp.where(drift_ok, jnp.abs(kth - state.prev_kth), 0.0)
    n_drift = jnp.maximum(drift_ok.sum(), 1)
    drift_mean = drift.sum() / n_drift.astype(jnp.float32)
    drift_max = drift.max(initial=0.0)

    # (Qp, k, k): does current entry j appear anywhere in the previous row?
    match = (nn_idx[:, :, None] == state.prev_idx[:, None, :]) & (
        state.prev_idx[:, None, :] >= 0
    )
    kept = (match.any(axis=2) & valid).sum(axis=1)
    n_valid = valid.sum(axis=1)
    churn_row = 1.0 - kept / jnp.maximum(n_valid, 1).astype(jnp.float32)
    churn_row = jnp.where(n_valid > 0, churn_row, 0.0)
    churn_row = jnp.where(has_prev, churn_row, 1.0)
    churn_live = jnp.where(live, churn_row, 0.0)
    churn_mean = churn_live.sum() / jnp.maximum(n_live, 1).astype(jnp.float32)
    churn_max = churn_live.max(initial=0.0)

    n = index.n_objects
    rank = (
        jnp.zeros((n,), jnp.int32)
        .at[index.ids]
        .set(jnp.arange(n, dtype=jnp.int32))
    )
    flat = nn_idx.reshape(-1)
    ok = (valid & live[:, None]).reshape(-1)
    r = rank[jnp.clip(flat, 0, max(n - 1, 0))]
    if use_bounds:
        owner = (jnp.searchsorted(bounds, r, side="right") - 1).astype(jnp.int32)
    else:
        cap = -(-n // num_shards)
        owner = r // cap
    owner = jnp.where(ok, owner, num_shards)  # out of range -> dropped
    shard_hits = (
        jnp.zeros((num_shards,), jnp.float32)
        .at[owner]
        .add(1.0, mode="drop")
    )

    new_state = SinkState(
        prev_idx=jnp.where(live[:, None], nn_idx, -1).astype(jnp.int32),
        prev_kth=jnp.where(live, kth, -1.0),
    )
    agg = TickAggregates(
        kth_dist=kth,
        kth_drift_mean=drift_mean,
        kth_drift_max=drift_max,
        churn_mean=churn_mean,
        churn_max=churn_max,
        shard_hits=shard_hits,
        n_live=jnp.asarray(n_live, jnp.int32),
    )
    return new_state, agg


class ResultSink:
    """Interface: a jitted per-tick consumer of device-resident results.

    ``init(qp, k)`` returns the device-resident cross-tick state;
    ``update(state, nn_idx, nn_dist, index, bounds, n_live)`` consumes one
    tick's padded ``(Qp, k)`` outputs and returns ``(state', aggregates)``
    — both device-resident, dispatched asynchronously.  Implementations
    must not force a host sync (no ``float()``/``np.asarray`` inside).
    """

    def init(self, qp: int, k: int):
        raise NotImplementedError

    def update(self, state, nn_idx, nn_dist, index, bounds, n_live):
        raise NotImplementedError


class StatsSink(ResultSink):
    """The default ``collect="stats"`` sink: drift + churn + shard hits."""

    def __init__(self, num_obj_shards: int = 1):
        self.num_obj_shards = max(1, int(num_obj_shards))

    def init(self, qp: int, k: int) -> SinkState:
        return init_sink_state(qp, k)

    def update(self, state, nn_idx, nn_dist, index, bounds, n_live):
        use_bounds = bounds is not None
        return _stats_update(
            state, nn_idx, nn_dist, index,
            bounds if use_bounds else jnp.zeros((1,), jnp.int32),
            n_live,
            num_shards=self.num_obj_shards,
            use_bounds=use_bounds,
        )
