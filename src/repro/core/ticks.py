"""Iterated batch processing of k-NN queries over ticks (paper Sec. 2.2/2.3).

This module is the **execution core** under the serving facade
:mod:`repro.api` (DESIGN.md §11): it owns the jitted per-tick device program
(:func:`_tick_step`), the device-side delta-ingest primitive
(:func:`scatter_positions`), the delta routing helpers and the per-tick
result record (:class:`TickResult`).  The stateful serving loop — persistent
query registry, delta object updates, overlapped submit — lives in
:class:`repro.api.KnnSession`, configured by :class:`repro.api.ServiceSpec`.

The whole steady-state tick is ONE jitted device program (:func:`_tick_step`,
DESIGN.md §8/§11): stage (ii) index refresh (object re-sort + interval/
pyramid rebuild), the chunked query sweep (``lax.map`` over fixed-shape
chunks — no per-chunk host loop), and the drift statistic all run device-
side; the host reads back results plus one boolean.  The step dispatches
*asynchronously* — deliberately no buffer donation, which would force a
synchronous dispatch (see the docstring) — so the session can overlap next-
tick staging with this tick's device compute.  State *ingest* is split out
of the step: positions cross the host boundary either as a full snapshot
(``jnp.asarray``) or as a delta scatter of just the moved rows
(:func:`scatter_positions`); the step itself only ever sees device arrays.

Index maintenance follows the paper (Sec. 4.1.1): stage (ii) runs every tick;
stage (i) (the space partition / z_map) is rebuilt **only** when the measured
computation volume of the last tick exceeds the volume observed when the
partition was built by ``rebuild_factor`` — the paper's trigger "the overall
amount of computations yielded during the last tick exceeds by a given factor
the amount yielded during past, recent ticks".  The trigger is *computed on
device* from the tick's candidate counter and crosses to the host as a single
scalar together with the results.

The SCAN backend is configurable per session (``ServiceSpec.backend``; see
``repro.core.executor.available_backends``), and so is the device layout of
the query sweep (``ServiceSpec.plan`` / ``mesh_shape``; DESIGN.md §10/§12):
``sharded`` replicates the index across a 1-D ``("query",)`` mesh and splits
the Morton-sorted batch with ``shard_map``; ``object_sharded`` splits the
*object* set into Morton-contiguous slices with a local quadtree per device
and merge-reduces per-query lists; ``hybrid`` composes both on a 2-D
``("query", "object")`` mesh (``mesh_shape`` becomes a pair).  How each
split axis is CUT is the partitioner's job (``ServiceSpec.partitioner``;
DESIGN.md §13): ``equal`` keeps the static equal-count splits,
``cost_balanced`` re-balances boundaries every tick from the count-pyramid
seed plus the per-query cost EMA threaded through the step.  Per-shard
candidate/iteration counters come back gathered over every mesh axis
(``TickResult.shard_candidates`` — the straggler-gap metric); their sum is
the whole-tick volume the rebuild trigger reads; :func:`object_shard_of`
evaluates the object-shard ownership rule (capacity or boundary form) for
the session's delta routing.
"""
from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.tracing import stage

from .executor import QueryExecutor
from .plan import ExecutionPlan
from .quadtree import reindex_objects, reindex_objects_delta

__all__ = [
    "TickResult",
    "MAINTENANCE_MODES",
    "scatter_positions",
    "object_shard_of",
    "route_delta",
    "delta_shard_counts",
    "shard_churn_over_budget",
]

# Index-maintenance policies (DESIGN.md §15).  "rebuild" = the paper's
# stage-(ii) full refresh every tick; "incremental" = delta recode + splice
# with work proportional to churn, deferring to a full refresh when the
# accumulated delta crosses ``churn_budget`` x N.  (The per-tick device step
# additionally knows an internal "skip" mode — the dirty-flag fast path for
# ticks with no position change — which is a session scheduling decision,
# not a user-facing policy.)
MAINTENANCE_MODES = ("rebuild", "incremental")


@dataclasses.dataclass
class TickResult:
    tick: int
    nn_idx: np.ndarray  # (Q, k); device arrays under result(materialize=False)
    nn_dist: np.ndarray  # (Q, k)
    rebuilt: bool
    wall_s: float  # submit -> results materialized, EXCLUDING compile_s
    candidates: float
    iterations: int
    compile_s: float = 0.0  # trace+compile time, nonzero on first-shape ticks
    qids: np.ndarray | None = None  # (Q,) registry qids, row-aligned with nn_*
    # per-shard measured work, one entry per mesh device (1 for the single
    # plan); candidates sums to `candidates` bitwise (PlanAux contract) and
    # max/mean of it is the straggler gap (repro.core.balance.straggler_gap)
    shard_candidates: np.ndarray | None = None  # (R_total,) f32
    shard_iterations: np.ndarray | None = None  # (R_total,) i32
    # host-transfer time actually spent materializing THIS tick's results,
    # attributed to the tick that materializes (not the tick that submits);
    # a subset of wall_s (satellite: overlapped-mode accounting, DESIGN.md §14)
    collect_s: float = 0.0
    # on-device aggregates (repro.api.sink.TickAggregates) under
    # collect="stats"; None under "full"/"none"
    aggregates: object | None = None
    # how THIS tick's step maintained the index: "rebuild" (full stage-(ii)
    # refresh), "incremental" (delta splice), or "skip" (dirty-flag fast
    # path: nothing moved since the last refresh, reindex elided)
    maintenance: str = "rebuild"
    # rows the tick's index refresh re-placed: the pending delta's unique
    # ids under "incremental", 0 under "skip", N under "rebuild" (the
    # whole re-sort); known on the host at dispatch, no readback
    delta_rows: int = 0

    @property
    def kth_dist(self):
        """(Q,) Euclidean k-th distance per query row, or None.

        The radius of each row's result ball — what the serving layer's
        spatial cache invalidation stores per entry.  Derived from
        ``nn_dist[:, k-1]`` under ``collect="full"`` (host or device array,
        matching the result's residency); under ``collect="stats"`` it is
        the sink's already-reduced ``aggregates.kth_dist`` sliced to the
        live rows.  None when neither carrier is available.
        """
        if self.nn_dist is not None:
            return self.nn_dist[:, -1]
        agg = self.aggregates
        if agg is not None and getattr(agg, "kth_dist", None) is not None:
            kd = agg.kth_dist
            if self.qids is not None:
                kd = kd[: self.qids.shape[0]]
            return kd
        return None


@partial(
    jax.jit,
    static_argnames=("k", "window", "chunk", "max_nav", "max_iters",
                     "executor", "plan", "maintenance"),
)
def _tick_step(
    index,
    positions,
    qpos,
    qid,
    qcost,
    work_at_build,
    rebuild_factor,
    delta_ids,
    delta_old_pos,
    qweight=None,
    *,
    k: int,
    window: int,
    chunk: int,
    max_nav: int,
    max_iters: int,
    executor: QueryExecutor,
    plan: ExecutionPlan,
    maintenance: str = "rebuild",
):
    """(index, P_tau, Q_tau) -> (index', R_tau, aux, should_rebuild).

    One fused device program per tick: index maintenance + the plan's query
    sweep + drift check.  The step is built *per plan* (a static argument,
    like the executor): under the ``single`` plan the sweep is the chunked
    one-device ``lax.map``; under ``sharded`` it is the ``shard_map``
    fan-out over the ``("query",)`` mesh with the refreshed index
    replicated; the gathered per-shard counters (``aux.shard_candidates``)
    sum to whole-tick volume, which is what the drift comparison below
    reads.  ``qcost`` is the per-query cost EMA the session threads across
    ticks (zeros = cold); the cost-balanced partitioner turns it into next
    tick's shard boundaries.  ``qweight`` is the optional (Q,) tenant-fair
    multiplier on that boundary seed (None = unweighted — and None being a
    valid pytree, sessions that never set weights hit the same compiled
    programs as before the seam existed).

    ``maintenance`` selects the stage-(ii) refresh, statically — one
    compiled program per (shape, mode) pair (DESIGN.md §15):

    * ``"rebuild"``: full ``reindex_objects`` — recode + argsort + recount
      over all N rows; ``delta_ids``/``delta_old_pos`` must be None (not
      baked into a program that ignores them).
    * ``"incremental"``: ``reindex_objects_delta`` — recode/sort/splice only
      the ``delta_ids`` rows (sentinel-N padded, deduped by the session;
      ``delta_old_pos`` carries their positions as of the last refresh so
      the old keys can be located by search), bitwise-equal to "rebuild" by
      the splice stability argument.
    * ``"skip"``: the dirty-flag fast path — positions are unchanged since
      the index was refreshed from this very buffer, so the reindex (a
      semantic no-op, since ``reindex_objects`` is a pure function of the
      positions buffer) is elided entirely; ``delta_ids`` must be None.
      Before the seam existed the no-op reindex ran anyway to keep one
      compiled program; now the session tracks dirtiness and each mode is
      its own cached executable, so clean ticks pay zero reindex.

    The step deliberately does NOT donate the incoming index: donated
    arguments make the host-side dispatch *synchronous* on this runtime (the
    call blocks for the whole device step instead of returning a future,
    measured on the CPU backend), which would serialize
    host staging against device compute and defeat the session API's
    submit/result overlap.  The in-place refresh saved one index-sized
    allocation per tick; the overlap is worth far more, and XLA's allocator
    still recycles the freed buffers.

    ``positions`` and ``qpos``/``qid`` are *already device-resident* (staged
    by the session via snapshot upload, delta scatter, or the persistent
    padded query registry); this step never touches the host boundary.
    """
    with stage("reindex"):
        if maintenance == "rebuild":
            index = reindex_objects(index, positions)
        elif maintenance == "incremental":
            index = reindex_objects_delta(
                index, positions, delta_ids, delta_old_pos
            )
        elif maintenance != "skip":
            raise ValueError(f"unknown step maintenance mode {maintenance!r}")
    # the mode rides into the plan (still static): under "incremental" and
    # "skip" the index's sorted order/pyramid are current for the buffer, so
    # the object-axis plans DERIVE their device-local trees from it instead
    # of re-building one per device from the replicated slice — the sharded
    # half of the maintenance seam (DESIGN.md §15)
    nn_idx, nn_dist, aux = plan.run(
        index,
        qpos,
        qid,
        qcost,
        k=k,
        window=window,
        chunk=chunk,
        max_nav=max_nav,
        max_iters=max_iters,
        executor=executor,
        qweight=qweight,
        maintenance=maintenance,
    )
    with stage("drift"):
        should_rebuild = aux.stats.candidates > rebuild_factor * work_at_build
    return index, nn_idx, nn_dist, aux, should_rebuild


@partial(jax.jit, static_argnames=("num_shards",))
def object_shard_of(index, ids, num_shards: int, bounds=None):
    """Owning object shard of each object id under the live index.

    Evaluates the shard-ownership rule of DESIGN.md §12/§13 device-side: an
    object's owner is determined by its Morton *rank* in the current index —
    rank divided by the shard capacity ``ceil(N / num_shards)`` under the
    equal partition, or the boundary interval containing the rank
    (``searchsorted``) when ``bounds`` carries the (R+1,) Morton-row
    boundaries a cost-balanced tick actually used
    (``PlanAux.object_bounds``).  Ownership must be re-derived from the
    index each tick because objects change rank as they move.  Returns (m,)
    int32 shard indices in ``[0, num_shards)``.

    ``ids`` must be in ``[0, index.n_objects)`` — jnp's clamping gather
    would otherwise return confidently wrong owners for stale ids, so the
    host-facing caller (``KnnSession.object_shards``) validates the range
    eagerly.
    """
    from .plan import object_shard_capacity

    n = index.n_objects
    rank = (
        jnp.zeros((n,), jnp.int32)
        .at[index.ids]
        .set(jnp.arange(n, dtype=jnp.int32))
    )
    r = rank[jnp.asarray(ids, jnp.int32)]
    if bounds is None:
        cap = object_shard_capacity(n, num_shards)
        return r // cap
    return (jnp.searchsorted(bounds, r, side="right") - 1).astype(jnp.int32)


@partial(jax.jit, static_argnames=("num_shards",))
def route_delta(index, ids, new_pos, num_shards: int, bounds=None):
    """Group a (sentinel-padded) delta batch by owning shard, device-side.

    Stable-sorts the batch rows by :func:`object_shard_of` ownership
    (sentinel rows — ``id >= N``, dropped by the scatter — sort last as a
    virtual shard ``num_shards``) and returns the reordered ``(ids,
    new_pos)``.  ``bounds`` forwards the cost-balanced boundary rule when
    the session has a completed tick's partition on hand.  Runs entirely on
    device: no host readback, so delta staging keeps the async-dispatch
    property the session's overlap contract relies on.  Today the positions
    buffer is replicated and the grouping is a pure reorder of unique ids
    (bit-identical results, pinned by the routing-edge regressions in
    tests/test_api.py); it stages the memory layout a per-shard-resident
    positions buffer will scatter as contiguous runs.
    """
    n = index.n_objects
    ids = jnp.asarray(ids, jnp.int32)
    shard = jnp.where(
        ids < n,
        object_shard_of(
            index, jnp.clip(ids, 0, max(n - 1, 0)), num_shards, bounds
        ),
        num_shards,
    )
    order = jnp.argsort(shard)  # jnp.argsort is stable by default
    return ids[order], new_pos[order]


@partial(jax.jit, static_argnames=("num_shards",))
def delta_shard_counts(index, ids, num_shards: int, bounds=None):
    """Pending delta rows per owning object shard, device-side.

    The per-shard half of the churn accounting (DESIGN.md §15): counts each
    valid id of a (sentinel-padded) pending delta batch against the shard
    that owns it under the LIVE index — the same ownership rule
    :func:`route_delta` sorts by, so a row is charged to its *source* shard
    (the shard whose local order it vacates; a cross-shard migrant perturbs
    its destination too, but the source count is the one the splice's
    delete-side work tracks, and charging one side keeps the counts a
    partition of the batch).  Sentinel rows (``id >= N``) fall into a
    virtual shard ``num_shards`` and are sliced off.  Returns (num_shards,)
    int32.
    """
    n = index.n_objects
    ids = jnp.asarray(ids, jnp.int32)
    shard = jnp.where(
        ids < n,
        object_shard_of(
            index, jnp.clip(ids, 0, max(n - 1, 0)), num_shards, bounds
        ),
        num_shards,
    )
    return jnp.bincount(
        shard, length=num_shards + 1
    )[:num_shards].astype(jnp.int32)


@partial(jax.jit, static_argnames=("num_shards",))
def shard_churn_over_budget(index, ids, num_shards: int, budget, bounds=None):
    """Does any object shard's pending churn exceed its per-shard budget?

    The sharded generalization of the session's global ``churn_budget`` rule
    (DESIGN.md §15): the incremental path's per-shard benefit — deriving each
    local tree from the spliced global order instead of re-sorting N/R rows —
    assumes churn stays a small fraction of every shard's OWNED rows; a
    single shard absorbing more than ``budget`` × its owned count is the
    local re-sort crossover, so the tick defers to a full rebuild.  Owned
    counts come from ``bounds`` (the cost-balanced boundaries the last tick
    used) or the equal-capacity rule clipped to N.  The comparison is strict
    (``>``): churn exactly AT the budget stays incremental, mirroring the
    global rule's ``<=`` boundary.  At ``num_shards == 1`` this degenerates
    to exactly the global rule (and callers skip it).  Returns a () bool.
    """
    from .plan import object_shard_capacity

    n = index.n_objects
    counts = delta_shard_counts(index, ids, num_shards, bounds)
    if bounds is None:
        cap = object_shard_capacity(n, num_shards)
        edges = jnp.minimum(
            jnp.arange(num_shards + 1, dtype=jnp.int32) * cap, n
        )
    else:
        edges = jnp.asarray(bounds, jnp.int32)
    owned = edges[1:] - edges[:-1]
    return jnp.any(
        counts.astype(jnp.float32)
        > jnp.float32(budget) * owned.astype(jnp.float32)
    )


@jax.jit
def scatter_positions(positions, ids, new_pos):
    """Delta object ingest: scatter ``new_pos`` rows at ``ids`` device-side.

    This is the session API's ``update_objects`` path (DESIGN.md §11): only
    the moved rows cross the host boundary; the (N, 2) buffer never does.
    Rows whose id is out of range are dropped (``mode="drop"``): callers pad
    variable-size update batches to a fixed multiple with the sentinel id
    ``N`` so every delta size reuses one compiled scatter.  Functional (no
    donation) on purpose — twofold: donated dispatch is synchronous on this
    runtime (see ``_tick_step``), and an in-flight tick may still be reading
    the previous buffer while the session scatters the next tick's motion
    into a fresh one (double-buffering).
    """
    return positions.at[ids].set(new_pos, mode="drop")
