"""ExecutionPlan — how a tick's query batch is laid onto devices (DESIGN.md §10).

The pipeline (``core/pipeline.py``) knows how to answer *sorted* queries
against an index; the serving layer (``core/ticks.py``) knows *when* to run a
tick.  The plan is the seam between them: it owns device layout — how the
Morton-sorted batch is chunked, split across a mesh, and gathered back.  Four
plans ship:

``single``
    Today's path: global Morton sort, ``lax.map`` over fixed-shape chunks on
    one device (the chunked sweep formerly inlined in
    ``pipeline.knn_chunked_device``, rehomed here behind the seam).

``sharded``
    A 1-D ``("query",)`` mesh (``launch.mesh.make_query_mesh``) laid out by
    the spatial logical-axis rules (``repro.dist.SPATIAL_RULES``): the
    quadtree index — positions, ids, starts, count pyramid — is *replicated*
    across devices, the Morton-sorted query batch is split into per-device
    contiguous shards with ``shard_map``, each device runs the identical
    masked dense iteration locally over its shard, and the per-shard
    ``(k, dist, id)`` lists are gathered by concatenation (query shards are
    disjoint, so the gather needs no merge).

``object_sharded``
    A 1-D ``("object",)`` mesh (``launch.mesh.make_object_mesh``, DESIGN.md
    §12): the **object set** is split into Morton-contiguous slices, each
    device builds its own quadtree over its slice and runs the full query
    batch against it locally, and the per-device *partial* result lists are
    ``all_gather``-ed along the object axis and reduced with a binary tree
    of the MERGE backends (``kernels.ops.tree_merge_lists`` over
    ``dense_merge`` | ``fused_merge``).  This is the partition-then-merge
    route to object sets larger than one device's memory (Gowanlock's
    hybrid KNN-join, PAPERS.md).

``hybrid``
    The 2-D ``("query", "object")`` mesh composing both decompositions
    (``launch.mesh.make_spatial_mesh``): the Morton-sorted query batch
    splits along the query axis, the Morton-sorted object array along the
    object axis; each device sweeps its query shard over its object slice,
    partial lists merge-reduce along the object axis and gather by
    concatenation along the query axis.  ``mesh_shape=(qd, od)`` picks the
    factorization; the default is the most balanced one
    (``launch.mesh.default_hybrid_shape``).

**Partitioner seam (DESIGN.md §13).**  Plans no longer hard-code equal
splits: where to cut the Morton-sorted query batch (in whole-chunk units)
and the Morton-sorted object array (in row units) is delegated to a
:class:`repro.core.balance.Partitioner` carried inside the plan.  ``equal``
reproduces the pre-seam equal-count splits; ``cost_balanced`` bins the same
contiguous ranges so each shard's *estimated cost* balances — seeded from
the count pyramid (:func:`_query_cost_estimate` — each query's leaf
population) and refined by the per-query EMA of measured candidate volume
the session threads through ``qcost`` (the repeated-query feedback loop).
The object axis stays count-balanced (:func:`_object_row_costs` — see its
docstring for the measured rationale), boundaries still flowing through the
same seam.
Because shard shapes must stay static under ``jit``/``shard_map``, balanced
shards are **uneven-but-static**: every shard compiles at a fixed capacity
(``Partitioner.*_capacity``) and masks the unused tail — dead query chunks
are skipped with a ``lax.cond`` inside the chunk map, surplus object rows
carry sentinel id -1 exactly like the equal plan's tail padding.

ALL plans are **bit-identical** to ``single`` for EVERY partitioner (pinned
by tests/test_plan.py and the property harness tests/test_properties.py
across the full backend × plan × partitioner matrix).  Two disciplines make
that hold:

  * every query-shard boundary coincides with a chunk boundary — the host
    pads the batch to ``(query devices) * chunk`` (:func:`pad_queries`) and
    partitioners cut in whole-chunk units, so per-chunk programs are
    identical to the single plan's regardless of which device owns a chunk;
  * selection is everywhere the canonical lexicographic ``(d2, id)`` order
    and navigation keeps equal-distance blocks (DESIGN.md §12), so a
    query's result is a pure function of the candidate *set* — any object
    partition yields the same bits after the merge reduction (the
    composition law ``knn(∪ P_r) = tree_merge(knn(P_r))``, contract-tested
    R-way in tests/test_kernels.py).

Every ``run`` returns a :class:`PlanAux` alongside the result lists: global
:class:`~repro.core.pipeline.KnnStats` scalars (the drift trigger), the
per-shard candidate/iteration counters (the straggler-gap metric — no
longer only the psum-reduced global), the next per-query cost EMA, and the
object-axis boundaries actually used (the serving layer routes delta
updates by them).

Plans are frozen (hence hashable) dataclasses, carried through ``jax.jit`` as
*static* arguments exactly like :class:`repro.core.executor.QueryExecutor`:
the jitted tick step specializes per (plan, backend, partitioner) triple —
boundaries are data, so per-tick re-balancing never recompiles.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import ClassVar, NamedTuple

import jax
import jax.numpy as jnp

from repro.dist import SPATIAL_RULES, shard_map_compat, use_rules
from repro.kernels.ops import get_merge_backend, tree_merge_lists
from repro.launch.mesh import (
    default_hybrid_shape,
    make_object_mesh,
    make_query_mesh,
    make_spatial_mesh,
)
from repro.tracing import stage

from . import morton
from .balance import EqualPartitioner, Partitioner, resolve_partitioner
from .pipeline import (
    KnnStats,
    _knn_sorted_impl,
    _resolve_max_nav,
    _sort_unsort,
    nav_table,
    window_tables,
    zero_stats,
)
from .quadtree import (
    QuadtreeIndex,
    _leaf_levels,
    build_index,
    local_pyramid_from_starts,
    starts_from_pyramid,
)

__all__ = [
    "ExecutionPlan",
    "PlanAux",
    "SinglePlan",
    "ShardedPlan",
    "ObjectShardedPlan",
    "HybridPlan",
    "register_plan",
    "resolve_plan",
    "plan_names",
    "pad_capacity",
    "pad_queries",
    "object_shard_capacity",
    "knn_chunked_device",
    "knn_sharded_device",
    "knn_query_batch_chunked",
    "run_plan_device",
]

# EMA weight applied to the measured per-query candidate volume when the
# plan's partitioner does not define one (EqualPartitioner has no cost
# model; the EMA is still maintained so a later cost_balanced session —
# or introspection — sees warm per-query costs).
_EMA_ALPHA_DEFAULT = 0.25


class PlanAux(NamedTuple):
    """Per-tick auxiliary outputs every plan returns beside the result lists.

    ``stats``
        Global :class:`KnnStats` scalars — computed as the SUM of the
        per-shard counters, so ``stats.candidates`` equals
        ``shard_candidates.sum()`` by construction (pinned by tests).
    ``shard_candidates`` / ``shard_iterations``
        (R_total,) per-shard measured counters, one entry per mesh device
        (R_total = 1 for ``single``); ``max/mean`` of the candidates row is
        the straggler gap benchmarks report (``balance.straggler_gap``).
    ``qcost_next``
        (Q_padded,) f32 per-query cost EMA in the CALLER's row order — the
        session persists it across ticks and feeds it back as ``qcost``.
    ``object_bounds``
        (R_o + 1,) i32 Morton-row boundaries of the object partition this
        tick actually used (R_o = ``object_axis_size``; ``[0, N]`` when the
        object axis is unsharded).  The serving layer routes delta updates
        and answers ``object_shards`` introspection with them.
    """

    stats: KnnStats
    shard_candidates: jnp.ndarray
    shard_iterations: jnp.ndarray
    qcost_next: jnp.ndarray
    object_bounds: jnp.ndarray


def pad_capacity(nq: int, multiple: int) -> int:
    """Padded row count for ``nq`` queries at the plan's granularity.

    This is the capacity of the persistent padded query registry
    (``repro.api``): the registry restages its device batch only when the
    live set changes, and the compiled tick step is keyed by this capacity
    (chunk count per shard), never by the raw query count.
    """
    return max(1, -(-nq // multiple)) * multiple


def pad_queries(qpos, qid, multiple: int):
    """Host-side pad of (Q,2)/(Q,) to :func:`pad_capacity` rows.

    ``multiple`` is the plan's padding granularity (:meth:`ExecutionPlan.
    pad_multiple`): ``chunk`` for the single plan, ``num_devices * chunk`` for
    the sharded plan — one pad, host-side, so every device shard is a whole
    number of identical fixed-shape chunks.  Padding rows clone the last
    query with qid=-2; callers strip them after the gather via ``[:Q]`` (the
    global unsort returns them to the tail).  Both the snapshot path
    (``TickEngine``/``knn_query_batch_chunked``) and the session registry pad
    through HERE, which is what makes their padded batches — and hence their
    results and stats — bit-identical.
    """
    import numpy as np

    nq = qpos.shape[0]
    padded = pad_capacity(nq, multiple)
    if padded == nq:
        return qpos, qid
    pad = padded - nq
    qpos = np.concatenate([qpos, np.tile(np.asarray(qpos[-1:]), (pad, 1))])
    qid = np.concatenate([np.asarray(qid), np.full((pad,), -2, np.int32)])
    return qpos, qid


def object_shard_capacity(n_objects: int, num_shards: int) -> int:
    """Rows per object shard under the EQUAL partition: ``ceil(N / R)``.

    The equal-split object plans slice the Morton-sorted object array into
    ``num_shards`` consecutive slices of this capacity (the last one padded
    with sentinel id -1 rows) — an object's owning shard is its Morton
    *rank* divided by this capacity.  Under ``cost_balanced`` the slices
    are uneven and ownership is defined by the boundaries the tick returns
    (``PlanAux.object_bounds``); ``repro.core.ticks.object_shard_of``
    evaluates either rule device-side for delta-ingest routing.
    """
    if num_shards < 1:
        raise ValueError(f"num_shards must be >= 1, got {num_shards}")
    return -(-max(1, n_objects) // num_shards)


# --------------------------------------------------------------------------
# cost estimates (the partitioner's seed — count-pyramid statistics)
# --------------------------------------------------------------------------


def _query_cost_estimate(index: QuadtreeIndex, qpos_s, window: int):
    """(Q,) f32 estimated candidate volume per (Morton-sorted) query.

    The z_map lookup the first SCAN iteration performs anyway: each query's
    own-leaf population, plus one ``window`` as the floor every query pays
    (at least one scheduled scan + navigation).  Pure count-pyramid reads —
    no extra state; refined by the measured EMA from the second tick on.
    """
    fine = morton.morton_encode_points(
        qpos_s, index.origin, index.side, index.l_max
    )
    lvl = index.leaf_level[fine]
    shift = 2 * (index.l_max - lvl)
    key = (fine >> shift) << shift
    span = jnp.left_shift(jnp.int32(1), shift)
    s0 = index.starts[key]
    e0 = index.starts[jnp.clip(key + span, 0, index.n_fine)]
    return (e0 - s0).astype(jnp.float32) + jnp.float32(window)


def _object_row_costs(index: QuadtreeIndex):
    """(N,) f32 per-object cost on the object axis: uniform — count-balanced.

    The object axis stays "objects per slice" on purpose.  Unlike the query
    axis, per-shard sweep cost is NOT additive in rows: every (replicated)
    query runs a full local k-NN against each slice, and a slice's cost
    grows with its spatial extent — measured on Zipf workloads, balancing
    slices by query-interaction density instead of count inflated total
    candidate volume by >2x (sparse slices grew, and every query paid to
    search them).  Equal-count Morton slices are also the memory constraint
    the object axis exists for (ceil(N/R) rows per device).  The uniform
    cost still flows through the Partitioner seam, so a future object-axis
    cost model (ROADMAP: dynamic re-sharding without rebuild) plugs in
    without touching the plans.
    """
    return jnp.ones((index.n_objects,), jnp.float32)


def _ema_next(prev_rows, measured_rows, alpha: float):
    """Per-query cost EMA step; rows with no history adopt the measurement."""
    a = jnp.float32(alpha)
    return jnp.where(
        prev_rows > 0, (1 - a) * prev_rows + a * measured_rows, measured_rows
    )


# --------------------------------------------------------------------------
# static-capacity padding + uneven-shard addressing helpers
# --------------------------------------------------------------------------


def _pad_tail_rows(qpos_s, qid_s, extra: int):
    """Sorted query arrays padded by ``extra`` clone rows (qid -2) so a
    shard's ``dynamic_slice`` of one capacity never clamps at the tail.

    Built by static-slice scatter (``.at[:n].set``), NOT ``jnp.concatenate``
    — see :func:`_pad_object_slices` for the jax-0.4.x GSPMD rationale.
    """
    n = qpos_s.shape[0]
    qp = (
        jnp.zeros((n + extra, 2), qpos_s.dtype)
        .at[:n].set(qpos_s)
        .at[n:].set(qpos_s[-1])
    )
    qi = jnp.full((n + extra,), -2, jnp.int32).at[:n].set(qid_s)
    return qp, qi


def _pad_object_tail(index: QuadtreeIndex, extra: int):
    """Morton-sorted (pos, gids, codes) padded by ``extra`` sentinel rows.

    Same construction as :func:`_pad_object_slices` (clone-position, id -1
    rows the scan's validity mask drops), but sized for the boundary-sliced
    path: a shard reads ``capacity`` rows starting at its boundary, so the
    tail needs ``capacity`` spare rows for the last shard's mask region.
    The padded codes clone the last real code — consistent with the cloned
    positions (``encode`` of the clone position IS the clone code), which is
    what keeps the derived local-index path (:func:`_local_index_derived`)
    bitwise-equal to re-encoding the cloned slice.
    """
    n = index.n_objects
    opos = (
        jnp.zeros((n + extra, 2), index.pos.dtype)
        .at[:n].set(index.pos)
        .at[n:].set(index.pos[-1])
    )
    oids = jnp.full((n + extra,), -1, jnp.int32).at[:n].set(index.ids)
    ocodes = (
        jnp.zeros((n + extra,), jnp.int32)
        .at[:n].set(index.codes)
        .at[n:].set(index.codes[-1])
    )
    return opos, oids, ocodes


def _pad_object_slices(index: QuadtreeIndex, num_shards: int):
    """Morton-sorted (pos, gids) padded so every EQUAL shard slice is equal.

    Padding rows clone the last object's position (staying at the tail of the
    Morton order, so slices remain Morton-contiguous) with sentinel id -1 —
    the scan's validity mask drops them, so they can never enter a result
    list (they only inflate the padded shard's candidate statistic).

    Built by static-slice scatter (``.at[:n].set``), NOT ``jnp.concatenate``:
    on jax 0.4.x, a concatenate produced inside the enclosing jit and fed to
    the fully-manual shard_map fallback over a 2-D mesh is mis-partitioned by
    GSPMD — devices receive garbage slices (bit-parity caught it on the
    forced 8-device grid; eager mode and 1-D meshes are unaffected).

    Kept for the mesh-free R-way composition harness
    (tests/test_properties.py); the plans themselves now slice by
    partitioner boundaries via :func:`_pad_object_tail`.
    """
    n = index.n_objects
    cap = object_shard_capacity(n, num_shards)
    pad = num_shards * cap - n
    if not pad:
        return index.pos, index.ids
    opos = (
        jnp.zeros((n + pad, 2), index.pos.dtype)
        .at[:n].set(index.pos)
        .at[n:].set(index.pos[-1])
    )
    oids = jnp.full((n + pad,), -1, jnp.int32).at[:n].set(index.ids)
    return opos, oids


def _owner_positions(bounds, nq: int, chunk: int, shard_stride: int):
    """Row positions of the global sorted batch inside the tiled gather.

    The uneven-shard paths emit shard ``r``'s rows starting at
    ``r * shard_stride`` of the concatenated ``shard_map`` output (each
    shard a fixed ``capacity`` block, real rows first).  Global sorted row
    ``j`` lives in chunk ``c = j // chunk``, owned by the shard whose
    boundary interval contains ``c`` (``searchsorted`` over the chunk-unit
    boundaries), at chunk offset ``c - bounds[r]`` within that shard.
    """
    rows = jnp.arange(nq, dtype=jnp.int32)
    c = rows // chunk
    r = (jnp.searchsorted(bounds, c, side="right") - 1).astype(jnp.int32)
    return r * shard_stride + (c - bounds[r]) * chunk + rows % chunk


def _local_index(opos, oids, origin, side, *, l_max, th_quad):
    """A shard-local quadtree over one Morton-contiguous object slice.

    Built with the *global* region geometry (origin/side/l_max), so Morton
    codes — and hence query sort order and navigation arithmetic — agree
    with every other shard and with the single plan.  ``build_index``
    assigns ids by sort position within its input; they are remapped through
    ``oids`` back to global object ids so result lists and the qid
    self-exclusion are partition-invariant.
    """
    local = build_index(opos, origin, side, l_max=l_max, th_quad=th_quad)
    return dataclasses.replace(local, ids=oids[local.ids])


def _local_index_derived(origin, side, opos_l, oids_l, codes_l, clone_code,
                         gstarts, start, own, capo: int, *, l_max, th_quad):
    """The shard-local quadtree DERIVED from the globally maintained order.

    The incremental maintenance path (DESIGN.md §15) keeps the global index's
    ``(code, id)``-sorted order current by splicing only the moved rows — and
    a device's Morton-contiguous boundary slice of that order is *already*
    sorted, so :func:`_local_index`'s ``build_index`` (encode + stable argsort
    + bincount over the slice) is the identity permutation re-deriving what
    the global arrays already hold:

    * ``pos``/``ids``/``codes`` are the masked slice itself (surplus capacity
      rows collapse onto the last owned row / its code, exactly as the build
      path's clone rows encode);
    * the local count pyramid is interval arithmetic over the GLOBAL
      ``starts`` (:func:`~repro.core.quadtree.local_pyramid_from_starts`) —
      integer-exact equal to the build path's ``bincount``;
    * ``leaf_level`` and local ``starts`` are the same ``_leaf_levels`` /
      ``starts_from_pyramid`` ops over that (bitwise-equal) pyramid.

    Net: per-shard index maintenance costs O(4**l_max) gathers + adds instead
    of the build path's O(capo log capo) sort — the local trees pay for churn
    (already paid globally, Δ-sized) instead of N/R, which is the tentpole of
    the sharded incremental maintenance PR.  Bitwise-equal to
    :func:`_local_index` whenever the global index is current for the sliced
    arrays (pinned by tests/test_maintenance.py and the property harness).
    """
    pyramid = local_pyramid_from_starts(
        gstarts, start, own, clone_code, capo, l_max
    )
    leaf_level = _leaf_levels(pyramid, l_max, th_quad)
    starts = starts_from_pyramid(pyramid, l_max)
    return QuadtreeIndex(
        origin=origin,
        side=side,
        pos=opos_l,
        ids=oids_l,
        codes=codes_l,
        starts=starts,
        leaf_level=leaf_level,
        pyramid=pyramid,
        l_max=l_max,
        th_quad=th_quad,
    )


def _take_replica0(x, n_replicas: int):
    """(n_replicas * Q, ...) tiled output -> one replica's (Q, ...) rows."""
    if n_replicas == 1:
        return x
    return x.reshape((n_replicas, x.shape[0] // n_replicas) + x.shape[1:])[0]


def _stats1(st: KnnStats) -> KnnStats:
    """Scalar stats -> (1,) arrays, the tiled per-shard out_spec unit."""
    return KnnStats(*(x.reshape(1) for x in st))


def _stats_spec(spec) -> KnnStats:
    """One ``shard_map`` spec for every :class:`KnnStats` counter."""
    return KnnStats(*(spec,) * len(KnnStats._fields))


def _stats_total(st_t: KnnStats) -> KnnStats:
    """Gathered (R,) per-shard stats -> global scalars (their sum).

    The global candidate counter is DEFINED as the sum of the per-shard
    counters, so ``aux.stats.candidates == aux.shard_candidates.sum()``
    holds bitwise by construction.
    """
    return KnnStats(*(x.sum() for x in st_t))


# --------------------------------------------------------------------------
# chunked sweeps (trace-level bodies shared by the plans)
# --------------------------------------------------------------------------


@stage("order")
def _sort_queries(index, qpos, qid):
    """The batch in Morton order: ``(order, inv, qpos[order], qid[order])``."""
    order, inv = _sort_unsort(index, qpos)
    return order, inv, qpos[order], qid[order]


@stage("order")
def _unsort(idx_s, d2_s, inv):
    """Sorted-order results back in the caller's order, distances euclidean."""
    return idx_s[inv], jnp.sqrt(d2_s[inv])


@stage("sweep")
def _chunked_sweep(index, qpos_s, qid_s, *, k, window, chunk, max_nav,
                   max_iters, executor):
    """``lax.map`` of the sorted-query program over fixed-shape chunks.

    Trace-level body shared by the plans: on the single plan it covers the
    whole batch, on the mesh plans it is the device-local program inside
    ``shard_map``.  Inputs must already be Morton-sorted and a whole number
    of chunks.  Returns ``(idx, d2, stats, cand_q)`` — the per-query
    measured candidate volume rides along for the cost-EMA feedback loop.
    """
    nq = qpos_s.shape[0]
    n_chunks = nq // chunk
    tables = window_tables(index, window)  # once for every chunk
    nav = nav_table(index)

    def one_chunk(args):
        qp, qi = args
        return _knn_sorted_impl(
            index, qp, qi, k, window, max_nav, max_iters, executor, tables,
            nav,
        )

    idx_c, d2_c, stats_c, cq_c = jax.lax.map(
        one_chunk,
        (qpos_s.reshape(n_chunks, chunk, 2), qid_s.reshape(n_chunks, chunk)),
    )
    stats = _stats_total(stats_c)
    return idx_c.reshape(nq, k), d2_c.reshape(nq, k), stats, cq_c.reshape(nq)


@stage("sweep")
def _chunked_sweep_masked(index, qpos_s, qid_s, n_live_chunks, *, k, window,
                          chunk, max_nav, max_iters, executor):
    """:func:`_chunked_sweep` with a dynamic live-chunk count.

    The uneven-shard paths compile every shard at a fixed chunk *capacity*;
    a shard that owns fewer chunks skips the dead tail with a ``lax.cond``
    per chunk (``lax.map`` lowers to ``scan``, so the dead branch really is
    skipped, not select-executed).  Dead chunks contribute (-1, inf) rows —
    never gathered — and zero stats, so per-shard counters only count owned
    work.
    """
    nq = qpos_s.shape[0]
    n_chunks = nq // chunk
    tables = window_tables(index, window)  # once for every chunk
    nav = nav_table(index)

    def one_chunk(args):
        qp, qi, live = args

        def real(_):
            return _knn_sorted_impl(
                index, qp, qi, k, window, max_nav, max_iters, executor, tables,
                nav,
            )

        def dead(_):
            return (
                jnp.full((chunk, k), -1, jnp.int32),
                jnp.full((chunk, k), jnp.inf, jnp.float32),
                zero_stats(),
                jnp.zeros((chunk,), jnp.float32),
            )

        return jax.lax.cond(live, real, dead, None)

    live = jnp.arange(n_chunks, dtype=jnp.int32) < n_live_chunks
    idx_c, d2_c, stats_c, cq_c = jax.lax.map(
        one_chunk,
        (qpos_s.reshape(n_chunks, chunk, 2), qid_s.reshape(n_chunks, chunk),
         live),
    )
    stats = _stats_total(stats_c)
    return idx_c.reshape(nq, k), d2_c.reshape(nq, k), stats, cq_c.reshape(nq)


def _object_merge_local(origin, side, opos_r, oids_r, ocodes_r, gstarts,
                        qp_l, qi_l, ownq_chunks, bo, capo, *, l_max, th_quad,
                        k, window, chunk, max_nav, max_iters, executor, merge,
                        maintenance="rebuild"):
    """Device-local body shared by object_sharded and hybrid (inside shard_map).

    Carves the device's own Morton-contiguous object slice out of the padded
    (replicated) object arrays by its ``"object"``-axis boundary interval
    (``dynamic_slice`` of one static ``capo``-row capacity; rows past the
    owned count take sentinel id -1 — identical semantics to the equal
    plan's tail padding, so the valid candidate set per shard is exactly the
    boundary interval), builds the local quadtree over the slice, sweeps the
    (replicated or query-sharded) batch over it, then reduces the per-shard
    partial lists across the ``object`` mesh axis: ``all_gather`` of the
    (Q_local, k) lists — O(R·Q·k), list-sized, never candidate-sized —
    followed by a local binary ``tree_merge_lists`` with the selected MERGE
    backend.  Every device along the object axis computes the identical
    merged list (the reduction is deterministic), so the output is
    replicated on that axis.  ``ownq_chunks`` is the query-axis live-chunk
    count (None = whole batch, the object_sharded case); the per-query
    measured candidate volume is psum-reduced over the object axis so the
    cost EMA sees each query's whole-tick volume.

    ``origin``/``side`` arrive as explicit (replicated) operands, not a
    closure — shard_map bodies must not capture traced values.

    ``maintenance`` (a STATIC python string, safe to close over) selects how
    the device-local quadtree is obtained: ``"rebuild"`` re-derives it from
    the sliced positions with :func:`_local_index` (encode + argsort +
    bincount over ``capo`` rows — the pre-seam behaviour and the bench
    baseline); any other mode (``"incremental"`` / ``"skip"``) means the
    global index's sorted order and pyramid are current for the sliced
    arrays, so the local tree is *derived* from them
    (:func:`_local_index_derived`: masked slice + interval pyramid from the
    replicated global ``starts``) — no per-device sort, O(4**l_max) instead
    of O(capo log capo).  ``ocodes_r``/``gstarts`` carry the padded global
    codes and global prefix offsets for that path (replicated operands, dead
    code under ``"rebuild"``).

    Two jax-0.4.x fallback-shard_map miscompiles shape this body (both
    caught by the bit-parity harness on the forced 8-device grid; newer jax
    and eager mode are unaffected, and the workarounds are semantically
    neutral there):

    * object arrays enter REPLICATED and each device slices locally
      (``axis_index`` + ``dynamic_slice``) — an in_spec that splits a value
      computed inside the enclosing jit along the object axis hands some
      devices garbage slices;
    * outputs leave TILED over every mesh axis, never spec'd as replicated —
      an out_spec that omits a mesh axis of a 2-D mesh assembles garbage
      from the "replicated" dim.  The caller keeps replica 0
      (:func:`_take_replica0` / :func:`_owner_positions`).
    """
    r = jax.lax.axis_index("object")
    start = bo[r]
    own = bo[r + 1] - bo[r]
    opos_raw = jax.lax.dynamic_slice_in_dim(opos_r, start, capo, 0)
    oids_raw = jax.lax.dynamic_slice_in_dim(oids_r, start, capo, 0)
    mask = jnp.arange(capo, dtype=jnp.int32) < own
    # rows past the owned count are the NEXT shard's objects (the capacity
    # window overlaps it): besides dropping their ids, pile their positions
    # onto the slice's last owned row — left in place they would occupy real
    # cells of the local tree and attract scans (capacity slack would turn
    # into measured work); collapsed they cost at most one leaf, exactly
    # like the equal plan's tail padding
    clone = opos_raw[jnp.clip(own - 1, 0, capo - 1)]
    opos_l = jnp.where(mask[:, None], opos_raw, clone[None, :])
    oids_l = jnp.where(mask, oids_raw, -1)
    if maintenance == "rebuild":
        local = _local_index(opos_l, oids_l, origin, side,
                             l_max=l_max, th_quad=th_quad)
    else:
        codes_raw = jax.lax.dynamic_slice_in_dim(ocodes_r, start, capo, 0)
        clone_code = codes_raw[jnp.clip(own - 1, 0, capo - 1)]
        codes_l = jnp.where(mask, codes_raw, clone_code)
        local = _local_index_derived(
            origin, side, opos_l, oids_l, codes_l, clone_code, gstarts,
            start, own, capo, l_max=l_max, th_quad=th_quad,
        )
    if ownq_chunks is None:
        idx_l, d2_l, st, cq_l = _chunked_sweep(
            local, qp_l, qi_l, k=k, window=window, chunk=chunk,
            max_nav=max_nav, max_iters=max_iters, executor=executor,
        )
    else:
        idx_l, d2_l, st, cq_l = _chunked_sweep_masked(
            local, qp_l, qi_l, ownq_chunks, k=k, window=window, chunk=chunk,
            max_nav=max_nav, max_iters=max_iters, executor=executor,
        )
    with stage("merge"):
        d2_all = jax.lax.all_gather(d2_l, "object")  # (R, Q_local, k)
        idx_all = jax.lax.all_gather(idx_l, "object")
        d2_m, idx_m = tree_merge_lists(d2_all, idx_all, k=k, merge=merge)
        cq_m = jax.lax.psum(cq_l, "object")
    return idx_m, d2_m, _stats1(st), cq_m


class ExecutionPlan:
    """Interface: device layout of one tick's query sweep (see module doc)."""

    name: ClassVar[str]

    @property
    def object_axis_size(self) -> int:
        """Shards on the object axis (1 = objects unsharded).

        The serving layer reads this to route delta updates to the owning
        shard (``repro.core.ticks.object_shard_of``; DESIGN.md §12).
        """
        return 1

    def pad_multiple(self, chunk: int) -> int:
        """Host-side padding granularity for :func:`pad_queries`."""
        raise NotImplementedError

    def run(self, index: QuadtreeIndex, qpos, qid, qcost, *, k, window,
            chunk, max_nav, max_iters, executor, qweight=None,
            maintenance="rebuild"):
        """Trace-level tick sweep: (index, padded Q) -> (idx, dist, aux).

        ``qpos.shape[0]`` must be a whole multiple of ``pad_multiple(chunk)``;
        ``qcost`` is the (Q,) per-query cost EMA in the caller's row order
        (zeros = no history; the count-pyramid estimate seeds instead).
        ``qweight`` is an optional (Q,) f32 multiplier on the boundary-seeding
        cost (the serving layer's tenant-fairness weights,
        ``core.balance.tenant_fair_weights``); it scales *influence on shard
        boundaries only* — plans that never split the query axis ignore it,
        and because boundaries only move shard ownership (DESIGN.md §13) it
        can never change results.  ``maintenance`` is the STATIC mode the
        tick step refreshed the index under (DESIGN.md §15): plans without
        per-device local trees ignore it; the object-axis plans use it to
        pick the local-index path — ``"rebuild"`` re-builds each local tree
        from its slice, ``"incremental"``/``"skip"`` derive it from the
        (current) global sorted order with no per-device sort.  Results come
        back in the caller's query order, distances euclidean; ``aux`` is the
        :class:`PlanAux` record.
        """
        raise NotImplementedError

    def describe(self) -> str:
        """Human-readable mesh/layout summary (the example service prints it)."""
        raise NotImplementedError


@dataclasses.dataclass(frozen=True)
class SinglePlan(ExecutionPlan):
    """One device, the refactor-invariant path: sort -> chunked sweep -> unsort.

    Has no split axes, so the partitioner seam is moot here — but the
    per-query cost EMA is still maintained (measured candidate volume per
    query), so a session that later runs a cost-balanced mesh plan starts
    from warm costs.
    """

    name: ClassVar[str] = "single"

    def pad_multiple(self, chunk: int) -> int:
        return chunk

    def run(self, index, qpos, qid, qcost, *, k, window, chunk, max_nav,
            max_iters, executor, qweight=None, maintenance="rebuild"):
        del qweight  # no query-axis split: fairness weights have no seam here
        del maintenance  # no local trees: the global index is swept directly
        order, inv, qpos_s, qid_s = _sort_queries(index, qpos, qid)
        idx_s, d2_s, stats, cq_s = _chunked_sweep(
            index, qpos_s, qid_s, k=k, window=window, chunk=chunk,
            max_nav=max_nav, max_iters=max_iters, executor=executor,
        )
        qcost_next = _ema_next(qcost[order], cq_s, _EMA_ALPHA_DEFAULT)[inv]
        aux = PlanAux(
            stats=stats,
            shard_candidates=stats.candidates.reshape(1),
            shard_iterations=stats.iterations.reshape(1),
            qcost_next=qcost_next,
            object_bounds=jnp.asarray([0, index.n_objects], jnp.int32),
        )
        return (*_unsort(idx_s, d2_s, inv), aux)

    def describe(self) -> str:
        return "plan=single mesh=() devices=1"


@dataclasses.dataclass(frozen=True)
class ShardedPlan(ExecutionPlan):
    """Replicated index, query-sharded sweep over a 1-D ``("query",)`` mesh.

    ONE boundary-driven body for both partitioners (the last split-``in_specs``
    path was retired with DESIGN.md §14): the sorted batch enters ``shard_map``
    REPLICATED, boundaries ride in as data, and each device ``dynamic_slice``s
    its owned chunk range out of one static capacity — chunks past its
    boundary interval are skipped by the masked sweep.  Under ``equal`` the
    boundaries are the static equal-count cuts (every device owns exactly
    ``n_chunks / R`` chunks, so no chunk is ever masked); under
    ``cost_balanced`` they re-balance every tick.  Replicating the query
    batch is bounded by the index this plan already replicates.
    """

    num_devices: int
    partitioner: Partitioner = EqualPartitioner()
    name: ClassVar[str] = "sharded"

    def __post_init__(self):
        if self.num_devices < 1:
            raise ValueError(f"num_devices must be >= 1, got {self.num_devices}")

    def pad_multiple(self, chunk: int) -> int:
        # every device shard must be a whole number of chunks
        return self.num_devices * chunk

    def run(self, index, qpos, qid, qcost, *, k, window, chunk, max_nav,
            max_iters, executor, qweight=None, maintenance="rebuild"):
        del maintenance  # index replicated, no local trees to maintain
        from jax.sharding import PartitionSpec as P

        mesh = make_query_mesh(self.num_devices)
        with use_rules(mesh, SPATIAL_RULES) as rules:
            qpos_spec = rules.spec(("query", None))   # (Q, 2) split on axis 0
            qvec_spec = rules.spec(("query",))        # (Q,) split
        repl_spec = P()

        # global Morton sort: shards stay spatially coherent AND chunk
        # boundaries coincide with the single plan's (bit-identity argument)
        order, inv, qpos_s, qid_s = _sort_queries(index, qpos, qid)
        obj_bounds = jnp.asarray([0, index.n_objects], jnp.int32)
        alpha = getattr(self.partitioner, "ema_alpha", _EMA_ALPHA_DEFAULT)

        nq = qpos.shape[0]
        n_chunks = nq // chunk
        cap_c = self.partitioner.query_capacity(n_chunks, self.num_devices)
        est_s = _query_cost_estimate(index, qpos_s, window)
        prev_s = qcost[order]
        cost_s = jnp.where(prev_s > 0, prev_s, est_s)
        if qweight is not None:
            # tenant-fair boundary seeding: weights scale each query's
            # influence on the split, never its results (DESIGN.md §16)
            cost_s = cost_s * qweight[order]
        bounds = self.partitioner.query_boundaries(
            cost_s.reshape(n_chunks, chunk).sum(axis=1), self.num_devices
        )
        qs_pad, qi_pad = _pad_tail_rows(qpos_s, qid_s, cap_c * chunk)

        def device_local(index, qp, qi, b):
            r = jax.lax.axis_index("query")
            start = b[r] * chunk
            ownq = b[r + 1] - b[r]
            qp_l = jax.lax.dynamic_slice_in_dim(qp, start, cap_c * chunk, 0)
            qi_l = jax.lax.dynamic_slice_in_dim(qi, start, cap_c * chunk, 0)
            idx_l, d2_l, st, cq_l = _chunked_sweep_masked(
                index, qp_l, qi_l, ownq, k=k, window=window, chunk=chunk,
                max_nav=max_nav, max_iters=max_iters, executor=executor,
            )
            # local (1,)-shaped stats leave TILED along the mesh — the
            # gathered (R,) rows ARE the per-shard counters; the global
            # drift statistic is their sum, taken outside the mesh
            return idx_l, d2_l, _stats1(st), cq_l

        # batch + boundaries enter REPLICATED (devices self-slice by
        # boundary), outputs leave tiled — the jax-0.4.x discipline of
        # _object_merge_local applied to the query axis
        sharded = shard_map_compat(
            device_local,
            mesh=mesh,
            in_specs=(repl_spec, repl_spec, repl_spec, repl_spec),
            out_specs=(qpos_spec, qpos_spec,
                       _stats_spec(qvec_spec),
                       qvec_spec),
            axis_names={"query"},
            check_vma=False,
        )
        idx_t, d2_t, st_t, cq_t = sharded(index, qs_pad, qi_pad, bounds)
        pos = _owner_positions(bounds, nq, chunk, cap_c * chunk)
        idx_s, d2_s, cq_s = idx_t[pos], d2_t[pos], cq_t[pos]

        qcost_next = _ema_next(qcost[order], cq_s, alpha)[inv]
        aux = PlanAux(
            stats=_stats_total(st_t),
            shard_candidates=st_t.candidates,
            shard_iterations=st_t.iterations,
            qcost_next=qcost_next,
            object_bounds=obj_bounds,
        )
        return (*_unsort(idx_s, d2_s, inv), aux)

    def describe(self) -> str:
        return (
            f"plan=sharded mesh=({self.num_devices},) axes=('query',) "
            f"devices={self.num_devices} partitioner={self.partitioner.name}"
        )


@dataclasses.dataclass(frozen=True)
class ObjectShardedPlan(ExecutionPlan):
    """Morton-sliced objects, one local quadtree per device, merge-reduced.

    The inverse decomposition of :class:`ShardedPlan`: the query batch is
    *replicated* across the 1-D ``("object",)`` mesh while each device owns
    a Morton-contiguous boundary interval of the object array — equal-count
    (``ceil(N / R)``) under the ``equal`` partitioner, interaction-density
    balanced under ``cost_balanced`` — and a quadtree over just its slice;
    per-device object state shrinks by R, which is what scales the *object*
    axis past one device's memory (the paper's massive datasets).  The
    per-query partial lists reduce across the mesh with a binary tree of
    ``merge`` (a MERGE backend name; DESIGN.md §12).
    """

    num_devices: int
    merge: str = "dense_merge"
    partitioner: Partitioner = EqualPartitioner()
    name: ClassVar[str] = "object_sharded"

    def __post_init__(self):
        if self.num_devices < 1:
            raise ValueError(f"num_devices must be >= 1, got {self.num_devices}")
        get_merge_backend(self.merge)  # fail fast on unknown names

    @property
    def object_axis_size(self) -> int:
        return self.num_devices

    def pad_multiple(self, chunk: int) -> int:
        # queries are replicated, not split: single-plan granularity
        return chunk

    def run(self, index, qpos, qid, qcost, *, k, window, chunk, max_nav,
            max_iters, executor, qweight=None, maintenance="rebuild"):
        del qweight  # queries replicated, not split: no boundary to seed
        from jax.sharding import PartitionSpec as P

        mesh = make_object_mesh(self.num_devices)
        with use_rules(mesh, SPATIAL_RULES) as rules:
            out2_spec = rules.spec(("object", None))  # tiled outputs
            out1_spec = rules.spec(("object",))
        repl_spec = P()

        order, inv, qpos_s, qid_s = _sort_queries(index, qpos, qid)
        capo = self.partitioner.object_capacity(
            index.n_objects, self.num_devices
        )
        bo = self.partitioner.object_boundaries(
            _object_row_costs(index), self.num_devices
        )
        opos, oids, ocodes = _pad_object_tail(index, capo)

        def device_local(origin, side, opos_r, oids_r, ocodes_r, gstarts,
                         qp, qi, bo_r):
            return _object_merge_local(
                origin, side, opos_r, oids_r, ocodes_r, gstarts, qp, qi,
                None, bo_r, capo,
                l_max=index.l_max, th_quad=index.th_quad, k=k, window=window,
                chunk=chunk, max_nav=max_nav, max_iters=max_iters,
                executor=executor, merge=self.merge, maintenance=maintenance,
            )

        # object arrays + boundaries enter replicated (devices self-slice by
        # axis index), outputs leave tiled over the object axis
        # (replica-major); see _object_merge_local for why nothing else is
        # spec'd
        sharded = shard_map_compat(
            device_local,
            mesh=mesh,
            in_specs=(repl_spec,) * 9,
            out_specs=(out2_spec, out2_spec,
                       _stats_spec(out1_spec), out1_spec),
            axis_names={"object"},
            check_vma=False,
        )
        idx_t, d2_t, st_t, cq_t = sharded(
            index.origin, index.side, opos, oids, ocodes, index.starts,
            qpos_s, qid_s, bo
        )
        idx_s = _take_replica0(idx_t, self.num_devices)
        d2_s = _take_replica0(d2_t, self.num_devices)
        cq_s = _take_replica0(cq_t, self.num_devices)
        alpha = getattr(self.partitioner, "ema_alpha", _EMA_ALPHA_DEFAULT)
        qcost_next = _ema_next(qcost[order], cq_s, alpha)[inv]
        aux = PlanAux(
            stats=_stats_total(st_t),
            shard_candidates=st_t.candidates,
            shard_iterations=st_t.iterations,
            qcost_next=qcost_next,
            object_bounds=bo,
        )
        return (*_unsort(idx_s, d2_s, inv), aux)

    def describe(self) -> str:
        return (
            f"plan=object_sharded mesh=({self.num_devices},) axes=('object',) "
            f"devices={self.num_devices} merge={self.merge} "
            f"partitioner={self.partitioner.name}"
        )


@dataclasses.dataclass(frozen=True)
class HybridPlan(ExecutionPlan):
    """2-D ``("query", "object")`` mesh: both decompositions composed.

    Device ``(i, j)`` sweeps query-boundary interval ``i`` over object
    slice ``j``; results merge-reduce along the object axis (identical on
    every device of a query row) and gather by concatenation along the
    query axis.  The query padding granularity is ``query_devices * chunk``
    — object slicing needs no query-side padding (DESIGN.md §12).  Both
    axes take their boundaries from the partitioner (equal-count under
    ``equal``, cost-balanced under ``cost_balanced``); like
    :class:`ShardedPlan` there is ONE boundary-driven body for both
    partitioners — the query batch enters replicated either way, which is
    bounded by the object arrays this plan already replicates, and equal
    boundaries never mask a chunk.
    """

    query_devices: int
    object_devices: int
    merge: str = "dense_merge"
    partitioner: Partitioner = EqualPartitioner()
    name: ClassVar[str] = "hybrid"

    def __post_init__(self):
        if self.query_devices < 1 or self.object_devices < 1:
            raise ValueError(
                "mesh_shape axes must be >= 1, got "
                f"({self.query_devices}, {self.object_devices})"
            )
        get_merge_backend(self.merge)  # fail fast on unknown names

    @property
    def object_axis_size(self) -> int:
        return self.object_devices

    def pad_multiple(self, chunk: int) -> int:
        # every query shard must be a whole number of chunks
        return self.query_devices * chunk

    def run(self, index, qpos, qid, qcost, *, k, window, chunk, max_nav,
            max_iters, executor, qweight=None, maintenance="rebuild"):
        from jax.sharding import PartitionSpec as P

        qd, od = self.query_devices, self.object_devices
        mesh = make_spatial_mesh(qd, od)
        repl_spec = P()
        # outputs tiled over BOTH axes — query-major, object as the inner
        # (replica) block; see _object_merge_local for why
        out2_spec = P(("query", "object"), None)
        out1_spec = P(("query", "object"))

        order, inv, qpos_s, qid_s = _sort_queries(index, qpos, qid)
        nq = qpos.shape[0]
        n_chunks = nq // chunk
        capq = self.partitioner.query_capacity(n_chunks, qd)
        capo = self.partitioner.object_capacity(index.n_objects, od)
        est_s = _query_cost_estimate(index, qpos_s, window)
        prev_s = qcost[order]
        cost_s = jnp.where(prev_s > 0, prev_s, est_s)
        if qweight is not None:
            cost_s = cost_s * qweight[order]
        bq = self.partitioner.query_boundaries(
            cost_s.reshape(n_chunks, chunk).sum(axis=1), qd
        )
        bo = self.partitioner.object_boundaries(_object_row_costs(index), od)
        qs_pad, qi_pad = _pad_tail_rows(qpos_s, qid_s, capq * chunk)
        opos, oids, ocodes = _pad_object_tail(index, capo)

        def device_local(origin, side, opos_r, oids_r, ocodes_r, gstarts,
                         qp, qi, bq_r, bo_r):
            i = jax.lax.axis_index("query")
            qstart = bq_r[i] * chunk
            ownq = bq_r[i + 1] - bq_r[i]
            qp_l = jax.lax.dynamic_slice_in_dim(qp, qstart, capq * chunk, 0)
            qi_l = jax.lax.dynamic_slice_in_dim(qi, qstart, capq * chunk, 0)
            return _object_merge_local(
                origin, side, opos_r, oids_r, ocodes_r, gstarts, qp_l, qi_l,
                ownq, bo_r, capo,
                l_max=index.l_max, th_quad=index.th_quad, k=k, window=window,
                chunk=chunk, max_nav=max_nav, max_iters=max_iters,
                executor=executor, merge=self.merge, maintenance=maintenance,
            )

        sharded = shard_map_compat(
            device_local,
            mesh=mesh,
            in_specs=(repl_spec,) * 10,
            out_specs=(out2_spec, out2_spec,
                       _stats_spec(out1_spec), out1_spec),
            axis_names={"query", "object"},
            check_vma=False,
        )
        idx_t, d2_t, st_t, cq_t = sharded(
            index.origin, index.side, opos, oids, ocodes, index.starts,
            qs_pad, qi_pad, bq, bo
        )
        # shard (i, j) emits at block i*od + j of the tiled output; taking
        # object-replica j=0 makes the query-shard stride od * capq * chunk
        pos = _owner_positions(bq, nq, chunk, od * capq * chunk)
        idx_s, d2_s, cq_s = idx_t[pos], d2_t[pos], cq_t[pos]
        alpha = getattr(self.partitioner, "ema_alpha", _EMA_ALPHA_DEFAULT)
        qcost_next = _ema_next(qcost[order], cq_s, alpha)[inv]
        aux = PlanAux(
            stats=_stats_total(st_t),
            shard_candidates=st_t.candidates,
            shard_iterations=st_t.iterations,
            qcost_next=qcost_next,
            object_bounds=bo,
        )
        return (*_unsort(idx_s, d2_s, inv), aux)

    def describe(self) -> str:
        return (
            f"plan=hybrid mesh=({self.query_devices}, {self.object_devices}) "
            f"axes=('query', 'object') "
            f"devices={self.query_devices * self.object_devices} "
            f"merge={self.merge} partitioner={self.partitioner.name}"
        )


# --------------------------------------------------------------------------
# plan registry — serving/benchmarks/examples select a plan by name
# --------------------------------------------------------------------------

# name -> factory(num_devices | None, Partitioner, merge | None) -> ExecutionPlan
_PLANS: dict = {}


def register_plan(name: str):
    """Decorator: register an ExecutionPlan factory under ``name``."""

    def deco(factory):
        _PLANS[name] = factory
        return factory

    return deco


def plan_names() -> tuple[str, ...]:
    """Names accepted by ``resolve_plan`` / ``EngineConfig.plan``."""
    return tuple(sorted(_PLANS))


@register_plan("single")
def _make_single(num_devices=None, partitioner=None, merge=None) -> SinglePlan:
    # the single plan has no split axes; the partitioner/merge knobs are
    # accepted (specs default them globally) and ignored
    return SinglePlan()


def _as_1d(name: str, num_devices) -> int:
    if num_devices is None:
        return jax.device_count()
    if isinstance(num_devices, (tuple, list)):
        raise ValueError(
            f"plan {name!r} lays a 1-D mesh; mesh_shape must be an int, "
            f"got {tuple(num_devices)!r} (use plan='hybrid' for 2-D shapes)"
        )
    return int(num_devices)


@register_plan("sharded")
def _make_sharded(num_devices=None, partitioner=None, merge=None) -> ShardedPlan:
    # no object axis, hence no merge reduction; the knob is accepted and
    # ignored like the single plan's partitioner
    return ShardedPlan(
        num_devices=_as_1d("sharded", num_devices),
        partitioner=resolve_partitioner(partitioner),
    )


@register_plan("object_sharded")
def _make_object_sharded(
    num_devices=None, partitioner=None, merge=None
) -> ObjectShardedPlan:
    return ObjectShardedPlan(
        num_devices=_as_1d("object_sharded", num_devices),
        partitioner=resolve_partitioner(partitioner),
        **({} if merge is None else {"merge": str(merge)}),
    )


@register_plan("hybrid")
def _make_hybrid(num_devices=None, partitioner=None, merge=None) -> HybridPlan:
    if isinstance(num_devices, (tuple, list)):
        if len(num_devices) != 2:
            raise ValueError(
                f"hybrid mesh_shape must be (query, object), got {num_devices!r}"
            )
        q, o = (int(x) for x in num_devices)
    else:
        q, o = default_hybrid_shape(num_devices)
    return HybridPlan(
        query_devices=q, object_devices=o,
        partitioner=resolve_partitioner(partitioner),
        **({} if merge is None else {"merge": str(merge)}),
    )


def resolve_plan(plan, *, num_devices=None, partitioner=None,
                 merge=None) -> ExecutionPlan:
    """Name | ExecutionPlan | None -> ExecutionPlan (default: single).

    ``num_devices`` parameterizes named plans (``EngineConfig.mesh_shape``):
    an int for the 1-D plans (``sharded`` / ``object_sharded``, default every
    visible device) or a ``(query, object)`` pair for ``hybrid`` (default the
    most balanced factorization of the device count).  ``partitioner`` is a
    :mod:`repro.core.balance` name or instance (default ``equal``); ``merge``
    a MERGE backend name for the object-axis reduction (default
    ``dense_merge``; ``fused_multi`` collapses the tree into one Pallas
    program — DESIGN.md §14).  Both are ignored when ``plan`` is already an
    ExecutionPlan instance (the instance carries its own).
    """
    if plan is None:
        return SinglePlan()
    if isinstance(plan, ExecutionPlan):
        return plan
    try:
        factory = _PLANS[str(plan)]
    except KeyError:
        raise ValueError(
            f"unknown execution plan {plan!r}; registered: {plan_names()}"
        ) from None
    return factory(num_devices, partitioner, merge)


# --------------------------------------------------------------------------
# jitted drivers
# --------------------------------------------------------------------------


@partial(
    jax.jit,
    static_argnames=("k", "window", "chunk", "max_nav", "max_iters",
                     "executor", "plan", "maintenance"),
)
def run_plan_device(
    index: QuadtreeIndex,
    qpos: jnp.ndarray,
    qid: jnp.ndarray,
    qcost: jnp.ndarray | None = None,
    qweight: jnp.ndarray | None = None,
    *,
    k: int,
    window: int,
    chunk: int,
    max_nav: int,
    max_iters: int,
    executor,
    plan: ExecutionPlan,
    maintenance: str = "rebuild",
):
    """Memory-bounded batch k-NN as ONE device program, laid out by ``plan``.

    ``Q`` must already be a whole number of ``plan.pad_multiple(chunk)`` rows:
    callers pad on the host (:func:`pad_queries`) so the compiled program is
    keyed by chunk count per shard, not by the raw query count — variable
    per-tick batch sizes reuse the same executable.  ``qcost`` is the (Q,)
    per-query cost EMA (None/zeros = no history; the serving session threads
    ``aux.qcost_next`` back in).  ``qweight`` is the optional (Q,) fairness
    multiplier on the boundary seed (None = unweighted; see
    :meth:`ExecutionPlan.run`) — None is a valid pytree leaf-set, so sessions
    that never set weights compile the exact same program as before.

    ``maintenance`` forwards the tick step's STATIC refresh mode to the plan
    (see :meth:`ExecutionPlan.run`): the object-axis plans derive their local
    trees from the global sorted order instead of re-building them whenever
    the mode guarantees that order is current (``"incremental"``/``"skip"``).
    The default ``"rebuild"`` is always valid.

    Returns (nn_idx (Q,k) i32, nn_dist (Q,k) f32 euclidean, aux
    :class:`PlanAux`) in the caller's query order (padding rows come back in
    their input positions).
    """
    nq = qpos.shape[0]
    assert nq % plan.pad_multiple(chunk) == 0, (nq, chunk, plan)
    if qcost is None:
        qcost = jnp.zeros((nq,), jnp.float32)
    return plan.run(
        index,
        qpos.astype(jnp.float32),
        qid.astype(jnp.int32),
        qcost.astype(jnp.float32),
        k=k,
        window=window,
        chunk=chunk,
        max_nav=max_nav,
        max_iters=max_iters,
        executor=executor,
        qweight=None if qweight is None else qweight.astype(jnp.float32),
        maintenance=maintenance,
    )


def knn_chunked_device(index, qpos, qid, *, k, window, chunk, max_nav,
                       max_iters, executor):
    """The single plan's sweep (kept as the PR-1 name and 3-tuple return;
    serving now goes through :func:`run_plan_device` with an explicit plan)."""
    ii, dd, aux = run_plan_device(
        index, qpos, qid, k=k, window=window, chunk=chunk, max_nav=max_nav,
        max_iters=max_iters, executor=executor, plan=SinglePlan(),
    )
    return ii, dd, aux.stats


def knn_sharded_device(index, qpos, qid, *, k, window, chunk, max_nav,
                       max_iters, executor, num_devices):
    """The sharded plan's sweep over ``num_devices`` mesh devices."""
    ii, dd, aux = run_plan_device(
        index, qpos, qid, k=k, window=window, chunk=chunk, max_nav=max_nav,
        max_iters=max_iters, executor=executor,
        plan=ShardedPlan(num_devices=num_devices),
    )
    return ii, dd, aux.stats


def knn_query_batch_chunked(
    index: QuadtreeIndex,
    qpos,
    qid=None,
    *,
    k: int = 32,
    window: int = 128,
    chunk: int = 8192,
    max_nav: int | None = None,
    max_iters: int = 100_000,
    backend=None,
    precision=None,
    plan=None,
    num_devices: int | None = None,
    partitioner=None,
    merge=None,
    maintenance: str = "rebuild",
    with_aux: bool = False,
):
    """Host-friendly wrapper over :func:`run_plan_device` (numpy in/out).

    ``plan``/``num_devices``/``partitioner``/``merge`` select the execution
    plan by name (default ``single`` / ``equal`` / ``dense_merge``);
    ``backend``/``precision`` the executor (default ``dense_topk`` /
    ``fp32``).  Padding and stripping are handled here, once, host-side.
    ``maintenance`` forwards the local-tree path to the object-axis plans
    (``"rebuild"`` builds per-device trees; ``"incremental"`` derives them
    from the index's sorted order — valid because a hand-built index IS
    current for itself).  ``with_aux=True`` appends the host-materialized
    :class:`PlanAux` (per-shard counters, cost EMA, object boundaries) to
    the return tuple — the benchmarks' straggler-gap probe.
    """
    import numpy as np

    from .executor import resolve_executor

    nq = qpos.shape[0]
    if qid is None:
        qid = np.full((nq,), -2, np.int32)
    plan = resolve_plan(plan, num_devices=num_devices, partitioner=partitioner,
                        merge=merge)
    qpos_p, qid_p = pad_queries(
        np.asarray(qpos), np.asarray(qid), plan.pad_multiple(chunk)
    )
    ii, dd, aux = run_plan_device(
        index,
        jnp.asarray(qpos_p, jnp.float32),
        jnp.asarray(qid_p, jnp.int32),
        k=k,
        window=window,
        chunk=chunk,
        max_nav=_resolve_max_nav(index, max_nav),
        max_iters=max_iters,
        executor=resolve_executor(backend, precision),
        plan=plan,
        maintenance=maintenance,
    )
    stats = KnnStats(
        iterations=int(aux.stats.iterations),
        candidates=float(aux.stats.candidates),
        leaves_visited=int(aux.stats.leaves_visited),
        windows_fetched=int(aux.stats.windows_fetched),
        nav_lane_steps=int(aux.stats.nav_lane_steps),
    )
    out = (np.asarray(ii[:nq]), np.asarray(dd[:nq]), stats)
    if with_aux:
        out += (PlanAux(
            stats=stats,
            shard_candidates=np.asarray(aux.shard_candidates),
            shard_iterations=np.asarray(aux.shard_iterations),
            qcost_next=np.asarray(aux.qcost_next[:nq]),
            object_bounds=np.asarray(aux.object_bounds),
        ),)
    return out
