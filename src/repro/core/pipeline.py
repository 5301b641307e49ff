"""Iterative k-NN query computation (paper Sec. 4.2) — TPU/JAX adaptation.

Paper recap: after indexing, every query is joined with its own quadtree leaf
(first iteration); queries whose result list may still be improved by objects in
other leaves remain *active* and advance along the Morton total order of leaves in
two alternating directions (left/right), pruning every leaf/subtree whose box is
farther than the query's current k-th distance, until no query is active.

TPU adaptation (see DESIGN.md §3): the paper materializes per-cell thread-block
tasks on the fly and sorts them by weight to balance GPU SMs.  Under XLA we run a
**masked dense iteration**: all queries advance in lockstep inside one
``lax.while_loop``; per iteration each query either
  * SCANs one fixed-width window of ``W`` candidate objects from its current leaf
    (row fetch by DMA, ``kernels/window_fetch.py`` -> masked distance tile ->
    top-k merge), or
  * NAVigates the *virtual full quadtree* (arithmetic-only, paper Sec. 4.2.2):
    up to ``max_nav`` aligned-block jumps that skip empty (count-pyramid) or
    pruned (box farther than kth) regions in O(4^a)-sized strides; each step
    reads one packed navigation word per lane (:func:`nav_table`).
Queries are pre-sorted by Morton code, so active lanes stay spatially coherent —
the same locality argument as the paper's SM-task packing, expressed as vector-lane
coherence instead of warp coherence.

The SCAN step's distance+selection is NOT inlined here: it dispatches through a
:class:`repro.core.executor.QueryExecutor` to a registered kernel-layer backend
(``dense_topk`` | ``fused_bucket`` | ``brute`` — DESIGN.md §6), carried through
``jax.jit`` as a static argument.

Batching: ``knn_query_batch`` runs one device program over the whole batch.
Memory-bounded chunking and device layout live one layer up, behind the
ExecutionPlan seam (``core/plan.py``, DESIGN.md §10): the ``single`` plan maps
this module's sorted-query program over fixed-shape chunks with ``lax.map``
inside one jitted call, the ``sharded`` plan additionally splits the sorted
batch across a device mesh with ``shard_map``.  (``knn_query_batch_chunked``
remains importable here as a thin delegate — see its docstring.)

Invariants that make block-skipping sound (proved in tests):
  * cursors ``cl``/``cr`` always sit on leaf boundaries;
  * an aligned block that starts (ends) on a leaf boundary is a union of whole
    leaves, hence skippable as a unit;
  * the k-th distance is non-increasing, so a once-far block stays prunable;
  * pruning keeps equal-distance blocks (``<=``/``>`` comparisons) and every
    selection step is lexicographic by ``(d2, id)``, so the final list is the
    unique canonical k-NN answer — independent of scan order, chunk
    boundaries, query sharding AND object partition (DESIGN.md §12; this is
    what lets the object-sharded plans merge per-shard lists bit-exactly).
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.kernels.window_fetch import row_tables, window_fetch
from repro.tracing import stage

from . import morton
from .executor import QueryExecutor, resolve_executor
from .quadtree import QuadtreeIndex

__all__ = [
    "knn_query_batch",
    "knn_query_batch_chunked",
    "default_max_nav",
    "KnnStats",
]

INF = jnp.inf


class KnnStats(NamedTuple):
    iterations: jnp.ndarray  # () i32 — outer while-loop trips
    candidates: jnp.ndarray  # () i64-ish f32 — total candidate object slots scanned
    leaves_visited: jnp.ndarray  # () i32 — scheduled leaf scans (incl. own leaf)
    windows_fetched: jnp.ndarray  # () i32 — lane windows fetched, over trips
    nav_lane_steps: jnp.ndarray  # () i32 — NAV lane steps that ran, over trips


def zero_stats() -> KnnStats:
    """All-zero stats — the masked-out chunk's contribution (core/plan.py)."""
    return KnnStats(
        iterations=jnp.int32(0),
        candidates=jnp.float32(0.0),
        leaves_visited=jnp.int32(0),
        windows_fetched=jnp.int32(0),
        nav_lane_steps=jnp.int32(0),
    )


class _State(NamedTuple):
    best_d: jnp.ndarray  # (Q, k) ascending squared dists, inf-padded
    best_i: jnp.ndarray  # (Q, k) object ids, -1 padded
    scanning: jnp.ndarray  # (Q,) bool
    s_cur: jnp.ndarray  # (Q,) i32 scan interval start (object array)
    e_cur: jnp.ndarray  # (Q,) i32 scan interval end
    off: jnp.ndarray  # (Q,) i32 window offset within interval
    cl: jnp.ndarray  # (Q,) i32 left frontier (fine code, leaf boundary)
    cr: jnp.ndarray  # (Q,) i32 right frontier
    act_l: jnp.ndarray  # (Q,) bool
    act_r: jnp.ndarray  # (Q,) bool
    next_right: jnp.ndarray  # (Q,) bool — alternation bit (paper Sec. 4.2.2)
    it: jnp.ndarray  # () i32
    cand_q: jnp.ndarray  # (Q,) f32 — candidate slots scanned PER QUERY (cost model)
    leaves: jnp.ndarray  # () i32
    fetched: jnp.ndarray  # () i32 — lanes that fetched a window, over trips
    nav_steps: jnp.ndarray  # () i32 — NAV lane steps that ran, over trips


# A navigation word (:func:`nav_table`) packs every read of one NAV step:
# bits 0-4 the probed leaf's level, bit 5 whether that leaf holds objects,
# and bit ``_EMPTY_BIT0 + a - 1`` whether the block of ``4**a`` fine cells
# the step would skip from an aligned cursor is empty, for a = 1..l_max.
_LVL_MASK = 31
_CNT_BIT = 5
_EMPTY_BIT0 = 6


def _shift(x, m, fill):
    """``y[p] = x[p + m]`` (static ``m``), ``fill`` where ``p + m`` leaves ``x``."""
    return jax.lax.pad(x, fill.astype(x.dtype), [(-m, m, 0)])


def nav_table(index: QuadtreeIndex) -> jnp.ndarray:
    """The NAV step's reads for every probed cell, one int32 word each way.

    ``table[dir_r * 4**l_max + cprobe]``, where ``cprobe`` is the fine cell
    a step probes: the cursor's own (rightwards) or the one before it
    (leftwards), clipped into the domain.  What a step reads from
    ``leaf_level``, ``starts`` and the count pyramid depends on that cell
    and the direction only, never on the query, so the walk reads one word
    per lane and step.  Built once for every chunk swept over the index.

    A block's population is a difference of ``starts``, so the empty tests
    compare ``starts`` with itself at static shifts: rightwards the cells
    ``[p, p + 4**a)``, leftwards ``(p - 4**a, p]``.  From an aligned cursor
    these are the aligned blocks the count pyramid holds, and only an
    aligned cursor reads them.  The leftward leaf count reads
    ``starts`` at the leaf's bounds.  No table of a 4**l_max-cell layout is
    repeated or interleaved: on a TPU such relayouts compile to megabytes
    of code in every program that sweeps.
    """
    l_max = index.l_max
    if _EMPTY_BIT0 + l_max > 31:
        raise ValueError(f"l_max {l_max} does not fit a navigation word")
    n_fine = 4**l_max
    ll, starts = index.leaf_level, index.starts
    a0 = (l_max - ll).astype(jnp.int32)
    with stage("nav"):
        left, right = ll, ll
        found_r = jnp.zeros(n_fine, bool)
        for a in range(l_max + 1):
            empty_r = (_shift(starts, 4**a, starts[n_fine])[:n_fine]
                       == starts[:n_fine])
            empty_l = (_shift(starts, 1 - 4**a, starts[0])[:n_fine]
                       == starts[1:])
            # rightwards the leaf is [cprobe, cprobe + span0), clipped
            found_r = jnp.where(a0 == a, ~empty_r, found_r)
            if a:
                right = right | (empty_r.astype(jnp.int32)
                                 << (_EMPTY_BIT0 + a - 1))
                left = left | (empty_l.astype(jnp.int32)
                               << (_EMPTY_BIT0 + a - 1))
        # leftwards it is the aligned block holding cprobe
        cprobe = jnp.arange(n_fine, dtype=jnp.int32)
        span0 = jnp.left_shift(jnp.int32(1), 2 * a0)
        s, e = _leaf_offsets(starts, (cprobe >> (2 * a0)) << (2 * a0), span0)
        left = left | ((e > s).astype(jnp.int32) << _CNT_BIT)
        right = right | (found_r.astype(jnp.int32) << _CNT_BIT)
        return jnp.stack([left, right]).reshape(-1)


def _leaf_offsets(starts, leaf_key, span0):
    """Object interval ``[s, e)`` of the leaf ``[leaf_key, leaf_key + span0)``."""
    n_fine = starts.shape[0] - 1
    return (starts[jnp.clip(leaf_key, 0, n_fine - 1)],
            starts[jnp.clip(leaf_key + span0, 0, n_fine)])


def _nav_step(index: QuadtreeIndex, table, qx, qy, kth2, cursor, run, dir_r):
    """One navigation step; ``dir_r`` is a per-query bool (True = rightwards).

    Returns (found, leaf_key, span0, new_cursor, exhausted):
      found     — a near, non-empty leaf was located (schedule its scan)
      leaf_key, span0 — that leaf's fine cells ``[leaf_key, leaf_key + span0)``
                  (:func:`_leaf_offsets` gives its object interval)
      new_cursor— cursor after the step (past the found leaf, or past the skipped
                  aligned block)
      exhausted — cursor left the domain; direction goes inactive

    The step reads one word of ``table`` (:func:`nav_table`) per lane; the
    rest is arithmetic.  The level loop is unrolled: with no memory read
    left in it, its ``l_max`` distance and bit tests fuse into one
    elementwise pass.
    """
    l_max = index.l_max
    n_fine = 4**l_max
    one = jnp.int32(1)

    exhausted = jnp.where(dir_r, cursor >= n_fine, cursor <= 0)
    cprobe = jnp.clip(jnp.where(dir_r, cursor, cursor - 1), 0, n_fine - 1)
    word = table[dir_r.astype(jnp.int32) * n_fine + cprobe]

    lvl = word & _LVL_MASK
    a0 = (l_max - lvl).astype(jnp.int32)
    span0 = jnp.left_shift(one, 2 * a0)
    # leaf start (right: == cursor; left: aligned block ending at cursor)
    leaf_key = jnp.where(dir_r, cprobe, (cprobe >> (2 * a0)) << (2 * a0))
    leaf_d2 = morton.point_to_block_dist2(
        qx, qy, leaf_key, a0, index.origin, index.side, l_max
    )
    # `<=`, not `<`: leaves whose box sits EXACTLY at the k-th distance are
    # scanned, so every candidate tied at the k-th distance enters selection.
    # Together with the lexicographic (d2, id) selection contract (DESIGN.md
    # §12) this makes the result a pure function of the candidate set —
    # identical bits under any chunking, query sharding or object partition.
    nonempty = ((word >> _CNT_BIT) & 1) == 1
    found = run & ~exhausted & nonempty & (leaf_d2 <= kth2)

    # --- far/empty aligned-block skip: pick the largest admissible jump.
    best_a = a0
    for a in range(1, l_max + 1):
        ai = jnp.int32(a)
        blk = 4**a
        code = jnp.where(dir_r, cursor, cursor - blk)
        in_dom = jnp.where(dir_r, cursor + blk <= n_fine, cursor - blk >= 0)
        empty = ((word >> (_EMPTY_BIT0 + a - 1)) & 1) == 1
        far = (
            morton.point_to_block_dist2(
                qx, qy, code, ai, index.origin, index.side, l_max
            )
            > kth2  # strict: blocks AT the k-th distance still get scanned
        )
        aligned = (cursor & (blk - 1)) == 0
        ok = aligned & in_dom & (ai >= a0) & (empty | far)
        best_a = jnp.where(ok & (ai > best_a), ai, best_a)
    jump = jnp.left_shift(one, 2 * best_a)

    step = jnp.where(found, span0, jump)
    new_cursor = jnp.where(
        run & ~exhausted, jnp.where(dir_r, cursor + step, cursor - step), cursor
    )
    return found, leaf_key, span0, new_cursor, run & exhausted


def _knn_sorted_impl(
    index: QuadtreeIndex,
    qpos: jnp.ndarray,
    qid: jnp.ndarray,
    k: int,
    window: int,
    max_nav: int,
    max_iters: int,
    executor: QueryExecutor,
    tables,
    nav_words,
):
    """k-NN for queries already sorted by Morton code (trace-level body).

    ``tables`` are the index's row tables for ``window``
    (:func:`window_tables`) and ``nav_words`` its navigation words
    (:func:`nav_table`), both built once for every chunk swept over it.
    """
    nq = qpos.shape[0]
    n_fine = index.n_fine
    l_max = index.l_max
    qx, qy = qpos[:, 0], qpos[:, 1]

    # --- first-iteration setup: query indexing (z_map lookup), own-leaf task.
    fine = morton.morton_encode_points(qpos, index.origin, index.side, l_max)
    lvl = index.leaf_level[fine]
    shift = 2 * (l_max - lvl)
    key = (fine >> shift) << shift
    span = jnp.left_shift(jnp.int32(1), shift)
    s0 = index.starts[key]
    e0 = index.starts[jnp.clip(key + span, 0, n_fine)]

    state = _State(
        best_d=jnp.full((nq, k), INF, jnp.float32),
        best_i=jnp.full((nq, k), -1, jnp.int32),
        scanning=e0 > s0,
        s_cur=s0,
        e_cur=e0,
        off=jnp.zeros((nq,), jnp.int32),
        cl=key,
        cr=key + span,
        act_l=jnp.ones((nq,), bool),
        act_r=jnp.ones((nq,), bool),
        next_right=jnp.ones((nq,), bool),
        it=jnp.int32(0),
        cand_q=jnp.zeros((nq,), jnp.float32),
        leaves=(e0 > s0).sum().astype(jnp.int32),
        fetched=jnp.int32(0),
        nav_steps=jnp.int32(0),
    )

    def live(st: _State):
        return st.scanning | st.act_l | st.act_r

    def cond(st: _State):
        return jnp.any(live(st)) & (st.it < max_iters)

    def body(st: _State) -> _State:
        # ---------------- SCAN: one window of W candidates per scanning query.
        start = st.s_cur + st.off
        with stage("gather"):
            # whole 128-wide rows per lane, by DMA; slot j holds object idx
            cx, cy, cids, idx = window_fetch(
                tables, start, st.scanning, window=window)
            cpos = jnp.stack([cx, cy], axis=-1)  # (Q, Wf, 2)
        stop = jnp.minimum(start + window, st.e_cur)
        in_window = (
            st.scanning[:, None]
            & (idx >= start[:, None])
            & (idx < stop[:, None])
        )
        with stage("scan"):
            # negative ids are sentinels: -2 external queries, -1 the padding
            # rows the object-sharded plans append to even out shard slices
            valid = in_window & (cids != qid[:, None]) & (cids >= 0)
            # distance + k-selection merge: dispatched to the registered
            # backend (result lists stay ascending; linear layout of Fig. 1)
            best_d, best_i = executor.scan_merge(
                qpos, cpos, cids, valid, st.best_d, st.best_i, k=k
            )
        kth2 = best_d[:, k - 1]

        off2 = st.off + window
        leaf_done = st.s_cur + off2 >= st.e_cur
        scanning = st.scanning & ~leaf_done
        off = jnp.where(st.scanning & ~leaf_done, off2, st.off)
        # candidates stat counts scanned slots incl. the issuer (seed
        # semantics), kept PER QUERY: the per-query totals are the measured
        # work the cost-balanced partitioner's EMA feeds on (core/balance.py),
        # and their sum is the global drift statistic as before
        cand_q = st.cand_q + in_window.sum(axis=1).astype(jnp.float32)

        # ---------------- NAV: bounded frontier advance for idle active queries.
        nav = ~scanning & (st.act_l | st.act_r)

        def nav_body(_, nst):
            (cl, cr, act_l, act_r, next_right, f_key, f_span, found_any,
             steps) = nst
            pending = nav & ~found_any & (act_l | act_r)
            go_right = act_r & (next_right | ~act_l)
            run = pending & (go_right | act_l)
            cursor = jnp.where(go_right, cr, cl)
            f, key_f, span_f, cur2, ex = _nav_step(
                index, nav_words, qx, qy, kth2, cursor, run, go_right
            )
            cr = jnp.where(run & go_right, cur2, cr)
            cl = jnp.where(run & ~go_right, cur2, cl)
            act_r = act_r & ~(ex & go_right)
            act_l = act_l & ~(ex & ~go_right)
            # a lane finds at most one leaf a trip (`pending` drops it after)
            f_key = jnp.where(f, key_f, f_key)
            f_span = jnp.where(f, span_f, f_span)
            # alternate directions while both remain active (paper Sec. 4.2.2)
            next_right = jnp.where(f, ~go_right, next_right)
            found_any = found_any | f
            steps = steps + run.astype(jnp.int32)
            return (cl, cr, act_l, act_r, next_right, f_key, f_span,
                    found_any, steps)

        zeros = jnp.zeros((nq,), jnp.int32)
        nst = (st.cl, st.cr, st.act_l, st.act_r, st.next_right, zeros, zeros,
               jnp.zeros((nq,), bool), zeros)
        with stage("nav"):
            (cl, cr, act_l, act_r, next_right, f_key, f_span, found_any,
             steps) = jax.lax.fori_loop(0, max_nav, nav_body, nst)
            # the found leaf's object interval: two reads a trip, not a step
            s_f, e_f = _leaf_offsets(index.starts, f_key, f_span)
            s_cur = jnp.where(found_any, s_f, st.s_cur)
            e_cur = jnp.where(found_any, e_f, st.e_cur)

        scanning = scanning | found_any
        off = jnp.where(found_any, 0, off)
        leaves = st.leaves + found_any.sum().astype(jnp.int32)

        return _State(
            best_d=best_d,
            best_i=best_i,
            scanning=scanning,
            s_cur=s_cur,
            e_cur=e_cur,
            off=off,
            cl=cl,
            cr=cr,
            act_l=act_l,
            act_r=act_r,
            next_right=next_right,
            it=st.it + 1,
            cand_q=cand_q,
            leaves=leaves,
            fetched=st.fetched + st.scanning.sum().astype(jnp.int32),
            nav_steps=st.nav_steps + steps.sum(),
        )

    st = jax.lax.while_loop(cond, body, state)
    stats = KnnStats(
        iterations=st.it,
        candidates=st.cand_q.sum(),
        leaves_visited=st.leaves,
        windows_fetched=st.fetched,
        nav_lane_steps=st.nav_steps,
    )
    return st.best_i, st.best_d, stats, st.cand_q


def window_tables(index: QuadtreeIndex, window: int):
    """The index's object store as the row tables the window fetch reads."""
    with stage("gather"):
        return row_tables(index.pos, index.ids, window)


_knn_sorted = jax.jit(
    _knn_sorted_impl,
    static_argnames=("k", "window", "max_nav", "max_iters", "executor"),
)


def _sort_unsort(index: QuadtreeIndex, qpos: jnp.ndarray):
    """Morton sort permutation of the queries (locality; see module docstring)."""
    qcodes = morton.morton_encode_points(qpos, index.origin, index.side, index.l_max)
    order = jnp.argsort(qcodes)
    return order, jnp.argsort(order)


def default_max_nav(l_max: int) -> int:
    """Navigation steps bundled per iteration: enough aligned jumps to cross
    the whole domain (the single source of this formula — serving reuses it)."""
    return 2 * l_max + 4


def _resolve_max_nav(index: QuadtreeIndex, max_nav):
    return default_max_nav(index.l_max) if max_nav is None else max_nav


def knn_query_batch(
    index: QuadtreeIndex,
    qpos: jnp.ndarray,
    qid: jnp.ndarray | None = None,
    *,
    k: int = 32,
    window: int = 128,
    max_nav: int | None = None,
    max_iters: int = 100_000,
    backend: str | QueryExecutor | None = None,
):
    """Compute a batch of k-NN queries against the index (one tick's ``Q``).

    Parameters
    ----------
    index: built/refreshed :class:`QuadtreeIndex` over the tick's positions ``P``.
    qpos: (Q, 2) query centers.
    qid:  (Q,) issuing-object id, excluded from its own result (Def. 1's ``i != j``);
          pass None for external (non-object) queries.
    k: result-list size.
    window: candidate window width W (the per-iteration tile).
    max_nav: navigation steps bundled per iteration (default ``2*l_max + 4``,
        enough to cross the whole domain by aligned jumps).
    backend: SCAN backend name or :class:`QueryExecutor` (default ``dense_topk``;
        see ``repro.core.executor.available_backends``).

    Returns
    -------
    (nn_idx (Q, k) i32, nn_dist (Q, k) f32 *euclidean*, stats) — rows ascending by
    distance, padded with (-1, inf) when fewer than k objects exist.  Ties at the
    k-th distance are resolved arbitrarily (paper Sec. 2.1).
    """
    qpos = jnp.asarray(qpos, jnp.float32)
    nq = qpos.shape[0]
    if qid is None:
        qid = jnp.full((nq,), -2, jnp.int32)  # never matches a real id
    else:
        qid = jnp.asarray(qid, jnp.int32)
    executor = resolve_executor(backend)
    max_nav = _resolve_max_nav(index, max_nav)
    # spatial sort of queries (locality for z_map lookups & frontier coherence)
    order, inv = _sort_unsort(index, qpos)
    idx_s, d2_s, stats, _ = _knn_sorted(
        index, qpos[order], qid[order], k, window, max_nav, max_iters,
        executor, window_tables(index, window), nav_table(index),
    )
    return idx_s[inv], jnp.sqrt(d2_s[inv]), stats


def knn_query_batch_chunked(index, qpos, qid=None, **kw):
    """Delegates to :func:`repro.core.plan.knn_query_batch_chunked` — chunking
    and device layout are rehomed behind the ExecutionPlan seam.  Kept here so
    the serving-layer contract test (tests/test_backends.py) can pin that the
    tick engine never routes through a host-side chunk driver.  The lazy
    import avoids a module cycle (plan.py imports this module's trace-level
    internals)."""
    from .plan import knn_query_batch_chunked as impl

    return impl(index, qpos, qid, **kw)
