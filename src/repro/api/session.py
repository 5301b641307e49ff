"""KnnSession — the session-oriented serving facade (DESIGN.md §11).

The paper's workload is *repeated* k-NN queries: queries persist across ticks
while object positions stream in as updates, and throughput comes from
overlapping CPU-side staging with device-side query processing.  A session
speaks exactly that language:

* **Persistent queries** — ``register_queries`` / ``update_queries`` /
  ``drop_queries`` maintain a device-resident *padded query registry* with
  stable :class:`~repro.api.handles.QueryHandle` groups.  The padded device
  batch is (re)staged only when the registry changes; unchanged query sets
  ride across ticks with zero host work (``set_queries`` is the bulk
  snapshot fallback that :meth:`KnnSession.process_tick` uses).
* **Delta object updates** — ``update_objects(ids, positions)`` scatters
  moved objects into the device-resident positions buffer
  (:func:`repro.core.ticks.scatter_positions`; functional, so an in-flight
  tick keeps reading the previous buffer — double-buffering);
  ``ingest_objects`` keeps the full-snapshot upload as the fallback path.
  Under the object-sharded plans (DESIGN.md §12) the batch is grouped by
  owning shard, device-side — Morton rank // ``ceil(N/R)``, re-derived from
  the live index (``object_shards`` / ``core.ticks.route_delta``) — staging
  the contiguous-run layout a per-shard-resident buffer scatters directly.
* **Overlapped ticks** — ``submit()`` stages + dispatches one tick and
  returns a :class:`~repro.api.handles.TickHandle` immediately; ``result()``
  materializes lazily.  Submitting tick τ+1 while τ's ``(Q, k)`` results are
  still in flight double-buffers host staging against device compute, the
  paper's pipeline.  Drift-rebuild bookkeeping is *finalized* per tick at
  the earlier of ``result(τ)`` and ``submit(τ+1)``, reading back only two
  scalars — so the decision sequence is identical to the blocking loop and
  the session is bit-identical to a second session fed whole snapshots
  through ``process_tick`` (pinned by tests/test_api.py).

The execution core is unchanged: every tick is still the ONE jitted device
program :func:`repro.core.ticks._tick_step` (reindex + the plan's chunked
sweep + drift statistic), specialized per (backend, plan) and dispatching
asynchronously (no buffer donation — donated dispatch is host-synchronous
on this runtime; see the step's docstring).
"""
from __future__ import annotations

import dataclasses
import time
from collections import deque

import jax.numpy as jnp
import numpy as np

from repro.core.executor import resolve_executor
from repro.core.pipeline import default_max_nav
from repro.core.plan import pad_capacity, pad_queries, resolve_plan
from repro.core.quadtree import build_index, rebuild_zmap, reindex_objects_delta
from repro.core.ticks import (
    _tick_step,
    object_shard_of,
    route_delta,
    scatter_positions,
    shard_churn_over_budget,
)
from repro.tracing import compile_time, span

from .handles import QueryHandle, TickHandle
from .sink import StatsSink
from .spec import ServiceSpec

__all__ = ["KnnSession"]


class _QueryRegistry:
    """Host mirror + cached padded device staging of the live query set.

    Rows are kept contiguous (drops compact); padding rows clone the last
    active query with qid = -2 — the exact :func:`repro.core.plan.pad_queries`
    convention of the one-shot path (``knn_query_batch_chunked``), which is
    what makes session results bit-identical to it.  ``owner`` maps each
    row to the :class:`QueryHandle` that registered it (-1 for bulk
    ``set_queries`` rows); handles survive compaction because membership is
    by owner id, not by row position.
    """

    def __init__(self, multiple: int):
        self.multiple = multiple  # plan padding granularity (pad_multiple(chunk))
        self.qpos = np.zeros((0, 2), np.float32)
        self.qid = np.zeros((0,), np.int32)
        self.owner = np.zeros((0,), np.int64)
        self._next_hid = 0
        self._live: set[int] = set()
        self._dirty = True
        self._staged = None
        # True whenever the ROW SET changed (add/drop/replace — not moves):
        # the session's per-query cost EMA is row-aligned and must reset;
        # position-only updates keep it (the repeated-query assumption)
        self.rows_changed = True

    @property
    def nq(self) -> int:
        return int(self.qpos.shape[0])

    def _coerce(self, qpos, qid):
        qpos = np.asarray(qpos, np.float32).reshape(-1, 2)
        m = qpos.shape[0]
        if qid is None:
            qid = np.full((m,), -2, np.int32)
        else:
            qid = np.asarray(qid, np.int32).reshape(-1)
            if qid.shape[0] != m:
                raise ValueError(
                    f"qid has {qid.shape[0]} rows but qpos has {m}"
                )
        return qpos, qid

    def register(self, qpos, qid=None) -> QueryHandle:
        qpos, qid = self._coerce(qpos, qid)
        if qpos.shape[0] == 0:
            raise ValueError("cannot register an empty query group")
        hid = self._next_hid
        self._next_hid += 1
        self.qpos = np.concatenate([self.qpos, qpos])
        self.qid = np.concatenate([self.qid, qid])
        self.owner = np.concatenate(
            [self.owner, np.full((qpos.shape[0],), hid, np.int64)]
        )
        self._live.add(hid)
        self._dirty = True
        self.rows_changed = True
        return QueryHandle(hid=hid, count=qpos.shape[0])

    def _check(self, handle: QueryHandle):
        if handle.hid not in self._live:
            raise KeyError(
                f"{handle} is not live in this registry (already dropped, "
                "or invalidated by set_queries)"
            )

    def rows(self, handle: QueryHandle) -> np.ndarray:
        self._check(handle)
        return np.nonzero(self.owner == handle.hid)[0]

    def update(self, handle: QueryHandle, qpos):
        rows = self.rows(handle)
        qpos = np.asarray(qpos, np.float32).reshape(-1, 2)
        if qpos.shape[0] != rows.shape[0]:
            raise ValueError(
                f"update_queries: {handle} owns {rows.shape[0]} rows, "
                f"got {qpos.shape[0]} positions"
            )
        self.qpos[rows] = qpos
        self._dirty = True

    def drop(self, handle: QueryHandle):
        rows = self.rows(handle)
        keep = np.ones(self.nq, bool)
        keep[rows] = False
        self.qpos = self.qpos[keep]
        self.qid = self.qid[keep]
        self.owner = self.owner[keep]
        self._live.discard(handle.hid)
        self._dirty = True
        self.rows_changed = True

    def replace_all(self, qpos, qid=None):
        """Bulk snapshot staging: replaces every row, invalidates all handles."""
        qpos, qid = self._coerce(qpos, qid)
        self.qpos = qpos.copy()
        self.qid = qid.copy()
        self.owner = np.full((qpos.shape[0],), -1, np.int64)
        self._live = set()
        self._dirty = True
        self.rows_changed = True

    def staged(self):
        """(qpos_dev, qid_dev, nq, qids, owner) — padded, device-resident.

        Cached until the registry changes: steady-state ticks with a stable
        query set re-submit the SAME device arrays, no host pad/upload.
        """
        if self._dirty or self._staged is None:
            qpos_p, qid_p = pad_queries(self.qpos, self.qid, self.multiple)
            self._staged = (
                jnp.asarray(qpos_p, jnp.float32),
                jnp.asarray(qid_p, jnp.int32),
                self.nq,
                self.qid.copy(),
                self.owner.copy(),
            )
            self._dirty = False
        return self._staged


class KnnSession:
    """A live serving session: device-resident object + query state, ticked.

    Construct from a :class:`~repro.api.spec.ServiceSpec`, seed object state
    with ``ingest_objects`` (snapshot) and queries with ``register_queries``,
    then per tick: push motion (``update_objects`` deltas or a fresh
    snapshot), optionally move queries, and ``submit()``.  See the module
    docstring for the overlap contract.
    """

    def __init__(self, spec: ServiceSpec):
        self.spec = spec
        self.executor = resolve_executor(spec.backend, spec.precision)
        self.plan = resolve_plan(
            spec.plan, num_devices=spec.mesh_shape,
            partitioner=spec.partitioner, merge=spec.merge,
        )
        self._registry = _QueryRegistry(self.plan.pad_multiple(spec.chunk))
        self._positions = None  # (N, 2) f32, device-resident, by object id
        self._index = None
        self._work_at_build: float | None = None
        self._tick = 0
        self._pending: deque[TickHandle] = deque()
        # per-query cost EMA, device-resident, row-aligned with the padded
        # registry batch: persists across ticks AND drift rebuilds (queries
        # are the stable entities of the repeated-query workload); reset
        # whenever the registry's row set changes (DESIGN.md §13)
        self._qcost = None
        # object-axis boundaries the LAST submitted tick actually used
        # (PlanAux.object_bounds, device-resident): delta routing and
        # object_shards follow the live partition under cost_balanced;
        # cleared on drift rebuild (the Morton ranks it indexes change)
        self._obj_bounds = None
        # optional per-query fairness weights on the boundary-seeding cost
        # (set_query_cost_weights; the serving layer's tenant fair share,
        # DESIGN.md §16) — host mirror + a cached padded device staging
        self._qweight_host: np.ndarray | None = None
        self._qweight_ver = 0
        self._qweight_staged = None  # (ver, padded_len, device array)
        # on-device result consumer (DESIGN.md §14): under collect="stats"
        # submit() feeds each tick's padded (Qp, k) outputs straight into the
        # jitted sink update — asynchronously, right behind the tick step —
        # and only the O(Q) aggregates ever reach the host
        self._sink = (
            StatsSink(self.plan.object_axis_size)
            if spec.collect == "stats" else None
        )
        self._sink_state = None
        # --- index-maintenance bookkeeping (DESIGN.md §15) ---
        # True iff the positions buffer changed since the index was last
        # refreshed from it; a clean buffer makes the reindex a semantic
        # no-op (reindex is a pure function of the buffer), so the step can
        # statically skip it — the dirty-flag fast path
        self._positions_dirty = True
        # union of object ids moved since the last refresh, sorted unique
        # (delta batches are deduped); None = "unknown delta" — a snapshot
        # ingest replaced the whole buffer, only a full refresh is safe
        self._pending_ids: np.ndarray | None = None
        # device-side batches of pre-update positions (gathered just before
        # each delta scatter) plus, per pending id, the row of its FIRST
        # touch inside their concatenation: the incremental reindex needs
        # each moved object's position as of the last refresh to re-derive
        # (and binary-search) its old sort key — kept on device, assembled
        # by one gather at submit, so update_objects stays fully async
        self._pending_old_batches: list = []
        self._pending_old_rows = 0
        self._pending_src: np.ndarray | None = None
        # (mode, padded delta length) of the last tick that refreshed the
        # index in its step: the program lower_tick() lowers
        self._last_refresh: tuple[str, int] = ("rebuild", 0)

    # ------------------------------------------------------------ state views
    @property
    def tick(self) -> int:
        """Ticks submitted so far (the next submit gets this tick number)."""
        return self._tick

    @property
    def index(self):
        return self._index

    @property
    def num_objects(self) -> int:
        return 0 if self._positions is None else int(self._positions.shape[0])

    @property
    def query_count(self) -> int:
        return self._registry.nq

    # ------------------------------------------------------------ object state
    def ingest_objects(self, positions):
        """Full-snapshot ingest (fallback path): replace all object positions.

        ``positions`` is (N, 2), indexed by object id.  The first ingest (or
        any later one) does NOT rebuild the space partition by itself — the
        partition is built lazily at the first ``submit()`` and thereafter
        only on the drift trigger, exactly like the snapshot engine.
        """
        positions = np.asarray(positions, np.float32)
        if positions.ndim != 2 or positions.shape[1] != 2:
            raise ValueError(f"positions must be (N, 2), got {positions.shape}")
        with span("session.ingest", tick=self._tick):
            self._positions = jnp.asarray(positions, jnp.float32)
        # whole buffer replaced, delta unknown: only a full refresh is safe
        self._positions_dirty = True
        self._pending_ids = None
        self._pending_old_batches = []
        self._pending_old_rows = 0
        self._pending_src = None

    def update_objects(self, ids, positions):
        """Delta ingest: scatter ``positions[i]`` to object ``ids[i]`` on device.

        Steady-state motion costs one O(m) staging + device scatter — the
        (N, 2) buffer never re-crosses the host boundary.  Batches are
        padded to ``spec.delta_pad`` rows with the out-of-range sentinel id
        ``N`` (dropped by the scatter) so every delta size shares one
        compiled program; duplicate ids within a batch resolve deterministically
        to the last observation.
        """
        with span("session.ingest", tick=self._tick):
            self._update_objects(ids, positions)

    def _update_objects(self, ids, positions):
        if self._positions is None:
            raise RuntimeError("update_objects before ingest_objects: the "
                               "session has no object state to update")
        ids = np.asarray(ids, np.int32).reshape(-1)
        positions = np.asarray(positions, np.float32).reshape(-1, 2)
        if ids.shape[0] != positions.shape[0]:
            raise ValueError(
                f"update_objects: {ids.shape[0]} ids vs "
                f"{positions.shape[0]} positions"
            )
        m = ids.shape[0]
        if m == 0:
            return
        n = self.num_objects
        if (ids < 0).any() or (ids >= n).any():
            bad = ids[(ids < 0) | (ids >= n)]
            raise ValueError(
                f"update_objects: ids out of range [0, {n}): {bad[:8]}"
            )
        uniq = np.unique(ids)
        if uniq.shape[0] != m:
            # several observations for one object in one batch: keep the LAST
            # (deterministic feed semantics — jnp scatter with repeated
            # indices applies them in unspecified order, which would break
            # the delta ≡ snapshot bit-identity contract)
            _, last_rev = np.unique(ids[::-1], return_index=True)
            keep = np.sort((m - 1) - last_rev)
            ids, positions = ids[keep], positions[keep]
            m = ids.shape[0]
        pad = pad_capacity(m, self.spec.delta_pad) - m
        if pad:
            ids = np.concatenate([ids, np.full((pad,), n, np.int32)])
            positions = np.concatenate(
                [positions, np.zeros((pad, 2), np.float32)]
            )
        ids_dev, pos_dev = jnp.asarray(ids), jnp.asarray(positions)
        tracking = not (self._positions_dirty and self._pending_ids is None)
        if tracking:
            # positions BEFORE this batch's scatter, in the host-known
            # (deduped, padded) id order — an id's first touch since the
            # last refresh reads its as-of-refresh position, which is what
            # the incremental reindex needs to locate its old sort key.
            # Padding rows gather a clamped garbage row, never consumed.
            old_batch = self._positions[ids_dev]
        if self.plan.object_axis_size > 1 and self._index is not None:
            # object-sharded plans: group the batch by owning shard (the
            # Morton-rank rule, DESIGN.md §12; under cost_balanced, the
            # boundary intervals the last tick used — §13) — entirely
            # device-side (core/ticks.py::route_delta), so staging stays
            # async.  A pure reordering of now-unique ids: the scattered
            # buffer, and hence every result, is bit-identical (pinned by
            # the routing-edge regressions in tests/test_api.py).
            ids_dev, pos_dev = route_delta(
                self._index, ids_dev, pos_dev, self.plan.object_axis_size,
                self._obj_bounds,
            )
        self._positions = scatter_positions(self._positions, ids_dev, pos_dev)
        # accumulate the delta set for the maintenance decision at submit:
        # `ids` is unique by now (padding rows are >= n and excluded); union
        # because the SAME object moving twice between submits is one moved
        # row from the index's point of view
        moved = ids[:m]
        if tracking:
            self._pending_old_batches.append(old_batch)
            src_batch = self._pending_old_rows + np.arange(m, dtype=np.int64)
            self._pending_old_rows += int(ids.shape[0])
            if self._pending_ids is None:
                order = np.argsort(moved)
                self._pending_ids = moved[order]
                self._pending_src = src_batch[order]
            else:
                # first touch wins for the old position (it is the one taken
                # against the last refresh); the id set is a union because
                # the same object moving twice is one moved row to the index
                fresh = ~np.isin(moved, self._pending_ids)
                merged = np.union1d(self._pending_ids, moved)
                src = np.empty(merged.size, np.int64)
                src[np.searchsorted(merged, self._pending_ids)] = (
                    self._pending_src
                )
                src[np.searchsorted(merged, moved[fresh])] = src_batch[fresh]
                self._pending_ids, self._pending_src = merged, src
        # else: unknown delta (snapshot since last refresh) stays unknown
        self._positions_dirty = True

    def object_shards(self, ids) -> np.ndarray:
        """Owning object shard per object id under the live plan + index.

        Evaluates the shard-ownership rule (DESIGN.md §12: Morton rank //
        ``ceil(N / R)``; under ``cost_balanced``, §13: the boundary interval
        containing the rank) against the *current* index — objects change
        owner as they move through the Morton order, so the answer is only
        valid until the next tick's reindex.  Plans without an object axis
        own everything on shard 0.  Requires a built index (the rule is
        defined by the index's Morton order): before the first submit the
        partition does not exist yet.

        Any still-pending tick is **finalized first** (blocking on its two
        bookkeeping scalars): a pending tick may carry a drift-rebuild
        decision, and answering from the pre-rebuild Morton order would
        silently route the caller's next updates to shards the rebuilt
        partition no longer owns (the rebuild-then-route regression,
        tests/test_api.py).
        """
        ids = np.asarray(ids, np.int32).reshape(-1)
        r = self.plan.object_axis_size
        if r == 1:
            return np.zeros(ids.shape, np.int32)
        if self._index is None:
            raise RuntimeError(
                "object_shards before the first submit: the index (and with "
                "it the Morton shard ownership) is built lazily at submit()"
            )
        # apply any pending drift-rebuild decision BEFORE reading ownership,
        # then recompute from whatever index is live afterwards
        self._finalize_through()
        n = self._index.n_objects
        if ids.size and ((ids < 0).any() or (ids >= n).any()):
            # jnp's clamping gather would return confidently wrong owners
            # for ids the (possibly stale) index has never seen
            bad = ids[(ids < 0) | (ids >= n)]
            raise ValueError(
                f"object_shards: ids outside the live index's [0, {n}): "
                f"{bad[:8]}"
            )
        return np.asarray(
            object_shard_of(self._index, ids, r, self._obj_bounds)
        )

    # ------------------------------------------------------------ query state
    def register_queries(self, qpos, qid=None) -> QueryHandle:
        """Add a persistent query group; returns its stable handle.

        ``qid`` is the issuing object id per query (excluded from its own
        result list); default -2 = no exclusion, matching
        ``knn_query_batch_chunked``.
        """
        return self._registry.register(qpos, qid)

    def update_queries(self, handle: QueryHandle, qpos):
        """Move a registered group: same row count, new positions.

        Any registry change currently restages the whole padded batch on the
        next submit (host pad + upload, O(total registry rows)); the zero-
        host-work steady state holds for query sets that don't move.  A
        device-side qpos scatter (mirroring ``update_objects``) is the
        prepared next step — it must also maintain the padding rows, which
        clone the last active query for snapshot-path bit-identity.
        """
        with span("session.update_queries", tick=self._tick):
            self._registry.update(handle, qpos)

    def drop_queries(self, handle: QueryHandle):
        """Remove a group; its rows stop being served from the next submit."""
        self._registry.drop(handle)

    def set_queries(self, qpos, qid=None):
        """Bulk snapshot staging of the whole query set (the shim's path).

        Replaces the registry contents and invalidates all handles; prefer
        ``register_queries`` + ``update_queries`` for persistent sets.
        """
        self._registry.replace_all(qpos, qid)

    def set_query_cost_weights(self, weights):
        """Per-query multipliers on the boundary-seeding cost (or None).

        ``weights`` is (query_count,) f32, aligned with the registry's
        current row order; the serving layer sets the tenant-fair weights
        here (``core.balance.tenant_fair_weights``) so no tenant's query
        volume buys it outsized influence on the cost-balanced shard
        boundaries.  Weights scale the boundary seed ONLY — boundaries move
        shard ownership, never results (DESIGN.md §13), so this cannot
        change bits on any plan.  Pass None to clear.  Weights must be
        re-set after any registry row-set change (validated at submit).
        """
        if weights is None:
            self._qweight_host = None
        else:
            w = np.asarray(weights, np.float32).reshape(-1)
            if w.shape[0] != self._registry.nq:
                raise ValueError(
                    f"set_query_cost_weights: {w.shape[0]} weights for a "
                    f"{self._registry.nq}-row registry"
                )
            if w.size and not (np.isfinite(w).all() and (w > 0).all()):
                raise ValueError(
                    "set_query_cost_weights: weights must be finite and > 0"
                )
            self._qweight_host = w.copy()
        self._qweight_ver += 1
        self._qweight_staged = None

    # ------------------------------------------------------------ serving
    def _assemble_delta(self):
        """Padded (delta_ids, delta_old_pos) device arrays for the pending set.

        ``delta_ids`` is the sorted-unique pending union padded to the
        ``delta_pad`` granularity with the sentinel id N; ``delta_old_pos``
        gathers each id's as-of-refresh position (first touch wins) out of
        the captured pre-scatter batches — one device-side gather, async.
        Requires ``self._pending_ids`` to be a known (non-None) delta.
        """
        n = self.num_objects
        m = self._pending_ids.size
        pad = pad_capacity(max(m, 1), self.spec.delta_pad) - m
        delta_ids_dev = jnp.asarray(np.concatenate(
            [self._pending_ids, np.full((pad,), n, np.int32)]
        ))
        sel = np.concatenate(
            [self._pending_src, np.zeros((pad,), np.int64)]
        ).astype(np.int32)
        batches = self._pending_old_batches
        cat = batches[0] if len(batches) == 1 else jnp.concatenate(batches)
        return delta_ids_dev, cat[jnp.asarray(sel)]

    def _build(self):
        """(Re)build the space partition from the current device positions.

        Three routes to the same bits (the stage-(i) reuse rule, DESIGN.md
        §15).  The drift policy only needs the leaf partition (z_map)
        re-decided; the sorted order, pyramid and offsets are pure functions
        of the positions buffer that the maintenance paths may already hold:

        * buffer CLEAN (index refreshed from this very buffer): everything
          but ``leaf_level`` is already what ``build_index`` would produce —
          ``rebuild_zmap`` replaces the O(N log N) re-sort with one
          O(4**l_max) leaf-level pass;
        * buffer dirty with a known in-budget delta under an incremental
          spec: splice the pending rows into the order
          (``reindex_objects_delta``), then re-derive the leaf partition
          from the spliced pyramid — still no fresh argsort;
        * anything else (first build, snapshot ingest, over-budget churn,
          rebuild spec): the full ``build_index``.

        All three produce bitwise-identical indexes (build ≡ reindex on
        pos/ids/codes/starts/pyramid; ``leaf_level`` is the same
        ``_leaf_levels`` op over equal pyramids), pinned by
        tests/test_maintenance.py.
        """
        with span("session.rebuild", tick=self._tick):
            self._rebuild()

    def _rebuild(self):
        spec = self.spec
        if self._index is not None and not self._positions_dirty:
            self._index = rebuild_zmap(self._index)
        elif (
            self._index is not None
            and spec.maintenance == "incremental"
            and self._pending_ids is not None
            and self._pending_ids.size <= spec.churn_budget * self.num_objects
        ):
            ids_dev, old_dev = self._assemble_delta()
            self._index = rebuild_zmap(
                reindex_objects_delta(
                    self._index, self._positions, ids_dev, old_dev
                )
            )
        else:
            self._index = build_index(
                self._positions,
                jnp.asarray(self.spec.origin, jnp.float32),
                self.spec.side,
                l_max=self.spec.l_max,
                th_quad=self.spec.th_quad,
            )
        self._work_at_build = None  # set at the next tick's finalize
        # the stored object boundaries index Morton ranks of the PREVIOUS
        # partition — stale after a rebuild; ownership answers fall back to
        # the capacity rule until the next tick returns fresh boundaries
        self._obj_bounds = None
        # the index was just refreshed from the live buffer: clean slate for
        # the maintenance decision (build_index ≡ reindex_objects on pos/
        # ids/codes/starts/pyramid, so the next clean tick may skip)
        self._positions_dirty = False
        self._pending_ids = None
        self._pending_old_batches = []
        self._pending_old_rows = 0
        self._pending_src = None

    def _finalize_one(self, h: TickHandle):
        """Read back the tick's bookkeeping scalars and apply the drift policy.

        Blocks only on the two scalars (the step must have finished computing,
        but the big result arrays stay un-materialized on device).  Mirrors
        the snapshot engine exactly: the first finalized tick after a build
        becomes the work baseline; later ticks whose candidate volume exceeds
        ``rebuild_factor`` × baseline rebuild the partition — from the newest
        object state — before the next dispatch.
        """
        with span("session.finalize", tick=h.tick):
            h._work = float(h._aux.stats.candidates)
            h._iterations = int(h._aux.stats.iterations)
            if self._work_at_build is None:
                self._work_at_build = h._work
            elif bool(h._should_rebuild):
                self._build()
                h._rebuilt_post = True
        h._finalized = True

    def _finalize_through(self, target: TickHandle | None = None):
        """Finalize pending ticks in submit order, up to ``target`` (or all)."""
        if target is not None and target._finalized:
            return  # don't touch (and block on) target's successors
        while self._pending:
            h = self._pending.popleft()
            self._finalize_one(h)
            if h is target:
                break

    def finalize_pending(self):
        """Apply the drift policy of every still-pending tick, now.

        Blocks only on each pending tick's two bookkeeping scalars (the big
        result arrays stay on device).  ``submit()`` does this implicitly;
        the serving layer (``repro.serve``) calls it explicitly so a
        drift-rebuild decision is *observable* (``TickHandle`` bookkeeping)
        before it consults its epoch-keyed result cache.
        """
        self._finalize_through()

    def submit(self) -> TickHandle:
        """Dispatch one tick against the current object + query state.

        Returns immediately after host staging + device dispatch; call
        ``TickHandle.result()`` to materialize.  Any still-pending earlier
        tick is finalized first (scalar readback + drift policy), which is
        the synchronization point that keeps overlapped submission
        bit-identical to the blocking loop.
        """
        if self._positions is None:
            raise RuntimeError("submit before ingest_objects: no object state")
        if self._registry.nq == 0:
            raise RuntimeError("submit with an empty query registry: "
                               "register_queries (or set_queries) first")
        with span("session.submit", tick=self._tick):
            self._finalize_through()
            t0 = time.perf_counter()
            with span("session.dispatch", tick=self._tick), \
                    compile_time() as compiling:
                h = self._dispatch(t0)
            # submit_s covers the whole dispatch, INCLUDING any trace and
            # compile that ran synchronously inside it; compile_s is that
            # part, measured, which consumers subtract to get pure staging
            # time (the serve layer's wall_s decomposition relies on this)
            h.submit_s = time.perf_counter() - t0
            h.compile_s = compiling.seconds
        self._tick += 1
        self._pending.append(h)
        return h

    def _dispatch(self, t0: float) -> TickHandle:
        """Stage the registry and dispatch the tick step (and the sink)."""
        rebuilt_pre = False
        if self._index is None:
            self._build()
            rebuilt_pre = True
        if self._registry.rows_changed:
            # the cost EMA is row-aligned with the padded registry batch; a
            # changed row set invalidates the alignment — re-seed from the
            # count-pyramid estimate (moves via update_queries keep it);
            # likewise the sink's cross-tick memory (prev neighbour lists)
            self._qcost = None
            self._sink_state = None
            self._registry.rows_changed = False
        qpos_dev, qid_dev, nq, qids, owner = self._registry.staged()
        qcost_dev = self._qcost
        if qcost_dev is None or qcost_dev.shape[0] != qpos_dev.shape[0]:
            qcost_dev = jnp.zeros((qpos_dev.shape[0],), jnp.float32)
        qweight_dev = None
        if self._qweight_host is not None:
            if self._qweight_host.shape[0] != nq:
                raise RuntimeError(
                    "query cost weights are stale: the registry row set "
                    "changed since set_query_cost_weights (re-set or clear)"
                )
            cap = int(qpos_dev.shape[0])
            st = self._qweight_staged
            if st is None or st[0] != self._qweight_ver or st[1] != cap:
                # padding rows clone the last active query (pad_queries), so
                # they clone its weight too — pure consistency; padding can
                # only shift boundaries, never results
                w = self._qweight_host
                w_p = np.concatenate(
                    [w, np.full((cap - nq,), w[-1], np.float32)]
                )
                self._qweight_staged = (
                    self._qweight_ver, cap, jnp.asarray(w_p, jnp.float32)
                )
            qweight_dev = self._qweight_staged[2]
        spec = self.spec
        with span("session.delta", tick=self._tick):
            mode, delta_ids_dev, delta_old_pos_dev = self._maintenance_step()
        # rows the refresh re-places (TickResult.delta_rows)
        delta_rows = 0
        if mode != "skip":
            delta_rows = (self.num_objects if delta_ids_dev is None
                          else int(self._pending_ids.size))
            self._last_refresh = (
                mode, 0 if delta_ids_dev is None else delta_ids_dev.shape[0])
        self._index, nn_idx, nn_dist, aux, should_rebuild = _tick_step(
            self._index,
            self._positions,
            qpos_dev,
            qid_dev,
            qcost_dev,
            jnp.float32(np.inf if self._work_at_build is None
                        else self._work_at_build),
            jnp.float32(spec.rebuild_factor),
            delta_ids_dev,
            delta_old_pos_dev,
            qweight_dev,
            **self._step_statics(mode),
        )
        # the index is now refreshed from this very buffer: clean until the
        # next position change (the dispatched step reads the buffer as of
        # dispatch; later update_objects scatter into a NEW buffer)
        self._positions_dirty = False
        self._pending_ids = None
        self._pending_old_batches = []
        self._pending_old_rows = 0
        self._pending_src = None
        # thread the repeated-query feedback loop: next tick's boundaries
        # see this tick's measured per-query work (device arrays, async)
        self._qcost = aux.qcost_next
        self._obj_bounds = (
            aux.object_bounds if self.plan.object_axis_size > 1 else None
        )
        agg = None
        if self._sink is not None:
            # consume the padded results ON DEVICE, behind the tick step in
            # the same async dispatch stream: tick τ+1's staging overlaps
            # τ's aggregation exactly as it overlaps τ's sweep
            if (
                self._sink_state is None
                or self._sink_state.prev_idx.shape != nn_idx.shape
            ):
                self._sink_state = self._sink.init(
                    int(nn_idx.shape[0]), spec.k
                )
            self._sink_state, agg = self._sink.update(
                self._sink_state, nn_idx, nn_dist, self._index,
                self._obj_bounds, jnp.int32(nq),
            )
        return TickHandle(
            session=self,
            tick=self._tick,
            nn_idx=nn_idx,
            nn_dist=nn_dist,
            aux=aux,
            should_rebuild=should_rebuild,
            nq=nq,
            qids=qids,
            owner=owner,
            t0=t0,
            rebuilt_pre=rebuilt_pre,
            collect=spec.collect,
            agg=agg,
            maintenance=mode,
            delta_rows=delta_rows,
        )

    def _maintenance_step(self):
        """``(mode, delta_ids, delta_old_pos)`` of the next tick's refresh."""
        spec = self.spec
        # --- maintenance decision (DESIGN.md §15), made per tick, host-side:
        # clean buffer -> "skip" (reindex would be a bitwise no-op);
        # known small delta under an incremental spec -> "incremental";
        # anything else (rebuild spec, snapshot ingest, churn over budget)
        # -> full "rebuild" refresh.  Each mode is a static of the step, so
        # every (shape, mode) pair is its own cached executable.
        n = self.num_objects
        delta_ids_dev = None
        delta_old_pos_dev = None
        if not self._positions_dirty:
            mode = "skip"
        elif (
            spec.maintenance == "incremental"
            and self._pending_ids is not None
            and self._pending_ids.size <= spec.churn_budget * n
        ):
            mode = "incremental"
            # as-of-refresh positions of the pending ids: one gather over
            # the captured pre-scatter batches (device-side, async)
            delta_ids_dev, delta_old_pos_dev = self._assemble_delta()
            if self.plan.object_axis_size > 1:
                # per-shard budget (DESIGN.md §15): the global fraction can
                # hide one shard absorbing most of the churn — past
                # churn_budget × its OWNED rows, that shard's local re-sort
                # is the cheaper refresh, so the whole tick defers.  One ()
                # bool readback against the last tick's index/boundaries; the
                # pending ticks were already finalized above, so this is not
                # a new synchronization point.
                if bool(shard_churn_over_budget(
                    self._index, delta_ids_dev, self.plan.object_axis_size,
                    spec.churn_budget, self._obj_bounds,
                )):
                    mode = "rebuild"
                    delta_ids_dev = delta_old_pos_dev = None
        else:
            # over-budget churn defers to the FULL stage-(ii) refresh (not
            # build_index: the z_map stays put so the drift trigger fires
            # identically under both maintenance policies)
            mode = "rebuild"
        return mode, delta_ids_dev, delta_old_pos_dev

    def _step_statics(self, mode: str) -> dict:
        """The static arguments of :func:`_tick_step` under this spec."""
        spec = self.spec
        return dict(
            k=spec.k, window=spec.window, chunk=spec.chunk,
            max_nav=default_max_nav(spec.l_max), max_iters=spec.max_iters,
            executor=self.executor, plan=self.plan, maintenance=mode,
        )

    def lower_tick(self):
        """The tick program for the current state, lowered but not run.

        Returns the ``jax.stages.Lowered`` of the step that last refreshed
        the index (``"incremental"``, at the padded length of its delta, or
        the full-refresh ``"rebuild"``, which is also what a session whose
        every tick skipped gets) over the live index, object buffer and
        query registry; its ``compile()`` gives the executable's text (which
        kernels it runs: a Pallas kernel compiled for the TPU shows as
        ``tpu_custom_call``) and memory analysis.  Needs a submitted tick
        (the index is built lazily at the first ``submit()``).
        """
        if self._index is None:
            raise RuntimeError("lower_tick before the first submit: no index")
        qpos_dev, qid_dev = self._registry.staged()[:2]
        mode, m = self._last_refresh
        delta_ids = delta_old_pos = None
        if mode == "incremental":
            delta_ids = jnp.full((m,), self.num_objects, jnp.int32)
            delta_old_pos = jnp.zeros((m, 2), jnp.float32)
        return _tick_step.lower(
            self._index,
            self._positions,
            qpos_dev,
            qid_dev,
            jnp.zeros((qpos_dev.shape[0],), jnp.float32),
            jnp.float32(np.inf),
            jnp.float32(self.spec.rebuild_factor),
            delta_ids,
            delta_old_pos,
            None,
            **self._step_statics(mode),
        )

    def process_tick(self, positions, qpos, qid=None):
        """One blocking tick from whole frames: ingest + set_queries + submit
        + result.

        For callers that hold a full position snapshot and a full query
        batch each tick (tests, one-off scripts) rather than persistent
        handles and deltas; results are bitwise those of the handle loop.
        ``wall_s`` here is measured from the top of the call, staging
        included.
        """
        t0 = time.perf_counter()
        self.ingest_objects(positions)
        self.set_queries(qpos, qid)
        res = self.submit().result()
        return dataclasses.replace(
            res, wall_s=time.perf_counter() - t0 - res.compile_s
        )
