"""Handles — the session API's stable references to queries and in-flight ticks.

:class:`QueryHandle` names a group of registered queries; it stays valid
across ticks (and across registry compaction after drops) until the group is
dropped.  :class:`TickHandle` names one submitted tick: ``submit()`` returns
it immediately after dispatch, and ``result()`` materializes the ``(Q, k)``
result batch lazily — so tick τ+1 can be staged and submitted while τ's
results are still computing/transferring (the paper's CPU/GPU pipeline
overlap, DESIGN.md §11).

Host collection is ONE batched transfer: ``result()`` pulls ``nn_idx``,
``nn_dist`` and the per-shard counters through a single ``jax.device_get``
instead of separate blocking ``np.asarray`` syncs (each sync pays the full
dispatch-queue drain; batching them collapsed the dominant steady-tick host
cost, measured on a CPU host).  Pipelines that consume results
on-device skip the transfer entirely with ``result(materialize=False)``.

What ``result()`` fetches is the spec's ``collect`` mode (DESIGN.md §14):
``"full"`` ships the ``(Q, k)`` lists as above; ``"stats"`` ships only the
sink's O(Q)/O(1) :class:`~repro.api.sink.TickAggregates` (``nn_idx``/
``nn_dist`` come back ``None``); ``"none"`` ships nothing at all — the
finalize scalars the session already read are the whole host footprint.
``TickResult.collect_s`` records the transfer time each mode actually paid,
attributed to the tick whose ``result()`` materialized it (NOT the tick
whose ``submit()`` happened to overlap it), so host-collect times stay
honest under overlapped submission.  ``result()`` first drains the
device computation (``block_until_ready``) *outside* the timed window, so
``collect_s`` is pure host materialization cost — on a CPU host, where
device compute shares the cores, folding the compute drain into the collect
column is exactly the conflation the column used to suffer from.
"""
from __future__ import annotations

import dataclasses
import time

import jax
import numpy as np

from repro.core.ticks import TickResult
from repro.tracing import span

__all__ = ["QueryHandle", "TickHandle"]


@dataclasses.dataclass(frozen=True)
class QueryHandle:
    """Stable reference to a registered query group (``count`` rows)."""

    hid: int
    count: int


class TickHandle:
    """One in-flight tick: dispatched device work + lazy host materialization.

    The handle owns references to the tick's device-side outputs.  The big
    ``(Q, k)`` result arrays stay on device until :meth:`result` is called;
    the tiny bookkeeping scalars (candidate counter, rebuild trigger) are
    read by the session when the tick is *finalized* — at the earlier of
    ``result()`` and the next ``submit()`` — so drift rebuilds apply in tick
    order even when results are collected late or out of order.
    """

    def __init__(
        self,
        session,
        tick: int,
        nn_idx,
        nn_dist,
        aux,
        should_rebuild,
        nq: int,
        qids: np.ndarray,
        owner: np.ndarray,
        t0: float,
        rebuilt_pre: bool,
        collect: str = "full",
        agg=None,
        maintenance: str = "rebuild",
        delta_rows: int = 0,
    ):
        self._session = session
        self.tick = tick
        self._nn_idx = nn_idx
        self._nn_dist = nn_dist
        self._aux = aux
        self._should_rebuild = should_rebuild
        self._collect = collect
        self._agg = agg  # device-resident TickAggregates (collect="stats")
        self._nq = nq
        self._qids = qids
        self._owner = owner
        self._t0 = t0
        # set by the session once the dispatch has returned: its host time,
        # and the part of it JAX spent tracing and compiling
        self.submit_s = 0.0
        self.compile_s = 0.0
        self._rebuilt_pre = rebuilt_pre
        # how the step maintained the index this tick ("rebuild" |
        # "incremental" | "skip") — the session's scheduling decision,
        # recorded for TickResult.maintenance
        self._maintenance = maintenance
        self._delta_rows = delta_rows
        # set by the session at finalize time
        self._finalized = False
        self._rebuilt_post = False
        self._work: float | None = None
        self._iterations: int | None = None
        self._result: TickResult | None = None
        self._result_dev: TickResult | None = None

    @property
    def finalized(self) -> bool:
        """Has this tick's drift bookkeeping landed (finalize or result)?

        Public read-only view for layers above the session (the server's
        epoch/cache observation) — once True, :attr:`rebuilt_post` is
        settled and will not change.
        """
        return self._finalized or self._result is not None

    @property
    def rebuilt_post(self) -> bool:
        """Did the drift check of THIS tick trigger a rebuild after it ran?

        Meaningful once :attr:`finalized` is True (False until then).  A
        post-rebuild re-sorts the same positions the tick already answered
        under — results stay bit-correct; it is scheduling bookkeeping, not
        a world change.
        """
        return self._rebuilt_post

    def done(self) -> bool:
        """Non-blocking: have this tick's result arrays materialized?"""
        if self._result is not None:
            return True
        try:
            return bool(self._nn_idx.is_ready() and self._nn_dist.is_ready())
        except AttributeError:  # older jax without Array.is_ready
            return False

    def block_until_ready(self) -> "TickHandle":
        """Block until this tick's device outputs are computed — NO transfer.

        The wait is device-compute drain, not host collection: callers that
        want the two costs separated (bench/, latency-sensitive serving
        loops) call this first, then ``result()``, whose ``collect_s`` then
        times only the materialization.  Idempotent; a no-op once the tick
        has materialized.
        """
        if self._result is None:
            payload = [a for a in (self._nn_idx, self._nn_dist, self._agg)
                       if a is not None]
            if payload:
                with span("tick.wait", tick=self.tick):
                    jax.block_until_ready(payload)
        return self

    def _tick_result(self, nn_idx, nn_dist, shard_cand, shard_it,
                     collect_s: float = 0.0, aggregates=None) -> TickResult:
        return TickResult(
            tick=self.tick,
            nn_idx=nn_idx,
            nn_dist=nn_dist,
            rebuilt=self._rebuilt_pre or self._rebuilt_post,
            wall_s=time.perf_counter() - self._t0 - self.compile_s,
            candidates=self._work,
            iterations=self._iterations,
            compile_s=self.compile_s,
            qids=self._qids,
            shard_candidates=shard_cand,
            shard_iterations=shard_it,
            collect_s=collect_s,
            aggregates=aggregates,
            maintenance=self._maintenance,
            delta_rows=self._delta_rows,
        )

    def result(self, materialize: bool = True) -> TickResult:
        """Block until this tick's results are available (idempotent).

        Finalizes every earlier in-flight tick first (in submit order), so
        rebuild bookkeeping is independent of the order in which callers
        collect results.

        What crosses the host boundary is the spec's ``collect`` mode:
        ``"full"`` materializes the ``(Q, k)`` lists + shard counters in ONE
        batched ``jax.device_get``; ``"stats"`` fetches only the sink
        aggregates + shard counters (``nn_idx``/``nn_dist`` = ``None``);
        ``"none"`` fetches nothing — every host-facing field beyond the
        finalize bookkeeping is ``None``.  ``TickResult.collect_s`` is the
        time THIS call spent in the blocking transfer — the tick that
        materializes pays it, not the tick whose submit it overlapped.

        ``materialize=False`` hands back a :class:`TickResult` whose
        ``nn_idx``/``nn_dist``/``shard_*``/``aggregates`` fields are
        **device arrays** (sliced views of the tick's outputs) — for
        pipelines that consume results on-device, where a host round-trip
        per tick would throw away the submit/result overlap.  The arrays
        stay valid while later ticks submit and even across a drift rebuild
        (nothing donates or overwrites them — pinned by tests/test_api.py).
        It does not release the device buffers; a later ``result()`` still
        materializes and releases them.
        """
        if self._result is not None:
            return self._result
        with span("tick.result", tick=self.tick):
            return self._materialize(materialize)

    def _materialize(self, materialize: bool) -> TickResult:
        self._session._finalize_through(self)
        nq = self._nq
        if not materialize:
            if self._result_dev is None:
                self._result_dev = self._tick_result(
                    self._nn_idx[:nq], self._nn_dist[:nq],
                    self._aux.shard_candidates, self._aux.shard_iterations,
                    aggregates=self._agg,
                )
            return self._result_dev
        if self._collect == "none":
            # nothing to transfer: the finalize scalars the session already
            # read are this mode's whole host footprint
            self._result = self._tick_result(None, None, None, None)
        elif self._collect == "stats":
            # drain compute OUTSIDE the timed window: collect_s is the pure
            # materialization cost, not the device queue
            self.block_until_ready()
            tc = time.perf_counter()
            with span("tick.collect", tick=self.tick):
                agg, shard_cand, shard_it = jax.device_get(
                    (self._agg, self._aux.shard_candidates,
                     self._aux.shard_iterations)
                )
            self._result = self._tick_result(
                None, None, shard_cand, shard_it,
                collect_s=time.perf_counter() - tc, aggregates=agg,
            )
        else:
            # ONE batched host transfer for everything the result carries,
            # timed after the compute drain (same decomposition as "stats")
            self.block_until_ready()
            tc = time.perf_counter()
            with span("tick.collect", tick=self.tick):
                nn_idx, nn_dist, shard_cand, shard_it = jax.device_get(
                    (self._nn_idx[:nq], self._nn_dist[:nq],
                     self._aux.shard_candidates, self._aux.shard_iterations)
                )
            self._result = self._tick_result(
                nn_idx, nn_dist, shard_cand, shard_it,
                collect_s=time.perf_counter() - tc,
            )
        # release device references so XLA can recycle the buffers
        self._nn_idx = self._nn_dist = self._aux = self._should_rebuild = None
        self._agg = None
        self._result_dev = None
        return self._result

    def result_for(self, handle: QueryHandle):
        """This tick's rows for one query group: (nn_idx, nn_dist, qids).

        Rows are selected by the registry ownership snapshot taken at submit
        time, so the mapping stays correct even if the group is updated or
        dropped after this tick was submitted.  Under ``collect != "full"``
        the host never receives the lists, so the rows come back as sliced
        **device arrays** (via ``result(materialize=False)``).
        """
        if self._collect == "full":
            res = self.result()
        else:
            res = self.result(materialize=False)
        if res.nn_idx is None:
            raise RuntimeError(
                f"result_for after result() under collect={self._collect!r}: "
                "the neighbour lists were never transferred and their device "
                "buffers are released; call result_for (or "
                "result(materialize=False)) before materializing"
            )
        rows = np.nonzero(self._owner == handle.hid)[0]
        return res.nn_idx[rows], res.nn_dist[rows], res.qids[rows]
