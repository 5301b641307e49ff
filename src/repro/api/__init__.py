"""repro.api — the public session-oriented serving facade (DESIGN.md §11).

Speak in sessions, deltas and in-flight ticks, not snapshots:

    from repro.api import KnnSession, ServiceSpec

    session = KnnSession(ServiceSpec(k=32, side=22_500.0))
    session.ingest_objects(P0)                      # snapshot seed
    hq = session.register_queries(qpos, qid)        # persistent query group
    for tick in range(30):
        session.update_objects(moved_ids, moved_pos)   # delta ingest
        handle = session.submit()                      # non-blocking
        ...                                            # stage the next tick
        res = handle.result()                          # (Q, k) lazily

The execution core underneath is :mod:`repro.core` (`_tick_step`, the
ExecutionPlan/QueryExecutor seams).  A session is configured by exactly one
:class:`ServiceSpec`; ``KnnSession.process_tick`` is the blocking snapshot
call (ingest + ``set_queries`` + ``submit().result()``) for callers that
hold whole frames.
"""
from .handles import QueryHandle, TickHandle
from .session import KnnSession
from .sink import ResultSink, SinkState, StatsSink, TickAggregates
from .spec import COLLECT_MODES, ServiceSpec

__all__ = [
    "KnnSession",
    "ServiceSpec",
    "COLLECT_MODES",
    "QueryHandle",
    "TickHandle",
    "ResultSink",
    "StatsSink",
    "SinkState",
    "TickAggregates",
]
