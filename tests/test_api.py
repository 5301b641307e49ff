"""Session-oriented serving API (repro.api, DESIGN.md §11).

The acceptance contract of the api_redesign PR: the ``KnnSession`` delta-
update and overlapped-submit paths are **bit-identical** to the snapshot
``TickEngine`` path — same padded batches, same jitted step, same drift
bookkeeping sequence — on all three workload families and under both
execution plans.  Plus: eager ServiceSpec/EngineConfig validation, the
persistent query registry (add/update/drop with stable handles), two-in-
flight TickHandle ordering, the compile_s/wall_s split, and the deprecation-
shim equivalence (TickEngine.run ≡ a blocking KnnSession loop).
"""
import os
import subprocess
import sys
import warnings

import jax
import numpy as np
import pytest

from repro.api import KnnSession, QueryHandle, ServiceSpec
from repro.core import EngineConfig, TickEngine, knn_bruteforce_chunked
from repro.data import make_workload

SRC = os.path.join(os.path.dirname(__file__), "..", "src")
NDEV = jax.device_count()


def _spec(plan="single", **kw):
    base = dict(k=6, th_quad=24, l_max=6, window=32, chunk=64, side=22_500.0,
                plan=plan, mesh_shape=NDEV if plan == "sharded" else None,
                delta_pad=64)
    base.update(kw)
    return ServiceSpec(**base)


def _engine(spec):
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", DeprecationWarning)
        return TickEngine(spec.engine_config(), origin=spec.origin,
                          side=spec.side)


# ----------------------------------------------------------------- validation

@pytest.mark.parametrize("bad, match", [
    (dict(backend="nope"), r"unknown backend 'nope'.*registered SCAN backends.*dense_topk"),
    (dict(plan="nope"), r"unknown execution plan 'nope'.*registered plans.*single"),
    (dict(chunk=100, window=64), r"chunk \(100\).*multiple of window \(64\)"),
    (dict(k=3000, chunk=2048, window=256), r"k \(3000\).*<= chunk \(2048\)"),
    (dict(mesh_shape=0), r"mesh_shape"),
    (dict(mesh_shape=(2, 4, 8)), r"query, object"),
    (dict(plan="sharded", mesh_shape=(2, 4)), r"1-D mesh"),
    (dict(plan="object_sharded", mesh_shape=(2, 4)), r"1-D mesh"),
    (dict(side=-1.0), r"side"),
    (dict(delta_pad=0), r"delta_pad"),
    (dict(partitioner="nope"), r"unknown partitioner 'nope'.*cost_balanced"),
    (dict(precision="nope"), r"unknown precision 'nope'.*mixed"),
    (dict(merge="nope"), r"unknown merge backend 'nope'.*fused_multi"),
    (dict(collect="nope"), r"unknown collect mode 'nope'.*stats"),
])
def test_service_spec_validates_eagerly(bad, match):
    with pytest.raises(ValueError, match=match):
        ServiceSpec(**bad)


@pytest.mark.parametrize("bad, match", [
    (dict(backend="nope"), r"unknown backend.*registered SCAN backends"),
    (dict(plan="nope"), r"unknown execution plan.*registered plans"),
    (dict(chunk=100, window=64), r"chunk.*multiple of window"),
    (dict(k=3000, chunk=2048, window=256), r"k.*<= chunk"),
])
def test_engine_config_validates_eagerly(bad, match):
    """Bad names used to surface only as a deep registry KeyError on first use."""
    with pytest.raises(ValueError, match=match):
        EngineConfig(**bad)


def test_spec_subsumes_engine_config_roundtrip():
    cfg = EngineConfig(k=8, th_quad=48, l_max=6, window=64, chunk=1024,
                       backend="brute", plan="sharded", mesh_shape=1,
                       precision="mixed", merge="fused_multi")
    spec = ServiceSpec.from_engine(cfg, origin=(1.0, 2.0), side=9_000.0)
    assert spec.engine_config() == cfg
    assert spec.origin == (1.0, 2.0) and spec.side == 9_000.0
    assert spec.precision == "mixed" and spec.merge == "fused_multi"


# ------------------------------------------------- delta-update parity (tent)

def _moved_subset(rng, pts, frac, side=22_500.0):
    m = max(1, int(len(pts) * frac))
    ids = rng.choice(len(pts), m, replace=False).astype(np.int32)
    new = pts.copy()
    new[ids] = np.clip(
        new[ids] + rng.uniform(-180, 180, (m, 2)).astype(np.float32),
        0, side - 1e-3,
    ).astype(np.float32)
    return ids, new


@pytest.mark.parametrize("dist", ["uniform", "gaussian", "network"])
def test_delta_updates_bit_identical_to_snapshot(dist):
    """N scattered updates (applied in several chunks) ≡ the equivalent full
    snapshot through the TickEngine path — ids AND distances bitwise."""
    w = make_workload(700, dist, seed=5)
    pts = w.positions().copy()
    qid = np.arange(len(pts), dtype=np.int32)
    rng = np.random.default_rng(17)

    spec = _spec()
    sess = KnnSession(spec)
    sess.ingest_objects(pts)
    hq = sess.register_queries(pts, qid)
    eng = _engine(spec)

    cur = pts
    for t in range(3):
        if t > 0:
            ids, cur = _moved_subset(rng, cur, frac=0.3)
            # deltas land in three separate scatter calls (accumulation path)
            for part in np.array_split(np.arange(len(ids)), 3):
                sess.update_objects(ids[part], cur[ids[part]])
            sess.update_queries(hq, cur)
        r_s = sess.submit().result()
        r_e = eng.process_tick(cur, cur, qid)
        np.testing.assert_array_equal(r_s.nn_idx, r_e.nn_idx)
        np.testing.assert_array_equal(r_s.nn_dist, r_e.nn_dist)
        assert r_s.rebuilt == r_e.rebuilt
        assert r_s.candidates == r_e.candidates


def test_delta_updates_bit_identical_sharded_plan():
    w = make_workload(500, "gaussian", seed=3, hotspots=4)
    pts = w.positions().copy()
    qid = np.arange(len(pts), dtype=np.int32)
    rng = np.random.default_rng(7)
    spec = _spec(plan="sharded", chunk=32)
    sess = KnnSession(spec)
    sess.ingest_objects(pts)
    hq = sess.register_queries(pts, qid)
    eng = _engine(spec)
    cur = pts
    for t in range(2):
        if t > 0:
            ids, cur = _moved_subset(rng, cur, frac=0.5)
            sess.update_objects(ids, cur[ids])
            sess.update_queries(hq, cur)
        r_s = sess.submit().result()
        r_e = eng.process_tick(cur, cur, qid)
        np.testing.assert_array_equal(r_s.nn_idx, r_e.nn_idx)
        np.testing.assert_array_equal(r_s.nn_dist, r_e.nn_dist)


# ------------------------------------------- delta routing, object-axis plans

def _object_plan_spec(plan):
    mesh = NDEV if plan == "object_sharded" else None  # hybrid: balanced
    return _spec(plan=plan, chunk=32, mesh_shape=mesh)


@pytest.mark.parametrize("plan", ["object_sharded", "hybrid"])
def test_delta_routing_single_shard_batch(plan):
    """Routing edge 1: an update batch whose every moved row is owned by ONE
    object shard — the grouped scatter must stay bit-identical to the
    snapshot engine path (DESIGN.md §12 ownership rule)."""
    w = make_workload(400, "gaussian", seed=11, hotspots=3)
    pts = w.positions().copy()
    qid = np.arange(len(pts), dtype=np.int32)
    spec = _object_plan_spec(plan)
    sess = KnnSession(spec)
    sess.ingest_objects(pts)
    hq = sess.register_queries(pts, qid)
    eng = _engine(spec)
    r_s = sess.submit().result()
    r_e = eng.process_tick(pts, pts, qid)
    np.testing.assert_array_equal(r_s.nn_idx, r_e.nn_idx)

    owners = sess.object_shards(np.arange(len(pts)))
    target = int(owners[0])
    ids = np.nonzero(owners == target)[0].astype(np.int32)
    assert ids.size > 0 and (sess.object_shards(ids) == target).all()
    rng = np.random.default_rng(5)
    cur = pts.copy()
    cur[ids] = np.clip(
        cur[ids] + rng.uniform(-50, 50, (ids.size, 2)).astype(np.float32),
        0, spec.side - 1e-3)
    sess.update_objects(ids, cur[ids])
    sess.update_queries(hq, cur)
    r_s = sess.submit().result()
    r_e = eng.process_tick(cur, cur, qid)
    np.testing.assert_array_equal(r_s.nn_idx, r_e.nn_idx)
    np.testing.assert_array_equal(r_s.nn_dist, r_e.nn_dist)


@pytest.mark.parametrize("plan", ["object_sharded", "hybrid"])
def test_delta_routing_row_crosses_shard_ownership(plan):
    """Routing edge 2: a row whose move changes its owning shard between
    ticks (Morton rank jump across slice boundaries) — ownership is
    re-derived from the live index, results stay bit-identical."""
    w = make_workload(300, "uniform", seed=13)
    pts = w.positions().copy()
    qid = np.arange(len(pts), dtype=np.int32)
    spec = _object_plan_spec(plan)
    sess = KnnSession(spec)
    sess.ingest_objects(pts)
    hq = sess.register_queries(pts, qid)
    eng = _engine(spec)
    sess.submit().result()
    eng.process_tick(pts, pts, qid)

    # the Morton-first object, teleported to the far corner: rank 0 -> n-1
    mover = int(np.asarray(sess.index.ids)[0])
    before = int(sess.object_shards([mover])[0])
    cur = pts.copy()
    cur[mover] = [spec.side - 1.0, spec.side - 1.0]
    sess.update_objects([mover], cur[mover][None])
    sess.update_queries(hq, cur)
    r_s = sess.submit().result()
    r_e = eng.process_tick(cur, cur, qid)
    np.testing.assert_array_equal(r_s.nn_idx, r_e.nn_idx)
    np.testing.assert_array_equal(r_s.nn_dist, r_e.nn_dist)
    after = int(sess.object_shards([mover])[0])
    shards = sess.plan.object_axis_size
    if shards > 1:
        assert before == 0 and after == shards - 1  # ownership crossed


@pytest.mark.parametrize("plan", ["object_sharded", "hybrid"])
def test_delta_routing_empty_delta_tick(plan):
    """Routing edge 3: an empty update batch is a no-op tick — identical
    results to resubmitting unchanged state, and to the snapshot engine."""
    w = make_workload(250, "network", seed=19)
    pts = w.positions().copy()
    qid = np.arange(len(pts), dtype=np.int32)
    spec = _object_plan_spec(plan)
    sess = KnnSession(spec)
    sess.ingest_objects(pts)
    sess.register_queries(pts, qid)
    eng = _engine(spec)
    r0 = sess.submit().result()
    e0 = eng.process_tick(pts, pts, qid)
    sess.update_objects(np.zeros((0,), np.int32), np.zeros((0, 2), np.float32))
    r1 = sess.submit().result()
    e1 = eng.process_tick(pts, pts, qid)
    np.testing.assert_array_equal(r0.nn_idx, r1.nn_idx)
    np.testing.assert_array_equal(r1.nn_idx, e1.nn_idx)
    np.testing.assert_array_equal(r1.nn_dist, e1.nn_dist)
    np.testing.assert_array_equal(r0.nn_idx, e0.nn_idx)


def test_object_shards_ownership_rule():
    """`object_shards` IS the documented rule: Morton rank // ceil(N/R)."""
    w = make_workload(200, "gaussian", seed=23, hotspots=2)
    pts = w.positions()
    spec = _object_plan_spec("object_sharded")
    sess = KnnSession(spec)
    sess.ingest_objects(pts)
    sess.register_queries(pts[:32], np.arange(32, dtype=np.int32))
    if sess.plan.object_axis_size > 1:
        # ownership is defined by the index's Morton order: not built yet
        with pytest.raises(RuntimeError, match="before the first submit"):
            sess.object_shards([0])
    sess.submit().result()
    shards = sess.object_shards(np.arange(len(pts)))
    r = sess.plan.object_axis_size
    assert shards.min() >= 0 and shards.max() < r
    # independent spelling of the rule from the index's Morton order
    order = np.asarray(sess.index.ids)
    rank = np.empty(len(pts), np.int64)
    rank[order] = np.arange(len(pts))
    cap = -(-len(pts) // r)
    np.testing.assert_array_equal(shards, rank // cap)
    # stale/unknown ids raise instead of returning clamped garbage owners
    if r > 1:
        with pytest.raises(ValueError, match="outside the live index"):
            sess.object_shards([len(pts)])
        with pytest.raises(ValueError, match="outside the live index"):
            sess.object_shards([-1])
    # plans without an object axis own everything on shard 0
    s2 = KnnSession(_spec())
    s2.ingest_objects(pts)
    s2.register_queries(pts[:32], np.arange(32, dtype=np.int32))
    s2.submit().result()
    assert (s2.object_shards(np.arange(len(pts))) == 0).all()


# ------------------------------------------------------ query registry (tent)

@pytest.mark.parametrize("plan", ["single", "sharded"])
def test_query_registry_add_drop_across_ticks(plan):
    """Handles persist across ticks; drops compact the registry; the served
    batch always equals the equivalent snapshot batch, bitwise."""
    rng = np.random.default_rng(4)
    pts = rng.uniform(0, 22_500, (600, 2)).astype(np.float32)
    qa = rng.uniform(0, 22_500, (90, 2)).astype(np.float32)
    qb = rng.uniform(0, 22_500, (40, 2)).astype(np.float32)
    qc = rng.uniform(0, 22_500, (25, 2)).astype(np.float32)

    spec = _spec(plan=plan, chunk=32)
    sess = KnnSession(spec)
    sess.ingest_objects(pts)
    ha = sess.register_queries(qa)
    hb = sess.register_queries(qb, np.arange(40, dtype=np.int32))
    assert isinstance(ha, QueryHandle) and ha.count == 90

    def reference(qpos, qid):
        eng = _engine(spec)
        return eng.process_tick(pts, qpos, qid)

    # tick 0: A + B
    r0 = sess.submit().result()
    ref = reference(np.concatenate([qa, qb]),
                    np.concatenate([np.full(90, -2, np.int32),
                                    np.arange(40, dtype=np.int32)]))
    np.testing.assert_array_equal(r0.nn_idx, ref.nn_idx)
    np.testing.assert_array_equal(r0.nn_dist, ref.nn_dist)

    # tick 1: drop A -> only B remains (compacted to the front)
    sess.drop_queries(ha)
    r1 = sess.submit().result()
    ref1 = reference(qb, np.arange(40, dtype=np.int32))
    np.testing.assert_array_equal(r1.nn_idx, ref1.nn_idx)
    np.testing.assert_array_equal(r1.nn_dist, ref1.nn_dist)
    assert r1.nn_idx.shape == (40, spec.k)

    # tick 2: register C -> B + C
    hc = sess.register_queries(qc)
    h2 = sess.submit()
    r2 = h2.result()
    ref2 = reference(np.concatenate([qb, qc]),
                     np.concatenate([np.arange(40, dtype=np.int32),
                                     np.full(25, -2, np.int32)]))
    np.testing.assert_array_equal(r2.nn_idx, ref2.nn_idx)
    np.testing.assert_array_equal(r2.nn_dist, ref2.nn_dist)
    # per-handle result slicing via the ownership snapshot
    ci, cd, cq = h2.result_for(hc)
    np.testing.assert_array_equal(ci, r2.nn_idx[40:])
    np.testing.assert_array_equal(cd, r2.nn_dist[40:])
    assert (cq == -2).all()
    bi, bd, bq = h2.result_for(hb)
    np.testing.assert_array_equal(bi, r2.nn_idx[:40])
    np.testing.assert_array_equal(bq, np.arange(40, dtype=np.int32))

    # dropped handle is dead
    with pytest.raises(KeyError, match="not live"):
        sess.update_queries(ha, qa)


def test_update_queries_moves_only_that_group():
    rng = np.random.default_rng(9)
    pts = rng.uniform(0, 22_500, (400, 2)).astype(np.float32)
    qa = rng.uniform(0, 22_500, (30, 2)).astype(np.float32)
    qb = rng.uniform(0, 22_500, (20, 2)).astype(np.float32)
    spec = _spec()
    sess = KnnSession(spec)
    sess.ingest_objects(pts)
    ha = sess.register_queries(qa)
    hb = sess.register_queries(qb)
    sess.submit().result()
    qa2 = np.clip(qa + 50.0, 0, 22_499).astype(np.float32)
    sess.update_queries(ha, qa2)
    r = sess.submit().result()
    ref = _engine(spec).process_tick(pts, np.concatenate([qa2, qb]), None)
    np.testing.assert_array_equal(r.nn_idx, ref.nn_idx)
    np.testing.assert_array_equal(r.nn_dist, ref.nn_dist)


# --------------------------------------------------- overlapped submit (tent)

def test_two_in_flight_handles_any_collection_order():
    """Submit τ+1 while τ's results are in flight; collect out of order;
    every tick bitwise-equal to the blocking reference loop."""
    w = make_workload(500, "gaussian", seed=2, hotspots=4)
    qid = np.arange(500, dtype=np.int32)
    frames = []
    for _ in range(4):
        frames.append(w.positions().copy())
        w.advance()

    spec = _spec()
    eng = _engine(spec)
    blocking = [eng.process_tick(p, p, qid) for p in frames]

    sess = KnnSession(spec)
    sess.ingest_objects(frames[0])
    hq = sess.register_queries(frames[0], qid)
    handles = [sess.submit()]
    for p in frames[1:]:
        sess.ingest_objects(p)
        sess.update_queries(hq, p)
        handles.append(sess.submit())  # up to 2 unmaterialized in flight
        if len(handles) > 2:
            handles[-3].result()
    # collect the tail out of order
    res = {h.tick: h.result() for h in reversed(handles)}
    assert sorted(res) == [0, 1, 2, 3]
    assert [h.tick for h in handles] == [0, 1, 2, 3]
    for t, ref in enumerate(blocking):
        np.testing.assert_array_equal(res[t].nn_idx, ref.nn_idx)
        np.testing.assert_array_equal(res[t].nn_dist, ref.nn_dist)
        assert res[t].rebuilt == ref.rebuilt
    # result() is idempotent
    assert handles[1].result() is res[1]
    assert handles[0].done()


def test_result_of_finalized_tick_leaves_successor_pending():
    """result(τ) after submit(τ+1) — τ was finalized by the submit — must not
    finalize (and block on) τ+1; τ+1 stays in flight."""
    rng = np.random.default_rng(3)
    pts = rng.uniform(0, 22_500, (200, 2)).astype(np.float32)
    sess = KnnSession(_spec())
    sess.ingest_objects(pts)
    sess.register_queries(pts[:50])
    ha = sess.submit()
    hb = sess.submit()  # finalizes ha's bookkeeping
    ra = ha.result()
    assert len(sess._pending) == 1 and sess._pending[0] is hb
    rb = hb.result()
    assert not sess._pending
    np.testing.assert_array_equal(ra.nn_idx, rb.nn_idx)  # static state


# ------------------------------------------------------- shim equivalence

def test_tick_engine_shim_equivalent_to_session_loop():
    """TickEngine.run ≡ the manual KnnSession loop, tick for tick, bitwise
    (results, rebuilt flags, candidate counters)."""
    cfg = EngineConfig(k=6, th_quad=16, l_max=5, window=32, chunk=64)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", DeprecationWarning)
        eng = TickEngine(cfg)
    w1 = make_workload(600, "gaussian", seed=2, hotspots=4)
    engine_res = eng.run(w1, ticks=3)

    sess = KnnSession(ServiceSpec.from_engine(cfg))
    w2 = make_workload(600, "gaussian", seed=2, hotspots=4)
    hq = None
    session_res = []
    for _ in range(3):
        qpos, qid = w2.query_batch(1.0)
        sess.ingest_objects(w2.positions())
        if hq is None:
            hq = sess.register_queries(qpos, qid)
        else:
            sess.update_queries(hq, qpos)
        session_res.append(sess.submit().result())
        w2.advance()

    for re_, rs in zip(engine_res, session_res):
        np.testing.assert_array_equal(re_.nn_idx, rs.nn_idx)
        np.testing.assert_array_equal(re_.nn_dist, rs.nn_dist)
        assert re_.rebuilt == rs.rebuilt
        assert re_.candidates == rs.candidates
        assert re_.iterations == rs.iterations


def test_tick_engine_warns_deprecation():
    with pytest.warns(DeprecationWarning, match="KnnSession"):
        TickEngine(EngineConfig(k=4, th_quad=16, l_max=5, window=32, chunk=64))


# ------------------------------------------------------- compile_s split

def test_compile_time_split_from_wall_time():
    """First submit of a new shape records compile_s; steady ticks report 0
    and wall_s excludes the compile entirely."""
    rng = np.random.default_rng(1)
    pts = rng.uniform(0, 22_500, (300, 2)).astype(np.float32)
    # odd geometry -> guaranteed fresh jit cache entry in this process
    sess = KnnSession(_spec(k=5, window=32, chunk=96))
    sess.ingest_objects(pts)
    sess.register_queries(pts[:33])
    r0 = sess.submit().result()
    r1 = sess.submit().result()
    assert r0.compile_s > 0.0
    assert r1.compile_s == 0.0
    assert r0.wall_s >= 0.0 and r1.wall_s >= 0.0
    # the shim surfaces the same split; its tick 1 is the FIRST snapshot
    # re-ingest of this shape, which runs the "rebuild" maintenance mode —
    # a distinct static, hence its own one-time compile (DESIGN.md §15) —
    # so steady state (compile_s == 0) starts at tick 2
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", DeprecationWarning)
        eng = TickEngine(EngineConfig(k=5, th_quad=24, l_max=6, window=32,
                                      chunk=96))
    e0 = eng.process_tick(pts, pts[:33], None)
    e1 = eng.process_tick(pts, pts[:33], None)
    e2 = eng.process_tick(pts, pts[:33], None)
    assert e0.compile_s >= 0.0 and e1.compile_s >= 0.0
    assert e2.compile_s == 0.0


def test_compile_s_is_measured_for_a_new_padded_capacity():
    """compile_s comes from JAX's own compile events: a second session whose
    registry pads to a capacity no program was built for reports it on its
    first tick, and only there."""
    rng = np.random.default_rng(2)
    pts = rng.uniform(0, 22_500, (400, 2)).astype(np.float32)
    spec = _spec(k=5, window=32, chunk=96)
    first = KnnSession(spec)
    first.ingest_objects(pts)
    first.register_queries(pts[:33])  # pads to one chunk
    first.submit().result()
    second = KnnSession(spec)
    second.ingest_objects(pts)
    second.register_queries(pts[:150])  # pads to two chunks: a new shape
    r0 = second.submit().result()
    r1 = second.submit().result()
    assert r0.compile_s > 0.0
    assert r1.compile_s == 0.0


def _trace_spans(path):
    """``{name: [tick, ...]}`` of the ``knn.`` spans in a recorded trace."""
    from pathlib import Path

    data = jax.profiler.ProfileData.from_file(
        str(next(Path(path).rglob("*.xplane.pb"))))
    out = {}
    for plane in data.planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("knn."):
                    stats = dict(ev.stats)
                    out.setdefault(ev.name, []).append(
                        (int(stats["tick"]), ev.start_ns,
                         ev.start_ns + ev.duration_ns))
    return out


def test_one_tick_emits_the_session_spans(tmp_path):
    """One tick of the closed loop (one tick in flight) writes exactly these
    host spans into the profiler's trace, each with its tick number;
    finalize and dispatch nest in submit, collect in result."""
    rng = np.random.default_rng(3)
    pts = rng.uniform(0, 22_500, (500, 2)).astype(np.float32)
    sess = KnnSession(_spec())
    sess.ingest_objects(pts)
    hq = sess.register_queries(pts[:40], np.arange(40))
    prev = sess.submit()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        sess.ingest_objects(pts[::-1].copy())
        prev.block_until_ready()
        sess.update_queries(hq, pts[40:80])
        nxt = sess.submit()
        prev.result()
    finally:
        jax.profiler.stop_trace()
    nxt.result()
    spans = _trace_spans(tmp_path)
    assert set(spans) == {
        "knn.session.ingest", "knn.session.update_queries",
        "knn.session.submit", "knn.session.finalize", "knn.session.dispatch",
        "knn.session.delta", "knn.tick.wait", "knn.tick.result",
        "knn.tick.collect",
    }
    assert [t for t, _, _ in spans["knn.session.submit"]] == [1]
    assert [t for t, _, _ in spans["knn.session.finalize"]] == [0]
    assert {t for t, _, _ in spans["knn.tick.wait"]} == {0}

    def inside(child, parent):
        (_, s, e), = spans[child]
        (_, ps, pe), = spans[parent]
        return ps <= s and e <= pe

    assert inside("knn.session.finalize", "knn.session.submit")
    assert inside("knn.session.dispatch", "knn.session.submit")
    assert inside("knn.session.delta", "knn.session.dispatch")
    assert inside("knn.tick.collect", "knn.tick.result")


def test_a_delta_tick_emits_the_delta_span(tmp_path):
    """A tick fed by ``update_objects`` under the incremental spec marks its
    maintenance decision and delta assembly as ``knn.session.delta``, inside
    the dispatch, and reports the rows it spliced."""
    rng = np.random.default_rng(4)
    pts = rng.uniform(0, 22_500, (500, 2)).astype(np.float32)
    sess = KnnSession(_spec(maintenance="incremental"))
    sess.ingest_objects(pts)
    sess.register_queries(pts[:40], np.arange(40))
    sess.submit().result()
    ids = rng.choice(500, 30, replace=False).astype(np.int32)
    sess.update_objects(ids, rng.uniform(0, 22_500, (30, 2)).astype(
        np.float32))
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        res = sess.submit().result()
    finally:
        jax.profiler.stop_trace()
    assert res.maintenance == "incremental" and res.delta_rows == 30
    spans = _trace_spans(tmp_path)
    (tick, s, e), = spans["knn.session.delta"]
    (_, ds, de), = spans["knn.session.dispatch"]
    assert tick == 1 and ds <= s and e <= de


# ------------------------------------------------------- drift rebuild

def test_drift_rebuild_through_delta_path():
    """Teleporting all objects into one hotspot via update_objects must
    trigger the partition rebuild and stay exact (paper Sec. 4.1.1)."""
    n, k = 3000, 16
    rng = np.random.default_rng(12)
    uniform = rng.uniform(0, 22_500, (n, 2)).astype(np.float32)
    clustered = (rng.normal(0, 60, (n, 2)) + 11_250).astype(np.float32).clip(0, 22_499)
    qid = np.arange(n, dtype=np.int32)

    sess = KnnSession(_spec(k=k, th_quad=32, l_max=6, window=64, chunk=1024,
                            rebuild_factor=1.5))
    sess.ingest_objects(uniform)
    hq = sess.register_queries(uniform, qid)
    r0 = sess.submit().result()
    assert r0.rebuilt  # initial build
    r1 = sess.submit().result()
    assert not r1.rebuilt
    sess.update_objects(np.arange(n, dtype=np.int32), clustered)
    sess.update_queries(hq, clustered)
    r2 = sess.submit().result()
    assert r2.rebuilt, (r2.candidates, r1.candidates)
    bi, bd = knn_bruteforce_chunked(clustered, clustered, qid, k=k, chunk=1024)
    np.testing.assert_allclose(r2.nn_dist, bd, rtol=1e-5, atol=1e-3)


@pytest.mark.parametrize("partitioner", ["equal", "cost_balanced"])
def test_object_shards_fresh_after_drift_rebuild(partitioner):
    """Rebuild-then-route regression (object_sharded): ownership answered
    while a drift-rebuild decision is still pending must reflect the POST-
    rebuild Morton order, not the submitted tick's stale one.

    ``object_shards`` finalizes pending ticks first; the answer must agree
    with an independent spelling of the ownership rule evaluated on
    whatever index is live AFTER the call — which the next tick serves from.
    """
    n = 2000
    rng = np.random.default_rng(21)
    uniform = rng.uniform(0, 22_500, (n, 2)).astype(np.float32)
    clustered = (rng.normal(0, 60, (n, 2)) + 11_250).astype(
        np.float32).clip(0, 22_499)
    qid = np.arange(n, dtype=np.int32)
    sess = KnnSession(_spec(plan="object_sharded", mesh_shape=NDEV,
                            th_quad=32, chunk=512, rebuild_factor=1.5,
                            partitioner=partitioner))
    sess.ingest_objects(uniform)
    hq = sess.register_queries(uniform, qid)
    sess.submit().result()
    sess.submit().result()  # baseline tick (sets the work-at-build anchor)
    sess.update_objects(qid, clustered)
    sess.update_queries(hq, clustered)
    h = sess.submit()  # drift tick: rebuild decision PENDING until finalize
    owners = sess.object_shards(qid)  # must finalize + answer post-rebuild
    if sess.plan.object_axis_size > 1:  # trivial-ownership fast path skips it
        assert h._finalized
    res = h.result()
    assert res.rebuilt  # the teleport really did trigger the rebuild
    # independent spelling of the rule from the live (post-rebuild) index
    order = np.asarray(sess.index.ids)
    rank = np.empty(n, np.int64)
    rank[order] = np.arange(n)
    if sess._obj_bounds is not None:
        bounds = np.asarray(sess._obj_bounds)
        expect = np.searchsorted(bounds, rank, side="right") - 1
    else:
        expect = rank // -(-n // sess.plan.object_axis_size)
    np.testing.assert_array_equal(owners, expect)


def test_result_materialize_false_returns_device_arrays():
    """result(materialize=False) hands back device arrays (no host sync);
    a later result() still materializes numpy, bit-identically."""
    rng = np.random.default_rng(9)
    pts = rng.uniform(0, 22_500, (300, 2)).astype(np.float32)
    sess = KnnSession(_spec())
    sess.ingest_objects(pts)
    sess.register_queries(pts, np.arange(300, dtype=np.int32))
    h = sess.submit()
    dev = h.result(materialize=False)
    assert isinstance(dev.nn_idx, jax.Array) and isinstance(
        dev.nn_dist, jax.Array)
    assert dev.nn_idx.shape == (300, sess.spec.k)
    assert isinstance(dev.shard_candidates, jax.Array)
    # idempotent: same device-result object, no re-slice
    assert h.result(materialize=False) is dev
    host = h.result()
    assert isinstance(host.nn_idx, np.ndarray)
    np.testing.assert_array_equal(np.asarray(dev.nn_idx), host.nn_idx)
    np.testing.assert_array_equal(np.asarray(dev.nn_dist), host.nn_dist)
    assert np.float32(host.shard_candidates.sum()) == np.float32(
        host.candidates)
    assert h.result() is host  # materialized result is cached


def test_update_objects_duplicate_ids_last_wins():
    """Several observations for one object in one delta batch resolve
    deterministically to the LAST one (≡ applying them in order)."""
    rng = np.random.default_rng(6)
    pts = rng.uniform(0, 22_500, (300, 2)).astype(np.float32)
    spec = _spec()
    sess = KnnSession(spec)
    sess.ingest_objects(pts)
    sess.register_queries(pts, np.arange(300, dtype=np.int32))
    sess.submit().result()
    ids = np.array([7, 7, 12, 7, 12], np.int32)
    upd = rng.uniform(0, 22_500, (5, 2)).astype(np.float32)
    sess.update_objects(ids, upd)
    expect = pts.copy()
    expect[7], expect[12] = upd[3], upd[4]  # last observation per id
    r = sess.submit().result()
    ref = _engine(spec)
    ref.process_tick(pts, pts, np.arange(300, dtype=np.int32))
    ref_r = ref.process_tick(expect, pts, np.arange(300, dtype=np.int32))
    np.testing.assert_array_equal(r.nn_idx, ref_r.nn_idx)
    np.testing.assert_array_equal(r.nn_dist, ref_r.nn_dist)


# --------------------------------- on-device result consumers (DESIGN.md §14)

def test_collect_stats_aggregates_match_full_results():
    """collect="stats": nn lists never cross the host boundary; the sink's
    aggregates agree with what the full lists imply — k-th distances
    bitwise, zero drift/churn on a static workload, shard hit total = Q*k,
    first tick churn = 1 (no previous observation)."""
    rng = np.random.default_rng(31)
    pts = rng.uniform(0, 22_500, (500, 2)).astype(np.float32)
    q = rng.uniform(0, 22_500, (64, 2)).astype(np.float32)

    full = KnnSession(_spec())
    full.ingest_objects(pts)
    full.register_queries(q)
    f0 = full.submit().result()
    f1 = full.submit().result()

    sess = KnnSession(_spec(collect="stats"))
    sess.ingest_objects(pts)
    sess.register_queries(q)
    r0 = sess.submit().result()
    r1 = sess.submit().result()
    assert r0.nn_idx is None and r0.nn_dist is None
    a0, a1 = r0.aggregates, r1.aggregates
    assert float(a0.churn_mean) == 1.0 and float(a0.churn_max) == 1.0
    assert float(a1.churn_mean) == 0.0 and float(a1.kth_drift_mean) == 0.0
    np.testing.assert_array_equal(
        np.asarray(a1.kth_dist)[:64], f1.nn_dist[:, -1])
    assert int(a1.n_live) == 64
    assert float(np.asarray(a1.shard_hits).sum()) == 64 * sess.spec.k
    # bookkeeping unaffected by the collect mode
    assert r1.candidates == f1.candidates
    assert r1.iterations == f1.iterations
    np.testing.assert_array_equal(r1.shard_candidates, f1.shard_candidates)


@pytest.mark.parametrize("plan", ["object_sharded", "hybrid"])
def test_collect_stats_shard_hits_follow_object_partition(plan):
    """Under the object-axis plans the hit histogram spans the mesh's object
    shards and matches a host-side recount from the full lists + the
    session's own ownership answer."""
    w = make_workload(400, "gaussian", seed=11, hotspots=3)
    pts = w.positions()
    qid = np.arange(64, dtype=np.int32)
    spec = _spec(plan=plan, chunk=32,
                 mesh_shape=NDEV if plan == "object_sharded" else None,
                 collect="stats")
    sess = KnnSession(spec)
    sess.ingest_objects(pts)
    sess.register_queries(pts[:64], qid)
    r = sess.submit().result()
    hits = np.asarray(r.aggregates.shard_hits)
    assert hits.shape == (sess.plan.object_axis_size,)
    assert hits.sum() == 64 * spec.k
    full = KnnSession(_spec(plan=plan, chunk=32, mesh_shape=spec.mesh_shape))
    full.ingest_objects(pts)
    full.register_queries(pts[:64], qid)
    rf = full.submit().result()
    owners = sess.object_shards(rf.nn_idx.reshape(-1))
    np.testing.assert_array_equal(
        hits, np.bincount(owners, minlength=hits.shape[0]))


def test_collect_none_ships_nothing():
    """collect="none": the result record carries only the bookkeeping the
    finalize scalars already paid for — no lists, no counters, no transfer
    time — while the drift-rebuild sequence stays identical to full."""
    n = 3000
    rng = np.random.default_rng(12)
    uniform = rng.uniform(0, 22_500, (n, 2)).astype(np.float32)
    clustered = (rng.normal(0, 60, (n, 2)) + 11_250).astype(
        np.float32).clip(0, 22_499)
    qid = np.arange(n, dtype=np.int32)

    def drive(collect):
        sess = KnnSession(_spec(k=16, th_quad=32, l_max=6, window=64,
                                chunk=1024, rebuild_factor=1.5,
                                collect=collect))
        sess.ingest_objects(uniform)
        hq = sess.register_queries(uniform, qid)
        out = [sess.submit().result(), sess.submit().result()]
        sess.update_objects(np.arange(n, dtype=np.int32), clustered)
        sess.update_queries(hq, clustered)
        out.append(sess.submit().result())
        return out

    none_res = drive("none")
    full_res = drive("full")
    for rn, rf in zip(none_res, full_res):
        assert rn.nn_idx is None and rn.nn_dist is None
        assert rn.shard_candidates is None and rn.aggregates is None
        assert rn.collect_s == 0.0
        assert rn.rebuilt == rf.rebuilt
        assert rn.candidates == rf.candidates
        assert rn.iterations == rf.iterations
    assert none_res[2].rebuilt  # the teleport's drift trigger still fired


def test_collect_stats_churn_resets_on_registry_change():
    """The sink's cross-tick memory is row-aligned with the padded registry
    batch: a row-set change resets it (churn reports 1 again) instead of
    comparing against another query's stale neighbour list."""
    rng = np.random.default_rng(44)
    pts = rng.uniform(0, 22_500, (400, 2)).astype(np.float32)
    sess = KnnSession(_spec(collect="stats"))
    sess.ingest_objects(pts)
    sess.register_queries(pts[:40])
    sess.submit().result()
    r1 = sess.submit().result()
    assert float(r1.aggregates.churn_mean) == 0.0
    sess.register_queries(pts[40:50])  # row set changed -> sink state reset
    r2 = sess.submit().result()
    assert float(r2.aggregates.churn_mean) == 1.0
    r3 = sess.submit().result()
    assert float(r3.aggregates.churn_mean) == 0.0


def test_result_for_device_rows_under_stats_mode():
    """result_for under collect="stats" serves device-array rows (no host
    transfer of the lists) and refuses after the buffers are released."""
    rng = np.random.default_rng(3)
    pts = rng.uniform(0, 22_500, (300, 2)).astype(np.float32)
    sess = KnnSession(_spec(collect="stats"))
    sess.ingest_objects(pts)
    hq = sess.register_queries(pts[:32])
    h = sess.submit()
    di, dd, dq = h.result_for(hq)
    assert isinstance(di, jax.Array) and di.shape == (32, sess.spec.k)
    full = KnnSession(_spec())
    full.ingest_objects(pts)
    full.register_queries(pts[:32])
    rf = full.submit().result()
    np.testing.assert_array_equal(np.asarray(di), rf.nn_idx)
    np.testing.assert_array_equal(np.asarray(dd), rf.nn_dist)
    h.result()  # materializes the aggregates, releases the list buffers
    with pytest.raises(RuntimeError, match="never transferred"):
        h.result_for(hq)


def test_mixed_precision_session_bitwise_over_ticks():
    """precision="mixed" through the session (delta ingest, drift rebuild)
    == fp32, tick for tick, bitwise (DESIGN.md §14)."""
    w = make_workload(500, "gaussian", seed=2, hotspots=4)
    qid = np.arange(500, dtype=np.int32)
    frames = []
    for _ in range(3):
        frames.append(w.positions().copy())
        w.advance()

    def drive(precision):
        sess = KnnSession(_spec(precision=precision))
        sess.ingest_objects(frames[0])
        hq = sess.register_queries(frames[0], qid)
        out = []
        for t, p in enumerate(frames):
            if t > 0:
                moved = np.nonzero((p != frames[t - 1]).any(1))[0].astype(
                    np.int32)
                sess.update_objects(moved, p[moved])
                sess.update_queries(hq, p)
            out.append(sess.submit().result())
        return out

    for rm, rf in zip(drive("mixed"), drive("fp32")):
        np.testing.assert_array_equal(rm.nn_idx, rf.nn_idx)
        np.testing.assert_array_equal(rm.nn_dist, rf.nn_dist)
        assert rm.rebuilt == rf.rebuilt


# ---------------------------- in-flight device handles (satellite, §14)

def test_device_handles_stay_valid_across_submits_and_rebuild():
    """Two-in-flight materialize=False contract: tick τ's device arrays stay
    valid (and correct) after τ+1 submits, and after a drift rebuild is
    applied between τ's submit and τ's result — nothing donates or
    overwrites the result buffers."""
    n, k = 2000, 8
    rng = np.random.default_rng(27)
    uniform = rng.uniform(0, 22_500, (n, 2)).astype(np.float32)
    clustered = (rng.normal(0, 60, (n, 2)) + 11_250).astype(
        np.float32).clip(0, 22_499)
    qid = np.arange(n, dtype=np.int32)

    spec = _spec(k=k, th_quad=32, l_max=6, window=64, chunk=512,
                 rebuild_factor=1.5)
    eng = _engine(spec)
    ref = [eng.process_tick(uniform, uniform, qid),
           eng.process_tick(uniform, uniform, qid),
           eng.process_tick(clustered, clustered, qid),
           eng.process_tick(clustered, clustered, qid)]

    sess = KnnSession(spec)
    sess.ingest_objects(uniform)
    hq = sess.register_queries(uniform, qid)
    h0 = sess.submit()
    h1 = sess.submit()  # two in flight; h0 finalized here
    dev0 = h0.result(materialize=False)
    sess.update_objects(qid, clustered)
    sess.update_queries(hq, clustered)
    h2 = sess.submit()  # the drift tick; h1 finalized here
    dev1 = h1.result(materialize=False)
    h3 = sess.submit()  # finalizing h2 applies the REBUILD before dispatch
    # h2's device arrays were produced pre-rebuild; the rebuild between its
    # submit and this read must not invalidate or corrupt them
    dev2 = h2.result(materialize=False)
    assert h2._finalized and h2.result().rebuilt
    r3 = h3.result()
    assert not r3.rebuilt
    for dev, r in zip((dev0, dev1, dev2), ref):
        assert isinstance(dev.nn_idx, jax.Array)
        np.testing.assert_array_equal(np.asarray(dev.nn_idx), r.nn_idx)
        np.testing.assert_array_equal(np.asarray(dev.nn_dist), r.nn_dist)
    np.testing.assert_array_equal(r3.nn_idx, ref[3].nn_idx)


def test_device_aggregates_stay_valid_with_two_in_flight():
    """Same contract for the stats sink's device aggregates: τ's aggregate
    arrays survive τ+1's submit (the sink state advances functionally)."""
    rng = np.random.default_rng(5)
    pts = rng.uniform(0, 22_500, (400, 2)).astype(np.float32)
    sess = KnnSession(_spec(collect="stats"))
    sess.ingest_objects(pts)
    hq = sess.register_queries(pts[:48])
    h0 = sess.submit()
    moved = pts[:48] + 25.0
    sess.update_queries(hq, np.clip(moved, 0, 22_499).astype(np.float32))
    h1 = sess.submit()
    d0 = h0.result(materialize=False)
    d1 = h1.result(materialize=False)
    assert isinstance(d0.aggregates.kth_dist, jax.Array)
    assert float(d0.aggregates.churn_mean) == 1.0  # first tick
    assert 0.0 <= float(d1.aggregates.churn_mean) <= 1.0
    r0 = h0.result()
    assert isinstance(r0.aggregates.kth_dist, np.ndarray)
    assert float(r0.aggregates.churn_mean) == 1.0


# ------------------------------------------------------- error surface

def test_session_error_surface():
    sess = KnnSession(_spec())
    with pytest.raises(RuntimeError, match="ingest_objects"):
        sess.update_objects([0], [[1.0, 1.0]])
    with pytest.raises(RuntimeError, match="no object state"):
        sess.submit()
    pts = np.random.default_rng(0).uniform(0, 22_500, (100, 2)).astype(np.float32)
    sess.ingest_objects(pts)
    with pytest.raises(RuntimeError, match="empty query registry"):
        sess.submit()
    with pytest.raises(ValueError, match="empty query group"):
        sess.register_queries(np.zeros((0, 2), np.float32))
    h = sess.register_queries(pts[:10])
    with pytest.raises(ValueError, match="10 rows"):
        sess.update_queries(h, pts[:5])
    with pytest.raises(ValueError, match="out of range"):
        sess.update_objects([100], [[1.0, 1.0]])
    with pytest.raises(ValueError, match="ids vs"):
        sess.update_objects([1, 2], [[1.0, 1.0]])
    with pytest.raises(ValueError, match="qid has"):
        sess.register_queries(pts[:4], np.arange(3, dtype=np.int32))
    sess.drop_queries(h)
    with pytest.raises(KeyError):
        sess.drop_queries(h)
    sess.set_queries(pts[:8])
    assert sess.query_count == 8


# -------------------------------------------- forced 8-device mesh (real XLA)

def test_session_parity_on_forced_8_device_mesh():
    """The acceptance criterion on real multi-device XLA: delta ingest +
    overlapped submit through KnnSession is bit-identical to the snapshot
    TickEngine path under BOTH plans on an 8-device host mesh, all three
    workload families.  Subprocess: device count must precede jax init."""
    code = r"""
import os, warnings
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import numpy as np, jax
assert jax.device_count() == 8, jax.device_count()
from repro.api import KnnSession, ServiceSpec
from repro.core import EngineConfig, TickEngine
from repro.data import make_workload

for plan in ("single", "sharded"):
    for dist in ("uniform", "gaussian", "network"):
        spec = ServiceSpec(k=4, th_quad=16, l_max=5, window=32, chunk=32,
                           plan=plan, mesh_shape=8 if plan == "sharded" else None,
                           delta_pad=64)
        w = make_workload(400, dist, seed=5)
        frames = []
        for _ in range(3):
            frames.append(w.positions().copy()); w.advance()
        qid = np.arange(400, dtype=np.int32)
        with warnings.catch_warnings():
            warnings.simplefilter("ignore", DeprecationWarning)
            eng = TickEngine(spec.engine_config())
        ref = [eng.process_tick(p, p, qid) for p in frames]

        sess = KnnSession(spec)
        sess.ingest_objects(frames[0])
        hq = sess.register_queries(frames[0], qid)
        handles, prev = [], None
        for t, p in enumerate(frames):
            if t > 0:
                moved = np.nonzero((p != frames[t-1]).any(1))[0].astype(np.int32)
                sess.update_objects(moved, p[moved])
                sess.update_queries(hq, p)
            handles.append(sess.submit())  # overlapped: result lags one tick
        for h, r in zip(handles, ref):
            got = h.result()
            np.testing.assert_array_equal(got.nn_idx, r.nn_idx)
            np.testing.assert_array_equal(got.nn_dist, r.nn_dist)
            assert got.rebuilt == r.rebuilt
print("SESSION_8DEV_OK")
"""
    env = dict(os.environ, PYTHONPATH=SRC)
    env.pop("XLA_FLAGS", None)  # the child pins its own device count
    r = subprocess.run(
        [sys.executable, "-c", code], env=env, capture_output=True, text=True
    )
    assert r.returncode == 0, (r.stdout[-1000:], r.stderr[-3000:])
    assert "SESSION_8DEV_OK" in r.stdout
