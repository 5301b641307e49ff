"""End-to-end behaviour of the paper's system: the tick loop (Sec. 2.2/2.3).

Iterated batch processing over moving objects: timeslice semantics per tick,
index reuse across ticks, rebuild policy on distribution drift.
"""
import numpy as np

from repro.api import KnnSession, ServiceSpec
from repro.core import knn_bruteforce_chunked
from repro.data import WorkloadConfig, MovingObjectWorkload, make_workload
from repro.testing import run_ticks


def test_engine_matches_bruteforce_over_ticks():
    n, k, ticks = 2000, 8, 4
    sess = KnnSession(ServiceSpec(k=k, th_quad=32, l_max=6, window=64, chunk=1024))
    w = make_workload(n, "gaussian", seed=11)
    results = run_ticks(sess, w, ticks=ticks)
    assert len(results) == ticks
    # replay the workload and verify every tick against brute force
    w2 = make_workload(n, "gaussian", seed=11)
    for t in range(ticks):
        qpos, qid = w2.query_batch()
        bi, bd = knn_bruteforce_chunked(w2.positions(), qpos, qid, k=k, chunk=1024)
        np.testing.assert_allclose(results[t].nn_dist, bd, rtol=1e-5, atol=1e-3)
        w2.advance()
    # index built once, reused after
    assert results[0].rebuilt
    assert not results[1].rebuilt


def test_rebuild_policy_triggers_on_drift():
    """Teleporting all objects into one hotspot must blow up the work counter
    and trigger a partition rebuild (paper Sec. 4.1.1 trigger)."""
    n, k = 3000, 16
    sess = KnnSession(
        ServiceSpec(k=k, th_quad=32, l_max=6, window=64, chunk=1024, rebuild_factor=1.5)
    )
    rng = np.random.default_rng(12)
    uniform = rng.uniform(0, 22500, (n, 2)).astype(np.float32)
    clustered = (rng.normal(0, 60, (n, 2)) + 11250).astype(np.float32).clip(0, 22499)
    qid = np.arange(n, dtype=np.int32)

    r0 = sess.process_tick(uniform, uniform, qid)
    assert r0.rebuilt  # initial build
    r1 = sess.process_tick(uniform, uniform, qid)
    assert not r1.rebuilt
    # drift: everything collapses into one cluster -> old partition is bad
    r2 = sess.process_tick(clustered, clustered, qid)
    assert r2.rebuilt, (r2.candidates, r1.candidates)
    # and the result is still exact under the stale partition
    bi, bd = knn_bruteforce_chunked(clustered, clustered, qid, k=k, chunk=1024)
    np.testing.assert_allclose(r2.nn_dist, bd, rtol=1e-5, atol=1e-3)


def test_query_rate_below_one():
    w = MovingObjectWorkload(WorkloadConfig(n_objects=500, distribution="uniform", seed=3))
    qpos, qid = w.query_batch(rate=0.25)
    assert len(qid) == 125
    sess = KnnSession(ServiceSpec(k=4, th_quad=16, l_max=5, window=32, chunk=256))
    res = sess.process_tick(w.positions(), qpos, qid)
    bi, bd = knn_bruteforce_chunked(w.positions(), qpos, qid, k=4, chunk=256)
    np.testing.assert_allclose(res.nn_dist, bd, rtol=1e-5, atol=1e-3)


def test_workload_speed_bound():
    """Table 1: per-tick displacement <= max_speed (all three generators)."""
    for dist in ("uniform", "gaussian", "network"):
        w = make_workload(300, dist, seed=7)
        p0 = w.positions().copy()
        w.advance()
        p1 = w.positions()
        disp = np.linalg.norm(p1 - p0, axis=1)
        assert disp.max() <= w.cfg.max_speed * 1.5 + 1e-3, (dist, disp.max())


def test_cpu_kdtree_reference():
    import jax.numpy as jnp

    from repro.core import KDTree, knn_bruteforce

    rng = np.random.default_rng(13)
    pts = rng.uniform(0, 1000, (400, 2)).astype(np.float32)
    tree = KDTree(pts, leaf_size=16)
    ids, dist = tree.query_batch(pts[:50], k=5, qid=np.arange(50))
    bi, bd = knn_bruteforce(jnp.asarray(pts), jnp.asarray(pts[:50]), jnp.arange(50, dtype=jnp.int32), 5)
    np.testing.assert_allclose(dist, np.asarray(bd), rtol=1e-5, atol=1e-4)
