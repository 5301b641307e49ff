"""Backend parity: every registered SCAN backend == the sequential CPU oracle.

The executor contract (DESIGN.md §6): all backends return identical neighbor
sets up to k-th-distance ties, on easy *and* adversarial inputs — skewed
(Gaussian-cluster) distributions, duplicate positions (distance ties), and
``n_objects < k`` padding rows.  The oracle is ``core/cpu_ref.py``'s kd-tree
(the paper's K-NN_CPU competitor), deliberately a different algorithm family
from both the pipeline and the brute-force jnp baseline.

Also pins the serving-layer contract introduced by the device-resident tick
refactor: ``KnnSession.process_tick`` never routes through the host-side
chunk loop.
"""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import KnnSession, ServiceSpec
from repro.core import (
    KDTree,
    QueryExecutor,
    available_backends,
    build_index,
    knn_query_batch,
    knn_query_batch_chunked,
)
from repro.data import make_workload
from repro.testing import run_ticks

BACKENDS = available_backends()


def _assert_matches_kdtree(pts, qpos, qid, k, *, backend, l_max=6, th=24,
                           window=32, side=22_500.0, chunk=None):
    idx = build_index(jnp.asarray(pts), jnp.zeros(2), side, l_max=l_max, th_quad=th)
    if chunk is None:
        ii, dd, _ = knn_query_batch(
            idx, jnp.asarray(qpos), jnp.asarray(qid), k=k, window=window,
            backend=backend,
        )
        ii, dd = np.asarray(ii), np.asarray(dd)
    else:
        ii, dd, _ = knn_query_batch_chunked(
            idx, qpos, qid, k=k, window=window, chunk=chunk, backend=backend
        )
    tree = KDTree(pts)
    ri, rd = tree.query_batch(qpos, k, qid=qid)
    # distances must agree exactly as multisets per row (ties make ids ambiguous)
    np.testing.assert_allclose(dd, rd, rtol=1e-5, atol=1e-3)
    # where the distance is strictly below the k-th, the id sets must agree
    for r in range(len(qpos)):
        kth = rd[r, k - 1]
        want = set(ri[r][rd[r] < kth * (1 - 1e-6)]) - {-1}
        got = set(ii[r][dd[r] < kth * (1 - 1e-6)]) - {-1}
        assert want == got, (r, want, got)
    return ii, dd


@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("hotspots", [2, 25])
def test_backend_parity_gaussian_skew(backend, hotspots):
    """Skewed hotspot clusters: deep tree regions + long scan intervals."""
    w = make_workload(1200, "gaussian", seed=5, hotspots=hotspots)
    pts = w.positions()
    qpos, qid = w.query_batch()
    _assert_matches_kdtree(pts, qpos, qid, 8, backend=backend)


@pytest.mark.parametrize("backend", BACKENDS)
def test_backend_parity_chunked_driver(backend):
    """The lax.map chunked driver agrees with the oracle across chunks."""
    w = make_workload(900, "gaussian", seed=9, hotspots=3)
    pts = w.positions()
    qpos, qid = w.query_batch()
    _assert_matches_kdtree(pts, qpos, qid, 8, backend=backend, chunk=256)


@pytest.mark.parametrize("backend", BACKENDS)
def test_backend_parity_duplicate_positions(backend):
    """Stacked duplicates => massive distance ties (the bucket kernel's worst
    case: k-th element on a histogram bucket edge)."""
    rng = np.random.default_rng(17)
    base = rng.uniform(0, 22_500, (80, 2)).astype(np.float32)
    pts = np.repeat(base, 6, axis=0)  # every position 6 times
    rng.shuffle(pts)
    qid = np.arange(len(pts), dtype=np.int32)
    _assert_matches_kdtree(pts, pts, qid, 10, backend=backend, th=8)


@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("n", [1, 3, 7])
def test_backend_parity_fewer_objects_than_k(backend, n):
    """n_objects < k: rows must pad with (-1, inf) identically everywhere."""
    rng = np.random.default_rng(n)
    pts = rng.uniform(0, 22_500, (n, 2)).astype(np.float32)
    qid = np.arange(n, dtype=np.int32)
    k = 8
    idx = build_index(jnp.asarray(pts), jnp.zeros(2), 22_500.0, l_max=4, th_quad=4)
    ii, dd, _ = knn_query_batch(
        idx, jnp.asarray(pts), jnp.asarray(qid), k=k, window=16, backend=backend
    )
    ii, dd = np.asarray(ii), np.asarray(dd)
    # each query sees the other n-1 objects, then padding
    assert np.isfinite(dd[:, : n - 1]).all()
    assert np.isinf(dd[:, n - 1 :]).all()
    assert (ii[:, n - 1 :] == -1).all()


def test_unknown_backend_rejected():
    with pytest.raises(ValueError, match="unknown scan backend"):
        QueryExecutor(backend="nope")


def test_engine_never_uses_host_chunk_loop(monkeypatch):
    """The acceptance contract of the device-resident tick refactor: one fused
    jitted call per tick — the host-side chunk loop must be unreachable from
    ``process_tick``."""
    import repro.core.pipeline as pipeline
    import repro.core.ticks as ticks

    def boom(*a, **k):  # pragma: no cover - would fail the test if reached
        raise AssertionError("host chunk loop used inside process_tick")

    monkeypatch.setattr(pipeline, "knn_query_batch_chunked", boom)
    monkeypatch.setattr(pipeline, "knn_query_batch", boom)

    sess = KnnSession(ServiceSpec(k=4, th_quad=16, l_max=5, window=32, chunk=256))
    w = make_workload(600, "uniform", seed=1)
    results = run_ticks(sess, w, ticks=2)
    assert len(results) == 2
    assert results[0].nn_dist.shape == (600, 4)
    assert np.isfinite(results[1].nn_dist).all()


@pytest.mark.parametrize("backend", BACKENDS)
def test_engine_backend_config_parity(backend):
    """ServiceSpec.backend threads through to identical tick results."""
    w = make_workload(700, "gaussian", seed=2, hotspots=4)
    pts = w.positions()
    qpos, qid = w.query_batch()
    sess = KnnSession(
        ServiceSpec(k=6, th_quad=16, l_max=5, window=32, chunk=256, backend=backend)
    )
    res = sess.process_tick(pts, qpos, qid)
    tree = KDTree(pts)
    _, rd = tree.query_batch(qpos, 6, qid=qid)
    np.testing.assert_allclose(res.nn_dist, rd, rtol=1e-5, atol=1e-3)
