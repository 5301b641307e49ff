"""The iterative k-NN pipeline vs the brute-force oracle (Def. 1 semantics)."""
import jax.numpy as jnp
import numpy as np
import pytest
try:
    from hypothesis import given, settings, strategies as st
except ModuleNotFoundError:  # offline container: deterministic fallback shim
    from repro.testing import given, settings, strategies as st

from repro.core import build_index, knn_bruteforce, knn_query_batch, knn_query_batch_chunked
from repro.data import make_workload


def _check(pts, qpos, qid, k, l_max=5, th=16, window=32, side=1000.0):
    idx = build_index(jnp.asarray(pts), jnp.zeros(2), side, l_max=l_max, th_quad=th)
    ii, dd, stats = knn_query_batch(
        idx, jnp.asarray(qpos), None if qid is None else jnp.asarray(qid), k=k, window=window
    )
    bi, bd = knn_bruteforce(
        jnp.asarray(pts),
        jnp.asarray(qpos),
        jnp.full((len(qpos),), -2, jnp.int32) if qid is None else jnp.asarray(qid),
        k,
    )
    np.testing.assert_allclose(np.asarray(dd), np.asarray(bd), rtol=1e-5, atol=1e-3)
    return ii, dd, stats


@pytest.mark.parametrize("dist", ["uniform", "gaussian", "network"])
@pytest.mark.parametrize("k", [1, 8, 33])
def test_vs_bruteforce_distributions(dist, k):
    w = make_workload(1500, dist, seed=2)
    pts = w.positions()
    qpos, qid = w.query_batch()
    idx = build_index(jnp.asarray(pts), jnp.zeros(2), 22500.0, l_max=6, th_quad=24)
    ii, dd, _ = knn_query_batch(idx, jnp.asarray(qpos), jnp.asarray(qid), k=k, window=32)
    bi, bd = knn_bruteforce(jnp.asarray(pts), jnp.asarray(qpos), jnp.asarray(qid), k)
    np.testing.assert_allclose(np.asarray(dd), np.asarray(bd), rtol=1e-5, atol=1e-3)


@pytest.mark.parametrize("th_quad", [4, 64, 4096])
def test_tree_height_extremes(th_quad):
    """th_quad sweep: deep tree (many leaf visits) and flat tree (one big leaf)."""
    rng = np.random.default_rng(3)
    pts = rng.uniform(0, 1000, (800, 2)).astype(np.float32)
    _check(pts, pts[:200], np.arange(200, dtype=np.int32), 16, th=th_quad)


def test_k_exceeds_population():
    """k > |P|-1: lists padded with (-1, inf), all real objects present."""
    rng = np.random.default_rng(4)
    pts = rng.uniform(0, 1000, (10, 2)).astype(np.float32)
    idx = build_index(jnp.asarray(pts), jnp.zeros(2), 1000.0, l_max=4, th_quad=4)
    ii, dd, _ = knn_query_batch(idx, jnp.asarray(pts), jnp.arange(10, dtype=jnp.int32), k=16)
    ii = np.asarray(ii)
    dd = np.asarray(dd)
    for row in range(10):
        real = ii[row][ii[row] >= 0]
        assert len(real) == 9  # everything except self
        assert np.isinf(dd[row][len(real):]).all()


def test_external_queries_and_self_exclusion():
    rng = np.random.default_rng(5)
    pts = rng.uniform(0, 1000, (300, 2)).astype(np.float32)
    # external queries (no issuing object): nearest can be distance 0
    ii, dd, _ = _check(pts, pts[:50], None, 4)
    assert (np.asarray(dd)[:, 0] == 0).all()
    # object queries: self excluded -> nearest distance > 0 (points distinct whp)
    ii2, dd2, _ = _check(pts, pts[:50], np.arange(50, dtype=np.int32), 4)
    assert (np.asarray(dd2)[:, 0] > 0).all()


def test_duplicate_points():
    pts = np.ones((50, 2), np.float32) * 500.0
    _check(pts, pts[:10], np.arange(10, dtype=np.int32), 8)


def test_skewed_cluster_in_corner():
    rng = np.random.default_rng(6)
    a = rng.uniform(0, 10, (400, 2))
    b = rng.uniform(900, 1000, (20, 2))
    pts = np.concatenate([a, b]).astype(np.float32)
    q = np.concatenate([a[:30], b[:10]]).astype(np.float32)
    _check(pts, q, None, 12, th=8)


@settings(max_examples=15, deadline=None)
@given(
    st.lists(st.tuples(st.floats(0, 999.9), st.floats(0, 999.9)), min_size=3, max_size=200),
    st.integers(1, 12),
    st.integers(2, 5),
    st.integers(2, 24),
)
def test_property_random_sets(points, k, l_max, th):
    """Any point set, any k/tree shape: pipeline == brute force (dist multiset)."""
    pts = np.asarray(points, np.float32)
    _check(pts, pts, np.arange(len(pts), dtype=np.int32), k, l_max=l_max, th=th, window=16)


def test_chunked_driver_matches():
    rng = np.random.default_rng(7)
    pts = rng.uniform(0, 1000, (700, 2)).astype(np.float32)
    idx = build_index(jnp.asarray(pts), jnp.zeros(2), 1000.0, l_max=5, th_quad=16)
    qid = np.arange(700, dtype=np.int32)
    ii_a, dd_a, _ = knn_query_batch(idx, jnp.asarray(pts), jnp.asarray(qid), k=8)
    ii_b, dd_b, _ = knn_query_batch_chunked(idx, pts, qid, k=8, chunk=256)
    np.testing.assert_allclose(np.asarray(dd_a), dd_b, rtol=1e-6)


def _element_gather_fetch(tables, start, scanning, *, window):
    """The sweep's window as the element gather read it: W slots from
    ``start``, one element each, slot j holding object ``start + j``."""
    xt, yt, it = (t.reshape(-1) for t in tables)
    idx = start[:, None] + jnp.arange(window, dtype=jnp.int32)
    return xt[idx], yt[idx], it[idx], idx


@pytest.mark.parametrize("plan", ["single", "hybrid"])
@pytest.mark.parametrize("window,chunk", [(64, 128), (128, 512), (256, 256)])
def test_window_fetch_sweep_matches_element_gather(monkeypatch, plan, window,
                                                   chunk):
    """The row fetch leaves the sweep bitwise as the element gather had it:
    the same candidate set per lane and trip, so the same lists, distances,
    candidates and trips; ``hybrid`` on a (1, 1) mesh runs the object-tail
    padding path.  ``windows_fetched`` counts at most one window per lane
    and trip."""
    import jax

    from repro.core import pipeline

    w = make_workload(1500, "gaussian", seed=4, hotspots=5)
    pts = w.positions()
    qpos, qid = w.query_batch()
    idx = build_index(jnp.asarray(pts), jnp.zeros(2), 22_500.0, l_max=6,
                      th_quad=24)
    kw = dict(k=8, window=window, chunk=chunk, plan=plan,
              num_devices=(1, 1) if plan == "hybrid" else None)
    got = knn_query_batch_chunked(idx, qpos, qid, **kw)
    jax.clear_caches()
    monkeypatch.setattr(pipeline, "window_fetch", _element_gather_fetch)
    want = knn_query_batch_chunked(idx, qpos, qid, **kw)
    monkeypatch.undo()
    jax.clear_caches()
    np.testing.assert_array_equal(got[0], want[0])
    np.testing.assert_array_equal(got[1], want[1])
    for field in ("iterations", "candidates", "leaves_visited"):
        assert getattr(got[2], field) == getattr(want[2], field), field
    assert 0 < got[2].windows_fetched <= got[2].iterations * chunk


def test_windows_fetched_counts_every_lane_when_all_scan():
    """One leaf holds every object: each lane scans it on every trip, so
    every lane fetches a window on every trip."""
    rng = np.random.default_rng(3)
    pts = rng.uniform(0, 1000, (600, 2)).astype(np.float32)
    idx = build_index(jnp.asarray(pts), jnp.zeros(2), 1000.0, l_max=3,
                      th_quad=1000)
    qid = np.arange(600, dtype=np.int32)
    _, _, stats = knn_query_batch_chunked(idx, pts, qid, k=4, window=128,
                                          chunk=256)
    assert stats.iterations == 3 * -(-600 // 128)  # three chunks, 5 trips each
    assert stats.windows_fetched == stats.iterations * 256
