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


# ------------------------------------------ NAV step: one navigation word

def _per_level_nav_step(index, qx, qy, kth2, cursor, run, dir_r):
    """The NAV step as it read the index before the navigation word: the
    probed cell's ``leaf_level`` and its leaf's ``starts`` (twice), then one
    count-pyramid gather per level in a rolled loop.  Returns the step's
    ``(found, s, e, new_cursor, exhausted)`` and the leaf's
    ``(leaf_key, span0)``."""
    import jax

    from repro.core import morton

    l_max = index.l_max
    n_fine = 4**l_max
    one = jnp.int32(1)

    exhausted = jnp.where(dir_r, cursor >= n_fine, cursor <= 0)
    cprobe = jnp.clip(jnp.where(dir_r, cursor, cursor - 1), 0, n_fine - 1)

    lvl = index.leaf_level[cprobe]
    a0 = (l_max - lvl).astype(jnp.int32)
    span0 = jnp.left_shift(one, 2 * a0)
    leaf_key = jnp.where(dir_r, cprobe, (cprobe >> (2 * a0)) << (2 * a0))
    s = index.starts[jnp.clip(leaf_key, 0, n_fine - 1)]
    e = index.starts[jnp.clip(leaf_key + span0, 0, n_fine)]
    cnt = e - s
    leaf_d2 = morton.point_to_block_dist2(
        qx, qy, leaf_key, a0, index.origin, index.side, l_max
    )
    found = run & ~exhausted & (cnt > 0) & (leaf_d2 <= kth2)

    pyr_n = index.pyramid.shape[0]

    def try_level(a, best_a):
        ai = jnp.int32(a)
        blk = jnp.left_shift(one, 2 * ai)
        code = jnp.where(dir_r, cursor, cursor - blk)
        in_dom = jnp.where(dir_r, cursor + blk <= n_fine, cursor - blk >= 0)
        pidx = jnp.where(dir_r, cursor >> (2 * ai), (cursor >> (2 * ai)) - 1)
        lvl_off = (jnp.left_shift(one, 2 * (l_max - ai)) - 1) // 3
        empty = index.pyramid[jnp.clip(lvl_off + pidx, 0, pyr_n - 1)] == 0
        far = (
            morton.point_to_block_dist2(
                qx, qy, code, ai, index.origin, index.side, l_max
            )
            > kth2
        )
        aligned = (cursor & (blk - 1)) == 0
        ok = aligned & in_dom & (ai >= a0) & (empty | far)
        return jnp.where(ok & (ai > best_a), ai, best_a)

    best_a = jax.lax.fori_loop(1, l_max + 1, try_level, a0)
    jump = jnp.left_shift(one, 2 * best_a)

    step = jnp.where(found, span0, jump)
    new_cursor = jnp.where(
        run & ~exhausted, jnp.where(dir_r, cursor + step, cursor - step), cursor
    )
    return (found, s, e, new_cursor, run & exhausted), (leaf_key, span0)


def _oracle_nav_step(index, table, qx, qy, kth2, cursor, run, dir_r):
    """:func:`_per_level_nav_step` behind the sweep's step signature."""
    (found, _, _, new_cursor, exhausted), (leaf_key, span0) = (
        _per_level_nav_step(index, qx, qy, kth2, cursor, run, dir_r))
    return found, leaf_key, span0, new_cursor, exhausted


def _nav_world(world, n=3000, seed=11):
    """Points in [0, 1000)^2: uniform, five Gaussian hotspots, or a dense
    corner cluster with a sparse far corner."""
    rng = np.random.default_rng(seed)
    if world == "uniform":
        pts = rng.uniform(0, 1000, (n, 2))
    elif world == "gaussian":
        centers = rng.uniform(100, 900, (5, 2))
        pts = centers[rng.integers(0, 5, n)] + rng.normal(0, 1000 / 64, (n, 2))
    else:
        pts = np.concatenate([rng.uniform(0, 10, (n - n // 20, 2)),
                              rng.uniform(900, 1000, (n // 20, 2))])
    return np.clip(pts, 0, 999.9).astype(np.float32)


def _nav_index(world, l_max):
    return build_index(jnp.asarray(_nav_world(world)), jnp.zeros(2), 1000.0,
                       l_max=l_max, th_quad=4)


@pytest.mark.parametrize("kth2_kind", ["zero", "exact", "random", "inf"])
@pytest.mark.parametrize("world", ["uniform", "gaussian", "corner"])
@pytest.mark.parametrize("l_max", [3, 5, 8])
def test_nav_step_matches_per_level_gathers(l_max, world, kth2_kind):
    """The packed-word step returns bitwise what the per-level step did,
    for every cursor of the domain in both directions: found, the found
    leaf's object interval, the new cursor and exhaustion.  ``exact``
    puts each lane's k-th distance exactly on one block it probes (its leaf
    or the block of a random level), where `<=` and `>` decide."""
    import jax

    from repro.core import morton, pipeline

    index = _nav_index(world, l_max)
    n_fine = 4**l_max
    rng = np.random.default_rng(l_max)
    c = np.arange(n_fine + 1, dtype=np.int32)
    cursor = jnp.asarray(np.concatenate([c, c]))
    dir_r = jnp.asarray(np.repeat([False, True], n_fine + 1))
    lanes = cursor.shape[0]
    qx, qy = (jnp.asarray(rng.uniform(0, 1000, lanes).astype(np.float32))
              for _ in range(2))
    run = jnp.asarray(rng.random(lanes) < 0.9)

    oracle = jax.jit(_per_level_nav_step)
    if kth2_kind == "zero":
        kth2 = jnp.zeros(lanes, jnp.float32)
    elif kth2_kind == "inf":
        kth2 = jnp.full(lanes, jnp.inf, jnp.float32)
    elif kth2_kind == "random":
        kth2 = jnp.asarray(10 ** rng.uniform(-1, 6, lanes), jnp.float32)
    else:
        _, (leaf_key, span0) = oracle(index, qx, qy, jnp.zeros(lanes), cursor,
                                      run, dir_r)
        a0 = jnp.log2(span0.astype(jnp.float32)).astype(jnp.int32) // 2
        a = jnp.asarray(rng.integers(0, l_max + 1, lanes), jnp.int32)
        code = jnp.where(a == 0, leaf_key,
                         jnp.where(dir_r, cursor, cursor - (1 << (2 * a))))
        kth2 = morton.point_to_block_dist2(
            qx, qy, code, jnp.where(a == 0, a0, a), index.origin, index.side,
            l_max)

    want, _ = oracle(index, qx, qy, kth2, cursor, run, dir_r)
    found, leaf_key, span0, new_cursor, exhausted = jax.jit(pipeline._nav_step)(
        index, pipeline.nav_table(index), qx, qy, kth2, cursor, run, dir_r)
    s, e = pipeline._leaf_offsets(index.starts, leaf_key, span0)
    for name, g, w in zip(("found", "s", "e", "new_cursor", "exhausted"),
                          (found, s, e, new_cursor, exhausted), want):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w),
                                      err_msg=name)
    assert bool(found.any()) == (kth2_kind != "zero" or bool(want[0].any()))


@pytest.mark.parametrize("world", ["uniform", "gaussian", "corner"])
def test_sweep_matches_per_level_nav_sweep(monkeypatch, world):
    """The sweep on the navigation word answers and counts bitwise as the
    sweep driven by the per-level step."""
    import jax

    from repro.core import pipeline

    pts = _nav_world(world, n=1200)
    index = build_index(jnp.asarray(pts), jnp.zeros(2), 1000.0, l_max=6,
                        th_quad=8)
    qid = jnp.arange(len(pts), dtype=jnp.int32)
    kw = dict(k=8, window=32)
    got = knn_query_batch(index, jnp.asarray(pts), qid, **kw)
    jax.clear_caches()
    monkeypatch.setattr(pipeline, "_nav_step", _oracle_nav_step)
    want = knn_query_batch(index, jnp.asarray(pts), qid, **kw)
    monkeypatch.undo()
    jax.clear_caches()
    np.testing.assert_array_equal(np.asarray(got[0]), np.asarray(want[0]))
    np.testing.assert_array_equal(np.asarray(got[1]), np.asarray(want[1]))
    for field in ("iterations", "candidates", "leaves_visited",
                  "windows_fetched", "nav_lane_steps"):
        assert int(getattr(got[2], field)) == int(getattr(want[2], field)), (
            field)


def _gathers(jaxpr, scope=None, per_run=False, _stack=""):
    """Gather ops in ``jaxpr`` and its sub-programs, only those under the
    named scope ``scope`` where one is given.  ``per_run`` counts a
    ``scan`` body once per iteration (the gathers one run executes)."""
    n = 0
    for eqn in jaxpr.eqns:
        stack = f"{_stack}/{eqn.source_info.name_stack}"
        if eqn.primitive.name == "gather" and (scope is None or scope in stack):
            n += 1
        times = eqn.params["length"] if (
            per_run and eqn.primitive.name == "scan") else 1
        for v in eqn.params.values():
            for sub in v if isinstance(v, (tuple, list)) else (v,):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    n += times * _gathers(sub, scope, per_run, stack)
    return n


@pytest.mark.parametrize("l_max", [3, 8])
def test_nav_step_reads_one_word(l_max):
    """One NAV step runs one gather, the navigation word: 3 + l_max before
    (leaf_level, starts twice, a pyramid read per level); one trip's
    ``knn.nav`` holds 3, the word and the found leaf's two offsets."""
    import jax

    from repro.core import pipeline
    from repro.core.executor import resolve_executor

    index = _nav_index("gaussian", l_max)
    lanes = 64
    f32 = jnp.zeros(lanes, jnp.float32)
    i32 = jnp.zeros(lanes, jnp.int32)
    b = jnp.zeros(lanes, bool)
    table = pipeline.nav_table(index)
    step = jax.make_jaxpr(pipeline._nav_step)(
        index, table, f32, f32, f32, i32, b, b)
    old = jax.make_jaxpr(_per_level_nav_step)(index, f32, f32, f32, i32, b, b)
    assert _gathers(step.jaxpr, per_run=True) == 1
    assert _gathers(old.jaxpr, per_run=True) == 3 + l_max

    pts = jnp.asarray(_nav_world("gaussian", n=lanes))
    sweep = jax.make_jaxpr(
        lambda idx, q, qi: pipeline._knn_sorted_impl(
            idx, q, qi, 4, 32, pipeline.default_max_nav(l_max), 1000,
            resolve_executor(None), pipeline.window_tables(idx, 32), table)
    )(index, pts, jnp.arange(lanes, dtype=jnp.int32))
    assert _gathers(sweep.jaxpr, scope="knn.nav") == 3


def test_nav_lane_steps_match_host_recount(monkeypatch):
    """``nav_lane_steps`` is the number of lane steps with ``run`` set,
    recounted on the host from every step's lanes."""
    import jax

    from repro.core import pipeline

    counts = []
    real_step = pipeline._nav_step

    def counting_step(index, table, qx, qy, kth2, cursor, run, dir_r):
        jax.debug.callback(lambda r: counts.append(int(np.sum(r))), run)
        return real_step(index, table, qx, qy, kth2, cursor, run, dir_r)

    pts = _nav_world("corner", n=500)
    index = build_index(jnp.asarray(pts), jnp.zeros(2), 1000.0, l_max=5,
                        th_quad=8)
    jax.clear_caches()
    monkeypatch.setattr(pipeline, "_nav_step", counting_step)
    _, _, stats = knn_query_batch(index, jnp.asarray(pts),
                                  jnp.arange(500, dtype=jnp.int32), k=6,
                                  window=32)
    jax.effects_barrier()
    monkeypatch.undo()
    jax.clear_caches()
    assert len(counts) == int(stats.iterations) * pipeline.default_max_nav(5)
    assert int(stats.nav_lane_steps) == sum(counts) > 0


def test_nav_lane_steps_identical_across_plans():
    """Where the plans' other stats agree (query shards cut at chunk
    boundaries), ``nav_lane_steps`` agrees too."""
    import jax

    ndev = jax.device_count()
    w = make_workload(700, "gaussian", seed=5)
    pts = w.positions()
    qpos, qid = w.query_batch()
    idx = build_index(jnp.asarray(pts), jnp.zeros(2), 22_500.0, l_max=6,
                      th_quad=24)
    runs = {
        plan: knn_query_batch_chunked(idx, qpos, qid, k=8, window=32,
                                      chunk=64, plan=plan, num_devices=mesh)[2]
        for plan, mesh in (("single", None), ("sharded", ndev),
                           ("hybrid", (ndev, 1)))
    }
    want = runs["single"]
    assert want.nav_lane_steps > 0
    for plan, got in runs.items():
        assert got == want, plan
