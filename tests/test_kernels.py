"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps (interpret=True)."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import (
    MIXED_WIDEN,
    bucket_kselect_op,
    bucket_kselect_ref,
    merge_backend_names,
    get_merge_backend,
    merge_topk_lists_ref,
    mixed_prune_keep,
    pairwise_dist_op,
    pairwise_dist_ref,
    topk_select_op,
    topk_select_ref,
    tree_merge_lists,
)
from repro.core import find_kdist
from repro.kernels.refine import bucket_refine_step


def _data(q, c, seed=0, dtype=np.float32):
    rng = np.random.default_rng(seed)
    qpos = jnp.asarray(rng.uniform(0, 1000, (q, 2)).astype(dtype))
    ppos = jnp.asarray(rng.uniform(0, 1000, (c, 2)).astype(dtype))
    valid = jnp.asarray(rng.random(c) < 0.9)
    return qpos, ppos, valid


@pytest.mark.parametrize("q,c", [(1, 1), (8, 128), (20, 300), (64, 1024), (7, 130)])
def test_pairwise_dist_shapes(q, c):
    qpos, ppos, valid = _data(q, c, seed=q * 1000 + c)
    got = pairwise_dist_op(qpos, ppos, valid)
    want = pairwise_dist_ref(qpos[:, 0], qpos[:, 1], ppos[:, 0], ppos[:, 1], valid)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-6)


@pytest.mark.parametrize("k", [1, 4, 16, 64])
@pytest.mark.parametrize("q,c", [(8, 128), (17, 333)])
def test_bucket_kselect_guarantee(q, c, k):
    qpos, ppos, valid = _data(q, c, seed=k)
    r = np.asarray(bucket_kselect_op(qpos, ppos, valid, k=k))
    ref = np.asarray(
        bucket_kselect_ref(qpos[:, 0], qpos[:, 1], ppos[:, 0], ppos[:, 1], valid,
                           k=k, num_bins=32, iters=4)
    )
    np.testing.assert_allclose(r, ref, rtol=1e-5)
    d2 = np.asarray(pairwise_dist_ref(qpos[:, 0], qpos[:, 1], ppos[:, 0], ppos[:, 1], valid))
    nv = int(np.asarray(valid).sum())
    cnt = (d2 < r[:, None]).sum(1)
    assert (cnt >= min(k, nv)).all()
    if nv >= k:
        # selection is tight: at most a thin shell above k after 4 refinements
        assert cnt.mean() <= k * 1.5 + 2


def _edge_heavy_rows(q=4096, c=64, seed=0):
    """Squared distances on a 1/64 lattice at random per-row scales: many
    values sit on or beside the refinement's bucket edges, where bucketing
    by ``floor((d - lo) / width)`` and testing ``d < edge`` disagree."""
    rng = np.random.default_rng(seed)
    d2 = np.round(rng.uniform(0, 1, (q, c)) * 64) / 64
    return (d2 * rng.uniform(0.5, 2, (q, 1))).astype(np.float32)


@pytest.mark.parametrize("k", [8, 32])
def test_bucket_refinement_keeps_k_below_radius(k):
    """The refinement's guarantee ``count(d < radius) >= k`` holds on every
    row, edge-sitting values included: in the jnp oracle (``find_kdist``)
    and in the kernel-side step, iterated as the fused_bucket SCAN kernel
    iterates it to get its prune radius (a row that broke it lost a true
    neighbour from its merged list)."""
    d2 = _edge_heavy_rows()
    r = np.asarray(find_kdist(jnp.asarray(d2), jnp.ones(d2.shape, bool), k=k))
    assert ((d2 < r[:, None]).sum(1) >= k).all()
    lo = jnp.asarray(d2.min(1, keepdims=True))
    hi = jnp.asarray(d2.max(1, keepdims=True)) * (1 + 1e-6) + 1e-30
    kth = jnp.full_like(lo, k)
    for _ in range(4):
        lo, hi, kth = bucket_refine_step((jnp.asarray(d2),), lo, hi, kth, 32)
    assert ((d2 < np.asarray(hi)).sum(1) >= k).all()


@pytest.mark.parametrize("k", [1, 8, 32])
@pytest.mark.parametrize("q,c", [(8, 64), (30, 257)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_topk_select_sweep(q, c, k, dtype):
    rng = np.random.default_rng(q + c + k)
    d2 = jnp.asarray(rng.uniform(0, 100, (q, c))).astype(dtype).astype(jnp.float32)
    ids = jnp.tile(jnp.arange(c, dtype=jnp.int32)[None], (q, 1))
    got_d, got_i = topk_select_op(d2, ids, k=min(k, c))
    want_d, want_i = topk_select_ref(d2, ids, k=min(k, c))
    np.testing.assert_allclose(np.asarray(got_d), np.asarray(want_d), rtol=1e-6)
    # ids may differ on exact ties; distances must match exactly per rank
    got_vals = np.take_along_axis(np.asarray(d2), np.asarray(got_i), 1)
    want_vals = np.take_along_axis(np.asarray(d2), np.asarray(want_i), 1)
    np.testing.assert_allclose(got_vals, want_vals, rtol=1e-6)


def test_topk_select_with_infs():
    d2 = jnp.asarray([[1.0, jnp.inf, 0.5, jnp.inf]])
    ids = jnp.asarray([[10, 11, 12, 13]], jnp.int32)
    out_d, out_i = topk_select_op(d2, ids, k=3)
    assert list(np.asarray(out_i)[0][:2]) == [12, 10]
    assert int(np.asarray(out_i)[0][2]) == -1  # inf slot -> padded id


@pytest.mark.parametrize("scale", [1.0, 1e3, 22_500.0])
@pytest.mark.parametrize("seed", [0, 7, 91])
def test_mixed_prune_keep_is_conservative(seed, scale):
    """The bf16 widened-radius prefilter NEVER drops a candidate at or
    inside the exact k-th boundary (the bitwise-identity precondition of
    the mixed sweep, DESIGN.md §14) — coincident points, near-boundary
    candidates and kth = inf under-full rows included; and the widening
    really is wider than the accumulated bf16 relative error."""
    assert MIXED_WIDEN > (1 + 2.0 ** -8) ** 5  # margin over 5 roundings
    rng = np.random.default_rng(seed)
    t, w = 16, 256
    qpos = rng.uniform(0, scale, (t, 2)).astype(np.float32)
    cpos = rng.uniform(0, scale, (t, w, 2)).astype(np.float32)
    cpos[:, :7] = qpos[:, None, :]  # coincident candidates (d2 = 0)
    dx = jnp.asarray(cpos[:, :, 0] - qpos[:, None, 0])
    dy = jnp.asarray(cpos[:, :, 1] - qpos[:, None, 1])
    d2 = np.asarray(dx * dx + dy * dy)
    k = 8
    kth = np.sort(d2, axis=1)[:, k - 1].astype(np.float32)
    kth[0] = np.inf  # under-full row: everything must be kept
    keep = np.asarray(mixed_prune_keep(dx, dy, jnp.asarray(kth)))
    inside = d2 <= kth[:, None]
    assert (keep | ~inside).all(), "prefilter dropped an in-boundary candidate"
    assert keep[0].all()  # kth = inf keeps the whole window
    # and it really prunes: far-away candidates don't survive
    assert (~keep[1:] & (d2[1:] > 2.0 * kth[1:, None])).sum() > 0 or (
        np.isinf(kth[1:]).all()
    )


def _ascending_lists(q, width, k, seed, lo=0.0, hi=100.0, id_base=0):
    """Random ascending +inf/-1-padded (dist, id) lists, ragged fill per row."""
    rng = np.random.default_rng(seed)
    n_real = rng.integers(0, width + 1, size=q)
    d = np.full((q, width), np.inf, np.float32)
    i = np.full((q, width), -1, np.int32)
    for r in range(q):
        vals = np.sort(rng.uniform(lo, hi, n_real[r])).astype(np.float32)
        d[r, : n_real[r]] = vals
        i[r, : n_real[r]] = id_base + rng.choice(10_000, n_real[r], replace=False)
    return jnp.asarray(d), jnp.asarray(i)


@pytest.mark.parametrize("backend", merge_backend_names())
@pytest.mark.parametrize("q,ka,kb,k", [(1, 4, 4, 4), (9, 8, 3, 8), (32, 16, 16, 8)])
def test_merge_topk_lists_backends(backend, q, ka, kb, k):
    """Every merge backend == the jnp oracle: distances exact per rank, ids
    equal off ties, +inf rows padded with -1 (DESIGN.md §10 contract)."""
    d_a, i_a = _ascending_lists(q, ka, k, seed=q + ka)
    d_b, i_b = _ascending_lists(q, kb, k, seed=q + kb + 1, id_base=20_000)
    got_d, got_i = get_merge_backend(backend)(d_a, i_a, d_b, i_b, k)
    want_d, want_i = merge_topk_lists_ref(d_a, i_a, d_b, i_b, k=k)
    np.testing.assert_allclose(np.asarray(got_d), np.asarray(want_d), rtol=1e-6)
    got_i, want_i = np.asarray(got_i), np.asarray(want_i)
    ties = np.asarray(want_d)[:, :, None] == np.asarray(want_d)[:, None, :]
    unique = ties.sum(axis=2)[np.isfinite(np.asarray(want_d))] == 1
    np.testing.assert_array_equal(
        got_i[np.isfinite(np.asarray(got_d))][unique],
        want_i[np.isfinite(np.asarray(want_d))][unique],
    )
    assert (got_i[np.isinf(np.asarray(got_d))] == -1).all()


@pytest.mark.parametrize("backend", merge_backend_names())
def test_merge_composes_partitioned_knn(backend):
    """The object-sharding composition law the primitive exists for:
    ``knn(P_a ∪ P_b) = merge(knn(P_a), knn(P_b))`` per query row."""
    rng = np.random.default_rng(23)
    pts = rng.uniform(0, 1000, (200, 2)).astype(np.float32)
    qpos = rng.uniform(0, 1000, (24, 2)).astype(np.float32)
    k = 6
    d2 = np.square(qpos[:, None, :] - pts[None, :, :]).sum(-1)
    ids = np.tile(np.arange(200, dtype=np.int32), (24, 1))
    half = 100
    da, ia = topk_select_ref(jnp.asarray(d2[:, :half]), jnp.asarray(ids[:, :half]), k=k)
    db, ib = topk_select_ref(jnp.asarray(d2[:, half:]), jnp.asarray(ids[:, half:]), k=k)
    full_d, full_i = topk_select_ref(jnp.asarray(d2), jnp.asarray(ids), k=k)
    got_d, got_i = get_merge_backend(backend)(da, ia, db, ib, k)
    np.testing.assert_allclose(np.asarray(got_d), np.asarray(full_d), rtol=1e-6)
    np.testing.assert_array_equal(np.asarray(got_i), np.asarray(full_i))


@pytest.mark.parametrize("backend", merge_backend_names())
@pytest.mark.parametrize("r", [2, 3, 8])
def test_tree_merge_composes_r_way_partition(backend, r):
    """The sharded generalization: knn over an R-way object partition equals
    an R-way ``tree_merge_lists`` reduction of the per-partition lists —
    including the uneven final shard (its list padded with (inf, -1) rows
    when the slice holds fewer than k candidates) and massed distance ties
    (duplicated columns), bit-for-bit under the canonical lexicographic
    ``(d2, id)`` contract of DESIGN.md §12."""
    rng = np.random.default_rng(100 + r)
    n, q, k = 89, 24, 6  # 89: uneven tail slice for every r; tail < cap
    qpos = rng.uniform(0, 1000, (q, 2)).astype(np.float32)
    pts = rng.uniform(0, 1000, (45, 2)).astype(np.float32)
    pts = np.tile(pts, (2, 1))[:n]  # duplicated positions -> distance ties
    d2 = np.square(qpos[:, None, :] - pts[None, :, :]).sum(-1).astype(np.float32)
    ids = np.tile(rng.permutation(n).astype(np.int32), (q, 1))
    full_d, full_i = topk_select_ref(jnp.asarray(d2), jnp.asarray(ids), k=k)
    cap = -(-n // r)
    parts_d, parts_i = [], []
    for s in range(r):
        sl = slice(s * cap, min((s + 1) * cap, n))
        pd, pi = topk_select_ref(
            jnp.asarray(d2[:, sl]), jnp.asarray(ids[:, sl]), k=k)
        pad = k - pd.shape[1]
        if pad > 0:  # final shard narrower than k: inf/-1 padded list
            pd = jnp.concatenate(
                [pd, jnp.full((q, pad), jnp.inf, jnp.float32)], axis=1)
            pi = jnp.concatenate(
                [pi, jnp.full((q, pad), -1, jnp.int32)], axis=1)
        parts_d.append(pd)
        parts_i.append(pi)
    got_d, got_i = tree_merge_lists(
        jnp.stack(parts_d), jnp.stack(parts_i), k=k, merge=backend)
    np.testing.assert_array_equal(np.asarray(got_d), np.asarray(full_d))
    np.testing.assert_array_equal(np.asarray(got_i), np.asarray(full_i))


@pytest.mark.parametrize("n,window,q", [
    (1000, 256, 200),  # n not a multiple of 128, Q not one of the lane tile
    (100, 256, 13),  # n < W
    (1024, 64, 128),
    (300, 128, 37),
])
def test_window_fetch_rows_cover_each_window(n, window, q):
    """Each scanning lane's fetched slots, masked as the sweep masks them
    (``start <= slot < min(start + W, e)``), are ``table[start : min(start +
    W, e)]`` in order; a lane that does not scan reads id -1 everywhere."""
    from repro.kernels.window_fetch import row_tables, window_fetch

    rng = np.random.default_rng(n + window + q)
    pos = rng.uniform(0, 1000, (n, 2)).astype(np.float32)
    ids = rng.permutation(n).astype(np.int32)
    edges = [s for s in (0, 128, 127, n - 1) if s < n]
    start = np.concatenate([edges, rng.integers(0, n, q - len(edges))])
    start = start.astype(np.int32)
    stop = rng.integers(start + 1, n + 1).astype(np.int32)  # the leaf's end
    scanning = rng.random(q) < 0.7
    scanning[: len(edges)] = True
    cx, cy, cids, slot = map(np.asarray, window_fetch(
        row_tables(jnp.asarray(pos), jnp.asarray(ids), window),
        jnp.asarray(start), jnp.asarray(scanning), window=window))
    for lane in range(q):
        if not scanning[lane]:
            assert (cids[lane] == -1).all()
            assert np.isfinite(cx[lane]).all() and np.isfinite(cy[lane]).all()
            continue
        s, e = start[lane], min(start[lane] + window, stop[lane])
        keep = (slot[lane] >= s) & (slot[lane] < e)
        np.testing.assert_array_equal(cids[lane][keep], ids[s:e])
        np.testing.assert_array_equal(cx[lane][keep], pos[s:e, 0])
        np.testing.assert_array_equal(cy[lane][keep], pos[s:e, 1])
