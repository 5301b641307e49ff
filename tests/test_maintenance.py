"""Incremental index maintenance (DESIGN.md §15): splice, pyramid, session.

Three layers of pinning, bottom-up:

* kernel — the delta-splice rank merge (``repro.kernels.delta_splice``)
  against a host-side reference merge: stability on cross-run code ties,
  sentinel discipline, permutation property; and the sparse gather plan
  (the production path — Δ-sized scatters only) bitwise against the dense
  scatter formulation;
* core — ``reindex_objects_delta`` bitwise against ``reindex_objects`` for
  delta sizes from 1 row to 100% churn (coincident points, same-cell moves,
  no-op moves, sentinel padding included), and ``pyramid_delta`` bitwise
  against a from-scratch recount;
* session — the scheduling policy: dirty-flag "skip" on clean ticks, the
  churn-budget deferral to a full refresh, snapshot ingest forcing a full
  refresh, and ``TickResult.maintenance`` recording what actually ran.

The cross-plan lockstep property (incremental ≡ rebuild, every tick, across
the plan × partitioner grid on however many devices exist) lives in
tests/test_properties.py.
"""
import dataclasses

import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import KnnSession, ServiceSpec
from repro.core import (
    EngineConfig,
    MAINTENANCE_MODES,
    build_index,
    knn_bruteforce,
    pyramid_delta,
    rebuild_zmap,
    reindex_objects,
    reindex_objects_delta,
    starts_from_pyramid,
)
from repro.core.quadtree import _count_pyramid
from repro.kernels import (
    gather_splice,
    merge_ranks,
    searchsorted_pairs,
    sparse_splice_plan,
    splice_payload,
)

SIDE = 1000.0


def _index(pts, l_max=5, th=8):
    return build_index(jnp.asarray(pts), jnp.zeros(2), SIDE, l_max=l_max, th_quad=th)


def _assert_index_equal(a, b, fields=("pos", "ids", "codes", "starts",
                                      "pyramid", "leaf_level")):
    for f in fields:
        np.testing.assert_array_equal(
            np.asarray(getattr(a, f)), np.asarray(getattr(b, f)), err_msg=f
        )


# --------------------------------------------------------------------- kernel
def _ref_merge_positions(ca, ia, cb, ib):
    """Host reference: positions of each run element in the stable merge."""
    tagged = [(c, i, 0, j) for j, (c, i) in enumerate(zip(ca, ia))] + [
        (c, i, 1, j) for j, (c, i) in enumerate(zip(cb, ib))
    ]
    tagged.sort(key=lambda t: (t[0], t[1], t[2]))  # A before B on full ties
    pa = np.empty(len(ca), np.int32)
    pb = np.empty(len(cb), np.int32)
    for pos, (_, _, run, j) in enumerate(tagged):
        (pa if run == 0 else pb)[j] = pos
    return pa, pb


@pytest.mark.parametrize("seed", [0, 1, 2])
@pytest.mark.parametrize("na,nb", [(17, 5), (64, 64), (1, 33), (40, 1)])
def test_merge_ranks_matches_reference(seed, na, nb):
    """Rank merge == the host-side stable merge, ties and all.

    Codes are drawn from a tiny alphabet so cross-run code collisions are
    the common case, and ids are globally unique (the quadtree's contract) —
    the (code, id) pairs decide every tie.
    """
    rng = np.random.default_rng(seed)
    ids = rng.permutation(na + nb).astype(np.int32)
    ca = np.sort(rng.integers(0, 6, na).astype(np.int32))
    cb = np.sort(rng.integers(0, 6, nb).astype(np.int32))
    # sort ids within equal-code segments to honor the sorted-run contract
    ia = ids[:na][np.lexsort((ids[:na], ca))]
    ca = ca[np.argsort(ca, kind="stable")]
    ib = ids[na:][np.lexsort((ids[na:], cb))]
    cb = cb[np.argsort(cb, kind="stable")]
    pa, pb = merge_ranks(
        jnp.asarray(ca), jnp.asarray(ia), jnp.asarray(cb), jnp.asarray(ib)
    )
    ref_a, ref_b = _ref_merge_positions(ca, ia, cb, ib)
    np.testing.assert_array_equal(np.asarray(pa), ref_a)
    np.testing.assert_array_equal(np.asarray(pb), ref_b)
    # real positions are a permutation of [0, na+nb)
    assert sorted(np.concatenate([pa, pb]).tolist()) == list(range(na + nb))


def test_merge_ranks_sentinel_rows_land_past_n():
    """Equal sentinel keys across BOTH runs land at positions >= n_real and
    are dropped by the payload scatter — the no-mask sentinel discipline."""
    sent_c, sent_i = np.int32(1 << 10), np.int32(100)
    ca = np.array([1, 3, sent_c, sent_c], np.int32)
    ia = np.array([7, 2, sent_i, sent_i], np.int32)
    cb = np.array([3, sent_c, sent_c], np.int32)
    ib = np.array([0, sent_i, sent_i], np.int32)
    pa, pb = merge_ranks(
        jnp.asarray(ca), jnp.asarray(ia), jnp.asarray(cb), jnp.asarray(ib)
    )
    n_real = 3
    real = sorted([int(pa[0]), int(pa[1]), int(pb[0])])
    assert real == [0, 1, 2]
    assert int(pb[0]) == 1  # (3, 0) precedes (3, 2): id breaks the code tie
    assert all(int(p) >= n_real for p in [pa[2], pa[3], pb[1], pb[2]])
    out = splice_payload(pa, pb, jnp.asarray(ia), jnp.asarray(ib), n_real, fill=-1)
    np.testing.assert_array_equal(np.asarray(out), [7, 0, 2])


@pytest.mark.parametrize("side", ["left", "right"])
def test_searchsorted_pairs_matches_numpy_on_packed_keys(side):
    """Pair binary search == np.searchsorted over the packed 64-bit key."""
    rng = np.random.default_rng(3)
    kc = np.sort(rng.integers(0, 50, 200).astype(np.int32))
    ki = rng.integers(0, 1000, 200).astype(np.int32)
    ki = ki[np.lexsort((ki, kc))]
    qc = rng.integers(0, 50, 77).astype(np.int32)
    qi = rng.integers(0, 1000, 77).astype(np.int32)
    packed = kc.astype(np.int64) * 1_000_000 + ki
    q_packed = qc.astype(np.int64) * 1_000_000 + qi
    got = searchsorted_pairs(
        jnp.asarray(kc), jnp.asarray(ki), jnp.asarray(qc), jnp.asarray(qi),
        side=side,
    )
    np.testing.assert_array_equal(
        np.asarray(got), np.searchsorted(packed, q_packed, side=side)
    )


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_sparse_splice_plan_matches_dense_merge(seed):
    """The gather plan (Δ-sized scatters only) reproduces the dense
    merge_ranks/splice_payload output bitwise — including heavy code ties,
    sentinel padding on both event arrays, and a 2-D payload."""
    rng = np.random.default_rng(seed)
    n, d, npad = 120, 30, 9
    sent_c, sent_i = np.int32(1 << 12), np.int32(n)
    codes = np.sort(rng.integers(0, 12, n).astype(np.int32))  # heavy ties
    ids = rng.permutation(n).astype(np.int32)
    ids = ids[np.lexsort((ids, codes))]
    pay2d = rng.uniform(0, 1, (n, 2)).astype(np.float32)
    slots_real = np.sort(rng.choice(n, d, replace=False)).astype(np.int32)
    new_codes = rng.integers(0, 12, d).astype(np.int32)
    ord_b = np.lexsort((ids[slots_real], new_codes))
    cb = np.concatenate([new_codes[ord_b], np.full(npad, sent_c)])
    ib = np.concatenate([ids[slots_real][ord_b], np.full(npad, sent_i)])
    pb2d = np.concatenate(
        [rng.uniform(0, 1, (d, 2)), rng.uniform(0, 1, (npad, 2))]
    ).astype(np.float32)
    # dense reference: compacted survivors + sentinel tail, rank-merged
    keep = np.ones(n, bool)
    keep[slots_real] = False
    ca = np.concatenate([codes[keep], np.full(d, sent_c)])
    ia = np.concatenate([ids[keep], np.full(d, sent_i)])
    pa2d = np.concatenate([pay2d[keep], np.zeros((d, 2), np.float32)])
    pos_a, pos_b = merge_ranks(
        jnp.asarray(ca), jnp.asarray(ia), jnp.asarray(cb), jnp.asarray(ib)
    )
    want_ids = splice_payload(pos_a, pos_b, jnp.asarray(ia), jnp.asarray(ib), n)
    want_2d = splice_payload(
        pos_a, pos_b, jnp.asarray(pa2d), jnp.asarray(pb2d), n
    )
    # sparse plan: event arrays padded with sentinels, searched vs ORIGINAL keys
    packed = codes.astype(np.int64) * (1 << 13) + ids
    ins_full = np.searchsorted(
        packed, cb.astype(np.int64) * (1 << 13) + ib, side="right"
    ).astype(np.int32)
    slots = np.concatenate([slots_real, np.full(npad, n, np.int32)])
    src_a, b_src = sparse_splice_plan(
        jnp.asarray(slots), jnp.asarray(ins_full), n
    )
    got_ids = gather_splice(src_a, b_src, jnp.asarray(ids), jnp.asarray(ib))
    got_2d = gather_splice(src_a, b_src, jnp.asarray(pay2d), jnp.asarray(pb2d))
    np.testing.assert_array_equal(np.asarray(got_ids), np.asarray(want_ids))
    np.testing.assert_array_equal(np.asarray(got_2d), np.asarray(want_2d))


# ----------------------------------------------------------------------- core
def test_pyramid_delta_equals_recount():
    """Scatter-add of per-level ±1 deltas == a from-scratch recount, bitwise
    (int32 adds commute exactly); zero-weight (padding) rows are inert."""
    rng = np.random.default_rng(4)
    l_max = 5
    codes = rng.integers(0, 4**l_max, 500).astype(np.int32)
    pyr = _count_pyramid(jnp.asarray(codes), l_max)
    moved = rng.choice(500, 60, replace=False)
    new_codes_rows = rng.integers(0, 4**l_max, 60).astype(np.int32)
    codes2 = codes.copy()
    codes2[moved] = new_codes_rows
    # 60 real rows + 4 padding rows with garbage (but in-range) codes
    old = np.concatenate([codes[moved], np.array([0, 1, 2, 3], np.int32)])
    new = np.concatenate([new_codes_rows, np.array([3, 2, 1, 0], np.int32)])
    w = np.concatenate([np.ones(60, np.int32), np.zeros(4, np.int32)])
    got = pyramid_delta(
        pyr, jnp.asarray(old), jnp.asarray(new), jnp.asarray(w), l_max
    )
    want = _count_pyramid(jnp.asarray(codes2), l_max)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    np.testing.assert_array_equal(
        np.asarray(starts_from_pyramid(got, l_max)),
        np.asarray(starts_from_pyramid(want, l_max)),
    )


@pytest.mark.parametrize("delta_frac", [0.002, 0.05, 0.5, 1.0])
def test_reindex_delta_bitwise_equals_full(delta_frac):
    """reindex_objects_delta == reindex_objects, bitwise, for every churn
    level — duplicates (coincident points, code ties) and no-op moves mixed
    in, delta padded with sentinel-N rows like the session pads it."""
    rng = np.random.default_rng(5)
    n = 800
    pts = rng.uniform(0, SIDE, (n, 2)).astype(np.float32)
    pts[::7] = pts[3]  # coincident points: heavy code ties
    idx = _index(pts)
    d = max(1, int(n * delta_frac))
    ids = rng.choice(n, d, replace=False).astype(np.int32)
    pts2 = pts.copy()
    pts2[ids] = rng.uniform(0, SIDE, (d, 2)).astype(np.float32)
    pts2[ids[: d // 4]] = pts[ids[: d // 4]] + 0.01  # same-cell nudge
    pts2[ids[d // 4: d // 2]] = pts[ids[d // 4: d // 2]]  # no-op move
    padded = np.concatenate([ids, np.full(7, n, np.int32)])
    # old positions as of the index build; padding rows deliberately garbage
    old_pos = np.concatenate(
        [pts[ids], rng.uniform(0, SIDE, (7, 2)).astype(np.float32)]
    )
    got = reindex_objects_delta(
        idx, jnp.asarray(pts2), jnp.asarray(padded), jnp.asarray(old_pos)
    )
    want = reindex_objects(idx, jnp.asarray(pts2))
    _assert_index_equal(got, want)


def test_reindex_delta_pair_fallback_bitwise():
    """The pair-key search/sort fallback (taken when (code, id) cannot pack
    into an int32: 4**l_max * (n+1) + n >= 2**31) stays bitwise-equal to the
    full reindex.  l_max=8 with n >= 32767 crosses the threshold."""
    rng = np.random.default_rng(11)
    n = 33_000
    assert 4**8 * (n + 1) + n >= 2**31  # really exercises the fallback
    pts = rng.uniform(0, SIDE, (n, 2)).astype(np.float32)
    idx = _index(pts, l_max=8, th=96)
    d = 64
    ids = rng.choice(n, d, replace=False).astype(np.int32)
    pts2 = pts.copy()
    pts2[ids] = rng.uniform(0, SIDE, (d, 2)).astype(np.float32)
    padded = np.concatenate([ids, np.full(5, n, np.int32)])
    old_pos = np.concatenate([pts[ids], np.zeros((5, 2), np.float32)])
    got = reindex_objects_delta(
        idx, jnp.asarray(pts2), jnp.asarray(padded), jnp.asarray(old_pos)
    )
    want = reindex_objects(idx, jnp.asarray(pts2))
    _assert_index_equal(got, want)


def test_reindex_delta_chained_ticks():
    """Feeding each tick's *incremental* output into the next stays bitwise
    on the full-reindex trajectory — errors cannot accumulate because there
    are none."""
    rng = np.random.default_rng(6)
    n = 600
    pts = rng.uniform(0, SIDE, (n, 2)).astype(np.float32)
    inc = full = _index(pts)
    for _ in range(5):
        ids = rng.choice(n, 31, replace=False).astype(np.int32)
        old = pts[ids].copy()
        pts[ids] = np.clip(
            pts[ids] + rng.normal(0, SIDE / 10, (31, 2)), 0, SIDE - 0.01
        ).astype(np.float32)
        inc = reindex_objects_delta(
            inc, jnp.asarray(pts), jnp.asarray(ids), jnp.asarray(old)
        )
        full = reindex_objects(full, jnp.asarray(pts))
        _assert_index_equal(inc, full)


# -------------------------------------------------------------------- session
def _session(maintenance, pts, qpos, **kw):
    spec = ServiceSpec(
        k=4, chunk=256, window=32, l_max=5, th_quad=32, side=SIDE,
        delta_pad=64, maintenance=maintenance, **kw,
    )
    s = KnnSession(spec)
    s.ingest_objects(pts)
    s.register_queries(qpos)
    return s


def test_session_modes_and_bit_identity():
    """One motion script, two sessions: the scheduling decisions differ
    exactly as specified, the bits never do."""
    rng = np.random.default_rng(7)
    n = 500
    pts = rng.uniform(0, SIDE, (n, 2)).astype(np.float32)
    qpos = rng.uniform(0, SIDE, (32, 2)).astype(np.float32)
    a = _session("rebuild", pts, qpos)
    b = _session("incremental", pts, qpos, churn_budget=0.25)
    script = [None, 20, None, 20, 400, 20]  # rows moved before each tick
    want_a = ["skip", "rebuild", "skip", "rebuild", "rebuild", "rebuild"]
    want_b = ["skip", "incremental", "skip", "incremental", "rebuild",
              "incremental"]
    for t, mv in enumerate(script):
        if mv:
            ids = rng.choice(n, mv, replace=False)
            new = rng.uniform(0, SIDE, (mv, 2)).astype(np.float32)
            a.update_objects(ids, new)
            b.update_objects(ids, new)
        ra, rb = a.submit().result(), b.submit().result()
        assert ra.maintenance == want_a[t], t
        assert rb.maintenance == want_b[t], t
        # rows each refresh re-placed: the whole store on a re-sort, the
        # moved rows on a splice, none on a skip
        rows = {"skip": 0, "rebuild": n, "incremental": mv}
        assert ra.delta_rows == rows[ra.maintenance], t
        assert rb.delta_rows == rows[rb.maintenance], t
        np.testing.assert_array_equal(ra.nn_idx, rb.nn_idx, err_msg=str(t))
        np.testing.assert_array_equal(ra.nn_dist, rb.nn_dist, err_msg=str(t))
        _assert_index_equal(a.index, b.index)


def test_cyclic_reports_one_tick_ahead_match_brute_force():
    """The delta-reporting deployment in small: each tick the next ``r``
    objects of one fixed cyclic order report their fix (``update_objects``),
    one tick stays in flight, and each standing query follows its issuer's
    last report.  Every tick matches the brute force over the host mirror
    of the reported world, and a ``maintenance="rebuild"`` session fed the
    same stream bitwise; ``delta_rows`` counts the tick's reports."""
    rng = np.random.default_rng(21)
    n, r, q, k, ticks = 600, 60, 48, 4, 14  # the order wraps after 10 ticks
    mirror = rng.uniform(0, SIDE, (n, 2)).astype(np.float32)
    order = rng.permutation(n).astype(np.int32)
    qid = rng.choice(n, q, replace=False).astype(np.int32)
    sessions, handles = [], []
    for maintenance in ("incremental", "rebuild"):
        s = KnnSession(ServiceSpec(
            k=k, chunk=256, window=32, l_max=5, th_quad=32, side=SIDE,
            delta_pad=64, maintenance=maintenance))
        # a copy: on the CPU the session's buffer may alias what it is
        # handed, and the mirror is written below
        s.ingest_objects(mirror.copy())
        handles.append(s.register_queries(mirror[qid], qid))
        sessions.append(s)
    prev = [s.submit() for s in sessions]
    world = mirror.copy()
    modes = []

    def check(pending, world):
        inc, full = (h.result() for h in pending)
        bi, bd = knn_bruteforce(jnp.asarray(world), jnp.asarray(world[qid]),
                                jnp.asarray(qid), k)
        np.testing.assert_array_equal(inc.nn_idx, np.asarray(bi))
        np.testing.assert_allclose(inc.nn_dist, np.asarray(bd), rtol=1e-6)
        np.testing.assert_array_equal(inc.nn_idx, full.nn_idx)
        np.testing.assert_array_equal(inc.nn_dist, full.nn_dist)
        rows = {"skip": 0, "incremental": r, "rebuild": n}
        assert inc.delta_rows == rows[inc.maintenance]
        assert full.delta_rows == rows[full.maintenance]
        modes.append(inc.maintenance)

    for t in range(ticks):
        ids = order[(t * r + np.arange(r)) % n]
        fix = np.clip(mirror[ids] + rng.normal(0, 25, (r, 2)), 0,
                      SIDE - 1e-3).astype(np.float32)
        for s in sessions:
            s.update_objects(ids, fix)
        mirror[ids] = fix
        for h in prev:
            h.block_until_ready()
        for s, hq in zip(sessions, handles):
            s.update_queries(hq, mirror[qid])
        nxt = [s.submit() for s in sessions]
        check(prev, world)
        prev, world = nxt, mirror.copy()
    check(prev, world)
    assert modes[0] == "skip" and modes.count("incremental") >= ticks - 2


def test_session_snapshot_ingest_forces_full_refresh():
    """A snapshot replaces the buffer with an unknown delta: the next tick
    must run the full refresh even under an incremental spec."""
    rng = np.random.default_rng(8)
    pts = rng.uniform(0, SIDE, (300, 2)).astype(np.float32)
    qpos = rng.uniform(0, SIDE, (16, 2)).astype(np.float32)
    s = _session("incremental", pts, qpos)
    assert s.submit().result().maintenance == "skip"  # fresh build
    s.update_objects([5], [[1.0, 2.0]])
    assert s.submit().result().maintenance == "incremental"
    s.ingest_objects(rng.uniform(0, SIDE, (300, 2)).astype(np.float32))
    assert s.submit().result().maintenance == "rebuild"


def test_session_duplicate_delta_ids_count_once_against_budget():
    """The same object moving many times between submits is ONE moved row
    for the churn budget (the pending set is a union)."""
    rng = np.random.default_rng(9)
    n = 200
    pts = rng.uniform(0, SIDE, (n, 2)).astype(np.float32)
    qpos = rng.uniform(0, SIDE, (8, 2)).astype(np.float32)
    s = _session("incremental", pts, qpos, churn_budget=0.05)  # budget = 10 rows
    s.submit().result()
    for _ in range(30):  # 30 batches, all hitting the same 6 objects
        s.update_objects([0, 1, 2, 3, 4, 5],
                         rng.uniform(0, SIDE, (6, 2)).astype(np.float32))
    assert s.submit().result().maintenance == "incremental"
    ref = reindex_objects(s.index, s._positions)
    _assert_index_equal(s.index, ref, fields=("pos", "ids", "codes", "starts",
                                              "pyramid"))


# ------------------------------------------- sharded maintenance (DESIGN §15)
def test_rebuild_zmap_equals_fresh_build():
    """Stage-(i) reuse: ``rebuild_zmap`` over a spliced (current) index ==
    ``build_index`` from scratch, every field bitwise — the drift policy's
    z_map re-decision needs no fresh argsort when the order is current."""
    rng = np.random.default_rng(12)
    n = 700
    pts = rng.uniform(0, SIDE, (n, 2)).astype(np.float32)
    pts[::9] = pts[4]  # coincident rows: code ties in the kept order
    idx = _index(pts)
    ids = rng.choice(n, 90, replace=False)
    pts2 = pts.copy()
    pts2[ids] = rng.uniform(0, SIDE, (90, 2)).astype(np.float32)
    got = rebuild_zmap(reindex_objects(idx, jnp.asarray(pts2)))
    want = _index(pts2)
    _assert_index_equal(got, want)
    # idempotent on an already-current index too
    _assert_index_equal(rebuild_zmap(want), want)


@pytest.mark.parametrize("r", [2, 3, 8])
def test_derived_local_index_bitwise_equals_local_rebuild(r):
    """The derived local tree (masked slice + interval pyramid from the
    GLOBAL starts — ``_local_index_derived``) == the per-shard
    ``build_index`` over the same slice (``_local_index``), every field
    bitwise, over equal-capacity boundaries — including the uneven final
    shard and coincident duplicates.  This is the shard_map body's
    maintenance branch run host-side, shard by shard."""
    from repro.core import plan as plan_mod

    rng = np.random.default_rng(20 + r)
    n = 89  # uneven final slice for r = 2, 3, 8
    pts = rng.uniform(0, SIDE, (n, 2)).astype(np.float32)
    pts[::7] = pts[3]
    idx = _index(pts)
    cap = plan_mod.object_shard_capacity(n, r)
    bo = np.minimum(np.arange(r + 1) * cap, n)
    for s in range(r):
        rebuilt, derived = _shard_local_pair(idx, bo, s, cap)
        _assert_index_equal(rebuilt, derived)


def test_derived_local_index_uneven_and_empty_shards():
    """Cost-balanced-style boundaries as data: uneven owned counts, an EMPTY
    shard (own = 0 collapses the whole capacity window onto one clone row)
    and a full-capacity shard all stay bitwise-equal to the rebuild."""
    rng = np.random.default_rng(24)
    n = 200
    pts = rng.uniform(0, SIDE, (n, 2)).astype(np.float32)
    idx = _index(pts)
    bo = np.array([0, 10, 10, 120, 200])  # shard 1 owns nothing
    capo = 110  # >= max owned count, as the partitioner guarantees
    for s in range(4):
        rebuilt, derived = _shard_local_pair(idx, bo, s, capo)
        _assert_index_equal(rebuilt, derived)


def _shard_local_pair(idx, bo, r, capo):
    """Host-side emulation of ``_object_merge_local``'s two local-tree
    branches for shard ``r``: returns (rebuilt, derived) local indexes."""
    from repro.core import plan as plan_mod

    opos, oids, ocodes = plan_mod._pad_object_tail(idx, capo)
    start, own = int(bo[r]), int(bo[r + 1] - bo[r])
    opos_raw = opos[start:start + capo]
    oids_raw = oids[start:start + capo]
    mask = jnp.arange(capo) < own
    clone = opos_raw[int(np.clip(own - 1, 0, capo - 1))]
    opos_l = jnp.where(mask[:, None], opos_raw, clone[None, :])
    oids_l = jnp.where(mask, oids_raw, -1)
    rebuilt = plan_mod._local_index(
        opos_l, oids_l, idx.origin, idx.side, l_max=idx.l_max,
        th_quad=idx.th_quad,
    )
    codes_raw = ocodes[start:start + capo]
    clone_code = codes_raw[int(np.clip(own - 1, 0, capo - 1))]
    codes_l = jnp.where(mask, codes_raw, clone_code)
    derived = plan_mod._local_index_derived(
        idx.origin, idx.side, opos_l, oids_l, codes_l, clone_code,
        idx.starts, jnp.int32(start), jnp.int32(own), capo,
        l_max=idx.l_max, th_quad=idx.th_quad,
    )
    return rebuilt, derived


def test_delta_shard_counts_matches_host_recount():
    """Per-source-shard pending counts == a host bincount over the ownership
    rule, under both the capacity rule and explicit boundaries; sentinel-N
    padding rows are charged to no shard."""
    from repro.core.ticks import delta_shard_counts, object_shard_of

    rng = np.random.default_rng(13)
    n = 257
    pts = rng.uniform(0, SIDE, (n, 2)).astype(np.float32)
    idx = _index(pts)
    real = rng.choice(n, 40, replace=False).astype(np.int32)
    padded = jnp.asarray(np.concatenate([real, np.full(9, n, np.int32)]))
    for r, bounds in ((8, None), (4, jnp.asarray([0, 30, 101, 101, 257],
                                                 jnp.int32))):
        got = delta_shard_counts(idx, padded, r, bounds)
        shards = np.asarray(object_shard_of(idx, jnp.asarray(real), r, bounds))
        np.testing.assert_array_equal(
            np.asarray(got), np.bincount(shards, minlength=r)
        )


def test_shard_churn_over_budget_exact_boundary():
    """The per-shard deferral rule is STRICT: exactly churn_budget × owned
    movers in one shard stays incremental (mirroring the global ``<=`` rule);
    one more defers.  Spreading the same total across shards stays under;
    sentinel padding rows are inert."""
    from repro.core.ticks import shard_churn_over_budget

    rng = np.random.default_rng(14)
    n, r = 64, 4  # equal rule: 16 owned per shard, budget = 4 rows each
    pts = rng.uniform(0, SIDE, (n, 2)).astype(np.float32)
    idx = _index(pts)
    by_rank = np.asarray(idx.ids).astype(np.int32)

    def over(ranks):
        ids = jnp.asarray(by_rank[np.asarray(ranks)])
        return bool(shard_churn_over_budget(idx, ids, r, 0.25))

    assert not over(range(4))          # shard 0 at exactly its budget
    assert over(range(5))              # one past: defer
    assert not over([0, 1, 2, 3, 16])  # same 5 movers spread over 2 shards
    padded = jnp.asarray(np.concatenate(
        [by_rank[:4], np.full(6, n, np.int32)]
    ))
    assert not bool(shard_churn_over_budget(idx, padded, r, 0.25))


def test_session_churn_budget_exact_quarter_boundary():
    """The session's global deferral boundary is inclusive: exactly 25% of N
    pending splices incrementally, one row more defers to the full refresh —
    and both land on the full-reindex bits."""
    rng = np.random.default_rng(15)
    n = 64
    pts = rng.uniform(0, SIDE, (n, 2)).astype(np.float32)
    qpos = rng.uniform(0, SIDE, (8, 2)).astype(np.float32)
    for m, want in ((16, "incremental"), (17, "rebuild")):
        s = _session("incremental", pts, qpos, churn_budget=0.25)
        s.submit().result()
        ids = rng.choice(n, m, replace=False)
        s.update_objects(ids, rng.uniform(0, SIDE, (m, 2)).astype(np.float32))
        assert s.submit().result().maintenance == want, m
        ref = reindex_objects(s.index, s._positions)
        _assert_index_equal(s.index, ref, fields=("pos", "ids", "codes",
                                                  "starts", "pyramid"))


@pytest.mark.parametrize("plan", ["single", "sharded", "object_sharded",
                                  "hybrid"])
def test_no_motion_tick_skips_on_all_plans(plan):
    """A clean tick statically skips the reindex on EVERY plan — the mesh
    plans' derived local trees included — and replays the same bits."""
    import jax

    from repro.launch.mesh import default_hybrid_shape

    ndev = jax.device_count()
    mesh = (None if plan == "single"
            else default_hybrid_shape(ndev) if plan == "hybrid" else ndev)
    rng = np.random.default_rng(16)
    n = 96
    pts = rng.uniform(0, SIDE, (n, 2)).astype(np.float32)
    qpos = rng.uniform(0, SIDE, (16, 2)).astype(np.float32)
    for maint in ("rebuild", "incremental"):
        spec = ServiceSpec(
            k=4, window=16, chunk=32, l_max=5, th_quad=8, side=SIDE,
            plan=plan, mesh_shape=mesh, maintenance=maint,
            churn_budget=0.25, delta_pad=16,
        )
        s = KnnSession(spec)
        s.ingest_objects(pts)
        s.register_queries(qpos)
        assert s.submit().result().maintenance == "skip"  # fresh build
        ids = rng.choice(n, 8, replace=False)
        s.update_objects(ids, rng.uniform(0, SIDE, (8, 2)).astype(np.float32))
        moved = s.submit().result()
        assert moved.maintenance != "skip"
        still = s.submit().result()  # no motion since
        assert still.maintenance == "skip", (plan, maint)
        np.testing.assert_array_equal(moved.nn_idx, still.nn_idx)
        np.testing.assert_array_equal(moved.nn_dist, still.nn_dist)


def test_session_per_shard_budget_defers_concentrated_churn():
    """Movers concentrating in ONE object shard defer the whole tick to the
    full refresh even when the global fraction is comfortably in budget; the
    same total spread across shards splices — and either way the session
    lands on the full-reindex bits.  Needs a real object mesh (skipped on
    one device, where the per-shard rule degenerates to the global one)."""
    import jax

    if jax.device_count() < 2:
        pytest.skip("per-shard budget needs an object mesh (R > 1)")
    r = jax.device_count()
    n = 64 * r  # equal capacity 64 per shard, per-shard budget = 16 rows
    rng = np.random.default_rng(17)
    pts = rng.uniform(0, SIDE, (n, 2)).astype(np.float32)
    qpos = rng.uniform(0, SIDE, (8, 2)).astype(np.float32)
    cases = (
        (np.arange(17), "rebuild"),                       # all in shard 0
        (np.concatenate([np.arange(16), [64]]), "incremental"),  # spread
    )
    for ranks, want in cases:
        s = _session("incremental", pts, qpos, plan="object_sharded",
                     mesh_shape=r, churn_budget=0.25)
        s.submit().result()
        ids = np.asarray(s.index.ids)[ranks]
        s.update_objects(
            ids, rng.uniform(0, SIDE, (len(ids), 2)).astype(np.float32)
        )
        assert s.submit().result().maintenance == want, ranks
        ref = reindex_objects(s.index, s._positions)
        _assert_index_equal(s.index, ref, fields=("pos", "ids", "codes",
                                                  "starts", "pyramid"))


def test_session_drift_rebuild_reuses_spliced_order():
    """Drift × maintenance: a low ``rebuild_factor`` fires the stage-(i)
    z_map rebuild between ticks; under the incremental spec it reuses the
    spliced order (``rebuild_zmap``, no fresh argsort) and must stay bitwise
    on the rebuild session's trajectory."""
    rng = np.random.default_rng(18)
    n = 400
    pts = rng.uniform(0, SIDE, (n, 2)).astype(np.float32)
    qpos = rng.uniform(0, SIDE, (16, 2)).astype(np.float32)
    a = _session("rebuild", pts, qpos, rebuild_factor=0.5)
    b = _session("incremental", pts, qpos, rebuild_factor=0.5,
                 churn_budget=0.25)
    rebuilds = 0
    for t in range(5):
        ids = rng.choice(n, 20, replace=False)
        new = rng.uniform(0, SIDE, (20, 2)).astype(np.float32)
        a.update_objects(ids, new)
        b.update_objects(ids, new)
        ra, rb = a.submit().result(), b.submit().result()
        rebuilds += bool(rb.rebuilt)
        assert ra.maintenance == ("rebuild" if t else "skip")
        np.testing.assert_array_equal(ra.nn_idx, rb.nn_idx, err_msg=str(t))
        np.testing.assert_array_equal(ra.nn_dist, rb.nn_dist, err_msg=str(t))
        _assert_index_equal(a.index, b.index)
    assert rebuilds >= 1  # the drift trigger actually fired mid-run


def test_validation_rejects_bad_maintenance_knobs():
    with pytest.raises(ValueError, match="maintenance"):
        ServiceSpec(maintenance="lazy")
    with pytest.raises(ValueError, match="churn_budget"):
        ServiceSpec(maintenance="incremental", churn_budget=0.0)
    with pytest.raises(ValueError, match="churn_budget"):
        EngineConfig(churn_budget=1.5)
    with pytest.raises(ValueError, match="maintenance"):
        EngineConfig(maintenance="never")
    assert "rebuild" in MAINTENANCE_MODES and "incremental" in MAINTENANCE_MODES


def test_spec_round_trips_maintenance_knobs():
    cfg = EngineConfig(maintenance="incremental", churn_budget=0.1)
    spec = ServiceSpec.from_engine(cfg)
    assert spec.maintenance == "incremental" and spec.churn_budget == 0.1
    cfg2 = spec.engine_config()
    assert cfg2.maintenance == "incremental" and cfg2.churn_budget == 0.1
    assert dataclasses.asdict(cfg) == dataclasses.asdict(cfg2)
