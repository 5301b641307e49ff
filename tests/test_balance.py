"""Partitioner seam: boundaries, per-shard counters, cost_balanced ≡ equal.

The acceptance contract of the cost-balanced partitioning refactor
(DESIGN.md §13): partitioners only move chunk/slice boundaries, so

  * ``cost_balanced`` results are bit-identical to ``equal`` (and hence to
    the ``single`` plan) across the full plan × backend matrix;
  * the new per-shard candidate counters sum to the existing global
    ``stats.candidates`` bitwise — the global IS defined as their sum;
  * on a skewed (Zipf) workload over a real 8-device mesh, ``cost_balanced``
    reduces the straggler gap (max/mean per-shard candidates) vs ``equal``
    on the query-sharded plan.

Runs on however many devices exist; the subprocess tests force an 8-device
host grid regardless of the outer environment.
"""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    CostBalancedPartitioner,
    EqualPartitioner,
    ShardedPlan,
    available_backends,
    available_partitioners,
    build_index,
    knn_query_batch_chunked,
    partitioner_names,
    resolve_partitioner,
    resolve_plan,
    straggler_gap,
)
from repro.core.balance import balanced_boundaries, equal_boundaries
from repro.data import make_workload

SRC = os.path.join(os.path.dirname(__file__), "..", "src")
NDEV = jax.device_count()


# ---------------------------------------------------------------- registry

def test_partitioner_registry_names():
    assert set(partitioner_names()) == {"equal", "cost_balanced"}
    assert available_partitioners() == partitioner_names()


def test_resolve_partitioner():
    assert resolve_partitioner(None) == EqualPartitioner()
    assert resolve_partitioner("equal") == EqualPartitioner()
    cb = resolve_partitioner("cost_balanced")
    assert isinstance(cb, CostBalancedPartitioner)
    assert resolve_partitioner(cb) is cb
    with pytest.raises(ValueError, match="unknown partitioner"):
        resolve_partitioner("nope")


def test_cost_balanced_knob_validation():
    with pytest.raises(ValueError, match="slack"):
        CostBalancedPartitioner(slack=0.5)
    with pytest.raises(ValueError, match="ema_alpha"):
        CostBalancedPartitioner(ema_alpha=0.0)
    with pytest.raises(ValueError, match="ema_alpha"):
        CostBalancedPartitioner(ema_alpha=1.5)


def test_plans_carry_partitioner():
    """resolve_plan threads the partitioner into every mesh plan; the
    ServiceSpec name knob rejects unknown partitioners and reaches the
    session's plan."""
    from repro.api import KnnSession, ServiceSpec

    for name in ("sharded", "object_sharded", "hybrid"):
        p = resolve_plan(name, num_devices=(1, 1) if name == "hybrid" else 1,
                         partitioner="cost_balanced")
        assert isinstance(p.partitioner, CostBalancedPartitioner), name
        assert "cost_balanced" in p.describe()
        q = resolve_plan(name, num_devices=(1, 1) if name == "hybrid" else 1)
        assert q.partitioner == EqualPartitioner()
    with pytest.raises(ValueError, match="unknown partitioner"):
        ServiceSpec(partitioner="nope")
    sess = KnnSession(ServiceSpec(plan="sharded", mesh_shape=1,
                                  partitioner="cost_balanced"))
    assert isinstance(sess.plan.partitioner, CostBalancedPartitioner)


# -------------------------------------------------------------- boundaries

def test_equal_boundaries_match_capacity_rule():
    b = np.asarray(equal_boundaries(32, 8))
    np.testing.assert_array_equal(b, np.arange(9) * 4)
    # uneven: last shard short, coverage exact
    b = np.asarray(equal_boundaries(10, 4))
    assert b[0] == 0 and b[-1] == 10
    assert (np.diff(b) <= 3).all() and (np.diff(b) >= 0).all()


@pytest.mark.parametrize("n,r,skew", [(32, 8, 8.0), (100, 4, 3.0),
                                      (7, 8, 5.0), (64, 3, 1.0)])
def test_balanced_boundaries_invariants(n, r, skew):
    """Monotone, full coverage, capacity respected, feasible for n < R."""
    rng = np.random.default_rng(n * 31 + r)
    costs = jnp.asarray(rng.pareto(1.5, n).astype(np.float32) * skew + 1.0)
    cap = CostBalancedPartitioner().query_capacity(n, r)
    b = np.asarray(balanced_boundaries(costs, r, cap))
    assert b.shape == (r + 1,)
    assert b[0] == 0 and b[-1] == n
    assert (np.diff(b) >= 0).all()
    assert (np.diff(b) <= cap).all()


def test_balanced_boundaries_reduce_max_shard_cost():
    """On a hotspot cost vector the balanced split's max shard cost is
    strictly below the equal split's (the whole point of the seam)."""
    costs = np.ones(32, np.float32)
    costs[:4] = 100.0  # hotspot in the first equal shard
    r = 8
    cap = CostBalancedPartitioner().query_capacity(32, r)
    bb = np.asarray(balanced_boundaries(jnp.asarray(costs), r, cap))
    be = np.asarray(equal_boundaries(32, r))

    def max_shard(b):
        return max(costs[b[i]:b[i + 1]].sum() for i in range(r))

    assert max_shard(bb) < max_shard(be)
    # infeasible capacity is rejected eagerly
    with pytest.raises(ValueError, match="infeasible"):
        balanced_boundaries(jnp.asarray(costs), 8, 3)


def test_balanced_boundaries_uniform_costs_are_equalish():
    b = np.asarray(balanced_boundaries(jnp.ones(40, jnp.float32), 4,
                                       CostBalancedPartitioner()
                                       .query_capacity(40, 4)))
    np.testing.assert_array_equal(b, [0, 10, 20, 30, 40])


# ------------------------------------------- per-shard counters + parity

def _zipf_case(n=512, nq=128, seed=3):
    pts = make_workload(n, "zipf", seed=seed, zipf_a=1.8,
                        hotspot_sigma_frac=0.003).positions()
    rng = np.random.default_rng(seed)
    qsel = rng.choice(n, nq, replace=False)
    idx = build_index(jnp.asarray(pts), jnp.zeros(2), 22_500.0,
                      l_max=6, th_quad=16)
    return idx, pts[qsel], qsel.astype(np.int32)


@pytest.mark.parametrize("plan,mesh", [
    ("single", None), ("sharded", NDEV), ("object_sharded", NDEV),
    ("hybrid", None),
])
@pytest.mark.parametrize("partitioner", ["equal", "cost_balanced"])
def test_shard_counters_sum_to_global(plan, mesh, partitioner):
    """aux.shard_candidates sums to stats.candidates bitwise, and the
    counter vector has one entry per mesh device."""
    idx, qpos, qid = _zipf_case()
    _, _, stats, aux = knn_query_batch_chunked(
        idx, qpos, qid, k=6, window=32, chunk=32, plan=plan,
        num_devices=mesh, partitioner=partitioner, with_aux=True)
    p = resolve_plan(plan, num_devices=mesh)
    if plan == "single":
        expect_r = 1
    elif plan == "hybrid":
        expect_r = p.query_devices * p.object_devices
    else:
        expect_r = p.num_devices
    assert aux.shard_candidates.shape == (expect_r,)
    assert aux.shard_iterations.shape == (expect_r,)
    assert np.float32(aux.shard_candidates.sum()) == np.float32(
        stats.candidates)
    assert int(aux.shard_iterations.sum()) == int(stats.iterations)
    # object boundaries cover the object array exactly
    assert aux.object_bounds[0] == 0
    assert aux.object_bounds[-1] == idx.n_objects
    assert (np.diff(aux.object_bounds) >= 0).all()


def test_cost_balanced_bitwise_equal_full_matrix():
    """cost_balanced ≡ equal, bitwise, for every backend × mesh plan (the
    satellite pin; the property harness fuzzes the same contract)."""
    idx, qpos, qid = _zipf_case()
    for backend in available_backends():
        for plan, mesh in (("sharded", NDEV), ("object_sharded", NDEV),
                           ("hybrid", None)):
            a_i, a_d, _ = knn_query_batch_chunked(
                idx, qpos, qid, k=6, window=32, chunk=32, backend=backend,
                plan=plan, num_devices=mesh, partitioner="equal")
            b_i, b_d, _ = knn_query_batch_chunked(
                idx, qpos, qid, k=6, window=32, chunk=32, backend=backend,
                plan=plan, num_devices=mesh, partitioner="cost_balanced")
            np.testing.assert_array_equal(a_i, b_i,
                                          err_msg=f"{backend}/{plan}")
            np.testing.assert_array_equal(a_d, b_d,
                                          err_msg=f"{backend}/{plan}")


def test_equal_partitioner_plan_equality():
    """The default-constructed plan IS the equal-partitioner plan (jit cache
    keys and registry defaults agree)."""
    assert ShardedPlan(num_devices=2) == ShardedPlan(
        num_devices=2, partitioner=EqualPartitioner())
    assert ShardedPlan(num_devices=2) != ShardedPlan(
        num_devices=2, partitioner=CostBalancedPartitioner())


# ------------------------------------------- tenant-fair boundary weights

def test_tenant_fair_weights_sum_to_one_per_tenant():
    """Each tenant's rows carry 1/count, so every tenant's total influence
    on the boundary seed is exactly 1.0 regardless of its query volume."""
    from repro.core.balance import tenant_fair_weights

    tid = np.array([0, 0, 0, 0, 1, 2, 2], np.int64)
    w = np.asarray(tenant_fair_weights(tid))
    assert w.dtype == np.float32 and w.shape == (7,)
    np.testing.assert_allclose(w, [0.25] * 4 + [1.0] + [0.5] * 2)
    for t in (0, 1, 2):
        np.testing.assert_allclose(w[tid == t].sum(), 1.0, rtol=1e-6)
    # non-contiguous / unordered ids work; empty input is empty
    w2 = np.asarray(tenant_fair_weights([7, -3, 7]))
    np.testing.assert_allclose(w2, [0.5, 1.0, 0.5])
    assert tenant_fair_weights([]).shape == (0,)


def test_query_cost_weights_validation_and_bit_identity():
    """set_query_cost_weights validates eagerly (length, positivity) and —
    because weights scale the boundary seed only — cannot change bits on
    the cost-balanced plans even under wildly skewed weights."""
    from repro.api import KnnSession, ServiceSpec

    def run(weights_fn, plan, mesh):
        spec = ServiceSpec(k=4, th_quad=16, l_max=5, window=32, chunk=32,
                           plan=plan, mesh_shape=mesh,
                           partitioner="cost_balanced")
        sess = KnnSession(spec)
        w = make_workload(300, "zipf", seed=13, zipf_a=1.6)
        sess.ingest_objects(w.positions())
        h = sess.register_queries(w.positions(),
                                  np.arange(300, dtype=np.int32))
        rng = np.random.default_rng(5)
        out = []
        for _ in range(3):
            if weights_fn is not None:
                sess.set_query_cost_weights(weights_fn(rng))
            out.append(sess.submit().result())
            w.advance()
            sess.update_objects(np.arange(300), w.positions())
            sess.update_queries(h, w.positions())
        return out

    skewed = lambda rng: rng.pareto(1.2, 300).astype(np.float32) + 1e-3
    for plan, mesh in (("sharded", NDEV), ("object_sharded", NDEV),
                       ("hybrid", None)):
        a, b = run(None, plan, mesh), run(skewed, plan, mesh)
        for ra, rb in zip(a, b):
            np.testing.assert_array_equal(ra.nn_idx, rb.nn_idx, err_msg=plan)
            np.testing.assert_array_equal(ra.nn_dist, rb.nn_dist,
                                          err_msg=plan)

    from repro.api import ServiceSpec as SS
    sess = KnnSession(SS(k=4, th_quad=16, l_max=5, window=32, chunk=32))
    sess.ingest_objects(make_workload(64, "uniform", seed=0).positions())
    sess.register_queries(np.zeros((4, 2), np.float32))
    with pytest.raises(ValueError, match="4-row registry"):
        sess.set_query_cost_weights(np.ones(3, np.float32))
    with pytest.raises(ValueError, match="finite and > 0"):
        sess.set_query_cost_weights(np.array([1, 0, 1, 1], np.float32))
    with pytest.raises(ValueError, match="finite and > 0"):
        sess.set_query_cost_weights(np.array([1, np.inf, 1, 1], np.float32))
    sess.set_query_cost_weights(np.ones(4, np.float32))
    sess.submit().result()
    # weights must be re-set after a row-set change (validated at submit)
    sess.register_queries(np.ones((2, 2), np.float32))
    with pytest.raises(RuntimeError, match="row set changed"):
        sess.submit()
    sess.set_query_cost_weights(None)
    sess.submit().result()


# ------------------------------------------------------- session EMA loop

def test_session_qcost_ema_persists_and_resets():
    """The per-query cost EMA warms after one tick, persists across ticks
    and drift rebuilds, and resets when the registry's row set changes."""
    from repro.api import KnnSession, ServiceSpec

    spec = ServiceSpec(k=4, th_quad=16, l_max=5, window=32, chunk=32,
                       plan="sharded", mesh_shape=NDEV,
                       partitioner="cost_balanced", rebuild_factor=1.2)
    sess = KnnSession(spec)
    w = make_workload(400, "zipf", seed=7, zipf_a=1.6)
    sess.ingest_objects(w.positions())
    h = sess.register_queries(w.positions(), np.arange(400, dtype=np.int32))
    assert sess._qcost is None
    sess.submit().result()
    warm = np.asarray(sess._qcost)
    assert warm.shape[0] >= 400 and (warm[:400] > 0).all()
    # persists across ticks (and any drift rebuild triggered by motion)
    for _ in range(3):
        w.advance()
        sess.update_objects(np.arange(400), w.positions())
        sess.update_queries(h, w.positions())
        sess.submit().result()
    assert (np.asarray(sess._qcost)[:400] > 0).all()
    # row-set change invalidates the row alignment -> reset
    sess.register_queries(w.positions()[:8])
    sess.submit().result()
    assert sess._qcost is not None  # re-seeded by the tick just run
    sess.drop_queries(h)
    assert sess._registry.rows_changed


def test_session_results_identical_across_partitioners_over_ticks():
    """A moving zipf workload served tick-for-tick: cost_balanced sessions
    return the same bits as equal ones while re-cutting boundaries from the
    measured-work EMA every tick."""
    from repro.api import KnnSession, ServiceSpec

    def run(partitioner, plan, mesh):
        spec = ServiceSpec(k=4, th_quad=16, l_max=5, window=32, chunk=32,
                           plan=plan, mesh_shape=mesh,
                           partitioner=partitioner)
        sess = KnnSession(spec)
        w = make_workload(300, "hotspot_cluster", seed=11, clusters=3)
        sess.ingest_objects(w.positions())
        h = sess.register_queries(w.positions(),
                                  np.arange(300, dtype=np.int32))
        out = []
        for _ in range(3):
            out.append(sess.submit().result())
            w.advance()
            sess.update_objects(np.arange(300), w.positions())
            sess.update_queries(h, w.positions())
        return out

    for plan, mesh in (("sharded", NDEV), ("object_sharded", NDEV)):
        a, b = run("equal", plan, mesh), run("cost_balanced", plan, mesh)
        for ra, rb in zip(a, b):
            np.testing.assert_array_equal(ra.nn_idx, rb.nn_idx,
                                          err_msg=plan)
            np.testing.assert_array_equal(ra.nn_dist, rb.nn_dist,
                                          err_msg=plan)
            assert ra.rebuilt == rb.rebuilt


# ------------------------------------- forced 8-device mesh (real XLA)

def test_partitioner_parity_and_straggler_gap_on_8_devices():
    """On a real 8-device grid with a Zipf hotspot: every plan × partitioner
    matches the single plan bitwise, per-shard counters sum to the global,
    and cost_balanced tightens the straggler gap on the query-sharded plan
    (the acceptance criterion of DESIGN.md §13).

    Runs in a subprocess because the device count must be set before jax
    init.
    """
    code = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import numpy as np, jax, jax.numpy as jnp
assert jax.device_count() == 8, jax.device_count()
from repro.core import build_index, knn_query_batch_chunked, straggler_gap
from repro.data import make_workload

pts = make_workload(2048, "zipf", seed=0, zipf_a=1.6,
                    hotspot_sigma_frac=0.002).positions()
qid = np.arange(2048, dtype=np.int32)
idx = build_index(jnp.asarray(pts), jnp.zeros(2), 22500.0, l_max=6, th_quad=24)
a_i, a_d, _ = knn_query_batch_chunked(idx, pts, qid, k=8, window=32, chunk=32,
                                      plan="single")
gaps = {}
for plan, mesh in (("sharded", 8), ("object_sharded", 8), ("hybrid", (2, 4))):
    for part in ("equal", "cost_balanced"):
        b_i, b_d, st, aux = knn_query_batch_chunked(
            idx, pts, qid, k=8, window=32, chunk=32, plan=plan,
            num_devices=mesh, partitioner=part, with_aux=True)
        np.testing.assert_array_equal(a_i, b_i, err_msg=f"{plan}/{part}")
        np.testing.assert_array_equal(a_d, b_d, err_msg=f"{plan}/{part}")
        assert np.float32(aux.shard_candidates.sum()) == np.float32(
            st.candidates), (plan, part)
        assert aux.shard_candidates.shape == (8,)
        gaps[(plan, part)] = straggler_gap(aux.shard_candidates)
assert gaps[("sharded", "cost_balanced")] < gaps[("sharded", "equal")], gaps
print("BALANCE_8DEV_OK", gaps)
"""
    env = dict(os.environ, PYTHONPATH=SRC)
    env.pop("XLA_FLAGS", None)  # the child pins its own device count
    r = subprocess.run(
        [sys.executable, "-c", code], env=env, capture_output=True, text=True
    )
    assert r.returncode == 0, (r.stdout[-1000:], r.stderr[-3000:])
    assert "BALANCE_8DEV_OK" in r.stdout
