"""ExecutionPlan seam: sharded == single bit-for-bit, padding, registry.

The acceptance contract of the mesh-sharded refactor (DESIGN.md §10): the
``sharded`` plan — replicated index, ``shard_map`` query shards, concatenating
gather — must produce **bit-identical** ids and distances to the ``single``
plan on the same inputs, because every shard boundary coincides with a chunk
boundary of the single plan's sweep.  Runs on however many devices exist
(CI runs the suite twice: 1 real CPU device and 8 forced host devices); the
subprocess test additionally pins an 8-device mesh regardless of the outer
environment.
"""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import KnnSession, ServiceSpec
from repro.core import (
    HybridPlan,
    ObjectShardedPlan,
    ShardedPlan,
    SinglePlan,
    available_plans,
    build_index,
    knn_bruteforce_chunked,
    knn_query_batch_chunked,
    resolve_plan,
)
from repro.data import make_workload
from repro.testing import run_ticks

SRC = os.path.join(os.path.dirname(__file__), "..", "src")
NDEV = jax.device_count()


# ------------------------------------------------------------------ registry

def test_plan_registry_names():
    assert set(available_plans()) == {
        "single", "sharded", "object_sharded", "hybrid"
    }


def test_unknown_plan_rejected():
    with pytest.raises(ValueError, match="unknown execution plan"):
        resolve_plan("nope")


def test_executor_resolve_plan_is_plan_resolve_plan():
    """core.executor re-exports the canonical plan resolver as the SAME
    function object (a documented alias, not a divergent wrapper)."""
    from repro.core import executor as executor_mod
    from repro.core import plan as plan_mod

    assert executor_mod.resolve_plan is plan_mod.resolve_plan
    for name in ("single", "sharded"):
        a = executor_mod.resolve_plan(name, num_devices=1)
        b = plan_mod.resolve_plan(name, num_devices=1)
        assert a == b
    assert executor_mod.resolve_plan(None) == plan_mod.resolve_plan(None)


def test_resolve_plan_defaults():
    assert resolve_plan(None) == SinglePlan()
    assert resolve_plan("single") == SinglePlan()
    p = resolve_plan("sharded")
    assert isinstance(p, ShardedPlan) and p.num_devices == NDEV
    assert resolve_plan("sharded", num_devices=1) == ShardedPlan(num_devices=1)
    assert resolve_plan(p) is p
    o = resolve_plan("object_sharded")
    assert isinstance(o, ObjectShardedPlan) and o.num_devices == NDEV
    assert o.object_axis_size == NDEV and o.merge == "dense_merge"
    h = resolve_plan("hybrid")
    assert isinstance(h, HybridPlan)
    assert h.query_devices * h.object_devices == NDEV
    assert h.query_devices <= h.object_devices  # balanced factorization
    assert resolve_plan("hybrid", num_devices=(1, 1)) == HybridPlan(1, 1)
    # 1-D plans reject 2-D mesh shapes, hybrid rejects malformed tuples
    with pytest.raises(ValueError, match="1-D mesh"):
        resolve_plan("sharded", num_devices=(2, 2))
    with pytest.raises(ValueError, match="1-D mesh"):
        resolve_plan("object_sharded", num_devices=(2, 2))
    with pytest.raises(ValueError, match="query, object"):
        resolve_plan("hybrid", num_devices=(2, 2, 2))


def test_resolve_plan_merge_axis():
    """The MERGE backend rides the same resolution seam as the partitioner:
    named object-axis plans pick it up; plans without an object axis ignore
    it; unknown names fail eagerly at the registry."""
    o = resolve_plan("object_sharded", merge="fused_multi")
    assert isinstance(o, ObjectShardedPlan) and o.merge == "fused_multi"
    h = resolve_plan("hybrid", merge="fused_multi")
    assert isinstance(h, HybridPlan) and h.merge == "fused_multi"
    assert resolve_plan("object_sharded").merge == "dense_merge"
    # query-axis-only plans have no merge reduce: the knob is ignored
    assert resolve_plan("single", merge="fused_multi") == SinglePlan()
    assert resolve_plan("sharded", merge="fused_multi") == ShardedPlan(
        num_devices=NDEV)
    with pytest.raises(ValueError, match="unknown merge backend"):
        knn_query_batch_chunked(
            _tiny_index(), np.zeros((4, 2), np.float32), None,
            k=2, chunk=4, plan="object_sharded", num_devices=1, merge="nope",
        )


@pytest.mark.parametrize("plan,mesh", [
    ("object_sharded", None),
    ("hybrid", None),
])
def test_fused_multi_merge_plan_parity(plan, mesh):
    """merge="fused_multi" (one multi-way kernel over the concatenated
    per-shard lists — no HBM round-trip between binary-tree rounds) must
    reproduce the dense_merge bits on the object-axis plans: the canonical
    ``(d2, id)`` selection is associative, so a multi-way selection over
    R·k entries equals the binary reduction tree (DESIGN.md §14)."""
    w = make_workload(600, "gaussian", seed=6, hotspots=4)
    pts = w.positions()
    qpos, qid = w.query_batch()
    idx = build_index(jnp.asarray(pts), jnp.zeros(2), 22_500.0, l_max=6,
                      th_quad=24)
    ref_i, ref_d, _ = knn_query_batch_chunked(
        idx, qpos, qid, k=6, window=32, chunk=32, plan="single")
    for merge in ("dense_merge", "fused_multi"):
        ii, dd, _ = knn_query_batch_chunked(
            idx, qpos, qid, k=6, window=32, chunk=32, plan=plan,
            num_devices=mesh, merge=merge)
        np.testing.assert_array_equal(ii, ref_i, err_msg=f"{plan}/{merge}")
        np.testing.assert_array_equal(dd, ref_d, err_msg=f"{plan}/{merge}")


def test_plan_pad_multiples_and_object_axis():
    """Query padding granularity: chunk per query-axis device; the object
    axis never pads queries (the batch is replicated across it)."""
    chunk = 64
    assert SinglePlan().pad_multiple(chunk) == chunk
    assert ShardedPlan(num_devices=4).pad_multiple(chunk) == 4 * chunk
    assert ObjectShardedPlan(num_devices=4).pad_multiple(chunk) == chunk
    assert HybridPlan(2, 4).pad_multiple(chunk) == 2 * chunk
    assert SinglePlan().object_axis_size == 1
    assert ShardedPlan(num_devices=4).object_axis_size == 1
    assert ObjectShardedPlan(num_devices=4).object_axis_size == 4
    assert HybridPlan(2, 4).object_axis_size == 4


def test_sharded_plan_rejects_bad_device_counts():
    with pytest.raises(ValueError):
        ShardedPlan(num_devices=0)
    with pytest.raises(ValueError):
        ObjectShardedPlan(num_devices=0)
    with pytest.raises(ValueError):
        HybridPlan(0, 1)
    with pytest.raises(ValueError, match="devices"):
        # plan constructs, the mesh (built at trace time) rejects the overask
        knn_query_batch_chunked(
            _tiny_index(), np.zeros((4, 2), np.float32), None,
            k=2, chunk=4, plan="sharded", num_devices=NDEV + 1,
        )
    with pytest.raises(ValueError, match="devices"):
        knn_query_batch_chunked(
            _tiny_index(), np.zeros((4, 2), np.float32), None,
            k=2, chunk=4, plan="object_sharded", num_devices=NDEV + 1,
        )


def _tiny_index():
    rng = np.random.default_rng(0)
    pts = rng.uniform(0, 1000, (32, 2)).astype(np.float32)
    return build_index(jnp.asarray(pts), jnp.zeros(2), 1000.0, l_max=4, th_quad=8)


# ------------------------------------------------- determinism across plans

def _both_plans(pts, qpos, qid, *, k, chunk, num_devices):
    idx = build_index(jnp.asarray(pts), jnp.zeros(2), 22_500.0, l_max=6, th_quad=24)
    a_i, a_d, _ = knn_query_batch_chunked(
        idx, qpos, qid, k=k, window=32, chunk=chunk, plan="single"
    )
    b_i, b_d, _ = knn_query_batch_chunked(
        idx, qpos, qid, k=k, window=32, chunk=chunk,
        plan="sharded", num_devices=num_devices,
    )
    return (a_i, a_d), (b_i, b_d)


@pytest.mark.parametrize("dist", ["uniform", "gaussian", "network"])
def test_sharded_bit_identical_to_single(dist):
    """All three workload families: ids AND distances bit-for-bit equal."""
    w = make_workload(700, dist, seed=5)
    pts = w.positions()
    qpos, qid = w.query_batch()
    (a_i, a_d), (b_i, b_d) = _both_plans(
        pts, qpos, qid, k=8, chunk=64, num_devices=NDEV
    )
    np.testing.assert_array_equal(a_i, b_i)
    np.testing.assert_array_equal(a_d, b_d)


def test_sharded_bit_identical_duplicate_ties_and_padding():
    """Duplicate positions (massed distance ties) and n < k inf/-1 padding
    must resolve identically across plans — same per-query op sequence."""
    rng = np.random.default_rng(8)
    base = rng.uniform(0, 22_500, (40, 2)).astype(np.float32)
    pts = np.repeat(base, 4, axis=0)  # every position 4 times -> ties
    rng.shuffle(pts)
    qid = np.arange(len(pts), dtype=np.int32)
    (a_i, a_d), (b_i, b_d) = _both_plans(
        pts, pts, qid, k=6, chunk=32, num_devices=NDEV
    )
    np.testing.assert_array_equal(a_i, b_i)
    np.testing.assert_array_equal(a_d, b_d)
    # n < k: padding rows identical too
    small = rng.uniform(0, 22_500, (3, 2)).astype(np.float32)
    (a_i, a_d), (b_i, b_d) = _both_plans(
        small, small, np.arange(3, dtype=np.int32), k=8, chunk=16,
        num_devices=NDEV,
    )
    np.testing.assert_array_equal(a_i, b_i)
    np.testing.assert_array_equal(a_d, b_d)
    assert (a_i[:, 2:] == -1).all() and np.isinf(a_d[:, 2:]).all()


# ------------------------------------------------------- padding regression

@pytest.mark.parametrize("nq", [1, None])  # None -> num_devices * chunk - 1
def test_sharded_pad_strip_regression(nq):
    """A batch not divisible by num_devices * chunk pads once host-side and
    strips after the gather: Q=1 and Q=num_devices*chunk-1 (the two worst
    cases: maximal padding, and one-row-short of no padding)."""
    chunk = 32
    nq = NDEV * chunk - 1 if nq is None else nq
    rng = np.random.default_rng(nq)
    pts = rng.uniform(0, 22_500, (500, 2)).astype(np.float32)
    qpos = rng.uniform(0, 22_500, (nq, 2)).astype(np.float32)
    idx = build_index(jnp.asarray(pts), jnp.zeros(2), 22_500.0, l_max=5, th_quad=16)
    ii, dd, _ = knn_query_batch_chunked(
        idx, qpos, None, k=4, window=32, chunk=chunk,
        plan="sharded", num_devices=NDEV,
    )
    assert ii.shape == (nq, 4) and dd.shape == (nq, 4)
    bi, bd = knn_bruteforce_chunked(
        pts, qpos, np.full((nq,), -2, np.int32), k=4, chunk=max(nq, 1)
    )
    np.testing.assert_allclose(dd, bd, rtol=1e-5, atol=1e-3)


def test_engine_sharded_pad_strip_q1():
    """The session path: a single query through the sharded tick step."""
    w = make_workload(400, "uniform", seed=9)
    sess = KnnSession(
        ServiceSpec(k=4, th_quad=16, l_max=5, window=32, chunk=32,
                     plan="sharded", mesh_shape=NDEV)
    )
    qpos = w.positions()[:1]
    res = sess.process_tick(w.positions(), qpos, np.array([0], np.int32))
    assert res.nn_idx.shape == (1, 4)
    bi, bd = knn_bruteforce_chunked(
        w.positions(), qpos, np.array([0], np.int32), k=4, chunk=32
    )
    np.testing.assert_allclose(res.nn_dist, bd, rtol=1e-5, atol=1e-3)


# ----------------------------------------------------- session plan parity

def test_engine_plan_parity_over_ticks():
    """A session under plan=sharded == plan=single, tick for tick, bitwise."""
    def run(plan):
        sess = KnnSession(
            ServiceSpec(k=6, th_quad=16, l_max=5, window=32, chunk=64,
                         plan=plan, mesh_shape=NDEV if plan == "sharded" else None)
        )
        w = make_workload(600, "gaussian", seed=2, hotspots=4)
        return run_ticks(sess, w, ticks=3)

    single, sharded = run("single"), run("sharded")
    for rs, rh in zip(single, sharded):
        np.testing.assert_array_equal(rs.nn_idx, rh.nn_idx)
        np.testing.assert_array_equal(rs.nn_dist, rh.nn_dist)
        assert rs.rebuilt == rh.rebuilt


@pytest.mark.parametrize("plan,mesh", [
    ("object_sharded", None),   # None -> every visible device
    ("hybrid", None),           # None -> balanced factorization
])
def test_engine_object_plan_parity_over_ticks(plan, mesh):
    """A session under the object-axis plans == plan=single, tick for tick,
    bitwise on results.  (Stats — iterations/candidates — legitimately differ:
    local trees prune differently; the canonical-selection contract makes
    results partition-invariant anyway, see DESIGN.md §12.)"""
    def run(p, m):
        sess = KnnSession(
            ServiceSpec(k=6, th_quad=16, l_max=5, window=32, chunk=64,
                         plan=p, mesh_shape=m)
        )
        w = make_workload(600, "gaussian", seed=2, hotspots=4)
        return run_ticks(sess, w, ticks=3)

    for rs, rh in zip(run("single", None), run(plan, mesh)):
        np.testing.assert_array_equal(rs.nn_idx, rh.nn_idx)
        np.testing.assert_array_equal(rs.nn_dist, rh.nn_dist)


# -------------------------------------------- forced 8-device mesh (real XLA)

def test_sharded_determinism_on_forced_8_device_mesh():
    """The acceptance criterion on real multi-device XLA: an 8-device CPU grid
    (forced host devices) produces bit-identical results to the single plan on
    all three workload families for EVERY mesh plan — sharded (8-way query),
    object_sharded (8-way object) and hybrid (the 2x4 grid) — session path
    included.

    Runs in a subprocess because the device count must be set before jax init.
    """
    code = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import numpy as np, jax, jax.numpy as jnp
assert jax.device_count() == 8, jax.device_count()
from repro.api import KnnSession, ServiceSpec
from repro.core import build_index, knn_query_batch_chunked
from repro.data import make_workload
from repro.testing import run_ticks

MESHES = [("sharded", 8), ("object_sharded", 8), ("hybrid", (2, 4))]
for dist in ("uniform", "gaussian", "network"):
    w = make_workload(500, dist, seed=5)
    pts = w.positions(); qpos, qid = w.query_batch()
    idx = build_index(jnp.asarray(pts), jnp.zeros(2), 22500.0, l_max=5, th_quad=24)
    a_i, a_d, _ = knn_query_batch_chunked(idx, qpos, qid, k=6, window=32, chunk=32, plan="single")
    for plan, mesh in MESHES:
        b_i, b_d, _ = knn_query_batch_chunked(idx, qpos, qid, k=6, window=32, chunk=32, plan=plan, num_devices=mesh)
        np.testing.assert_array_equal(a_i, b_i, err_msg=f"{dist}/{plan}")
        np.testing.assert_array_equal(a_d, b_d, err_msg=f"{dist}/{plan}")

w = make_workload(400, "gaussian", seed=3, hotspots=3)
ref = run_ticks(KnnSession(ServiceSpec(k=4, th_quad=16, l_max=5, window=32, chunk=32)),
                make_workload(400, "gaussian", seed=3, hotspots=3), ticks=2)
for plan, mesh in MESHES:
    sess = KnnSession(ServiceSpec(k=4, th_quad=16, l_max=5, window=32, chunk=32, plan=plan, mesh_shape=mesh))
    res = run_ticks(sess, make_workload(400, "gaussian", seed=3, hotspots=3), ticks=2)
    assert res[0].nn_dist.shape == (400, 4)
    for r, s in zip(ref, res):
        np.testing.assert_array_equal(r.nn_idx, s.nn_idx, err_msg=plan)
        np.testing.assert_array_equal(r.nn_dist, s.nn_dist, err_msg=plan)
print("SHARDED_8DEV_OK")
"""
    env = dict(os.environ, PYTHONPATH=SRC)
    env.pop("XLA_FLAGS", None)  # the child pins its own device count
    r = subprocess.run(
        [sys.executable, "-c", code], env=env, capture_output=True, text=True
    )
    assert r.returncode == 0, (r.stdout[-1000:], r.stderr[-3000:])
    assert "SHARDED_8DEV_OK" in r.stdout
