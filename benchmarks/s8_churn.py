"""S8: churn rate x maintenance mode x plan — pay for churn, not for N.

The acceptance probe of the incremental index-maintenance seam (DESIGN.md
§15): a Zipf-skewed moving-object workload where a controlled fraction of
the objects TELEPORT each tick (uniform re-draw over the region — the
worst case for the splice: Morton ranks scatter across the whole order and,
under the mesh plans, rows cross shard boundaries), served on a forced
8-device host grid under ``maintenance="rebuild" | "incremental"`` across
the plan sweep.  Per row we record:

* ``reindex_stage_s`` — the reindex-stage time of the maintenance mode the
  session actually ran (per-stage counter: the stage is timed as its own
  jitted device program at the session's exact N / delta-pad shapes,
  ``block_until_ready``-bracketed, min of ``reps``).  Both variants are
  always reported (``reindex_rebuild_s`` / ``reindex_incremental_s``) so
  the artifact carries the full rebuild-vs-delta curve.  For the plans with
  an object mesh axis (object_sharded / hybrid) the stage is PLAN-AWARE:
  it adds the per-device local-tree refresh the shard_map body runs each
  tick — ``build_index`` over one ceil(N/R)-row slice under ``rebuild``
  vs the derived local tree (masked slice + interval pyramid off the global
  starts, ``core.plan._local_index_derived``) under ``incremental`` — split
  out as ``local_rebuild_s`` / ``local_derived_s`` next to the global
  ``global_rebuild_s`` / ``global_incremental_s`` components;
* ``mode_used`` — what the session's scheduler chose in steady state: at
  100% churn the budget (``churn_budget=0.25``) correctly defers the
  incremental spec to the full refresh, and the row shows it;
* ``tick_s_median`` — whole-tick wall through the session API (on a CPU
  host the query sweep shares cores with the forced devices, so the stage
  column is the honest churn-scaling signal);
* ``bit_identical`` — every tick's results compared bitwise against a
  lockstep single-plan REBUILD session (the §15 contract, asserted), plus a
  bitwise index comparison of the standalone stage programs at benchmark
  size.

Each row runs in a subprocess because
``--xla_force_host_platform_device_count`` must be set before jax init; the
children run with ``JAX_PLATFORMS=cpu`` (rows say ``platform: "cpu"``), so
on a TPU host the chip stays the parent's.

  PYTHONPATH=src python benchmarks/s8_churn.py [--objects N] [--ticks T]
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

DEFAULT_CHURNS = (0.001, 0.01, 0.1, 1.0)
DEFAULT_PLANS = (("single", ""), ("sharded", "8"), ("object_sharded", "8"),
                 ("hybrid", "2x4"))
DEFAULT_DEVICES = 8
DELTA_PAD = 256
CHURN_BUDGET = 0.25
SIDE = 22_500.0


def _parse_mesh(mesh: str):
    if not mesh:
        return None
    if "x" in mesh:
        q, o = mesh.split("x")
        return (int(q), int(o))
    return int(mesh)


def _child(args) -> None:
    """One (churn, maintenance, plan) row; prints a tagged JSON line."""
    import numpy as np

    import jax
    import jax.numpy as jnp

    from repro.api import KnnSession, ServiceSpec
    from repro.core import (
        build_index,
        object_shard_capacity,
        pad_capacity,
        reindex_objects,
        reindex_objects_delta,
    )
    from repro.data import make_workload

    n = args.objects
    d = max(1, int(round(n * args.churn)))
    rng = np.random.default_rng(0)
    w = make_workload(n, "zipf", seed=0, zipf_a=args.zipf_a,
                      hotspot_sigma_frac=0.003)
    pts = np.asarray(w.positions(), np.float32)
    nq = min(args.queries, n)
    qpos = pts[:nq].copy()
    qid = np.arange(nq, dtype=np.int32)

    def session(plan, mesh, maintenance):
        return KnnSession(ServiceSpec(
            k=args.k, th_quad=96, l_max=7, window=128, chunk=args.chunk,
            plan=plan, mesh_shape=mesh, maintenance=maintenance,
            churn_budget=CHURN_BUDGET, delta_pad=DELTA_PAD,
        ))

    sess = session(args.plan, _parse_mesh(args.mesh), args.maintenance)
    ref = session("single", None, "rebuild")
    for s in (sess, ref):
        s.ingest_objects(pts)
    sess.register_queries(qpos, qid)
    ref.register_queries(qpos, qid)

    cur = pts.copy()
    walls, modes, bit_identical = [], [], True
    for t in range(args.ticks):
        r = sess.submit().result()
        r_ref = ref.submit().result()
        bit_identical &= bool(
            np.array_equal(r.nn_idx, r_ref.nn_idx)
            and np.array_equal(r.nn_dist, r_ref.nn_dist)
        )
        assert bit_identical, f"tick {t}: diverged from single/rebuild"
        if t >= 1:  # skip the build+compile tick
            walls.append(r.wall_s)
            modes.append(r.maintenance)
        ids = rng.choice(n, d, replace=False).astype(np.int32)
        new = rng.uniform(0, SIDE, (d, 2)).astype(np.float32)
        cur[ids] = new
        sess.update_objects(ids, new)
        ref.update_objects(ids, new)
    mode_used = max(set(modes), key=modes.count)

    # reindex stage as its own device program, at the session's shapes: the
    # tick program is fused, so stage attribution needs standalone timing —
    # the same ops _tick_step inlines, same N, same padded delta length.
    idx = build_index(jnp.asarray(cur), jnp.zeros(2, jnp.float32), SIDE,
                      l_max=7, th_quad=96)
    ids = np.sort(rng.choice(n, d, replace=False).astype(np.int32))
    nxt = cur.copy()
    nxt[ids] = rng.uniform(0, SIDE, (d, 2)).astype(np.float32)
    pad = pad_capacity(d, DELTA_PAD) - d
    padded = np.concatenate([ids, np.full(pad, n, np.int32)])
    old_pos = np.concatenate([cur[ids], np.zeros((pad, 2), np.float32)])
    nxt_dev, padded_dev = jnp.asarray(nxt), jnp.asarray(padded)
    old_dev = jnp.asarray(old_pos)
    full = jax.block_until_ready(reindex_objects(idx, nxt_dev))
    inc = jax.block_until_ready(
        reindex_objects_delta(idx, nxt_dev, padded_dev, old_dev))
    for f in ("pos", "ids", "codes", "starts", "pyramid"):
        assert np.array_equal(np.asarray(getattr(full, f)),
                              np.asarray(getattr(inc, f))), f
    bit_identical &= True

    def stage_time(fn, *fa):
        # min over reps: the 8 forced host devices contend for cores, and
        # scheduler noise only ever ADDS time — the floor is the honest
        # per-device stage cost
        ts = []
        for _ in range(args.reps):
            t0 = time.perf_counter()
            jax.block_until_ready(fn(*fa))
            ts.append(time.perf_counter() - t0)
        return float(np.min(ts))

    t_rebuild = stage_time(reindex_objects, idx, nxt_dev)
    t_incremental = stage_time(reindex_objects_delta, idx, nxt_dev,
                               padded_dev, old_dev)

    # plan-aware local-tree component: the object-mesh plans refresh R
    # device-local quadtrees per tick inside shard_map — under "rebuild"
    # each device re-sorts its ceil(N/R)-row slice (build_index); under
    # "incremental" it derives the local tree from the already-spliced
    # global order (masked slice + interval pyramid, no per-device sort).
    # Timed standalone at one full shard's exact capacity — devices run
    # concurrently, so one shard's cost IS the per-tick stage cost.
    mesh = _parse_mesh(args.mesh)
    r_o = 1
    if args.plan == "object_sharded":
        r_o = int(mesh)
    elif args.plan == "hybrid":
        r_o = int(mesh[1])
    t_local_rebuild = t_local_derived = 0.0
    if r_o > 1:
        from repro.core import plan as plan_mod

        capo = object_shard_capacity(n, r_o)
        opos, oids, ocodes = plan_mod._pad_object_tail(full, capo)
        own = min(capo, n)  # shard 0 is always full
        opos_l, oids_l, codes_l = opos[:capo], oids[:capo], ocodes[:capo]
        clone_code = codes_l[own - 1]

        @jax.jit
        def _loc_rebuild(p, i):
            return plan_mod._local_index(
                p, i, full.origin, full.side, l_max=7, th_quad=96)

        @jax.jit
        def _loc_derived(p, i, c, cc, gs):
            return plan_mod._local_index_derived(
                full.origin, full.side, p, i, c, cc, gs, jnp.int32(0),
                jnp.int32(own), capo, l_max=7, th_quad=96)

        loc_reb = jax.block_until_ready(_loc_rebuild(opos_l, oids_l))
        loc_der = jax.block_until_ready(_loc_derived(
            opos_l, oids_l, codes_l, clone_code, full.starts))
        for f in ("pos", "ids", "codes", "starts", "pyramid", "leaf_level"):
            assert np.array_equal(np.asarray(getattr(loc_reb, f)),
                                  np.asarray(getattr(loc_der, f))), f
        t_local_rebuild = stage_time(_loc_rebuild, opos_l, oids_l)
        t_local_derived = stage_time(_loc_derived, opos_l, oids_l, codes_l,
                                     clone_code, full.starts)

    reb_total = t_rebuild + t_local_rebuild
    inc_total = t_incremental + t_local_derived
    row = {
        "churn": args.churn,
        "delta_rows": d,
        "maintenance": args.maintenance,
        "mode_used": mode_used,
        "plan": args.plan,
        "mesh": args.mesh,
        "platform": jax.devices()[0].platform,
        "devices": int(jax.device_count()),
        "objects": n,
        "ticks": args.ticks,
        "k": args.k,
        "chunk": args.chunk,
        "object_axis": r_o,
        "reindex_stage_s": (inc_total if mode_used == "incremental"
                            else reb_total),
        "reindex_rebuild_s": reb_total,
        "reindex_incremental_s": inc_total,
        "global_rebuild_s": t_rebuild,
        "global_incremental_s": t_incremental,
        "local_rebuild_s": t_local_rebuild,
        "local_derived_s": t_local_derived,
        "tick_s_median": float(np.median(walls)),
        "bit_identical": bit_identical,
    }
    print("S8ROW " + json.dumps(row), flush=True)


def run(
    objects: int = 50_000,
    ticks: int = 5,
    k: int = 8,
    chunk: int = 256,
    queries: int = 512,
    reps: int = 15,
    churns=DEFAULT_CHURNS,
    plans=DEFAULT_PLANS,
    devices: int = DEFAULT_DEVICES,
    check: bool = True,
    out: str | None = "BENCH_churn.json",
):
    """Sweep churn x maintenance x plan on forced host devices.

    Returns the row list; the JSON artifact additionally carries a
    per-(churn, plan) summary with the rebuild -> incremental reindex-stage
    ratio — the headline number (>1 = the delta path is cheaper).  With
    ``check`` (full runs), asserts the §15 acceptance criterion: >= 3x
    stage reduction at every churn level <= 10%.
    """
    from repro.launch.mesh import forced_cpu_env

    here = os.path.dirname(os.path.abspath(__file__))
    src = os.path.join(here, "..", "src")
    rows = []
    for churn in churns:
        for plan, mesh in plans:
            for maintenance in ("rebuild", "incremental"):
                env = forced_cpu_env(devices)
                env["PYTHONPATH"] = src + os.pathsep + env.get("PYTHONPATH", "")
                cmd = [
                    sys.executable, os.path.abspath(__file__), "--child",
                    "--plan", plan, "--mesh", mesh,
                    "--maintenance", maintenance,
                    "--churn", str(churn),
                    "--objects", str(objects), "--ticks", str(ticks),
                    "--k", str(k), "--chunk", str(chunk),
                    "--queries", str(queries), "--reps", str(reps),
                ]
                r = subprocess.run(cmd, env=env, capture_output=True,
                                   text=True)
                if r.returncode != 0:
                    raise RuntimeError(
                        f"s8 child (churn={churn}, plan={plan}, "
                        f"maintenance={maintenance}) failed:\n"
                        + r.stderr[-2000:]
                    )
                row = json.loads(next(
                    l for l in r.stdout.splitlines() if l.startswith("S8ROW ")
                )[6:])
                rows.append(row)
                print(f"s8_churn/c{churn}_{plan}_{maintenance},"
                      f"{row['reindex_stage_s'] * 1e6:.1f},"
                      f"mode={row['mode_used']}", flush=True)

    summary = []
    for churn in churns:
        for plan, _ in plans:
            pair = {
                row["maintenance"]: row for row in rows
                if row["churn"] == churn and row["plan"] == plan
            }
            reb = pair["rebuild"]["reindex_stage_s"]
            inc = pair["incremental"]["reindex_stage_s"]
            summary.append({
                "churn": churn,
                "plan": plan,
                "object_axis": pair["incremental"]["object_axis"],
                "delta_rows": pair["incremental"]["delta_rows"],
                "mode_used_incremental": pair["incremental"]["mode_used"],
                "reindex_rebuild_s": reb,
                "reindex_incremental_s": inc,
                "local_rebuild_s": pair["rebuild"]["local_rebuild_s"],
                "local_derived_s": pair["incremental"]["local_derived_s"],
                "stage_ratio": reb / inc if inc > 0 else float("inf"),
            })
    if check:
        # §15 acceptance: the stage pays for churn, not for N — at every
        # churn level <= 10% the incremental stage must be >= 3x cheaper
        # (at 100% churn the budget defers to rebuild and the ratio ~ 1).
        # For the object-mesh plans the stage includes the per-device
        # local-tree refresh, whose derived path saves a capo-row sort but
        # keeps an O(4**l_max) floor — the sharded acceptance bar is >= 2x
        # (ISSUE 10), still on the plan-aware total.
        for s in summary:
            if s["churn"] <= 0.1:
                bar = 2.0 if s["object_axis"] > 1 else 3.0
                assert s["mode_used_incremental"] == "incremental", s
                assert s["stage_ratio"] >= bar, (
                    f"incremental reindex not >= {bar}x cheaper at churn "
                    f"{s['churn']} on plan {s['plan']}: {s}"
                )
    if out:
        rec = {
            "schema": 2,
            "unit": "seconds",
            "devices": devices,
            "churn_budget": CHURN_BUDGET,
            "delta_pad": DELTA_PAD,
            "rows": rows,
            "summary": summary,
            "timestamp": time.time(),
        }
        with open(out, "w") as f:
            json.dump(rec, f, indent=2)
        print(f"# wrote {out}", flush=True)
    return rows


def main() -> None:
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))
    ap = argparse.ArgumentParser()
    ap.add_argument("--child", action="store_true", help=argparse.SUPPRESS)
    ap.add_argument("--plan", default="single")
    ap.add_argument("--mesh", default="",
                    help="mesh shape: '' (single), '8' (1-D) or '2x4'")
    ap.add_argument("--maintenance", default="incremental")
    ap.add_argument("--churn", type=float, default=0.01)
    ap.add_argument("--zipf-a", type=float, default=1.6)
    ap.add_argument("--objects", type=int, default=50_000)
    ap.add_argument("--ticks", type=int, default=5)
    ap.add_argument("--k", type=int, default=8)
    ap.add_argument("--chunk", type=int, default=256)
    ap.add_argument("--queries", type=int, default=512)
    ap.add_argument("--reps", type=int, default=15)
    ap.add_argument("--no-check", action="store_true",
                    help="skip the >= 3x stage-reduction assertion "
                         "(small smoke sizes)")
    ap.add_argument("--churns", default=None,
                    help="comma list of churn fractions for the sweep "
                         "(default: %s)" % (DEFAULT_CHURNS,))
    ap.add_argument("--plans", default=None,
                    help="comma list of plan[:mesh] entries, e.g. "
                         "'sharded:8,hybrid:2x4' (default: full matrix)")
    ap.add_argument("--out", default="BENCH_churn.json")
    args = ap.parse_args()
    if args.child:
        _child(args)
        return
    churns = (tuple(float(c) for c in args.churns.split(","))
              if args.churns else DEFAULT_CHURNS)
    plans = (tuple((p.split(":") + [""])[:2] for p in args.plans.split(","))
             if args.plans else DEFAULT_PLANS)
    run(objects=args.objects, ticks=args.ticks, k=args.k, chunk=args.chunk,
        queries=args.queries, reps=args.reps, churns=churns, plans=plans,
        check=not args.no_check, out=args.out)


if __name__ == "__main__":
    main()
