"""S9: multi-tenant serving soak — tail latency + cache hits under Zipf arrival.

The acceptance probe of the serving layer (DESIGN.md §16): an OPEN-LOOP soak
of a :class:`repro.serve.KnnServer` on a forced 8-device host grid.  Per
tick, a Poisson number of tenant *requests* arrive — each request retargets
one tenant (round-robin) onto a query group drawn Zipf-style from a shared
hotspot pool, so tenants overlap heavily on the popular groups — and a
controlled fraction of the objects teleports every ``motion_every``-th tick
(fed as a per-tenant delta, round-robin).  The arrival schedule is fixed
up front and never waits on service (open loop): a slow tick eats the next
arrivals late, which is exactly what makes the TAIL of the latency
distribution honest.  Per row we record:

* ``p50_ms / p95_ms / p99_ms`` — post-warmup attributable serve latency
  (``ServerTickResult.wall_s`` = staging + device drain + assembly; host
  idle and compile excluded by construction);
* ``dedup_rate`` / ``cache_rate`` — post-warmup fractions of logical tenant
  rows served without fresh device work, reported SEPARATELY: intra-tick
  dedup (overlapping pool groups fold into one computed row) vs. cross-tick
  cache replay (rows served from a still-valid entry).  ``hit_rate`` keeps
  the combined number; a nonzero combined rate under Zipf overlap is the
  acceptance bar, and under ``--invalidations epoch,spatial`` the cache
  column is what shows spatial invalidation surviving unrelated motion;
* ``cache`` — the ResultCache lifetime counters (lookups/hits/insertions/
  evictions/invalidations) and the epoch count actually consumed.

Each row runs in a subprocess because
``--xla_force_host_platform_device_count`` must be set before jax init; the
children run with ``JAX_PLATFORMS=cpu`` (rows say ``platform: "cpu"``), so
on a TPU host the chip stays the parent's.

  PYTHONPATH=src python benchmarks/s9_soak.py [--objects N] [--ticks T]
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

DEFAULT_PLANS = (
    ("single", "", "equal"),
    ("sharded", "8", "cost_balanced"),
    ("hybrid", "2x4", "cost_balanced"),
)
DEFAULT_DEVICES = 8
SIDE = 22_500.0


def _parse_mesh(mesh: str):
    if not mesh:
        return None
    if "x" in mesh:
        q, o = mesh.split("x")
        return (int(q), int(o))
    return int(mesh)


def _child(args) -> None:
    """One (plan, partitioner) soak row; prints a tagged JSON line."""
    import numpy as np

    import jax

    from repro.api import ServiceSpec
    from repro.data import make_workload
    from repro.serve import KnnServer

    n = args.objects
    T = args.tenants
    H = args.pool
    g = args.group
    rng = np.random.default_rng(0)
    w = make_workload(n, "zipf", seed=0, zipf_a=args.zipf_a,
                      hotspot_sigma_frac=0.003)
    pts = np.asarray(w.positions(), np.float32)

    server = KnnServer(ServiceSpec(
        k=args.k, th_quad=96, l_max=7, window=128, chunk=args.chunk,
        plan=args.plan, mesh_shape=_parse_mesh(args.mesh),
        partitioner=args.partitioner,
    ), invalidation=args.invalidation)
    server.ingest_objects(pts)
    tenants = [server.admit(f"t{i}", quota=g) for i in range(T)]

    # the shared hotspot pool: H query groups of g rows, each a tight cloud
    # around a (Zipf-placed) object — what tenants overlap ON
    pool = []
    for _ in range(H):
        c = pts[int(rng.integers(n))]
        pool.append(np.asarray(
            c + rng.normal(0.0, SIDE * 0.002, (g, 2)), np.float32
        ))

    def zipf_group() -> int:
        return int((rng.zipf(args.zipf_a) - 1) % H)

    current = {}
    for i, t in enumerate(tenants):
        j = zipf_group()
        current[i] = (t.register_queries(pool[j]), j)

    # the OPEN-LOOP schedule: arrivals + motion per tick, fixed up front —
    # a slow tick never thins the load behind it
    arrivals = rng.poisson(args.lam, args.ticks)
    d = max(1, int(round(n * args.churn)))
    motion = [
        args.motion_every and t > 0 and t % args.motion_every == 0
        for t in range(args.ticks)
    ]

    event_i = 0
    cur = pts.copy()
    walls, served_at, computed_at = [], 0, 0
    dedup_at, cache_at = 0, 0
    rebuilds = 0
    for tick in range(args.ticks):
        for _ in range(int(arrivals[tick])):
            i = event_i % T
            event_i += 1
            old_handle, _ = current[i]
            tenants[i].drop_queries(old_handle)
            j = zipf_group()
            current[i] = (tenants[i].register_queries(pool[j]), j)
        if motion[tick]:
            ids = rng.choice(n, d, replace=False).astype(np.int32)
            new = rng.uniform(0, SIDE, (d, 2)).astype(np.float32)
            cur[ids] = new
            tenants[tick % T].update_objects(ids, new)
        res = server.submit().result()
        rebuilds += bool(res.rebuilt)
        if tick >= args.warmup:
            # attributable latency, not the host loop's wall: staging +
            # drain + assembly, idle and compile excluded by construction
            walls.append(res.wall_s)
            served_at += res.rows_total
            computed_at += res.rows_computed
            dedup_at += res.dedup_hit_rows
            cache_at += res.cache_hit_rows
    walls = np.asarray(walls)
    p50, p95, p99 = (float(x) for x in np.percentile(walls, [50, 95, 99]))
    row = {
        "plan": args.plan,
        "mesh": args.mesh,
        "partitioner": args.partitioner,
        "invalidation": args.invalidation,
        "platform": jax.devices()[0].platform,
        "devices": int(jax.device_count()),
        "objects": n,
        "tenants": T,
        "pool": H,
        "group_rows": g,
        "lam": args.lam,
        "zipf_a": args.zipf_a,
        "ticks": args.ticks,
        "warmup": args.warmup,
        "churn": args.churn,
        "motion_every": args.motion_every,
        "k": args.k,
        "chunk": args.chunk,
        "arrivals": int(arrivals.sum()),
        "rebuilds": rebuilds,
        "p50_ms": p50 * 1e3,
        "p95_ms": p95 * 1e3,
        "p99_ms": p99 * 1e3,
        "rows_served": served_at,
        "rows_computed": computed_at,
        "dedup_rate": dedup_at / max(served_at, 1),
        "cache_rate": cache_at / max(served_at, 1),
        "hit_rate": (dedup_at + cache_at) / max(served_at, 1),
        "epochs": int(server.cache.epoch),
        "cache": server.cache.stats.as_dict(),
    }
    print("S9ROW " + json.dumps(row), flush=True)


def run(
    objects: int = 20_000,
    tenants: int = 16,
    pool: int = 8,
    group: int = 64,
    lam: float = 4.0,
    zipf_a: float = 1.2,
    ticks: int = 40,
    warmup: int = 4,
    churn: float = 0.02,
    motion_every: int = 2,
    k: int = 16,
    chunk: int = 256,
    plans=DEFAULT_PLANS,
    invalidations=("epoch",),
    churns=None,
    devices: int = DEFAULT_DEVICES,
    check: bool = True,
    out: str | None = "BENCH_soak.json",
):
    """Soak each (plan, partitioner) × invalidation × churn row on forced
    host devices.

    ``invalidations`` selects the server's cache-invalidation modes to
    sweep; ``churns`` (None = just ``churn``) the per-motion-tick moved
    fraction — the epoch-vs-spatial comparison at 1% and 10% churn is the
    invalidation axis the CI soak uploads.  Returns the row list; with
    ``check`` (full runs) asserts the §16 acceptance criterion — a NONZERO
    hit rate under the Zipf-overlapping tenant workload on every row.
    """
    from repro.launch.mesh import forced_cpu_env

    here = os.path.dirname(os.path.abspath(__file__))
    src = os.path.join(here, "..", "src")
    if churns is None:
        churns = (churn,)
    rows = []
    for plan, mesh, partitioner in plans:
        for invalidation in invalidations:
            for c in churns:
                env = forced_cpu_env(devices)
                env["PYTHONPATH"] = src + os.pathsep + env.get("PYTHONPATH", "")
                cmd = [
                    sys.executable, os.path.abspath(__file__), "--child",
                    "--plan", plan, "--mesh", mesh,
                    "--partitioner", partitioner,
                    "--invalidation", invalidation,
                    "--objects", str(objects), "--tenants", str(tenants),
                    "--pool", str(pool), "--group", str(group),
                    "--lam", str(lam), "--zipf-a", str(zipf_a),
                    "--ticks", str(ticks), "--warmup", str(warmup),
                    "--churn", str(c), "--motion-every", str(motion_every),
                    "--k", str(k), "--chunk", str(chunk),
                ]
                r = subprocess.run(cmd, env=env, capture_output=True,
                                   text=True)
                if r.returncode != 0:
                    raise RuntimeError(
                        f"s9 child (plan={plan}, partitioner={partitioner}, "
                        f"invalidation={invalidation}, churn={c}) failed:\n"
                        + r.stderr[-2000:]
                    )
                row = json.loads(next(
                    l for l in r.stdout.splitlines()
                    if l.startswith("S9ROW ")
                )[6:])
                rows.append(row)
                print(
                    f"s9_soak/{plan}_{partitioner}_{invalidation}_c{c:g},"
                    f"p50={row['p50_ms']:.1f}ms,p95={row['p95_ms']:.1f}ms,"
                    f"p99={row['p99_ms']:.1f}ms,dedup={row['dedup_rate']:.2f},"
                    f"cache={row['cache_rate']:.2f}", flush=True)
    if check:
        for row in rows:
            assert row["hit_rate"] > 0.0, (
                "no dedup/cache hits under the Zipf-overlapping tenant "
                f"workload: {row}"
            )
    if out:
        rec = {
            "schema": 1,
            "unit": "milliseconds",
            "devices": devices,
            "rows": rows,
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
    ap.add_argument("--plan", default="sharded")
    ap.add_argument("--mesh", default="8",
                    help="mesh shape: '' (single), '8' (1-D) or '2x4'")
    ap.add_argument("--partitioner", default="cost_balanced")
    ap.add_argument("--invalidation", default="epoch",
                    choices=("epoch", "spatial"),
                    help="cache invalidation mode for the child row")
    ap.add_argument("--invalidations", default=None,
                    help="comma list of invalidation modes to sweep "
                         "(e.g. 'epoch,spatial'; default: --invalidation)")
    ap.add_argument("--churns", default=None,
                    help="comma list of churn fractions to sweep "
                         "(e.g. '0.01,0.10'; default: --churn)")
    ap.add_argument("--objects", type=int, default=20_000)
    ap.add_argument("--tenants", type=int, default=16)
    ap.add_argument("--pool", type=int, default=8,
                    help="shared hotspot query-group pool size")
    ap.add_argument("--group", type=int, default=64,
                    help="query rows per pool group")
    ap.add_argument("--lam", type=float, default=4.0,
                    help="Poisson arrival rate (tenant requests per tick)")
    ap.add_argument("--zipf-a", type=float, default=1.2)
    ap.add_argument("--ticks", type=int, default=40)
    ap.add_argument("--warmup", type=int, default=4,
                    help="ticks excluded from the latency/hit accounting")
    ap.add_argument("--churn", type=float, default=0.02)
    ap.add_argument("--motion-every", type=int, default=2,
                    help="teleport a churn-fraction every Nth tick (0 = "
                         "never); non-motion ticks serve from the cache")
    ap.add_argument("--k", type=int, default=16)
    ap.add_argument("--chunk", type=int, default=256)
    ap.add_argument("--devices", type=int, default=DEFAULT_DEVICES)
    ap.add_argument("--no-check", action="store_true",
                    help="skip the nonzero-hit-rate assertion")
    ap.add_argument("--plans", default=None,
                    help="comma list of plan[:mesh[:partitioner]] entries, "
                         "e.g. 'sharded:8:cost_balanced' (default: full "
                         "matrix)")
    ap.add_argument("--out", default="BENCH_soak.json")
    args = ap.parse_args()
    if args.child:
        _child(args)
        return
    plans = (tuple((p.split(":") + ["", "equal"])[:3]
                   for p in args.plans.split(","))
             if args.plans else DEFAULT_PLANS)
    invalidations = (tuple(args.invalidations.split(","))
                     if args.invalidations else (args.invalidation,))
    churns = (tuple(float(c) for c in args.churns.split(","))
              if args.churns else None)
    run(objects=args.objects, tenants=args.tenants, pool=args.pool,
        group=args.group, lam=args.lam, zipf_a=args.zipf_a, ticks=args.ticks,
        warmup=args.warmup, churn=args.churn, motion_every=args.motion_every,
        k=args.k, chunk=args.chunk, plans=plans, invalidations=invalidations,
        churns=churns, devices=args.devices,
        check=not args.no_check, out=args.out)


if __name__ == "__main__":
    main()
