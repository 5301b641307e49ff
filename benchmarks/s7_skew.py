"""S7: Zipf exponent x plan x partitioner — the straggler-gap sweep.

The acceptance probe of the cost-balanced partitioning seam (DESIGN.md §13):
a Zipf-skewed moving-object workload is served on a forced 8-device host
grid under every mesh plan (``sharded`` 8-way, ``object_sharded`` 8-way,
``hybrid`` 2x4) x partitioner (``equal`` | ``cost_balanced``), through the
session API (persistent queries + delta object updates, so the measured-work
EMA feedback loop is live).  Per row we record:

* ``gap_mean`` / ``gap_max`` — the straggler gap, max/mean per-shard
  candidate volume (``TickResult.shard_candidates``; 1.0 = perfectly
  balanced, 8.0 = one device does everything) over the steady ticks;
* ``tick_s_median`` — wall per tick (on a CPU host the forced devices share
  cores, so this shows the *overhead* of boundary computation + masked
  capacity slack, not real speedup — the gap column is what an accelerator
  mesh converts to wall-clock);
* ``bit_identical`` — every tick's results compared bitwise against a
  lockstep ``single``-plan session (the §12/§13 contract, asserted).

Each row runs in a subprocess because
``--xla_force_host_platform_device_count`` must be set before jax init; the
children run with ``JAX_PLATFORMS=cpu`` (rows say ``platform: "cpu"``), so
on a TPU host the chip stays the parent's.

  PYTHONPATH=src python benchmarks/s7_skew.py [--objects N] [--ticks T]
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

DEFAULT_EXPONENTS = (1.2, 1.6)
DEFAULT_PLANS = (("sharded", "8"), ("object_sharded", "8"), ("hybrid", "2x4"))
DEFAULT_DEVICES = 8


def _parse_mesh(mesh: str):
    if not mesh:
        return None
    if "x" in mesh:
        q, o = mesh.split("x")
        return (int(q), int(o))
    return int(mesh)


def _child(args) -> None:
    """One (zipf_a, plan, partitioner) row; prints a tagged JSON line."""
    import numpy as np

    import jax

    from repro.api import KnnSession, ServiceSpec
    from repro.core import straggler_gap
    from repro.data import make_workload

    def session(plan, mesh, partitioner):
        return KnnSession(ServiceSpec(
            k=args.k, th_quad=96, l_max=7, window=128, chunk=args.chunk,
            plan=plan, mesh_shape=mesh, partitioner=partitioner,
        ))

    w = make_workload(args.objects, "zipf", seed=0, zipf_a=args.zipf_a,
                      hotspot_sigma_frac=0.003)
    qid = np.arange(args.objects, dtype=np.int32)
    sess = session(args.plan, _parse_mesh(args.mesh), args.partitioner)
    ref = session("single", None, "equal")
    pts = w.positions()
    for s in (sess, ref):
        s.ingest_objects(pts)
    hq = sess.register_queries(pts, qid)
    hr = ref.register_queries(pts, qid)

    gaps, walls, cands, bit_identical = [], [], [], True
    for t in range(args.ticks):
        r = sess.submit().result()
        r_ref = ref.submit().result()
        bit_identical &= bool(
            np.array_equal(r.nn_idx, r_ref.nn_idx)
            and np.array_equal(r.nn_dist, r_ref.nn_dist)
        )
        assert bit_identical, f"tick {t}: results diverged from single"
        if t >= 1:  # skip the build+compile tick
            gaps.append(straggler_gap(r.shard_candidates))
            walls.append(r.wall_s)
            cands.append(r.candidates)
        w.advance()
        pts = w.positions()
        sess.update_objects(qid, pts)
        sess.update_queries(hq, pts)
        ref.update_objects(qid, pts)
        ref.update_queries(hr, pts)

    row = {
        "zipf_a": args.zipf_a,
        "plan": args.plan,
        "mesh": args.mesh,
        "partitioner": args.partitioner,
        "platform": jax.devices()[0].platform,
        "devices": int(jax.device_count()),
        "objects": args.objects,
        "ticks": args.ticks,
        "k": args.k,
        "chunk": args.chunk,
        "gap_mean": float(np.mean(gaps)),
        "gap_max": float(np.max(gaps)),
        "tick_s_median": float(np.median(walls)),
        "candidates_per_tick": float(np.mean(cands)),
        "bit_identical": bit_identical,
    }
    print("S7ROW " + json.dumps(row), flush=True)


def run(
    objects: int = 4_096,
    ticks: int = 4,
    k: int = 8,
    chunk: int = 128,
    exponents=DEFAULT_EXPONENTS,
    plans=DEFAULT_PLANS,
    devices: int = DEFAULT_DEVICES,
    out: str | None = "BENCH_skew.json",
):
    """Sweep zipf_a x plan x partitioner on forced host devices.

    Returns the row list; the JSON artifact additionally carries a
    per-(zipf_a, plan) summary with the equal -> cost_balanced gap ratio —
    the headline number (>1 = cost_balanced is better balanced).
    """
    from repro.launch.mesh import forced_cpu_env

    here = os.path.dirname(os.path.abspath(__file__))
    src = os.path.join(here, "..", "src")
    rows = []
    for zipf_a in exponents:
        for plan, mesh in plans:
            for partitioner in ("equal", "cost_balanced"):
                env = forced_cpu_env(devices)
                env["PYTHONPATH"] = src + os.pathsep + env.get("PYTHONPATH", "")
                cmd = [
                    sys.executable, os.path.abspath(__file__), "--child",
                    "--plan", plan, "--mesh", mesh,
                    "--partitioner", partitioner,
                    "--zipf-a", str(zipf_a),
                    "--objects", str(objects), "--ticks", str(ticks),
                    "--k", str(k), "--chunk", str(chunk),
                ]
                r = subprocess.run(cmd, env=env, capture_output=True,
                                   text=True)
                if r.returncode != 0:
                    raise RuntimeError(
                        f"s7 child (zipf_a={zipf_a}, plan={plan}, "
                        f"partitioner={partitioner}) failed:\n"
                        + r.stderr[-2000:]
                    )
                row = json.loads(next(
                    l for l in r.stdout.splitlines() if l.startswith("S7ROW ")
                )[6:])
                rows.append(row)
                print(f"s7_skew/a{zipf_a}_{plan}_{partitioner},"
                      f"{row['tick_s_median'] * 1e6:.1f},"
                      f"gap={row['gap_mean']:.3f}", flush=True)

    summary = []
    for zipf_a in exponents:
        for plan, _ in plans:
            pair = {
                row["partitioner"]: row for row in rows
                if row["zipf_a"] == zipf_a and row["plan"] == plan
            }
            summary.append({
                "zipf_a": zipf_a,
                "plan": plan,
                "gap_equal": pair["equal"]["gap_mean"],
                "gap_cost_balanced": pair["cost_balanced"]["gap_mean"],
                "gap_ratio": pair["equal"]["gap_mean"]
                / pair["cost_balanced"]["gap_mean"],
            })
    # the acceptance criterion: cost_balanced tightens the gap on at least
    # one sharded plan at every exponent.  Needs balancing freedom: with
    # fewer than ~2 chunks per device (objects/chunk <= devices) contiguous
    # chunk-granular boundaries cannot move and the ratio degenerates to 1.
    for zipf_a in exponents:
        assert any(s["gap_ratio"] > 1.0 for s in summary
                   if s["zipf_a"] == zipf_a), (
            f"no plan improved at zipf_a={zipf_a} — if objects/chunk "
            f"({objects}/{chunk}) is close to the device count "
            f"({devices}), boundaries have no freedom to move; "
            f"{summary}")
    if out:
        rec = {
            "schema": 1,
            "unit": "seconds",
            "devices": devices,
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
    ap.add_argument("--plan", default="sharded")
    ap.add_argument("--mesh", default="8",
                    help="mesh shape: '8' (1-D plans) or '2x4' (hybrid)")
    ap.add_argument("--partitioner", default="equal")
    ap.add_argument("--zipf-a", type=float, default=1.6)
    ap.add_argument("--objects", type=int, default=4_096)
    ap.add_argument("--ticks", type=int, default=4)
    ap.add_argument("--k", type=int, default=8)
    ap.add_argument("--chunk", type=int, default=128)
    ap.add_argument("--out", default="BENCH_skew.json")
    args = ap.parse_args()
    if args.child:
        _child(args)
        return
    run(objects=args.objects, ticks=args.ticks, k=args.k, chunk=args.chunk,
        out=args.out)


if __name__ == "__main__":
    main()
