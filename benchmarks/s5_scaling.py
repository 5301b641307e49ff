"""S5: plan x mesh-shape x device-count scaling sweep on forced host devices.

The sweep axis of the ExecutionPlan seam (DESIGN.md §10/§12): the same tick
engine runs under every registered plan at FIXED total query load — ``single``
(the one-device reference row), ``sharded`` at 1/2/4/8 devices on the
("query",) mesh, ``object_sharded`` at 1/2/4/8 on the ("object",) mesh (per-
device object state shrinks with the device count — THE object-axis scaling
row the paper's massive datasets need), and ``hybrid`` on 2-D
(query, object) grids (2x2, 2x4, 4x2) — and per-tick latency + candidates/s
are recorded per (plan, mesh_shape, devices) row into ``BENCH_scaling.json``.

Each row runs in a subprocess because ``--xla_force_host_platform_device_count``
must be set before jax initializes.  The children run with
``JAX_PLATFORMS=cpu`` (their rows say ``platform: "cpu"``) even on a TPU
host, where the chip belongs to the parent.  The forced devices share
the same cores, so this measures the *overhead* of each mesh decomposition
(shard_map fan-out, per-shard index builds, merge tree, psum, gather) rather
than real speedup — the point is that the decompositions are load-bearing
and cheap; accelerator meshes supply the parallelism.

  PYTHONPATH=src python benchmarks/s5_scaling.py [--objects N] [--ticks T]
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

DEFAULT_DEVICE_COUNTS = (1, 2, 4, 8)
DEFAULT_HYBRID_SHAPES = ((2, 2), (2, 4), (4, 2))


def _parse_mesh(mesh: str):
    """CLI mesh spec -> EngineConfig.mesh_shape: '' None, '4' int, '2x4' pair."""
    if not mesh:
        return None
    if "x" in mesh:
        q, o = mesh.split("x")
        return (int(q), int(o))
    return int(mesh)


def _child(args) -> None:
    """One (plan, mesh) row; prints a tagged JSON line for the parent."""
    import numpy as np

    from repro.core import EngineConfig, TickEngine
    from repro.data import make_workload

    import jax

    mesh_shape = _parse_mesh(args.mesh) if args.plan != "single" else None
    eng = TickEngine(
        EngineConfig(k=args.k, th_quad=192, l_max=7, window=128,
                     chunk=args.chunk, plan=args.plan, mesh_shape=mesh_shape)
    )
    w = make_workload(args.objects, "gaussian", seed=0)
    results = eng.run(w, ticks=args.ticks)
    steady = [r.wall_s for r in results[1:]]
    cand = float(np.mean([r.candidates for r in results[1:]]))
    tick_s = float(np.median(steady))
    row = {
        "plan": args.plan,
        "mesh_shape": mesh_shape if isinstance(mesh_shape, int) or mesh_shape
        is None else list(mesh_shape),
        "platform": jax.devices()[0].platform,
        "devices": int(jax.device_count()),
        "objects": args.objects,
        "k": args.k,
        "chunk": args.chunk,
        "ticks": args.ticks,
        "tick_s_median": tick_s,
        "queries_per_s": args.objects / tick_s,
        "candidates_per_s": cand / tick_s,
        "candidates_per_tick": cand,
    }
    print("S5ROW " + json.dumps(row), flush=True)


def run(
    objects: int = 8_000,
    ticks: int = 4,
    k: int = 16,
    chunk: int = 1024,
    device_counts=DEFAULT_DEVICE_COUNTS,
    hybrid_shapes=DEFAULT_HYBRID_SHAPES,
    out: str | None = "BENCH_scaling.json",
):
    """Sweep plan x mesh shape at fixed total Q; returns the row list."""
    from repro.launch.mesh import forced_cpu_env

    here = os.path.dirname(os.path.abspath(__file__))
    src = os.path.join(here, "..", "src")
    rows = []
    sweep = (
        [("single", "", 1)]
        + [("sharded", str(d), d) for d in device_counts]
        + [("object_sharded", str(d), d) for d in device_counts]
        + [("hybrid", f"{q}x{o}", q * o) for q, o in hybrid_shapes]
    )
    for plan, mesh, devices in sweep:
        env = forced_cpu_env(devices)
        env["PYTHONPATH"] = src + os.pathsep + env.get("PYTHONPATH", "")
        cmd = [
            sys.executable, os.path.abspath(__file__), "--child",
            "--plan", plan, "--mesh", mesh,
            "--objects", str(objects), "--ticks", str(ticks),
            "--k", str(k), "--chunk", str(chunk),
        ]
        r = subprocess.run(cmd, env=env, capture_output=True, text=True)
        if r.returncode != 0:
            raise RuntimeError(
                f"s5 child (plan={plan}, mesh={mesh or devices}) failed:\n"
                + r.stderr[-2000:]
            )
        row = json.loads(
            next(l for l in r.stdout.splitlines() if l.startswith("S5ROW "))[6:]
        )
        rows.append(row)
        tag = f"{plan}_{mesh}" if mesh else f"{plan}_d{devices}"
        print(f"s5_scaling/{tag},"
              f"{row['tick_s_median'] * 1e6:.1f},"
              f"qps={row['queries_per_s']:.0f}", flush=True)
    if out:
        rec = {
            "schema": 2,
            "unit": "seconds",
            "fixed_total_queries": objects,
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
    ap.add_argument("--plan", default="single")
    ap.add_argument("--mesh", default="",
                    help="mesh shape: '4' (1-D plans) or '2x4' (hybrid)")
    ap.add_argument("--objects", type=int, default=8_000)
    ap.add_argument("--ticks", type=int, default=4)
    ap.add_argument("--k", type=int, default=16)
    ap.add_argument("--chunk", type=int, default=1024)
    ap.add_argument("--out", default="BENCH_scaling.json")
    args = ap.parse_args()
    if args.child:
        _child(args)
        return
    run(objects=args.objects, ticks=args.ticks, k=args.k, chunk=args.chunk,
        out=args.out)


if __name__ == "__main__":
    main()
