"""Benchmark runner — one function per paper table/figure + kernels.

Prints ``name,us_per_call,derived`` CSV rows.  Each sub-bench is importable and
has a __main__ for full-size runs; this runner uses CPU-feasible defaults.

``--smoke`` runs a minutes-scale subset and writes ``BENCH_smoke.json``
(queries/s + candidates/s per backend, engine tick latency, serving-mode
rows) plus ``BENCH_serving_smoke.json`` (ingest x submit x collect mode,
s6) and ``BENCH_skew_smoke.json`` (straggler gap: equal vs cost_balanced partitioner
on a forced 8-device grid, s7) and ``BENCH_churn_smoke.json`` (rebuild vs
incremental reindex under teleport churn, s8) — the per-PR perf trajectory
artifacts consumed by CI.  The plain
``BENCH_serving.json``/``BENCH_skew.json``/``BENCH_churn.json`` are
committed full-size artifacts, regenerated only by full (non-smoke) runs.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time


def _smoke(out_path: str) -> None:
    import jax
    import numpy as np

    from benchmarks import s4_backends
    from repro.core import EngineConfig, TickEngine, available_backends
    from repro.data import make_workload

    rec: dict = {"schema": 2, "unit": "seconds"}
    rec["device_count"] = int(jax.device_count())
    rec["backends"] = s4_backends.run(
        n_objects=8_000, k=16, dists=("uniform",), chunk=2048, out=None
    )

    # engine steady-state: per-tick wall time after warmup, default backend;
    # every SCAN backend on the single plan, plus the sharded plan over
    # whatever mesh this process sees (1 locally, 8 in the CI multi-device job)
    def engine_row(backend, plan):
        eng = TickEngine(
            EngineConfig(k=16, th_quad=192, l_max=7, window=128, chunk=2048,
                         backend=backend, plan=plan)
        )
        w = make_workload(8_000, "gaussian", seed=0)
        results = eng.run(w, ticks=4)
        steady = [r.wall_s for r in results[1:]]
        return {
            "plan": eng.plan.name,
            "devices": int(jax.device_count()),
            "tick_s_median": float(np.median(steady)),
            "queries_per_s": float(8_000 / np.median(steady)),
            "candidates_per_tick": float(np.mean([r.candidates for r in results[1:]])),
        }

    ticks = {b: engine_row(b, "single") for b in available_backends()}
    rec["engine"] = ticks
    rec["engine_sharded"] = engine_row("dense_topk", "sharded")

    # serving-mode sweep (session API): ingest x submit x collect mode,
    # reduced size.  Written under a _smoke name: the plain
    # BENCH_serving.json is the committed full-size (50K x 30) artifact and
    # must not be clobbered by smoke runs.
    from benchmarks import s6_serving

    rec["serving"] = s6_serving.run(
        objects=4_000, ticks=4, k=16, chunk=1024, window=128,
        out="BENCH_serving_smoke.json",
    )

    # skew row: the partitioner seam's straggler-gap probe on a forced
    # 8-device grid (equal vs cost_balanced, bit-identity asserted in-run);
    # one exponent x the query-sharded plans keeps smoke minutes-scale.
    # Written under a _smoke name: the plain BENCH_skew.json is the
    # committed full-matrix artifact (s7 at full size) and must not be
    # clobbered by smoke runs — same discipline as BENCH_serving.json above
    from benchmarks import s7_skew

    rec["skew"] = s7_skew.run(
        objects=2_048, ticks=3, k=8, chunk=128, exponents=(1.6,),
        plans=(("sharded", "8"), ("hybrid", "2x4")),
        out="BENCH_skew_smoke.json",
    )

    # churn row (maintenance seam, s8): rebuild vs incremental reindex-stage
    # cost under teleport churn on the forced 8-device grid, bit-identity
    # asserted in-child.  check=False: at smoke sizes the delta path is
    # dispatch-bound, so the >= 3x acceptance ratio only holds at full size
    # (the committed BENCH_churn.json, regenerated by full runs below).
    from benchmarks import s8_churn

    rec["churn"] = s8_churn.run(
        objects=4_000, ticks=3, k=8, chunk=256, queries=256, reps=5,
        churns=(0.01, 0.1), plans=(("sharded", "8"),),
        check=False, out="BENCH_churn_smoke.json",
    )

    # soak row (serving layer, s9): multi-tenant KnnServer under open-loop
    # Zipf arrival on the forced 8-device grid — p50/p95/p99 tick latency +
    # dedup/cache hit rate (the §16 probe).  check=True even at smoke size:
    # Zipf-overlapping pool groups guarantee a nonzero hit rate.  Written
    # under a _smoke name: the plain BENCH_soak.json is the committed
    # full-size artifact, regenerated only by full runs.
    from benchmarks import s9_soak

    rec["soak"] = s9_soak.run(
        objects=2_000, tenants=6, pool=4, group=16, lam=3.0, ticks=8,
        warmup=2, plans=(("sharded", "8", "cost_balanced"),),
        out="BENCH_soak_smoke.json",
    )
    rec["timestamp"] = time.time()
    with open(out_path, "w") as f:
        json.dump(rec, f, indent=2)
    print(f"# wrote {out_path}", flush=True)


def main() -> None:
    root = os.path.join(os.path.dirname(__file__), "..")
    sys.path.insert(0, root)  # `benchmarks` namespace package
    sys.path.insert(0, os.path.join(root, "src"))
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="reduced sweep; writes the JSON perf artifact")
    ap.add_argument("--out", default="BENCH_smoke.json",
                    help="smoke-mode JSON output path")
    args = ap.parse_args()

    from repro.launch.compile_cache import use_compile_cache

    use_compile_cache()
    print("name,us_per_call,derived")
    if args.smoke:
        _smoke(args.out)
        return

    from benchmarks import (
        kernels,
        s1_skew,
        s1_treeheight,
        s2_vs_baseline,
        s3_vary_k,
        s3_vs_cpu,
        s4_backends,
        s5_scaling,
        s6_serving,
        s7_skew,
    )

    s1_treeheight.run(n_objects=30_000, ks=(8, 32), th_quads=(48, 384, 1536))
    s1_skew.run(n_objects=30_000, hotspots=(4, 25), th_quads=(96, 384))
    s2_vs_baseline.run_vary_n(ns=(5_000, 20_000))
    s2_vs_baseline.run_vary_k(n=20_000, ks=(8, 64))
    s3_vs_cpu.run(ns=(20_000,), dists=("uniform", "gaussian"))
    s3_vary_k.run(n=20_000, ks=(8, 64), dists=("uniform",))
    s3_vary_k.run_update_strategies(q=64, c=512, ks=(32,))
    s4_backends.run(n_objects=20_000, k=32, out="BENCH_backends.json")
    s5_scaling.run(objects=8_000, ticks=4, out="BENCH_scaling.json")
    s7_skew.run(objects=4_096, ticks=4, out="BENCH_skew.json")
    # full scale matches the committed artifact (50K objects x 4096 queries
    # x 30 ticks) so a full run regenerates BENCH_serving.json at its
    # documented size
    s6_serving.run(objects=50_000, queries=4_096, ticks=30, passes=6,
                   out="BENCH_serving.json")
    # churn x maintenance x plan at committed size; asserts the >= 3x
    # incremental reindex-stage reduction at <= 10% churn (DESIGN.md §15)
    from benchmarks import s8_churn

    s8_churn.run(out="BENCH_churn.json")
    # multi-tenant soak at committed size; asserts a nonzero dedup/cache hit
    # rate under Zipf-overlapping tenant queries (DESIGN.md §16)
    from benchmarks import s9_soak

    s9_soak.run(out="BENCH_soak.json")
    kernels.run(q=64, c=512, k=16)


if __name__ == "__main__":
    main()
