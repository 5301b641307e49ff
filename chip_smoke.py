"""Bring-up check: the k-NN session and server, end to end, on one TPU chip.

One process drives the serving path through the entry points a user calls,
at the paper's iterated-join size (N objects, one query per object, k = 32),
and checks every answer.  Phases, in order, each printing one line:

  a  session  ``KnnSession(ServiceSpec(k=32, maintenance="incremental"))``
              (single plan, dense_topk): ingest, register one query per
              object, then ``--ticks`` ticks that move objects by snapshot
              ingest (tick 2: a 1% ``update_objects`` delta, maintained
              incrementally) and queries by ``update_queries``.  After every
              tick ``--check-rows`` rows, spread over the batch, must equal a
              host NumPy brute force bit for bit (ids, and distances).
  b  server   ``KnnServer`` with 4 tenants over the same world for ticks 0-2
              (tick 2's delta arrives through a tenant): every tenant's
              ``result_for`` equals the phase-a session's rows bit for bit.
  c  pallas   ticks 0-1 again with ``backend="fused_bucket"``, in fp32 and
              in ``precision="mixed"``: bitwise equal to phase a, and the
              compiled tick program carries the kernel (``tpu_custom_call``).

``--chips 4`` runs only the mesh plans (``sharded`` over 4, ``object_sharded``
over 4 and ``hybrid`` 2x2; ``dense_merge`` and ``fused_multi`` where there is
an object axis) against the ``single`` plan, bitwise, and prints each
device's memory so state held by one chip alone shows.

The last line is ``{"ok": true, "device": {...}}`` only when every check
passed on a TPU.  Anywhere else the script exits non-zero without it: with
no TPU it stops at once, unless ``--objects`` asks for a size small enough
to rehearse on the CPU, where every phase runs (Pallas kernels interpreted).

  python chip_smoke.py                 # one chip, N = Q = 1,000,000
  python chip_smoke.py --chips 4       # four chips: mesh plans vs single
  JAX_PLATFORMS=cpu python chip_smoke.py --objects 20000   # CPU rehearsal
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from concurrent.futures import ThreadPoolExecutor
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

K = 32
DELTA_TICK = 2  # the tick whose motion is a churn delta, not a snapshot
CPU_MAX_OBJECTS = 100_000  # largest --objects the CPU rehearsal accepts
TENANTS = 4
SERVER_TICKS = 3
PALLAS_TICKS = 2
MESH_PLANS = (  # (plan, mesh_shape, merge): the --chips 4 path
    ("sharded", 4, "dense_merge"),
    ("object_sharded", 4, "dense_merge"),
    ("object_sharded", 4, "fused_multi"),
    ("hybrid", (2, 2), "dense_merge"),
    ("hybrid", (2, 2), "fused_multi"),
)


def _parse_args():
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--objects", type=int, default=None,
                    help="moving objects N, one query each (default "
                         "1,000,000; without a TPU at most "
                         f"{CPU_MAX_OBJECTS:,})")
    ap.add_argument("--ticks", type=int, default=4,
                    help=f"phase-a ticks (>= {DELTA_TICK + 1})")
    ap.add_argument("--check-rows", type=int, default=1024,
                    help="query rows checked against the host brute force "
                         "per tick")
    ap.add_argument("--churn", type=float, default=0.01,
                    help=f"fraction of objects moved by tick {DELTA_TICK}'s "
                         "delta")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the mesh plans against the single plan")
    args = ap.parse_args()
    if args.ticks < DELTA_TICK + 1:
        ap.error(f"--ticks must be >= {DELTA_TICK + 1}")
    return args


class Checks:
    """Failed checks, collected so that every phase still runs."""

    def __init__(self):
        self.failed: list[str] = []

    def expect(self, ok: bool, what: str) -> bool:
        if not ok:
            self.failed.append(what)
            print(f"  FAILED: {what}", file=sys.stderr, flush=True)
        return ok


def make_feed(n: int, ticks: int, churn: float, seed: int):
    """Per-tick inputs of the workload, made once and replayed by each phase.

    Tick 0 is the initial snapshot; every later tick advances the gaussian
    workload one step.  Its objects arrive as a full snapshot, except on
    ``DELTA_TICK``, where only a ``churn`` fraction of them moves, as an
    ``update_objects`` delta.  Queries (one per object, excluding itself)
    sit at the objects' advanced positions.  ``world`` is the object
    buffer the service holds after the tick's ingest.
    """
    import numpy as np

    from repro.data import make_workload

    w = make_workload(n, "gaussian", seed=seed)
    rng = np.random.default_rng(seed + 1)
    qpos, qid = w.query_batch(1.0)
    world = np.array(w.positions(), np.float32)
    feed = [dict(objects=("snapshot", world), world=world,
                 qpos=np.array(qpos, np.float32))]
    for t in range(1, ticks):
        w.advance()
        new = np.array(w.positions(), np.float32)
        if t == DELTA_TICK:
            m = max(1, int(round(n * churn)))
            ids = np.sort(rng.choice(n, m, replace=False)).astype(np.int32)
            world = world.copy()
            world[ids] = new[ids]
            objects = ("delta", ids, new[ids])
        else:
            world = new
            objects = ("snapshot", new)
        feed.append(dict(objects=objects, world=world, qpos=new))
    return feed, np.asarray(qid, np.int32)


def feed_objects(target, objects):
    """Apply one tick's object motion to a session or a tenant/server."""
    if objects[0] == "snapshot":
        target.ingest_objects(objects[1])
    else:
        target.update_objects(objects[1], objects[2])


def square_sum(dx, dy, form: str):
    """f32 ``dx*dx + dy*dy`` on the host, rounded as the platform rounds it.

    ``"plain"`` rounds both products and then the sum; ``"fma"`` fuses
    ``dx*dx`` into the add (one rounding of ``dx*dx + f32(dy*dy)``), which
    is what XLA's CPU backend emits.  The fused form is evaluated in f64
    (the product exactly) before its rounding to f32.
    """
    import numpy as np

    if form == "plain":
        return dx * dx + dy * dy
    dx64 = dx.astype(np.float64)
    return (dx64 * dx64 + (dy * dy).astype(np.float64)).astype(np.float32)


def device_square_sum_form() -> str:
    """Which rounding of ``dx*dx + dy*dy`` the device's compiler emits."""
    import jax
    import numpy as np

    dx, dy = np.random.default_rng(0).uniform(
        -300, 300, (2, 1 << 16)).astype(np.float32)
    got = np.asarray(jax.jit(lambda a, b: a * a + b * b)(dx, dy))
    for form in ("plain", "fma"):
        if same_bits(got, square_sum(dx, dy, form)):
            return form
    return "unknown"


def reference_knn(world, qpos, qid, rows, k: int, form: str):
    """Host NumPy brute force for ``rows``: (ids, squared distances).

    The same f32 ``dx*dx + dy*dy`` (object minus query, rounded as the
    device rounds it: ``form``) as the service, the issuing object
    excluded, and the canonical ``(d², id)`` order with the lowest id first
    among equal distances (DESIGN.md §12).
    """
    import numpy as np

    out_i = np.empty((rows.size, k), np.int32)
    out_d = np.empty((rows.size, k), np.float32)
    wx, wy = world[:, 0], world[:, 1]
    block = 16

    def run(lo):
        r = rows[lo:lo + block]
        dx = wx[None, :] - qpos[r, 0][:, None]
        dy = wy[None, :] - qpos[r, 1][:, None]
        d2 = square_sum(dx, dy, form)
        d2[np.arange(r.size), qid[r]] = np.inf
        kth = np.partition(d2, k - 1, axis=1)[:, k - 1]
        for j in range(r.size):
            cand = np.flatnonzero(d2[j] <= kth[j])
            ids = cand[np.lexsort((cand, d2[j, cand]))[:k]]
            out_i[lo + j] = ids
            out_d[lo + j] = d2[j, ids]

    with ThreadPoolExecutor(8) as ex:
        list(ex.map(run, range(0, rows.size, block)))
    return out_i, out_d


def check_rows(checks, tag, res, f, qid, rows, sqrt, form):
    """Sampled rows of one tick against the host brute force, bitwise.

    Distances are compared after the reference's squared distances pass
    through the device's own f32 square root (``sqrt``), so the check
    covers the search and its arithmetic, not the platform's rounding of
    ``sqrt``; how often that rounding differs from NumPy's is returned.
    """
    import numpy as np

    ref_i, ref_d2 = reference_knn(f["world"], f["qpos"], qid, rows, K, form)
    got_i, got_d = res.nn_idx[rows], res.nn_dist[rows]
    bad_i = int((got_i != ref_i).any(axis=1).sum())
    bad_d = int((got_d.view(np.int32)
                 != sqrt(ref_d2).view(np.int32)).any(axis=1).sum())
    checks.expect(bad_i == 0 and bad_d == 0,
                  f"{tag}: {bad_i} rows with other ids, {bad_d} with other "
                  f"distances than the host brute force (of {rows.size})")
    return int((sqrt(ref_d2) != np.sqrt(ref_d2)).sum())


def same_bits(a, b) -> bool:
    import numpy as np

    a, b = np.asarray(a), np.asarray(b)
    return a.shape == b.shape and a.tobytes() == b.tobytes()


def run_ticks(spec, feed, qid, ticks, on_tick=None):
    """Drive one KnnSession through ``ticks`` ticks of the feed.

    Returns (per-tick TickResults, compile seconds); ``on_tick(t, res)``
    sees each result while the session is alive.
    """
    from repro.api import KnnSession

    sess = KnnSession(spec)
    out, compile_s = [], 0.0
    for t in range(ticks):
        f = feed[t]
        feed_objects(sess, f["objects"])
        if t == 0:
            hq = sess.register_queries(f["qpos"], qid)
        else:
            sess.update_queries(hq, f["qpos"])
        res = sess.submit().result()
        compile_s += res.compile_s
        out.append(res)
        if on_tick is not None:
            on_tick(t, res, sess)
    return out, compile_s


def device_line(devices) -> str:
    import jax

    d = devices[0]
    return (f"platform={d.platform} kind={d.device_kind!r} "
            f"devices={len(devices)} jax={jax.__version__}")


def peak_hbm(devices) -> str:
    stats = [d.memory_stats() for d in devices]
    if any(s is None for s in stats):
        return "peak_hbm=not reported by this backend"
    return "peak_hbm_bytes=" + ",".join(
        str(s.get("peak_bytes_in_use", "n/a")) for s in stats)


def median_tick_ms(results) -> float:
    """Median submit-to-result wall of the ticks after the first (compile
    excluded), in ms."""
    import numpy as np

    return float(np.median([r.wall_s for r in results[1:]]) * 1e3)


def phase_session(args, feed, qid, devices, checks):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.api import ServiceSpec

    spec = ServiceSpec(k=K, maintenance="incremental")
    sqrt = jax.jit(jnp.sqrt)
    rng = np.random.default_rng(args.seed + 2)
    q = qid.size
    m = min(args.check_rows, q)
    stride = q // m
    sqrt_ulps = 0
    modes = []
    form = device_square_sum_form()
    checks.expect(form != "unknown", "phase a: the device rounds dx*dx + "
                  "dy*dy neither plainly nor as one fused multiply-add")

    def on_tick(t, res, sess):
        nonlocal sqrt_ulps
        modes.append(res.maintenance)
        rows = np.arange(m) * stride + rng.integers(0, stride, m)
        sqrt_ulps += check_rows(checks, f"phase a tick {t}", res, feed[t],
                                qid, rows, lambda x: np.asarray(sqrt(x)), form)

    t0 = time.perf_counter()
    results, compile_s = run_ticks(spec, feed, qid, args.ticks, on_tick)
    checks.expect(modes[DELTA_TICK] == "incremental",
                  f"phase a tick {DELTA_TICK}: maintenance {modes[DELTA_TICK]!r},"
                  " expected the incremental splice")
    print(f"phase a session: {device_line(devices)} objects={q} "
          f"queries={q} k={K} ticks={args.ticks} maintenance={modes} "
          f"compile_s={compile_s:.2f} median_tick_ms={median_tick_ms(results):.1f} "
          f"{peak_hbm(devices)} rows_checked_per_tick={m} d2_rounding={form} "
          f"sqrt_differs_from_numpy={sqrt_ulps} "
          f"wall_s={time.perf_counter() - t0:.1f}", flush=True)
    return results


def phase_server(args, feed, qid, ref, devices, checks):
    import numpy as np

    from repro.api import ServiceSpec
    from repro.serve import KnnServer

    t0 = time.perf_counter()
    server = KnnServer(ServiceSpec(k=K, maintenance="incremental"))
    tenants = [server.admit(f"tenant-{i}") for i in range(TENANTS)]
    results = []
    for t in range(SERVER_TICKS):
        f = feed[t]
        if f["objects"][0] == "snapshot":
            server.ingest_objects(f["objects"][1])
        else:
            feed_objects(tenants[t % TENANTS], f["objects"])
        if t == 0:
            groups = [tn.register_queries(f["qpos"][i::TENANTS],
                                          qid[i::TENANTS])
                      for i, tn in enumerate(tenants)]
        else:
            for i, tn in enumerate(tenants):
                tn.update_queries(groups[i], f["qpos"][i::TENANTS])
        st = server.submit()
        res = st.result()
        results.append(res.inner)
        for i in range(TENANTS):
            idx, dist, rows_qid = st.result_for(groups[i])
            checks.expect(
                same_bits(idx, ref[t].nn_idx[i::TENANTS])
                and same_bits(dist, ref[t].nn_dist[i::TENANTS])
                and same_bits(rows_qid, qid[i::TENANTS]),
                f"phase b tick {t} tenant {i}: rows differ from the solo "
                "session's")
    compile_s = sum(r.compile_s for r in results)
    print(f"phase b server: {device_line(devices)} tenants={TENANTS} "
          f"rows={qid.size} ticks={SERVER_TICKS} "
          f"compile_s={compile_s:.2f} median_tick_ms={median_tick_ms(results):.1f} "
          f"{peak_hbm(devices)} rows_served={server.rows_served} "
          f"rows_computed={server.rows_computed} "
          f"wall_s={time.perf_counter() - t0:.1f}", flush=True)


def phase_pallas(args, feed, qid, ref, devices, checks):
    from repro.api import ServiceSpec

    for precision in ("fp32", "mixed"):
        t0 = time.perf_counter()
        spec = ServiceSpec(k=K, maintenance="incremental",
                           backend="fused_bucket", precision=precision)
        kernel = []

        def on_tick(t, res, sess):
            checks.expect(
                same_bits(res.nn_idx, ref[t].nn_idx)
                and same_bits(res.nn_dist, ref[t].nn_dist),
                f"phase c {precision} tick {t}: fused_bucket differs from "
                "dense_topk")
            if t == PALLAS_TICKS - 1:
                text = sess.lower_tick().compile().as_text()
                kernel.append("tpu_custom_call" in text)

        results, compile_s = run_ticks(spec, feed, qid, PALLAS_TICKS, on_tick)
        # a program lowered for the CPU interprets its kernels by design
        checks.expect(kernel[0] or devices[0].platform != "tpu",
                      f"phase c {precision}: the tick program holds no "
                      "compiled kernel (tpu_custom_call)")
        print(f"phase c pallas: {device_line(devices)} backend=fused_bucket "
              f"precision={precision} ticks={PALLAS_TICKS} "
              f"tpu_custom_call={kernel[0]} compile_s={compile_s:.2f} "
              f"median_tick_ms={median_tick_ms(results):.1f} "
              f"{peak_hbm(devices)} wall_s={time.perf_counter() - t0:.1f}",
              flush=True)


def phase_mesh(args, feed, qid, devices, checks):
    """--chips 4: every mesh plan against the single plan, bitwise."""
    from repro.api import ServiceSpec

    def memory(label):
        stats = [d.memory_stats() for d in devices]
        if any(s is None for s in stats):
            print(f"  memory {label}: not reported by this backend",
                  flush=True)
            return
        print(f"  memory {label}: bytes_in_use="
              + ",".join(str(s["bytes_in_use"]) for s in stats)
              + " peak_bytes_in_use="
              + ",".join(str(s["peak_bytes_in_use"]) for s in stats),
              flush=True)

    t0 = time.perf_counter()
    spec = ServiceSpec(k=K, maintenance="incremental")
    ref, compile_s = run_ticks(spec, feed, qid, args.ticks,
                               lambda t, r, s: t == args.ticks - 1
                               and memory("single"))
    print(f"mesh single: {device_line(devices)} ticks={args.ticks} "
          f"compile_s={compile_s:.2f} median_tick_ms={median_tick_ms(ref):.1f} "
          f"wall_s={time.perf_counter() - t0:.1f}", flush=True)
    for plan, mesh, merge in MESH_PLANS:
        t0 = time.perf_counter()
        label = f"{plan}:{mesh} merge={merge}"
        spec = ServiceSpec(k=K, maintenance="incremental", plan=plan,
                           mesh_shape=mesh, merge=merge)

        def on_tick(t, res, sess):
            checks.expect(
                same_bits(res.nn_idx, ref[t].nn_idx)
                and same_bits(res.nn_dist, ref[t].nn_dist),
                f"mesh {label} tick {t}: differs from the single plan")
            if t == args.ticks - 1:
                memory(label)

        results, compile_s = run_ticks(spec, feed, qid, args.ticks, on_tick)
        print(f"mesh {label}: {device_line(devices)} ticks={args.ticks} "
              f"maintenance={[r.maintenance for r in results]} "
              f"compile_s={compile_s:.2f} "
              f"median_tick_ms={median_tick_ms(results):.1f} "
              f"{peak_hbm(devices)} wall_s={time.perf_counter() - t0:.1f}",
              flush=True)


def main() -> int:
    args = _parse_args()
    import jax

    from repro.launch.compile_cache import use_compile_cache

    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu" and (args.objects is None
                              or args.objects > CPU_MAX_OBJECTS):
        print(f"no TPU found (JAX platform {platform!r}); rehearse on the "
              f"CPU with --objects <= {CPU_MAX_OBJECTS:,}", file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"--chips {args.chips} needs {args.chips} devices, JAX sees "
              f"{len(devices)}", file=sys.stderr)
        return 2
    devices = devices[:args.chips]
    n = args.objects or 1_000_000
    use_compile_cache()

    t0 = time.perf_counter()
    feed, qid = make_feed(n, args.ticks, args.churn, args.seed)
    print(f"# workload: {n} gaussian objects, {qid.size} queries, "
          f"{args.ticks} ticks, made in {time.perf_counter() - t0:.1f}s",
          file=sys.stderr, flush=True)
    checks = Checks()
    if args.chips == 4:
        phase_mesh(args, feed, qid, devices, checks)
    else:
        ref = phase_session(args, feed, qid, devices, checks)
        phase_server(args, feed, qid, ref, devices, checks)
        phase_pallas(args, feed, qid, ref, devices, checks)

    if checks.failed or platform != "tpu":
        why = (f"{len(checks.failed)} checks failed" if checks.failed
               else f"every check passed, but on {platform!r}, not a TPU")
        print(f"chip smoke NOT ok: {why}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": platform, "kind": devices[0].device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
