"""Trace one window of a cell and print where its device and host time went.

    python bench/stage_report.py --workload <cell> --seed <n> --seconds <s>

Set-up and the window run as in ``run.py --trace 1``; the window's trace
is then read with ``knnbench.stages``: device self time per program stage
(the tick program's ``knn.*`` scopes), the program's ``knn.*`` host spans,
the ten longest ops labelled with their stage and the ten longest idle
gaps named after the harness's and the program's spans.  The op names come
from the trace where it carries them, else from the tick program's
compiled HLO text.  The printed line holds those and the per-tick metrics
of ``bench/metrics/`` that read them (``STAGE_METRICS``).  It checks no
answer and measures nothing end to end: ``run.py`` does that.

Without a TPU it exits with code 2, as ``run.py`` does.  The tests call
``report`` on the CPU at the cell's rehearsal size, where no device op is
read and only the host spans mean anything.
"""
from __future__ import annotations

import argparse
import json
import sys
import tempfile
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(ROOT / "src"))

STAGE_METRICS = (
    "reindex_device_ms", "sweep_gather_device_ms", "sweep_nav_device_ms",
    "sweep_scan_device_ms", "device_unscoped_share", "session_dispatch_ms",
    "session_finalize_ms",
)


def _args(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True, help="cell name")
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True,
                    help="length of the traced window")
    return ap.parse_args(argv)


def programs(state) -> list[str]:
    """Compiled HLO text of the tick program the window ran, where the
    driver's state holds a session (``KnnSession.lower_tick``).

    Compiled afresh, with JAX's in-memory caches cleared and its
    persistent cache off: the persistent cache's key leaves the ops'
    metadata out (``jax_compilation_cache_include_metadata_in_key``), so
    the executable the window ran may have been loaded with the op names of
    an earlier build of the same program, one without the stage scopes,
    and the in-memory caches hand that executable back.  The compiler
    names the instructions alike either way.
    """
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    sess = getattr(state, "sess", None)
    if sess is None:
        return []
    was = jax.config.jax_enable_compilation_cache
    jax.clear_caches()
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        return [sess.lower_tick().compile().as_text()]
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()


def report(cell, seed: int, seconds: float, devices, *, root: Path = ROOT,
           log=None) -> dict:
    """Set up, trace one window and reduce it; returns the record."""
    import jax

    from knnbench import harness, stages, trace

    log = log or (lambda msg: print(msg, file=sys.stderr, flush=True))
    driver = cell.driver()
    state = driver.setup(cell, seed, devices, log)
    span = harness.span_factory(True)
    window = harness.SPAN_PREFIX + "window"
    with tempfile.TemporaryDirectory(prefix="knnbench-stages-") as d:
        jax.profiler.start_trace(
            d, profiler_options=harness._profile_options())
        try:
            with span("window"):
                driver.window(state, seconds, span)
        finally:
            jax.profiler.stop_trace()
        run = driver.record(state)
        path = sorted(Path(d).rglob("*.xplane.pb"))[-1]
        base = trace.reduce_xplane(path, window_span=window) or {}
        staged = stages.reduce_xplane_stages(
            path, window_span=window, hlo_texts=programs(state),
            device_event=stages.tpu_op_event) or {}
    driver.release(state)
    run["trace"] = {**base, **staged}
    metrics = {}
    for name in STAGE_METRICS:
        path = root / "bench" / "metrics" / f"{name}.py"
        metrics[name] = harness.load_module(path).read(run)
    tr = run["trace"]
    ticks = sorted(run["tick_s"])
    return dict(
        ticks=len(ticks), tick_s_median=ticks[len(ticks) // 2],
        window_s=tr.get("window_s"),
        busy_s=tr.get("busy_s"), stages=tr.get("stages"),
        unscoped_s=tr.get("unscoped_s"),
        unscoped_ops=tr.get("unscoped_ops"),
        program_spans=tr.get("program_spans"), metrics=metrics,
        breakdown=dict(device_ops=tr.get("device_ops", []),
                       idle_gaps=tr.get("idle_gaps", [])),
    )


def main(argv=None) -> int:
    args = _args(argv)
    from knnbench import harness

    cell = harness.load_cell(args.workload, ROOT)
    harness.use_compile_cache(ROOT)
    try:
        devices = harness.require_devices(cell.chips)
    except harness.NoDevice as e:
        print(f"no result: {e}", file=sys.stderr)
        return 2
    out = report(cell, args.seed, args.seconds, devices)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
