"""The benchmark's arithmetic: rates, counters and the trace reduction."""
from __future__ import annotations

import sys
import time
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

from knnbench import harness, trace  # noqa: E402

METRICS = BENCH / "metrics"


def metric(name):
    return harness.load_module(METRICS / f"{name}.py")


def test_a_stalled_tick_moves_the_rate_over_the_whole_window():
    rate = metric("queries_per_s")
    run = dict(kind="closed", queries_answered=30 * 16384, window_s=30.0)
    assert rate.read(run) == pytest.approx(16384.0)
    stalled = dict(run, window_s=31.0)  # one tick took 1 s longer
    assert rate.read(stalled) == pytest.approx(30 * 16384 / 31.0)
    assert rate.read(dict(run, kind="open")) is None


def test_counter_metrics():
    ticks = [dict(stage_s=0.01, iterations=100, candidates=1.0e6, chunks=2,
                  shard_candidates=[1.0, 3.0]),
             dict(stage_s=0.03, iterations=300, candidates=3.0e6, chunks=2,
                  shard_candidates=[2.0, 2.0])]
    run = dict(ticks=ticks, chunk=1000, lanes_window=10)
    assert metric("session_stage_ms").read(run) == pytest.approx(20.0)
    assert metric("sweep_trips_per_chunk").read(run) == pytest.approx(100.0)
    assert metric("sweep_useful_lane_share").read(run) == pytest.approx(
        100.0 * 4.0e6 / (400 * 1000 * 10))
    assert metric("plan_straggler_gap").read(run) == pytest.approx(1.25)
    single = dict(run, ticks=[dict(t, shard_candidates=[5.0]) for t in ticks])
    assert metric("plan_straggler_gap").read(single) is None
    assert metric("peak_hbm_mb").read(dict(memory_peak_bytes=2.5e8)) == 250.0
    assert metric("peak_hbm_mb").read(dict(memory_peak_bytes=None)) is None


def test_busy_union_idle_share_and_gap_attribution():
    ops = {"dev0": [("sort", 0.0, 4.0), ("gather", 1.0, 2.0),
                    ("sort", 4.0, 5.0), ("fusion", 7.0, 9.0)],
           "dev1": [("sort", 1.0, 9.0)]}
    spans = [("submit", 4.5, 7.5), ("stage", 5.2, 6.8), ("result", 9.0, 10)]
    out = trace.reduce_events(ops, spans, (0.0, 10.0))
    # dev0 busy [0,5] + [7,9] = 7 s, dev1 busy [1,9] = 8 s
    assert out["busy_s"] == pytest.approx(7.5)
    assert out["idle_share"] == pytest.approx(0.25)
    gaps = out["idle_gaps"]
    # dev0's [5, 7]: "submit" covers all of it, the nested "stage" less
    assert gaps[0] == ["submit", pytest.approx(2.0)]
    assert sorted(g for _, g in gaps) == pytest.approx([1.0, 1.0, 1.0, 2.0])
    assert sorted(n for n, _ in gaps) == ["none", "result", "result",
                                          "submit"]
    # the gather nests in the first sort: self times, averaged per device
    top = dict(out["device_ops"])
    assert top["gather"] == pytest.approx(0.5)
    assert top["sort"] == pytest.approx((4.0 - 1.0 + 1.0 + 8.0) / 2)
    assert trace.merge([(3, 4), (0, 1), (0.5, 2)]) == [(0, 2), (3, 4)]


def test_trace_reduction_on_a_recorded_trace(tmp_path):
    """A trace recorded here on the CPU: the host sleeps in a named span
    between two device calls, and the reduction names that gap after it."""
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: jnp.sort(x @ x.T, axis=1).sum())
    x = jnp.ones((384, 384), jnp.float32)
    f(x).block_until_ready()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation("bench.window"):
            f(x).block_until_ready()
            with jax.profiler.TraceAnnotation("bench.host_wait"):
                time.sleep(0.2)
            f(x).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    path = next(tmp_path.rglob("*.xplane.pb"))

    def cpu_ops(plane, line):
        return plane == "/host:CPU" and line.startswith("tf_XLA")

    out = trace.reduce_xplane(path, window_span="bench.window",
                              device_line=cpu_ops)
    assert out is not None and out["events"] > 0
    assert 0.2 < out["window_s"] < 5.0
    assert 0.0 < out["busy_s"] < out["window_s"] - 0.19
    assert 0.0 < out["idle_share"] < 1.0
    name, longest = out["idle_gaps"][0]
    assert name == "host_wait" and longest >= 0.19
    assert out["device_ops"] and out["device_ops"][0][1] > 0.0
    assert trace.reduce_xplane(path, window_span="bench.absent",
                               device_line=cpu_ops) is None


def test_peaks_are_known_by_device_kind():
    v5e = harness.device_peaks("TPU v5 lite")
    assert v5e["bf16_flops_per_s"] == 197e12
    assert v5e["hbm_bytes_per_s"] == 819e9
    with pytest.raises(harness.NoDevice):
        harness.device_peaks("TPU v9 imaginary")
