"""Device time per program stage and the program's spans, from a trace."""
from __future__ import annotations

import sys
import time
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(ROOT / "src"))

from knnbench import harness, stages  # noqa: E402

SEED = 2_147_483_659


def metric(name):
    return harness.load_module(BENCH / "metrics" / f"{name}.py")


def test_stage_self_times_unscoped_time_and_gap_names():
    tick = "jit__tick_step"
    ops = {"dev0": [
        # the sweep's while loop holds a gather and a nav op
        ("while.1", tick, "jit(_tick_step)/knn.sweep/while", 0.0, 4.0),
        ("fusion.2", tick, "jit(_tick_step)/knn.sweep/while/body/knn.gather"
         "/gather", 0.5, 2.0),
        ("fusion.3", tick, "jit(_tick_step)/knn.sweep/while/body/knn.nav"
         "/while/body/add", 2.0, 3.0),
        ("fusion.4", tick, "jit(_tick_step)/mul", 4.0, 4.5),
        ("copy.5", "jit_rebuild_zmap", None, 7.0, 8.0),
    ]}
    harness_spans = [("submit", 4.5, 7.0), ("result", 8.0, 10.0)]
    program_spans = [("session.submit", 4.6, 6.9),
                     ("session.finalize", 4.6, 6.0),
                     ("session.dispatch", 6.0, 6.9),
                     ("tick.result", 8.0, 9.9), ("tick.result", 9.95, 12.0)]
    out = stages.reduce_stages(ops, harness_spans, program_spans,
                               (0.0, 10.0))
    assert out["stages"] == pytest.approx(dict(
        sweep=1.5, gather=1.5, nav=1.0, jit_rebuild_zmap=1.0))
    assert out["unscoped_s"] == pytest.approx(0.5)
    # stage self times and unscoped time add up to the busy union (5.5 s)
    assert sum(out["stages"].values()) + out["unscoped_s"] == \
        pytest.approx(5.5)
    labels = dict(out["device_ops"])
    assert labels["fusion.2 @gather"] == pytest.approx(1.5)
    assert labels["fusion.4 @unscoped"] == pytest.approx(0.5)
    assert labels["copy.5 @jit_rebuild_zmap"] == pytest.approx(1.0)
    assert out["idle_gaps"] == [["submit/session.finalize", 2.5],
                                ["result/tick.result", 2.0]]
    # spans are clipped to the window
    assert out["program_spans"]["tick.result"] == [pytest.approx(1.95), 2]
    assert out["program_spans"]["session.dispatch"] == [
        pytest.approx(0.9), 1]


def test_a_program_without_scopes_gives_no_stages():
    ops = {"dev0": [("fusion.1", "jit__tick_step", "jit(_tick_step)/mul",
                     0.0, 1.0)]}
    out = stages.reduce_stages(ops, [], [], (0.0, 2.0))
    assert "stages" not in out and "unscoped_s" not in out
    assert out["device_ops"] == [["fusion.1 @jit__tick_step", 1.0]]
    assert out["idle_gaps"] == [["none/none", 1.0]]
    run = dict(ticks=[{}], trace=dict(out, busy_s=1.0))
    for name in ("reindex_device_ms", "sweep_gather_device_ms",
                 "sweep_nav_device_ms", "sweep_scan_device_ms",
                 "device_unscoped_share", "session_dispatch_ms",
                 "session_finalize_ms"):
        assert metric(name).read(run) is None, name


def test_stage_metrics_per_tick():
    trace = dict(stages=dict(reindex=0.2, gather=4.0, nav=2.0), busy_s=8.0,
                 unscoped_s=0.4,
                 program_spans={"session.dispatch": [0.02, 4],
                                "session.finalize": [0.01, 3]})
    run = dict(ticks=[{}] * 4, trace=trace)
    assert metric("reindex_device_ms").read(run) == pytest.approx(50.0)
    assert metric("sweep_gather_device_ms").read(run) == pytest.approx(1e3)
    assert metric("sweep_nav_device_ms").read(run) == pytest.approx(500.0)
    assert metric("sweep_scan_device_ms").read(run) == 0.0
    assert metric("device_unscoped_share").read(run) == pytest.approx(5.0)
    assert metric("session_dispatch_ms").read(run) == pytest.approx(5.0)
    assert metric("session_finalize_ms").read(run) == pytest.approx(2.5)


TPU_LIKE_HLO = """\
HloModule jit__tick_step, is_scheduled=true

%fused_computation.4 (param_0: s32[8]) -> s32[8] {
  %param_0 = s32[8]{0} parameter(0)
  ROOT %gather.1 = s32[8]{0} gather(%param_0), metadata={op_name="jit(_tick_step)/knn.sweep/while/body/knn.gather/gather"}
}

%body.2 (p: (s32[8])) -> (s32[8]) {
  %p = (s32[8]{0}) parameter(0)
  %fusion.194 = s32[8]{0} fusion(%p), kind=kCustom, calls=%fused_computation.4
  %copy.5 = s32[8]{0} copy(%fusion.194)
  ROOT %tuple.1 = (s32[8]{0}) tuple(%copy.5)
}

ENTRY %main.55 (x: s32[8]) -> (s32[8]) {
  %x = s32[8]{0} parameter(0)
  %sort.3 = s32[8]{0} sort(%x), dimensions={0}, metadata={op_name="jit(_tick_step)/knn.reindex/sort"}
  %while.134 = (s32[8]{0}) while(%sort.3), condition=%cond.1, body=%body.2, metadata={op_name="jit(_tick_step)/knn.sweep/while"}
  ROOT %copy.9 = (s32[8]{0}) copy(%while.134)
}
"""


def test_hlo_op_names_fill_in_what_the_compiler_left_out():
    """A fusion without metadata is named after its root; an instruction of
    a loop body without metadata after the loop; an instruction of the
    entry computation without metadata stays unnamed."""
    names = stages.hlo_op_names([TPU_LIKE_HLO])["jit__tick_step"]
    assert stages.stage_of(names["fusion.194"]) == "gather"
    assert stages.stage_of(names["copy.5"]) == "sweep"
    assert stages.stage_of(names["sort.3"]) == "reindex"
    assert stages.stage_of(names["while.134"]) == "sweep"
    assert "copy.9" not in names
    assert stages.stage_of("jit(f)/knn.sweep/while/body/knn.nav/add") == "nav"
    assert stages.stage_of("jit(f)/while/body/add") is None


def test_stages_on_a_recorded_trace(tmp_path):
    """A trace recorded here on the CPU: its op events carry the program and
    instruction (``hlo_module``/``hlo_op``) but no scope path, so the stage
    comes from the compiled HLO text; the host sleeps inside a ``knn.``
    span, which names the gap after the harness's span."""
    import jax
    import jax.numpy as jnp

    from repro.tracing import span, stage

    @jax.jit
    def f(x):
        with stage("gather"):
            y = x @ x.T
        with stage("order"):
            y = jnp.sort(y, axis=1)
        return y.sum()

    x = jnp.ones((384, 384), jnp.float32)
    f(x).block_until_ready()
    text = f.lower(x).compile().as_text()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation("bench.window"):
            f(x).block_until_ready()
            with jax.profiler.TraceAnnotation("bench.host_wait"), \
                    span("session.finalize", tick=7):
                time.sleep(0.2)
            f(x).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    path = next(tmp_path.rglob("*.xplane.pb"))
    out = stages.reduce_xplane_stages(
        path, window_span="bench.window", hlo_texts=[text],
        device_event=stages.cpu_op_event)
    assert out is not None
    assert {"gather", "order"} <= set(out["stages"])
    assert out["stages"]["gather"] > 0.0 and out["stages"]["order"] > 0.0
    assert out["unscoped_s"] >= 0.0
    assert out["program_spans"]["session.finalize"][1] == 1
    assert out["program_spans"]["session.finalize"][0] >= 0.19
    name, longest = out["idle_gaps"][0]
    assert name == "host_wait/session.finalize" and longest >= 0.19
    assert any(label.endswith(" @gather") for label, _ in out["device_ops"])
    # without the HLO text the CPU trace cannot say where an op belongs
    bare = stages.reduce_xplane_stages(
        path, window_span="bench.window", device_event=stages.cpu_op_event)
    assert "stages" not in bare
    assert stages.reduce_xplane_stages(
        path, window_span="bench.absent",
        device_event=stages.cpu_op_event) is None


def test_traced_rehearsal_reads_the_session_spans():
    """``stage_report`` on the rehearsal size: the window's ticks leave
    their dispatch and finalize spans in the trace (no device op is read
    on the CPU, so the device stages stay silent)."""
    import jax

    report = harness.load_module(BENCH / "stage_report.py")
    cell = harness.load_cell("gaussian_join").rehearsal()
    out = report.report(cell, SEED, 1.0, jax.devices()[:1],
                        log=lambda msg: None)
    assert out["ticks"] > 0
    m = out["metrics"]
    assert m["session_dispatch_ms"] > 0.0
    assert m["session_finalize_ms"] > 0.0
    assert m["reindex_device_ms"] is None
    spans = out["program_spans"]
    assert spans["session.submit"][1] == out["ticks"]
    assert {"session.ingest", "session.update_queries", "session.dispatch",
            "session.finalize", "tick.wait", "tick.result",
            "tick.collect"} <= set(spans)
