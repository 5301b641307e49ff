"""Ahead-of-time compiles for a described TPU v5e: the kernels the chip runs.

The tests run on the CPU, where every Pallas kernel is interpreted.  These
compile the served-path kernels with ``interpret=False`` for a ``v5e:2x2``
topology described by ``jax.experimental.topologies`` (no chip needed), at
the widths the service uses (query chunk 8192, window 256, k 32), so a
kernel the TPU's compiler (Mosaic) refuses fails here; each kernel must
show in the compiled program under its stable name, the instruction name a
profiler trace shows it by.  The last tests
compile whole ticks for that chip and require their kernels in them
(``fused_scan`` under ``fused_bucket``, ``window_fetch`` under every
backend): a program lowered for the TPU must carry them compiled
(``tpu_custom_call``), never interpreted; one of them is the incremental
tick at the delta-reporting cell's full shapes.
"""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import KnnSession, ServiceSpec
from repro.api import session as session_mod
from repro.core import build_index
from repro.kernels import (
    bucket_kselect,
    fused_scan,
    merge_topk,
    pairwise_dist,
    topk_select,
    window_fetch,
)

Q, W, K = 8192, 256, 32
F32, I32 = jnp.float32, jnp.int32


@pytest.fixture(scope="module")
def tpu():
    """The first device of a described ``v5e:2x2`` topology."""
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"cannot describe a v5e topology here: {e}")
    return topo.devices[0]


def _compile_text(fn, tpu, *shapes):
    on = jax.sharding.SingleDeviceSharding(tpu)
    args = [jax.ShapeDtypeStruct(s, dt, sharding=on) for s, dt in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


def _compiled_kernel(text, name):
    """Is there a compiled Pallas kernel named ``name`` in ``text``?"""
    pattern = rf"%{name}(\.\d+)? = .*custom_call_target=\"tpu_custom_call\""
    return re.search(pattern, text) is not None


@pytest.mark.parametrize("precision", ["fp32", "mixed"])
def test_fused_scan_merge_compiles_for_tpu(tpu, precision):
    text = _compile_text(
        lambda *a: fused_scan.fused_scan_merge(
            *a, k=K, precision=precision, interpret=False),
        tpu,
        ((Q,), F32), ((Q,), F32), ((Q, W), F32), ((Q, W), F32),
        ((Q, W), I32), ((Q, W), jnp.bool_), ((Q, K), F32), ((Q, K), I32),
    )
    assert _compiled_kernel(text, "fused_scan")


def test_merge_topk_lists_compiles_for_tpu(tpu):
    text = _compile_text(
        lambda *a: merge_topk.merge_topk_lists(*a, k=K, interpret=False),
        tpu, ((Q, K), F32), ((Q, K), I32), ((Q, K), F32), ((Q, K), I32),
    )
    assert _compiled_kernel(text, "merge_topk_lists")


def test_merge_topk_multi_compiles_for_tpu(tpu):
    r = 4
    text = _compile_text(
        lambda *a: merge_topk.merge_topk_multi(*a, k=K, interpret=False),
        tpu, ((Q, r * K), F32), ((Q, r * K), I32),
    )
    assert _compiled_kernel(text, "merge_topk_multi")


C = 1024  # a shared candidate window of the brute-force kernels


@pytest.mark.parametrize("name, fn, shapes", [
    ("bucket_kselect",
     lambda *a: bucket_kselect.bucket_kselect(*a, k=K, interpret=False),
     (((Q,), F32), ((Q,), F32), ((C,), F32), ((C,), F32),
      ((C,), jnp.bool_))),
    ("pairwise_dist",
     lambda *a: pairwise_dist.pairwise_dist(*a, interpret=False),
     (((Q,), F32), ((Q,), F32), ((C,), F32), ((C,), F32),
      ((C,), jnp.bool_))),
    ("topk_select",
     lambda *a: topk_select.topk_select(*a, k=K, interpret=False),
     (((Q, C), F32), ((Q, C), I32))),
])
def test_other_kernels_compile_for_tpu_under_their_names(tpu, name, fn,
                                                          shapes):
    assert _compiled_kernel(_compile_text(fn, tpu, *shapes), name)


def test_window_fetch_compiles_for_tpu(tpu):
    n = 1_000_000  # the row tables of a million-object store
    rows = (n - 1) // window_fetch.LANES + window_fetch.fetch_rows(W)
    text = _compile_text(
        lambda x, y, ids, start, scanning: window_fetch.window_fetch(
            (x, y, ids), start, scanning, window=W, interpret=False),
        tpu, ((rows, 128), F32), ((rows, 128), F32), ((rows, 128), I32),
        ((Q,), I32), ((Q,), jnp.bool_),
    )
    assert _compiled_kernel(text, "window_fetch")


def _tick_on_tpu(tpu, monkeypatch, backend):
    """A small session's tick, compiled on the CPU and for the described
    chip: ``(cpu_text, tpu_text)`` of the two compiled programs."""
    n = 2048
    rng = np.random.default_rng(0)
    pts = rng.uniform(0, 1000, (n, 2)).astype(np.float32)
    sess = KnnSession(ServiceSpec(k=8, th_quad=64, l_max=5, window=64,
                                  chunk=512, backend=backend))
    sess.ingest_objects(pts)
    sess.register_queries(pts, np.arange(n, dtype=np.int32))
    sess.submit().result()
    cpu_text = sess.lower_tick().compile().as_text()

    # the same arguments, as shapes on the described chip
    step = session_mod._tick_step
    on = jax.sharding.SingleDeviceSharding(tpu)

    class OnTpu:
        def lower(self, *args, **statics):
            args = jax.tree.map(
                lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=on),
                args)
            return step.lower(*args, **statics)

    monkeypatch.setattr(session_mod, "_tick_step", OnTpu())
    return cpu_text, sess.lower_tick().compile().as_text()


def test_dense_topk_tick_carries_the_compiled_window_fetch(tpu, monkeypatch):
    """The default backend's tick carries the window fetch compiled, under
    its stable name, and interprets it on the CPU."""
    cpu_text, tpu_text = _tick_on_tpu(tpu, monkeypatch, "dense_topk")
    assert "tpu_custom_call" not in cpu_text
    assert _compiled_kernel(tpu_text, "window_fetch")


def test_fused_bucket_tick_carries_the_compiled_kernel(tpu, monkeypatch):
    """A whole tick, lowered for the chip with the kernels left to the
    default (``interpret=None``): the kernel is compiled into it, while the
    same session's CPU program interprets it."""
    cpu_text, tpu_text = _tick_on_tpu(tpu, monkeypatch, "fused_bucket")
    assert "tpu_custom_call" not in cpu_text
    assert _compiled_kernel(tpu_text, "fused_scan")


def test_incremental_tick_compiles_for_tpu_at_the_churn_cell_shapes(tpu):
    """The tick the delta-reporting cell runs: the incremental splice step
    at N 1,000,000, a padded delta of 32,768 rows, Q 16,384, W 256, chunk
    8,192, compiled for the described chip with the window fetch in it.
    At this N the splice searches int32 key pairs, since
    ``4**l_max * (N + 1) + N`` does not fit an int32."""
    n, delta, q = 1_000_000, 32_768, 16_384
    spec = ServiceSpec(maintenance="incremental")
    on = jax.sharding.SingleDeviceSharding(tpu)

    def shape(s, dt):
        return jax.ShapeDtypeStruct(s, dt, sharding=on)

    index = jax.eval_shape(
        lambda p: build_index(p, jnp.zeros(2, F32), spec.side,
                              l_max=spec.l_max, th_quad=spec.th_quad),
        jax.ShapeDtypeStruct((n, 2), F32))
    index = jax.tree.map(lambda x: shape(x.shape, x.dtype), index)
    args = (index, shape((n, 2), F32), shape((q, 2), F32), shape((q,), I32),
            shape((q,), F32), shape((), F32), shape((), F32),
            shape((delta,), I32), shape((delta, 2), F32), None)
    statics = KnnSession(spec)._step_statics("incremental")
    compiled = session_mod._tick_step.lower(*args, **statics).compile()
    assert _compiled_kernel(compiled.as_text(), "window_fetch")
    # a select between two gathers of (N, 2) positions takes a layout
    # padded to 128 lanes, 1,033 MB of temporaries; one coordinate at a
    # time the step needs 74 MB, against the full re-sort's 61 MB
    assert compiled.memory_analysis().temp_size_in_bytes < 150e6
