"""Pallas TPU kernel: fused distance + bucket k-selection (paper Sec. 4.2.1).

The paper's second pillar is the bucket k-selection of Alabi et al.: find a
radius enclosing the k nearest candidates by iterative histogram refinement,
*without* sorting and without materializing distances.  The GPU version runs one
query per thread with a private refinement loop; the TPU version processes a
Q_TILE of queries per grid step with the whole candidate window resident in
VMEM: distances are (re)computed on the VPU, the per-query histogram is built by
bin-broadcast compares, and the refinement loop is a ``lax.fori_loop`` — the
distance matrix never touches HBM (the fusion is the win; see DESIGN.md §7).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .refine import bucket_refine_step
from .runtime import pallas_call

__all__ = ["bucket_kselect", "Q_TILE"]

Q_TILE = 8


def _make_kernel(k: int, num_bins: int, iters: int):
    def kernel(qx_ref, qy_ref, px_ref, py_ref, valid_ref, out_ref):
        valid = valid_ref[:, :] != 0  # (1, C)
        dx = qx_ref[:, :] - px_ref[:, :]  # (Q_TILE, 1) - (1, C)
        dy = qy_ref[:, :] - py_ref[:, :]
        d2 = dx * dx + dy * dy
        big = jnp.asarray(jnp.inf, d2.dtype)
        d2 = jnp.where(valid, d2, big)
        n_valid = jnp.sum(jnp.where(valid, 1.0, 0.0), axis=1, keepdims=True)

        lo = jnp.min(d2, axis=1, keepdims=True)
        hi0 = jnp.max(jnp.where(valid, d2, -big), axis=1, keepdims=True)
        hi = jnp.maximum(hi0, lo) * (1 + 1e-6) + 1e-30
        kth = jnp.full_like(lo, k)

        def body(_, state):
            lo, hi, kth = state
            return bucket_refine_step((d2,), lo, hi, kth, num_bins)

        lo, hi, kth = jax.lax.fori_loop(0, iters, body, (lo, hi, kth))
        out_ref[:, :] = jnp.where(n_valid < k, big, hi)

    return kernel


@functools.partial(
    jax.jit, static_argnames=("k", "num_bins", "iters", "interpret")
)
def bucket_kselect(
    qx,
    qy,
    px,
    py,
    valid,
    *,
    k: int,
    num_bins: int = 32,
    iters: int = 4,
    interpret: bool | None = None,
):
    """(Q,) queries x (C,) shared candidate window -> (Q,) k-selection radius.

    Guarantee: ``count(valid & d2 < r) >= min(k, n_valid)`` per query, with the
    excess bounded by one bucket width after ``iters`` refinements; rows with
    fewer than k valid candidates return +inf.  ``interpret``: see
    :func:`repro.kernels.runtime.pallas_call`.
    """
    q, c = qx.shape[0], px.shape[0]
    assert q % Q_TILE == 0, q
    col = pl.BlockSpec((Q_TILE, 1), lambda i: (i, 0))
    row = pl.BlockSpec((1, c), lambda i: (0, 0))
    out = pallas_call(
        _make_kernel(k, num_bins, iters),
        name="bucket_kselect",
        grid=(q // Q_TILE,),
        in_specs=[col, col, row, row, row],
        out_specs=col,
        out_shape=jax.ShapeDtypeStruct((q, 1), jnp.float32),
        interpret=interpret,
    )(
        qx.reshape(q, 1), qy.reshape(q, 1), px.reshape(1, c), py.reshape(1, c),
        valid.astype(jnp.int32).reshape(1, c),
    )
    return out[:, 0]
