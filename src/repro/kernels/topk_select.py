"""Pallas TPU kernel: per-row top-k smallest (result-list materialization).

Materializes the paper's nearest-neighbour lists (Fig. 1 linear layout): given a
(Q, C) tile of candidate distances + ids, emit the k smallest per row, ascending.
Implementation is k rounds of masked row-argmin on the VPU — for the moderate k
of the paper's sweet spot (and for MoE router top-k, which reuses this kernel
with ``-logits`` as distances) this beats a full sort; for very large k the
bucket radius + threshold path is preferred (see DESIGN.md §7).

Also the TPU answer to the paper's cached-vs-coalesced write study: the result
tile lives in VMEM and flushes as one contiguous aligned store — there is a
single sensible write pattern on TPU (DESIGN.md §3).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .refine import masked_argmin_rounds
from .runtime import pallas_call

__all__ = ["topk_select", "Q_TILE"]

Q_TILE = 8


def _make_kernel(k: int):
    def kernel(d2_ref, ids_ref, out_d_ref, out_i_ref):
        out_d, out_i = masked_argmin_rounds([(d2_ref[:, :], ids_ref[:, :])], k)
        out_d_ref[:, :] = out_d
        out_i_ref[:, :] = out_i

    return kernel


@functools.partial(jax.jit, static_argnames=("k", "interpret"))
def topk_select(d2, ids, *, k: int, interpret: bool | None = None):
    """(Q, C) distances + (Q, C) ids -> ((Q, k) dists, (Q, k) ids), ascending."""
    q, c = d2.shape
    assert q % Q_TILE == 0, q
    grid = (q // Q_TILE,)
    out_d, out_i = pallas_call(
        _make_kernel(k),
        name="topk_select",
        grid=grid,
        in_specs=[
            pl.BlockSpec((Q_TILE, c), lambda i: (i, 0)),
            pl.BlockSpec((Q_TILE, c), lambda i: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((Q_TILE, k), lambda i: (i, 0)),
            pl.BlockSpec((Q_TILE, k), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((q, k), jnp.float32),
            jax.ShapeDtypeStruct((q, k), jnp.int32),
        ],
        interpret=interpret,
    )(d2, ids)
    return out_d, out_i
