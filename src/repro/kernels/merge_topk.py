"""Pallas TPU kernel: merge two ascending (dist, id) result lists per row.

The reduction operator of the object-sharded execution plans (DESIGN.md
§10/§12): given two partial k-NN result lists per query — each ascending,
``+inf``/``-1`` padded, produced against *disjoint* candidate subsets — emit
the k smallest of the union, ascending, under the same canonical
lexicographic ``(d2, id)`` tie contract as the SCAN backends (distance ties
resolve to the lowest id).  This is what makes per-partition k-NN composable
*bit-exactly*: ``knn(P_a ∪ P_b) = merge(knn(P_a), knn(P_b))`` — the
per-partition merge of Gowanlock's hybrid KNN-join, wired into the
``object_sharded``/``hybrid`` plans' cross-device tree reduction
(``kernels.ops.tree_merge_lists``).

Implementation mirrors ``topk_select``: the concatenated (T, ka+kb) row lives
in VMEM and is materialized by k masked argmin rounds — for list-sized inputs
(ka, kb ~ k) this is a tiny tile, and the ascending property lets the wrapper
pre-slice each input to its first k columns before dispatch.

Two entry points:

* :func:`merge_topk_lists` — the binary operator (one pair per call), the
  reduction step of ``tree_merge_lists``'s pairwise tree;
* :func:`merge_topk_multi` — the R-way fusion (DESIGN.md §14): ALL R partial
  lists of a query concatenate into one (T, R*k) VMEM row and materialize in
  a single ``pallas_call``.  The binary tree dispatches ``R - 1`` kernels
  whose (Q, k) intermediates round-trip HBM between rounds; the multi-way
  form reads R*Q*k list entries once and writes Q*k once — same bits (the
  canonical (d2, id) selection over the union is associative), ~log2(R)x
  less list traffic.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .refine import masked_argmin_rounds
from .runtime import pallas_call

__all__ = ["merge_topk_lists", "merge_topk_multi", "Q_TILE"]

Q_TILE = 8


def _make_multi_kernel(k: int):
    def kernel(d_ref, i_ref, out_d_ref, out_i_ref):
        out_d, out_i = masked_argmin_rounds([(d_ref[:, :], i_ref[:, :])], k)
        out_d_ref[:, :] = out_d
        out_i_ref[:, :] = out_i

    return kernel


@functools.partial(jax.jit, static_argnames=("k", "interpret"))
def merge_topk_multi(d_cat, i_cat, *, k: int, interpret: bool | None = None):
    """(Q, R*k) concatenated ascending lists -> (Q, k) merged, ONE kernel.

    The caller lays the R per-shard lists of each query side by side
    (``ops.multi_merge_lists_op`` does the transpose/reshape); the kernel is
    the ``topk_select`` body over that row — k masked argmin rounds with the
    canonical lowest-id tie-break, so the output is bit-identical to folding
    the same lists through the binary ``merge_topk_lists`` tree.
    Q must be a multiple of Q_TILE (the wrapper pads); inputs are f32/int32.
    """
    q, c = d_cat.shape
    assert q % Q_TILE == 0, q
    grid = (q // Q_TILE,)
    row = lambda i: (i, 0)
    out_d, out_i = pallas_call(
        _make_multi_kernel(k),
        name="merge_topk_multi",
        grid=grid,
        in_specs=[
            pl.BlockSpec((Q_TILE, c), row),
            pl.BlockSpec((Q_TILE, c), row),
        ],
        out_specs=[
            pl.BlockSpec((Q_TILE, k), row),
            pl.BlockSpec((Q_TILE, k), row),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((q, k), jnp.float32),
            jax.ShapeDtypeStruct((q, k), jnp.int32),
        ],
        interpret=interpret,
    )(d_cat, i_cat)
    return out_d, out_i


def _make_kernel(k: int):
    def kernel(da_ref, ia_ref, db_ref, ib_ref, out_d_ref, out_i_ref):
        # the row [a ‖ b], kept as its two blocks (no lane concatenation)
        out_d, out_i = masked_argmin_rounds(
            [(da_ref[:, :], ia_ref[:, :]), (db_ref[:, :], ib_ref[:, :])], k
        )
        out_d_ref[:, :] = out_d
        out_i_ref[:, :] = out_i

    return kernel


@functools.partial(jax.jit, static_argnames=("k", "interpret"))
def merge_topk_lists(d_a, i_a, d_b, i_b, *, k: int, interpret: bool | None = None):
    """(Q, ka)+(Q, kb) ascending lists -> (Q, k) merged ascending list.

    Q must be a multiple of Q_TILE (``ops.merge_topk_lists_op`` pads and
    casts to f32/int32).
    """
    q, ca = d_a.shape
    cb = d_b.shape[1]
    assert q % Q_TILE == 0, q
    grid = (q // Q_TILE,)
    row = lambda i: (i, 0)
    out_d, out_i = pallas_call(
        _make_kernel(k),
        name="merge_topk_lists",
        grid=grid,
        in_specs=[
            pl.BlockSpec((Q_TILE, ca), row),
            pl.BlockSpec((Q_TILE, ca), row),
            pl.BlockSpec((Q_TILE, cb), row),
            pl.BlockSpec((Q_TILE, cb), row),
        ],
        out_specs=[
            pl.BlockSpec((Q_TILE, k), row),
            pl.BlockSpec((Q_TILE, k), row),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((q, k), jnp.float32),
            jax.ShapeDtypeStruct((q, k), jnp.int32),
        ],
        interpret=interpret,
    )(d_a, i_a, d_b, i_b)
    return out_d, out_i
