"""Jit'd public wrappers around the Pallas kernels + the SCAN backend registry.

Two things live here:

1. **Padding wrappers** (``*_op``): pad ragged shapes to kernel tile multiples,
   dispatch, slice back.  A program lowered for the TPU compiles every kernel
   to Mosaic; one lowered for the CPU runs the kernel body in interpret mode
   (as XLA ops on the host) — see :func:`repro.kernels.runtime.pallas_call`.

2. **The scan-backend registry** (DESIGN.md §6): the pipeline's SCAN step —
   "merge one window of gathered candidates into each query's ascending result
   list" — is a pluggable strategy selected by name.  All backends implement
   ``merge(qpos, cpos, cids, valid, best_d, best_i, k, precision="fp32")``
   with identical semantics (k smallest of the union, ascending, (-1, inf)
   padded; distance ties resolved to the lowest id — the canonical
   lexicographic ``(d2, id)`` selection order of DESIGN.md §12) so they are
   interchangeable under the executor *bit-for-bit*:

   - ``dense_topk``   XLA ``lax.top_k`` over the concatenated row (seed path);
   - ``fused_bucket`` one Pallas kernel: distance tile + Alabi bucket radius +
                      masked argmin rounds, all VMEM-resident (DESIGN.md §7);
   - ``brute``        full per-row sort (Garcia-baseline flavour: selection
                      cost independent of k, the S2 yardstick).

   ``precision="mixed"`` (DESIGN.md §14) prepends the bf16 widened-radius
   prefilter (``refine.mixed_prune_keep``) to any backend's exact fp32
   selection — results stay bitwise-identical to fp32 (the property harness
   fuzzes the parity across the whole backend x plan x partitioner matrix).
"""
from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

from . import bucket_kselect as _bk
from . import fused_scan as _fs
from . import merge_topk as _mt
from . import pairwise_dist as _pd
from . import topk_select as _tk
from .ref import merge_topk_lists_ref
from .refine import mixed_prune_keep

__all__ = [
    "pairwise_dist_op",
    "bucket_kselect_op",
    "topk_select_op",
    "fused_scan_merge_op",
    "merge_topk_lists_op",
    "multi_merge_lists_op",
    "tree_merge_lists",
    "register_scan_backend",
    "get_scan_backend",
    "scan_backend_names",
    "register_merge_backend",
    "get_merge_backend",
    "merge_backend_names",
]


def _pad_to(x, n, fill):
    if x.shape[0] == n:
        return x
    pad = n - x.shape[0]
    return jnp.concatenate([x, jnp.full((pad,) + x.shape[1:], fill, x.dtype)])


def pairwise_dist_op(qpos, ppos, valid=None, *, interpret: bool | None = None):
    """(Q,2) x (C,2) [+ (C,) mask] -> (Q, C) masked squared distances."""
    q, c = qpos.shape[0], ppos.shape[0]
    qp = int(np.ceil(q / _pd.Q_TILE)) * _pd.Q_TILE
    cp = int(np.ceil(c / _pd.C_TILE)) * _pd.C_TILE
    if valid is None:
        valid = jnp.ones((c,), bool)
    qx = _pad_to(qpos[:, 0].astype(jnp.float32), qp, 0)
    qy = _pad_to(qpos[:, 1].astype(jnp.float32), qp, 0)
    px = _pad_to(ppos[:, 0].astype(jnp.float32), cp, 0)
    py = _pad_to(ppos[:, 1].astype(jnp.float32), cp, 0)
    v = _pad_to(valid, cp, False)
    out = _pd.pairwise_dist(qx, qy, px, py, v, interpret=interpret)
    return out[:q, :c]


def bucket_kselect_op(
    qpos,
    ppos,
    valid=None,
    *,
    k: int,
    num_bins: int = 32,
    iters: int = 4,
    interpret: bool | None = None,
):
    """(Q,2) queries x (C,2) shared candidates -> (Q,) k-selection radius."""
    q, c = qpos.shape[0], ppos.shape[0]
    qp = int(np.ceil(q / _bk.Q_TILE)) * _bk.Q_TILE
    if valid is None:
        valid = jnp.ones((c,), bool)
    qx = _pad_to(qpos[:, 0].astype(jnp.float32), qp, 0)
    qy = _pad_to(qpos[:, 1].astype(jnp.float32), qp, 0)
    out = _bk.bucket_kselect(
        qx,
        qy,
        ppos[:, 0].astype(jnp.float32),
        ppos[:, 1].astype(jnp.float32),
        valid,
        k=k,
        num_bins=num_bins,
        iters=iters,
        interpret=interpret,
    )
    return out[:q]


def topk_select_op(d2, ids, *, k: int, interpret: bool | None = None):
    """(Q, C) distances + ids -> ((Q, k), (Q, k)) ascending top-k smallest."""
    q = d2.shape[0]
    qp = int(np.ceil(q / _tk.Q_TILE)) * _tk.Q_TILE
    d2p = _pad_to(d2.astype(jnp.float32), qp, jnp.inf)
    idsp = _pad_to(ids.astype(jnp.int32), qp, -1)
    out_d, out_i = _tk.topk_select(d2p, idsp, k=k, interpret=interpret)
    return out_d[:q], out_i[:q]


def fused_scan_merge_op(
    qpos, cpos, cids, valid, best_d, best_i, *, k: int,
    precision: str = "fp32",
    interpret: bool | None = None,
):
    """Pad-and-dispatch wrapper for :func:`repro.kernels.fused_scan.fused_scan_merge`.

    qpos (Q,2) x per-query windows cpos (Q,W,2) / cids / valid (Q,W) x current
    lists best_d/best_i (Q,k) -> merged (Q,k) lists.
    """
    q = qpos.shape[0]
    qp = int(np.ceil(q / _fs.Q_TILE)) * _fs.Q_TILE
    qx = _pad_to(qpos[:, 0].astype(jnp.float32), qp, 0)
    qy = _pad_to(qpos[:, 1].astype(jnp.float32), qp, 0)
    cx = _pad_to(cpos[:, :, 0].astype(jnp.float32), qp, 0)
    cy = _pad_to(cpos[:, :, 1].astype(jnp.float32), qp, 0)
    ci = _pad_to(cids.astype(jnp.int32), qp, -1)
    v = _pad_to(valid, qp, False)
    bd = _pad_to(best_d.astype(jnp.float32), qp, jnp.inf)
    bi = _pad_to(best_i.astype(jnp.int32), qp, -1)
    out_d, out_i = _fs.fused_scan_merge(
        qx, qy, cx, cy, ci, v, bd, bi, k=k, precision=precision,
        interpret=interpret,
    )
    return out_d[:q], out_i[:q]


def merge_topk_lists_op(
    d_a, i_a, d_b, i_b, *, k: int, interpret: bool | None = None
):
    """Pad-and-dispatch wrapper for :func:`repro.kernels.merge_topk.merge_topk_lists`.

    Two ascending +inf/-1-padded lists per row, (Q, ka) and (Q, kb), -> the k
    smallest of the union, ascending (DESIGN.md §10 merge contract).  Because
    the inputs are ascending, only the first k columns of each can reach the
    output — they are sliced off before dispatch so the kernel tile is at most
    (Q_TILE, 2k).
    """
    q = d_a.shape[0]
    d_a, i_a = d_a[:, :k], i_a[:, :k]
    d_b, i_b = d_b[:, :k], i_b[:, :k]
    qp = int(np.ceil(max(q, 1) / _mt.Q_TILE)) * _mt.Q_TILE
    da = _pad_to(d_a.astype(jnp.float32), qp, jnp.inf)
    ia = _pad_to(i_a.astype(jnp.int32), qp, -1)
    db = _pad_to(d_b.astype(jnp.float32), qp, jnp.inf)
    ib = _pad_to(i_b.astype(jnp.int32), qp, -1)
    out_d, out_i = _mt.merge_topk_lists(da, ia, db, ib, k=k, interpret=interpret)
    return out_d[:q], out_i[:q]


# --------------------------------------------------------------------------
# SCAN backend registry
# --------------------------------------------------------------------------

# merge(qpos, cpos, cids, valid, best_d, best_i, k, precision="fp32")
#   -> (best_d, best_i)
ScanMergeFn = Callable[..., tuple]

_SCAN_BACKENDS: dict[str, ScanMergeFn] = {}


def register_scan_backend(name: str):
    """Decorator: register a SCAN merge strategy under ``name``."""

    def deco(fn: ScanMergeFn) -> ScanMergeFn:
        _SCAN_BACKENDS[name] = fn
        return fn

    return deco


def get_scan_backend(name: str) -> ScanMergeFn:
    try:
        return _SCAN_BACKENDS[name]
    except KeyError:
        raise ValueError(
            f"unknown scan backend {name!r}; registered: {scan_backend_names()}"
        ) from None


def scan_backend_names() -> tuple[str, ...]:
    return tuple(sorted(_SCAN_BACKENDS))


def _lex_sort_merge(qpos, cpos, cids, valid, best_d, best_i, k: int,
                    precision: str = "fp32"):
    """Concatenated row -> XLA two-key ``lax.sort``, lexicographic (d2, id).

    One body for both the ``dense_topk`` and ``brute`` names: the canonical
    lowest-id tie order (DESIGN.md §12) cannot be expressed by
    ``lax.top_k`` (its tie-break is positional), so the seed top_k path and
    the full-row-sort Garcia flavour collapse into the same program — a
    k-independent full sort.  Both names stay registered for the serving/
    benchmark surface; s4 rows for them now measure the same executable.

    Under ``precision="mixed"`` the bf16 widened-radius prefilter narrows the
    validity mask first; the exact fp32 sort below then re-ranks only the
    survivors — same bits (DESIGN.md §14).
    """
    dx = cpos[:, :, 0] - qpos[:, None, 0]
    dy = cpos[:, :, 1] - qpos[:, None, 1]
    if precision == "mixed":
        valid = valid & mixed_prune_keep(dx, dy, best_d[:, k - 1])
    d2 = jnp.where(valid, dx * dx + dy * dy, jnp.inf)
    all_d = jnp.concatenate([best_d, d2], axis=1)
    all_i = jnp.concatenate([best_i, cids.astype(jnp.int32)], axis=1)
    sd, si = jax.lax.sort((all_d, all_i), num_keys=2)
    out_d = sd[:, :k]
    return out_d, jnp.where(jnp.isinf(out_d), -1, si[:, :k])


register_scan_backend("dense_topk")(_lex_sort_merge)


@register_scan_backend("fused_bucket")
def _fused_bucket_merge(qpos, cpos, cids, valid, best_d, best_i, k: int,
                        precision: str = "fp32"):
    """Fused Pallas kernel; compiled on the TPU (runtime.pallas_call).

    ``precision`` rides into the kernel as a static: the mixed-mode prefilter
    runs on the VMEM-resident distance deltas, not as a separate pass.
    """
    return fused_scan_merge_op(
        qpos, cpos, cids, valid, best_d, best_i, k=k, precision=precision
    )


register_scan_backend("brute")(_lex_sort_merge)


# --------------------------------------------------------------------------
# MERGE backend registry — the reduction step of sharded plans (DESIGN.md §10)
# --------------------------------------------------------------------------

# merge(d_a, i_a, d_b, i_b, k) -> (d, i): k smallest of the union of two
# ascending +inf/-1-padded lists, ascending, same tie contract as SCAN.
MergeListsFn = Callable[..., tuple]

_MERGE_BACKENDS: dict[str, MergeListsFn] = {}


def register_merge_backend(name: str):
    """Decorator: register a result-list merge strategy under ``name``."""

    def deco(fn: MergeListsFn) -> MergeListsFn:
        _MERGE_BACKENDS[name] = fn
        return fn

    return deco


def get_merge_backend(name: str) -> MergeListsFn:
    try:
        return _MERGE_BACKENDS[name]
    except KeyError:
        raise ValueError(
            f"unknown merge backend {name!r}; registered: {merge_backend_names()}"
        ) from None


def merge_backend_names() -> tuple[str, ...]:
    return tuple(sorted(_MERGE_BACKENDS))


@register_merge_backend("dense_merge")
def _dense_merge_lists(d_a, i_a, d_b, i_b, k: int):
    """XLA ``lax.top_k`` over the concatenated row (jnp mirror of the kernel)."""
    return merge_topk_lists_ref(d_a, i_a, d_b, i_b, k=k)


@register_merge_backend("fused_merge")
def _fused_merge_lists(d_a, i_a, d_b, i_b, k: int):
    """Pallas kernel; compiled on the TPU (runtime.pallas_call)."""
    return merge_topk_lists_op(d_a, i_a, d_b, i_b, k=k)


def multi_merge_lists_op(d_all, i_all, *, k: int, interpret: bool | None = None):
    """(R, Q, ≥k) per-shard lists -> (Q, k), ONE fused Pallas program.

    The R-way fusion of the merge epilogue (DESIGN.md §14): each query's R
    partial lists are laid side by side into one (Q, R*k) row — a pure
    transpose/reshape, fused into the gather by XLA — and materialized by a
    single ``merge_topk_multi`` dispatch.  Bit-identical to folding the same
    lists through the binary tree (the canonical selection over a union is
    associative; pinned in tests/test_kernels.py), but the (Q, k)
    intermediates of the ``R - 1`` pairwise merges never exist, so partial
    lists cross HBM exactly once.
    """
    r, q = d_all.shape[0], d_all.shape[1]
    d_cat = jnp.swapaxes(d_all[:, :, :k], 0, 1).reshape(q, r * k)
    i_cat = jnp.swapaxes(i_all[:, :, :k], 0, 1).reshape(q, r * k)
    qp = int(np.ceil(max(q, 1) / _mt.Q_TILE)) * _mt.Q_TILE
    d_cat = _pad_to(d_cat.astype(jnp.float32), qp, jnp.inf)
    i_cat = _pad_to(i_cat.astype(jnp.int32), qp, -1)
    out_d, out_i = _mt.merge_topk_multi(d_cat, i_cat, k=k, interpret=interpret)
    return out_d[:q], out_i[:q]


@register_merge_backend("fused_multi")
def _fused_multi_lists(d_a, i_a, d_b, i_b, k: int):
    """Binary form of the R-way fused merge (registry signature adapter).

    Selecting ``merge="fused_multi"`` on a plan makes ``tree_merge_lists``
    collapse the whole reduction into one ``multi_merge_lists_op`` dispatch;
    this pairwise form exists so the name also satisfies the binary MERGE
    contract (and its validation) on its own.  The contract admits lists of
    different widths (narrower than k on under-full shards), so each side is
    (inf, -1)-padded to a common k-column block before stacking.
    """

    def _block(d, i):
        d = d[:, :k].astype(jnp.float32)
        i = i[:, :k].astype(jnp.int32)
        pad = k - d.shape[1]
        if pad > 0:
            q = d.shape[0]
            d = jnp.concatenate(
                [d, jnp.full((q, pad), jnp.inf, jnp.float32)], axis=1)
            i = jnp.concatenate([i, jnp.full((q, pad), -1, jnp.int32)], axis=1)
        return d, i

    da, ia = _block(d_a, i_a)
    db, ib = _block(d_b, i_b)
    return multi_merge_lists_op(jnp.stack([da, db]), jnp.stack([ia, ib]), k=k)


def tree_merge_lists(d_all, i_all, *, k: int, merge="dense_merge"):
    """(R, Q, ≥k) per-shard lists -> (Q, k) merged list.

    The reduction of the object-sharded plans (DESIGN.md §12): ``R`` partial
    result lists — one per object shard, each ascending and +inf/-1 padded —
    are pairwise-merged in ``ceil(log2 R)`` rounds with the selected MERGE
    backend.  Because the merge operator is the canonical lexicographic
    ``(d2, id)`` k-selection, the reduction is associative and commutative on
    id-disjoint inputs: any tree shape yields the same bits, and the result
    equals ``knn`` over the union of the partitions (the composition law,
    pinned R-way in tests/test_kernels.py).

    ``merge="fused_multi"`` short-circuits the tree entirely: the whole
    reduction runs as ONE Pallas program over the (Q, R*k) concatenated row
    (:func:`multi_merge_lists_op`) — same bits, no per-round HBM round-trips
    (DESIGN.md §14).

    ``R`` need not be a power of two: odd tails pass through a round unmerged.
    Shapes are static (R is a Python int), so under ``jit`` the tree unrolls
    into a fixed ``log2 R``-deep program.
    """
    if isinstance(merge, str) and merge == "fused_multi":
        if d_all.shape[0] < 1:
            raise ValueError("tree_merge_lists needs at least one shard list")
        return multi_merge_lists_op(d_all, i_all, k=k)
    fn = get_merge_backend(merge) if isinstance(merge, str) else merge
    lists = [(d_all[r], i_all[r]) for r in range(d_all.shape[0])]
    if not lists:
        raise ValueError("tree_merge_lists needs at least one shard list")
    while len(lists) > 1:
        nxt = []
        for a in range(0, len(lists) - 1, 2):
            (da, ia), (db, ib) = lists[a], lists[a + 1]
            nxt.append(fn(da, ia, db, ib, k))
        if len(lists) % 2:
            nxt.append(lists[-1])
        lists = nxt
    d, i = lists[0]
    return d[:, :k], i[:, :k]
