"""Selection-round helpers shared by the Pallas kernel bodies.

Factored out of the individual kernels so each contract has a single
kernel-side spelling: ``bucket_refine_step`` (the Alabi refinement round,
counted against its own bucket edges, DESIGN.md §4 — from
``bucket_kselect``/``fused_scan``), ``masked_argmin_rounds`` (the ascending
top-k materialization with the inf→-1 id padding rule — from
``topk_select``/``fused_scan``/``merge_topk``) and ``mixed_prune_keep`` (the
bf16 widened-radius prefilter of the ``precision="mixed"`` sweep mode,
DESIGN.md §14 — from the SCAN backends).  The jnp oracle
(``kernels/ref.py::bucket_refine_ref``, which ``core/kselect.py`` also
runs) is written independently on purpose — it is the correctness contract
the allclose sweeps compare the kernels against.

Every helper is written in the subset Mosaic (the TPU kernel compiler)
lowers: 2-D (rows, lanes) values only — per-row scalars are (T, 1) columns —
min/max/sum lane reductions, selects and compares; no argmax, cumsum,
gather, dynamic lane index or lane concatenation.  A row that is logically
the concatenation of several blocks (current list ‖ candidate window) is
passed as the sequence of its blocks and reduced block by block.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = [
    "MIXED_WIDEN",
    "bucket_refine_step",
    "masked_argmin_rounds",
    "mixed_prune_keep",
]

# Widening factor of the mixed-precision prefilter (DESIGN.md §14).  The bf16
# pass computes d2_b from fp32 deltas rounded to bf16 (two casts, two squares,
# one add — five roundings at machine epsilon 2^-8), so
# ``d2_b <= d2_f32 * (1 + 2^-8)^5 < d2_f32 * (1 + 6 * 2^-8)``.  Widening the
# k-th-distance threshold by 16 * 2^-8 = 2^-4 (>2.5x the bound) guarantees no
# candidate with ``d2_f32 <= kth`` is ever pruned — the exact-refine pass then
# returns bitwise-identical lists to fp32 (the pruned candidates are provably
# strictly beyond the current k-th distance, so they cannot enter the merged
# list even via the lowest-id tie-break).
MIXED_WIDEN = 1.0 + 2.0 ** -4


def mixed_prune_keep(dx, dy, kth):
    """bf16 widened-radius prefilter: keep-mask over a candidate window.

    ``dx``/``dy`` are the (T, W) **fp32 coordinate deltas** (candidate minus
    query — cast AFTER the subtraction: casting raw coordinates first would
    lose the cancellation that makes the error bound *relative*), ``kth`` the
    current exact k-th distance per query as (T,) or (T, 1)
    (``best_d[:, k-1]``; ``inf`` while the list is under-filled, which keeps
    everything).  Returns the (T, W) bool mask of candidates inside the
    conservatively widened k-th boundary.  The comparison is inclusive so
    exact k-th-distance ties (which can enter the list via the lowest-id
    rule) always survive.
    """
    dxb = dx.astype(jnp.bfloat16)
    dyb = dy.astype(jnp.bfloat16)
    d2b = (dxb * dxb + dyb * dyb).astype(jnp.float32)
    return d2b <= jnp.reshape(kth, (-1, 1)) * jnp.float32(MIXED_WIDEN)


def _row_min(blocks):
    """(T, 1) minimum over the lanes of every block."""
    out = jnp.min(blocks[0], axis=1, keepdims=True)
    for b in blocks[1:]:
        out = jnp.minimum(out, jnp.min(b, axis=1, keepdims=True))
    return out


def masked_argmin_rounds(parts, k: int):
    """k rounds of masked row-argmin: (dist, id) blocks -> ascending (T, k).

    ``parts`` is a sequence of ``(d, ids)`` pairs, (T, C_p) each, that
    together form one logical row of ``sum(C_p)`` columns in sequence order.
    The kernel-side top-k materialization (paper Fig. 1 linear layout): each
    round extracts the row minimum, records (dist, id) — +inf slots pad with
    id -1 — and masks the hit.  ``d`` must have invalid entries pre-masked to
    +inf.  Distance ties resolve to the **lowest id** (the canonical
    lexicographic ``(dist, id)`` selection contract of DESIGN.md §12): every
    backend/kernel/plan produces the same list bit-for-bit, which is what
    makes per-partition results composable under the object-sharded plans.
    Exact ``(dist, id)`` duplicates (only the +inf/-1 padding in valid use)
    resolve to the lowest column, one per round.
    """
    t = parts[0][0].shape[0]
    big = jnp.asarray(jnp.inf, jnp.float32)
    id_big = jnp.asarray(jnp.iinfo(jnp.int32).max, jnp.int32)
    ids = [i for _, i in parts]
    cols, off = [], 0
    for d, _ in parts:
        cols.append(jax.lax.broadcasted_iota(jnp.int32, d.shape, 1) + off)
        off += d.shape[1]
    slot = jax.lax.broadcasted_iota(jnp.int32, (t, k), 1)

    def body(j, state):
        ds, out_d, out_i = state
        mval = _row_min(ds)  # (T, 1)
        mid = _row_min([jnp.where(d == mval, i, id_big) for d, i in zip(ds, ids)])
        first = _row_min([
            jnp.where((d == mval) & (i == mid), c, off)
            for d, i, c in zip(ds, ids, cols)
        ])  # the lowest column holding the winning (dist, id): one hit
        ds = tuple(jnp.where(c == first, big, d) for d, c in zip(ds, cols))
        here = slot == j
        out_d = jnp.where(here, mval, out_d)
        out_i = jnp.where(here, jnp.where(mval == big, -1, mid), out_i)
        return ds, out_d, out_i

    out_d = jnp.zeros((t, k), jnp.float32)
    out_i = jnp.zeros((t, k), jnp.int32)
    _, out_d, out_i = jax.lax.fori_loop(
        0, k, body, (tuple(d for d, _ in parts), out_d, out_i)
    )
    return out_d, out_i


def bucket_refine_step(blocks, lo, hi, kth, num_bins: int):
    """Descend one histogram level toward the k-th element.

    blocks: sequence of (T, C_p) populations, invalid entries pre-masked to
    +inf; lo/hi: (T, 1) current half-open interval; kth: (T, 1) f32 count of
    elements still wanted inside it.  Returns the refined (lo, hi, kth).

    The histogram is walked as its running sum over the bucket EDGES
    ``e_b = lo + b * width``: ``cum[b]`` counts the in-range elements below
    ``e_{b+1}`` (the last bucket ends at ``hi`` itself).  The refined
    interval ``[e_sel, e_sel+1)`` is made of the very edge values the counts
    were taken against, so the elements counted below it are exactly those
    under its lower edge and the invariant ``count(lo <= d < hi) >= kth``
    carries from level to level: after the last one, ``count(d < hi) >= k``.
    (Assigning buckets by ``floor((d - lo) / width)`` instead lets rounding
    count an element on one side of an edge and test it on the other —
    enough to lose a true k-th neighbour.)  Counts are f32, exact far beyond
    any tile width; ``cum`` is non-decreasing, so the selected bucket is the
    first with ``cum >= kth`` and the count below it the last ``cum < kth``.
    If no bucket reaches kth, the interval is kept.
    """
    width = jnp.maximum((hi - lo) / num_bins, 1e-30)
    in_range = [(d2 >= lo) & (d2 < hi) for d2 in blocks]
    new_lo, new_hi, below = lo, hi, jnp.zeros_like(lo)
    found = jnp.zeros(lo.shape, bool)
    for b in range(num_bins):
        edge = lo + (b + 1) * width if b < num_bins - 1 else hi
        cum = sum(
            jnp.sum(jnp.where(r & (d2 < edge), 1.0, 0.0), axis=1, keepdims=True)
            for d2, r in zip(blocks, in_range)
        )
        under = cum < kth
        new_lo = jnp.where(under, edge, new_lo)
        below = jnp.where(under, cum, below)
        new_hi = jnp.where(found | under, new_hi, edge)
        found = found | ~under
    return (
        jnp.where(found, new_lo, lo),
        jnp.where(found, new_hi, hi),
        jnp.where(found, kth - below, kth),
    )
