"""Pure-jnp oracles for every Pallas kernel (the correctness contracts).

Each ``*_ref`` function has exactly the same signature/semantics as the jit'd
wrapper in :mod:`repro.kernels.ops`; kernel tests sweep shapes/dtypes and
``assert_allclose`` kernel-vs-oracle.
"""
from __future__ import annotations

import jax.numpy as jnp

__all__ = [
    "pairwise_dist_ref",
    "bucket_kselect_ref",
    "bucket_refine_ref",
    "topk_select_ref",
    "merge_topk_lists_ref",
]


def pairwise_dist_ref(qx, qy, px, py, valid):
    """Masked squared L2 distances: (Q,),(Q,),(C,),(C,),(C,) -> (Q, C).

    Invalid candidates map to +inf (paper Alg. 1 distance scans; SoV layout).
    """
    dx = qx[:, None] - px[None, :]
    dy = qy[:, None] - py[None, :]
    d2 = dx * dx + dy * dy
    return jnp.where(valid[None, :], d2, jnp.inf)


def bucket_kselect_ref(qx, qy, px, py, valid, *, k: int, num_bins: int, iters: int):
    """Fused distance + bucket k-selection radius (paper's findKDist pillar).

    Returns (Q,) radius r with count(valid & d2 < r) >= min(k, n_valid); rows
    with fewer than k valid candidates return +inf (paper Sec. 4.2.1).
    """
    d2 = pairwise_dist_ref(qx, qy, px, py, valid)
    n_valid = valid.sum()
    big = jnp.asarray(jnp.inf, d2.dtype)
    lo = jnp.min(d2, axis=1)
    hi0 = jnp.max(jnp.where(jnp.isinf(d2), -big, d2), axis=1)
    hi = jnp.maximum(hi0, lo) * (1 + 1e-6) + 1e-30
    kth = jnp.full((d2.shape[0],), k, jnp.int32)
    for _ in range(iters):
        lo, hi, kth = bucket_refine_ref(d2, lo, hi, kth, num_bins)
    return jnp.where(n_valid < k, big, hi)


def bucket_refine_ref(d2, lo, hi, kth, num_bins: int):
    """One histogram level over (Q, C) ``d2``: the kernel's edge-exact step.

    Bucket b holds ``e_b <= d < e_{b+1}`` with ``e_b = lo + b * width`` and
    the last bucket ending at ``hi``; the refined interval is made of the
    edge values the counts were taken against, so ``count(lo <= d < hi) >=
    kth`` holds at every level.  If no bucket reaches kth (the row has
    fewer in range), the interval is kept.
    """
    width = jnp.maximum((hi - lo) / num_bins, 1e-30)
    b = jnp.arange(1, num_bins + 1, dtype=d2.dtype)
    edges = (lo[:, None] + b[None, :] * width[:, None]).at[:, -1].set(hi)
    in_range = (d2 >= lo[:, None]) & (d2 < hi[:, None])
    cum = jnp.sum(
        in_range[:, None, :] & (d2[:, None, :] < edges[:, :, None]), axis=2
    )  # (Q, NB) running bucket counts
    under = cum < kth[:, None]
    sel = under.sum(axis=1)
    ok = ~under[:, -1]
    below = jnp.max(jnp.where(under, cum, 0), axis=1)
    take = lambda j: jnp.take_along_axis(edges, j[:, None], 1)[:, 0]
    new_lo = jnp.where(sel > 0, take(jnp.maximum(sel - 1, 0)), lo)
    new_hi = take(jnp.minimum(sel, num_bins - 1))
    return (
        jnp.where(ok, new_lo, lo),
        jnp.where(ok, new_hi, hi),
        jnp.where(ok, kth - below, kth),
    )


def topk_select_ref(d2, ids, *, k: int):
    """Per-row k smallest: (Q, C) dists + (Q, C) ids -> ((Q, k) d2, (Q, k) ids).

    Ascending; +inf / -1 padded.  This is the result-list materialization of the
    paper (Fig. 1 linear layout) and doubles as MoE top-k routing (on -logits).
    Distance ties resolve to the lowest id — the canonical lexicographic
    ``(d2, id)`` selection order (DESIGN.md §12) shared by every SCAN/MERGE
    backend, which makes selection a pure function of the candidate *set*:
    composable across arbitrary object partitions, hence across plans.
    """
    import jax

    sd, si = jax.lax.sort((d2, ids), num_keys=2)
    out_d = sd[:, :k]
    out_i = jnp.where(jnp.isinf(out_d), -1, si[:, :k])
    return out_d, out_i


def merge_topk_lists_ref(d_a, i_a, d_b, i_b, *, k: int):
    """Merge two ascending per-row (dist, id) lists -> k smallest of the union.

    The reduction operator of the sharded plans (DESIGN.md §10/§12): both
    inputs ascending and +inf/-1 padded, output likewise; distance ties
    resolve to the lowest id — identical contract to the SCAN backends, so
    per-partition partial results compose *bit-exactly*:
    ``knn(A ∪ B) = merge(knn(A), knn(B))``.
    """
    all_d = jnp.concatenate([d_a, d_b], axis=1)
    all_i = jnp.concatenate([i_a, i_b], axis=1)
    return topk_select_ref(all_d, all_i, k=k)
