"""Bucket-based k-selection (Alabi et al.), the paper's first pillar (Sec. 4.2.1).

Given per-query candidate distances, find a per-query radius ``dist_k`` enclosing
(at least) the k nearest candidates *without sorting*: iteratively histogram the
distances into ``num_bins`` buckets over a shrinking [lo, hi) range and descend into
the bucket containing the k-th element.

This module is the pure-jnp reference; ``repro.kernels.bucket_kselect`` is the fused
Pallas version that never materializes the distance matrix in HBM.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels.ref import bucket_refine_ref

__all__ = ["find_kdist"]


@partial(jax.jit, static_argnames=("k", "num_bins", "iters"))
def find_kdist(
    dist2: jnp.ndarray,
    valid: jnp.ndarray,
    *,
    k: int,
    num_bins: int = 32,
    iters: int = 4,
) -> jnp.ndarray:
    """Per-row k-selection radius.

    Parameters
    ----------
    dist2: (Q, C) squared distances (rows = queries, cols = candidates).
    valid: (Q, C) bool mask of real candidates.
    k: number of neighbours wanted.
    num_bins / iters: bucket refinement parameters — after ``iters`` rounds the
        returned radius is the upper edge of the bucket containing the k-th element,
        i.e. ``count(d < radius) >= k`` and the excess is < (range / num_bins**iters)
        wide in distance.

    Returns
    -------
    (Q,) radius r with ``count(valid & (dist2 < r)) >= min(k, count(valid))``.
    Rows with fewer than k valid candidates return +inf (paper: findKDist returns
    +inf when |c| < k, no computation needed).
    """
    q = dist2.shape[0]
    big = jnp.asarray(jnp.inf, dist2.dtype)
    d = jnp.where(valid, dist2, big)
    n_valid = valid.sum(axis=1)

    lo = jnp.min(jnp.where(valid, dist2, big), axis=1)  # (Q,)
    hi = jnp.max(jnp.where(valid, dist2, -big), axis=1)
    hi = jnp.maximum(hi, lo) * (1 + 1e-6) + 1e-30  # half-open upper edge
    kth = jnp.full((q,), k, jnp.int32)

    def body(_, state):
        # one histogram level, bucket edges exact (kernels/ref.py)
        return bucket_refine_ref(d, *state, num_bins)

    lo, hi, kth = jax.lax.fori_loop(0, iters, body, (lo, hi, kth))
    r = hi
    return jnp.where(n_valid < k, big, r)
