"""Pallas TPU kernel: fused SCAN-step merge — distance + bucket prune + top-k.

This is the per-iteration inner join of the pipeline (paper Sec. 4.2) as ONE
kernel: each grid step takes a Q_TILE of queries, their gathered candidate
window (per-query rows, unlike ``bucket_kselect``'s shared window), and the
current ascending result lists, and emits the merged lists.  Everything between
the coordinate planes (in) and the (Q, k) lists (out) — the distance tile, the
histogram refinement, the merge working set — lives in VMEM for the whole step
(DESIGN.md §7): HBM traffic is O(Q·W) coordinates in + O(Q·k) lists out, never
the O(Q·(W+k)) distance matrix that the unfused path materializes between the
distance op and the selection op.

Selection is two-phase, both pillars of the paper fused back-to-back:
  1. **bucket k-selection** (Alabi et al., Sec. 4.2.1): refine a per-query
     radius r over the combined [current list ‖ window] population with
     ``count(d < r) >= min(k, n_valid)`` — so every true top-k member is < r;
  2. **masked argmin rounds** on the r-pruned row materialize the ascending
     (dist, id) lists, exactly like ``topk_select`` but on VMEM-resident
     distances.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .refine import bucket_refine_step, masked_argmin_rounds, mixed_prune_keep
from .runtime import pallas_call

__all__ = ["fused_scan_merge", "Q_TILE"]

Q_TILE = 8


def _make_kernel(k: int, num_bins: int, iters: int, precision: str):
    def kernel(
        qx_ref, qy_ref, cx_ref, cy_ref, cids_ref, valid_ref,
        best_d_ref, best_i_ref, out_d_ref, out_i_ref,
    ):
        qx = qx_ref[:, :]  # (T, 1)
        qy = qy_ref[:, :]
        valid = valid_ref[:, :] != 0  # (T, W)
        big = jnp.asarray(jnp.inf, jnp.float32)

        dx = cx_ref[:, :] - qx
        dy = cy_ref[:, :] - qy
        if precision == "mixed":
            # bf16 prefilter against the widened exact k-th boundary
            # (DESIGN.md §14): candidates strictly beyond the current k-th
            # distance drop out of the fp32 distance tile AND the bucket
            # refinement population below — entirely in VMEM, so the win is
            # VPU work, not an extra HBM pass.  Bitwise-neutral: the argmin
            # rounds still pick the exact k smallest of the survivors, and
            # no true top-k member (ties included) can be pruned.
            valid = valid & mixed_prune_keep(dx, dy, best_d_ref[:, k - 1:k])
        d2 = jnp.where(valid, dx * dx + dy * dy, big)  # (T, W) — stays in VMEM

        # the merge population is the row [current list ‖ window], kept as
        # its two blocks (no lane concatenation)
        pop = (best_d_ref[:, :], d2)
        n_valid = sum(
            jnp.sum(jnp.where(d < big, 1.0, 0.0), axis=1, keepdims=True)
            for d in pop
        )  # (T, 1)

        # --- pillar 1: bucket refinement of the k-th-distance radius.
        lo = jnp.minimum(*(jnp.min(d, axis=1, keepdims=True) for d in pop))
        hi0 = jnp.maximum(*(
            jnp.max(jnp.where(d < big, d, -big), axis=1, keepdims=True)
            for d in pop
        ))
        hi = jnp.maximum(hi0, lo) * (1 + 1e-6) + 1e-30
        kth = jnp.full_like(lo, k)

        def refine(_, state):
            lo, hi, kth = state
            return bucket_refine_step(pop, lo, hi, kth, num_bins)

        _, fhi, _ = jax.lax.fori_loop(0, iters, refine, (lo, hi, kth))
        # the refinement keeps count(d < fhi) >= k (its edge-exact counting),
        # so every true top-k member, ties at the k-th distance included,
        # survives the prune; the argmin rounds pick the exact k smallest
        radius = jnp.where(n_valid < k, big, fhi)

        # --- pillar 2: ascending materialization by masked argmin rounds.
        out_d, out_i = masked_argmin_rounds(
            [
                (jnp.where(d < radius, d, big), i)
                for d, i in zip(pop, (best_i_ref[:, :], cids_ref[:, :]))
            ],
            k,
        )
        out_d_ref[:, :] = out_d
        out_i_ref[:, :] = out_i

    return kernel


@functools.partial(
    jax.jit, static_argnames=("k", "num_bins", "iters", "precision", "interpret")
)
def fused_scan_merge(
    qx, qy, cx, cy, cids, valid, best_d, best_i,
    *,
    k: int,
    num_bins: int = 32,
    iters: int = 4,
    precision: str = "fp32",
    interpret: bool | None = None,
):
    """(Q,) queries x (Q, W) per-query windows x (Q, k) lists -> merged lists.

    Semantics match the unfused dense path bit for bit (canonical
    ``(d², id)`` order, lowest id first among ties): ``merge(best, window)``
    = k smallest of the union, ascending, (-1, inf) padded.  Q must be a
    multiple of Q_TILE (wrappers pad).
    ``precision="mixed"`` adds the in-VMEM bf16 widened-radius prefilter —
    bitwise-identical output (tests/test_properties.py fuzzes the parity).
    ``interpret=None`` compiles for the TPU and interprets where lowered for
    the CPU (:func:`repro.kernels.runtime.pallas_call`).
    """
    q, w = cx.shape
    assert q % Q_TILE == 0, q
    row = lambda i: (i, 0)
    out_d, out_i = pallas_call(
        _make_kernel(k, num_bins, iters, precision),
        name="fused_scan",
        grid=(q // Q_TILE,),
        in_specs=[
            pl.BlockSpec((Q_TILE, 1), row),
            pl.BlockSpec((Q_TILE, 1), row),
            pl.BlockSpec((Q_TILE, w), row),
            pl.BlockSpec((Q_TILE, w), row),
            pl.BlockSpec((Q_TILE, w), row),
            pl.BlockSpec((Q_TILE, w), row),
            pl.BlockSpec((Q_TILE, k), row),
            pl.BlockSpec((Q_TILE, k), row),
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
    )(
        qx.reshape(q, 1), qy.reshape(q, 1), cx, cy, cids,
        valid.astype(jnp.int32), best_d, best_i,
    )
    return out_d, out_i
