"""Pallas TPU kernel: masked pairwise squared-L2 distance tile (paper Alg. 1).

The paper's distance scans stream a cell's objects past each query thread.  On
TPU we instead compute a (Q_TILE x C_TILE) distance tile per grid step with the
operands resident in VMEM: queries and candidates arrive as *structure-of-vectors*
planes (x‖y — the paper's SoV layout, Sec. 3.4.1), the tile is pure VPU
elementwise work, and results stream back to HBM one aligned tile at a time.

For 2-D points arithmetic intensity is ~0.25 flop/byte — the kernel is memory
bound; its value is feeding the fused consumers (``bucket_kselect``) without a
round-trip through HBM, and providing the BlockSpec tiling pattern they inherit.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .runtime import pallas_call

__all__ = ["pairwise_dist", "Q_TILE", "C_TILE"]

Q_TILE = 8
C_TILE = 128


def _kernel(qx_ref, qy_ref, px_ref, py_ref, valid_ref, out_ref):
    dx = qx_ref[:, :] - px_ref[:, :]  # (Q_TILE, 1) - (1, C_TILE)
    dy = qy_ref[:, :] - py_ref[:, :]
    d2 = dx * dx + dy * dy
    out_ref[:, :] = jnp.where(valid_ref[:, :] != 0, d2, jnp.inf)


@functools.partial(jax.jit, static_argnames=("interpret",))
def pairwise_dist(qx, qy, px, py, valid, *, interpret: bool | None = None):
    """(Q,),(Q,),(C,),(C,),(C,)bool -> (Q, C) f32 masked squared distances.

    Q must be a multiple of Q_TILE and C of C_TILE (wrappers pad); queries
    enter as (Q, 1) columns and candidates as (1, C) rows.  ``interpret``:
    see :func:`repro.kernels.runtime.pallas_call`.
    """
    q, c = qx.shape[0], px.shape[0]
    assert q % Q_TILE == 0 and c % C_TILE == 0, (q, c)
    col = pl.BlockSpec((Q_TILE, 1), lambda i, j: (i, 0))
    row = pl.BlockSpec((1, C_TILE), lambda i, j: (0, j))
    return pallas_call(
        _kernel,
        name="pairwise_dist",
        grid=(q // Q_TILE, c // C_TILE),
        in_specs=[col, col, row, row, row],
        out_specs=pl.BlockSpec((Q_TILE, C_TILE), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((q, c), jnp.float32),
        interpret=interpret,
    )(
        qx.reshape(q, 1), qy.reshape(q, 1), px.reshape(1, c), py.reshape(1, c),
        valid.astype(jnp.int32).reshape(1, c),
    )
