"""Pallas TPU kernel: each sweep lane's candidate window, fetched as whole rows.

Every SCAN trip of the sweep (``core/pipeline.py``) reads, for each scanning
lane, the W objects of the Morton-sorted object store that start at the
lane's cursor ``start``: one contiguous run.  Written as ``pos[start +
arange(W)]``, XLA cannot see the run and gathers one element at a time.

Here the object arrays are viewed as ``(R, 128)`` row tables
(:func:`row_tables`: x, y and ids, padded at the tail), and each lane that
scans this trip copies the ``NR = (W + 254) // 128`` aligned rows that cover
``[start, start + W)`` — the most rows a window of W can straddle — with
one DMA per table into VMEM.  A grid step takes a tile of lanes, issues
all their copies (one semaphore per table), then waits for them.  A lane
that does not scan issues no DMA; its slots read id -1 at coordinates
(0, 0).

The fetched tile is ``Wf = NR * 128`` slots wide, slot ``j`` of a lane
holding object ``(start // 128) * 128 + j``; the caller masks the slots to
``[start, min(start + W, e))``.  That is exactly the candidate set of the
element gather, and SCAN selection is order-free (DESIGN.md §12), so the
results are the same bits.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .runtime import pallas_call

__all__ = ["LANES", "LANE_TILE", "fetch_rows", "row_tables", "window_fetch"]

LANES = 128  # objects per table row: one vector register's lanes
LANE_TILE = 128  # sweep lanes per grid step


def fetch_rows(window: int) -> int:
    """Aligned table rows that cover any ``window`` consecutive objects."""
    return (window + 2 * LANES - 2) // LANES


def row_tables(pos, ids, window: int):
    """``(x, y, ids)`` of a Morton-sorted object store as ``(R, 128)`` tables.

    ``R`` leaves :func:`fetch_rows` rows from the one holding object ``n - 1``
    inside the table, so a lane starting at any object reads no further.  The
    tail reads id -1, which the scan's ``ids >= 0`` mask drops, at (0, 0).
    """
    n = pos.shape[0]
    rows = (max(n, 1) - 1) // LANES + fetch_rows(window)

    def table(col, fill):
        flat = jnp.full((rows * LANES,), fill, col.dtype).at[:n].set(col)
        return flat.reshape(rows, LANES)

    pos = pos.astype(jnp.float32)
    return (table(pos[:, 0], 0.0), table(pos[:, 1], 0.0),
            table(ids.astype(jnp.int32), -1))


def _make_kernel(nr: int, tile: int):
    def kernel(row_ref, scan_ref, xt, yt, it, ox, oy, oi, bx, by, bi, sem):
        base = pl.program_id(0) * tile
        tables = ((xt, bx), (yt, by), (it, bi))

        def copies(t):
            r0 = row_ref[base + t]
            return [
                pltpu.make_async_copy(tab.at[pl.ds(r0, nr), :], buf.at[t],
                                      sem.at[j])
                for j, (tab, buf) in enumerate(tables)
            ]

        def issue(t, carry):
            on = scan_ref[base + t] != 0

            @pl.when(on)
            def _():
                for cp in copies(t):
                    cp.start()

            @pl.when(~on)
            def _():
                bx[t] = jnp.zeros((nr, LANES), jnp.float32)
                by[t] = jnp.zeros((nr, LANES), jnp.float32)
                bi[t] = jnp.full((nr, LANES), -1, jnp.int32)

            return carry

        def wait(t, carry):
            @pl.when(scan_ref[base + t] != 0)
            def _():
                for cp in copies(t):
                    cp.wait()

            return carry

        # every lane's copies in flight before the first wait
        jax.lax.fori_loop(0, tile, issue, 0)
        jax.lax.fori_loop(0, tile, wait, 0)
        for j in range(nr):
            cols = slice(j * LANES, (j + 1) * LANES)
            ox[:, cols] = bx[:, j, :]
            oy[:, cols] = by[:, j, :]
            oi[:, cols] = bi[:, j, :]

    return kernel


@functools.partial(jax.jit, static_argnames=("window", "interpret"))
def window_fetch(tables, start, scanning, *, window: int,
                 interpret: bool | None = None):
    """Each lane's fetched window, from :func:`row_tables`' ``tables``.

    ``start`` (Q,) i32 is each lane's first object and ``scanning`` (Q,)
    bool whether it scans this trip; a scanning lane's ``start`` lies in
    ``[0, n)``.  Returns ``cx, cy, cids`` (Q, Wf) and ``slot`` (Q, Wf) i32,
    the object index each slot holds.  Q is padded inside to a whole number
    of lane tiles.
    """
    xt, yt, it = tables
    q = start.shape[0]
    nr = fetch_rows(window)
    wf = nr * LANES
    tile = min(LANE_TILE, -(-q // 8) * 8)
    # At least two grid steps.  Interpreted, a one-step grid's loop is
    # inlined, and the kernel's buffers then fuse into the SCAN's distance
    # arithmetic, where the CPU compiler contracts the other product of
    # ``dx*dx + dy*dy`` into its FMA: the distance bits would depend on Q.
    n_tiles = max(2, -(-q // tile))
    qp = n_tiles * tile
    # first row of each lane's copy, kept inside the table for any start
    row0 = jnp.clip(start.astype(jnp.int32) // LANES, 0, xt.shape[0] - nr)
    pad = lambda a: jnp.zeros((qp,), jnp.int32).at[:q].set(a)
    block = pl.BlockSpec((tile, wf), lambda g, *_: (g, 0))
    cx, cy, cids = pallas_call(
        _make_kernel(nr, tile),
        name="window_fetch",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(n_tiles,),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY)] * 3,
            out_specs=[block, block, block],
            scratch_shapes=[
                pltpu.VMEM((tile, nr, LANES), jnp.float32),
                pltpu.VMEM((tile, nr, LANES), jnp.float32),
                pltpu.VMEM((tile, nr, LANES), jnp.int32),
                pltpu.SemaphoreType.DMA((3,)),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((qp, wf), jnp.float32),
            jax.ShapeDtypeStruct((qp, wf), jnp.float32),
            jax.ShapeDtypeStruct((qp, wf), jnp.int32),
        ],
        interpret=interpret,
    )(pad(row0), pad(scanning.astype(jnp.int32)), xt, yt, it)
    slot = (row0 * LANES)[:, None] + jnp.arange(wf, dtype=jnp.int32)
    return cx[:q], cy[:q], cids[:q], slot
