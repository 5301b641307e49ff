"""PR-quadtree spatial index (paper Sec. 4.1), built as a single device program.

The paper builds the tree level-by-level with a GPU/CPU ping-pong (Morton codes +
radix sort on GPU, split decisions on CPU).  On TPU/XLA we improve on this with a
**count pyramid**: one ``bincount`` at the finest level ``l_max`` plus ``l_max``
reshape-sums give the population of *every* quadrant at *every* level in O(|P|).
The PR-quadtree leaf predicate — "deepest ancestor chain whose counts exceed
``th_quad``" — is then evaluated vectorized for all ``4**l_max`` fine cells at once,
which directly materializes the paper's ``z_map`` lookup table (fine cell -> leaf).

Leaf identity convention (matches the paper's total order, Fig. 2): a leaf at level
``l`` is identified by its *first fine cell code* ``key = z << 2*(l_max - l)``; leaves
are totally ordered by ``key`` and tile ``[0, 4**l_max)`` into consecutive intervals.
Because of the Morton sort invariance, the objects of a leaf occupy the contiguous
slice ``[starts[key], starts[key + 4**(l_max-l)])`` of the sorted object array.
"""
from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from . import morton
from ..kernels.delta_splice import (
    gather_splice,
    searchsorted_pairs,
    sparse_splice_plan,
)

__all__ = [
    "QuadtreeIndex",
    "build_index",
    "rebuild_zmap",
    "reindex_objects",
    "reindex_objects_delta",
    "leaf_of_points",
    "starts_from_pyramid",
    "local_pyramid_from_starts",
    "pyramid_delta",
    "ball_stab_mask",
]


@partial(
    jax.tree_util.register_dataclass,
    data_fields=[
        "origin",
        "side",
        "pos",
        "ids",
        "codes",
        "starts",
        "leaf_level",
        "pyramid",
    ],
    meta_fields=["l_max", "th_quad"],
)
@dataclasses.dataclass(frozen=True)
class QuadtreeIndex:
    """The spatial index + Morton-sorted object store (a pytree).

    Attributes
    ----------
    origin: (2,) f32 — lower-left corner of the MBR ``G``.
    side:   ()  f32 — side length of ``G`` (squared region, as in the paper).
    pos:    (N, 2) f32 — object positions, sorted by fine Morton code (SoV layout).
    ids:    (N,) i32 — original object ids, same order.
    codes:  (N,) i32 — fine Morton codes, sorted.
    starts: (4**l_max + 1,) i32 — prefix offsets: fine cell c holds objects
            ``pos[starts[c]:starts[c+1]]``.
    leaf_level: (4**l_max,) i32 — level of the quadtree leaf covering each fine cell
            (this *is* the paper's z_map: leaf key = (c >> 2d) << 2d,
            d = l_max - leaf_level[c]).
    pyramid: flattened i32 array of quadrant populations at every level
            (``pyr[pyramid_offset(l) + z]``); ``starts`` and the leaf levels
            derive from it.
    l_max:   static int — maximum quadtree depth.
    th_quad: static int — max objects per leaf (split threshold).
    """

    origin: jnp.ndarray
    side: jnp.ndarray
    pos: jnp.ndarray
    ids: jnp.ndarray
    codes: jnp.ndarray
    starts: jnp.ndarray
    leaf_level: jnp.ndarray
    pyramid: jnp.ndarray
    l_max: int
    th_quad: int

    def level_counts(self, level: int) -> jnp.ndarray:
        """Populations of the 4**level quadrants at ``level`` (view of pyramid)."""
        off = pyramid_offset(level)
        return self.pyramid[off : off + 4**level]

    @property
    def n_objects(self) -> int:
        return self.pos.shape[0]

    @property
    def n_fine(self) -> int:
        return 4**self.l_max


def pyramid_offset(level: int) -> int:
    """Start of level ``level`` inside the flattened pyramid: (4**l - 1) / 3."""
    return ((1 << (2 * level)) - 1) // 3


def _count_pyramid(codes: jnp.ndarray, l_max: int) -> jnp.ndarray:
    """Quadrant populations at every level, flattened level-major.

    ``pyr[pyramid_offset(l) + z]`` = population of quadrant ``(l, z)``.
    Total size (4**(l_max+1) - 1) / 3.
    """
    counts = jnp.bincount(codes, length=4**l_max).astype(jnp.int32)
    levels = [counts]
    cur = counts
    for _ in range(l_max):
        cur = cur.reshape(-1, 4).sum(axis=1)
        levels.append(cur)
    return jnp.concatenate(list(reversed(levels)))


def starts_from_pyramid(pyramid: jnp.ndarray, l_max: int) -> jnp.ndarray:
    """Prefix offsets from the pyramid's fine level: ``starts[c] = # codes < c``.

    Shared by every index-maintenance path (build / full reindex / delta
    reindex) so that ``starts`` is always the same op over the same int32
    counts — equal pyramids therefore give bitwise-equal offsets.
    """
    fine_counts = pyramid[pyramid_offset(l_max) :]
    return jnp.concatenate(
        [jnp.zeros((1,), jnp.int32), jnp.cumsum(fine_counts).astype(jnp.int32)]
    )


def local_pyramid_from_starts(starts, lo, own, clone_code, capo: int, l_max: int):
    """Count pyramid of one Morton-contiguous slice, derived from GLOBAL offsets.

    A shard owning global sorted ranks ``[lo, lo + own)`` (padded to a static
    ``capo``-row capacity whose surplus rows all carry ``clone_code``) does
    not need to re-``bincount`` its slice: the global ``starts`` array already
    counts every fine cell, so the slice's population of cell ``c`` is the
    overlap of the cell's global rank interval ``[starts[c], starts[c+1])``
    with the owned window —

        ``max(0, min(starts[c+1], lo + own) - max(starts[c], lo))``

    — an O(4**l_max) gather + arithmetic with no scatter and no sort.  The
    ``capo - own`` clone rows are added at ``clone_code`` in one scalar
    update.  All int32 arithmetic, so the fine level is integer-exact equal
    to ``bincount`` over the slice's codes, and the reshape-sum rollup is the
    same op chain as :func:`_count_pyramid` — bitwise-equal pyramids (the
    per-shard derived-index identity of DESIGN.md §15).
    """
    s = starts[:-1]
    e = starts[1:]
    hi = lo + own
    fine = jnp.maximum(
        jnp.minimum(e, hi) - jnp.maximum(s, lo), 0
    ).astype(jnp.int32)
    fine = fine.at[clone_code].add(jnp.int32(capo) - own)
    levels = [fine]
    cur = fine
    for _ in range(l_max):
        cur = cur.reshape(-1, 4).sum(axis=1)
        levels.append(cur)
    return jnp.concatenate(list(reversed(levels)))


def pyramid_delta(
    pyramid: jnp.ndarray,
    old_codes: jnp.ndarray,
    new_codes: jnp.ndarray,
    weight: jnp.ndarray,
    l_max: int,
) -> jnp.ndarray:
    """Update the count pyramid for rows whose fine code changed.

    Scatter-subtract ``weight`` at the old fine-level quadrant and
    scatter-add it at the new one — Δ-sized scatters at the *fine level
    only* — then rebuild the ``l_max`` coarser levels by 4-way reshape-sums
    (the same derivation :func:`_count_pyramid` uses, O(4**l_max) adds
    total).  ``weight`` is 1 for real delta rows, 0 for padding; codes at or
    above ``4**l_max`` (the sentinel convention) fall outside the fine level
    and are dropped.  Integer adds are exact and commute, so the result is
    bitwise-equal to a from-scratch recount of the updated code set — the
    incremental path's pyramid identity in DESIGN.md §15.  O(Δ + 4**l_max)
    work versus the recount's O(N + 4**l_max), and no per-level scatter
    chain (XLA scatters cost ~per-element; the reshape-sums vectorize).
    """
    fine = pyramid[pyramid_offset(l_max) :]
    fine = fine.at[old_codes].add(-weight, mode="drop").at[new_codes].add(
        weight, mode="drop"
    )
    levels = [fine]
    cur = fine
    for _ in range(l_max):
        cur = cur.reshape(-1, 4).sum(axis=1)
        levels.append(cur)
    return jnp.concatenate(list(reversed(levels)))


def _part1by1_np(v: np.ndarray) -> np.ndarray:
    """numpy replica of :func:`repro.core.morton.part1by1` (host-side stab)."""
    v = np.asarray(v, np.uint32)
    v = (v | (v << 8)) & np.uint32(0x00FF00FF)
    v = (v | (v << 4)) & np.uint32(0x0F0F0F0F)
    v = (v | (v << 2)) & np.uint32(0x33333333)
    v = (v | (v << 1)) & np.uint32(0x55555555)
    return v


def _encode_cells_np(cx: np.ndarray, cy: np.ndarray) -> np.ndarray:
    return (_part1by1_np(cx) | (_part1by1_np(cy) << 1)).astype(np.int64)


# conservative widening on the stored squared k-th distance: the kernel
# measures the Euclidean k-th distance in f32 (f32 squared distance,
# possibly FMA-fused, then f32 sqrt), and the cache squares that back in
# f64 on insert — so the stored r^2 can sit a handful of ulps below the
# exact value (a few 2**-23 relative from the kernel's d^2 plus half an
# ulp from the sqrt, doubled by the squaring); 2**-17 gives ~an order of
# magnitude of headroom over that ~5*2**-23 worst case while staying
# geometrically negligible, and at r^2 == 0 no margin is needed (f32
# subtraction yields exactly 0 iff the coordinates are bitwise equal).
_STAB_MARGIN = 1.0 + 2.0**-17


def ball_stab_mask(
    centers: np.ndarray,
    kth2: np.ndarray,
    moved: np.ndarray,
    *,
    origin,
    side,
    l_max: int,
    exact_rows: int = 64,
) -> np.ndarray:
    """Which closed k-th-distance balls does a set of moved points stab?

    Host-side (pure numpy) primitive of the serving layer's spatial cache
    invalidation (DESIGN.md §16): cached entry *e* — query center
    ``centers[e]``, squared k-th distance ``kth2[e]`` — can only have
    changed if some moved row's old or new position lies inside its
    **closed** ball (inclusive boundary: an object tied at exactly the k-th
    distance can flip the canonical id tie-break).  Returns an ``(E,)`` bool
    mask, True = must evict.  The mask is *conservative*: widened by
    ``_STAB_MARGIN`` against f32 kernel rounding, coarsened to cell
    granularity on the pyramid path, and clipped positions only merge cells
    at the region boundary — every approximation adds stabs, never drops
    one.

    Two regimes, same contract:

    * ``moved`` small (``<= exact_rows``): exact vectorized pairwise check.
      f64 squared distance of f32 inputs is *exact* (products of f32 are
      exact in f64 and their sum carries <= 49 significand bits), so only
      the stored radius needs the margin.
    * ``moved`` large: a Morton occupancy pyramid over the moved rows'
      fine cells (the same level-major layout as :func:`_count_pyramid`,
      booleans instead of counts) and, per ball, the coarsest level whose
      cell side covers the ball diameter — there the ball's bbox spans at
      most 2x2 cells, so four occupancy probes decide the stab.

    Non-finite geometry is handled per entry: NaN/inf centers or NaN radius
    always stab (a NaN-payload geometry key is a legitimate cache key whose
    ball is undefined — evicting is the only safe answer), and an infinite
    radius (fewer than k live candidates) stabs on any motion.
    """
    centers = np.asarray(centers, np.float64).reshape(-1, 2)
    kth2 = np.asarray(kth2, np.float64).reshape(-1)
    moved = np.asarray(moved, np.float64).reshape(-1, 2)
    E = centers.shape[0]
    M = moved.shape[0]
    bad = ~(np.isfinite(centers).all(axis=1) & ~np.isnan(kth2))
    if E == 0 or M == 0:
        # no movement to localize, but non-finite geometry (NaN *or* inf
        # radius) still reports as a stab — the always-evict contract does
        # not depend on the delta
        return bad | np.isinf(kth2)
    r2 = kth2 * _STAB_MARGIN
    if M <= exact_rows:
        d2 = (
            (centers[:, None, 0] - moved[None, :, 0]) ** 2
            + (centers[:, None, 1] - moved[None, :, 1]) ** 2
        )
        return bad | (d2 <= r2[:, None]).any(axis=1)
    ox, oy = float(np.asarray(origin).reshape(-1)[0]), float(
        np.asarray(origin).reshape(-1)[1]
    )
    side = float(side)
    n_fine = 1 << l_max
    # occupancy pyramid over the moved rows' fine cells (clip = boundary
    # cells, conservative for out-of-region motion)
    mx = np.clip(np.floor((moved[:, 0] - ox) / side * n_fine), 0, n_fine - 1)
    my = np.clip(np.floor((moved[:, 1] - oy) / side * n_fine), 0, n_fine - 1)
    occ_fine = np.zeros((n_fine * n_fine,), bool)
    occ_fine[_encode_cells_np(mx.astype(np.int64), my.astype(np.int64))] = True
    levels = [occ_fine]
    cur = occ_fine
    for _ in range(l_max):
        cur = cur.reshape(-1, 4).any(axis=1)
        levels.append(cur)
    occ = np.concatenate(list(reversed(levels)))
    # per ball: coarsest level with cell side >= ball diameter (r == 0 ->
    # finest; inf radius or any non-finite geometry -> unconditional stab)
    r = np.sqrt(np.maximum(r2, 0.0))
    always = bad | np.isinf(r)
    ok = ~always
    lvl = np.full((E,), l_max, np.int64)
    pos_r = ok & (r > 0.0)
    with np.errstate(divide="ignore", invalid="ignore"):
        want = np.floor(np.log2(side / (2.0 * np.where(pos_r, r, 1.0))))
    lvl[pos_r] = np.clip(want[pos_r], 0, l_max).astype(np.int64)
    n_cells = np.int64(1) << lvl
    off = ((np.int64(1) << (2 * lvl)) - 1) // 3

    def cell(coord, o):
        c = np.floor((coord - o) / side * n_cells)
        return np.clip(c, 0, n_cells - 1).astype(np.int64)

    # sanitize the always-stab rows so the int casts below see finite values
    cx = np.where(ok, centers[:, 0], ox)
    cy = np.where(ok, centers[:, 1], oy)
    r = np.where(ok & np.isfinite(r), r, 0.0)
    xs = (cell(cx - r, ox), cell(cx + r, ox))
    ys = (cell(cy - r, oy), cell(cy + r, oy))
    hit = np.zeros((E,), bool)
    for ix in xs:
        for iy in ys:
            hit |= occ[off + _encode_cells_np(ix, iy)]
    return always | (ok & hit)


def _leaf_levels(pyramid: jnp.ndarray, l_max: int, th_quad: int) -> jnp.ndarray:
    """Leaf level per fine cell = number of split ancestors along its path.

    A node splits iff its population exceeds ``th_quad`` (and l < l_max).  Path
    populations are non-increasing with depth, so the split predicate holds on a
    prefix of levels and the *count of splitting ancestors* equals the leaf level.
    """
    fine = jnp.arange(4**l_max, dtype=jnp.int32)
    ll = jnp.zeros(4**l_max, dtype=jnp.int32)
    for l in range(l_max):  # levels 0 .. l_max-1 may split
        anc = fine >> jnp.int32(2 * (l_max - l))
        lvl_counts = pyramid[pyramid_offset(l) : pyramid_offset(l) + 4**l]
        ll = ll + (lvl_counts[anc] > th_quad).astype(jnp.int32)
    return ll


@partial(jax.jit, static_argnames=("l_max", "th_quad"))
def build_index(
    points: jnp.ndarray,
    origin: jnp.ndarray,
    side,
    *,
    l_max: int = 8,
    th_quad: int = 192,
) -> QuadtreeIndex:
    """Stage (i) + (ii) of the pipeline: build the PR-quadtree and index objects.

    Equivalent to the paper's *index creation* (Sec. 4.1.1) + *moving objects
    indexing* (Sec. 4.1.2), fused into one device program:
      1. fine Morton codes for all points                      (paper: GPU)
      2. sort by code (XLA sort ~ radix sort role)             (paper: GPU radix)
      3. count pyramid + leaf levels -> z_map                  (paper: GPU+CPU loop)
      4. prefix offsets -> per-cell object intervals           (paper: GPU)
    """
    points = points.astype(jnp.float32)
    origin = jnp.asarray(origin, jnp.float32)
    side = jnp.asarray(side, jnp.float32)
    codes = morton.morton_encode_points(points, origin, side, l_max)
    order = jnp.argsort(codes)
    codes_s = codes[order]
    pos_s = points[order]
    ids_s = order.astype(jnp.int32)
    pyramid = _count_pyramid(codes, l_max)
    leaf_level = _leaf_levels(pyramid, l_max, th_quad)
    starts = starts_from_pyramid(pyramid, l_max)
    return QuadtreeIndex(
        origin=origin,
        side=side,
        pos=pos_s,
        ids=ids_s,
        codes=codes_s,
        starts=starts,
        leaf_level=leaf_level,
        pyramid=pyramid,
        l_max=l_max,
        th_quad=th_quad,
    )


@jax.jit
def rebuild_zmap(index: QuadtreeIndex) -> QuadtreeIndex:
    """Stage (i) only: re-derive the leaf partition (z_map) from the live pyramid.

    The drift policy's rebuild re-decides where the quadtree splits — but when
    the index's sorted order and pyramid are already current for the positions
    buffer (a clean buffer, or right after a splice/reindex), a full
    ``build_index`` would recompute the encode + argsort + recount only to
    arrive at the very same arrays: ``build_index``'s stable argsort of the
    id-indexed codes IS the order the maintenance paths keep, and its pyramid
    is the recount the splice's integer deltas already equal.  The only field
    a rebuild actually changes is ``leaf_level``, a pure function of the
    pyramid — so the stage-(i) reuse rule (DESIGN.md §15) replaces the
    O(N log N) re-sort with one O(4**l_max) ``_leaf_levels`` pass, bitwise
    equal to ``build_index`` over the same positions.
    """
    return dataclasses.replace(
        index,
        leaf_level=_leaf_levels(index.pyramid, index.l_max, index.th_quad),
    )


@partial(jax.jit, static_argnames=())
def reindex_objects(index: QuadtreeIndex, points: jnp.ndarray) -> QuadtreeIndex:
    """Stage (ii) only: re-sort fresh object positions into the *existing* partition.

    Per the paper, stage (i) (the space partition / z_map) is reused across ticks
    while the distribution is stable; every tick only re-sorts the new positions and
    recomputes the per-cell intervals (+ the pyramid, which is O(|C|) and needed for
    empty-block pruning).
    """
    l_max = index.l_max
    points = points.astype(jnp.float32)
    codes = morton.morton_encode_points(points, index.origin, index.side, l_max)
    order = jnp.argsort(codes)
    pyramid = _count_pyramid(codes, l_max)
    starts = starts_from_pyramid(pyramid, l_max)
    return dataclasses.replace(
        index,
        pos=points[order],
        ids=order.astype(jnp.int32),
        codes=codes[order],
        starts=starts,
        pyramid=pyramid,
    )


@jax.jit
def reindex_objects_delta(
    index: QuadtreeIndex,
    points: jnp.ndarray,
    delta_ids: jnp.ndarray,
    delta_old_pos: jnp.ndarray,
) -> QuadtreeIndex:
    """Stage (ii) with work proportional to the delta, not to N.

    Produces bitwise the same index as ``reindex_objects(index, points)``
    when ``points`` differs from the indexed positions only at ``delta_ids``
    (DESIGN.md §15 has the full argument):

    * the canonical order is lexicographic ``(code, id)`` — a stable argsort
      of id-indexed codes — so it can be reproduced by splicing the Δ moved
      rows (the only sort, O(Δ log Δ) via a 2-key ``lax.sort``) into the
      surviving rows of the old order.  The splice is the *sparse* plan of
      the delta-splice kernel: moved slots are located by a
      ``(old code, id)`` pair binary search against the existing sorted
      keys (no O(N) inverse-rank scatter), and the merged order comes back
      as gather sources, so no step issues an N-sized scatter —
      kernels/delta_splice.py documents why that distinction carries the
      whole speedup on XLA backends;
    * the pyramid is int32 counts, so ±1 fine-level scatter-adds at the
      old/new cells + reshape-sum rollup are exactly a recount
      (:func:`pyramid_delta`);
    * ``starts`` is the same :func:`starts_from_pyramid` op over that
      pyramid; ``leaf_level`` (stage i) is untouched, exactly as in
      ``reindex_objects``.

    ``delta_ids`` must contain each object id at most once (the session
    dedups keep-first before padding); out-of-range ids (the sentinel-N
    padding convention of ``scatter_positions``) are ignored.
    ``delta_old_pos`` row ``r`` must hold the position object
    ``delta_ids[r]`` had when ``index`` was built — bitwise, as float32 —
    so its old ``(code, id)`` key can be recomputed and found by search;
    padding rows are arbitrary.  Cost: O(Δ log Δ) sort + O(Δ log N) search
    + O(Δ) scatters + two O(N) cumsums and the O(N) output gathers.  When
    ``(code, id)`` fits a packed int32 (the common case: it needs
    ``4**l_max * (n+1) + n < 2**31``) the sort and search run over packed
    single keys; otherwise the explicit pair formulation of
    kernels/delta_splice.py takes over (x64 is disabled, so there is no
    64-bit packed fallback).
    """
    n = index.n_objects
    l_max = index.l_max
    points = points.astype(jnp.float32)
    ids = delta_ids.astype(jnp.int32)
    p = ids.shape[0]
    valid = ids < n
    safe = jnp.where(valid, ids, 0)
    sent_code = jnp.int32(4**l_max)  # > every real fine code
    q_ids = jnp.where(valid, ids, n)
    old_codes = jnp.where(
        valid,
        morton.morton_encode_points(
            delta_old_pos.astype(jnp.float32), index.origin, index.side, l_max
        ),
        sent_code,
    )
    # run B: the moved rows, (code, id)-lexsorted — the only sort in the path
    new_pos = points[safe]
    new_codes = morton.morton_encode_points(new_pos, index.origin, index.side, l_max)
    new_codes_m = jnp.where(valid, new_codes, sent_code)
    arange_p = jnp.arange(p, dtype=jnp.int32)
    if 4**l_max * (n + 1) + n < 2**31:
        # (code, id) packs into one int32 (id < n+1 makes numeric order equal
        # lexicographic order): a 1-key sort + plain searchsorted beat the
        # pair formulation's 2-key sort + gather-per-iteration binary search.
        mult = jnp.int32(n + 1)
        pk_b, perm = jax.lax.sort(
            (new_codes_m * mult + q_ids, arange_p), num_keys=1
        )
        codes_b = new_codes_m[perm]
        ids_b = q_ids[perm]
        # ONE fused search, side="right": the first half hits existing keys
        # exactly (rank = slot + 1); the second ranks new keys for insertion.
        res = jnp.searchsorted(
            index.codes * mult + index.ids,
            jnp.concatenate([old_codes * mult + q_ids, pk_b]),
            side="right",
        ).astype(jnp.int32)
    else:
        codes_b, ids_b, perm = jax.lax.sort(
            (new_codes_m, q_ids, arange_p), num_keys=2
        )
        res = searchsorted_pairs(
            index.codes,
            index.ids,
            jnp.concatenate([old_codes, codes_b]),
            jnp.concatenate([q_ids, ids_b]),
            side="right",
        )
    pos_b = new_pos[perm]
    slots = jnp.where(valid, res[:p] - 1, n)
    src_a, b_src = sparse_splice_plan(slots, res[p:], n)
    codes_n = gather_splice(src_a, b_src, index.codes, codes_b)
    ids_n = gather_splice(src_a, b_src, index.ids, ids_b)
    # one coordinate at a time: on the TPU a select between two gathers of
    # (N, 2) rows is laid out with its 2-wide minor dimension padded to 128
    # lanes (1 GB of temporaries at N = 1M on a v5e; 20 MB per coordinate)
    pos_n = jnp.stack([
        gather_splice(src_a, b_src, index.pos[:, d], pos_b[:, d])
        for d in range(2)
    ], axis=1)
    pyramid = pyramid_delta(
        index.pyramid,
        old_codes,
        new_codes_m,
        valid.astype(jnp.int32),
        l_max,
    )
    starts = starts_from_pyramid(pyramid, l_max)
    return dataclasses.replace(
        index,
        pos=pos_n,
        ids=ids_n,
        codes=codes_n,
        starts=starts,
        pyramid=pyramid,
    )


def leaf_of_points(index: QuadtreeIndex, points: jnp.ndarray):
    """z_map lookup (paper Sec. 4.1.1): points -> (leaf_key, leaf_level).

    Constant-time arithmetic + one table read per point; no tree descent.
    """
    fine = morton.morton_encode_points(points, index.origin, index.side, index.l_max)
    lvl = index.leaf_level[fine]
    shift = 2 * (index.l_max - lvl)
    key = (fine >> shift) << shift
    return key, lvl
