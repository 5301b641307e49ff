"""Delta-splice: merge a sorted delta run into an existing sorted order.

The maintenance-seam primitive (DESIGN.md §15): instead of re-running a full
``argsort`` over all N objects every tick, the incremental index refresh
extracts the Δ moved rows, sorts **just the delta** (O(Δ log Δ)) and splices
the two ascending runs back together.  The splice itself is a *rank merge*:
each element's output position is its own run offset plus the count of
smaller elements in the other run — a vectorized binary search
(O((N + Δ) log)) followed by one scatter per payload array.  That replaces
the O(N log N) comparison sort that dominates the rebuild path's reindex
stage.

Keys are *pairs*: the quadtree's canonical object order is lexicographic
``(morton code, object id)`` — what a stable ``argsort`` over the
id-indexed positions buffer produces — and ids are the tie-break whenever
two objects share a fine cell.  A packed 64-bit key (``code << 32 | id``)
would be the obvious encoding, but this repo runs with JAX's default
``jax_enable_x64=False`` where ``int64`` silently aliases ``int32``, so the
merge compares the two int32 components explicitly instead:
:func:`searchsorted_pairs` is ``jnp.searchsorted`` generalized to
lexicographic pair keys via an unrolled-bound ``fori_loop`` binary search
(each of the ``ceil(log2 n)`` steps is one vectorized gather + compare).

Stability contract: :func:`merge_ranks` implements the classic stable
two-run merge — on fully-equal keys, run-A elements precede run-B elements
(``side="left"`` for A against B, ``side="right"`` for B against A).  Real
``(code, id)`` keys are unique across runs (an id lives in exactly one
run), so the A/B tie side only ever decides *sentinel* rows — and those
carry keys strictly greater than every real key, landing at merged
positions ``>= n_real`` where :func:`splice_payload`'s scatter bound drops
them.  No masks needed.

Two formulations of the same merge live here:

* **dense** (:func:`merge_ranks` + :func:`splice_payload`): run A is the
  full compacted survivor array, positions are found by an N-query binary
  search and payloads land via N-element scatters.  Simple, and the
  executable specification the tests pin the sparse path against — but on
  an XLA CPU/TPU backend an N-element *scatter* costs ~40x an N-element
  gather (scatters serialize; gathers vectorize), so O(N) scatters swallow
  the whole win over a fresh sort;
* **sparse** (:func:`sparse_splice_plan` + :func:`gather_splice`): the
  production path.  Run A is never materialized — the plan works directly
  on the *moved-slot set*: every scatter it issues is Δ-sized (bump arrays
  of ±1 at run-B insertion points and at the output positions where a
  vacated slot starts shifting its successors), every O(N) step is a
  cumsum or a gather.  The merged order comes back as *gather sources*
  (``src_a``/``b_src``), so payloads are produced by ``jnp.where`` over two
  gathers.  Total: O(Δ log N) search + O(Δ) scatters + two O(N) cumsums —
  this is what makes the incremental reindex pay for churn, not for N.

Why this is a jnp op and not a ``pl.pallas_call`` like its siblings: a
two-run merge is pure data movement — ~zero FLOPs over O(N) bytes, no tile
reuse — and a hand-rolled sequential-merge kernel would serialize what the
rank formulation keeps embarrassingly parallel; there is no arithmetic
intensity for VMEM residency to win back (the same reasoning that keeps the
Morton encode out of Pallas).  It lives in ``kernels/`` because it is a
backend-agnostic reduction primitive of the same family as ``merge_topk`` —
the PR-2/PR-6 merge machinery applied to the index axis instead of the
per-query result lists.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = [
    "searchsorted_pairs",
    "merge_ranks",
    "splice_payload",
    "sparse_splice_plan",
    "gather_splice",
]


def searchsorted_pairs(keys_c, keys_i, q_c, q_i, *, side: str):
    """``jnp.searchsorted`` over lexicographic ``(c, i)`` pair keys.

    ``(keys_c, keys_i)`` must be ascending by ``(c, i)``; returns, for every
    query pair, the count of keys strictly less than it (``side="left"``) or
    less-or-equal (``side="right"``) — all int32, no packed wide key.  The
    binary search runs a static ``bit_length + 1`` iterations (enough for
    the half-open search range to collapse from ``[0, n]``), each one
    gather + pair-compare over the whole query batch.
    """
    if side not in ("left", "right"):
        raise ValueError(f"side must be 'left' or 'right', got {side!r}")
    n = keys_c.shape[0]
    if n == 0:
        return jnp.zeros(q_c.shape, jnp.int32)

    def pair_less(ac, ai, bc, bi):
        return (ac < bc) | ((ac == bc) & (ai < bi))

    lo = jnp.zeros(q_c.shape, jnp.int32)
    hi = jnp.full(q_c.shape, n, jnp.int32)

    def body(_, lohi):
        lo, hi = lohi
        active = lo < hi
        mid = (lo + hi) >> 1
        kc = keys_c[jnp.minimum(mid, n - 1)]
        ki = keys_i[jnp.minimum(mid, n - 1)]
        if side == "left":
            go_right = pair_less(kc, ki, q_c, q_i)  # key[mid] < q
        else:
            go_right = ~pair_less(q_c, q_i, kc, ki)  # key[mid] <= q
        lo = jnp.where(active & go_right, mid + 1, lo)
        hi = jnp.where(active & ~go_right, mid, hi)
        return lo, hi

    lo, hi = jax.lax.fori_loop(0, n.bit_length() + 1, body, (lo, hi))
    return lo


@jax.jit
def merge_ranks(codes_a, ids_a, codes_b, ids_b):
    """Output positions of a stable two-run merge of two ``(code, id)``-sorted runs.

    Returns ``(pos_a, pos_b)`` int32 arrays: element ``i`` of run A lands at
    ``pos_a[i]`` of the merged sequence, element ``j`` of run B at
    ``pos_b[j]``.  With real keys unique across runs the real positions are
    a permutation of ``[0, n_real)``; sentinel rows (keys above every real
    key) land at positions ``>= n_real``.
    """
    pos_a = jnp.arange(codes_a.shape[0], dtype=jnp.int32) + searchsorted_pairs(
        codes_b, ids_b, codes_a, ids_a, side="left"
    )
    pos_b = jnp.arange(codes_b.shape[0], dtype=jnp.int32) + searchsorted_pairs(
        codes_a, ids_a, codes_b, ids_b, side="right"
    )
    return pos_a, pos_b


def splice_payload(pos_a, pos_b, val_a, val_b, n_out: int, fill=0):
    """Scatter two runs' payload rows to their merged positions.

    ``pos_a``/``pos_b`` come from :func:`merge_ranks`; rows whose merged
    position falls outside ``[0, n_out)`` — the sentinel tails — are dropped
    by the scatter, so the output holds exactly the real rows of both runs.
    Trace-level (callers jit the enclosing program); one fused scatter pair
    per payload array.
    """
    shape = (n_out,) + val_a.shape[1:]
    out = jnp.full(shape, fill, val_a.dtype)
    return out.at[pos_a].set(val_a, mode="drop").at[pos_b].set(val_b, mode="drop")


def sparse_splice_plan(slots, ins_full, n: int):
    """Gather plan for splicing a sorted Δ-run into an N-row sorted order.

    Inputs describe the delta against the *original* (pre-compaction) sorted
    order of ``n`` rows:

    * ``slots`` (Δp,) i32 — original slot of each moved row (``n`` for
      sentinel/padding rows, which then influence nothing);
    * ``ins_full`` (Δp,) i32 — for each run-B row (ascending ``(code, id)``),
      ``searchsorted_pairs(orig_keys, b_keys, side="right")``: its rank among
      the original rows.  Searching the original order (not the compacted
      survivors) is deliberate — the compacted rank is recovered here by
      subtracting the moved-slot prefix, so run A never needs materializing.

    Returns ``(src_a, b_src)``:

    * ``src_a`` (n,) i32 — for every merged output position, the original
      slot whose row lands there (meaningful where ``b_src < 0``);
    * ``b_src`` (n,) i32 — index into the sorted B run for output positions
      taken by a moved row, ``-1`` elsewhere.

    The construction inverts the forward merge map without any N-sized
    scatter: the output-position shift ``src_a[j] - j`` is piecewise
    constant with only O(Δ) breakpoints — each B insertion stalls the
    survivor stream by one (bump ``-1`` just past its output position) and
    each vacated slot advances it by one (bump ``+1`` at the output position
    of the first surviving successor) — so it is a cumsum over a Δ-sparse
    bump array.  Sentinel rows carry keys above every real key: their
    ``ins_full`` is ``n``, their computed positions land at ``>= n`` and
    every scatter drops them.  Bitwise-equivalent to the dense
    ``merge_ranks``/``splice_payload`` pair (pinned in
    tests/test_maintenance.py).
    """
    slots = slots.astype(jnp.int32)
    ins_full = ins_full.astype(jnp.int32)
    p = slots.shape[0]
    arange_p = jnp.arange(p, dtype=jnp.int32)
    moved = jnp.zeros((n,), bool).at[slots].set(True, mode="drop")
    # pref[j] = number of moved slots < j, for j in [0, n]
    pref = jnp.concatenate(
        [jnp.zeros((1,), jnp.int32), jnp.cumsum(moved.astype(jnp.int32))]
    )
    # rank of each B row among the *survivors*; + own B rank = output position
    ins_c = ins_full - pref[ins_full]
    pos_b = ins_c + arange_p
    # a vacated slot shifts all outputs from its first surviving successor's
    # final position onward; sentinels overflow past n and drop.  The count of
    # B rows inserted at survivor rank <= d is a Δ-sized binary search rather
    # than an O(N) counting cumsum: ins_c is nondecreasing (ins_full is, and
    # pref grows at most one per unit step).
    d_m = slots - pref[jnp.clip(slots, 0, n)]
    e_m = d_m + jnp.searchsorted(ins_c, d_m, side="right").astype(jnp.int32)
    bump = (
        jnp.zeros((n + 1,), jnp.int32)
        .at[pos_b + 1]
        .add(-1, mode="drop")
        .at[e_m]
        .add(1, mode="drop")
    )
    shift = jnp.cumsum(bump)[:n]
    src_a = jnp.clip(jnp.arange(n, dtype=jnp.int32) + shift, 0, n - 1)
    b_src = jnp.full((n,), -1, jnp.int32).at[pos_b].set(arange_p, mode="drop")
    return src_a, b_src


def gather_splice(src_a, b_src, val_a, val_b):
    """Materialize one payload array of a :func:`sparse_splice_plan` merge.

    Two gathers and a select — no scatter.  ``val_a`` is indexed by original
    slot, ``val_b`` by sorted-B rank; trailing payload dimensions broadcast.
    """
    take_b = b_src >= 0
    bs = jnp.clip(b_src, 0, val_b.shape[0] - 1)
    if val_a.ndim > 1:
        take_b = take_b.reshape((-1,) + (1,) * (val_a.ndim - 1))
    return jnp.where(take_b, val_b[bs], val_a[src_a])
