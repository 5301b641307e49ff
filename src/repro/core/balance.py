"""Partitioner — cost-balanced work splitting for the execution plans (DESIGN.md §13).

The paper's speedups hold "even [for workloads] characterized by highly
skewed spatial distributions", and its repeated-queries setting means tick
τ's measured work is the best predictor of tick τ+1's.  The ExecutionPlan
seam (``core/plan.py``) used to ignore both: every plan split the
Morton-sorted query batch into equal-count contiguous chunks and the
Morton-sorted object array into equal-count slices, so under Zipf skew every
``shard_map`` barrier waited on the device that drew the dense hotspot.

This module is the seam that fixes it: plans no longer hard-code equal
splits — they ask a registered :class:`Partitioner` for **contiguous split
boundaries** along the query axis (in whole-chunk units, so shard boundaries
keep coinciding with chunk boundaries — the bit-identity argument of
DESIGN.md §10) and/or the object axis (in Morton-sorted row units).  Two
partitioners ship:

``equal``
    Today's behavior, bit-for-bit: equal-count contiguous splits, a pure
    function of the unit count.  The ``sharded`` plan keeps its static
    equal-split fast path (split ``in_specs``, no masking) when this
    partitioner is selected; the object-axis plans (``object_sharded`` /
    ``hybrid``) run ONE boundary-driven body for both partitioners — equal
    boundaries are constant-folded values, the replication they add is
    bounded by the object arrays those plans already replicate, and under
    equal boundaries no chunk is ever masked (the per-chunk ``cond`` always
    takes the live branch).  Results are bit-identical either way.

``cost_balanced``
    Boundaries chosen so every shard's *estimated cost* is as equal as the
    contiguity constraint allows (:func:`balanced_boundaries` — a prefix-sum
    + ``searchsorted`` split, clamped to a static per-shard capacity).
    Query-axis costs are seeded from statistics the index already computes
    — the count pyramid gives each query's leaf population (its candidate-
    volume estimate) — and refined each tick by an EMA of the *measured*
    per-query candidate volume fed back through the session (the
    repeated-query feedback loop; ``repro.api.KnnSession`` persists the EMA
    across ticks and rebuilds).  The object axis stays count-balanced
    ("objects per slice" — the memory budget; see
    ``core.plan._object_row_costs`` for the measured rationale), its
    boundaries flowing through the same seam.

Because boundaries move at runtime, shards become uneven — but shapes must
stay static under ``jit``/``shard_map``.  The plans therefore give every
shard a static *capacity* (:meth:`Partitioner.query_capacity` /
:meth:`Partitioner.object_capacity`, ``ceil(units / shards) * slack``) and
mask the unused tail: boundaries are data, capacities are compiled.

Partitioners are frozen (hence hashable) dataclasses carried inside the
ExecutionPlan — itself a static ``jit`` argument — so the tick step
specializes per (plan, backend, partitioner) triple while the *boundaries*
stay dynamic: re-balancing every tick never recompiles.
"""
from __future__ import annotations

import dataclasses
from typing import ClassVar

import jax.numpy as jnp

__all__ = [
    "Partitioner",
    "EqualPartitioner",
    "CostBalancedPartitioner",
    "balanced_boundaries",
    "equal_boundaries",
    "register_partitioner",
    "resolve_partitioner",
    "partitioner_names",
    "straggler_gap",
    "tenant_fair_weights",
]


def equal_boundaries(n_units: int, num_shards: int) -> jnp.ndarray:
    """(R+1,) i32 equal-count contiguous boundaries — today's split rule.

    ``b[r] = r * ceil(n / R)`` clipped to ``n``: exactly the slices the
    equal-split plans carve (query chunks per device, object rows per shard
    — ``core.plan.object_shard_capacity``'s rule expressed as boundaries).
    """
    cap = -(-max(1, n_units) // num_shards)
    return jnp.asarray(
        [min(r * cap, n_units) for r in range(num_shards + 1)], jnp.int32
    )


def balanced_boundaries(costs, num_shards: int, capacity: int) -> jnp.ndarray:
    """Contiguous boundaries with (approximately) equal cost per shard.

    ``costs`` is a (n_units,) f32 array of per-unit cost estimates (traced —
    this runs inside the jitted tick step).  Shard ``r`` receives units
    ``[b[r], b[r+1])``; the ideal boundary for shard prefix ``r`` is where
    the cost prefix sum crosses ``r/R`` of the total (``searchsorted``), then
    clamped so that

      * boundaries are monotone (contiguity),
      * no shard exceeds ``capacity`` units (the static shape the plans
        compiled for), and
      * every unit is covered (``b[R] = n`` stays reachable given
        ``R * capacity >= n`` — guaranteed by the capacity formulas below).

    The clamp recursion is unrolled over ``R`` (static, small): each step is
    O(1) on scalars, the single ``searchsorted`` is O(R log n).
    """
    n = costs.shape[0]
    if num_shards * capacity < n:
        raise ValueError(
            f"infeasible partition: {num_shards} shards x capacity "
            f"{capacity} < {n} units"
        )
    cum = jnp.cumsum(costs.astype(jnp.float32))
    total = cum[-1]
    targets = total * (
        jnp.arange(1, num_shards, dtype=jnp.float32) / num_shards
    )
    # side="right": a target landing exactly on a prefix sum cuts AFTER that
    # unit, so uniform costs reproduce the equal split exactly
    want = jnp.searchsorted(cum, targets, side="right").astype(jnp.int32)
    bs = [jnp.int32(0)]
    for r in range(1, num_shards):
        lo = jnp.maximum(bs[-1], n - (num_shards - r) * capacity)
        hi = jnp.minimum(bs[-1] + capacity, r * capacity)
        bs.append(jnp.clip(want[r - 1], lo, hi).astype(jnp.int32))
    bs.append(jnp.int32(n))
    return jnp.stack(bs)


def straggler_gap(shard_work) -> float:
    """max/mean per-shard work — THE skew metric of the partitioners.

    1.0 = perfectly balanced; R = one shard does everything.  Computed on
    host from the per-shard candidate counters a tick returns
    (``TickResult.shard_candidates``).
    """
    import numpy as np

    w = np.asarray(shard_work, np.float64)
    mean = w.mean()
    return float(w.max() / mean) if mean > 0 else 1.0


def tenant_fair_weights(tenant_ids) -> "jnp.ndarray":
    """(R,) f32 per-row fairness weights from per-row tenant ids.

    The serving layer (``repro.serve``) coalesces many tenants' queries into
    one registry; under the ``cost_balanced`` partitioner the boundary seed
    is a per-query *cost*, so a tenant registering 10x more queries would
    command 10x the boundary-seeding influence.  This helper computes the
    fair-share correction: every row of tenant *t* gets weight
    ``1 / count(t)``, so each tenant's total influence on the boundary seed
    is identical regardless of how many rows it registered.  The weights
    multiply the cost seed (``core.plan`` threads them through as
    ``qweight``); only their *ratios* matter to ``balanced_boundaries``,
    and — because boundaries only move shard ownership, never results
    (DESIGN.md §13) — they can never change bits.

    Host-side numpy (runs at registration time, not in the tick step).
    """
    import numpy as np

    tid = np.asarray(tenant_ids, np.int64).reshape(-1)
    if tid.size == 0:
        return np.zeros((0,), np.float32)
    _, inv, counts = np.unique(tid, return_inverse=True, return_counts=True)
    return (1.0 / counts[inv]).astype(np.float32)


class Partitioner:
    """Interface: contiguous split boundaries for one mesh axis (module doc)."""

    name: ClassVar[str]

    @property
    def is_equal(self) -> bool:
        """True if boundaries are always the equal-count split (a pure
        function of the unit count).  Every plan now runs ONE
        boundary-driven body for both partitioners (the ``sharded`` plan's
        split-``in_specs`` fast path was retired with DESIGN.md §14); the
        flag survives as a cheap query for tests that want
        to know whether boundaries can move between ticks."""
        return False

    def query_capacity(self, n_chunks: int, num_shards: int) -> int:
        """Static max CHUNKS per query shard (compiled shape)."""
        raise NotImplementedError

    def object_capacity(self, n_rows: int, num_shards: int) -> int:
        """Static max Morton-sorted object ROWS per object shard."""
        raise NotImplementedError

    def query_boundaries(self, chunk_costs, num_shards: int) -> jnp.ndarray:
        """(R+1,) i32 chunk-unit boundaries from per-chunk cost estimates."""
        raise NotImplementedError

    def object_boundaries(self, row_costs, num_shards: int) -> jnp.ndarray:
        """(R+1,) i32 row-unit boundaries from per-object cost estimates."""
        raise NotImplementedError


@dataclasses.dataclass(frozen=True)
class EqualPartitioner(Partitioner):
    """Equal-count contiguous splits — the pre-seam behavior, bit-for-bit."""

    name: ClassVar[str] = "equal"

    @property
    def is_equal(self) -> bool:
        return True

    def query_capacity(self, n_chunks: int, num_shards: int) -> int:
        return -(-n_chunks // num_shards)

    def object_capacity(self, n_rows: int, num_shards: int) -> int:
        return -(-max(1, n_rows) // num_shards)

    def query_boundaries(self, chunk_costs, num_shards: int) -> jnp.ndarray:
        return equal_boundaries(chunk_costs.shape[0], num_shards)

    def object_boundaries(self, row_costs, num_shards: int) -> jnp.ndarray:
        return equal_boundaries(row_costs.shape[0], num_shards)


@dataclasses.dataclass(frozen=True)
class CostBalancedPartitioner(Partitioner):
    """Boundaries balance estimated cost; shard capacity = equal * ``slack``.

    ``slack`` bounds how uneven shards may get (a shard can hold at most
    ``slack`` times its equal share) — it is a STATIC knob: larger values
    admit better balance under extreme skew at the price of a bigger
    compiled per-shard shape (masked, so mostly-idle).  ``ema_alpha`` is the
    per-query cost EMA weight the plans apply to the measured candidate
    volume each tick (0 < alpha <= 1; higher = faster adaptation).
    """

    slack: float = 2.0
    ema_alpha: float = 0.25
    name: ClassVar[str] = "cost_balanced"

    def __post_init__(self):
        if self.slack < 1.0:
            raise ValueError(f"slack must be >= 1.0, got {self.slack}")
        if not 0.0 < self.ema_alpha <= 1.0:
            raise ValueError(
                f"ema_alpha must be in (0, 1], got {self.ema_alpha}"
            )

    def _cap(self, n_units: int, num_shards: int) -> int:
        import math

        equal = -(-max(1, n_units) // num_shards)
        return min(max(1, n_units), math.ceil(equal * self.slack))

    def query_capacity(self, n_chunks: int, num_shards: int) -> int:
        return self._cap(n_chunks, num_shards)

    def object_capacity(self, n_rows: int, num_shards: int) -> int:
        # the object axis is count-balanced (core.plan._object_row_costs):
        # uniform row costs never produce a slice beyond the equal share, so
        # no slack — capacity IS the memory budget per device
        return -(-max(1, n_rows) // num_shards)

    def query_boundaries(self, chunk_costs, num_shards: int) -> jnp.ndarray:
        return balanced_boundaries(
            chunk_costs, num_shards,
            self.query_capacity(chunk_costs.shape[0], num_shards),
        )

    def object_boundaries(self, row_costs, num_shards: int) -> jnp.ndarray:
        return balanced_boundaries(
            row_costs, num_shards,
            self.object_capacity(row_costs.shape[0], num_shards),
        )


# --------------------------------------------------------------------------
# partitioner registry — the session spec selects one by name
# --------------------------------------------------------------------------

_PARTITIONERS: dict = {}


def register_partitioner(name: str):
    """Decorator: register a Partitioner factory under ``name``."""

    def deco(factory):
        _PARTITIONERS[name] = factory
        return factory

    return deco


def partitioner_names() -> tuple[str, ...]:
    """Names accepted by ``resolve_partitioner`` / ``ServiceSpec.partitioner``."""
    return tuple(sorted(_PARTITIONERS))


@register_partitioner("equal")
def _make_equal() -> EqualPartitioner:
    return EqualPartitioner()


@register_partitioner("cost_balanced")
def _make_cost_balanced() -> CostBalancedPartitioner:
    return CostBalancedPartitioner()


def resolve_partitioner(partitioner) -> Partitioner:
    """Name | Partitioner | None -> Partitioner (default: equal)."""
    if partitioner is None:
        return EqualPartitioner()
    if isinstance(partitioner, Partitioner):
        return partitioner
    try:
        factory = _PARTITIONERS[str(partitioner)]
    except KeyError:
        raise ValueError(
            f"unknown partitioner {partitioner!r}; registered: "
            f"{partitioner_names()}"
        ) from None
    return factory()
