"""ServiceSpec — the declarative description of one k-NN serving session.

A spec is the one configuration of a session: the algorithm and device
layout knobs, the workload geometry (the squared region ``G`` the paper's
index partitions: ``origin`` + ``side``), the staging knob ``delta_pad`` and
the result ``collect`` mode.  It is frozen, hashable and eagerly validated:
this is the layer that takes outside input, so unknown registry names and
inconsistent sweep geometry raise at construction time with the full
registry listing, instead of surfacing as a deep registry ``KeyError`` on
the first tick.  The registries themselves live below, in :mod:`repro.core`
and :mod:`repro.kernels`.
"""
from __future__ import annotations

import dataclasses

from repro.core.balance import partitioner_names
from repro.core.executor import (
    available_backends,
    available_plans,
    available_precisions,
)
from repro.core.ticks import MAINTENANCE_MODES
from repro.kernels import merge_backend_names

__all__ = ["ServiceSpec", "COLLECT_MODES"]

SIDE_DEFAULT = 22_500.0  # paper Table 1: squared region of side 22500 u

# what crosses the host boundary per tick (DESIGN.md §14):
#   "full"  — the (Q, k) neighbour lists + shard counters (the pre-§14 path);
#   "stats" — O(Q)/O(1) on-device aggregates only (TickAggregates);
#   "none"  — nothing beyond the two drift scalars the session already reads.
COLLECT_MODES = ("full", "stats", "none")


@dataclasses.dataclass(frozen=True)
class ServiceSpec:
    """Everything a :class:`repro.api.KnnSession` needs, declared up front.

    Algorithm / layout fields configure the tick program and its plan;
    ``origin``/``side`` pin the region ``G`` of the quadtree;
    ``delta_pad`` rounds ``update_objects`` batches up to a fixed multiple
    (sentinel-padded, dropped by the scatter) so every delta size reuses one
    compiled scatter program.
    """

    k: int = 32
    th_quad: int = 192
    l_max: int = 8
    window: int = 256
    chunk: int = 8192
    rebuild_factor: float = 2.0
    region_pad: float = 1e-3
    backend: str = "dense_topk"
    plan: str = "single"
    # int for the 1-D plans (sharded / object_sharded), (query, object) pair
    # for hybrid, None = all devices (hybrid: most balanced factorization)
    mesh_shape: int | tuple[int, int] | None = None
    # work partitioner for the plan's split axes ("equal" | "cost_balanced";
    # repro.core.balance) — cost_balanced re-cuts shard boundaries every tick
    # from the count-pyramid seed + the session's measured-work EMA
    partitioner: str = "equal"
    # sweep numeric mode ("fp32" | "mixed"; repro.core.executor.PRECISIONS)
    # — mixed runs a bf16 widened-radius prefilter + exact fp32 refine,
    # bitwise-identical results (DESIGN.md §14)
    precision: str = "fp32"
    # MERGE backend for the object-axis reduce ("dense_merge" | "fused_multi";
    # repro.kernels.merge_backend_names())
    merge: str = "dense_merge"
    # index-maintenance policy ("rebuild" | "incremental";
    # repro.core.ticks.MAINTENANCE_MODES, DESIGN.md §15): "incremental"
    # refreshes the Morton order / pyramid with work proportional to the
    # delta batch (recode + sort + splice of the moved rows only), bitwise-
    # identical to the full per-tick "rebuild" refresh at every tick
    maintenance: str = "rebuild"
    # incremental only: moved-fraction of N (accumulated since the last full
    # refresh) at which the session defers to one full reindex
    churn_budget: float = 0.25
    max_iters: int = 100_000
    origin: tuple[float, float] = (0.0, 0.0)
    side: float = SIDE_DEFAULT
    delta_pad: int = 1024
    # per-tick result consumption mode (COLLECT_MODES; DESIGN.md §14):
    # "full" ships the (Q, k) lists to the host, "stats" ships only the
    # on-device aggregates, "none" ships nothing beyond the drift scalars
    collect: str = "full"

    def __post_init__(self):
        """Reject unknown registry names and geometry the sweep cannot serve.

        Names are checked against the registries (``backend``, ``plan``,
        ``partitioner``, ``precision``, ``merge``, ``maintenance``,
        ``collect``) and the error lists the registered ones; geometry must
        satisfy ``chunk % window == 0`` and ``k <= chunk``.  Instances
        (``QueryExecutor`` / ``ExecutionPlan`` / ``Partitioner``) pass
        through unchecked — they validated themselves on construction.
        """
        if (isinstance(self.backend, str)
                and self.backend not in available_backends()):
            raise ValueError(
                f"unknown backend {self.backend!r}; registered SCAN "
                f"backends: {available_backends()}"
            )
        if isinstance(self.plan, str) and self.plan not in available_plans():
            raise ValueError(
                f"unknown execution plan {self.plan!r}; registered plans: "
                f"{available_plans()}"
            )
        if (isinstance(self.partitioner, str)
                and self.partitioner not in partitioner_names()):
            raise ValueError(
                f"unknown partitioner {self.partitioner!r}; registered "
                f"partitioners: {partitioner_names()}"
            )
        if (self.precision is not None
                and self.precision not in available_precisions()):
            raise ValueError(
                f"unknown precision {self.precision!r}; one of "
                f"{available_precisions()}"
            )
        if (isinstance(self.merge, str)
                and self.merge not in merge_backend_names()):
            raise ValueError(
                f"unknown merge backend {self.merge!r}; registered MERGE "
                f"backends: {merge_backend_names()}"
            )
        if (self.maintenance is not None
                and self.maintenance not in MAINTENANCE_MODES):
            raise ValueError(
                f"unknown maintenance mode {self.maintenance!r}; one of "
                f"{MAINTENANCE_MODES}"
            )
        if (self.churn_budget is not None
                and not 0.0 < self.churn_budget <= 1.0):
            raise ValueError(
                f"churn_budget must be in (0, 1], got {self.churn_budget!r} "
                "(fraction of N moved since the last full refresh at which "
                "the incremental path defers to a full reindex)"
            )
        if self.k < 1:
            raise ValueError(f"k must be >= 1, got {self.k}")
        if self.window < 1:
            raise ValueError(f"window must be >= 1, got {self.window}")
        if self.chunk < 1 or self.chunk % self.window != 0:
            raise ValueError(
                f"chunk ({self.chunk}) must be a positive multiple of window "
                f"({self.window})"
            )
        if self.k > self.chunk:
            raise ValueError(f"k ({self.k}) must be <= chunk ({self.chunk})")
        mesh = self.mesh_shape
        if isinstance(mesh, (tuple, list)):
            if len(mesh) != 2 or any(
                not isinstance(d, int) or d < 1 for d in mesh
            ):
                raise ValueError(
                    "mesh_shape tuples must be a (query, object) pair of "
                    f"positive ints, got {mesh!r}"
                )
            if isinstance(self.plan, str) and self.plan != "hybrid":
                raise ValueError(
                    f"plan {self.plan!r} lays a 1-D mesh; mesh_shape must be "
                    f"an int, got {tuple(mesh)!r} (2-D shapes are for "
                    "plan='hybrid')"
                )
        elif mesh is not None and mesh < 1:
            raise ValueError(f"mesh_shape must be >= 1, got {mesh}")
        if self.collect not in COLLECT_MODES:
            raise ValueError(
                f"unknown collect mode {self.collect!r}; one of {COLLECT_MODES}"
            )
        if self.side <= 0:
            raise ValueError(f"side must be > 0, got {self.side}")
        if len(self.origin) != 2:
            raise ValueError(f"origin must be an (x, y) pair, got {self.origin!r}")
        if self.delta_pad < 1:
            raise ValueError(f"delta_pad must be >= 1, got {self.delta_pad}")
