"""QueryExecutor — the seam between the kernel, core and serving layers.

The pipeline (core layer) never selects neighbours itself: every SCAN
iteration hands its gathered candidate window to an executor, which dispatches
to a registered kernel-layer backend (DESIGN.md §6).  The serving layer
(:class:`repro.api.KnnSession`) picks the backend by name
(``ServiceSpec.backend`` / ``--backend``), so swapping the selection
strategy — XLA top-k, the fused Pallas kernel, full-sort brute force, or any
future sharded/approximate variant — touches no pipeline code.

Since the ExecutionPlan refactor (DESIGN.md §10) the seam has TWO orthogonal
axes, both selected by name at this layer boundary:

  * **backend** (*what* merges a candidate window) — this module;
  * **plan** (*where* the sweep runs: one device or a ``("query",)`` mesh) —
    ``core/plan.py``; re-exposed here (:func:`available_plans`, plus
    ``resolve_plan`` as a lazy module-level alias of the canonical
    ``repro.core.plan.resolve_plan``) so callers configure both axes at one
    seam without a second resolution code path.

``QueryExecutor`` is a frozen (hence hashable) dataclass so it can ride
through ``jax.jit`` as a *static* argument: a jitted pipeline specializes per
backend, exactly like it specializes per ``k``/``window`` — and per plan.
"""
from __future__ import annotations

import dataclasses

from repro.kernels import get_scan_backend, scan_backend_names

__all__ = [
    "PRECISIONS",
    "QueryExecutor",
    "resolve_executor",
    "available_backends",
    "available_plans",
    "available_partitioners",
    "available_precisions",
    "resolve_plan",
]


def available_backends() -> tuple[str, ...]:
    """Names accepted by ``resolve_executor`` / ``ServiceSpec.backend``."""
    return scan_backend_names()


def available_plans() -> tuple[str, ...]:
    """Names accepted by ``resolve_plan`` / ``ServiceSpec.plan``."""
    from .plan import plan_names  # lazy: plan.py imports pipeline -> executor

    return plan_names()


def available_partitioners() -> tuple[str, ...]:
    """Names accepted by ``ServiceSpec.partitioner`` — the third seam axis
    (work splitting), configured at the same boundary as backend and plan."""
    from .balance import partitioner_names

    return partitioner_names()


def available_precisions() -> tuple[str, ...]:
    """Names accepted by ``ServiceSpec.precision`` — the sweep's numeric
    mode (DESIGN.md §14), configured at the same boundary as the backend."""
    return PRECISIONS


def __getattr__(name):
    # ``resolve_plan`` is a documented ALIAS of the canonical entry point
    # ``repro.core.plan.resolve_plan`` — resolved lazily (plan.py imports the
    # pipeline, which imports this module) and re-exported as the *same*
    # function object, so there is exactly one resolution code path
    # (tests/test_plan.py pins the identity).
    if name == "resolve_plan":
        from .plan import resolve_plan

        return resolve_plan
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")


PRECISIONS = ("fp32", "mixed")


@dataclasses.dataclass(frozen=True)
class QueryExecutor:
    """A named SCAN-merge strategy (+ the sweep's numeric precision mode).

    ``precision`` selects the sweep arithmetic (DESIGN.md §14): ``fp32`` is
    the exact path; ``mixed`` prepends a bf16 distance pass with a
    conservatively widened k-th-distance radius and re-ranks only the
    survivors in exact fp32 — bitwise-identical results for every backend
    (fuzzed across the plan x partitioner matrix by the property harness).
    """

    backend: str = "dense_topk"
    precision: str = "fp32"

    def __post_init__(self):
        get_scan_backend(self.backend)  # fail fast on unknown names
        if self.precision not in PRECISIONS:
            raise ValueError(
                f"unknown precision {self.precision!r}; one of {PRECISIONS}"
            )

    def scan_merge(self, qpos, cpos, cids, valid, best_d, best_i, *, k: int):
        """Merge one candidate window into the ascending result lists.

        qpos (Q,2); cpos (Q,W,2); cids/valid (Q,W); best_d/best_i (Q,k) ->
        (best_d, best_i), semantics identical across backends up to k-th-
        distance ties.
        """
        return get_scan_backend(self.backend)(
            qpos, cpos, cids, valid, best_d, best_i, k,
            precision=self.precision,
        )


def resolve_executor(backend, precision=None) -> QueryExecutor:
    """Name | QueryExecutor | None [+ precision] -> QueryExecutor.

    Defaults: ``dense_topk`` / ``fp32``.  An explicit ``precision`` overrides
    the one a passed-in ``QueryExecutor`` instance carries.
    """
    if isinstance(backend, QueryExecutor):
        if precision is not None and precision != backend.precision:
            return dataclasses.replace(backend, precision=str(precision))
        return backend
    kw = {}
    if backend is not None:
        kw["backend"] = str(backend)
    if precision is not None:
        kw["precision"] = str(precision)
    return QueryExecutor(**kw)
