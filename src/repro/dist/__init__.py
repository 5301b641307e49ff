"""Distribution utilities: logical-axis sharding rules + activation constraints.

``use_rules(mesh, overrides)`` binds the logical->mesh axis table; model code
then calls ``constrain(x, logical_axes)`` at layer boundaries, which lowers to
``with_sharding_constraint`` under an active rule scope and is a no-op outside
one (so the k-NN pipeline, tests and single-host runs never pay for it).
"""
from __future__ import annotations

import jax

from .sharding import (
    DEFAULT_RULES,
    SPATIAL_RULES,
    LogicalRules,
    current_rules,
    logical_to_spec,
    shard_map_compat,
    use_rules,
)

__all__ = [
    "DEFAULT_RULES",
    "SPATIAL_RULES",
    "LogicalRules",
    "constrain",
    "current_rules",
    "logical_to_spec",
    "shard_map_compat",
    "use_rules",
]


def _manual_axes_active() -> bool:
    """True while tracing inside a shard_map/pmap manual-axis region.

    ``with_sharding_constraint`` rejects a spec that names a manual axis
    ("can only refer to Auto axes of the mesh"), so ``constrain`` degrades to
    identity there — the constraint is an optimization hint, and GSPMD still
    propagates shardings through the auto axes.  jax exposes no public probe
    for the manual axes in scope; this reads its axis environment.
    """
    from jax._src import core as _core

    return bool(_core.get_axis_env().axis_sizes)


def constrain(x, logical_axes):
    """Sharding-constrain ``x`` by logical axis names; identity outside rules."""
    lr = current_rules()
    if lr is None or _manual_axes_active():
        return x
    spec = lr.spec(logical_axes, x.shape)
    sharding = jax.sharding.NamedSharding(lr.mesh, spec)
    return jax.lax.with_sharding_constraint(x, sharding)
