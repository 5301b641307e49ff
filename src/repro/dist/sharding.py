"""Logical-axis sharding rules (flax/T5X-style, trimmed to what we use).

Model code annotates activations/params with *logical* axis names
(``("batch", "act_seq", "ff")``); a thread-local rule table bound to a mesh by
:func:`use_rules` maps every logical name to zero or more *mesh* axes.  The
indirection keeps model code mesh-agnostic: the dry-run hillclimbs alternative
bindings purely via ``--override`` (see launch/dryrun.py) without touching a
single model file.

Spec construction applies three fixups, in order (tests in test_dist.py):
  1. **missing-axis filter** — mesh axes absent from the bound mesh are dropped
     (so the single-pod 16x16 mesh silently ignores the ``pod`` member of
     ``("pod", "data")`` bindings);
  2. **dedup** — a mesh axis may shard at most one dim of a value; the first
     binding wins, later duplicates are dropped;
  3. **divisibility fallback** — a mesh axis whose size does not divide the dim
     is dropped (XLA would reject the constraint otherwise).
"""
from __future__ import annotations

import contextlib
import threading
from typing import Mapping

from jax.sharding import Mesh, PartitionSpec as P

__all__ = [
    "DEFAULT_RULES",
    "SPATIAL_RULES",
    "LogicalRules",
    "current_rules",
    "logical_to_spec",
    "shard_map_compat",
    "use_rules",
]

# logical name -> mesh axis | tuple of mesh axes | None (replicate).
# 'batch' spans pod+data (DP across pods, FSDP/DP inside); 'embed' carries the
# FSDP param sharding; head/ff/vocab/expert dims are Megatron-TP on 'model'.
DEFAULT_RULES: dict[str, str | tuple[str, ...] | None] = {
    "batch": ("pod", "data"),
    "cache_batch": ("pod", "data"),
    "seq": None,
    "act_seq": None,
    "kv_seq": None,
    "act_kv_seq": None,
    "img": None,
    "embed": "data",
    "heads": "model",
    "kv": "model",
    "ff": "model",
    "vocab": "model",
    "expert": "model",
    "expert_cap": "model",
    "conv": None,
}

# Spatial logical axes for the k-NN serving path (DESIGN.md §10/§12).  Tick
# meshes name up to two axes: ``("query",)`` (the sharded plan: Morton-sorted
# query batch split across devices, quadtree replicated), ``("object",)``
# (the object-sharded plan: Morton-contiguous object slices, one local
# quadtree per device, per-query lists merge-reduced across the axis via
# kernels/merge_topk.py) and the 2-D ``("query", "object")`` hybrid mesh.
# The missing-axis fixup below makes one rule table serve all three: on a
# query-only mesh the "object" binding drops away (values replicate), and
# vice versa.  "cell" stays reserved (a future cell-granular layout).
SPATIAL_RULES: dict[str, str | tuple[str, ...] | None] = {
    "query": "query",
    "object": "object",
    "cell": None,
}


class LogicalRules:
    """A rule table bound to a mesh (the object ``current_rules()`` returns)."""

    def __init__(self, mesh: Mesh, rules: Mapping[str, str | tuple | None]):
        self.mesh = mesh
        self.rules = dict(rules)

    def spec(self, logical_axes, shape=None) -> P:
        axis_sizes = dict(zip(self.mesh.axis_names, self.mesh.devices.shape))
        used: set[str] = set()
        entries = []
        for d, name in enumerate(logical_axes):
            binding = self.rules.get(name) if name is not None else None
            if binding is None:
                entries.append(None)
                continue
            if isinstance(binding, str):
                binding = (binding,)
            kept = []
            prod = 1
            for ax in binding:
                if ax not in axis_sizes or ax in used:  # filter + dedup
                    continue
                if shape is not None and shape[d] % (prod * axis_sizes[ax]) != 0:
                    continue  # divisibility fallback: replicate instead
                kept.append(ax)
                used.add(ax)
                prod *= axis_sizes[ax]
            entries.append(None if not kept else kept[0] if len(kept) == 1 else tuple(kept))
        return P(*entries)


_local = threading.local()


def current_rules() -> LogicalRules | None:
    """The active rule table, or None outside any ``use_rules`` scope."""
    return getattr(_local, "rules", None)


@contextlib.contextmanager
def use_rules(mesh: Mesh, overrides: Mapping[str, str | tuple | None] | None = None):
    """Bind ``DEFAULT_RULES`` (+ per-experiment overrides) to ``mesh``."""
    merged = dict(DEFAULT_RULES)
    if overrides:
        merged.update(overrides)
    prev = current_rules()
    _local.rules = LogicalRules(mesh, merged)
    try:
        yield _local.rules
    finally:
        _local.rules = prev


def logical_to_spec(logical_axes, shape=None) -> P:
    """Logical axes (+ optional concrete shape for divisibility) -> PartitionSpec."""
    lr = current_rules()
    assert lr is not None, "logical_to_spec requires an active use_rules(mesh) scope"
    return lr.spec(logical_axes, shape)


def shard_map_compat(f, *, mesh, in_specs, out_specs, axis_names, check_vma):
    """``jax.shard_map`` with every argument spelled out (train and serving)."""
    import jax

    return jax.shard_map(
        f, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
        axis_names=axis_names, check_vma=check_vma,
    )
