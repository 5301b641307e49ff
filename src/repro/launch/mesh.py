"""Device meshes: the k-NN plans' meshes and a small local mesh for tests.

FUNCTIONS, not module-level constants — importing this module never touches
jax device state (a forced host device count must be set before jax init).
"""
from __future__ import annotations

import jax

__all__ = [
    "make_local_mesh",
    "make_query_mesh",
    "make_object_mesh",
    "make_spatial_mesh",
    "default_hybrid_shape",
]


def make_local_mesh(data: int = 1, model: int = 1):
    """Tiny mesh over however many (possibly fake) local devices exist — tests."""
    return jax.make_mesh((data, model), ("data", "model"))


def _take_devices(n: int | None):
    devs = jax.devices()
    n = len(devs) if n is None else int(n)
    if not 1 <= n <= len(devs):
        raise ValueError(f"requested {n} devices, have {len(devs)}")
    return devs[:n]


def make_query_mesh(num_devices: int | None = None):
    """The 1-D ``("query",)`` tick-serving mesh (DESIGN.md §10).

    The sharded ExecutionPlan splits the Morton-sorted query batch along this
    single axis; ``num_devices=None`` takes every visible device.  On CPU run
    under ``XLA_FLAGS=--xla_force_host_platform_device_count=N`` to get a
    multi-device mesh without accelerators.
    """
    import numpy as np

    return jax.sharding.Mesh(np.asarray(_take_devices(num_devices)), ("query",))


def make_object_mesh(num_devices: int | None = None):
    """The 1-D ``("object",)`` mesh of the object-sharded plan (DESIGN.md §12).

    Each device holds one Morton-contiguous slice of the object set (plus its
    own quadtree over that slice); per-query partial result lists reduce
    across this axis with the MERGE backends.
    """
    import numpy as np

    return jax.sharding.Mesh(np.asarray(_take_devices(num_devices)), ("object",))


def make_spatial_mesh(query: int, objects: int):
    """The 2-D ``("query", "object")`` mesh of the hybrid plan (DESIGN.md §12).

    ``query * objects`` devices arranged row-major: the query axis splits the
    Morton-sorted batch (disjoint shards, concatenating gather), the object
    axis splits the object set (overlapping partial lists, merge-reduced).
    """
    import numpy as np

    devs = _take_devices(query * objects)
    return jax.sharding.Mesh(
        np.asarray(devs).reshape(query, objects), ("query", "object")
    )


def default_hybrid_shape(num_devices: int | None = None) -> tuple[int, int]:
    """Most-balanced ``(query, object)`` factorization of the device count.

    The largest divisor pair with ``query <= object`` — 8 devices -> (2, 4),
    6 -> (2, 3), primes degrade to (1, n) (= pure object sharding along a
    2-D mesh).  Used when ``mesh_shape`` is not given for the hybrid plan.
    """
    n = len(jax.devices()) if num_devices is None else int(num_devices)
    if n < 1:
        raise ValueError(f"need at least one device, got {n}")
    q = max(d for d in range(1, int(n**0.5) + 1) if n % d == 0)
    return (q, n // q)
