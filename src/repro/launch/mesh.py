"""Production mesh construction (single-pod 16x16 and multi-pod 2x16x16).

A FUNCTION, not a module-level constant — importing this module never touches
jax device state (the dry-run sets XLA_FLAGS *before* any jax init).
"""
from __future__ import annotations

import os

import jax

__all__ = [
    "forced_cpu_env",
    "make_production_mesh",
    "make_local_mesh",
    "make_query_mesh",
    "make_object_mesh",
    "make_spatial_mesh",
    "default_hybrid_shape",
]


def forced_cpu_env(devices: int) -> dict:
    """Environment for a child process on ``devices`` forced CPU devices.

    ``JAX_PLATFORMS=cpu`` keeps the child off any accelerator: a TPU chip
    belongs to one process, which may be the parent.  The XLA flag splits
    the host into ``devices`` CPU devices; it only takes effect in a process
    that has not initialized JAX yet.
    """
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = (
        env.get("XLA_FLAGS", "")
        + f" --xla_force_host_platform_device_count={devices}"
    ).strip()
    return env


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes)


def make_local_mesh(data: int = 1, model: int = 1, pod: int = 1):
    """Tiny mesh over however many (possibly fake) local devices exist — tests."""
    if pod > 1:
        return jax.make_mesh((pod, data, model), ("pod", "data", "model"))
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
