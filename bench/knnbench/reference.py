"""The plain reference: an exact k-NN brute force, independent of the program.

The host brute force is the one ``chip_smoke.py`` checks against, copied
here so that the yardstick stays with the benchmark: the same f32
``dx*dx + dy*dy`` as the service (object minus query, rounded as the
device rounds it), the issuing object excluded, and the canonical
``(d², id)`` order with the lowest id first among equal distances.

``device_knn`` is the same brute force as plain ``jax.numpy`` on the
device, in a stated precision: in float32 it is a second witness of the
host reference, and in bfloat16 it is the control, the reference computed
one precision below what the deployment states, which the comparison must
refuse.
"""
from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor

import numpy as np

__all__ = [
    "square_sum", "device_square_sum_form", "reference_knn", "device_knn",
    "DeviceSqrt", "wrong_rows", "wrong_pairs",
]


def square_sum(dx, dy, form: str):
    """f32 ``dx*dx + dy*dy`` on the host, rounded as the platform rounds it.

    ``"plain"`` rounds both products and then the sum; ``"fma"`` fuses
    ``dx*dx`` into the add (one rounding of ``dx*dx + f32(dy*dy)``), which
    is what XLA's CPU backend emits.  The fused form is evaluated in f64
    (the product exactly) before its rounding to f32.
    """
    if form == "plain":
        return dx * dx + dy * dy
    dx64 = dx.astype(np.float64)
    return (dx64 * dx64 + (dy * dy).astype(np.float64)).astype(np.float32)


def device_square_sum_form() -> str:
    """Which rounding of ``dx*dx + dy*dy`` the device's compiler emits."""
    import jax

    dx, dy = np.random.default_rng(0).uniform(
        -300, 300, (2, 1 << 16)).astype(np.float32)
    got = np.asarray(jax.jit(lambda a, b: a * a + b * b)(dx, dy))
    for form in ("plain", "fma"):
        if got.tobytes() == square_sum(dx, dy, form).tobytes():
            return form
    return "unknown"


def reference_knn(world, qpos, qid, rows, k: int, form: str,
                  threads: int = 8):
    """Host NumPy brute force for ``rows``: (ids, squared distances).

    ``world`` is the (N, 2) f32 object table the tick saw, ``qpos``/``qid``
    the (Q, 2) query positions and (Q,) excluded issuer ids (-2: none).
    """
    world = np.asarray(world, np.float32)
    rows = np.asarray(rows, np.int64)
    out_i = np.empty((rows.size, k), np.int32)
    out_d = np.empty((rows.size, k), np.float32)
    wx, wy = world[:, 0], world[:, 1]
    block = 16

    def run(lo):
        r = rows[lo:lo + block]
        dx = wx[None, :] - qpos[r, 0][:, None]
        dy = wy[None, :] - qpos[r, 1][:, None]
        d2 = square_sum(dx, dy, form)
        own = qid[r]
        mine = own >= 0
        d2[np.flatnonzero(mine), own[mine]] = np.inf
        kth = np.partition(d2, k - 1, axis=1)[:, k - 1]
        for j in range(r.size):
            cand = np.flatnonzero(d2[j] <= kth[j])
            ids = cand[np.lexsort((cand, d2[j, cand]))[:k]]
            out_i[lo + j] = ids
            out_d[lo + j] = d2[j, ids]

    with ThreadPoolExecutor(threads) as ex:
        list(ex.map(run, range(0, rows.size, block)))
    return out_i, out_d


def device_knn(world, qpos, qid, k: int, dtype: str, block: int = 64):
    """Plain ``jax.numpy`` brute force on the device: (ids, distances).

    ``dx``/``dy`` are taken in f32 and rounded, with each step of the
    square sum, to ``dtype`` (``"float32"``: the reference's arithmetic;
    ``"bfloat16"``: the control).  Rows are selected by a full two-key sort on ``(d², id)``,
    so ties go to the lowest id; distances are ``sqrt(d²)`` in f32.
    """
    import jax
    import jax.numpy as jnp

    info = jnp.finfo(jnp.dtype(dtype))
    n = world.shape[0]

    def rnd(x):
        # round to ``dtype`` explicitly: a TPU compiler may keep an f32
        # value through a cast to bfloat16 and back (excess precision)
        return jax.lax.reduce_precision(x, exponent_bits=info.nexp,
                                        mantissa_bits=info.nmant)

    @jax.jit
    def one(w, q, own):
        dx = rnd(w[None, :, 0] - q[:, 0:1])
        dy = rnd(w[None, :, 1] - q[:, 1:2])
        d2 = rnd(rnd(dx * dx) + rnd(dy * dy))
        ids = jnp.broadcast_to(jnp.arange(n, dtype=jnp.int32), d2.shape)
        d2 = jnp.where(ids == own[:, None], jnp.inf, d2)
        d2s, ids_s = jax.lax.sort((d2, ids), dimension=1, num_keys=2)
        return ids_s[:, :k], jnp.sqrt(d2s[:, :k])

    w = jnp.asarray(world, jnp.float32)
    qpos = np.asarray(qpos, np.float32)
    qid = np.asarray(qid, np.int32)
    m = qpos.shape[0]
    pad = -m % block
    qp = np.concatenate([qpos, np.zeros((pad, 2), np.float32)])
    qi = np.concatenate([qid, np.full((pad,), -2, np.int32)])
    outs = [one(w, qp[lo:lo + block], qi[lo:lo + block])
            for lo in range(0, m + pad, block)]
    ids = np.concatenate([np.asarray(o[0]) for o in outs])[:m]
    dist = np.concatenate([np.asarray(o[1]) for o in outs])[:m]
    return ids, dist


class DeviceSqrt:
    """The device's own f32 square root, one compiled program per shape.

    The check compares distances after the reference's squared distances
    pass through it, so it covers the search and its arithmetic, not the
    platform's rounding of ``sqrt`` (the chip's differs from NumPy's in the
    last bit on about 40% of values).
    """

    def __init__(self):
        import jax
        import jax.numpy as jnp

        self._f = jax.jit(jnp.sqrt)

    def __call__(self, x):
        return np.asarray(self._f(np.asarray(x, np.float32)))


def wrong_rows(got_i, got_d, ref_i, ref_d2, sqrt) -> int:
    """Rows whose ids or distance bits differ from the reference's."""
    got_i = np.asarray(got_i)
    got_d = np.asarray(got_d, np.float32)
    ref_d = sqrt(ref_d2)
    bad = (got_i != ref_i).any(axis=1)
    bad |= (got_d.view(np.int32) != ref_d.view(np.int32)).any(axis=1)
    return int(bad.sum())


def wrong_pairs(world, qpos, qid, got_i, got_d, form: str, sqrt) -> int:
    """Returned (id, distance) pairs that cannot be right, over whole rows.

    A pair is wrong when its id is out of range or the row's own issuer,
    when its distance is not the f32 distance from the query to that object
    in ``world`` (bitwise, through the device's ``sqrt``), or when the row
    breaks the canonical order: ``(d², id)`` strictly increasing, so no id
    twice.  Cheap enough for every row a window returns; what it cannot see
    (a closer object left out) the sampled brute force sees.
    """
    world = np.asarray(world, np.float32)
    got_i = np.asarray(got_i, np.int64)
    got_d = np.asarray(got_d, np.float32)
    n = world.shape[0]
    in_range = (got_i >= 0) & (got_i < n)
    safe = np.where(in_range, got_i, 0)
    pos = world[safe]
    dx = pos[..., 0] - qpos[:, None, 0]
    dy = pos[..., 1] - qpos[:, None, 1]
    d2 = square_sum(dx.astype(np.float32), dy.astype(np.float32), form)
    bad = ~in_range | (got_i == np.asarray(qid)[:, None])
    bad |= sqrt(d2).view(np.int32) != got_d.view(np.int32)
    step_up = (d2[:, 1:] > d2[:, :-1]) | (
        (d2[:, 1:] == d2[:, :-1]) & (got_i[:, 1:] > got_i[:, :-1]))
    bad[:, 1:] |= ~step_up
    return int(bad.sum())
