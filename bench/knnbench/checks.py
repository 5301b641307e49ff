"""How ``correct`` is decided: what the window returned, against the reference.

A driver hands over what its window returned as a stream of groups: the
rows of one tick or one request, with the object world they had to reflect
(every report ingested before their submit) and the rows of them that the
seed drew for the brute force.  Two numbers are compared, each with the
limit 0, since the deployment states exact, bitwise answers:

* ``rows_wrong``: drawn rows whose ids or distance bits differ from the
  host brute force's (a row never returned counts as wrong);
* ``pairs_wrong``: over every row returned, the (id, distance) pairs that
  cannot be right: an id out of range or the issuer itself, a distance
  that is not the f32 distance to that object, or a row out of the
  canonical ``(d², id)`` order; a row never returned counts all its pairs.

``control`` puts the reference in the program's place, one precision below
the deployment's (bfloat16 square sums for float32): the same comparison
must refuse it.
"""
from __future__ import annotations

import dataclasses

import numpy as np

from .reference import (DeviceSqrt, device_knn, device_square_sum_form,
                        reference_knn, wrong_pairs, wrong_rows)

__all__ = ["Group", "compare", "control", "LIMITS"]

LIMITS = {"rows_wrong": 0, "pairs_wrong": 0}


@dataclasses.dataclass
class Group:
    """Rows answered together, and the world they had to reflect.

    ``got_i``/``got_d`` are None where the rows never came back.  ``world``
    may be a buffer the driver changes after the group is consumed.
    """

    world: np.ndarray
    qpos: np.ndarray
    qid: np.ndarray
    got_i: np.ndarray | None
    got_d: np.ndarray | None
    sampled: np.ndarray  # row indices drawn for the brute force

    def answered(self, k: int) -> bool:
        m = self.qpos.shape[0]
        return (self.got_i is not None and self.got_d is not None
                and np.shape(self.got_i) == (m, k)
                and np.shape(self.got_d) == (m, k))


def _score(g: Group, k: int, form: str, sqrt) -> tuple[int, int]:
    """(rows wrong among the drawn, pairs wrong) of one group."""
    m = g.qpos.shape[0]
    if not g.answered(k):
        return g.sampled.size, m * k
    pairs_bad = wrong_pairs(g.world, g.qpos, g.qid, g.got_i, g.got_d, form,
                            sqrt)
    rows_bad = 0
    if g.sampled.size:
        s = g.sampled
        ref_i, ref_d2 = reference_knn(g.world, g.qpos, g.qid, s, k, form)
        rows_bad = wrong_rows(g.got_i[s], g.got_d[s], ref_i, ref_d2, sqrt)
    return rows_bad, pairs_bad


def compare(groups, k: int, substitute=None) -> tuple[dict, dict]:
    """``({name: (value, limit)}, stats)`` over the driver's groups.

    ``substitute(group)``, where given, replaces each group before it is
    scored (the control) and may return None to leave it out.
    """
    form = device_square_sum_form()
    sqrt = DeviceSqrt()
    rows_bad = pairs_bad = rows = drawn = 0
    for g in groups:
        if substitute is not None:
            g = substitute(g)
            if g is None:
                continue
        rows += g.qpos.shape[0]
        drawn += g.sampled.size
        r, p = _score(g, k, form, sqrt)
        rows_bad += r
        pairs_bad += p
    checks = {"rows_wrong": (rows_bad, LIMITS["rows_wrong"]),
              "pairs_wrong": (pairs_bad, LIMITS["pairs_wrong"])}
    return checks, dict(rows=rows, drawn=drawn, form=form)


def control(groups, k: int, dtype: str) -> dict:
    """The comparison's numbers for ``device_knn`` in ``dtype`` put in the
    program's place on the drawn rows (``"bfloat16"``: the control;
    ``"float32"``: a second witness that sides with the host reference)."""

    def swap(g):
        if not g.sampled.size:
            return None
        s = g.sampled
        ids, dist = device_knn(g.world, g.qpos[s], g.qid[s], k, dtype)
        return Group(g.world, g.qpos[s], g.qid[s], ids, dist,
                     np.arange(s.size))

    return compare(groups, k, substitute=swap)[0]
