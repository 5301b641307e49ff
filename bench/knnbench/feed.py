"""Inputs made from ``--seed``: the world, its frames, the draws of a mix.

The world is one fixed draw per configuration; every other random draw of
a run comes from ``rng(seed, stream)``, one stream per purpose, so the
same seed gives the same inputs and a new purpose never shifts the draws
of another.
"""
from __future__ import annotations

import numpy as np

from .generators import MovingObjectWorkload, WorkloadConfig

__all__ = ["rng", "make_world", "FrameRing"]

STREAMS = {"issuers": 1, "check": 2, "labels": 3}


def rng(seed: int, stream: str) -> np.random.Generator:
    """The draws of one purpose; any whole number seeds it (folded to 63
    bits)."""
    return np.random.default_rng([int(seed) % (1 << 63), STREAMS[stream]])


def make_world(world: dict) -> MovingObjectWorkload:
    """The Sowell generator for a configuration's ``world`` block.

    The block fixes the generator's own ``seed``: a deployment's world is
    one draw, the same in every run, so that ``--seed`` changes the ids
    the objects carry, the order of the work and the rows checked, and not
    its amount.
    """
    return MovingObjectWorkload(WorkloadConfig(**world))


class FrameRing:
    """``frames`` positions of the world, replayed back and forth.

    Frame ``i + 1`` is frame ``i`` advanced one tick, so stepping the ring
    forward or back moves no object further than one tick's motion, and
    the generator costs nothing once the ring is made.
    """

    def __init__(self, gen: MovingObjectWorkload, frames: int):
        if frames < 2:
            raise ValueError("a frame ring needs at least 2 frames")
        n = gen.cfg.n_objects
        self.frames = np.empty((frames, n, 2), np.float32)
        self.frames[0] = gen.positions()
        for i in range(1, frames):
            gen.advance()
            self.frames[i] = gen.positions()

    @property
    def period(self) -> int:
        return 2 * self.frames.shape[0] - 2

    def index(self, tick: int) -> int:
        i = tick % self.period
        return i if i < self.frames.shape[0] else self.period - i

    def __getitem__(self, tick: int) -> np.ndarray:
        return self.frames[self.index(tick)]

