"""Where a Pallas kernel runs: compiled on the TPU, interpreted on the CPU.

Every kernel of this package goes through :func:`pallas_call`.  The choice
between Mosaic (the TPU kernel compiler) and the interpreter (the kernel
body evaluated as ordinary XLA ops) is made when the program is *lowered*,
from the platform it is lowered for — not from the process's default
backend.  A program lowered for a TPU therefore always carries the compiled
kernel (a ``tpu_custom_call`` in its HLO), including one compiled ahead of
time from a CPU-only host for a described TPU topology; the interpreter is
used only where the program is lowered for the CPU, which is where the
tests run.
"""
from __future__ import annotations

import jax
from jax.experimental import pallas as pl

__all__ = ["pallas_call"]


def pallas_call(kernel, *, name: str, interpret: bool | None = None,
                **kwargs):
    """``pl.pallas_call`` with the compile-or-interpret choice made at lowering.

    ``name`` is the kernel's stable name, the one a profiler trace and the
    compiled program show it under.  ``interpret=None`` stages both forms
    and lets ``lax.platform_dependent`` keep the interpreter for the CPU and
    the compiled kernel everywhere else (the other branch is never lowered);
    ``True``/``False`` force one form.
    """
    kwargs["name"] = name
    if interpret is not None:
        return pl.pallas_call(kernel, interpret=interpret, **kwargs)
    compiled = pl.pallas_call(kernel, **kwargs)
    interpreted = pl.pallas_call(kernel, interpret=True, **kwargs)
    return lambda *args: jax.lax.platform_dependent(
        *args, cpu=interpreted, default=compiled
    )
