"""Where a Pallas kernel runs decides how it runs.

Every kernel of this repository goes through :func:`pallas_call`: Mosaic
compiles it when the program is lowered for a TPU, and the Pallas
interpreter runs it on any other platform.  The choice is made at lowering
time by ``jax.lax.platform_dependent``, from the platform the arrays live
on, so one jitted function compiles for a described TPU topology on a host
without one and runs interpreted under ``JAX_PLATFORMS=cpu``.  Passing
``interpret=True`` or ``False`` pins the choice (tests do).
"""
from __future__ import annotations

from typing import Callable, Optional

import jax
from jax.experimental import pallas as pl


def pallas_call(
    kernel: Callable, *, interpret: Optional[bool] = None, **kwargs
) -> Callable:
    """``pl.pallas_call`` whose interpret mode follows the platform.

    ``interpret=None`` compiles on TPU and interprets elsewhere; a bool
    pins it.  Other keyword arguments go to ``pl.pallas_call`` unchanged."""
    if interpret is not None:
        return pl.pallas_call(kernel, interpret=interpret, **kwargs)
    compiled = pl.pallas_call(kernel, **kwargs)
    interpreted = pl.pallas_call(kernel, interpret=True, **kwargs)

    def call(*args):
        return jax.lax.platform_dependent(
            *args, tpu=compiled, default=interpreted
        )

    return call
