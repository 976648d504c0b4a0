"""Jit'd public wrapper for the SSD chunk-scan kernel."""
from __future__ import annotations

from typing import Optional

from .ssd import ssd_pallas


def ssd(x, dt, A, Bm, Cm, *, chunk: int = 128, h0=None,
        interpret: Optional[bool] = None):
    """Mamba2 SSD over (B, L, H, P). Returns (y, final_state (B,H,P,N)).
    ``h0`` is unsupported by the kernel path (serving uses the jnp path for
    state carry-in); must be None."""
    assert h0 is None, "kernel path starts from zero state"
    return ssd_pallas(x, dt, A, Bm, Cm, chunk=chunk, interpret=interpret)


__all__ = ["ssd"]
