"""Jit'd public wrapper for the hybrid-queue dispatch kernel."""
from __future__ import annotations

from typing import Optional

import jax

from .dispatch import dispatch_pallas
from .ref import dispatch_ref


def dispatch(
    part_ids: jax.Array,
    payloads: jax.Array,
    num_partitions: int,
    capacity: int,
    *,
    use_kernel: bool = True,
    interpret: Optional[bool] = None,
):
    """Route tuples (arrival order = index) into bounded per-partition FIFO
    buffers. Returns (buffers (P,C,W), counts (P,), dest (T,))."""
    if not use_kernel:
        return dispatch_ref(part_ids, payloads, num_partitions, capacity)
    return dispatch_pallas(
        part_ids,
        payloads,
        num_partitions=num_partitions,
        capacity=capacity,
        interpret=interpret,
    )


__all__ = ["dispatch"]
