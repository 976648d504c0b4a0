"""Jit'd public wrapper for the reorder-commit kernel."""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from .ref import ReorderState, commit_ref, init_state
from .reorder import commit_pallas


def commit(
    state: ReorderState,
    serials: jax.Array,
    payloads: jax.Array,
    *,
    use_kernel: bool = True,
    interpret: Optional[bool] = None,
) -> tuple[ReorderState, jax.Array, jax.Array, jax.Array]:
    """Batched reorder-commit: scatter K completed (serial, payload) pairs into
    the ring and emit the contiguous ready prefix in serial order.

    Returns (new_state, emitted (S,W), emit_count (), accepted (K,) bool).
    """
    if not use_kernel:
        return commit_ref(state, serials, payloads)
    buf, present, nxt, emitted, count, accepted = commit_pallas(
        state.buf,
        state.present.astype(jnp.int32),
        state.next,
        serials,
        payloads,
        interpret=interpret,
    )
    new_state = ReorderState(
        buf=buf, present=present[:, 0] > 0, next=nxt[0, 0]
    )
    return new_state, emitted, count[0, 0], accepted[:, 0] > 0


__all__ = ["ReorderState", "commit", "init_state"]
