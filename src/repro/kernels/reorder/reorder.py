"""Pallas TPU kernel: vectorized reorder-commit (paper §3 fig. 4, TPU-native).

Hardware adaptation (DESIGN.md §2): the multicore version relies on CAS
atomics; TPUs have none. Instead a *batch* of K completed (serial, payload)
pairs is committed per call, and both the scatter-into-ring and the in-order
drain are expressed as one-hot matmuls so the permutation work lands on the
MXU (the TPU-idiomatic replacement for random access):

  scatter: onehot (S, K) @ payloads (K, W)  -> ring writes
  drain:   rotation one-hot (S, S) @ ring   -> emitted rows, in serial order

The contiguous-prefix length (how many outputs are ready to send) is a masked
min-reduction over ring distances — the vectorized equivalent of fig. 4's
"while buffer[next % s] != EMPTY" walk.

The whole state lives in VMEM: (S, W) ring + (S,) present + scalar ``next``.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ..platform import pallas_call


def _commit_kernel(
    # inputs
    buf_ref,  # (S, W)
    present_ref,  # (S, 1) int32 (bool packed)
    next_ref,  # (1, 1) int32
    serials_ref,  # (K, 1) int32
    payloads_ref,  # (K, W)
    # outputs
    out_buf_ref,  # (S, W)
    out_present_ref,  # (S, 1)
    out_next_ref,  # (1, 1)
    emitted_ref,  # (S, W)
    emit_count_ref,  # (1, 1)
    accepted_ref,  # (K, 1) int32
):
    S, W = buf_ref.shape
    K = serials_ref.shape[0]
    nxt = next_ref[0, 0]
    serials = serials_ref[:, 0]  # (K,)
    present = present_ref[:, 0] > 0  # (S,)

    # ---- try_add (entry condition): one-hot scatter via MXU
    in_window = (serials >= 0) & (serials >= nxt) & (serials < nxt + S)
    slot = jnp.where(in_window, serials % S, -1)  # (K,)
    rows = jax.lax.broadcasted_iota(jnp.int32, (S, K), 0)
    onehot = (rows == slot[None, :]).astype(payloads_ref.dtype)  # (S, K)
    taken = jnp.sum(onehot, axis=1) > 0  # (S,)
    scattered = jnp.dot(
        onehot, payloads_ref[...], preferred_element_type=jnp.float32
    ).astype(buf_ref.dtype)
    buf = jnp.where(taken[:, None], scattered, buf_ref[...])
    present = present | taken

    # ---- drain: contiguous present prefix from ``next``
    idx = jax.lax.broadcasted_iota(jnp.int32, (S,), 0)
    pos = (idx - nxt) % S  # ring distance from head
    absent_pos = jnp.where(present, S, pos)
    emit_count = jnp.min(absent_pos)

    # rotation one-hot: emitted[i] = buf[j] where pos[j] == i and i < count
    out_rows = jax.lax.broadcasted_iota(jnp.int32, (S, S), 0)  # i
    rot = (out_rows == pos[None, :]) & (out_rows < emit_count)
    emitted_ref[...] = jnp.dot(
        rot.astype(jnp.float32), buf.astype(jnp.float32),
        preferred_element_type=jnp.float32,
    ).astype(emitted_ref.dtype)

    present = present & (pos >= emit_count)
    out_buf_ref[...] = buf
    out_present_ref[...] = present.astype(jnp.int32)[:, None]
    out_next_ref[0, 0] = nxt + emit_count
    emit_count_ref[0, 0] = emit_count
    accepted_ref[...] = in_window.astype(jnp.int32)[:, None]


@functools.partial(jax.jit, static_argnames=("interpret",))
def commit_pallas(buf, present, nxt, serials, payloads, *,
                  interpret: Optional[bool] = None):
    """One reorder-commit step. present: (S,) int32; nxt: () int32."""
    S, W = buf.shape
    K = serials.shape[0]
    out_shapes = (
        jax.ShapeDtypeStruct((S, W), buf.dtype),
        jax.ShapeDtypeStruct((S, 1), jnp.int32),
        jax.ShapeDtypeStruct((1, 1), jnp.int32),
        jax.ShapeDtypeStruct((S, W), buf.dtype),
        jax.ShapeDtypeStruct((1, 1), jnp.int32),
        jax.ShapeDtypeStruct((K, 1), jnp.int32),
    )
    specs = [
        pl.BlockSpec((S, W), lambda: (0, 0)),
        pl.BlockSpec((S, 1), lambda: (0, 0)),
        pl.BlockSpec((1, 1), lambda: (0, 0)),
        pl.BlockSpec((K, 1), lambda: (0, 0)),
        pl.BlockSpec((K, W), lambda: (0, 0)),
    ]
    out_specs = [
        pl.BlockSpec((S, W), lambda: (0, 0)),
        pl.BlockSpec((S, 1), lambda: (0, 0)),
        pl.BlockSpec((1, 1), lambda: (0, 0)),
        pl.BlockSpec((S, W), lambda: (0, 0)),
        pl.BlockSpec((1, 1), lambda: (0, 0)),
        pl.BlockSpec((K, 1), lambda: (0, 0)),
    ]
    return pallas_call(
        _commit_kernel,
        out_shape=out_shapes,
        in_specs=specs,
        out_specs=out_specs,
        interpret=interpret,
    )(
        buf,
        present.astype(jnp.int32)[:, None],
        nxt.reshape(1, 1).astype(jnp.int32),
        serials.astype(jnp.int32)[:, None],
        payloads,
    )
