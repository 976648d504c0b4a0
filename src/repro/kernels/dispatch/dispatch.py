"""Pallas TPU kernel: hybrid-queue partition dispatch (paper §4.3, TPU-native).

The multicore hybrid queue uses per-partition FIFO queues + delegation
counters. Vectorized: the rank of each tuple within its partition (= its FIFO
position, preserving arrival order) is a prefix-sum over a one-hot partition
matrix, computed as a triangular matmul on the MXU; the scatter into bounded
per-partition buffers is a second one-hot matmul. MoE dispatch is this exact
kernel with partitions = experts.

  onehot (T, P)   : tuple -> partition
  rank            = (strictly-lower-triangular ones (T,T)) @ onehot, row t at its own partition
  buffers (P*C, W)= slot-onehot (P*C, T) @ payloads (T, W)
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ..platform import pallas_call


def _dispatch_kernel(
    part_ids_ref,  # (T, 1) int32
    payloads_ref,  # (T, W)
    buffers_ref,  # (P*C, W)
    counts_ref,  # (P, 1) int32
    dest_ref,  # (T, 1) int32
    *,
    num_partitions: int,
    capacity: int,
):
    T = part_ids_ref.shape[0]
    ids = part_ids_ref[:, 0]  # (T,)
    valid = ids >= 0

    cols = jax.lax.broadcasted_iota(jnp.int32, (T, num_partitions), 1)
    onehot = ((cols == ids[:, None]) & valid[:, None]).astype(jnp.float32)

    # strictly-lower-triangular ones: rank[t] = # earlier tuples, same partition
    r = jax.lax.broadcasted_iota(jnp.int32, (T, T), 0)
    c = jax.lax.broadcasted_iota(jnp.int32, (T, T), 1)
    tri = (c < r).astype(jnp.float32)
    prior = jnp.dot(tri, onehot, preferred_element_type=jnp.float32)  # (T, P)
    rank = jnp.sum(prior * onehot, axis=1).astype(jnp.int32)  # (T,)

    counts = jnp.sum(onehot, axis=0).astype(jnp.int32)  # (P,)
    keep = valid & (rank < capacity)
    dest = jnp.where(keep, ids * capacity + rank, -1)

    # scatter via slot-onehot matmul
    PC = num_partitions * capacity
    slot_rows = jax.lax.broadcasted_iota(jnp.int32, (PC, T), 0)
    slot_onehot = (slot_rows == dest[None, :]).astype(jnp.float32)
    buffers_ref[...] = jnp.dot(
        slot_onehot, payloads_ref[...].astype(jnp.float32),
        preferred_element_type=jnp.float32,
    ).astype(buffers_ref.dtype)
    counts_ref[...] = counts[:, None]
    dest_ref[...] = dest[:, None]


@functools.partial(
    jax.jit, static_argnames=("num_partitions", "capacity", "interpret")
)
def dispatch_pallas(
    part_ids: jax.Array,
    payloads: jax.Array,
    *,
    num_partitions: int,
    capacity: int,
    interpret: Optional[bool] = None,
):
    T, W = payloads.shape
    PC = num_partitions * capacity
    kernel = functools.partial(
        _dispatch_kernel, num_partitions=num_partitions, capacity=capacity
    )
    buffers, counts, dest = pallas_call(
        kernel,
        out_shape=(
            jax.ShapeDtypeStruct((PC, W), payloads.dtype),
            jax.ShapeDtypeStruct((num_partitions, 1), jnp.int32),
            jax.ShapeDtypeStruct((T, 1), jnp.int32),
        ),
        in_specs=[
            pl.BlockSpec((T, 1), lambda: (0, 0)),
            pl.BlockSpec((T, W), lambda: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((PC, W), lambda: (0, 0)),
            pl.BlockSpec((num_partitions, 1), lambda: (0, 0)),
            pl.BlockSpec((T, 1), lambda: (0, 0)),
        ],
        interpret=interpret,
    )(part_ids.astype(jnp.int32)[:, None], payloads)
    return buffers.reshape(num_partitions, capacity, W), counts[:, 0], dest[:, 0]
