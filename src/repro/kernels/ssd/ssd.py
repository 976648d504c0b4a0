"""Pallas TPU kernel: Mamba2 SSD chunked scan [arXiv:2405.21060, TPU-native].

Grid: (B, H, num_chunks) with the chunk dim innermost. TPU grids execute
sequentially, so the (P, N) SSM state is carried across chunk iterations in a
VMEM scratch accumulator (reset at chunk 0) — the TPU-idiomatic replacement
for the GPU kernel's inter-block shared-memory recurrence.

Per chunk (all in fp32, on the MXU):
  scores  = C_chunk @ B_chunk^T                       (cl, cl)
  y_intra = (decay-mask * scores) @ (dt * x)          (cl, P)
  y_inter = exp(cumsum dA) * (C_chunk @ state^T)      (cl, P)
  state'  = exp(dA_total) * state + ((dt*decay_to_end*x)^T @ B_chunk)^T
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..platform import pallas_call


def _ssd_kernel(
    x_ref,  # (1, cl, 1, P)
    dt_ref,  # (1, cl, 1)
    a_ref,  # (1, 1) fp32  A for this head
    b_ref,  # (1, cl, N)
    c_ref,  # (1, cl, N)
    y_ref,  # (1, cl, 1, P)
    hT_ref,  # (1, 1, P, N)  final state output
    state_ref,  # VMEM scratch (P, N) fp32
    *,
    num_chunks: int,
):
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _():
        state_ref[...] = jnp.zeros_like(state_ref)

    x = x_ref[0, :, 0, :].astype(jnp.float32)  # (cl, P)
    dt = dt_ref[0, :, 0].astype(jnp.float32)  # (cl,)
    A = a_ref[0, 0]
    Bm = b_ref[0].astype(jnp.float32)  # (cl, N)
    Cm = c_ref[0].astype(jnp.float32)  # (cl, N)

    dA = dt * A  # (cl,)
    cum = jnp.cumsum(dA)  # (cl,)
    total = cum[-1]

    # intra-chunk: masked decay matrix L[q,k] = exp(cum_q - cum_k) for k<=q
    diff = cum[:, None] - cum[None, :]
    qi = jax.lax.broadcasted_iota(jnp.int32, diff.shape, 0)
    ki = jax.lax.broadcasted_iota(jnp.int32, diff.shape, 1)
    L = jnp.where(qi >= ki, jnp.exp(diff), 0.0)  # (cl, cl)
    scores = jnp.dot(Cm, Bm.T, preferred_element_type=jnp.float32)
    xdt = x * dt[:, None]  # (cl, P)
    y = jnp.dot(L * scores, xdt, preferred_element_type=jnp.float32)

    # inter-chunk from carried state
    state = state_ref[...]  # (P, N)
    y += jnp.exp(cum)[:, None] * jnp.dot(
        Cm, state.T, preferred_element_type=jnp.float32
    )

    # state update
    decay_to_end = jnp.exp(total - cum)  # (cl,)
    contrib = jnp.dot(
        (xdt * decay_to_end[:, None]).T, Bm, preferred_element_type=jnp.float32
    )  # (P, N)
    state_ref[...] = jnp.exp(total) * state + contrib

    y_ref[0, :, 0, :] = y.astype(y_ref.dtype)

    @pl.when(ci == num_chunks - 1)
    def _():
        hT_ref[0, 0, :, :] = state_ref[...].astype(hT_ref.dtype)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_pallas(
    x: jax.Array,  # (B, L, H, P) fp32
    dt: jax.Array,  # (B, L, H) fp32
    A: jax.Array,  # (H,) fp32
    Bm: jax.Array,  # (B, L, N) fp32
    Cm: jax.Array,  # (B, L, N) fp32
    *,
    chunk: int = 128,
    interpret: Optional[bool] = None,
):
    B, L, H, P = x.shape
    N = Bm.shape[-1]
    assert L % chunk == 0, "seq len must be a multiple of chunk"
    nc = L // chunk
    grid = (B, H, nc)
    kernel = functools.partial(_ssd_kernel, num_chunks=nc)
    y, hT = pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, chunk, 1, P), lambda b, h, c: (b, c, h, 0)),
            pl.BlockSpec((1, chunk, 1), lambda b, h, c: (b, c, h)),
            pl.BlockSpec((1, 1), lambda b, h, c: (h, 0)),
            pl.BlockSpec((1, chunk, N), lambda b, h, c: (b, c, 0)),
            pl.BlockSpec((1, chunk, N), lambda b, h, c: (b, c, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, chunk, 1, P), lambda b, h, c: (b, c, h, 0)),
            pl.BlockSpec((1, 1, P, N), lambda b, h, c: (b, h, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, L, H, P), x.dtype),
            jax.ShapeDtypeStruct((B, H, P, N), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((P, N), jnp.float32)],
        interpret=interpret,
    )(x, dt, A.reshape(H, 1), Bm, Cm)
    return y, hT
