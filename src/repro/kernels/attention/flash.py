"""Pallas TPU kernel: causal flash attention forward (GQA-aware).

Grid: (batch, q_heads, q_blocks); each program streams key blocks of the
causal prefix with the online-softmax recurrence, holding one (Bq, Dh) output
tile + (Bq,) running max/denominator in VMEM. GQA is handled by the KV
BlockSpec index map (kv head = q head // G) — no KV expansion in HBM.
Compiled for TPU, head_dim must be a multiple of 128 (the lane width).

VMEM working set per program: q (Bq,Dh) + k/v (Bk,Dh) + scores (Bq,Bk)
≈ a few hundred KB for Bq=Bk=128..512 — comfortably under the ~16MB VMEM.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ..platform import pallas_call

NEG_INF = -1e30


def _flash_kernel(
    q_ref,  # (Bq, Dh)
    k_ref,  # (S, Dh)
    v_ref,  # (S, Dh)
    o_ref,  # (Bq, Dh)
    *,
    block_q: int,
    block_k: int,
    seq_len: int,
    causal: bool,
):
    qi = pl.program_id(2)
    q = q_ref[...].astype(jnp.float32)  # (Bq, Dh)
    scale = 1.0 / jnp.sqrt(q.shape[-1]).astype(jnp.float32)
    q = q * scale

    m = jnp.full((block_q,), NEG_INF, jnp.float32)
    l = jnp.zeros((block_q,), jnp.float32)
    acc = jnp.zeros((block_q, q.shape[-1]), jnp.float32)

    q_pos = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, (block_q,), 0)

    def body(kb, carry):
        m, l, acc = carry
        rows = pl.ds(kb * block_k, block_k)
        k = k_ref[rows, :].astype(jnp.float32)
        v = v_ref[rows, :].astype(jnp.float32)
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32)  # (Bq, Bk)
        if causal:
            k_pos = kb * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_k,), 0
            )
            s = jnp.where(q_pos[:, None] >= k_pos[None, :], s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=1))
        p = jnp.exp(s - m_new[:, None])
        alpha = jnp.exp(m - m_new)
        l_new = l * alpha + jnp.sum(p, axis=1)
        acc_new = acc * alpha[:, None] + jnp.dot(
            p, v, preferred_element_type=jnp.float32
        )
        return m_new, l_new, acc_new

    upper = (
        jax.lax.div(qi * block_q + block_q + block_k - 1, block_k)
        if causal
        else seq_len // block_k
    )
    upper = jnp.minimum(upper, seq_len // block_k)
    m, l, acc = jax.lax.fori_loop(0, upper, body, (m, l, acc))

    o_ref[...] = (acc / jnp.maximum(l, 1e-20)[:, None]).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("causal", "block_q", "block_k", "interpret")
)
def flash_attention(
    q: jax.Array,  # (B, S, H, Dh)
    k: jax.Array,  # (B, S, Hkv, Dh)
    v: jax.Array,  # (B, S, Hkv, Dh)
    *,
    causal: bool = True,
    block_q: int = 128,
    block_k: int = 128,
    interpret: Optional[bool] = None,
) -> jax.Array:
    B, S, H, Dh = q.shape
    Hkv = k.shape[2]
    G = H // Hkv
    block_q = min(block_q, S)
    block_k = min(block_k, S)
    assert S % block_q == 0 and S % block_k == 0

    grid = (B, H, S // block_q)
    kernel = functools.partial(
        _flash_kernel,
        block_q=block_q,
        block_k=block_k,
        seq_len=S,
        causal=causal,
    )
    # heads are folded into the lane axis, (B, S, H * Dh): a head is then a
    # (rows, Dh) tile, which TPU tiling admits when Dh is a multiple of 128
    out = pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((None, block_q, Dh), lambda b, h, i: (b, i, h)),
            pl.BlockSpec((None, S, Dh), lambda b, h, i: (b, 0, h // G)),
            pl.BlockSpec((None, S, Dh), lambda b, h, i: (b, 0, h // G)),
        ],
        out_specs=pl.BlockSpec((None, block_q, Dh), lambda b, h, i: (b, i, h)),
        out_shape=jax.ShapeDtypeStruct((B, S, H * Dh), q.dtype),
        interpret=interpret,
    )(q.reshape(B, S, H * Dh), k.reshape(B, S, Hkv * Dh),
      v.reshape(B, S, Hkv * Dh))
    return out.reshape(B, S, H, Dh)
