"""Per-kernel tests: Pallas (interpreted off-TPU) vs pure-jnp oracle, sweeping
shapes and dtypes (deliverable c)."""
import numpy as np
import pytest

jax = pytest.importorskip(
    "jax",
    reason="pallas kernel tests need jax; the core runtime's tier-1 "
    "coverage runs without it (pure-NumPy reference backends)",
)
import jax.numpy as jnp

# ----------------------------------------------------------------- reorder
from repro.kernels.reorder import ops as reorder_ops
from repro.kernels.reorder.ref import commit_ref, init_state


@pytest.mark.parametrize("size,width", [(8, 128), (64, 128), (32, 256)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_reorder_kernel_matches_ref(size, width, dtype):
    rng = np.random.RandomState(0)
    state_k = init_state(size, width, dtype)
    state_r = init_state(size, width, dtype)
    emitted_k, emitted_r = [], []
    serial_pool = list(rng.permutation(3 * size))
    while serial_pool:
        kbatch = min(8, len(serial_pool))
        # take only serials within the ref window to respect back-pressure
        nxt = int(state_r.next)
        batch = [s for s in serial_pool if nxt <= s < nxt + size][:kbatch]
        for s in batch:
            serial_pool.remove(s)
        serials = jnp.array(batch + [-1] * (8 - len(batch)), jnp.int32)
        payloads = jnp.asarray(
            rng.randn(8, width), dtype
        )
        sk, ek, ck, ak = reorder_ops.commit(state_k, serials, payloads, use_kernel=True)
        sr, er, cr, ar = commit_ref(state_r, serials, payloads)
        assert int(ck) == int(cr)
        assert int(sk.next) == int(sr.next)
        np.testing.assert_array_equal(np.asarray(ak), np.asarray(ar))
        np.testing.assert_allclose(
            np.asarray(ek[: int(ck)], np.float32),
            np.asarray(er[: int(cr)], np.float32),
            rtol=1e-5,
        )
        state_k, state_r = sk, sr
        emitted_k.append(np.asarray(ek[: int(ck)], np.float32))
        emitted_r.append(np.asarray(er[: int(cr)], np.float32))
    # everything drained, in order
    assert int(state_r.next) == 3 * size
    assert not np.any(np.asarray(state_r.present))


def test_reorder_ref_emits_in_serial_order():
    state = init_state(16, 4)
    payload = lambda t: jnp.full((1, 4), t, jnp.float32)
    emitted_serials = []
    order = [3, 1, 0, 2, 5, 4, 7, 6, 9, 8, 11, 10, 13, 12, 15, 14]
    for t in order:
        state, em, c, acc = commit_ref(state, jnp.array([t]), payload(t))
        emitted_serials.extend(np.asarray(em[: int(c), 0], np.int32).tolist())
    assert emitted_serials == list(range(16))


# ----------------------------------------------------------------- dispatch
from repro.kernels.dispatch import ops as dispatch_ops
from repro.kernels.dispatch.ref import dispatch_ref


@pytest.mark.parametrize("T,P,C,W", [(64, 8, 16, 128), (128, 4, 8, 128), (32, 16, 4, 256)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_dispatch_kernel_matches_ref(T, P, C, W, dtype):
    rng = np.random.RandomState(1)
    ids = jnp.asarray(rng.randint(-1, P, T), jnp.int32)
    payloads = jnp.asarray(rng.randn(T, W), dtype)
    bk, ck, dk = dispatch_ops.dispatch(ids, payloads, P, C, use_kernel=True)
    br, cr, dr = dispatch_ref(ids, payloads, P, C)
    np.testing.assert_array_equal(np.asarray(ck), np.asarray(cr))
    np.testing.assert_array_equal(np.asarray(dk), np.asarray(dr))
    np.testing.assert_allclose(
        np.asarray(bk, np.float32), np.asarray(br, np.float32), rtol=1e-5, atol=1e-5
    )


def test_dispatch_preserves_arrival_order():
    """Theorem 4.1(2) vectorized: within a partition, buffer order = arrival."""
    T, P, C, W = 32, 2, 32, 4
    ids = jnp.asarray([t % P for t in range(T)], jnp.int32)
    payloads = jnp.arange(T, dtype=jnp.float32)[:, None] * jnp.ones((1, W))
    buf, counts, dest = dispatch_ops.dispatch(ids, payloads, P, C)
    for p in range(P):
        got = np.asarray(buf[p, : int(counts[p]), 0])
        expect = np.asarray([t for t in range(T) if t % P == p], np.float32)
        np.testing.assert_array_equal(got, expect)


# ----------------------------------------------------------------- attention
from repro.kernels.attention.flash import flash_attention as flash_fwd
from repro.kernels.attention.ref import attention_ref


@pytest.mark.parametrize(
    "B,S,H,Hkv,Dh", [(1, 128, 2, 2, 64), (2, 256, 4, 2, 64), (1, 256, 8, 1, 128)]
)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("causal", [True, False])
def test_flash_attention_matches_ref(B, S, H, Hkv, Dh, dtype, causal):
    keys = jax.random.split(jax.random.PRNGKey(2), 3)
    q = jax.random.normal(keys[0], (B, S, H, Dh), dtype)
    k = jax.random.normal(keys[1], (B, S, Hkv, Dh), dtype)
    v = jax.random.normal(keys[2], (B, S, Hkv, Dh), dtype)
    out = flash_fwd(q, k, v, causal=causal, block_q=128, block_k=128)
    ref = attention_ref(q, k, v, causal=causal)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32), rtol=tol, atol=tol
    )


def test_flash_attention_grad_path():
    """custom_vjp: kernel fwd + jnp bwd must be differentiable and close to
    full-jnp gradients."""
    from repro.kernels.attention.ops import flash_attention

    keys = jax.random.split(jax.random.PRNGKey(3), 3)
    B, S, H, Dh = 1, 128, 2, 64
    q = jax.random.normal(keys[0], (B, S, H, Dh))
    k = jax.random.normal(keys[1], (B, S, H, Dh))
    v = jax.random.normal(keys[2], (B, S, H, Dh))
    g1 = jax.grad(lambda q_: flash_attention(q_, k, v, True).sum())(q)
    g2 = jax.grad(lambda q_: attention_ref(q_, k, v, True).sum())(q)
    np.testing.assert_allclose(np.asarray(g1), np.asarray(g2), rtol=1e-3, atol=1e-3)


# ----------------------------------------------------------------- ssd
from repro.kernels.ssd import ops as ssd_ops
from repro.models.ssm import ssd_chunked


@pytest.mark.parametrize("B,L,H,P,N,chunk", [(1, 128, 2, 64, 128, 64), (2, 256, 4, 64, 128, 128), (1, 512, 2, 128, 64, 128)])
def test_ssd_kernel_matches_ref(B, L, H, P, N, chunk):
    keys = jax.random.split(jax.random.PRNGKey(4), 5)
    x = jax.random.normal(keys[0], (B, L, H, P))
    dt = jax.nn.softplus(jax.random.normal(keys[1], (B, L, H)))
    A = -jnp.exp(jax.random.normal(keys[2], (H,)) * 0.3)
    Bm = jax.random.normal(keys[3], (B, L, N)) * 0.3
    Cm = jax.random.normal(keys[4], (B, L, N)) * 0.3
    yk, hk = ssd_ops.ssd(x, dt, A, Bm, Cm, chunk=chunk)
    yr, hr = ssd_chunked(x, dt, A, Bm, Cm, chunk=chunk)
    np.testing.assert_allclose(np.asarray(yk), np.asarray(yr), rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(hk), np.asarray(hr), rtol=2e-4, atol=2e-4)
