"""The main path's Pallas kernels compile for a described TPU v5e.

Nothing here runs on a chip: the TPU compiler, installed with jax,
compiles for a ``v5e:2x2`` topology described in a fixture, so tiling and
VMEM refusals surface without one.  The topology is described only inside
the fixture (never at import, in a ``skipif`` or in ``parametrize``): one
process at a time may load the TPU library, and every test worker imports
this file.  The kernels choose compiled mode from the platform they are
lowered for, so each compile must contain a Mosaic ``tpu_custom_call``.
"""
import os

import pytest

jax = pytest.importorskip("jax", reason="the TPU compiler ships with jax")
import jax.numpy as jnp  # noqa: E402


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    # a compile for a described chip is written to a persistent cache but
    # cannot be read back without one: keep any cache out of these compiles
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 — any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topo.devices[0])


def _compiled_text(fn, *shapes) -> str:
    return jax.jit(fn).lower(*shapes).compile().as_text()


@pytest.mark.parametrize("rows", [65_536, 8_388_608])
def test_affine_pallas_compiles_for_v5e(one_chip, rows):
    """The stream path's device kernel at the smallest device batch the
    smoke run uses and at 8,388,608 rows, which a whole-column VMEM block
    could not hold."""
    from repro.columnar.device import affine_pallas

    col = jax.ShapeDtypeStruct((rows,), jnp.int32, sharding=one_chip)
    text = _compiled_text(lambda c: affine_pallas(c, 3, 7), col)
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("columns", [12, 24])
def test_packed_device_program_compiles_for_v5e(one_chip, columns):
    """The device stage's packed program at 256-row dispatches, for a
    12-column and a 24-column int32 schema: one ``(columns, 256)`` buffer
    each way, taken apart into one Mosaic kernel per column."""
    from repro.columnar.device import make_kernel, packed_program

    fn = make_kernel("affine_pallas", "jax", (("a", 10), ("b", 0)))
    buf = jax.ShapeDtypeStruct((columns, 256), jnp.int32, sharding=one_chip)
    text = _compiled_text(packed_program(fn, [jnp.int32] * columns), buf)
    assert text.count("tpu_custom_call") == columns


def test_flash_attention_compiles_for_v5e_at_olmo_1b_width(one_chip):
    """Flash attention at olmo-1b's head_dim 128 and 16 heads, seq 2048."""
    from repro.configs.olmo_1b import CONFIG
    from repro.kernels.attention.flash import flash_attention

    heads, head_dim = CONFIG.num_heads, CONFIG.hd
    assert head_dim == 128
    qkv = jax.ShapeDtypeStruct(
        (1, 2048, heads, head_dim), jnp.bfloat16, sharding=one_chip
    )
    text = _compiled_text(
        lambda q, k, v: flash_attention(q, k, v, causal=True), qkv, qkv, qkv
    )
    assert "tpu_custom_call" in text
