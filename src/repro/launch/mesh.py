"""Production mesh construction.

Defined as functions (never module-level constants) so importing this module
never touches jax device state. The dry-run scripts force 512 host devices
from their ``main()`` (:func:`force_host_devices`), before any backend comes
up; importing them changes nothing, so smoke tests and benches see the real
devices.
"""
from __future__ import annotations

import os

import jax
from jax.sharding import AxisType


def _mesh(shape, axes):
    # Auto axes: the model code places arrays with sharding constraints,
    # which Explicit axes (make_mesh's default) reject
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def force_host_devices(n: int = 512) -> None:
    """Give the CPU backend ``n`` devices unless ``XLA_FLAGS`` already sets a
    count.  Only takes effect before a jax backend comes up in the process."""
    flags = os.environ.get("XLA_FLAGS", "")
    if "--xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            f"{flags} --xla_force_host_platform_device_count={n}".strip()
        )


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mesh(shape, axes)


def make_test_mesh(*, multi_pod: bool = False):
    """Reduced mesh for CI on a handful of forced host devices (8)."""
    shape = (2, 2, 2) if multi_pod else (2, 4)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mesh(shape, axes)
