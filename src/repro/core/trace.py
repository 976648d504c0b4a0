"""Named host spans on the profiler's clock.

:func:`span` returns a context manager that records one host event under
its name while a profiler runs in this process, and a shared no-op
otherwise.  It records nothing until :func:`arm` hands it an annotation
factory: the device stage's executor arms it with
``jax.profiler.TraceAnnotation`` once its jax backend is up.  A profiler
running in that process then finds the spans on the process's host plane,
on the same clock as the device's operations.

This module imports no jax, so a stage worker that never holds the chip
never imports jax because of it.  Spans go per unit or per dispatch, never
per row or per column; their names are fixed (``DEVICE_SPANS``).
"""
from __future__ import annotations

import contextlib
from typing import Callable, Optional

DEVICE_WAIT = "stream.device.wait"  # an empty input ring until the next record
DEVICE_DECODE = "stream.device.decode"  # one unit, off the wire into columns
DEVICE_DISPATCH = "stream.device.dispatch"  # padded packed buffers up, launch
DEVICE_SYNC = "stream.device.sync"  # wait for a dispatch, packed buffers down
DEVICE_PUBLISH = "stream.device.publish"  # one unit, encoded and published
DEVICE_SPANS = (DEVICE_WAIT, DEVICE_DECODE, DEVICE_DISPATCH, DEVICE_SYNC,
                DEVICE_PUBLISH)

_NULL = contextlib.nullcontext()
_factory: Optional[Callable] = None


def arm(factory: Optional[Callable]) -> None:
    """Make :func:`span` return ``factory(name)`` from now on in this
    process (``None`` disarms it)."""
    global _factory
    _factory = factory


def span(name: str):
    """A context manager recording one host event ``name`` where armed;
    the shared no-op context elsewhere."""
    return _NULL if _factory is None else _factory(name)
