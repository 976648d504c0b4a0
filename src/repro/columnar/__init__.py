"""Columnar zero-copy batch subsystem + device-offload execution.

Micro-batches of fixed-width numeric tuples travel between stages as
:class:`ColumnBlock`\\ s — NumPy column vectors with per-row serials and a
ragged marker sidecar — written straight into shm ring span slots
(``TAG_COLBLOCK``) instead of round-tripping through pickle.  On top of
the block layer, ``DEVICE``-kind operators batch blocks up to device size
and dispatch them asynchronously to jax/pallas kernels with a pure-NumPy
reference backend.  See ``docs/columnar.md``.

Submodules import lazily (PEP 562, same pattern as :mod:`repro.serve`) so
``import repro.columnar`` costs nothing until a symbol is touched, and
nothing here ever imports jax at module scope — jax stays strictly
optional.
"""
from __future__ import annotations

_LAZY = {
    "Schema": ".block",
    "ColumnBlock": ".block",
    "DTYPES": ".block",
    "ColumnarCodec": ".codec",
    "encode_block": ".codec",
    "decode_block": ".codec",
    "DeviceExecutor": ".device",
    "device_op": ".device",
    "ref_apply": ".device",
    "make_kernel": ".device",
    "resolve_backend": ".device",
    "have_jax": ".device",
    "jax_fork_hazard": ".device",
    "host_has_tpu": ".device",
    "x64_enabled": ".device",
    "configure_compile_cache": ".device",
    "KERNELS": ".device",
}

__all__ = sorted(_LAZY)


def __getattr__(name):
    if name in _LAZY:
        import importlib

        mod = importlib.import_module(_LAZY[name], __name__)
        value = getattr(mod, name)
        globals()[name] = value
        return value
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")


def __dir__():
    return sorted(set(globals()) | set(__all__))
