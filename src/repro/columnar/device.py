"""Device-offload execution for ``DEVICE`` operator stages.

A device stage accumulates columnar micro-batches until it holds a
device-sized batch, dispatches the batch to a jax/pallas kernel
*asynchronously* (jax dispatch returns before the computation finishes),
and only synchronises — ``jax.block_until_ready`` — when a result must
cross the ordered-egress boundary.  With ``device_inflight >= 2`` batches
in flight, host-side ingest/encode overlaps device compute
(double-buffering).  See ``docs/columnar.md`` for the dispatch protocol.

Everything jax lives behind function-local imports: this module imports
cleanly without jax, and :func:`resolve_backend` picks the pure-NumPy
reference backend when jax is absent (``auto``) or when the caller pins
``backend="numpy"``.  The NumPy backend evaluates the same elementwise
math eagerly so ordered egress is bit-identical between backends for
integer schemas; float results may differ in the last ulp across
backends because XLA fuses multiply-add (see ``docs/columnar.md``).

Kernels are elementwise column maps ``fn(*cols) -> cols`` registered in
:data:`KERNELS` under a name; each entry supplies a NumPy factory and a
jax factory.  ``affine_pallas`` is the pallas-backed entry — compiled by
Mosaic on TPU, run by the Pallas interpreter elsewhere
(:func:`repro.kernels.platform.pallas_call`).  Batch boundaries never
change results precisely *because* kernels are elementwise; that is what
lets the runtime flush partial batches on barriers, EOF, or upstream
stalls without forking the output.

A jax device stage computes in the dtypes its schema names: 64-bit fields
need jax's x64 mode (plan rule PV413), and a process that brings up a jax
backend on a TPU host owns the chip, so a plan may hold at most one such
process there (PV414).  Both facts are read here without initializing a
jax backend (:func:`x64_enabled`, :func:`host_has_tpu`).
"""
from __future__ import annotations

import functools
import os
import sys
import time
from collections import deque
from pathlib import Path
from typing import Any, Callable, Deque, Dict, List, Optional, Tuple

import numpy as np

from ..core import trace
from ..core.operators import DEVICE, OpSpec
from .block import ColumnBlock, Schema

Params = Tuple[Tuple[str, Any], ...]

#: the compile cache's one path when ``JAX_COMPILATION_CACHE_DIR`` is unset
#: (fixed: the path is part of the cache key, so a moving directory never hits)
CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def have_jax() -> bool:
    """True when jax is importable (cached by the import system itself)."""
    try:
        import jax  # noqa: F401
    except Exception:
        return False
    return True


def jax_fork_hazard() -> bool:
    """True when THIS process has already initialized a jax backend client.

    Forking after client initialization is unrecoverable: the child
    inherits XLA/LLVM threadpool locks whose owner threads do not exist,
    so its first jax computation deadlocks (clearing the backend registry
    in the child does not help — verified experimentally).  Merely
    *importing* jax is safe; only running a computation (or e.g.
    ``jax.random.PRNGKey``) creates the client.  The process runtime
    checks this before forking jax device workers and fails fast with
    guidance instead of hanging until the drain timeout."""
    import sys

    if "jax" not in sys.modules:
        return False
    try:
        from jax._src import xla_bridge as xb

        return bool(xb.backends_are_initialized())
    except Exception:
        return False


def host_has_tpu() -> bool:
    """Whether a jax backend brought up in this process (or a child it
    forks) would open a TPU — read from ``JAX_PLATFORMS`` (or the imported
    jax config) and the PCI bus, without initializing a backend."""
    if "jax" in sys.modules:
        import jax

        names = jax.config.jax_platforms
    else:
        names = os.environ.get("JAX_PLATFORMS")
    if names:
        return names.split(",")[0].strip() == "tpu"
    try:
        from jax._src.hardware_utils import num_available_tpu_chips_and_device_id
    except ImportError:
        return False
    return num_available_tpu_chips_and_device_id()[0] > 0


def x64_enabled() -> bool:
    """Whether jax computes 64-bit dtypes in this process (and in the
    device workers it forks, which inherit its config and environment)."""
    if "jax" in sys.modules:
        import jax

        return bool(jax.config.jax_enable_x64)
    flag = os.environ.get("JAX_ENABLE_X64", "").strip().lower()
    return flag in ("1", "true", "t", "yes", "y", "on")


def configure_compile_cache() -> str:
    """Turn on jax's persistent compile cache before the first compile and
    return its directory: ``JAX_COMPILATION_CACHE_DIR`` when set (jax reads
    it itself; no other directory is set), else :data:`CACHE_DIR`.  Every
    compile is cached, however short, so a re-forked device worker finds
    the kernels its predecessor compiled."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return path


def resolve_backend(name: Optional[str] = "auto") -> str:
    """Resolve a backend request to ``"jax"`` or ``"numpy"``.

    ``auto`` prefers jax when importable; pinning ``jax`` without jax
    installed is an error (tests use it behind ``importorskip``)."""
    if name in (None, "", "auto"):
        return "jax" if have_jax() else "numpy"
    if name == "jax":
        if not have_jax():
            raise RuntimeError(
                "device backend 'jax' requested but jax is not importable; "
                "use backend='auto' to fall back to the NumPy reference"
            )
        return "jax"
    if name == "numpy":
        return "numpy"
    raise ValueError(f"unknown device backend {name!r} (auto|jax|numpy)")


# --------------------------------------------------------------- kernels
def _np_affine(params: Params) -> Callable[..., tuple]:
    kw = dict(params)
    a, b = kw.get("a", 1), kw.get("b", 0)

    def fn(*cols):
        return tuple(np.asarray(c * a + b, dtype=c.dtype) for c in cols)

    return fn


def _jax_affine(params: Params) -> Callable[..., tuple]:
    kw = dict(params)
    a, b = kw.get("a", 1), kw.get("b", 0)

    def fn(*cols):
        return tuple(c * a + b for c in cols)

    return fn


def _np_square(params: Params) -> Callable[..., tuple]:
    def fn(*cols):
        return tuple(np.asarray(c * c, dtype=c.dtype) for c in cols)

    return fn


def _jax_square(params: Params) -> Callable[..., tuple]:
    def fn(*cols):
        return tuple(c * c for c in cols)

    return fn


_LANES = 128  # a column is laid out lane-dense as (rows, 128)
_BLOCK_ROWS = 512  # rows per grid step: a (512, 128) 32-bit tile is 256 KiB


def _pallas_affine_body(x_ref, o_ref, *, a, b):
    o_ref[...] = x_ref[...] * a + b


def affine_pallas(col, a, b):
    """``col * a + b`` over a 1-D column as a gridded Pallas kernel.

    The column is padded to whole lanes and viewed as ``(rows, 128)``; the
    grid walks ``(512, 128)`` tiles, so VMEM holds a few tiles whatever the
    column length.  A column of at most 512 rows is one full-array block."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    from ..kernels.platform import pallas_call

    n = col.shape[0]
    rows = -(-n // _LANES)
    block = min(rows, _BLOCK_ROWS)
    rows = -(-rows // block) * block
    x = col
    if rows * _LANES != n:
        x = jnp.pad(col, (0, rows * _LANES - n))
    x = x.reshape(rows, _LANES)
    spec = pl.BlockSpec((block, _LANES), lambda i: (i, 0))
    out = pallas_call(
        functools.partial(_pallas_affine_body, a=a, b=b),
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        grid=(rows // block,),
        in_specs=[spec],
        out_specs=spec,
    )(x)
    return out.reshape(-1)[:n]


def _jax_affine_pallas(params: Params) -> Callable[..., tuple]:
    kw = dict(params)
    a, b = kw.get("a", 1), kw.get("b", 0)

    def fn(*cols):
        return tuple(affine_pallas(c, a, b) for c in cols)

    return fn


#: kernel name -> (numpy factory, jax factory); factories take the frozen
#: params tuple and return an elementwise column map ``fn(*cols) -> cols``.
KERNELS = {
    "affine": (_np_affine, _jax_affine),
    "square": (_np_square, _jax_square),
    "affine_pallas": (_np_affine, _jax_affine_pallas),
}


def make_kernel(
    kernel: str, backend: str, params: Params = ()
) -> Callable[..., tuple]:
    """Instantiate a registered kernel for a resolved backend."""
    try:
        np_factory, jax_factory = KERNELS[kernel]
    except KeyError:
        raise ValueError(
            f"unknown device kernel {kernel!r} (registered: {sorted(KERNELS)})"
        ) from None
    return jax_factory(params) if backend == "jax" else np_factory(params)


@functools.lru_cache(maxsize=None)
def _ref_kernel(kernel: str, params: Params) -> Callable[..., tuple]:
    return make_kernel(kernel, "numpy", params)


def ref_apply(value, kernel: str, params: Params, schema: Schema) -> list:
    """Per-value NumPy reference apply — the ``OpSpec.fn`` of a device op.

    This is what the thread backend, cost calibration, and correctness
    tests run; the batched device path must match it (bit-exactly for
    integer schemas)."""
    block = ColumnBlock.from_values([value], schema=schema)
    if block is None:
        raise TypeError(
            f"device-op input {value!r} does not fit schema {schema}"
        )
    outs = _ref_kernel(kernel, params)(*block.columns)
    return ColumnBlock.from_columns(schema, list(outs)).to_values()


def device_op(
    name: str,
    kernel: str,
    schema: Schema,
    *,
    params: Optional[dict] = None,
    device_batch: int = 0,
    backend: str = "auto",
    cost_us: float = 1.0,
) -> OpSpec:
    """Build a ``DEVICE``-kind :class:`OpSpec`.

    ``device_batch=0`` defers to the runtime's ``device_batch`` knob.
    The spec's ``fn`` is the NumPy reference (:func:`ref_apply`), so the
    same spec runs unchanged on the thread backend."""
    if kernel not in KERNELS:
        raise ValueError(
            f"unknown device kernel {kernel!r} (registered: {sorted(KERNELS)})"
        )
    frozen: Params = tuple(sorted((params or {}).items()))
    return OpSpec(
        name=name,
        kind=DEVICE,
        fn=functools.partial(
            ref_apply, kernel=kernel, params=frozen, schema=schema
        ),
        cost_us=cost_us,
        schema=schema,
        device_kernel=(kernel, frozen),
        device_batch=int(device_batch),
        device_backend=backend,
    )


def dtype_groups(dtypes) -> List[Tuple[np.dtype, List[int]]]:
    """Column indices by dtype, dtypes in order of first use: the layout
    of the packed buffers, one ``(len(indices), rows)`` buffer each."""
    groups: Dict[np.dtype, List[int]] = {}
    for i, dt in enumerate(dtypes):
        groups.setdefault(np.dtype(dt), []).append(i)
    return list(groups.items())


def packed_program(fn: Callable[..., tuple], dtypes) -> Callable[..., tuple]:
    """The column map ``fn`` over packed buffers, for ``jax.jit``: the
    buffers (laid out by :func:`dtype_groups`) are taken apart into
    columns, ``fn`` is applied, and its outputs are packed the same way."""
    groups = dtype_groups(dtypes)

    def program(*bufs):
        import jax.numpy as jnp

        outs = fn(*unpack(bufs, groups, len(dtypes)))
        # before packing, which would promote a stray dtype silently
        _check_dtypes(outs, dtypes)
        return tuple(jnp.stack([outs[i] for i in idx]) for _, idx in groups)

    return program


def unpack(bufs, groups, width: int, stop: Optional[int] = None) -> list:
    """The ``width`` columns held in packed ``bufs`` laid out by ``groups``
    (:func:`dtype_groups`), each cut to its first ``stop`` rows."""
    cols: list = [None] * width
    for buf, (_, idx) in zip(bufs, groups):
        for j, i in enumerate(idx):
            cols[i] = buf[j, :stop]
    return cols


def _check_dtypes(cols, dtypes) -> None:
    for c, dt in zip(cols, dtypes):
        if c.dtype != dt:
            raise TypeError(
                f"device kernel returned {c.dtype} for a {dt} column; "
                "a device stage computes in its schema's dtypes"
            )


class DeviceExecutor:
    """Double-buffered batch executor behind a device-stage worker.

    ``submit`` absorbs per-unit :class:`ColumnBlock`\\ s; once accumulated
    rows reach ``batch`` the pending blocks are concatenated and
    dispatched.  Up to ``inflight`` dispatched batches ride concurrently;
    submitting past the window synchronises on the *oldest* batch only,
    so with jax the newest dispatch overlaps both host ingest and the
    older batches still computing.  Completed batches are split back into
    the original per-unit blocks — serials and marks untouched — so the
    caller publishes each unit exactly as it arrived (the replay-identity
    requirement: re-fed units re-derive identical publishes regardless of
    how device batches regrouped them).

    A dispatch never holds more than ``batch`` rows unless one unit alone
    does.  The jax backend pads every dispatch to a whole number of
    batches, so a stream compiles the kernel once, at construction,
    however its partial flushes fall.  ``device`` describes where the jax
    backend came up: ``platform``, ``kind`` and device ``count`` as jax
    reports them, with the seconds spent tracing and lowering
    (``lower_s``) and compiling (``compile_s``, what the persistent cache
    saves) over ``compiles`` shapes; ``None`` on the NumPy backend.

    On the jax backend the columns travel packed: one ``(columns, rows)``
    buffer per dtype of the schema, so a dispatch makes one host-to-device
    transfer and one read-back per dtype, whatever the column count.  The
    compiled program takes the packed buffers apart, calls the kernel on
    the columns, and packs its outputs the same way; ``device`` counts the
    transfers (``h2d_transfers``, ``d2h_transfers``).

    Each submitted block is stamped (``held_since``) and the stamp rides
    its unit back out, so the caller can tell how long a unit was held.  A
    jax executor arms :func:`repro.core.trace.span` in its process: each
    dispatch records ``stream.device.dispatch`` (the zero-padded packed
    buffers up, and the launch) and each synchronisation
    ``stream.device.sync`` (the wait and the packed buffers back down)
    where a profiler runs."""

    def __init__(
        self,
        spec: OpSpec,
        batch: int = 256,
        inflight: int = 2,
        backend: str = "auto",
    ):
        if spec.kind != DEVICE or spec.device_kernel is None:
            raise ValueError(f"op {spec.name!r} is not a device op")
        kernel, params = spec.device_kernel
        self.schema: Schema = spec.schema
        self.batch = max(int(spec.device_batch or batch), 1)
        self.inflight_limit = max(int(inflight), 1)
        self.backend = resolve_backend(spec.device_backend or backend)
        self._fn = make_kernel(kernel, self.backend, params)
        self.device: Optional[Dict[str, Any]] = None
        self._executables: Dict[int, Any] = {}
        self._groups = dtype_groups(self.schema.dtypes)
        if self.backend == "jax":
            import jax

            self.device = self._bring_up(spec.name)
            self._packed = jax.jit(
                packed_program(self._fn, self.schema.dtypes))
            self._executable(self.batch)
            trace.arm(jax.profiler.TraceAnnotation)
        self._pending: List[ColumnBlock] = []
        self._pending_rows = 0
        self._inflight: Deque[Tuple[Any, list, int]] = deque()
        #: dispatched batch count (observability)
        self.dispatches = 0

    def _bring_up(self, name: str) -> Dict[str, Any]:
        import jax

        wide = [c for c, dt in zip(self.schema.codes, self.schema.dtypes)
                if dt.itemsize == 8]
        if wide and not jax.config.jax_enable_x64:
            raise ValueError(
                f"device op {name!r} declares 64-bit fields {wide} but jax "
                "computes in 32 bits here (x64 off); declare i4/f4 fields "
                "or set JAX_ENABLE_X64=1"
            )
        try:
            devices = jax.devices()
        except RuntimeError as exc:
            raise RuntimeError(
                f"device op {name!r}: the jax backend did not come up "
                f"({exc}); is the chip held by another process?"
            ) from exc
        if devices[0].platform != "cpu":
            # before the first compile; XLA:CPU cache hits save little and
            # warn about host features, so CPU runs leave the cache off
            configure_compile_cache()
        return {
            "platform": devices[0].platform,
            "kind": devices[0].device_kind,
            "count": len(devices),
            "lower_s": 0.0,
            "compile_s": 0.0,
            "compiles": 0,
            "h2d_transfers": 0,
            "d2h_transfers": 0,
        }

    def _executable(self, rows: int):
        """The packed program compiled for ``rows``-row columns (compiled
        once per shape, timed into ``device``)."""
        exe = self._executables.get(rows)
        if exe is None:
            import jax

            t0 = time.perf_counter()
            lowered = self._packed.lower(*(
                jax.ShapeDtypeStruct((len(idx), rows), dt)
                for dt, idx in self._groups
            ))
            t1 = time.perf_counter()
            exe = lowered.compile()
            self.device["lower_s"] += t1 - t0
            self.device["compile_s"] += time.perf_counter() - t1
            self.device["compiles"] += 1
            self._executables[rows] = exe
        return exe

    @property
    def pending_rows(self) -> int:
        """Rows accumulated but not yet dispatched."""
        return self._pending_rows

    @property
    def inflight(self) -> int:
        """Dispatched batches not yet synchronised."""
        return len(self._inflight)

    def submit(self, block: ColumnBlock) -> List[ColumnBlock]:
        """Absorb one unit's block; returns any units whose batches
        completed (possibly none, never blocks unless the window is full)."""
        block.held_since = time.perf_counter_ns()
        if self._pending and self._pending_rows + len(block) > self.batch:
            self._dispatch()
        self._pending.append(block)
        self._pending_rows += len(block)
        if self._pending_rows >= self.batch:
            self._dispatch()
        ready: List[ColumnBlock] = []
        while len(self._inflight) > self.inflight_limit:
            ready.extend(self._pop())
        return ready

    def flush(self) -> List[ColumnBlock]:
        """Dispatch any partial batch and synchronise everything in
        flight (barrier / EOF / upstream-stall path)."""
        if self._pending:
            self._dispatch()
        out: List[ColumnBlock] = []
        while self._inflight:
            out.extend(self._pop())
        return out

    def _dispatch(self) -> None:
        blocks = self._pending
        n = self._pending_rows
        units = [(b.serials, b.marks, b.held_since) for b in blocks]
        self._pending = []
        self._pending_rows = 0
        with trace.span(trace.DEVICE_DISPATCH):
            if self.backend == "jax":
                rows = -(-n // self.batch) * self.batch
                bufs = []
                for dt, idx in self._groups:
                    # fresh buffer, zero-padded to the compiled shape: safe
                    # to alias zero-copy, the host never mutates it after
                    # dispatch
                    buf = np.zeros((len(idx), rows), dt)
                    for j, i in enumerate(idx):
                        np.concatenate([b.columns[i] for b in blocks],
                                       out=buf[j, :n])
                    bufs.append(buf)
                outs = self._executable(rows)(*bufs)
                self.device["h2d_transfers"] += len(bufs)
            else:
                outs = self._fn(*ColumnBlock.concat(blocks).columns)
        self.dispatches += 1
        self._inflight.append((outs, units, n))

    def _pop(self) -> List[ColumnBlock]:
        outs, units, n = self._inflight.popleft()
        with trace.span(trace.DEVICE_SYNC):
            if self.backend == "jax":
                import jax

                packed = [np.asarray(o) for o in jax.block_until_ready(outs)]
                self.device["d2h_transfers"] += len(packed)
                cols = unpack(packed, self._groups, len(self.schema.dtypes),
                              n)
            else:
                cols = [np.asarray(o)[:n] for o in outs]
        _check_dtypes(cols, self.schema.dtypes)
        blocks: List[ColumnBlock] = []
        off = 0
        for serials, marks, held_since in units:
            n = len(serials)
            blocks.append(
                ColumnBlock(
                    self.schema,
                    [c[off : off + n] for c in cols],
                    serials,
                    list(marks),
                    held_since,
                )
            )
            off += n
        return blocks
