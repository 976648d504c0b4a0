"""Columnar subsystem battery: Schema/ColumnBlock units, wire-codec
round-trips (TAG_COLBLOCK and the widened TAG_TUPS raw path), the
columnar-vs-pickle exact-equality matrix through real process pipelines,
and the DeviceOp ordered-egress bit-identity contract against the
pure-NumPy reference (integer schemas, so jax and NumPy agree bitwise —
see docs/columnar.md for why float columns only agree to the last ulp).
"""
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # offline env: degrade to seeded randomized sampling
    from _hypothesis_compat import given, settings, strategies as st

from repro.core import Engine, EngineConfig, OpSpec, ProcessOptions
from repro.core import shm
from repro.columnar import (
    ColumnBlock,
    ColumnarCodec,
    DeviceExecutor,
    Schema,
    decode_block,
    device_op,
    encode_block,
    have_jax,
    ref_apply,
)


# ---------------------------------------------------------------- operators
def _ident(v):
    return [v]


def _widen(v):
    return [(v, v * 3, float(v) * 0.5)]


def _tup_map(t):
    return [(t[0] * 2 + 1, t[1] - 7, t[2] + 0.25)]


def _narrow(t):
    return [t[0] + t[1]]


def _mod5(t):
    return t[0] % 5


def _zero():
    return 0


def _ksum(s, k, t):
    s += t[0]
    return s, [(s, t[1], t[2])]


# ------------------------------------------------------------- schema units
def test_schema_infer_and_width_rules():
    assert Schema.infer(3) == Schema((("c0", "i8"),), scalar=True)
    assert Schema.infer(0.5) == Schema((("c0", "f8"),), scalar=True)
    assert Schema.infer((1, 2.0)) == Schema.of("i8", "f8")
    # bools, ragged, and object cells are non-columnar by design
    assert Schema.infer(True) is None
    assert Schema.infer((1, True)) is None
    assert Schema.infer("x") is None
    assert Schema.infer(()) is None
    assert Schema.of("i8", "f8").row_bytes == 16
    assert Schema.of("i4", "f4").row_bytes == 8
    with pytest.raises(ValueError):
        Schema.of("i8", "i8", scalar=True)  # scalar schemas are width 1
    with pytest.raises(ValueError):
        Schema.of("u2")  # unknown code


def test_block_round_trip_and_slicing():
    vals = [(i, i * 3, i + 0.5) for i in range(10)]
    marks = [(0, "m0"), (7, "m7")]
    blk = ColumnBlock.from_values(vals, head_serial=100, marks=marks)
    assert blk is not None and len(blk) == 10
    assert blk.head_serial == 100 and blk.contiguous_serials()
    assert blk.to_values() == vals

    # wire round-trip preserves rows, serials, marks
    rt = decode_block(encode_block(blk))
    assert rt.to_values() == vals
    assert rt.head_serial == 100 and rt.contiguous_serials()
    assert rt.marks == marks

    # slicing is zero-copy and re-offsets marks
    sl = blk.slice(5, 9)
    assert sl.to_values() == vals[5:9]
    assert sl.head_serial == 105
    assert sl.marks == [(2, "m7")]
    assert sl.columns[0].base is not None  # a view, not a copy

    # non-contiguous serials survive the wire (explicit-serials flag)
    gap = ColumnBlock.concat([blk.slice(0, 2), blk.slice(6, 8)])
    assert not gap.contiguous_serials()
    rt2 = decode_block(encode_block(gap))
    assert rt2.to_values() == vals[0:2] + vals[6:8]
    assert list(rt2.serials) == [100, 101, 106, 107]


def test_block_builder_rejects_nonconforming_rows():
    assert ColumnBlock.from_values([]) is None
    assert ColumnBlock.from_values([(1, 2), (1, 2, 3)]) is None  # ragged
    assert ColumnBlock.from_values([(1, 2), (1, "x")]) is None  # object cell
    assert ColumnBlock.from_values([1, 2.0]) is None  # mixed scalar types
    assert ColumnBlock.from_values([(1, True)]) is None  # bool is not int
    # i8 overflow falls back rather than wrapping silently
    assert ColumnBlock.from_values([(1 << 70,)]) is None


def test_codec_locks_schema_and_counts_fallbacks():
    codec = ColumnarCodec()
    enc = codec.try_encode_unit([(1, 2.0), (3, 4.0)], [], 1)
    assert enc is not None and codec.schema == Schema.of("i8", "f8")
    # later units must conform to the locked schema
    assert codec.try_encode_unit([(1, 2)], [], 3) is None
    assert codec.fallbacks == 1
    payload, span = enc
    assert span == 2
    assert decode_block(payload).to_values() == [(1, 2.0), (3, 4.0)]


# ----------------------------------------------------- TAG_TUPS raw fast path
@settings(max_examples=40, deadline=None)
@given(
    rows=st.lists(
        st.tuples(
            st.integers(min_value=-(2 ** 62), max_value=2 ** 62),
            st.floats(min_value=-1e9, max_value=1e9),
            st.integers(min_value=-5, max_value=5),
        ),
        min_size=1,
        max_size=40,
    )
)
def test_tups_raw_path_round_trips_exactly(rows):
    """Homogeneous small int/float tuples take the raw struct path and
    round-trip bit-exactly (the widened shm fast-path satellite)."""
    tag, data = shm.encode_bundle(rows)
    assert tag == shm.TAG_TUPS
    assert shm.decode_bundle(tag, data) == rows


def test_tups_fallback_cases_stay_pickle():
    # bool column, ragged rows, oversize ints, wide tuples -> pickle
    for outs in (
        [(1, True)],
        [(1, 2), (3,)],
        [(1 << 70, 2)],
        [tuple(range(17))],
    ):
        tag, _ = shm.encode_bundle(outs)
        assert tag == shm.TAG_PICKLE
    # and decode still inverts whatever encode chose
    for outs in ([(1, 2.5)], [(7,), (8,)], [("a", 1)]):
        tag, data = shm.encode_bundle(outs)
        assert shm.decode_bundle(tag, data) == outs


# ----------------------------------------- columnar-vs-pickle equality matrix
def _chain():
    """Numeric chain with a keyed interior stage: scalar -> wide tuple ->
    tuple map -> keyed running sum -> narrow."""
    return [
        OpSpec("widen", "stateless", _widen, cost_us=2.0),
        OpSpec("tmap", "stateless", _tup_map, cost_us=2.0),
        OpSpec("ksum", "partitioned", _ksum, key_fn=_mod5,
               num_partitions=10, init_state=_zero, cost_us=2.0),
        OpSpec("narrow", "stateless", _narrow, cost_us=2.0),
    ]


def _run_process(columnar: bool, batch_size: int, source):
    eng = Engine(EngineConfig(
        backend="process", num_workers=2, batch_size=batch_size,
        collect_outputs=True,
        process=ProcessOptions(columnar=columnar),
    ))
    return eng.run(list(_chain()), source).handle().outputs


@pytest.mark.timeout(90)
@pytest.mark.parametrize("batch_size", [1, 7, 32])
def test_columnar_egress_equals_pickle_egress(batch_size):
    """The columnar wire path is invisible: exact equality (content AND
    order) with the pickle path across micro-batch sizes, through a chain
    with a keyed stage (keyed dispatch always falls back to pickle — the
    block path must compose with it, not replace it)."""
    source = list(range(201))
    base = _run_process(False, batch_size, source)
    col = _run_process(True, batch_size, source)
    assert col == base
    # and both equal the thread backend's reference egress
    eng = Engine(EngineConfig(backend="thread", num_workers=2,
                              batch_size=batch_size, collect_outputs=True))
    ref = eng.run(list(_chain()), source).handle().outputs
    assert col == ref


# ------------------------------------------- device ordered-egress property
def _device_chain(backend: str, kernel: str = "affine"):
    return [
        OpSpec("widen2", "stateless", _pair, cost_us=1.0),
        device_op("dev", kernel, Schema.of("i4", "i4"),
                  params={"a": 3, "b": -1}, backend=backend, cost_us=4.0),
        OpSpec("fold", "stateless", _fold, cost_us=1.0),
    ]


def _pair(v):
    return [(v, v * 2)]


def _fold(t):
    return [t[0] - t[1]]


def _device_reference(source):
    out = []
    for v in source:
        (t,) = _pair(v)
        (r,) = ref_apply(t, "affine", (("a", 3), ("b", -1)),
                         Schema.of("i4", "i4"))
        out.extend(_fold(r))
    return out


@pytest.mark.timeout(120)
@pytest.mark.parametrize("batch_size", [1, 7, 32])
@pytest.mark.parametrize("backend", ["numpy", "jax"])
def test_device_egress_bit_identical_to_reference(backend, batch_size):
    """Device-stage egress is exactly ordered and bit-identical to the
    per-value NumPy reference, for both kernel backends, regardless of how
    device batches regroup dispatch units (integer schema: jax int math is
    exact, so cross-backend equality is bitwise)."""
    if backend == "jax" and not have_jax():
        pytest.skip("jax not installed; numpy reference backend still covers "
                    "the device path")
    source = list(range(157))
    eng = Engine(EngineConfig(
        backend="process", num_workers=2, batch_size=batch_size,
        collect_outputs=True,
        process=ProcessOptions(columnar=True, device_batch=64,
                               device_backend=backend),
    ))
    out = eng.run(list(_device_chain(backend)), source).handle().outputs
    assert out == _device_reference(source)


@pytest.mark.timeout(120)
def test_device_pallas_kernel_matches_reference_end_to_end():
    """The pallas-lowered kernel (interpret mode) is egress-identical to
    the NumPy reference through a real process pipeline."""
    if not have_jax():
        pytest.skip("jax not installed; pallas kernels need jax")
    source = list(range(100))
    eng = Engine(EngineConfig(
        backend="process", num_workers=2, batch_size=16,
        collect_outputs=True,
        process=ProcessOptions(columnar=True, device_batch=32,
                               device_backend="jax"),
    ))
    out = eng.run(
        list(_device_chain("jax", kernel="affine_pallas")), source
    ).handle().outputs
    assert out == _device_reference(source)


@settings(max_examples=15, deadline=None)
@given(
    sizes=st.lists(st.integers(min_value=1, max_value=9), min_size=1,
                   max_size=20),
    batch=st.integers(min_value=1, max_value=16),
)
def test_device_executor_preserves_unit_boundaries(sizes, batch):
    """DeviceExecutor splits completed batches back into the exact submitted
    units — serials and marks untouched — however units regroup into
    device batches."""
    spec = device_op("dev", "affine", Schema.of("i8", scalar=True),
                     params={"a": 2, "b": 1}, backend="numpy")
    ex = DeviceExecutor(spec, batch=batch, inflight=2)
    serial = 1
    submitted = []
    outs = []
    for n in sizes:
        vals = list(range(serial, serial + n))
        marks = [(0, f"mark{serial}")]
        blk = ColumnBlock.from_values(vals, head_serial=serial, marks=marks,
                                      schema=spec.schema)
        submitted.append((serial, vals, marks))
        outs.extend(ex.submit(blk))
        serial += n
    outs.extend(ex.flush())
    assert ex.pending_rows == 0 and ex.inflight == 0
    assert len(outs) == len(submitted)
    for blk, (head, vals, marks) in zip(outs, submitted):
        assert blk.head_serial == head and blk.contiguous_serials()
        assert blk.to_values() == [v * 2 + 1 for v in vals]
        assert blk.marks == marks


def _drive_executor(schema, kernel, backend, sizes, batch, row):
    """Units of ``sizes`` rows (``row(v)`` each) through a DeviceExecutor;
    returns the executor and each returned unit's serials, marks and
    column bytes."""
    spec = device_op("dev", kernel, schema, params={"a": 3, "b": -7},
                     backend=backend)
    ex = DeviceExecutor(spec, batch=batch, inflight=2)
    serial, outs = 1, []
    for n in sizes:
        vals = [row(v) for v in range(serial, serial + n)]
        blk = ColumnBlock.from_values(vals, head_serial=serial,
                                      marks=[(0, f"mark{serial}")],
                                      schema=schema)
        outs.extend(ex.submit(blk))
        serial += n
    outs.extend(ex.flush())
    assert ex.pending_rows == 0 and ex.inflight == 0
    return ex, [(b.serials.tolist(), b.marks,
                 [c.tobytes() for c in b.columns]) for b in outs]


@settings(max_examples=15, deadline=None)
@given(
    sizes=st.lists(st.integers(min_value=1, max_value=9), min_size=1,
                   max_size=20),
    batch=st.integers(min_value=1, max_value=16),
)
def _packed_executor_preserves_unit_boundaries(sizes, batch):
    schema = Schema.of("i4", "i4", "i4")

    def row(v):
        return (v, -2 * v, v * v)

    _, want = _drive_executor(schema, "affine", "numpy", sizes, batch, row)
    ex, got = _drive_executor(schema, "affine", "jax", sizes, batch, row)
    assert got == want
    heads = [1 + sum(sizes[:k]) for k in range(len(sizes))]
    assert [s for s, _, _ in got] == [
        list(range(h, h + n)) for h, n in zip(heads, sizes)]
    assert ex.device["h2d_transfers"] == ex.device["d2h_transfers"] \
        == ex.dispatches > 0


def _packed_executor_mixed_dtypes():
    schema = Schema.of("i4", "f4", "i4", "f4")

    def row(v):
        return (v, v * 0.37, -v, v / 7)

    sizes = [3, 7, 16, 1, 9, 30, 2]
    _, want = _drive_executor(schema, "square", "numpy", sizes, 16, row)
    ex, got = _drive_executor(schema, "square", "jax", sizes, 16, row)
    assert got == want
    assert ex.device["h2d_transfers"] == ex.device["d2h_transfers"] \
        == 2 * ex.dispatches > 0


def _in_fresh_interpreter(check) -> None:
    """Run ``check``, a function of this module, in a new interpreter: a
    jax backend brought up in the test process would make every jax device
    worker it later forks refuse to start (``jax_fork_hazard``)."""
    import os
    import subprocess
    import sys

    tests = os.path.dirname(os.path.abspath(__file__))
    src = os.path.join(os.path.dirname(tests), "src")
    proc = subprocess.run(
        [sys.executable, "-c",
         f"import test_columnar as t; t.{check.__name__}()"],
        capture_output=True, text=True, timeout=110, cwd=tests,
        env={**os.environ, "PYTHONPATH": os.pathsep.join([src, tests])},
    )
    assert proc.returncode == 0, proc.stderr[-3000:]


@pytest.mark.timeout(120)
def test_jax_device_executor_packs_and_preserves_unit_boundaries():
    """On the jax backend the columns travel packed, one transfer each way
    per dispatch for a one-dtype schema, and units still come back whole
    and in order, with serials, marks and values identical to the NumPy
    backend's, however units regroup into device batches."""
    if not have_jax():
        pytest.skip("jax not installed; the packed path needs jax")
    _in_fresh_interpreter(_packed_executor_preserves_unit_boundaries)


@pytest.mark.timeout(120)
def test_jax_device_executor_mixed_dtypes_match_reference():
    """A mixed i4/f4 schema packs into one buffer per dtype (two transfers
    each way per dispatch) and matches the NumPy backend bit for bit."""
    if not have_jax():
        pytest.skip("jax not installed; the packed path needs jax")
    _in_fresh_interpreter(_packed_executor_mixed_dtypes)


def test_device_op_rejects_bad_construction():
    with pytest.raises(ValueError):
        device_op("d", "no_such_kernel", Schema.of("i8"))
    with pytest.raises(ValueError):
        # device ops are 1:1 — a filtering device op would make partial-batch
        # flushes observable
        OpSpec("d", "device", _ident, selectivity=0.5,
               schema=Schema.of("i8"), device_kernel=("affine", ()))
    with pytest.raises(ValueError):
        OpSpec("d", "device", _ident)  # no kernel/schema
    with pytest.raises(TypeError):
        ref_apply("not numeric", "affine", (), Schema.of("i8", scalar=True))


@pytest.mark.timeout(120)
def test_jax_device_fork_hazard_fails_fast_not_deadlock():
    """A parent process that already initialized a jax backend cannot fork
    jax device workers — the child would deadlock on inherited XLA
    threadpool locks.  The runtime must detect this and raise immediately
    (instead of the opaque 60s drain timeout), and a jax-free parent must
    report no hazard.  Runs in a subprocess so the pytest process itself
    never initializes jax (which would poison every later test the same
    way — the original trigger was a module-level PRNGKey created at
    collection time)."""
    if not have_jax():
        pytest.skip("jax not installed; the hazard needs a jax parent")
    import os
    import subprocess
    import sys

    script = """
import time
from repro.columnar import jax_fork_hazard
assert not jax_fork_hazard(), "import-only parent must be hazard-free"
import jax
jax.random.PRNGKey(0)  # initializes the CPU client: the hazard
assert jax_fork_hazard()
from repro.core import Engine, EngineConfig, ProcessOptions
from repro.columnar import Schema, device_op
ops = [device_op("dev", "affine", Schema.of("i4", scalar=True),
                 params={"a": 2, "b": 1}, backend="jax")]
eng = Engine(EngineConfig(
    backend="process", num_workers=1, batch_size=4, collect_outputs=True,
    process=ProcessOptions(columnar=True, device_batch=8,
                           device_backend="jax"),
))
t0 = time.monotonic()
try:
    eng.run(ops, list(range(32)))
except RuntimeError as exc:
    assert "fork" in str(exc) and "numpy" in str(exc), exc
    assert time.monotonic() - t0 < 30, "guard must fire fast, not drain out"
    print("GUARDED")
else:
    raise SystemExit("expected the fork-hazard guard to raise")
"""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    proc = subprocess.run(
        [sys.executable, "-c", script], capture_output=True, text=True,
        timeout=110, cwd=repo,
        env={**os.environ, "PYTHONPATH": os.path.join(repo, "src")},
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "GUARDED" in proc.stdout
