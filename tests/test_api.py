"""Engine / Plan / Session API surface tests (the compile→plan→execute
redesign): strict config parsing with did-you-mean hints, the legacy shims'
uniform return contract across backends, golden ``PhysicalPlan.explain()``
renderings, ``to_dict``/``from_dict`` round-trip properties, and the
streaming ``Session`` push/results ordering property on both backends.
"""
import os
import warnings

import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # offline env: degrade to seeded randomized sampling
    from _hypothesis_compat import given, settings, strategies as st

from repro.core import (
    ConfigError,
    Engine,
    EngineConfig,
    Merge,
    OpSpec,
    PhysicalPlan,
    ProcessOptions,
    Session,
    SessionStarvation,
    Split,
    ThreadOptions,
    UnstagedGraphWarning,
    run_graph,
    run_pipeline,
)

GOLDEN = os.path.join(os.path.dirname(__file__), "golden")


# ---------------------------------------------------------------- operators
def _ident(v):
    return [v]


def _double(v):
    return [v * 2]


def _drop_mod3(v):
    return [v] if v % 3 else []


def _mod8(v):
    return v % 8


def _zero():
    return 0


def _ksum(s, k, v):
    s = (s or 0) + v
    return s, [(k, s)]


def _kcount(s, k, v):
    return (s or 0) + 1, [v]


def _sf_sum(s, v):
    s += v
    return s, [s]


def _keyed_chain():
    return [
        OpSpec("pre", "stateless", _ident, cost_us=3),
        OpSpec("hot", "partitioned", _kcount, key_fn=_mod8, num_partitions=64,
               init_state=_zero, cost_us=96),
        OpSpec("post", "stateless", _ident, cost_us=3),
    ]


def _session_chain():
    return [
        OpSpec("double", "stateless", _double, cost_us=2),
        OpSpec("ksum", "partitioned", _ksum, key_fn=_mod8, num_partitions=16,
               init_state=_zero, cost_us=4),
    ]


def _session_reference(values):
    state = {}
    out = []
    for v in values:
        d = v * 2
        k = d % 8
        state[k] = state.get(k, 0) + d
        out.append((k, state[k]))
    return out


def _split_merge_graph():
    nodes = {
        "pre": OpSpec("pre", "stateless", _ident, cost_us=4),
        "split": Split("round_robin"),
        "a": OpSpec("a", "stateless", _ident, cost_us=6),
        "b": OpSpec("b", "stateless", _ident, cost_us=6),
        "merge": Merge(),
        "sf": OpSpec("sf", "stateful", _sf_sum, init_state=_zero, cost_us=2),
    }
    edges = [
        ("pre", "split"), ("split", "a"), ("split", "b"),
        ("a", "merge"), ("b", "merge"), ("merge", "sf"),
    ]
    return nodes, edges


# --------------------------------------------------------- config validation
def test_unknown_kwarg_raises_config_error_with_suggestion():
    """The satellite bugfix: a typo like worker_budgett used to be silently
    swallowed by the process backend's **_ignored; now every legacy entry
    point parses through EngineConfig and raises a structured ConfigError."""
    with pytest.raises(ConfigError, match="worker_budget"):
        run_pipeline(_session_chain(), range(10), backend="process",
                     worker_budgett=8)
    err = None
    try:
        EngineConfig.from_kwargs(worker_budgett=8, backend="process")
    except ConfigError as e:
        err = e
    assert err is not None
    assert err.key == "worker_budgett"
    assert err.suggestion == "worker_budget"
    # a ConfigError is a ValueError: legacy except-clauses keep working
    assert isinstance(err, ValueError)


def test_process_only_option_on_thread_backend_conflicts():
    with pytest.raises(ConfigError, match="process-backend-only"):
        run_pipeline(_session_chain(), range(10), stages=2)
    with pytest.raises(ConfigError, match="io_batch"):
        EngineConfig.from_kwargs(io_batch=16)  # backend defaults to thread


@pytest.mark.parametrize("kw", [
    {"backend": "volcano"},
    {"num_workers": 0},
    {"num_workers": 2.5},
    {"batch_size": 0},
    {"heuristic": "nope"},
    {"reorder_scheme": "chaotic"},
    {"worklist_scheme": "mystery"},
    {"backend": "process", "stages": 0},
    {"backend": "process", "replan_threshold": 2.0},
    {"cost_priors": {"op": "cheap"}},
])
def test_invalid_values_raise_config_error(kw):
    with pytest.raises(ConfigError):
        EngineConfig.from_kwargs(**kw)


def test_run_graph_shim_validates_too():
    nodes, edges = _split_merge_graph()
    with pytest.raises(ConfigError, match="heuristc"):
        run_graph(nodes, edges, range(10), heuristc="ct")


def test_engine_rejects_config_plus_kwargs():
    with pytest.raises(ConfigError):
        Engine(EngineConfig(), num_workers=2)


def test_flat_and_subconfig_forms_conflict():
    with pytest.raises(ConfigError):
        EngineConfig.from_kwargs(
            backend="process", io_batch=8, process=ProcessOptions(io_batch=16)
        )


def test_config_dict_round_trip():
    cfg = EngineConfig(
        backend="process", num_workers="auto", batch_size=16,
        cost_priors={"hot": 12.5},
        thread=ThreadOptions(heuristic="lp"),
        process=ProcessOptions(worker_budget=3, stages=2),
    )
    d = cfg.to_dict()
    assert EngineConfig.from_dict(d).to_dict() == d


# --------------------------------------------------- legacy return contract
@pytest.mark.timeout(60)
def test_shim_return_contract_parity_across_backends():
    """run_pipeline(backend='process') used to return the runtime where the
    thread path returned a pipeline; both now return a JobResult-backed
    proxy with an identical documented surface."""
    src = list(range(1, 400))
    handles = {}
    for backend in ("thread", "process"):
        with pytest.warns(DeprecationWarning):
            handle, report = run_pipeline(
                _session_chain(), src, num_workers=2, backend=backend,
                collect_outputs=True,
            )
        handles[backend] = handle
        assert report.tuples_in == len(src)
    expected = _session_reference(src)
    for backend, handle in handles.items():
        assert type(handle).__name__ == "JobHandle"
        assert handle.outputs == expected, backend
        assert handle.egress_count == len(expected)
        assert isinstance(handle.markers, list) and handle.markers
        assert handle.result.plan.backend == backend
    # backend-specific introspection still passes through
    assert handles["process"].num_stages >= 1
    assert isinstance(handles["process"].stage_widths(), list)
    assert handles["thread"].specs[0].name == "double"


# ----------------------------------------------------------- golden explain
def _read_golden(name):
    with open(os.path.join(GOLDEN, name)) as f:
        return f.read().rstrip("\n")


def test_explain_golden_keyed_chain():
    eng = Engine(EngineConfig(
        backend="process", num_workers="auto", batch_size=32,
        process=ProcessOptions(worker_budget=5),
    ))
    plan = eng.plan(_keyed_chain())
    assert plan.explain() == _read_golden("plan_keyed_chain.txt")
    # widths came from the cost model: the hot keyed stage got the budget
    assert plan.stage_widths() == [1, 4]


def test_explain_golden_split_merge_dag_with_unstaged_tail():
    nodes, edges = _split_merge_graph()
    eng = Engine(EngineConfig(backend="process", num_workers=2))
    with pytest.warns(UnstagedGraphWarning):
        plan = eng.plan((nodes, edges))
    assert plan.explain() == _read_golden("plan_split_merge_dag.txt")
    assert plan.unstaged == ["a", "b", "merge", "sf", "split"]
    assert plan.routing == ["split", "merge"]


def _device_chain():
    from repro.columnar import Schema, device_op

    return [
        OpSpec("pre", "stateless", _ident, cost_us=3.0),
        device_op("affine", "affine", Schema.of("i4", scalar=True),
                  params={"a": 3, "b": 1}, cost_us=20.0),
        OpSpec("post", "stateless", _ident, cost_us=3.0),
    ]


def test_explain_golden_device_chain():
    """A columnar device chain renders the device stage, the columnar knob
    line, and the PV41x-verified footer deterministically."""
    eng = Engine(EngineConfig(
        backend="process", num_workers=2, batch_size=32,
        process=ProcessOptions(worker_budget=4, columnar=True,
                               device_batch=128),
    ))
    plan = eng.plan(_device_chain())
    assert plan.explain() == _read_golden("plan_device_chain.txt")
    # device stage is width-pinned (no elastic headroom) and checkpointed
    dev = [s for s in plan.stages if s.kind == "device"]
    assert len(dev) == 1 and dev[0].workers == dev[0].max_workers == 1
    assert dev[0].checkpointed
    # the device op row carries its declared schema
    assert [op.schema for op in plan.ops] == [None, ["i4"], None]


def test_device_plan_dict_round_trip_preserves_verification():
    eng = Engine(EngineConfig(
        backend="process", num_workers=2,
        process=ProcessOptions(worker_budget=4, columnar=True),
    ))
    plan = eng.plan(_device_chain())
    clone = PhysicalPlan.from_dict(plan.to_dict())
    assert clone.explain() == plan.explain()
    assert clone.verify(raise_on_violation=False) == []
    # degrade the clone: widen the device stage past its pin -> PV410
    dev = [s for s in clone.stages if s.kind == "device"][0]
    dev.workers = 3
    rules = {v.rule for v in clone.verify(raise_on_violation=False)}
    assert "PV410" in rules
    # degrade the ring: device batch below a dispatch unit -> PV411
    clone2 = PhysicalPlan.from_dict(plan.to_dict())
    clone2.ring["device_batch"] = 1
    rules2 = {v.rule for v in clone2.verify(raise_on_violation=False)}
    assert "PV411" in rules2
    # strip the schema claim -> PV412
    clone3 = PhysicalPlan.from_dict(plan.to_dict())
    clone3.ops[1].schema = None
    rules3 = {v.rule for v in clone3.verify(raise_on_violation=False)}
    assert "PV412" in rules3


def _jax_device_ops(code="i4", stages=1):
    from repro.columnar import Schema, device_op

    return [OpSpec("pre", "stateless", _ident, cost_us=3.0)] + [
        device_op(f"dev{i}", "affine", Schema.of(code, scalar=True),
                  params={"a": 3, "b": 1}, backend="jax")
        for i in range(stages)
    ]


def test_wide_jax_device_schema_is_refused_not_narrowed():
    """An i8 device schema on the jax backend would compute in 32 bits (x64
    off) while the NumPy reference computes in 64: refused at plan time
    (PV413) and again by the executor, instead of narrowed in silence."""
    pytest.importorskip("jax")
    from repro.columnar import DeviceExecutor, x64_enabled
    from repro.core import PlanVerificationError

    assert not x64_enabled()
    eng = Engine(EngineConfig(
        backend="process", num_workers=2,
        process=ProcessOptions(worker_budget=4, columnar=True),
    ))
    with pytest.raises(PlanVerificationError) as exc:
        eng.plan(_jax_device_ops("i8"))
    assert [v.rule for v in exc.value.violations] == ["PV413"]
    with pytest.raises(PlanVerificationError):
        eng.run(_jax_device_ops("f8"), [1, 2, 3])  # graphs verify too
    plan = eng.plan(_jax_device_ops("i4"))
    assert plan.stages[1].device_backend == "jax"
    clone = PhysicalPlan.from_dict(plan.to_dict())
    clone.ops[1].schema = ["i8"]
    assert {v.rule for v in clone.verify(raise_on_violation=False)} == {"PV413"}
    clone.ring["x64"] = 1  # workers running jax with x64 may declare i8
    assert clone.verify(raise_on_violation=False) == []
    with pytest.raises(ValueError, match="64-bit"):
        DeviceExecutor(_jax_device_ops("i8")[1])


@pytest.mark.parametrize("stages,device_workers", [(2, 1), (1, 2)])
def test_second_chip_owner_on_tpu_host_fails_fast(monkeypatch, stages,
                                                  device_workers):
    """Two jax device stages, or device_workers=2, would put two processes
    on one TPU: refused at plan time on a TPU host (the platform check is
    steered here), and by the supervisor when a device worker reports a
    TPU under a plan that was never verified."""
    pytest.importorskip("jax")
    import repro.columnar.device as device
    from repro.core import PlanVerificationError, ProcessRuntime

    ops = _jax_device_ops(stages=stages)
    eng = Engine(EngineConfig(
        backend="process", num_workers=2,
        process=ProcessOptions(worker_budget=4, columnar=True,
                               device_workers=device_workers),
    ))
    assert eng.plan(ops).verify(raise_on_violation=False) == []  # not a TPU
    monkeypatch.setattr(device, "host_has_tpu", lambda: True)
    with pytest.raises(PlanVerificationError, match="one chip-owning") as exc:
        eng.plan(ops)
    assert [v.rule for v in exc.value.violations] == ["PV414"]
    with pytest.raises(PlanVerificationError, match="one chip-owning"):
        eng.open(ops)

    rt = ProcessRuntime.from_chain(ops, num_workers=2,
                                   device_workers=device_workers)
    assert rt.chip_owners == 2
    rt._pinfo = [("worker", 1, 0)]
    with pytest.raises(RuntimeError, match="one chip-owning"):
        rt._on_message(0, ("device", 0, {"platform": "tpu", "kind": "TPU v5e",
                                         "count": 1}))


# ------------------------------------------------------- plan dict round-trip
_KINDS = st.sampled_from(["stateless", "filter", "keyed", "stateful"])


def _op_from_kind(kind, i):
    if kind == "stateless":
        return OpSpec(f"sl{i}", "stateless", _double, cost_us=2 + i)
    if kind == "filter":
        return OpSpec(f"f{i}", "stateless", _drop_mod3, cost_us=3,
                      selectivity=0.66)
    if kind == "keyed":
        return OpSpec(f"k{i}", "partitioned", _kcount, key_fn=_mod8,
                      num_partitions=8 + i, init_state=_zero, cost_us=5 + i)
    return OpSpec(f"sf{i}", "stateful", _sf_sum, init_state=_zero, cost_us=4)


@settings(max_examples=20, deadline=None)
@given(
    kinds=st.lists(_KINDS, min_size=1, max_size=6),
    backend=st.sampled_from(["thread", "process"]),
    workers=st.sampled_from([1, 2, "auto"]),
    batch=st.sampled_from([1, 32]),
)
def test_plan_to_dict_from_dict_round_trip(kinds, backend, workers, batch):
    """Property: for random chains and configs, a plan survives the dict
    round-trip exactly — same dict, same explain() rendering."""
    specs = [_op_from_kind(k, i) for i, k in enumerate(kinds)]
    cfg = EngineConfig.from_kwargs(
        backend=backend, num_workers=workers, batch_size=batch,
        **({"worker_budget": 4} if backend == "process" else {}),
    )
    plan = Engine(cfg).plan(specs)
    d = plan.to_dict()
    revived = PhysicalPlan.from_dict(d)
    assert revived.to_dict() == d
    assert revived.explain() == plan.explain()
    assert not revived.bound
    with pytest.raises(ConfigError, match="unbound"):
        revived.graph
    # re-binding restores executability metadata
    assert revived.bind(specs).bound


def test_unbound_plan_cannot_run_but_bound_copy_can():
    specs = _session_chain()
    eng = Engine(EngineConfig(num_workers=2, collect_outputs=True))
    revived = PhysicalPlan.from_dict(eng.plan(specs).to_dict())
    with pytest.raises(ConfigError, match="unbound"):
        eng.run(revived, range(10))
    result = eng.run(revived.bind(specs), range(50))
    assert result.outputs == _session_reference(range(50))


def test_bind_rejects_mismatched_graph():
    eng = Engine(EngineConfig(num_workers=2))
    revived = PhysicalPlan.from_dict(eng.plan(_session_chain()).to_dict())
    with pytest.raises(ConfigError, match="do not match"):
        revived.bind(_keyed_chain())
    # same names, different kind: a cached plan must not pin widths onto a
    # graph whose operators changed shape underneath it
    impostor = [
        OpSpec("double", "stateless", _double, cost_us=2),
        OpSpec("ksum", "stateless", _double, cost_us=4),
    ]
    with pytest.raises(ConfigError, match="do not match"):
        revived.bind(impostor)


# ------------------------------------------------------------- engine.run
@pytest.mark.timeout(60)
def test_run_executes_pinned_plan_widths():
    """engine.run(plan, src) must execute THE plan: with elastic replanning
    off, the executed widths equal the planned widths (no recalibration)."""
    eng = Engine(EngineConfig(
        backend="process", num_workers="auto", batch_size=16,
        collect_outputs=True,
        process=ProcessOptions(worker_budget=4, elastic=False),
    ))
    plan = eng.plan(_session_chain())
    result = eng.run(plan, range(1, 500))
    assert result.plan.stage_widths() == plan.stage_widths()
    assert result.replans == 0
    assert result.outputs == _session_reference(range(1, 500))
    assert result.report.tuples_in == 499


def test_run_rejects_plan_for_other_backend():
    thread_plan = Engine(EngineConfig(num_workers=2)).plan(_session_chain())
    proc_engine = Engine(EngineConfig(backend="process", num_workers=2))
    with pytest.raises(ConfigError, match="backend"):
        proc_engine.run(thread_plan, range(10))


# ----------------------------------------------------------------- sessions
@pytest.mark.timeout(60)
@settings(max_examples=6, deadline=None)
@given(
    n=st.integers(min_value=1, max_value=300),
    chunk=st.sampled_from([1, 7, 64]),
    read_between=st.sampled_from([0, 5]),
    backend=st.sampled_from(["thread", "process"]),
    batch=st.sampled_from([1, 16]),
)
def test_property_session_push_results_preserves_order(
    n, chunk, read_between, backend, batch
):
    """Property: arbitrary push chunking interleaved with partial results()
    reads yields exactly the sequential reference, in order, on both
    backends (the Session tentpole's correctness contract)."""
    values = list(range(n))
    expected = _session_reference(values)
    engine = Engine(EngineConfig.from_kwargs(
        backend=backend, num_workers=2, batch_size=batch,
    ))
    got = []
    with engine.open(engine.plan(_session_chain())) as session:
        for off in range(0, n, chunk):
            session.push(values[off:off + chunk])
            if read_between:
                # never ask for more than has been pushed: results() blocks
                # until the requested items exist (by design)
                pushed = min(off + chunk, n)
                want = min(read_between, pushed - len(got))
                if want > 0:
                    got.extend(session.results(max_items=want))
        report = session.close()
        got.extend(session.results())
    assert got == expected
    assert report.tuples_in == n
    assert report.tuples_out == n
    assert session.report is report


@pytest.mark.timeout(60)
def test_session_surface_and_stats_on_both_backends():
    for backend in ("thread", "process"):
        engine = Engine(EngineConfig.from_kwargs(backend=backend, num_workers=2))
        session = engine.open(_session_chain())
        assert isinstance(session, Session)
        session.push(range(100))
        stats = session.stats()
        assert stats["backend"] == backend
        assert stats["pushed"] == 100
        assert not stats["closed"]
        if backend == "process":
            assert stats["stage_widths"] == [2, 2]
        else:
            assert [op["op"] for op in stats["ops"]] == ["double", "ksum"]
        report = session.close()
        assert report.tuples_out == 100
        assert session.close() is report  # idempotent
        with pytest.raises(RuntimeError, match="closed"):
            session.push([1])


@pytest.mark.timeout(60)
def test_session_trims_consumed_outputs_on_both_backends():
    """A long-lived session must not retain its full egress history: once
    results() consumes past the trim threshold, the backing output list
    shrinks (bounded by in-flight work, not total traffic)."""
    n = 3 * Session._TRIM_THRESHOLD
    for backend in ("thread", "process"):
        engine = Engine(EngineConfig.from_kwargs(
            backend=backend, num_workers=2, batch_size=32,
        ))
        with engine.open(_session_chain()) as session:
            got = 0
            for off in range(0, n, 2048):
                session.push(range(off, min(off + 2048, n)))
                got += sum(1 for _ in session.results(max_items=2048))
            session.close()
            got += sum(1 for _ in session.results())
            backing = (
                session._pipe.outputs if backend == "thread"
                else session._rt.collected_outputs()
            )
        assert got == n, backend
        assert len(backing) < n // 2, (backend, len(backing))
        assert session.stats()["pushed"] == n


@pytest.mark.timeout(60)
def test_thread_session_push_applies_input_backpressure():
    """The thread backend's worklists are unbounded deques; the session's
    push gate must keep the input-side backlog bounded even when the
    producer is much faster than a lone worker."""
    slow = [OpSpec("slowish", "stateless", _spin_op, cost_us=50)]
    engine = Engine(EngineConfig(num_workers=1))
    with engine.open(slow) as session:
        session.push(range(30_000))
        backlog = sum(n.worklist_size() for n in session._pipe.nodes)
        cap = session._inflight_cap
        session.close()
    # the sweep is amortized over _GATE_EVERY pushes, so the gate admits at
    # most cap + _GATE_EVERY before it closes
    assert backlog <= cap + type(session)._GATE_EVERY, (backlog, cap)


def _spin_op(v):
    x = float(v)
    for _ in range(400):
        x = (x * 1.0000001 + 1.31) % 97.0
    return [x]


@pytest.mark.timeout(60)
def test_session_results_timeout_raises_with_snapshot():
    engine = Engine(EngineConfig(num_workers=1))
    with engine.open(_session_chain()) as session:
        with pytest.raises(SessionStarvation) as info:
            list(session.results(timeout=0.05))
        # diagnosable from the exception alone: live counters attached
        assert info.value.snapshot.get("pushed") == 0
        assert "snapshot" in str(info.value)
        # starvation does not poison the session: it keeps serving
        session.push([1])
        assert list(session.results(max_items=1)) == _session_reference([1])


@pytest.mark.timeout(60)
def test_thread_session_raises_on_worker_death_instead_of_hanging():
    """A raising op kills its worker thread; push/results/close must raise a
    clear RuntimeError instead of spinning on backpressure forever."""
    engine = Engine(EngineConfig(num_workers=1))
    session = engine.open([OpSpec("boom", "stateless", _boom)])
    with pytest.raises(RuntimeError, match="kaboom"):
        session.push(range(30_000))  # enough to close the gate post-death
        session.close()
    session._abort()
    with pytest.raises(RuntimeError, match="aborted"):
        list(session.results())


def test_two_op_tuple_is_a_chain_not_a_graph_pair():
    """A 2-tuple of OpSpecs must plan as a chain; a (specs, source) mistake
    must raise a structured ConfigError, not a raw TypeError."""
    eng = Engine(EngineConfig(num_workers=1))
    plan = eng.plan(tuple(_session_chain()))
    assert [op.name for op in plan.ops] == ["double", "ksum"]
    with pytest.raises(ConfigError, match="OpSpec"):
        eng.plan((_session_chain(), range(10)))


@pytest.mark.timeout(60)
def test_process_session_propagates_worker_errors():
    specs = [OpSpec("boom", "stateless", _boom)]
    engine = Engine(EngineConfig(backend="process", num_workers=2))
    session = engine.open(specs)
    with pytest.raises(RuntimeError, match="kaboom"):
        session.push(range(200))
        session.close()
    session._abort()  # teardown after failure must not leak shm


def _boom(v):
    if v == 37:
        raise ValueError("kaboom")
    return [v]


# ------------------------------------------------------------ run_query path
@pytest.mark.timeout(60)
def test_run_query_native_engine_path_keeps_contract():
    from repro.streams.tpcxbb import run_query

    handle, report = run_query("q15", n=2000, num_workers=2,
                               collect_outputs=True)
    assert report.tuples_in == 2000
    assert handle.egress_count == len(handle.outputs)
    with pytest.raises(ConfigError, match="stages"):
        run_query("q15", n=10, stages=2)  # thread backend: conflicting knob
