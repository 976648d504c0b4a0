"""Chaos battery for the fault-tolerance subsystem: deterministic fault
injection (``repro.core.faults``) driving epoch-checkpoint recovery,
heartbeat stall detection, router re-forks, dead-letter accounting, and
graceful-signal teardown.  Every scenario asserts the recovered egress is
*exactly* the sequential reference — recovery that loses, duplicates, or
reorders tuples is a correctness bug, not a degraded mode — and that no
shared-memory segment leaks."""
import os
import signal
import subprocess
import sys
import time

import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # offline env: degrade to seeded randomized sampling
    from _hypothesis_compat import given, settings, strategies as st

from repro.core import (
    DeadLetter,
    FaultOptions,
    FaultPlan,
    FaultSpec,
    InjectedFault,
    OpSpec,
    ProcessRuntime,
)
from repro.core.checkpoint import CheckpointStore, decode_barrier, encode_barrier
from repro.core.faults import (
    HANG,
    KILL,
    OP_ERROR,
    ROUTER_KILL,
    SPILL_DELAY,
    resolve_policies,
)

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------- helpers
def _shm_segments(pid=None):
    """Runtime segments created by this process (or ``pid``): their names
    carry the creator's pid, so test workers running side by side never
    see each other's live segments."""
    prefix = f"repro_{pid or os.getpid()}_"
    try:
        return {f for f in os.listdir("/dev/shm") if f.startswith(prefix)}
    except FileNotFoundError:  # non-Linux: nothing to check
        return set()


def _double(v):
    return [v * 2]


def _mod7(v):
    return v % 7


def _zero():
    return 0


def _ksum(s, k, v):
    s = (s or 0) + v
    return s, [(k, s)]


def _chain():
    """stateless double -> keyed running sum: the minimal shape that has
    both a replayable stage and a stage whose recovery needs a snapshot."""
    return [
        OpSpec("double", "stateless", _double),
        OpSpec(
            "acc", "partitioned", _ksum, key_fn=_mod7, num_partitions=14,
            init_state=_zero,
        ),
    ]


def _reference(n):
    states, out = {}, []
    for v in range(1, n + 1):
        d = v * 2
        k = d % 7
        states[k] = states.get(k, 0) + d
        out.append((k, states[k]))
    return out


def _slow_source(n, every=400, nap=0.02):
    """Feed with periodic naps so injected faults land mid-stream rather
    than after the pipeline has already drained."""
    for v in range(1, n + 1):
        if v % every == 0:
            time.sleep(nap)
        yield v


# -------------------------------------------------- fault-plan determinism
def test_fault_plan_generate_is_deterministic():
    kw = dict(n_faults=6, stage_widths=[2, 3], max_serial=5000,
              kinds=(KILL, HANG, OP_ERROR))
    a = FaultPlan.generate(7, **kw)
    b = FaultPlan.generate(7, **kw)
    assert a.specs == b.specs
    assert FaultPlan.generate(8, **kw).specs != a.specs
    # the delivery-path split partitions the schedule: signal faults fire
    # from the supervisor, op_error/spill_delay ride the fork arguments
    sup = {id(s) for s in a.supervisor_specs()}
    child = {
        id(s)
        for st_ in range(2)
        for w in range(3)
        for by_serial in a.child_specs(st_, w).values()
        for s in by_serial.values()
    }
    assert sup.isdisjoint(child)
    assert all(s.kind in (KILL, HANG, ROUTER_KILL) for s in a.supervisor_specs())


def test_fault_spec_and_options_validation():
    with pytest.raises(ValueError, match="kind"):
        FaultSpec(kind="explode").validate()
    with pytest.raises(ValueError, match="serial"):
        FaultSpec(kind=KILL, serial=0).validate()
    with pytest.raises(ValueError, match="on_error"):
        FaultOptions(on_error="explode").validate()
    opts = FaultOptions(
        plan=FaultPlan(specs=[FaultSpec(kind=SPILL_DELAY, delay=0.01)]),
        on_error={"acc": "dead_letter"},
    )
    opts.validate()
    rebuilt = FaultOptions.from_dict(opts.to_dict())
    assert rebuilt.plan.specs == opts.plan.specs
    assert rebuilt.policy_for("acc") == "dead_letter"
    assert rebuilt.policy_for("other") == "raise"
    assert resolve_policies({"acc": "skip"}, _chain()) == ("raise", "skip")


# ------------------------------------------------- checkpoint store (unit)
def test_checkpoint_store_epoch_protocol():
    store = CheckpointStore()
    assert store.latest(1) is None
    # acks complete only when every worker in the width has answered
    store.ack(1, 0, epoch=1, boundary=64, blob=b"w0", width=2)
    assert store.latest(1) is None
    store.ack(1, 1, epoch=1, boundary=64, blob=b"w1", width=2)
    snap = store.latest(1)
    assert snap is not None
    assert snap.boundary == 64 and snap.blobs == {0: b"w0", 1: b"w1"}
    # stale acks at or below the committed boundary are ignored
    store.ack(1, 0, epoch=1, boundary=64, blob=b"late", width=2)
    assert store.latest(1).blobs[0] == b"w0"
    # a forced (synthetic) checkpoint advances the epoch label
    store.force(1, boundary=128, blobs={0: b"x", 1: b"y"})
    assert store.latest(1).boundary == 128
    assert store.latest(1).epoch > snap.epoch
    store.clear_pending(1)
    assert store.latest(1).boundary == 128  # committed state survives


def test_barrier_codec_roundtrip():
    for epoch in (0, 1, 2**40):
        assert decode_barrier(encode_barrier(epoch)) == epoch


# ------------------------------------------- keyed kill -> snapshot replay
@pytest.mark.timeout(120)
def test_keyed_worker_kill_restores_from_checkpoint_exact_egress():
    """SIGKILL a keyed worker mid-stream: the supervisor must restore the
    last committed epoch snapshot, replay the tail of the feeder log, and
    produce byte-identical ordered egress."""
    n = 4000
    before = _shm_segments()
    plan = FaultPlan(specs=[
        FaultSpec(kind=KILL, stage=1, worker=1, serial=1500),
    ], seed=11)
    rt = ProcessRuntime.from_chain(
        _chain(), num_workers=3, collect_outputs=True, io_batch=8,
        checkpoint_interval=64, fault_plan=plan,
    )
    report = rt.run(_slow_source(n))
    assert rt.outputs == _reference(n)
    assert report.tuples_out == n
    assert rt.restarts >= 1 and rt.recoveries >= 1
    assert rt.dead_letters == []
    assert _shm_segments() == before


# --------------------------------------------------- router-kill recovery
@pytest.mark.timeout(120)
def test_router_kill_mid_stream_recovers_exact_egress():
    n = 4000
    before = _shm_segments()
    plan = FaultPlan(specs=[
        FaultSpec(kind=ROUTER_KILL, stage=1, serial=800),
    ], seed=3)
    rt = ProcessRuntime.from_chain(
        _chain(), num_workers=3, collect_outputs=True, io_batch=8,
        checkpoint_interval=64, fault_plan=plan,
    )
    rt.run(_slow_source(n))
    assert rt.outputs == _reference(n)
    assert rt.restarts >= 1 and rt.recoveries >= 1
    assert _shm_segments() == before


# ------------------------------------------------ SIGSTOP-hang stall soak
@pytest.mark.timeout(120)
def test_sigstop_hang_soak_stall_detector_recovers():
    """Seeded hang soak: SIGSTOPped workers are hung-not-dead, so only the
    heartbeat stall detector can find them; it must SIGKILL each into the
    ordinary crash path and the run must still finish exactly."""
    n = 4000
    before = _shm_segments()
    plan = FaultPlan(specs=[
        FaultSpec(kind=HANG, stage=1, worker=1, serial=600),
        FaultSpec(kind=HANG, stage=1, worker=0, serial=2400),
    ], seed=5)
    rt = ProcessRuntime.from_chain(
        _chain(), num_workers=3, collect_outputs=True, io_batch=8,
        checkpoint_interval=64, fault_plan=plan, stall_timeout=0.5,
    )
    rt.run(_slow_source(n))
    assert rt.outputs == _reference(n)
    assert rt.restarts >= 2, "both hung workers must be reaped"
    assert rt.recoveries >= 1
    assert _shm_segments() == before


# ------------------------------------------- kill during an elastic replan
def _spin(v):
    x = float(v)
    for _ in range(300):
        x = (x * 1.0000001 + 1.31) % 97.0
    return [int(x * 1000)]


def _mod9(v):
    return v % 9


def _spin_ksum(s, k, v):
    s = (s or 0) + v
    return s, [(k, s % 99991)]


@pytest.mark.timeout(120)
def test_keyed_kill_while_elastic_replans_churn():
    """Deliberately wrong priors force mid-run resizes of the stateless
    stage while an injected SIGKILL lands in the keyed stage: checkpoint
    restore and elastic replanning must compose without losing a tuple.
    (A restore that collides with a same-stage replan in its collect phase
    is unrecoverable by design; cross-stage it must abort the replan and
    proceed.)"""
    specs = [
        OpSpec("hot", "stateless", _spin, cost_us=1),  # lie: ~25 µs
        OpSpec(
            "cold", "partitioned", _spin_ksum, key_fn=_mod9,
            num_partitions=18, init_state=_zero, cost_us=60,  # lie: ~2
        ),
    ]
    n = 20000
    states, expected = {}, []
    for v in range(1, n + 1):
        out = _spin(v)[0]
        k = out % 9
        states[k] = states.get(k, 0) + out
        expected.append((k, states[k] % 99991))

    before = _shm_segments()
    plan = FaultPlan(specs=[
        FaultSpec(kind=KILL, stage=1, worker=0, serial=n // 2),
    ], seed=23)
    rt = ProcessRuntime.from_chain(
        specs, num_workers="auto", worker_budget=3, collect_outputs=True,
        cost_priors={"hot": 1.0, "cold": 60.0},
        replan_interval=0.05, replan_patience=2, batch_size=32,
        checkpoint_interval=128, fault_plan=plan,
    )
    report = rt.run(range(1, n + 1))
    assert rt.replans >= 1, "priors lie hard enough that a replan must fire"
    assert rt.restarts >= 1 and rt.recoveries >= 1
    assert rt.outputs == expected
    assert report.tuples_in == n
    assert _shm_segments() == before


# ------------------------------------------- dead-letter accounting (prop)
@pytest.mark.timeout(120)
@settings(max_examples=5, deadline=None)
@given(
    io_batch=st.sampled_from([1, 2, 8, 32]),
    bad=st.sets(st.integers(min_value=1, max_value=240), min_size=1, max_size=5),
)
def test_dead_letter_accounting_across_batch_sizes(io_batch, bad):
    """``on_error="dead_letter"`` quarantines exactly the faulted serials
    — for every dispatch-unit size — and every surviving tuple egresses in
    order.  Serial ownership is decided by dispatch, so a spec is planted
    per worker; only the owner fires it."""
    n = 240
    specs = [OpSpec("double", "stateless", _double)]
    plan = FaultPlan(specs=[
        FaultSpec(kind=OP_ERROR, stage=0, worker=w, serial=s)
        for s in sorted(bad) for w in range(2)
    ], seed=1)
    rt = ProcessRuntime.from_chain(
        specs, num_workers=2, collect_outputs=True, io_batch=io_batch,
        fault_plan=plan, on_error="dead_letter",
    )
    report = rt.run(range(1, n + 1))
    assert report.tuples_out == n - len(bad)
    assert sorted(d.serial for d in rt.dead_letters) == sorted(bad)
    assert all(
        isinstance(d, DeadLetter) and d.op == "double" and "InjectedFault" in d.error
        for d in rt.dead_letters
    )
    assert rt.outputs == [v * 2 for v in range(1, n + 1) if v not in bad]


@pytest.mark.timeout(60)
def test_on_error_policies_raise_and_skip():
    plan = FaultPlan(specs=[
        FaultSpec(kind=OP_ERROR, stage=0, worker=w, serial=5) for w in range(2)
    ])
    rt = ProcessRuntime.from_chain(
        [OpSpec("double", "stateless", _double)], num_workers=2,
        collect_outputs=True, fault_plan=plan,
    )
    with pytest.raises(RuntimeError, match="InjectedFault"):
        rt.run(range(1, 101))
    rt = ProcessRuntime.from_chain(
        [OpSpec("double", "stateless", _double)], num_workers=2,
        collect_outputs=True, fault_plan=plan, on_error="skip",
    )
    report = rt.run(range(1, 101))
    assert report.tuples_out == 99
    assert rt.dead_letters == []  # skip drops silently, no quarantine
    assert rt.outputs == [v * 2 for v in range(1, 101) if v != 5]


# ------------------------------------------------- graceful SIGTERM teardown
_SIGTERM_CHILD = """
import sys, time
sys.path.insert(0, {src!r})
from repro.core import OpSpec, ProcessRuntime

def spin(v):
    x = float(v)
    for _ in range(2000):
        x = (x * 1.0000001 + 1.31) % 97.0
    return [x]

def src():
    i = 0
    while True:
        yield i
        i += 1

rt = ProcessRuntime.from_chain(
    [OpSpec("spin", "stateless", spin)], num_workers=2,
)
print("READY", flush=True)
rt.run(src(), drain_timeout=300)
"""


@pytest.mark.timeout(120)
def _col_widen(v):
    return [(v, v * 3)]


def _col_ksum(s, k, t):
    s = (s or 0) + t[0]
    return s, [(k, s + t[1])]


def _col_chain():
    """Columnar-eligible chain: numeric tuples ride TAG_COLBLOCK through the
    stateless stage, then fall back to pickle at the keyed stage."""
    return [
        OpSpec("widen", "stateless", _col_widen),
        OpSpec("acc", "partitioned", _col_ksum, key_fn=_col_mod, num_partitions=14,
               init_state=_zero),
    ]


def _col_mod(t):
    return t[0] % 7


def _col_reference(n):
    states, out = {}, []
    for v in range(1, n + 1):
        t = (v, v * 3)
        k = t[0] % 7
        states[k] = states.get(k, 0) + t[0]
        out.append((k, states[k] + t[1]))
    return out


@pytest.mark.timeout(120)
def test_worker_kill_mid_columnar_stream_exact_egress_no_leak():
    """SIGKILL a stateless worker while the stream rides the columnar
    TAG_COLBLOCK path: re-fork + replay must re-derive byte-identical
    ordered egress (the columnar encoding is replay-indifferent — a
    replayed unit may re-publish as a block or as pickle and the reorder
    ring cannot tell), with zero shm segment leaks."""
    n = 4000
    before = _shm_segments()
    plan = FaultPlan(specs=[
        FaultSpec(kind=KILL, stage=0, worker=0, serial=1200),
        FaultSpec(kind=KILL, stage=1, worker=1, serial=2500),
    ], seed=23)
    rt = ProcessRuntime.from_chain(
        _col_chain(), num_workers=3, collect_outputs=True, io_batch=8,
        checkpoint_interval=64, fault_plan=plan, columnar=True,
    )
    report = rt.run(_slow_source(n))
    assert rt.outputs == _col_reference(n)
    assert report.tuples_out == n
    assert rt.restarts >= 2 and rt.recoveries >= 1
    assert rt.dead_letters == []
    assert _shm_segments() == before


@pytest.mark.timeout(120)
def test_device_worker_kill_recovers_via_checkpoint_replay():
    """SIGKILL a device-stage worker mid-stream: device batches span
    ingress units (advance-before-publish), so recovery must ride the
    checkpoint/replay-log group restore — and the recovered egress must
    stay bit-identical to the NumPy reference."""
    from repro.columnar import Schema, device_op

    n = 3000
    before = _shm_segments()
    dev = device_op("dev", "affine", Schema.of("i8", "i8"),
                    params={"a": 3, "b": -1}, backend="numpy")
    plan = FaultPlan(specs=[
        FaultSpec(kind=KILL, stage=1, worker=0, serial=900),
    ], seed=29)
    rt = ProcessRuntime.from_chain(
        [OpSpec("widen", "stateless", _col_widen), dev],
        num_workers=2, collect_outputs=True, io_batch=8,
        checkpoint_interval=64, fault_plan=plan, columnar=True,
        device_batch=32,
    )
    report = rt.run(_slow_source(n))
    assert rt.outputs == [(v * 3 - 1, v * 9 - 1) for v in range(1, n + 1)]
    assert report.tuples_out == n
    assert rt.restarts >= 1 and rt.recoveries >= 1
    assert _shm_segments() == before


def test_sigterm_mid_run_tears_down_without_shm_leak():
    """SIGTERM during a live stream must convert to SystemExit(143), run
    the normal teardown (reap children, unlink every segment), and exit
    with the conventional 128+15 status — not die mid-critical-section."""
    before = _shm_segments()
    script = _SIGTERM_CHILD.format(src=os.path.join(REPO_ROOT, "src"))
    proc = subprocess.Popen(
        [sys.executable, "-c", script],
        stdout=subprocess.PIPE, text=True, cwd=REPO_ROOT,
    )
    try:
        assert proc.stdout.readline().strip() == "READY"
        time.sleep(0.8)  # let the stream and its segments come up
        proc.send_signal(signal.SIGTERM)
        rc = proc.wait(timeout=60)
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.wait(timeout=10)
    assert rc == 143, f"expected graceful SystemExit(143), got {rc}"
    assert _shm_segments() == before
    assert _shm_segments(proc.pid) == set()  # the child's own segments


# --------------------------------------------------- spill-deadline context
def test_spill_timeout_error_carries_stage_context():
    from repro.core.procrun import _await_spill

    with pytest.raises(TimeoutError) as ei:
        _await_spill(
            {}, 7, lambda: None, timeout=0.05,
            describe=lambda: "stage 1 (acc) worker 0; backlog=[3, 9]",
        )
    msg = str(ei.value)
    assert "serial 7" in msg
    assert "stage 1 (acc) worker 0" in msg
    assert "spill_timeout" in msg  # points at the ProcessOptions knob


@pytest.mark.timeout(60)
def test_spill_delay_fault_still_drains():
    """An injected spill-relay delay must slow delivery, not break it:
    oversized bundles still arrive and egress stays exact."""
    n = 40
    payload = bytes(200_000)

    def fat(v):
        return [(v, payload)]

    plan = FaultPlan(specs=[
        FaultSpec(kind=SPILL_DELAY, stage=0, worker=w, serial=10, delay=0.05)
        for w in range(2)
    ])
    rt = ProcessRuntime.from_chain(
        [OpSpec("fat", "stateless", fat)], num_workers=2,
        collect_outputs=True, io_batch=2, fault_plan=plan,
    )
    report = rt.run(range(1, n + 1))
    assert report.tuples_out == n
    assert [v for v, _ in rt.outputs] == list(range(1, n + 1))


# ------------------------------------------- serving mux churn under crashes
def _accsum(s, v):
    s = (s or 0) + v
    return s, [s]


@pytest.mark.timeout(180)
def test_mux_session_churn_survives_keyed_worker_kill():
    """Session churn on a multiplexed process runtime while a keyed worker
    is SIGKILLed mid-stream (docs/serving.md): checkpoint restore + replay
    must keep every session's egress exact — state is per-session, so any
    cross-session leakage or replay duplication corrupts the running sums —
    retire closing sessions cleanly, admit a new session into the freed
    slot, and leak no shared memory."""
    from repro.core.api import Engine, EngineConfig, ProcessOptions
    from repro.serve import MuxConfig, SessionMux

    before = _shm_segments()
    plan = FaultPlan(specs=[
        FaultSpec(kind=KILL, stage=1, worker=1, serial=1200),
    ], seed=11)
    eng = Engine(EngineConfig(
        backend="process", num_workers=3, batch_size=8,
        process=ProcessOptions(checkpoint_interval=64, io_batch=8),
        faults=FaultOptions(plan=plan),
    ))
    chain = [
        OpSpec("double", "stateless", _double),
        OpSpec("acc", "stateful", _accsum),  # mux makes this sid-partitioned
    ]
    inputs = {
        name: [(ord(name) * 37 + j) % 501 + 1 for j in range(n)]
        for name, n in (("a", 500), ("b", 700), ("c", 400), ("d", 300))
    }

    def oracle(vals):
        out, s = [], 0
        for v in vals:
            s += 2 * v
            out.append(s)
        return out

    mux = SessionMux(eng, chain, config=MuxConfig(max_sessions=3))
    with mux:
        handles = {k: mux.open() for k in "abc"}  # wave 1
        # interleave wave-1 ingress with naps so the injected kill lands
        # mid-stream (serial 1200 of the ~1600 wave-1 tuples)
        cursors = dict.fromkeys("abc", 0)
        while any(cursors[k] < len(inputs[k]) for k in "abc"):
            for k in "abc":
                lo = cursors[k]
                if lo >= len(inputs[k]):
                    continue
                handles[k].push(inputs[k][lo:lo + 40])
                cursors[k] = lo + 40
            time.sleep(0.01)
        # churn across the crash window: drain + retire a, admit d into
        # the freed slot while b/c still have tuples in flight
        want_a = oracle(inputs["a"])
        got_a = list(handles["a"].results(max_items=len(want_a), timeout=90))
        assert got_a == want_a
        handles["a"].close()
        assert handles["a"].poll() == []
        handles["d"] = mux.open()
        handles["d"].push(inputs["d"])
        for k in "bcd":
            want = oracle(inputs[k])
            got = list(handles[k].results(max_items=len(want), timeout=90))
            assert got == want, f"session {k}: egress diverged after recovery"
            handles[k].close()
            assert handles[k].poll() == []
        rt = mux._inner._rt
        assert rt.restarts >= 1 and rt.recoveries >= 1, (
            "injected keyed-worker kill never fired"
        )
    assert _shm_segments() == before


@pytest.mark.timeout(180)
def test_traffic_resize_survives_keyed_worker_kill_and_retired_sessions():
    """Chaos: SIGKILL a sid-partitioned worker while *traffic-triggered*
    elasticity is live-resizing that same stage, with session churn across
    the window (one session retires mid-run, another is admitted into the
    freed slot).  The combination must stay exact: per-session running
    sums survive checkpoint restore + replay at whatever width the policy
    chose, the retired session's slot is reusable, and any late replay
    output of a retired sid is counted undeliverable — never delivered to
    the wrong session, never a crash."""
    from repro.core.api import Engine, EngineConfig, ProcessOptions
    from repro.serve import MuxConfig, SessionMux

    before = _shm_segments()
    plan = FaultPlan(specs=[
        # worker 0 always exists, so the kill cannot go moot if it fires
        # before the first grow; serial 600 of ~1900 lands after the
        # saturation-triggered resize in practice
        FaultSpec(kind=KILL, stage=1, worker=0, serial=600),
    ], seed=23)
    eng = Engine(EngineConfig(
        backend="process", num_workers=1, batch_size=8,
        process=ProcessOptions(
            worker_budget=3, checkpoint_interval=64, io_batch=8,
            replan_interval=600.0,  # occupancy monitor parked: traffic only
            traffic_elastic=True, traffic_interval=0.05,
            traffic_grow_util=0.65, traffic_shrink_util=0.30,
            traffic_patience=1, traffic_cooldown=0.2,
        ),
        faults=FaultOptions(plan=plan),
    ))
    chain = [
        OpSpec("double", "stateless", _double),
        OpSpec("acc", "stateful", _accsum),  # mux makes this sid-partitioned
    ]
    inputs = {
        name: [(ord(name) * 41 + j) % 503 + 1 for j in range(n)]
        for name, n in (("a", 400), ("b", 700), ("c", 500), ("d", 300))
    }

    def oracle(vals):
        out, s = [], 0
        for v in vals:
            s += 2 * v
            out.append(s)
        return out

    mux = SessionMux(eng, chain, config=MuxConfig(
        max_sessions=3, state_partitions=4, load_signal_interval=0.02,
    ))
    with mux:
        handles = {k: mux.open() for k in "abc"}
        # flood the DRR queues: admission pressure trips the policy's
        # saturation override, so a grow fires early in the stream and the
        # serial-600 kill lands in/around the resize window
        cursors = dict.fromkeys("abc", 0)
        while any(cursors[k] < len(inputs[k]) for k in "abc"):
            for k in "abc":
                lo = cursors[k]
                if lo >= len(inputs[k]):
                    continue
                handles[k].push(inputs[k][lo:lo + 80])
                cursors[k] = lo + 80
        # churn across the crash/resize window: retire a, admit d
        want_a = oracle(inputs["a"])
        got_a = list(handles["a"].results(max_items=len(want_a), timeout=90))
        assert got_a == want_a
        handles["a"].close()
        assert handles["a"].poll() == []
        handles["d"] = mux.open()
        handles["d"].push(inputs["d"])
        for k in "bcd":
            want = oracle(inputs[k])
            got = list(handles[k].results(max_items=len(want), timeout=90))
            assert got == want, f"session {k}: egress diverged"
            handles[k].close()
            assert handles[k].poll() == []
        rt = mux._inner._rt
        assert rt.restarts >= 1 and rt.recoveries >= 1, (
            "injected keyed-worker kill never fired"
        )
        assert rt.grows >= 1, "traffic policy never grew the keyed stage"
        stats = mux.stats()
        assert stats["undeliverable"] >= 0  # counted, not delivered/crashed
    assert _shm_segments() == before
