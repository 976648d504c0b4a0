"""Process-parallel backend tests: exact ordered output, zero tuple loss,
markers intact, crash/restart recovery, spill path, and shared-memory hygiene.

The watchdog rides at 60 s for these (process spawn/join failures must
surface fast, not after the 120 s default).
"""
import os
import signal
import time

import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # offline env: degrade to seeded randomized sampling
    from _hypothesis_compat import given, settings, strategies as st

from repro.core import OpSpec, ProcessRuntime, run_graph, run_pipeline
from repro.core.shm import ShmReorderRing, ShmSpscRing


# ---------------------------------------------------------------- helpers
def _mk_specs(drop_mod=3):
    return [
        OpSpec("double", "stateless", lambda v: [v * 2]),
        OpSpec(
            "filt", "stateless",
            lambda v, m=drop_mod: [v] if (m == 0 or v % m) else [],
        ),
        OpSpec(
            "count", "stateful",
            lambda s, v: (s + 1, [(v, s + 1)]), init_state=lambda: 0,
        ),
    ]


def _oracle(vals, drop_mod=3):
    out, c = [], 0
    for v in vals:
        d = v * 2
        if drop_mod == 0 or d % drop_mod:
            c += 1
            out.append((d, c))
    return out


def _shm_segments(pid=None):
    """Runtime segments created by this process (or ``pid``): their names
    carry the creator's pid, so test workers running side by side never
    see each other's live segments."""
    prefix = f"repro_{pid or os.getpid()}_"
    try:
        return {f for f in os.listdir("/dev/shm") if f.startswith(prefix)}
    except FileNotFoundError:  # non-Linux: nothing to check
        return set()


# ------------------------------------------------------------ ordered output
@pytest.mark.timeout(60)
@settings(max_examples=8, deadline=None)
@given(
    vals=st.lists(st.integers(min_value=0, max_value=1000), min_size=1, max_size=400),
    drop_mod=st.sampled_from([0, 2, 3, 7]),
    workers=st.sampled_from([1, 2, 4]),
    io_batch=st.sampled_from([1, 4, 32]),
)
def test_property_process_exact_order_no_loss(vals, drop_mod, workers, io_batch):
    """Random selectivity / batch sizes / worker counts: the process backend's
    egress equals the sequential reference exactly (order + zero loss)."""
    pipe, report = run_pipeline(
        _mk_specs(drop_mod),
        vals,
        num_workers=workers,
        backend="process",
        collect_outputs=True,
        io_batch=io_batch,
    )
    expected = _oracle(vals, drop_mod)
    assert pipe.outputs == expected
    assert report.tuples_in == len(vals)
    assert report.tuples_out == len(expected)


@pytest.mark.timeout(60)
def test_process_stateless_only_chain():
    src = list(range(1, 800))
    pipe, report = run_pipeline(
        _mk_specs()[:2], src, num_workers=3, backend="process",
        collect_outputs=True,
    )
    assert pipe.outputs == [v * 2 for v in src if (v * 2) % 3]
    assert report.egress_throughput > 0


@pytest.mark.timeout(60)
def test_process_keyed_routing_preserves_per_key_state():
    specs = [
        OpSpec(
            "ksum", "partitioned",
            lambda s, k, v: (s + v, [(k, s + v)]),
            key_fn=lambda v: v % 7, num_partitions=14, init_state=lambda: 0,
        ),
        OpSpec("id", "stateless", lambda v: [v]),
    ]
    src = list(range(1, 600))
    states, expected = {}, []
    for v in src:
        k = v % 7
        states[k] = states.get(k, 0) + v
        expected.append((k, states[k]))
    pipe, _ = run_pipeline(
        specs, src, num_workers=3, backend="process", collect_outputs=True
    )
    assert pipe.outputs == expected


@pytest.mark.timeout(60)
def test_process_markers_and_latency():
    src = list(range(1, 2000))
    pipe, report = run_pipeline(
        _mk_specs(), src, num_workers=2, backend="process", marker_interval=16
    )
    assert report.mean_latency > 0
    assert len(pipe.markers) > 0


@pytest.mark.timeout(60)
def test_process_backend_on_dag_graph():
    """run_graph(backend='process'): stateless prefix parallel, split/merge
    tail executed in the parent — egress equals the linear reference."""
    from repro.core import Merge, Split

    nodes = {
        "pre": OpSpec("pre", "stateless", lambda v: [v + 1]),
        "split": Split("round_robin"),
        "a": OpSpec("a", "stateless", lambda v: [v * 2]),
        "b": OpSpec("b", "stateless", lambda v: [v * 2]),
        "merge": Merge(),
        "tot": OpSpec(
            "tot", "stateful", lambda s, v: (s + v, [s + v]), init_state=lambda: 0
        ),
    }
    edges = [
        ("pre", "split"), ("split", "a"), ("split", "b"),
        ("a", "merge"), ("b", "merge"), ("merge", "tot"),
    ]
    src = list(range(50))
    expected, s = [], 0
    for v in src:
        s += (v + 1) * 2
        expected.append(s)
    pipe, _ = run_graph(
        nodes, edges, src, num_workers=2, backend="process", collect_outputs=True
    )
    assert pipe.outputs == expected


# --------------------------------------------------------------- spill path
@pytest.mark.timeout(60)
def test_process_oversized_payloads_take_spill_path():
    """Bundles larger than a reorder slot travel via the pipe side channel
    with a spill tag in the ring — order must survive."""
    src = [("x" * 3000, i) for i in range(200)]  # ~3 KB payloads
    specs = [
        OpSpec("stamp", "stateless", lambda t: [(t[0], t[1], len(t[0]))]),
        OpSpec("keep", "stateless", lambda t: [t] if t[1] % 2 else []),
    ]
    pipe, _ = run_pipeline(
        specs, src, num_workers=2, backend="process", collect_outputs=True,
        io_batch=8, reorder_payload=1024,
    )
    assert pipe.outputs == [
        ("x" * 3000, i, 3000) for _, i in src if i % 2
    ]


# ---------------------------------------------------------- crash / restart
@pytest.mark.timeout(60)
def test_process_worker_crash_restart_exact_output():
    """SIGKILL one worker mid-run: the runtime re-forks it, replays its
    in-flight serials, and the egress still equals the reference exactly."""
    def slowish(v):
        x = 0
        for _ in range(200):
            x += 1
        return [v * 3] if v % 5 else []

    specs = [OpSpec("slow", "stateless", slowish)]
    src = list(range(1, 12000))
    rt = ProcessRuntime.from_chain(
        specs, num_workers=2, collect_outputs=True, io_batch=4
    )

    orig_setup = rt._setup
    killed = {"done": False}

    def chaos_setup():
        orig_setup()
        pid = rt._procs[0].pid  # capture now; stop() clears the list later

        # kill worker 0 shortly after the pipeline starts moving
        import threading

        def killer():
            time.sleep(0.02)
            try:
                os.kill(pid, signal.SIGKILL)
                killed["done"] = True
            except ProcessLookupError:
                pass

        threading.Thread(target=killer, daemon=True).start()

    rt._setup = chaos_setup
    report = rt.run(src)
    assert killed["done"], "chaos killer never fired"
    assert rt.restarts >= 1, "crash was not detected/recovered"
    assert rt.outputs == [v * 3 for v in src if v % 5]
    assert report.tuples_in == len(src)
    assert report.tuples_out == len(rt.outputs)


@pytest.mark.timeout(60)
def test_process_worker_exception_propagates():
    def boom(v):
        if v == 37:
            raise ValueError("kaboom")
        return [v]

    with pytest.raises(RuntimeError, match="kaboom"):
        run_pipeline(
            [OpSpec("boom", "stateless", boom)],
            list(range(100)),
            num_workers=2,
            backend="process",
            io_batch=1,
        )


# ----------------------------------------------------------- crash soak
def _soak_hot(v):
    x = float(v)
    for _ in range(300):
        x = (x * 1.0000001 + 1.31) % 97.0
    return [int(x * 1000)]


def _soak_mod(v):
    return v % 9


def _soak_ksum(s, k, v):
    s = (s or 0) + v
    return s, [(k, s % 99991)]


def _soak_zero():
    return 0


@pytest.mark.timeout(120)
def test_crash_soak_ten_kills_including_during_elastic_replan():
    """Soak: SIGKILL a random stage-0 (stateless, recoverable) worker 10
    times over one run while elastic replans churn (deliberately wrong
    priors force a resize mid-run, so kills land in every replan phase).
    Egress must equal the sequential reference exactly and no shared-memory
    segment may leak."""
    import random
    import threading

    from repro.core import ProcessRuntime

    specs = [
        OpSpec("hot", "stateless", _soak_hot, cost_us=1),  # lie: ~25 µs
        OpSpec(
            "cold", "partitioned", _soak_ksum, key_fn=_soak_mod,
            num_partitions=18, init_state=_soak_zero, cost_us=60,  # lie: ~2
        ),
    ]
    src = list(range(1, 30001))
    states, expected = {}, []
    for v in src:
        x = float(v)
        for _ in range(300):
            x = (x * 1.0000001 + 1.31) % 97.0
        out = int(x * 1000)
        k = out % 9
        states[k] = states.get(k, 0) + out
        expected.append((k, states[k] % 99991))

    before = _shm_segments()
    rt = ProcessRuntime.from_chain(
        specs, num_workers="auto", worker_budget=3, collect_outputs=True,
        cost_priors={"hot": 1.0, "cold": 60.0},
        replan_interval=0.05, replan_patience=2, batch_size=32,
    )
    kills = {"done": 0}
    stop_killer = threading.Event()

    def killer():
        rng = random.Random(0xC0FFEE)
        while kills["done"] < 10 and not stop_killer.is_set():
            time.sleep(0.05)
            victims = rt.worker_groups()[0] if rt._procs else []
            victims = [p for p in victims if p.is_alive()]
            if not victims:
                continue
            try:
                os.kill(rng.choice(victims).pid, signal.SIGKILL)
                kills["done"] += 1
            except (ProcessLookupError, AttributeError):
                continue

    th = threading.Thread(target=killer, daemon=True)
    orig_setup = rt._setup

    def chaos_setup():
        orig_setup()
        th.start()

    rt._setup = chaos_setup
    try:
        report = rt.run(src)
    finally:
        stop_killer.set()
        th.join(timeout=5)
    assert kills["done"] >= 10, f"soak only landed {kills['done']} kills"
    assert rt.restarts >= 1, "no crash recovery happened"
    assert rt.outputs == expected
    assert report.tuples_in == len(src)
    assert _shm_segments() == before


def _slow_ksum(s, k, v):
    x = 0
    for _ in range(200):
        x += 1
    s = (s or 0) + v
    return s, [(k, s)]


def _slow_count(s, v):
    x = 0
    for _ in range(200):
        x += 1
    return s + 1, [(v, s + 1)]


def _stateful_stage_op(kind):
    if kind == "keyed":
        return OpSpec(
            "ks", "partitioned", _slow_ksum, key_fn=lambda v: v % 7,
            num_partitions=14, init_state=lambda: 0,
        )
    return OpSpec("ct", "stateful", _slow_count, init_state=lambda: 0)


def _chaos_kill_first_worker(rt, stage=1, after=0.05):
    """Wrap ``rt._setup`` so the first worker of ``stage`` is SIGKILLed
    shortly after the pipeline comes up."""
    orig_setup = rt._setup

    def chaos_setup():
        orig_setup()
        victim = rt.worker_groups()[stage][0].pid
        import threading

        def killer():
            time.sleep(after)
            try:
                os.kill(victim, signal.SIGKILL)
            except ProcessLookupError:
                pass

        threading.Thread(target=killer, daemon=True).start()

    rt._setup = chaos_setup


def _stateful_reference(kind, n):
    if kind == "keyed":
        states, out = {}, []
        for v in range(1, n):
            k = v % 7
            states[k] = states.get(k, 0) + v
            out.append((k, states[k]))
        return out
    return [(v, v) for v in range(1, n)]


@pytest.mark.timeout(60)
@pytest.mark.parametrize("kind", ["keyed", "stateful"])
def test_kill_in_stateful_stage_recovers_by_default(kind):
    """A SIGKILL in a keyed/stateful stage is survivable by default now
    that epoch checkpointing is on: the supervisor restores the last
    committed snapshot, replays, and egress equals the reference exactly
    — with every shm segment still unlinked at the end."""
    n = 60000
    specs = [OpSpec("id", "stateless", lambda v: [v]), _stateful_stage_op(kind)]
    before = _shm_segments()
    rt = ProcessRuntime.from_chain(specs, num_workers=2, collect_outputs=True)
    _chaos_kill_first_worker(rt)
    report = rt.run(range(1, n))
    assert rt.outputs == _stateful_reference(kind, n)
    assert report.tuples_out == n - 1
    assert rt.restarts >= 1 and rt.recoveries >= 1
    assert _shm_segments() == before


@pytest.mark.timeout(60)
@pytest.mark.parametrize("kind", ["keyed", "stateful"])
def test_kill_in_stateful_stage_raises_cleanly_when_ckpt_off(kind):
    """With checkpointing explicitly disabled, a SIGKILL in a keyed or
    stateful stage is unrecoverable (worker-local state is gone): the
    runtime must raise a clear error — not hang, not silently drop
    tuples — and still unlink every shm segment."""
    specs = [OpSpec("id", "stateless", lambda v: [v]), _stateful_stage_op(kind)]
    before = _shm_segments()
    rt = ProcessRuntime.from_chain(
        specs, num_workers=2, collect_outputs=True, checkpoint_interval=0,
    )
    _chaos_kill_first_worker(rt)
    with pytest.raises(RuntimeError, match="worker-local state|died"):
        rt.run(range(1, 60000))
    assert _shm_segments() == before


# ------------------------------------------------------------- shm hygiene
@pytest.mark.timeout(60)
def test_no_shared_memory_leaks_across_repeated_runs():
    """20 consecutive runs must not leave a single repro_* segment behind."""
    before = _shm_segments()
    specs = [OpSpec("id", "stateless", lambda v: [v])]
    for i in range(20):
        pipe, _ = run_pipeline(
            specs, list(range(50)), num_workers=2, backend="process",
            collect_outputs=True,
        )
        assert pipe.outputs == list(range(50))
    assert _shm_segments() == before


@pytest.mark.timeout(60)
def test_stop_is_idempotent():
    rt = ProcessRuntime.from_chain(
        [OpSpec("id", "stateless", lambda v: [v])], num_workers=1
    )
    rt.run(range(10))
    rt.stop()  # second stop after run's own stop: no-op, no raise
    rt.stop()


# ------------------------------------------------------------ ring unit tests
def test_spsc_ring_roundtrip_and_spanning_records():
    ring = ShmSpscRing(f"repro_test_{os.getpid()}_a", slots=8, slot_bytes=64)
    try:
        assert ring.get() is None
        assert ring.put(1, 2, b"abc")
        big = bytes(range(256)) * 1  # spans multiple 64-byte slots
        assert ring.put(2, 5, big)
        assert ring.get() == (1, 2, b"abc")
        assert ring.get() == (2, 5, big)
        assert ring.get() is None
        # fill until full -> put returns False, then drain frees space
        n = 0
        while ring.put(10 + n, 0, b"x" * 40):
            n += 1
        assert n > 0 and not ring.put(99, 0, b"x" * 40)
        assert ring.get() is not None
        assert ring.put(99, 0, b"x" * 40)
    finally:
        ring.close()
        ring.unlink()


def test_reorder_ring_orders_and_rejects():
    got = []
    ring = ShmReorderRing(f"repro_test_{os.getpid()}_b", size=4, payload_bytes=32)
    try:
        OK, FULL, STALE = (
            ShmReorderRing.PUBLISHED, ShmReorderRing.FULL, ShmReorderRing.STALE
        )
        assert ring.try_publish(2, 0, b"b") == OK
        assert ring.poll() is None  # serial 1 missing: window blocked
        assert ring.try_publish(5, 0, b"x") == FULL  # beyond next+size
        assert ring.try_publish(1, 0, b"a") == OK
        for expect in (1, 2):
            t, tag, data, span = ring.poll()
            got.append(t)
            assert span == 1
        assert got == [1, 2]
        assert ring.try_publish(1, 0, b"dup") == STALE  # replay of drained
        assert ring.try_publish(5, 0, b"x") == OK  # window advanced
    finally:
        ring.close()
        ring.unlink()


def test_reorder_ring_span_publish_covers_contiguous_run():
    """A span slot carries a whole contiguous micro-batch: the drain jumps
    ``next`` past the covered serials and the next span lines up."""
    ring = ShmReorderRing(f"repro_test_{os.getpid()}_c", size=8, payload_bytes=32)
    try:
        assert ring.try_publish(1, 0, b"abc", span=3) == ShmReorderRing.PUBLISHED
        assert ring.try_publish(4, 0, b"de", span=2) == ShmReorderRing.PUBLISHED
        t, tag, data, span = ring.poll()
        assert (t, data, span) == (1, b"abc", 3)
        t, tag, data, span = ring.poll()
        assert (t, data, span) == (4, b"de", 2)
        assert ring.poll() is None
        assert ring.next_serial == 6
        # serials inside a drained span are stale for any late replay
        assert ring.try_publish(2, 0, b"x") == ShmReorderRing.STALE
    finally:
        ring.close()
        ring.unlink()


def test_spsc_peek_advance_and_consumer_resync():
    """peek leaves the record uncommitted (crash-replay basis); sync_consumer
    realigns a fresh consumer mirror with the shared head cursor."""
    ring = ShmSpscRing(f"repro_test_{os.getpid()}_d", slots=8, slot_bytes=64)
    try:
        assert ring.put(7, 1, b"abc")
        serial, tag, data, nslots = ring.peek()
        assert (serial, tag, data) == (7, 1, b"abc")
        # not committed: a re-peek (crash replacement) sees the same record
        assert ring.peek()[:3] == (7, 1, b"abc")
        ring.advance(nslots)
        assert ring.peek() is None
        # a stale mirror (fresh fork) resyncs to the committed shared head
        ring._head = 0
        ring.sync_consumer()
        assert ring.peek() is None
    finally:
        ring.close()
        ring.unlink()


# ------------------------------------------------------------- staged stages
@pytest.mark.timeout(60)
def test_interior_stateful_op_runs_as_own_process_stage():
    """A chain with an interior stateful operator must cut into >= 2 process
    stages, each with its own live worker group (the tentpole claim: interior
    operators leave the parent)."""
    specs = _mk_specs()  # SL -> SL -> SF
    rt = ProcessRuntime.from_chain(specs, num_workers=2, collect_outputs=True)
    assert rt.num_stages == 2
    assert [p.kind for p in rt.stage_plans] == ["stateless", "stateful"]

    groups = {}
    orig_setup = rt._setup

    def spy_setup():
        orig_setup()
        groups["pids"] = [
            sorted(p.pid for p in g) for g in rt.worker_groups()
        ]

    rt._setup = spy_setup
    src = list(range(1, 500))
    rt.run(src)
    assert len(groups["pids"]) == 2  # two distinct worker groups ran
    assert all(groups["pids"]), "every stage must own live worker processes"
    assert set(groups["pids"][0]).isdisjoint(groups["pids"][1])
    assert rt.outputs == _oracle(src)


@pytest.mark.timeout(60)
def test_interior_keyed_stage_parallel_workers_exact_state():
    """SL -> PS -> SL: the partitioned op runs as its own keyed stage across
    several workers; per-key state and global order must both survive."""
    specs = [
        OpSpec("inc", "stateless", lambda v: [v + 1]),
        OpSpec(
            "ksum", "partitioned",
            lambda s, k, v: (s + v, [(k, s + v)]),
            key_fn=lambda v: v % 5, num_partitions=10, init_state=lambda: 0,
        ),
        OpSpec("fmt", "stateless", lambda t: [t]),
    ]
    src = list(range(1, 700))
    states, expected = {}, []
    for v in src:
        v1 = v + 1
        k = v1 % 5
        states[k] = states.get(k, 0) + v1
        expected.append((k, states[k]))
    rt = ProcessRuntime.from_chain(
        specs, num_workers=3, collect_outputs=True, io_batch=8
    )
    assert rt.num_stages == 2
    assert rt.stage_plans[1].kind == "keyed"
    assert rt.stage_plans[1].workers == 3
    rt.run(src)
    assert rt.outputs == expected


@pytest.mark.timeout(60)
def test_keyed_stage_composes_with_io_batch():
    """The PR-2 gap: keyed routing used to force io_batch=1.  Per-worker
    batches now carry per-tuple serials, so any batch size must reproduce
    the exact cross-worker interleave order."""
    specs = [
        OpSpec(
            "ksum", "partitioned",
            lambda s, k, v: (s + v, [(k, s + v)]),
            key_fn=lambda v: v % 7, num_partitions=14, init_state=lambda: 0,
        ),
    ]
    src = list(range(1, 600))
    states, expected = {}, []
    for v in src:
        k = v % 7
        states[k] = states.get(k, 0) + v
        expected.append((k, states[k]))
    for io_batch in (1, 7, 32):
        pipe, _ = run_pipeline(
            specs, src, num_workers=3, backend="process",
            collect_outputs=True, io_batch=io_batch,
        )
        assert pipe.outputs == expected, f"io_batch={io_batch}"


@pytest.mark.timeout(60)
def test_stages_1_restores_ingress_only_plan():
    """stages=1 is the PR-2 compatibility mode: one parallel ingress segment,
    the rest of the graph executed in the parent tail."""
    specs = _mk_specs()
    rt = ProcessRuntime.from_chain(specs, num_workers=2, stages=1,
                                   collect_outputs=True)
    assert rt.num_stages == 1
    assert rt._tail is not None  # the SF op stays in the parent
    src = list(range(1, 400))
    rt.run(src)
    assert rt.outputs == _oracle(src)
