"""Stage counters and device-stage spans on the process backend.

Every stage worker keeps monotone ``busy_ns``/``wait_ns``/``blocked_ns``/
``rows`` counters in its ingress ring's header, every exchange router the
first three in its upstream reorder ring's header, and the supervisor its
crank's time in-process; ``Session.stats()`` reports all three.  The span
helper (:mod:`repro.core.trace`) records nothing until a jax device
executor arms it, and imports no jax.
"""
import os
import signal
import subprocess
import sys
import time

import pytest

from repro.core import Engine, EngineConfig, OpSpec, ProcessOptions, trace
from repro.core.shm import ShmReorderRing, ShmSpscRing

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _inc(v):
    return [v + 1]


def _key(v):
    return v % 7


def _count(s, k, v):
    return s + 1, [(k, s + 1)]


def _zero():
    return 0


def _chain():
    return [
        OpSpec("inc", "stateless", _inc),
        OpSpec("count", "partitioned", _count, key_fn=_key,
               num_partitions=4, init_state=_zero),
    ]


def _open(**process):
    eng = Engine(EngineConfig(backend="process", num_workers=2,
                              collect_outputs=True,
                              process=ProcessOptions(**process)))
    return eng.open(eng.plan(_chain()))


def _oracle(n):
    seen, out = {}, []
    for v in range(n):
        k = _key(v + 1)
        seen[k] = seen.get(k, 0) + 1
        out.append((k, seen[k]))
    return out


def _procs(st):
    """Each worker's and router's counters, flattened."""
    return [w for group in st["stage_counters"] for w in group] + list(
        st["router_counters"])


def _ages(rt, st):
    """Seconds since each process of :func:`_procs` started, in order, from
    ``/proc/<pid>/stat`` (clock ticks since boot) and the boot clock."""
    tick = os.sysconf("SC_CLK_TCK")
    now = time.clock_gettime(time.CLOCK_BOOTTIME)

    def age(pid):
        with open(f"/proc/{pid}/stat") as f:
            fields = f.read().rsplit(")", 1)[1].split()
        return now - int(fields[19]) / tick  # field 22: starttime

    pids = {info: p.pid for p, info in zip(rt._procs, rt._pinfo)}
    keys = [("worker", s, w) for s, group in enumerate(st["stage_counters"])
            for w in range(len(group))]
    keys += [("router", s + 1) for s in range(len(st["router_counters"]))]
    return [age(pids[k]) for k in keys]


# ------------------------------------------------------------- shm header
def test_ring_counters_round_trip_and_survive_a_new_consumer():
    ring = ShmSpscRing(f"repro_test_{os.getpid()}_ctr", slots=8, slot_bytes=64)
    drain = ShmReorderRing(f"repro_test_{os.getpid()}_ctr", size=4,
                           payload_bytes=32)
    try:
        assert ring.read_counters() == dict.fromkeys(ShmSpscRing.COUNTERS, 0)
        ring.counters[:] = [5, 6, 7, 8, 9, 10]
        ring.store_counters()
        ring.counters = [0] * 6  # a re-forked consumer's stale mirror
        ring.sync_consumer()
        assert ring.counters == [5, 6, 7, 8, 9, 10]
        assert ring.read_counters()["hold_units"] == 10
        # the counters sit past the heartbeat and leave the cursors alone
        ring.beat()
        assert ring.heartbeat() == 1 and ring.put(1, 2, b"x")
        assert ring.get() == (1, 2, b"x")
        drain.counters[:] = [1, 2, 3]
        drain.store_counters()
        drain.counters = [0, 0, 0]
        assert drain.sync_drainer() == 1  # never committed: serial 1
        assert drain.counters == [1, 2, 3]
        assert drain.read_counters() == {"busy_ns": 1, "wait_ns": 2,
                                         "blocked_ns": 3}
    finally:
        for r in (ring, drain):
            r.close()
            r.unlink()


# ----------------------------------------------------------- live counters
@pytest.mark.timeout(60)
def test_counters_cover_each_process_time_and_rows_match_events():
    n = 20000
    s = _open()
    try:
        s.push(range(n))
        assert list(s.results(max_items=n, timeout=30)) == _oracle(n)
        time.sleep(0.3)
        st = s.stats()
        for c, age in zip(_procs(st), _ages(s._rt, st)):
            covered = (c["busy_ns"] + c["wait_ns"] + c["blocked_ns"]) * 1e-9
            # a start time ticks in hundredths of a second
            assert 0.9 * age <= covered <= age + 0.02, (c, age)
        assert st["supervisor_counters"]["ingress_ns"] > 0
        assert st["supervisor_counters"]["egress_ns"] > 0
    finally:
        s.close()
    final = s.stats()  # the last values, read when the stream stopped
    assert [sum(w["rows"] for w in g) for g in final["stage_counters"]] == [
        n, n]
    assert all(c["busy_ns"] > 0 for c in _procs(final))
    assert "hold_ns" not in final["stage_counters"][0][0]


@pytest.mark.timeout(60)
def test_idle_source_raises_wait_only():
    s = _open()
    try:
        s.push(range(100))
        assert len(list(s.results(max_items=100, timeout=30))) == 100
        time.sleep(0.2)
        a = _procs(s.stats())
        time.sleep(0.4)
        b = _procs(s.stats())
        for x, y in zip(a, b):
            assert y["busy_ns"] == x["busy_ns"], (x, y)
            assert y["blocked_ns"] == x["blocked_ns"], (x, y)
            assert y["wait_ns"] - x["wait_ns"] >= 0.25e9, (x, y)
    finally:
        s.close()


@pytest.mark.timeout(60)
def test_paused_downstream_raises_router_blocked_only():
    n = 20000
    s = _open()
    rt = s._rt
    victims = [p.pid for p in rt.worker_groups()[1]]
    try:
        for pid in victims:
            os.kill(pid, signal.SIGSTOP)
        pushed = 0
        while pushed < n and s.try_push(pushed):
            pushed += 1
        time.sleep(0.3)  # the router fills the keyed stage's window
        a = s.stats()["router_counters"][0]
        time.sleep(0.4)
        b = s.stats()["router_counters"][0]
        assert b["busy_ns"] == a["busy_ns"], (a, b)
        assert b["wait_ns"] == a["wait_ns"], (a, b)
        assert b["blocked_ns"] - a["blocked_ns"] >= 0.25e9, (a, b)
    finally:
        for pid in victims:
            os.kill(pid, signal.SIGCONT)
    s.push(range(pushed, n))
    assert list(s.results(max_items=n, timeout=30)) == _oracle(n)
    s.close()


@pytest.mark.timeout(60)
def test_counters_stay_monotone_across_a_worker_kill():
    n = 40000
    s = _open()
    rt = s._rt
    try:
        s.push(range(n // 2))
        assert len(list(s.results(max_items=n // 2, timeout=30))) == n // 2
        time.sleep(0.05)
        before = s.stats()["stage_counters"][0][0]
        assert before["rows"] > 0
        os.kill(rt.worker_groups()[0][0].pid, signal.SIGKILL)
        deadline = time.monotonic() + 20
        while rt.restarts < 1:
            assert time.monotonic() < deadline, "worker never re-forked"
            s.service()
            time.sleep(0.01)
        s.push(range(n // 2, n))
        assert len(list(s.results(max_items=n // 2, timeout=30))) == n // 2
        time.sleep(0.05)
        after = s.stats()["stage_counters"][0][0]
        for k, v in before.items():
            assert after[k] >= v, (k, before, after)
        assert after["rows"] > before["rows"]
    finally:
        s.close()


@pytest.mark.timeout(60)
def test_device_stage_counts_each_unit_held():
    from repro.columnar import Schema, device_op

    n = 3000
    ops = [device_op("dev", "affine", Schema.of("i4", scalar=True),
                     params={"a": 2, "b": 1}, backend="numpy")]
    eng = Engine(EngineConfig(
        backend="process", num_workers=1, batch_size=16, collect_outputs=True,
        process=ProcessOptions(columnar=True, device_batch=64,
                               device_backend="numpy",
                               checkpoint_interval=0, slot_bytes=1024),
    ))
    s = eng.open(eng.plan(ops))
    s.push(range(n))
    assert list(s.results(max_items=n, timeout=30)) == [
        2 * v + 1 for v in range(n)]
    # one ring slot per unit here, and no barriers: slots consumed = units
    units = s._rt._exchanges[0].rings[0].consumed_slots()
    s.close()
    dev = s.stats()["stage_counters"][0][0]
    assert dev["rows"] == n
    assert dev["hold_units"] == units >= n // 16
    assert dev["hold_ns"] > 0


# -------------------------------------------------------------- span helper
def test_span_is_a_shared_no_op_until_armed():
    assert trace.span(trace.DEVICE_SYNC) is trace.span(trace.DEVICE_WAIT)
    seen = []

    class Rec:
        def __init__(self, name):
            seen.append(name)

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return False

    trace.arm(Rec)
    try:
        for name in trace.DEVICE_SPANS:
            with trace.span(name):
                pass
    finally:
        trace.arm(None)
    assert seen == list(trace.DEVICE_SPANS)
    assert all(n.startswith("stream.device.") for n in seen)
    with trace.span(trace.DEVICE_PUBLISH) as got:
        assert got is None  # disarmed again: the no-op context


@pytest.mark.timeout(60)
def test_non_device_worker_never_imports_jax():
    script = """
import sys
from repro.core import Engine, EngineConfig, OpSpec, trace
assert "jax" not in sys.modules
def probe(v):
    return [("jax" in sys.modules, "numpy" in sys.modules)]
eng = Engine(EngineConfig(backend="process", num_workers=2,
                          collect_outputs=True))
out = eng.run([OpSpec("probe", "stateless", probe)], range(64))
outs = out.handle().outputs
assert len(outs) == 64 and not any(j for j, _ in outs), outs[:3]
assert "jax" not in sys.modules
print("NOJAX")
"""
    proc = subprocess.run(
        [sys.executable, "-c", script], capture_output=True, text=True,
        timeout=50, cwd=REPO,
        env={**os.environ, "PYTHONPATH": os.path.join(REPO, "src")},
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "NOJAX" in proc.stdout
