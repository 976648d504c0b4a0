"""One run of one cell: set-up, warm-up, the measured window, the check.

A cell (an entry of ``workloads`` in ``BENCHMARK.json``) names a
configuration, found as ``configs/<config>.json`` with the query in
``configs/<config>.py`` and its plain reference in
``configs/<config>_ref.py``, and a traffic mix, found as
``traffic/<traffic>.json``.  A per-layer metric is read by
``metrics/<metric>.py``.  Nothing here names a cell, a configuration or a
mix, so a later cell adds files and edits none.

The run drives ``Engine(EngineConfig(backend="process", ...)).open(plan)``
from this process's one thread, which is also the program's parent
supervisor.  Events are generated in NumPy during set-up; the window
only turns chunks of them into tuples and pushes them.  Every output read
is kept, packed into an int64 array, and compared after the window with
the reference, exactly and in serial order.
"""
from __future__ import annotations

import gc
import importlib.util
import json
import os
import re
import shutil
import statistics
import sys
import time
from array import array
from itertools import chain
from pathlib import Path

import numpy as np
import psutil

import faults
import loadgen
import peaks
import probe as probe_mod
import spanreduce
import tpcds
import tracereduce
import work

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
RUN_DIR = ROOT / ".bench_run"
CHUNK = 1024  # events turned into tuples and pushed per call
SAMPLE_S = 0.1  # stats() sampling period for the backlog readers
COUNTERS_S = 1.0  # period of the counters every run records in diag
LONG_CALL_S = 0.25  # a session call this long is recorded in diag
BRING_UP_TIMEOUT_S = 240.0
DRAIN_TIMEOUT_S = 60.0  # how long answers due in the window are waited for
MALFORMED = -(2 ** 63)  # fills the row of an output that is no int tuple
#: an end-to-end latency percentile, by its name: latency_p50_ms, ...
LATENCY = re.compile(r"^latency_p(\d+(?:\.\d+)?)_ms$")


def use_checkout_cache() -> None:
    """Keep jax's persistent compilation cache in the checkout, at a fixed
    path, whatever the machine sets: the program's device worker takes the
    directory from ``JAX_COMPILATION_CACHE_DIR``, which jax reads when it
    is imported, so this runs before anything imports jax."""
    if "jax" in sys.modules:
        raise RuntimeError("jax was imported before the cache was set")
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")


class NoChip(RuntimeError):
    """The run found no accelerator, or fewer chips than the cell asks."""


def _load_module(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_cell(workload: str, bench: dict | None = None) -> dict:
    """The cell named ``workload`` with its configuration, mix, query,
    reference and metric readers, all found by name."""
    bench = bench or json.loads((ROOT / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json "
                       f"(have {sorted(cells)})")
    cell = dict(cells[workload])
    cfg_name, mix = cell["config"], cell["traffic"]
    cell["cfg"] = json.loads((HERE / "configs" / f"{cfg_name}.json")
                             .read_text())
    cell["mix"] = json.loads((HERE / "traffic" / f"{mix}.json").read_text())
    cell["query"] = _load_module(HERE / "configs" / f"{cfg_name}.py",
                                 f"bench_query_{cfg_name}")
    cell["ref"] = _load_module(HERE / "configs" / f"{cfg_name}_ref.py",
                               f"bench_ref_{cfg_name}")

    def in_cell(metric):
        return workload in metric.get("workloads", [workload])

    cell["end_to_end"] = [m for m in bench["end_to_end"] if in_cell(m)]
    cell["per_layer"] = [m for m in bench["per_layer"] if in_cell(m)]
    return cell


def reader(name: str):
    """The ``read(ctx)`` of ``metrics/<name>.py``."""
    return _load_module(HERE / "metrics" / f"{name}.py",
                        f"bench_metric_{name}").read


def check_for_chip(chips: int) -> None:
    """Fail before any work where no TPU can be found, without bringing a
    jax backend up in this process (the device worker must own the
    chip)."""
    names = os.environ.get("JAX_PLATFORMS", "")
    if names and names.split(",")[0].strip() != "tpu":
        raise NoChip(f"JAX_PLATFORMS={names!r} names no TPU")
    try:
        from jax._src.hardware_utils import (
            num_available_tpu_chips_and_device_id,
        )
    except ImportError as exc:
        raise NoChip(f"cannot look for a TPU: {exc}") from exc
    found = num_available_tpu_chips_and_device_id()[0]
    if found < chips:
        raise NoChip(f"found {found} TPU chips, the cell needs {chips}")


def _pack(batch, width: int, store: array) -> int:
    """Append ``batch`` (output tuples) to ``store`` row by row; returns
    how many rows were malformed."""
    try:
        if all(type(t) is tuple and len(t) == width for t in batch):
            store.extend(chain.from_iterable(batch))
            return 0
    except (TypeError, OverflowError):
        pass
    bad = 0
    for t in batch:
        try:
            if type(t) is not tuple or len(t) != width:
                raise TypeError
            row = array("q", t)
        except (TypeError, OverflowError):
            row = array("q", [MALFORMED] * width)
            bad += 1
        store.extend(row)
    return bad


class Run:
    """State of one run; :meth:`execute` drives it end to end."""

    def __init__(self, cell: dict, seed: int, seconds: float, trace: bool,
                 *, require_chip: bool = True, fault: str | None = None,
                 layout: dict | None = None, check_cores: bool = True):
        self.cell, self.seed, self.seconds = cell, seed, seconds
        self.trace, self.require_chip, self.fault = trace, require_chip, fault
        self.cfg, self.mix = cell["cfg"], cell["mix"]
        self.layout = dict(self.cfg["layout"], **(layout or {}))
        self.check_cores = check_cores
        self.phases: dict = {}
        self.width = cell["cfg"]["out_width"]
        self.store = array("q")
        self.malformed = 0
        self.pushed = 0
        self._buf, self._buf_lo, self._buf_hi = [], 0, 0
        # when each read returned and the rows read by then; arrays, not
        # lists of tuples, so the parent's garbage collector has nothing
        # of the harness's to walk
        self.read_t, self.read_n = array("d"), array("q")
        self.push_t, self.push_lo = array("d"), array("q")
        self.samples: list = []
        self.last_stats: dict | None = None  # the newest sample's stats()
        self.counters: list = []
        self.long_calls: list = []
        self.procs: dict = {}
        self.session_call_s = 0.0

    # ------------------------------------------------------------ set-up
    def _engine_and_plan(self):
        from repro.core import Engine, EngineConfig, ProcessOptions

        kernel = faults.kernel_for(self.fault)
        ops = self.cell["query"].build(self.cfg, tpcds.COLUMNS, kernel)
        ops = self.ops = faults.wrap_ops(ops, self.fault)
        lay = self.layout
        engine = Engine(EngineConfig(
            backend="process", num_workers=lay["host_workers"],
            process=ProcessOptions(
                device_workers=lay["device_workers"],
                elastic=lay["elastic"], **self.cfg["engine"]),
        ))
        plan = engine.plan(ops)
        return engine, plan

    def _check_layout(self, plan) -> None:
        widths = plan.stage_widths()
        procs = 1 + sum(widths) + max(len(widths) - 1, 0)
        cores = len(os.sched_getaffinity(0))
        self.phases.update(cpu_count=os.cpu_count(), cores=cores,
                           processes=procs, stage_widths=widths,
                           stage_kinds=[s.kind for s in plan.stages])
        if procs > cores and self.check_cores:
            raise RuntimeError(
                f"the layout runs {procs} processes (parent, workers and "
                f"routers) on {cores} cores: it would oversubscribe the host")

    def _bring_up(self, session) -> dict:
        """Service the session until the device worker reports its backend
        (it has compiled its kernel by then)."""
        deadline = time.perf_counter() + BRING_UP_TIMEOUT_S
        while True:
            devices = session.stats()["devices"]
            if devices:
                break
            if time.perf_counter() > deadline:
                raise TimeoutError("the device worker never came up")
            session.service()
            time.sleep(0.005)
        dev = devices[0]
        self.phases["device_report_at"] = time.time()
        if self.require_chip and (dev["platform"] != "tpu"
                                  or dev["count"] < self.cell["chips"]):
            raise NoChip(f"the device worker found {dev['platform']} "
                         f"x{dev['count']}, not {self.cell['chips']} TPU")
        return dev

    # ------------------------------------------------------------ feeding
    def _tuples(self, start: int, stop: int) -> list:
        """Events ``start .. stop - 1`` as tuples, converted from the NumPy
        events ``CHUNK`` at a time and asked for in order."""
        if stop > self._buf_hi:
            hi = max(stop, self._buf_hi + CHUNK)
            fresh = list(map(tuple, tpcds.events(
                self.pool, self._buf_hi, hi).tolist()))
            self._buf = self._buf[start - self._buf_lo:] + fresh
            self._buf_lo, self._buf_hi = start, hi
        return self._buf[start - self._buf_lo:stop - self._buf_lo]

    def _read(self, session) -> None:
        self._keep(session.poll(), time.perf_counter())

    def _keep(self, batch: list, t: float) -> None:
        """Keep the outputs one read returned at time ``t``."""
        if batch:
            self.malformed += _pack(batch, self.width, self.store)
            self.read_t.append(t)
            self.read_n.append(len(self.store) // self.width)

    def _sample(self, session, now: float) -> None:
        """In the window: the backlog of every exchange ring each
        ``SAMPLE_S``, and each ``COUNTERS_S`` the counters that tie a stall
        to a ring, a worker or a process."""
        if now < self._next_sample:
            return
        self._next_sample = now + SAMPLE_S
        st = self.last_stats = session.stats()
        self.samples.append({"t": now, "backlog": st["backlog_slots"]})
        if now >= self._next_counters:
            self._next_counters = now + COUNTERS_S
            self.counters.append((now, st["egressed"], st["backlog_slots"],
                                  st["heartbeats"], _cpu_times(self.procs)))

    def _call(self, what: str, fn, *args):
        """``fn(*args)``, recorded in ``long_calls`` where it blocks the
        supervisor for ``LONG_CALL_S`` or more in the window."""
        t0 = time.perf_counter()
        out = fn(*args)
        dt = time.perf_counter() - t0
        if dt >= LONG_CALL_S and self._next_sample < float("inf"):
            self.long_calls.append([what, t0 - self.w0, dt])
        return out

    def _flood(self, session, t_end: float) -> None:
        while True:
            now = time.perf_counter()
            if now >= t_end:
                return
            self._sample(session, now)
            n = self.pushed
            self._call("push", session.push, self._tuples(n, n + CHUNK))
            self.pushed = n + CHUNK
            self._keep(self._call("poll", session.poll),
                       time.perf_counter())

    def _paced(self, session, t_end: float, until_read: int = 0,
               deadline: float = float("inf")) -> None:
        """Push on schedule until ``t_end`` and until ``until_read`` rows
        have been read (by ``deadline`` at the latest), timing the calls
        into the session."""
        sched, base = self.sched, self.t_base
        clock = time.perf_counter
        while True:
            now = clock()
            if now >= t_end and len(self.store) // self.width >= until_read:
                return
            if now > deadline:
                raise TimeoutError(
                    f"answers due in the window were not all read "
                    f"{DRAIN_TIMEOUT_S}s after it closed")
            self._sample(session, now)
            due = int(np.searchsorted(sched, now - base, side="right"))
            due = min(due, len(sched))
            if due > self.pushed:
                rows = self._tuples(self.pushed, due)
                t0 = clock()
                self.push_t.append(t0)
                self.push_lo.append(self.pushed)
                self._call("push", session.push, rows)
                self.pushed = due
            else:
                t0 = clock()
                self._call("service_once", session.service_once)
            batch = self._call("poll", session.poll)
            t1 = clock()
            self.session_call_s += t1 - t0
            self._keep(batch, t1)
            if due >= len(sched):
                raise RuntimeError("the schedule ran out before the window "
                                   "closed")

    # ------------------------------------------------------------ the run
    def execute(self) -> dict:
        t_proc = _process_start()
        if self.require_chip:
            check_for_chip(self.cell["chips"])
        run_dir = RUN_DIR / self.cell["name"]
        shutil.rmtree(run_dir, ignore_errors=True)
        probe_mod.install(run_dir)
        probe = probe_mod.Probe(run_dir)
        engine, plan = self._engine_and_plan()
        self._check_layout(plan)
        self._generate()
        t = time.time()
        session = engine.open(plan)
        self.phases["fork_s"] = time.time() - t
        try:
            return self._drive(session, probe, plan, t_proc, run_dir)
        finally:
            if not session._closed:
                session._abort()

    def _generate(self) -> None:
        """The events and, for an open loop, their schedule, from the seed.
        Made before the workers are forked, so that the device worker's
        bring-up does not compete with it for the host."""
        t = time.time()
        self.pool = tpcds.store_sales(self.cfg, self.seed,
                                      self.cfg["pool_rows"])
        if self.mix["mode"] == "poisson":
            horizon = self.mix["warmup_s"] + self.seconds + DRAIN_TIMEOUT_S
            self.sched = loadgen.arrivals(
                self.mix["rate_eps"], self.seed,
                int(self.mix["rate_eps"] * horizon))
        self.phases["generate_s"] = time.time() - t

    def _drive(self, session, probe, plan, t_proc, run_dir) -> dict:
        rated = self.mix["mode"] == "poisson"
        dev = self._bring_up(session)
        self.phases["lower_s"] = dev["lower_s"]
        self.phases["compile_s"] = dev["compile_s"]
        armed = probe.armed() or {}
        clock = time.perf_counter
        self._next_sample = self._next_counters = float("inf")
        self.w0 = 0.0

        # warm-up: the mix's own traffic, for warmup_s after bring-up
        t_warm = clock()
        self.t_base = t_warm
        if rated:
            self._paced(session, t_warm + self.mix["warmup_s"])
        else:
            self._flood(session, t_warm + self.mix["warmup_s"])
        if self.trace:
            self.t_trace0 = probe.trace_start(lambda: self._keep_going(
                session, rated))
        gc_pauses = GcPauses()
        gc.callbacks.append(gc_pauses)
        self.procs = _processes(session)
        cpu0 = _cpu_times(self.procs)
        st0 = session.stats()
        w0 = self.w0 = clock()
        setup_s = time.time() - t_proc
        self.phases["warmup_s"] = w0 - t_warm
        self.session_call_s = 0.0
        gc_pauses.on = True
        self._next_sample = self._next_counters = w0

        # the window
        w1 = w0 + self.seconds
        if rated:
            due_end = int(np.searchsorted(self.sched, w1 - self.t_base,
                                          side="right"))
            self._paced(session, w1)
            call_s = self.session_call_s
            # the counters at the window's end: its last sample, since the
            # answers due in the window are still being drained
            st_end = self.last_stats
            self._paced(session, w1, until_read=due_end,
                        deadline=w1 + DRAIN_TIMEOUT_S)
        else:
            self._flood(session, w1)
        t_end = clock()
        gc_pauses.on = False
        gc.callbacks.remove(gc_pauses)
        st1 = session.stats()
        cpu1 = _cpu_times(self.procs)
        if not rated:
            st_end = st1
        counters = spanreduce.counter_deltas(
            st0, st_end, [s.kind for s in plan.stages])
        self._next_sample = self._next_counters = float("inf")
        # events egressed in the window, as the runtime counts them
        in_window = st1["egressed"] - st0["egressed"]
        # keep the supervisor turning while the probe answers: in-flight
        # units still cross the rings, some by the parent's pipe relay
        trace_info = (probe.trace_stop(session.service) if self.trace
                      else None)
        mem = (probe.memory(session.service) if self.require_chip
               else {"peak_bytes": 0})
        session.close(drain_timeout=DRAIN_TIMEOUT_S)
        self._read(session)
        final = session.stats()
        del session
        gc.collect()

        rows_at, times = self._reads()
        buckets = _buckets(times, rows_at, w0, self.seconds)
        metrics, extra = {}, {}
        if rated:
            lat, late = self._latencies(w0, w1, due_end)
            extra.update(latency=lat, feeder_late_s=late,
                         session_call_s=call_s)
        else:
            late, call_s = None, 0.0
        for m in self.cell["end_to_end"]:
            name = m["name"]
            if name == "setup_s":
                v = setup_s
            elif name == "throughput_eps":
                v = in_window / (t_end - w0)
            elif LATENCY.match(name):
                q = float(LATENCY.match(name).group(1))
                v = 1e3 * float(np.percentile(extra["latency"], q,
                                              method="inverted_cdf"))
            else:
                raise KeyError(f"no end-to-end metric {name!r}")
            metrics[name] = {"value": v, "unit": m["unit"]}

        devices = final["devices"]
        dispatches = sum(d.get("dispatches", 0) for d in devices)
        diag = {
            "workload": self.cell["name"], "seed": self.seed,
            "seconds": self.seconds, "trace": self.trace,
            "phases": dict(self.phases, probe=armed,
                           setup_s=setup_s, window_s=w1 - w0,
                           after_window_s=t_end - w1),
            "layout": {"start": _layout(st0), "end": _layout(st1),
                       "final": _layout(final)},
            "egress_per_s": buckets,
            "counters": _counter_table(self.counters, w0),
            "long_calls": self.long_calls,
            "pushed": self.pushed, "read": len(self.store) // self.width,
            "read_in_window": in_window,
            "rows_per_dispatch_run": (self.pushed / dispatches
                                      if dispatches else None),
            "dispatches": dispatches,
            "stage_counters": counters,
            "parent_gc": gc_pauses.summary(),
            "cpu_busy": {k: (cpu1[k] - cpu0[k]) / (t_end - w0)
                         for k in cpu0 if k in cpu1 and cpu0[k] is not None
                         and cpu1[k] is not None},
        }
        if rated:
            diag["events_due_in_window"] = len(extra["latency"])
            diag["latency_ms"] = {
                q: 1e3 * float(np.percentile(extra["latency"], q,
                                             method="inverted_cdf"))
                for q in (50, 90, 99, 99.9, 100)}
            # lag by second of the window: growth means the rate is over
            # what the system sustains
            lat = extra["latency"]
            sec = np.minimum((np.arange(len(lat)) * self.seconds
                              // max(len(lat), 1)).astype(int),
                             int(self.seconds) - 1)
            diag["latency_ms_by_s"] = [
                [1e3 * float(np.percentile(lat[sec == k], q))
                 for q in (50, 99)]
                for k in range(int(self.seconds)) if (sec == k).any()]

        # the program's state is gone: the reference may run now
        checks = self.compare()
        result = {
            "correct": all(c["value"] <= c["limit"] for c in checks.values()),
            "attempted": self.pushed,
            "failed": checks["missing_rows"]["value"]
            + checks["mismatched_rows"]["value"],
            "metrics": metrics,
            "device": {"platform": dev["platform"], "kind": dev["kind"],
                       "count": dev["count"],
                       "memory_peak_bytes": mem["peak_bytes"]},
        }
        if self.trace:
            per_layer, summary = self._per_layer(
                run_dir, trace_info, dev, plan, w0, w1, late, call_s,
                counters, diag)
            result["metrics"] = per_layer
            result["device"].update(busy_s=summary["busy_s"],
                                    window_s=summary["window_s"])
            result["breakdown"] = {"device_ops": summary["device_ops"],
                                   "idle_gaps": summary["idle_gaps"]}
        result["checks"] = checks
        return {"result": result, "diag": diag}

    def _keep_going(self, session, rated: bool) -> None:
        """Traffic while the probe starts the profiler."""
        if rated:
            self._paced(session, time.perf_counter() + 0.01)
        else:
            self._flood(session, time.perf_counter() + 0.01)

    def _latencies(self, w0, w1, due_end):
        """Latency of every event due in the window, from its scheduled
        arrival to the read that returned it; and how late its push ran."""
        base = self.t_base
        due0 = int(np.searchsorted(self.sched, w0 - base, side="left"))
        counts, times = self._reads()
        idx = np.arange(due0, due_end)
        # row k was returned by the first read whose cumulative count > k
        at = np.searchsorted(counts, idx, side="right")
        if len(idx) and at[-1] >= len(times):
            raise RuntimeError("answers due in the window never came")
        due_at = base + self.sched[idx]
        lat = times[at] - due_at
        # event k went out with the last push that began at or before it
        push_lo = np.frombuffer(self.push_lo, np.int64)
        push_t = np.frombuffer(self.push_t, np.float64)
        late = push_t[np.searchsorted(push_lo, idx, side="right") - 1] \
            - due_at
        return lat, late

    def _reads(self):
        """(rows read by each read, the read's time) as arrays."""
        return (np.frombuffer(self.read_n, np.int64),
                np.frombuffer(self.read_t, np.float64))

    def compare(self, control: str | None = None) -> dict:
        """The reference over every event pushed, against every row read
        (with ``control``'s guarantee broken in what was read, for the
        tests of the comparison)."""
        got = np.frombuffer(self.store, np.int64).reshape(-1, self.width)
        got = faults.apply_control(got, control, self.seed)
        n = self.pushed
        ev = tpcds.events(self.pool, 0, n)
        want = self.cell["ref"].reference(self.cfg, tpcds.COLUMNS, ev)
        del ev
        m = min(len(got), n)
        mismatched = int(np.any(got[:m] != want[:m], axis=1).sum())
        return {
            "missing_rows": {"value": max(n - len(got), 0), "limit": 0},
            "extra_rows": {"value": max(len(got) - n, 0), "limit": 0},
            "mismatched_rows": {"value": mismatched, "limit": 0},
            "malformed_rows": {"value": self.malformed, "limit": 0},
        }

    def _per_layer(self, run_dir, trace_info, dev, plan, w0, w1, late,
                   call_s, counters, diag) -> dict:
        path = tracereduce.newest_xplane(str(run_dir / "trace"))
        t0 = self.t_trace0
        t1 = trace_info["t"]
        summary = tracereduce.reduce(tracereduce.extract(path), t1 - t0)
        shutil.rmtree(run_dir / "trace", ignore_errors=True)
        counts, times = self._reads()

        def rows_by(t):
            k = int(np.searchsorted(times, t, side="right"))
            return int(counts[k - 1]) if k else 0

        kinds = [s.kind for s in plan.stages]
        codes = next(op.schema for op in plan.ops if op.kind == "device")

        def ring_backlog(kind):
            if kind not in kinds or not self.samples:
                return None
            i = kinds.index(kind)
            return statistics.fmean(s["backlog"][i] for s in self.samples)

        ctx = {
            "trace": summary, "rows_traced": rows_by(t1) - rows_by(t0),
            "device": dev, "peaks": peaks.peaks(dev["kind"]),
            "stage_bytes": lambda rows: work.device_stage_bytes(rows, codes),
            "ring_backlog": ring_backlog, "session_call_s": call_s,
            "window_s": w1 - w0, "feeder_late_s": late, "reader": reader,
            "stage_counters": counters, "cfg": self.cfg,
            "device_op": next(op for op in self.ops if op.kind == "device"),
        }
        per_layer = {}
        for m in self.cell["per_layer"]:
            v = reader(m["name"])(ctx)
            if v is not None:
                per_layer[m["name"]] = {"value": v, "unit": m["unit"]}
        diag["trace_summary"] = dict(summary, probe_window_s=t1 - t0,
                             rows_traced=ctx["rows_traced"])
        return per_layer, summary


def _layout(st: dict) -> dict:
    return {"stage_widths": st["stage_widths"], "replans": st["replans"],
            "restarts": st["restarts"], "recoveries": st["recoveries"]}


def _buckets(times, rows_at, w0, seconds) -> list:
    """Rows read in each whole second of the window."""
    out = []
    for k in range(int(seconds)):
        lo = np.searchsorted(times, w0 + k, side="right")
        hi = np.searchsorted(times, w0 + k + 1, side="right")
        a = rows_at[lo - 1] if lo else 0
        b = rows_at[hi - 1] if hi else 0
        out.append(int(b - a))
    return out


class GcPauses:
    """The parent's garbage-collector pauses, by generation, while on."""

    def __init__(self):
        self.pauses = {0: [], 1: [], 2: []}
        self._t0 = 0.0
        self.on = False

    def __call__(self, phase, info):
        if not self.on:
            return
        if phase == "start":
            self._t0 = time.perf_counter()
        else:
            self.pauses[info["generation"]].append(
                time.perf_counter() - self._t0)

    def summary(self) -> dict:
        return {f"gen{g}": {"n": len(p), "total_s": sum(p),
                            "max_s": max(p, default=0.0)}
                for g, p in self.pauses.items()}


def _processes(session) -> dict:
    """This process and each child, by role where the runtime names it:
    ``w<stage>.<worker>`` for a stage worker, ``r<stage>`` for a router,
    else the pid."""
    rt = getattr(session, "_rt", None)
    roles = {}
    for proc, info in zip(getattr(rt, "_procs", ()), getattr(rt, "_pinfo",
                                                            ())):
        if proc is not None and proc.pid:
            roles[proc.pid] = (info[0][0] + ".".join(map(str, info[1:])))
    me = psutil.Process()
    out = {"parent": me}
    for child in me.children():
        out[roles.get(child.pid, str(child.pid))] = child
    return out


def _cpu_times(procs: dict) -> dict:
    """CPU seconds of each process so far (None once it is gone)."""
    out = {}
    for name, proc in procs.items():
        try:
            out[name] = sum(proc.cpu_times()[:2])
        except psutil.Error:
            out[name] = None
    return out


def _counter_table(counters: list, w0: float) -> dict:
    """The window's counters as one row per sample: its time in the
    window, then, since the sample before, the events egressed, each
    ring's backlog, each worker's heartbeats and each process's CPU
    share."""
    if not counters:
        return {}
    procs = list(counters[0][4])
    rows = []
    for prev, cur in zip(counters, counters[1:]):
        dt = cur[0] - prev[0]
        beats = [b - a for ra, rb in zip(prev[3], cur[3])
                 for a, b in zip(ra, rb)]
        cpu = [round((cur[4][k] - prev[4][k]) / dt, 3)
               if cur[4].get(k) is not None and prev[4][k] is not None
               else None for k in procs]
        rows.append([round(cur[0] - w0, 3), cur[1] - prev[1], cur[2],
                     beats, cpu])
    return {"columns": ["t", "egressed", "backlog", "beats", "cpu"],
            "procs": procs, "rows": rows}


def _process_start() -> float:
    """This process's start on the wall clock."""
    return psutil.Process().create_time()
