"""The program's spans and counters as the benchmark reduces them: span
counts and totals, idle time by the innermost program span, counter
deltas, the readers of the per-layer metrics built on them, and a CPU
trace of a device worker that holds every span name."""
import gzip
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

CHIP = Path(__file__).resolve().parents[1]
ROOT = CHIP.parents[1]
sys.path[:0] = [str(CHIP), str(ROOT / "src")]

import harness  # noqa: E402
import spanreduce  # noqa: E402
import tracereduce  # noqa: E402
from repro.core import trace  # noqa: E402

#: the readers of the per-layer metrics built on the spans and counters
READERS = ("device_h2d_ms_per_dispatch", "device_d2h_ms_per_dispatch",
           "device_worker_us_per_event", "device_worker_blocked_frac",
           "keyed_worker_us_per_event", "device_hold_ms_mean.rated",
           "supervisor_busy_frac.rated")


def _ev(plane, line, name, start, dur):
    return [plane, line, name, float(start), float(dur)]


DEV, HOST = "/device:TPU:0", "/host:CPU"
EVENTS = [
    _ev(HOST, "main", "window", 0, 1000),
    _ev(DEV, "XLA Ops", "copy", 100, 100),  # busy 100-200
    _ev(DEV, "XLA Ops", "affine", 600, 100),  # busy 600-700
    # 0-100: a wait, around a jax event of its own
    _ev(HOST, "main", trace.DEVICE_WAIT, 0, 90),
    _ev(HOST, "main", "PjRt", 20, 60),
    # 200-600: a sync with a jax read-back inside it; midpoint 400
    _ev(HOST, "main", trace.DEVICE_SYNC, 210, 300),
    _ev(HOST, "main", "np.asarray(jax.Array)", 300, 200),
    _ev(HOST, "main", trace.DEVICE_DISPATCH, 180, 400),  # outer of sync
    # 700-1000: no program span at 850
    _ev(HOST, "main", trace.DEVICE_PUBLISH, 700, 50),
    _ev(HOST, "worker", trace.DEVICE_DECODE, 990, 40),  # cut by the window
]


def test_idle_goes_to_the_innermost_program_span():
    got = spanreduce.idle_by_span(EVENTS, 1000e-9)
    assert got == {
        spanreduce.OUTSIDE: pytest.approx(300e-9),
        trace.DEVICE_SYNC: pytest.approx(400e-9),
        trace.DEVICE_WAIT: pytest.approx(100e-9),
    }
    # the same gaps as tracereduce's idle_gaps, only named otherwise
    r = tracereduce.reduce(EVENTS, 1000e-9)
    assert sum(got.values()) == pytest.approx(r["window_s"] - r["busy_s"])
    assert dict(r["idle_gaps"])["np.asarray(jax.Array)"] == pytest.approx(
        400e-9)


def test_span_counts_and_totals_are_exact():
    got = spanreduce.spans(EVENTS)
    assert got == {
        trace.DEVICE_WAIT: [1, pytest.approx(90e-9)],
        trace.DEVICE_SYNC: [1, pytest.approx(300e-9)],
        trace.DEVICE_DISPATCH: [1, pytest.approx(400e-9)],
        trace.DEVICE_PUBLISH: [1, pytest.approx(50e-9)],
        trace.DEVICE_DECODE: [1, pytest.approx(40e-9)],
    }
    # a shorter window cuts what the stop recorded
    cut = spanreduce.spans(EVENTS, 1000e-9)
    assert cut[trace.DEVICE_DECODE] == [1, pytest.approx(10e-9)]
    assert spanreduce.spans(EVENTS, 650e-9)[trace.DEVICE_DISPATCH] == [
        1, pytest.approx(400e-9)]
    assert trace.DEVICE_PUBLISH not in spanreduce.spans(EVENTS, 650e-9)


def test_a_trace_without_program_spans_is_all_outside():
    with gzip.open(CHIP / "tests" / "data" / "chip_trace.json.gz",
                   "rt") as f:
        rec = json.load(f)
    got = spanreduce.idle_by_span(rec["events"], rec["window_s"])
    r = tracereduce.reduce(rec["events"], rec["window_s"])
    assert list(got) == [spanreduce.OUTSIDE]
    assert got[spanreduce.OUTSIDE] == pytest.approx(r["window_s"]
                                                    - r["busy_s"])
    assert spanreduce.spans(rec["events"], rec["window_s"]) == {}
    assert spanreduce.idle_by_span([_ev(HOST, "t", "x", 0, 10)]) == {}


def _stats(shift=0, replans=0):
    return {
        "replans": replans, "restarts": 0,
        "stage_counters": [
            [{"busy_ns": 10 + shift, "wait_ns": 5, "blocked_ns": 0,
              "rows": 100 + shift}],
            [{"busy_ns": 40 + 2 * shift, "wait_ns": 1, "blocked_ns": shift,
              "rows": 100 + shift, "hold_ns": 7 * shift,
              "hold_units": shift}],
            [{"busy_ns": 20 + shift, "wait_ns": 0, "blocked_ns": 0,
              "rows": 50}, {"busy_ns": 30, "wait_ns": 0, "blocked_ns": 0,
                            "rows": 50 + shift}],
        ],
        "router_counters": [{"busy_ns": 1, "wait_ns": 2, "blocked_ns": 3}] * 2,
        "supervisor_counters": {"ingress_ns": shift, "egress_ns": 2 * shift,
                                "relay_ns": 0},
    }


KINDS = ["stateless", "device", "keyed"]


def test_counter_deltas_over_the_window():
    d = spanreduce.counter_deltas(_stats(), _stats(1000), KINDS)
    assert d["kinds"] == KINDS
    assert d["stage_counters"][1] == [{
        "busy_ns": 2000, "wait_ns": 0, "blocked_ns": 1000, "rows": 1000,
        "hold_ns": 7000, "hold_units": 1000}]
    assert d["router_counters"][0] == {"busy_ns": 0, "wait_ns": 0,
                                       "blocked_ns": 0}
    assert d["supervisor_counters"] == {"ingress_ns": 1000,
                                        "egress_ns": 2000, "relay_ns": 0}
    assert [w["rows"] for w in spanreduce.workers(d, "keyed")] == [0, 1000]
    assert spanreduce.workers(None, "keyed") == []
    # a plan that moved in the window, or a program without the counters
    assert spanreduce.counter_deltas(_stats(), _stats(1, replans=1),
                                     KINDS) is None
    assert spanreduce.counter_deltas({"replans": 0, "restarts": 0},
                                     _stats(), KINDS) is None


def _filled_ctx():
    spans = spanreduce.spans(EVENTS)
    return {
        "trace": dict(tracereduce.reduce(EVENTS), spans=spans,
                      idle_by_span=spanreduce.idle_by_span(EVENTS)),
        "stage_counters": spanreduce.counter_deltas(_stats(), _stats(1000),
                                                    KINDS),
        "window_s": 2e-6,
    }


def test_each_new_reader_reads_a_filled_context():
    ctx = _filled_ctx()
    got = {name: harness.reader(name)(ctx) for name in READERS}
    assert got == {
        "device_h2d_ms_per_dispatch": pytest.approx(400e-9 * 1e3),
        "device_d2h_ms_per_dispatch": pytest.approx(300e-9 * 1e3),
        "device_worker_us_per_event": pytest.approx(2.0e-3),
        "device_worker_blocked_frac": pytest.approx(0.5),
        "keyed_worker_us_per_event": pytest.approx(1e-3),
        "device_hold_ms_mean.rated": pytest.approx(7e-6),
        "supervisor_busy_frac.rated": pytest.approx(1.5),
    }


@pytest.mark.parametrize("name", sorted(READERS))
def test_each_new_reader_returns_nothing_on_the_old_context(name):
    old = {"trace": None, "rows_traced": 0, "device": None,
           "ring_backlog": lambda kind: None, "session_call_s": 0.0,
           "window_s": 1.0, "feeder_late_s": None,
           "reader": harness.reader}
    assert harness.reader(name)(old) is None
    # a trace of a program without spans, counters of a plan that moved
    old["trace"] = tracereduce.reduce(
        [e for e in EVENTS if not e[2].startswith(spanreduce.PREFIX)])
    old["stage_counters"] = None
    assert harness.reader(name)(old) is None


def test_device_worker_traced_on_the_cpu_records_every_span():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, str(CHIP / "tests" / "_cpu_trace.py")],
        capture_output=True, text=True, timeout=240, env=env,
        cwd=str(ROOT))
    lines = [x for x in proc.stdout.splitlines() if x.startswith("{")]
    assert proc.returncode == 0 and lines, proc.stderr[-4000:]
    got = json.loads(lines[-1])
    spans = got["spans"]
    assert sorted(spans) == sorted(trace.DEVICE_SPANS)
    # one dispatch and one sync span per dispatch, a decode and a publish
    # span per unit
    assert spans[trace.DEVICE_DISPATCH][0] == got["dispatches"] > 0
    assert spans[trace.DEVICE_SYNC][0] == got["dispatches"]
    assert spans[trace.DEVICE_DECODE][0] == spans[trace.DEVICE_PUBLISH][0]
    assert spans[trace.DEVICE_WAIT][0] >= 1


#: cells whose traced runs, between them, list every reader in READERS
TRACED_CELLS = ("store_sales_item.uniform", "store_sales_item.rated")


@pytest.fixture(scope="module")
def traced_runs():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, str(CHIP / "tests" / "_cpu_trace.py"),
         *TRACED_CELLS],
        capture_output=True, text=True, timeout=300, env=env,
        cwd=str(ROOT))
    lines = [json.loads(x) for x in proc.stdout.splitlines()
             if x.startswith("{")]
    assert proc.returncode == 0 and len(lines) == len(TRACED_CELLS), (
        proc.stderr[-4000:])
    return {line["workload"]: line for line in lines}


@pytest.mark.parametrize("workload", TRACED_CELLS)
def test_traced_run_fills_its_readers_through_per_layer(traced_runs,
                                                        workload):
    got = traced_runs[workload]
    cell = harness.load_cell(workload)
    listed = {m["name"] for m in cell["per_layer"]} & set(READERS)
    assert listed and listed <= set(got["metrics"]), got["metrics"]
    assert all(got["metrics"][name] > 0 for name in listed
               if name != "device_worker_blocked_frac")
    assert got["correct"] and got["stage_counters"]
    assert got["breakdown"] == ["device_ops", "idle_gaps"]
    assert got["idle_by_span"][trace.DEVICE_WAIT] > 0
    # the readers' context holds the cell's configuration and device op
    assert got["cfg"] == cell["config"]
    assert got["device_op"] == {
        "name": "price_convert", "kind": "device",
        "columns": len(cell["cfg"]["projection"]), "kernel": "affine_pallas",
        "params": {"a": 10, "b": 0}}


def test_the_traced_cells_list_every_new_reader():
    listed = set()
    for workload in TRACED_CELLS:
        listed |= {m["name"] for m in harness.load_cell(workload)["per_layer"]}
    assert set(READERS) <= listed
