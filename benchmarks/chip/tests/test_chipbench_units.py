"""The benchmark's own arithmetic, on the CPU: data generation, traffic,
the bytes function, the peaks table, the trace reduction, discovery by
name, ``BENCHMARK.json`` against its format, and a run that finds no
chip."""
import gzip
import json
import os
import re
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

CHIP = Path(__file__).resolve().parents[1]
ROOT = CHIP.parents[1]
sys.path[:0] = [str(CHIP), str(ROOT / "src")]

import harness  # noqa: E402
import loadgen  # noqa: E402
import peaks  # noqa: E402
import sweep  # noqa: E402
import tpcds  # noqa: E402
import tracereduce  # noqa: E402
import work  # noqa: E402

BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in BENCH["workloads"]]
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def _cfg(name="store_sales_item"):
    return json.loads((CHIP / "configs" / f"{name}.json").read_text())


# ------------------------------------------------------------------ data
def test_store_sales_rows_keep_the_spec_identities():
    ev = tpcds.store_sales(_cfg(), 7, 20_000).astype(np.int64)
    c = {n: ev[:, i] for i, n in enumerate(tpcds.COLUMNS)}
    q = c["ss_quantity"]
    assert ev.shape == (20_000, 24)
    assert (c["ss_ext_sales_price"] == c["ss_sales_price"] * q).all()
    assert (c["ss_ext_list_price"] == c["ss_list_price"] * q).all()
    assert (c["ss_ext_discount_amt"]
            == c["ss_ext_list_price"] - c["ss_ext_sales_price"]).all()
    assert (c["ss_net_profit"]
            == c["ss_net_paid"] - c["ss_ext_wholesale_cost"]).all()
    for name, (lo, hi) in _cfg()["domains"].items():
        assert lo <= c[name].min() and c[name].max() <= hi, name
    # x10 (cents to mills) stays exact in int32
    assert np.abs(ev).max() * 10 < 2 ** 31


def test_item_skew_is_zipf_and_its_head_does_not_move_with_the_seed():
    cfg = _cfg()
    a = tpcds.store_sales(cfg, 1, 200_000)[:, tpcds.COL["ss_item_sk"]]
    b = tpcds.store_sales(cfg, 2, 200_000)[:, tpcds.COL["ss_item_sk"]]
    hot_a, n_a = np.unique(a, return_counts=True)
    hot_b, n_b = np.unique(b, return_counts=True)
    assert hot_a[n_a.argmax()] == hot_b[n_b.argmax()]
    share = n_a.max() / len(a)
    assert 0.11 < share < 0.15  # about 13% on the hottest item


def test_uniform_items_spread_the_pool_over_the_whole_domain():
    cfg = _cfg("store_sales_item_uniform")
    item = tpcds.store_sales(cfg, 2 ** 31 + 11, cfg["pool_rows"])[
        :, tpcds.COL["ss_item_sk"]]
    _, counts = np.unique(item, return_counts=True)
    assert counts.max() <= 1e-4 * len(item)  # no item over 0.01% of rows
    assert len(counts) >= 150_000  # about 188,400 expected of 204,000
    lo, hi = cfg["domains"]["ss_item_sk"]
    assert lo <= item.min() and item.max() <= hi
    # the item configuration's file, but for the skew and what it says
    item_cfg = _cfg()
    differ = {k for k in item_cfg if item_cfg[k] != cfg.get(k)}
    assert differ == {"name", "source", "deployment", "item_zipf",
                      "keyed_state", "assumed"}
    assert set(cfg) == set(item_cfg) and cfg["item_zipf"] == 0


def test_events_cycle_the_pool_and_stamp_ev_id():
    pool = tpcds.store_sales(_cfg(), 3, 100)
    ev = tpcds.events(pool, 95, 110)
    assert ev[:, tpcds.EV_ID].tolist() == list(range(95, 110))
    assert (ev[5, :23] == pool[0, :23]).all()
    assert pool[:, tpcds.EV_ID].max() == 0


def test_arrivals_offer_the_same_load_for_every_seed():
    a = loadgen.arrivals(1000.0, 1, 5000)
    b = loadgen.arrivals(1000.0, 2 ** 31 + 5, 5000)
    assert np.isclose(a[-1], b[-1])
    gaps_a, gaps_b = np.diff(a, prepend=0.0), np.diff(b, prepend=0.0)
    assert np.allclose(np.sort(gaps_a), np.sort(gaps_b))
    assert abs(a[-1] - 5.0) < 0.01
    assert not np.allclose(gaps_a, gaps_b)


def test_latency_percentiles_are_named_by_their_metric():
    assert harness.LATENCY.match("latency_p50_ms").group(1) == "50"
    assert harness.LATENCY.match("latency_p99.9_ms").group(1) == "99.9"
    assert harness.LATENCY.match("setup_s") is None


def test_counter_table_ties_each_interval_to_rings_workers_processes():
    counters = [
        (10.0, 100, [0, 5], [[1, 1], [2]], {"parent": 1.0, "w1.0": 2.0}),
        (11.0, 400, [3, 9], [[4, 1], [7]], {"parent": 1.5, "w1.0": None}),
    ]
    table = harness._counter_table(counters, 9.5)
    assert table["procs"] == ["parent", "w1.0"]
    assert table["rows"] == [[1.5, 300, [3, 9], [3, 0, 5], [0.5, None]]]
    assert harness._counter_table([], 0.0) == {}


def test_lag_growth_compares_the_last_third_with_the_floor():
    assert sweep.lag_growth([50, 52, 49, 51, 50, 50]) == pytest.approx(
        1.0, abs=0.03)
    # one late spike is no growth; a lag that ends above its floor is
    assert sweep.lag_growth([50, 52, 49, 51, 50, 90, 50]) < sweep.GROWTH
    assert sweep.lag_growth([50, 60, 100, 140, 200, 250]) > sweep.GROWTH
    assert sweep.lag_growth([400, 500, 100, 300, 410]) > sweep.GROWTH


# ------------------------------------------------------------ yardstick
def test_device_stage_bytes():
    assert work.device_stage_bytes(256, ["i4"] * 12) == 256 * 12 * 4 * 2
    assert work.device_stage_bytes(10, ["i4", "f8"], ["i8"]) == 10 * 20


def test_peaks_by_device_kind_and_unknown_kind_raises():
    assert peaks.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError, match="no published peaks"):
        peaks.peaks("TPU v99")


def _ev(plane, line, name, start, dur):
    return [plane, line, name, float(start), float(dur)]


def test_trace_reduction_on_synthetic_events():
    dev, host = "/device:TPU:0", "/host:CPU"
    events = [
        _ev(host, "t1", "window", 0, 1000),
        _ev(dev, "XLA Modules", "jit_fn", 100, 300),
        _ev(dev, "XLA Modules", "jit_fn", 600, 100),
        _ev(dev, "XLA Ops", "add", 100, 200),
        _ev(dev, "XLA Ops", "mul", 250, 150),  # overlaps add
        _ev(dev, "XLA Ops", "add", 600, 100),
        _ev(host, "t1", "np.asarray", 420, 150),  # inside the 400-600 gap
    ]
    r = tracereduce.reduce(events)
    assert r["window_s"] == pytest.approx(1000e-9)
    assert r["busy_s"] == pytest.approx(400e-9)  # 100-400 and 600-700
    assert r["op_s"] == pytest.approx(450e-9)
    assert r["launches"] == 2
    assert r["device_ops"][0] == ["add", pytest.approx(300e-9)]
    gaps = dict(r["idle_gaps"])
    assert gaps["np.asarray"] == pytest.approx(200e-9)
    assert gaps["window"] == pytest.approx(400e-9)  # 0-100 and 700-1000
    # a window shorter than the trace clips what the stop itself recorded
    r = tracereduce.reduce(events, 650e-9)
    assert r["window_s"] == pytest.approx(650e-9)
    assert r["busy_s"] == pytest.approx(350e-9)  # 100-400 and 600-650


def test_trace_reduction_on_a_recorded_chip_trace():
    with gzip.open(CHIP / "tests" / "data" / "chip_trace.json.gz",
                   "rt") as f:
        rec = json.load(f)
    r = tracereduce.reduce(rec["events"], rec["window_s"])
    assert r["launches"] == 5  # one program per dispatch
    for key, want in rec["reduced"].items():
        if isinstance(want, float):
            assert r[key] == pytest.approx(want), key
        else:
            assert r[key] == want, key
    assert 0 < r["busy_s"] < r["window_s"]


def test_recorded_chip_trace_reduces_as_before_spans_were_added():
    with gzip.open(CHIP / "tests" / "data" / "chip_trace.json.gz",
                   "rt") as f:
        rec = json.load(f)
    before = json.loads((CHIP / "tests" / "data"
                         / "chip_trace_reduced.json").read_text())
    r = tracereduce.reduce(rec["events"], rec["window_s"])
    for key in ("device_ops", "idle_gaps", "op_s", "busy_s", "launches",
                "window_s", "device_planes"):
        assert r[key] == before[key], key
    # the program recorded no spans in that trace: all idle is outside
    assert r["spans"] == {}
    assert list(r["idle_by_span"]) == ["outside_program_span"]


def test_trace_without_a_device_plane_is_refused():
    with pytest.raises(ValueError, match="no device plane"):
        tracereduce.reduce([_ev("/host:CPU", "t", "x", 0, 10)])


# ------------------------------------------------------------ discovery
@pytest.mark.parametrize("workload", CELLS)
def test_cell_is_found_by_name(workload):
    cell = harness.load_cell(workload)
    assert cell["cfg"]["name"] == cell["config"]
    assert cell["mix"]["mode"] in ("closed", "poisson")
    assert callable(cell["query"].build)
    assert callable(cell["ref"].reference)
    names = {m["name"] for m in cell["end_to_end"]}
    assert "setup_s" in names and len(names) >= 2
    assert cell["per_layer"]
    for m in cell["per_layer"]:
        assert callable(harness.reader(m["name"]))


def test_reference_imports_nothing_of_the_program():
    for path in (CHIP / "configs").glob("*_ref.py"):
        assert "repro" not in path.read_text(), path


def test_unknown_workload_is_refused():
    with pytest.raises(KeyError, match="no workload"):
        harness.load_cell("no_such.cell")


def test_reader_returns_nothing_where_it_finds_nothing():
    ctx = {"trace": None, "rows_traced": 0, "device": None,
           "ring_backlog": lambda kind: None, "session_call_s": 0.0,
           "window_s": 1.0, "feeder_late_s": None,
           "reader": harness.reader}
    for m in BENCH["per_layer"]:
        assert harness.reader(m["name"])(ctx) is None, m["name"]


# --------------------------------------------------------------- format
def test_benchmark_json_keeps_its_format():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["paths"] == ["benchmarks/chip"]
    assert 1 <= BENCH["run_seconds"] <= 51
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    assert e2e["setup_s"]["bound"] <= 0.25
    for m in BENCH["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25 and m["source"] in (
            "host_clock", "device_trace")
    names = [x["name"] for part in ("configs", "workloads", "end_to_end",
                                    "per_layer") for x in BENCH[part]]
    assert len(names) == len(set(names))
    for n in names:
        assert NAME.match(n), n
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    for c in BENCH["configs"]:
        assert (ROOT / c["file"]).is_file()
        assert c["file"].startswith("benchmarks/chip/")
    by_cell = {w["name"]: w for w in BENCH["workloads"]}
    for m in BENCH["per_layer"]:
        assert m["moves"] in e2e
        for w in m.get("workloads", by_cell):
            assert w in e2e[m["moves"]].get("workloads", by_cell), (
                m["name"], w)
    assert all(w["chips"] == 1 for w in BENCH["workloads"])


def test_run_without_a_chip_fails_and_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, str(CHIP / "run.py"), "--workload", CELLS[0],
         "--seed", str(2 ** 31 + 3), "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, timeout=120, env=env, cwd=str(ROOT))
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "no chip" in proc.stderr
