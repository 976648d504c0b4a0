"""``store_sales_item`` on the CPU at a tiny size: the program's egress
equals the reference exactly, and every fault planted under the timed
path, and every control, makes ``correct`` false; the same of
``store_sales_item_uniform``, the query with items drawn uniformly, for a
sound run, ``low_digit`` and the controls."""
import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent))

from _scenarios import run_scenarios  # noqa: E402

FAULTS = ["device_identity", "half_batch", "low_digit", "state_unchanged",
          "answer_altered"]


@pytest.fixture(scope="module")
def lines():
    return run_scenarios("store_sales_item.saturate", ["sound", *FAULTS])


@pytest.fixture(scope="module")
def uniform():
    return run_scenarios("store_sales_item.uniform", ["sound", "low_digit"])


@pytest.fixture(scope="module")
def rated():
    return run_scenarios("store_sales_item.rated", ["sound"])["sound"]


def test_sound_run_matches_reference(lines):
    sound = lines["sound"]
    assert sound["correct"], sound["checks"]
    assert sound["attempted"] > 0
    assert sound["metrics"] == ["setup_s", "throughput_eps"]
    assert all(c["limit"] == 0 for c in sound["checks"].values())


@pytest.mark.parametrize("control", ["duplicate_delivery", "reorder_pair"])
def test_control_is_not_correct(lines, control):
    assert lines["sound"]["controls"][control] is False


@pytest.mark.parametrize("fault", FAULTS)
def test_fault_is_not_correct(lines, fault):
    line = lines[fault]
    assert line["correct"] is False
    assert line["checks"]["mismatched_rows"]["value"] > 0


def test_rated_run_matches_reference_and_reports_latency(rated):
    assert rated["correct"], rated["checks"]
    assert rated["metrics"] == ["latency_p50_ms", "setup_s"]


def test_uniform_sound_run_matches_reference(uniform):
    sound = uniform["sound"]
    assert sound["correct"], sound["checks"]
    assert sound["attempted"] > 0
    assert sound["metrics"] == ["setup_s", "throughput_eps"]


@pytest.mark.parametrize("control", ["duplicate_delivery", "reorder_pair"])
def test_uniform_control_is_not_correct(uniform, control):
    assert uniform["sound"]["controls"][control] is False


def test_uniform_low_digit_is_not_correct(uniform):
    line = uniform["low_digit"]
    assert line["correct"] is False
    assert line["checks"]["mismatched_rows"]["value"] > 0
