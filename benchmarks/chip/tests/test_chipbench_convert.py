"""``store_sales_convert`` on the CPU at a tiny size: the program's egress
equals the reference exactly, and every fault the cell can have, and
every control, makes ``correct`` false."""
import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent))

from _scenarios import run_scenarios  # noqa: E402

FAULTS = ["device_identity", "half_batch", "device_float32", "low_digit",
          "answer_altered"]


@pytest.fixture(scope="module")
def lines():
    return run_scenarios("store_sales_convert.saturate", ["sound", *FAULTS])


def test_sound_run_matches_reference(lines):
    sound = lines["sound"]
    assert sound["correct"], sound["checks"]
    assert sound["attempted"] > 0
    assert sound["metrics"] == ["setup_s", "throughput_eps"]


@pytest.mark.parametrize("control", ["duplicate_delivery", "reorder_pair"])
def test_control_is_not_correct(lines, control):
    assert lines["sound"]["controls"][control] is False


@pytest.mark.parametrize("fault", FAULTS)
def test_fault_is_not_correct(lines, fault):
    line = lines[fault]
    assert line["correct"] is False
    assert line["checks"]["mismatched_rows"]["value"] > 0
