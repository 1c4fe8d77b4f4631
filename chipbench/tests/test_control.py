"""Each cell's control (``control.py``: the reference in the nearest
precision below the configuration's, in the program's place) fails at
least one of the cell's committed limits. Small widths, on the CPU; the
readings at the cells' own sizes come from the chip (PERF.md)."""
import json

import pytest

import tiny
from chipbench import control

CELLS = ["gpo-d4096.fed-paper", "gpo-d4096.bestofn", "gpo-d4096.online",
         "gpo-d4096-int8.bestofn"]


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny.make(tmp_path_factory.mktemp("bench"))


@pytest.mark.parametrize("workload", CELLS)
def test_control_fails_a_limit(root, workload):
    limits = json.loads(
        (root / "chipbench" / "limits" / f"{workload}.json").read_text())
    for _, nums in control.readings(root, workload, [3, 4], served=64):
        assert any(v > limits[k] for k, v in nums.items()), nums
