"""The comparison that decides ``correct`` fails the control (the plain
reference in three bf16 passes, in the program's place) and each fault a
cell can have, planted in the timed path: a run with the chip look skipped,
at a tiny size on the CPU."""
import pathlib
import sys

import pytest

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent))

from chipbench_tiny import CELLS, run_tiny  # noqa: E402


@pytest.mark.parametrize("cell", CELLS)
def test_control_is_not_correct(cell):
    r, _ = run_tiny(cell, patch=lambda loop, s: loop.control(s))
    assert r["correct"] is False
    c = r["checks"]["rel_err"]
    assert c["value"] > c["limit"]


@pytest.mark.parametrize("fault", ["half_batch", "altered_answer"])
@pytest.mark.parametrize("cell", CELLS)
def test_fault_is_not_correct(cell, fault):
    r, _ = run_tiny(cell, patch=lambda loop, s: loop.fault(s, fault))
    assert r["correct"] is False
    assert r["checks"]["rel_err"]["value"] > r["checks"]["rel_err"]["limit"]
