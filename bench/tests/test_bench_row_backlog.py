"""Whole runs of the row_paper.bt_backlog cell at a CPU size, with the chip check
skipped: sound, the control, and each fault planted in the program."""
import pytest

import _faults

CELL = "row_paper.bt_backlog"


def test_sound_run_is_correct_and_control_is_not():
    res = _faults.check_sound_run(CELL)
    assert set(res["metrics"]) == {"solves_per_s", "setup_s"}


@pytest.mark.parametrize("fault", sorted(_faults.FAULTS))
def test_fault_makes_run_incorrect(fault, monkeypatch):
    _faults.check_fault(CELL, fault, monkeypatch)
