"""The two whole-run checks every one-chip cell's test file makes at a
CPU size with the chip check skipped: a sound program is correct and the
control is not, and each fault of ``bench/faults.py`` that a one-chip cell
can have makes ``correct`` come out false."""
import _paths  # noqa: F401
import _tiny
import faults

FAULTS = {k: v for k, v in faults.FAULTS.items() if k not in faults.MESH_ONLY}


def check_sound_run(cell):
    res = _tiny.run(cell, control=True)
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] > 0
    assert res["control"]["correct"] is False
    assert list(res)[-1] == "checks"
    return res


def check_fault(cell, fault, monkeypatch):
    FAULTS[fault](monkeypatch.setattr)
    res = _tiny.run(cell)
    assert res["correct"] is False, (fault, res["checks"])
    return res
