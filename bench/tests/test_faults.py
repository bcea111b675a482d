"""``correct`` comes out false when the timed path is broken underneath.

Each case drives a whole run on the CPU at its system's test size
(``small`` of ``bench/cases/<system>.py``; the chip check is skipped,
everything else is as in the cell), with one fault of its system planted
in the program's path (``FAULTS``: a copy that leaves its destination
unchanged, an answer altered where it is produced, a batch of which half
is left out, bursts refused at admission), or with the system's control
of ``bench/control.py`` (the reference one precision step down in the
program's place).  A sound run of each cell must come out correct.  One
chip, so no exchange between chips to drop.
"""
import pytest

from bench import cases, spec

BM = spec.load_benchmark()
CELLS = [w["name"] for w in BM["workloads"]]
PLANTED = [(c, f) for c in CELLS
           for f in cases.of(spec.config(BM, spec.cell(BM, c)["config"])).FAULTS]


def _run(cell, seed=2**31 + 5):
    result, checks = cases.run_small(cell, seed)
    assert result["attempted"] > 0
    return result, checks


@pytest.mark.parametrize("cell", CELLS)
def test_sound_run_is_correct(cell):
    result, checks = _run(cell)
    assert result["correct"], checks
    assert list(result)[-1] == "checks"


@pytest.mark.parametrize("cell", CELLS)
def test_control_is_not_correct(cell):
    hooks, _, _ = cases.small_cell(cell)
    with cases.replaced(hooks.CONTROL):
        result, checks = _run(cell)
    assert not result["correct"], (checks, result["attempted"])


@pytest.mark.parametrize("cell,fault", PLANTED)
def test_planted_fault_is_not_correct(cell, fault, monkeypatch):
    planted = cases.small_cell(cell)[0].FAULTS[fault]
    with cases.planted(planted, monkeypatch):
        result, checks = _run(cell)
    if planted.check is not None:
        planted.check(result, checks)
    assert not result["correct"], (checks, result["attempted"])
