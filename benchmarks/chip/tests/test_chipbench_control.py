"""The check refuses what it has to: the control and planted faults.

The control is the reference computed in bfloat16 put in the program's
place (``control.py`` reads it on the chip at each cell's own size).  The
faults (``faults.py``, which also reads them on the chip at a cell's own
size) break the timed path underneath a whole tiny run on the CPU, with
the harness's look for a chip steered around, and each has to turn
``correct`` false.
"""

import pytest

from chip import compare, control, faults, harness
from chip.tests import tiny

CELLS = [w["name"] for w in harness.load_json(harness.ROOT,
                                               "BENCHMARK.json")["workloads"]]


@pytest.mark.parametrize("name", CELLS)
def test_control_in_bfloat16_is_refused(name):
    cell = tiny.cell(name)
    readings = control.readings(cell, 2**33 + 3, 1.0)
    rows = compare.checks(readings, cell.limits)
    assert not compare.passed(rows), rows


@pytest.mark.parametrize("fault", sorted(faults.FAULTS))
@pytest.mark.parametrize("name", CELLS)
def test_a_broken_timed_path_reads_incorrect(name, fault):
    cell = tiny.cell(name)
    with faults.planted(fault):
        record = harness.drive(cell, 2**33 + 9, 0.3, False,
                               harness.time.perf_counter())
    out = harness.result(cell, record, False,
                         {"platform": "cpu", "kind": "cpu", "count": 1})
    assert not out["correct"], out["checks"]


def test_a_fault_is_taken_out_again_after_its_run():
    from repro.interface.session import InterfaceSession

    original = InterfaceSession.run_batched
    with faults.planted("halve_the_batch"):
        assert InterfaceSession.run_batched is not original
    assert InterfaceSession.run_batched is original
