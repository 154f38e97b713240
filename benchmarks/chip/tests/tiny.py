"""Cells of ``BENCHMARK.json`` shrunk to sizes a CPU test run can hold."""

import copy

from chip import harness


def shrink(cell: harness.Cell) -> harness.Cell:
    """A copy of ``cell`` at 64 neurons a core, 64 CAM entries, and the
    few lanes, ticks or requests its driver's ``shrink`` gives; the layout
    (chips, scheme, NoC) is kept."""
    cell = copy.deepcopy(cell)
    fab = cell.config["fabric"]
    fab.update(neurons_per_core=64, cam_entries_per_core=64)
    if fab["chips"] > 1:
        fab.update(chips=2, cores_per_chip=2, cores=4)
    else:
        fab.update(cores_per_chip=2, cores=2)
    harness.driver(cell).shrink(cell.mix)
    return cell


def cell(name: str) -> harness.Cell:
    return shrink(harness.load_cell(name))
