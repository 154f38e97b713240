"""Cells that run the chip-sharded path, name a new driver or state a cut,
added with new files and entries alone.

The sharded cells are tiny copies of ``board_sparse`` whose mix sets
``"shard": "chips"``: in this process (one CPU device) the session takes
its vmap fallback, and in a child process with four virtual CPU devices
it takes a real chip mesh.  A new driver is a file put beside the
package's drivers; a cut configuration is a file that states what it
changed and the published values.
"""

import copy
import json
import os
import shutil
import subprocess
import sys
import textwrap

import pytest

from chip import compare, control, faults, harness
from chip.tests import tiny

SEED = 2**33 + 21
CPU = {"platform": "cpu", "kind": "cpu", "count": 1}


def sharded_cell() -> harness.Cell:
    cell = tiny.cell("board_sparse")
    cell.mix["shard"] = "chips"
    return cell


def outcome(cell, seed=SEED, seconds=0.3):
    record = harness.drive(cell, seed, seconds, False,
                           harness.time.perf_counter())
    return record, harness.result(cell, record, False, CPU)


@pytest.fixture(scope="module")
def sharded_run():
    cell = sharded_cell()
    return (cell,) + outcome(cell)


def test_sharded_cell_reads_correct_on_the_vmap_fallback(sharded_run):
    cell, record, out = sharded_run
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["metrics"]["events_per_s"]["value"] > 0
    assert "; shard chips: vmap fallback;" in record.notes[0]


def test_a_cell_without_shard_makes_the_plain_call():
    record, out = outcome(tiny.cell("board_sparse"), seconds=0.1)
    assert out["correct"], out["checks"]
    assert "; unsharded;" in record.notes[0]


@pytest.mark.parametrize("fault", sorted(faults.FAULTS))
def test_a_broken_sharded_path_reads_incorrect(fault):
    cell = sharded_cell()
    with faults.planted(fault):
        _, out = outcome(cell, seed=SEED + 1)
    assert not out["correct"], out["checks"]


def test_control_of_a_sharded_cell_is_refused():
    cell = sharded_cell()
    rows = compare.checks(control.readings(cell, SEED, 1.0), cell.limits)
    assert not compare.passed(rows), rows


MESH_RUN = """
import json, sys
sys.path[:0] = {paths!r}
import jax
assert len(jax.devices()) == 4, jax.devices()
from chip import harness
from chip.tests import tiny
cell = tiny.cell("board_sparse")
cell.mix["shard"] = "chips"
record = harness.drive(cell, {seed}, 0.3, False, harness.time.perf_counter())
out = harness.result(cell, record, False,
                     {{"platform": "cpu", "kind": "cpu", "count": 4}})
print(json.dumps({{"notes": record.notes, "correct": out["correct"],
                  "checks": out["checks"]}}))
"""


def test_sharded_cell_reads_correct_on_a_real_mesh():
    benchmarks = os.path.dirname(harness.HERE)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    body = MESH_RUN.format(paths=[os.path.join(harness.ROOT, "src"),
                                  benchmarks], seed=SEED)
    proc = subprocess.run([sys.executable, "-c", textwrap.dedent(body)],
                          capture_output=True, text=True, timeout=600,
                          env=env, cwd=harness.ROOT)
    assert proc.returncode == 0, proc.stderr[-4000:]
    got = json.loads(proc.stdout.strip().splitlines()[-1])
    assert got["correct"], got["checks"]
    # the tiny board keeps two simulated chips: one device each
    assert "; shard chips: mesh of 2 devices;" in got["notes"][0]


NEW_DRIVER = '''"""A driver that runs the offline sweep at its own tiny size."""

from chip.drivers import offline
from chip.drivers.offline import run  # noqa: F401

CONTROLS = []


def shrink(mix):
    mix.update(lanes=3, ticks=8, sample_lanes=2, shrunk_by="{name}")


def control_readings(cell, seed, seconds):
    CONTROLS.append(seed)
    return offline.control_readings(cell, seed, seconds)
'''


@pytest.fixture
def new_tree(tmp_path, monkeypatch):
    """A copy of the benchmark to which a test adds files and entries; the
    harness and the driver package read it in place of the checkout's."""
    import chip.drivers

    shutil.copy(os.path.join(harness.ROOT, "BENCHMARK.json"), tmp_path)
    here = tmp_path / "benchmarks" / "chip"
    shutil.copytree(harness.HERE, here, ignore=shutil.ignore_patterns(
        "__pycache__", "tests"))
    monkeypatch.setattr(harness, "ROOT", str(tmp_path))
    monkeypatch.setattr(harness, "HERE", str(here))
    monkeypatch.setattr(chip.drivers, "__path__",
                        [str(here / "drivers")] + list(chip.drivers.__path__))
    added = []
    yield tmp_path, here, added
    for name in added:
        sys.modules.pop("chip.drivers." + name, None)


def add(root, here, *, cell, config, traffic, limits, entry, workload):
    """Write the files of a new cell and its entries into the copy."""
    for sub, name, body in (("configs", config["name"], config),
                            ("traffic", workload["traffic"], traffic),
                            ("limits", cell, limits)):
        with open(here / sub / (name + ".json"), "w") as f:
            json.dump(body, f)
    bench = harness.load_json(root, "BENCHMARK.json")
    if entry is not None:
        bench["configs"].append(entry)
    bench["workloads"].append(workload)
    with open(root / "BENCHMARK.json", "w") as f:
        json.dump(bench, f)


def half_board():
    """``dynapse_board`` cut to two of its four chips, the cut stated."""
    config = copy.deepcopy(harness.load_json(harness.HERE, "configs",
                                             "dynapse_board.json"))
    config["name"] = "dynapse_half_board"
    config["fabric"].update(chips=2, cores=8)
    config["reduced"] = ["chips", "cores"]
    config["published"] = {"chips": 4, "cores": 16}
    entry = {"name": "dynapse_half_board", "source": "a paper",
             "file": "benchmarks/chip/configs/dynapse_half_board.json",
             "reduced": ["chips", "cores"], "why": "two chips of a board"}
    return config, entry


def test_a_new_cell_is_found_with_new_files_and_entries_alone(new_tree):
    root, here, added = new_tree
    name = "tiny_driver_a"
    with open(here / "drivers" / (name + ".py"), "w") as f:
        f.write(NEW_DRIVER.format(name=name))
    added.append(name)
    config, entry = half_board()
    mix = dict(harness.load_json(harness.HERE, "traffic",
                                 "sparse_batch.json"),
               driver=name, shard="chips")
    add(root, here, cell="half_board_sharded", config=config,
        traffic=mix, limits=harness.load_json(harness.HERE, "limits",
                                              "board_sparse.json"),
        entry=entry, workload={
            "name": "half_board_sharded", "config": "dynapse_half_board",
            "traffic": "half_sharded", "chips": 1, "why": "a test cell"})

    cell = tiny.cell("half_board_sharded")
    assert cell.mix["shrunk_by"] == name
    assert (cell.mix["lanes"], cell.mix["ticks"]) == (3, 8)
    assert cell.config["fabric"]["neurons_per_core"] == 64
    record, out = outcome(cell)
    assert out["correct"], out["checks"]
    assert "; shard chips: vmap fallback;" in record.notes[0]

    rows = compare.checks(control.readings(cell, SEED, 1.0), cell.limits)
    assert not compare.passed(rows), rows
    assert harness.driver(cell).CONTROLS == [SEED]


def test_an_unstated_cut_is_refused_when_the_cell_loads(new_tree):
    root, here, _ = new_tree
    config, entry = half_board()
    del config["published"]
    add(root, here, cell="half_board", config=config,
        traffic=harness.load_json(harness.HERE, "traffic",
                                  "sparse_batch.json"),
        limits={}, entry=entry, workload={
            "name": "half_board", "config": "dynapse_half_board",
            "traffic": "half", "chips": 1, "why": "a test cell"})
    with pytest.raises(ValueError, match="published gives no value"):
        harness.load_cell("half_board")


def test_a_stated_cut_passes():
    config, entry = half_board()
    assert harness.cut_faults(entry, config) == []


@pytest.mark.parametrize("breaks, fault", [
    (lambda c, e: c.pop("published"), "published gives no value of 'chips'"),
    (lambda c, e: c["published"].pop("cores"),
     "published gives no value of 'cores'"),
    (lambda c, e: c["published"].update(chips=2),
     "'chips' is run at its published value 2"),
    (lambda c, e: e.update(reduced=["chips"]), "is not BENCHMARK.json's"),
    (lambda c, e: c.update(reduced=[]), "is not BENCHMARK.json's"),
    (lambda c, e: (c["reduced"].append("lanes"),
                   e["reduced"].append("lanes")),
     "'lanes' is not a key of the fabric"),
    (lambda c, e: (c["fabric"].update(cam_entries_per_core=512),
                   c["reduced"].append("cam_entries_per_core"),
                   e["reduced"].append("cam_entries_per_core"),
                   c["published"].update(cam_entries_per_core=16384)),
     "'cam_entries_per_core' is a width and is never cut"),
])
def test_an_unsound_cut_is_named(breaks, fault):
    config, entry = half_board()
    breaks(config, entry)
    faults_found = harness.cut_faults(entry, config)
    assert any(fault in f for f in faults_found), faults_found
