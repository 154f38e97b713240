"""The cell ``board_chips4``: sixteen simulated chips on four devices.

`tests/tiny.py` cuts every multi-chip fabric to two chips, so the shared
tests never reach a layout with several simulated chips on one device.
Here a tiny copy of the cell keeps its 16 chips and 4 cores a chip (16
neurons and 32 CAM entries a core, 2 lanes x 8 ticks) and runs in a child
process with four virtual CPU devices: the sharded tick then takes a mesh
of 4 devices, 4 simulated chips each, and the check has to read it
correct, and incorrect under each planted fault.
"""

import json
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from chip import data, faults, generators, harness
from repro import traffic

SEED = 2**33 + 41


@pytest.mark.parametrize("params", [
    {},
    {"cluster_size": 16, "active_prob": 0.25, "rate": 0.5},
    {"cluster_size": 5, "active_prob": 0.6, "rate": 0.9},
])
@pytest.mark.parametrize("shape", [(24, 4, 256), (9, 3, 64)])
def test_clustered_copy_reproduces_the_program_generator(params, shape):
    key = data.seed_key(2**33 + 17, 1)
    ticks, cores, n = shape
    ours = generators.load("clustered")(key, ticks, cores, n, **params)
    theirs = traffic.generate("clustered", key, ticks, (cores, n), **params)
    assert ours.dtype == theirs.dtype == np.bool_
    np.testing.assert_array_equal(np.asarray(ours), np.asarray(theirs))


MESH_RUN = """
import copy, json, sys
sys.path[:0] = {paths!r}
import jax
assert len(jax.devices()) == 4, jax.devices()
from chip import faults, harness

cell = copy.deepcopy(harness.load_cell("board_chips4"))
cell.config["fabric"].update(neurons_per_core=16, cam_entries_per_core=32)
cell.mix.update(lanes=2, ticks=8, sample_lanes=2)
device = {{"platform": "cpu", "kind": "cpu", "count": 4}}


def one(seed):
    record = harness.drive(cell, seed, 0.3, False, harness.time.perf_counter())
    out = harness.result(cell, record, False, device)
    return {{"notes": record.notes, "correct": out["correct"],
            "checks": out["checks"]}}


got = {{"sound": one({seed})}}
for i, fault in enumerate(sorted(faults.FAULTS)):
    with faults.planted(fault):
        got[fault] = one({seed} + 1 + i)
print(json.dumps(got))
"""


@pytest.fixture(scope="module")
def mesh_runs():
    """What a sound run and a run under each fault of the tiny cell read,
    in a child process with four virtual CPU devices."""
    benchmarks = os.path.dirname(harness.HERE)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    body = MESH_RUN.format(paths=[os.path.join(harness.ROOT, "src"),
                                  benchmarks], seed=SEED)
    proc = subprocess.run([sys.executable, "-c", textwrap.dedent(body)],
                          capture_output=True, text=True, timeout=600,
                          env=env, cwd=harness.ROOT)
    assert proc.returncode == 0, proc.stderr[-4000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def test_tiny_board_chips4_reads_correct_on_a_mesh_of_4(mesh_runs):
    got = mesh_runs["sound"]
    assert got["correct"], got["checks"]
    assert "; shard chips: mesh of 4 devices;" in got["notes"][0]


@pytest.mark.parametrize("fault", sorted(faults.FAULTS))
def test_a_fault_under_the_mesh_of_4_reads_incorrect(mesh_runs, fault):
    got = mesh_runs[fault]
    assert "; shard chips: mesh of 4 devices;" in got["notes"][0]
    assert not got["correct"], got["checks"]
