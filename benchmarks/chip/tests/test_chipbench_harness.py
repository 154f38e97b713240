"""The harness without a chip: its files by name, its drivers at a tiny
size on the CPU, and its refusal to run off a TPU."""

import os
import re
import shutil
import subprocess
import sys

import numpy as np
import pytest

from chip import compare, generators, harness, work
from chip.drivers import common
from chip.tests import tiny

ROOT = harness.ROOT
BENCH = harness.load_json(ROOT, "BENCHMARK.json")
CELLS = [w["name"] for w in BENCH["workloads"]]
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
LINE = re.compile(r"^[^\t\n]{1,200}$")


def test_benchmark_file_keeps_to_the_contract_shape():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["paths"] == ["benchmarks/chip"]
    assert BENCH["command"][1] == "benchmarks/chip/run.py"
    names = set()
    for c in BENCH["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["file"].startswith("benchmarks/chip/")
        assert os.path.exists(os.path.join(ROOT, c["file"]))
        assert all(NAME.match(k) for k in c["reduced"])
        names.add(c["name"])
    for w in BENCH["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["config"] in names and w["chips"] in (1, 4)
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    assert "setup_s" in e2e
    for m in BENCH["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in BENCH["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert m["moves"] in e2e and LINE.match(m["layer"])
        for cell in m.get("workloads", CELLS):
            moved = next(x for x in BENCH["end_to_end"]
                         if x["name"] == m["moves"])
            assert cell in moved.get("workloads", CELLS)
    for item in (BENCH["configs"] + BENCH["workloads"] +
                 BENCH["end_to_end"] + BENCH["per_layer"]):
        assert NAME.match(item["name"]), item["name"]
        for key in ("config", "traffic"):
            if key in item:
                assert NAME.match(item[key])
        if "unit" in item:
            assert UNIT.match(item["unit"]), item["unit"]
            assert item["better"] in ("lower", "higher")
        for key in ("why", "layer", "source"):
            if key in item:
                assert LINE.match(item[key])


@pytest.mark.parametrize("name", CELLS)
def test_every_file_of_a_cell_is_found_by_name(name):
    cell = harness.load_cell(name)
    assert cell.config["name"] == next(
        w["config"] for w in BENCH["workloads"] if w["name"] == name)
    generators.load(cell.mix["generator"])
    drv = harness.driver(cell)
    assert all(callable(getattr(drv, f, None))
               for f in ("run", "shrink", "control_readings"))
    for m in harness.metrics_of(name, "per_layer"):
        assert callable(harness.reader(m["name"]))
    assert common.program_config(cell.config).cores == \
        cell.config["fabric"]["cores"]
    entry = next(c for c in BENCH["configs"]
                 if c["name"] == cell.config["name"])
    assert harness.cut_faults(entry, cell.config) == []


def canned_trace():
    return {"window_s": 1.0, "busy_s": 0.6, "devices": 1,
            "idle_share": 0.4, "collective_share": 0.1,
            "device_ops": [["fusion.1 f32[4]", 0.5]],
            "idle_gaps": [["bench.call", 0.4]],
            "spans": {"interface.run_batched": [0.001, 0.003],
                      "serve.step": [0.004],
                      "serve.pump": [0.006, 0.001],
                      "serve.pump.wait": [0.002],
                      "serve.lock_wait": [0.0001]}}


@pytest.fixture(scope="module", params=CELLS)
def tiny_run(request):
    cell = tiny.cell(request.param)
    t0 = harness.time.perf_counter()
    return cell, harness.drive(cell, 2**33 + 7, 0.5, False, t0)


def test_driver_runs_tiny_cell_correctly_on_the_cpu(tiny_run):
    cell, record = tiny_run
    out = harness.result(cell, record, False,
                         {"platform": "cpu", "kind": "cpu", "count": 1})
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    wanted = {m["name"] for m in harness.metrics_of(cell.name, "end_to_end")}
    assert set(out["metrics"]) == wanted
    assert all(v["value"] > 0 for v in out["metrics"].values())
    assert list(out)[-1] == "checks"
    assert set(out["checks"]) == set(cell.limits)


def test_every_per_layer_metric_of_a_cell_reads_a_trace(tiny_run):
    cell, record = tiny_run
    record.trace = canned_trace()
    if "device_kind" in record.traced:
        record.traced["device_kind"] = "TPU v5 lite"
    out = harness.result(cell, record, True,
                         {"platform": "cpu", "kind": "cpu", "count": 1})
    wanted = {m["name"] for m in harness.metrics_of(cell.name, "per_layer")}
    assert set(out["metrics"]) == wanted
    assert out["device"]["busy_s"] == 0.6
    assert out["breakdown"]["device_ops"] == [["fusion.1 f32[4]", 0.5]]
    assert all(np.isfinite(v["value"]) for v in out["metrics"].values())
    pct = [v["value"] for k, v in out["metrics"].items() if "roofline" in k]
    assert all(0 < p <= 100 for p in pct)


@pytest.mark.parametrize("impl", ["xla", "pallas_sparse"])
def test_work_count_does_not_depend_on_the_impl(impl, monkeypatch):
    cell = tiny.cell("board_sparse")
    base = common.program_config

    def with_impl(config):
        import dataclasses

        return dataclasses.replace(base(config), impl=impl)

    monkeypatch.setattr(common, "program_config", with_impl)
    record = harness.drive(cell, 11, 0.2, False, harness.time.perf_counter())
    assert record.notes[0].startswith(f"impl {impl};")
    expect = work.tick_work(cell.config, cell.mix["lanes"], cell.mix["ticks"],
                            record.traced["events"], record.attempted)
    assert record.traced["work"] == expect
    assert compare.passed(compare.checks(record.readings, cell.limits))


def run_py(cwd, *extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, os.path.join(cwd, "benchmarks", "chip", "run.py"),
         "--workload", CELLS[0], "--seed", "1", "--seconds", "1",
         "--trace", "0", *extra],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_run_exits_nonzero_without_a_result_off_the_chip():
    proc = run_py(ROOT)
    assert proc.returncode == 2
    assert "needs a TPU" in proc.stderr
    assert not any(line.startswith("{") for line in proc.stdout.splitlines())


def test_start_refuses_a_cell_off_the_chip(monkeypatch):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "unused")
    with pytest.raises(harness.Refused, match="needs a TPU"):
        harness.start(harness.load_cell(CELLS[0]))
    assert os.environ["JAX_COMPILATION_CACHE_DIR"] == harness.CACHE_DIR


def test_run_fails_without_the_program(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "benchmarks", "chip"),
                    tmp_path / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("__pycache__"))
    proc = run_py(str(tmp_path))
    assert proc.returncode != 0
    assert not any(line.startswith("{") for line in proc.stdout.splitlines())
