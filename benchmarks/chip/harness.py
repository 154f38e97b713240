"""One run of one cell: find it by name, drive it, check it, print it.

`main` is what ``run.py`` calls.  It reads the cell from ``BENCHMARK.json``
at the root of the checkout, its configuration from ``configs/``, its
traffic mix from ``traffic/`` and its limits from ``limits/``, refuses to
run off a TPU or on fewer chips than the cell asks for, hands the cell to
the driver the mix names (``drivers/<driver>.py``), and prints:

- earlier lines on standard output: what ran (impl, calls, events,
  compiles in the window, cache hits);
- the numbers compared, each beside its limit, as the last lines of
  standard error;
- the result, one JSON object, as the last line of standard output.
"""

from __future__ import annotations

import dataclasses
import importlib
import importlib.util
import json
import os
import sys
import tempfile
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
CACHE_DIR = os.path.join(ROOT, ".jax_cache")


def load_json(*parts) -> dict:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


@dataclasses.dataclass
class Cell:
    """Everything a driver needs to know about the cell it runs."""

    name: str
    chips: int
    config: dict          # configs/<config>.json
    mix: dict             # traffic/<traffic>.json
    limits: dict          # limits/<cell>.json


@dataclasses.dataclass
class Record:
    """What a driver's run hands back to the harness."""

    setup_s: float
    e2e: dict                     # end-to-end metric name -> value
    attempted: int
    failed: int
    readings: dict                # number compared -> value
    memory_peak_bytes: int
    notes: list = dataclasses.field(default_factory=list)
    traced: dict = dataclasses.field(default_factory=dict)
    trace: dict | None = None     # `trace_reduce.reduce` of the window


# Keys of a configuration's ``fabric`` that are its shapes: never cut.
WIDTHS = ("cores_per_chip", "neurons_per_core", "cam_entries_per_core",
          "scheme", "noc", "cam")


def cut_faults(entry: dict, config: dict) -> list:
    """Why a configuration file's stated cut is not sound; [] if it is.

    ``entry`` is the configuration's entry in ``BENCHMARK.json``.  The
    file's ``reduced`` has to equal the entry's, every key in it has to be
    a key of the file's ``fabric`` that is not a width (`WIDTHS`), and the
    file's ``published`` has to give each such key's published value,
    which differs from the one run.
    """
    reduced = config.get("reduced")
    if reduced != entry["reduced"]:
        return [f"{config['name']}: the file's reduced {reduced!r} is not "
                f"BENCHMARK.json's {entry['reduced']!r}"]
    fab, published = config["fabric"], config.get("published", {})
    faults = []
    for key in reduced:
        if key not in fab:
            faults.append(f"{key!r} is not a key of the fabric")
        elif key in WIDTHS:
            faults.append(f"{key!r} is a width and is never cut")
        elif key not in published:
            faults.append(f"published gives no value of {key!r}")
        elif published[key] == fab[key]:
            faults.append(f"{key!r} is run at its published value "
                          f"{fab[key]!r}")
    return [f"{config['name']}: {f}" for f in faults]


def load_cell(name: str) -> Cell:
    """The cell ``name`` of ``BENCHMARK.json``, with its files read.

    Raises ValueError where its configuration states an unsound cut.
    """
    bench = load_json(ROOT, "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; known: "
                       f"{', '.join(cells)}")
    w = cells[name]
    config = load_json(HERE, "configs", w["config"] + ".json")
    entry = next(c for c in bench["configs"] if c["name"] == w["config"])
    faults = cut_faults(entry, config)
    if faults:
        raise ValueError("; ".join(faults))
    return Cell(name=name, chips=int(w["chips"]), config=config,
                mix=load_json(HERE, "traffic", w["traffic"] + ".json"),
                limits=load_json(HERE, "limits", name + ".json"))


def driver(cell: Cell):
    """The module ``drivers/<driver>.py`` that the cell's mix names.

    A driver has ``run`` (one run of the cell), ``shrink`` (the mix at a
    size a CPU test run can hold) and ``control_readings`` (the check's
    control at the cell's own sizes).
    """
    return importlib.import_module("chip.drivers." + cell.mix["driver"])


def metrics_of(name: str, kind: str) -> list:
    """The ``end_to_end`` or ``per_layer`` metrics that apply to a cell."""
    bench = load_json(ROOT, "BENCHMARK.json")
    return [m for m in bench[kind]
            if "workloads" not in m or name in m["workloads"]]


def reader(metric: str):
    """The ``read(trace, record)`` function of ``metrics/<metric>.py``."""
    path = os.path.join(HERE, "metrics", metric + ".py")
    spec = importlib.util.spec_from_file_location(
        "chip_metric_" + metric.replace(".", "_"), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


class CompileCounter:
    """Counts compile requests and persistent-cache hits, once registered."""

    def __init__(self):
        import jax

        self.requests = self.hits = 0
        jax.monitoring.register_event_listener(self._event)

    def _event(self, event: str, **_):
        if event == "/jax/compilation_cache/compile_requests_use_cache":
            self.requests += 1
        elif event == "/jax/compilation_cache/cache_hits":
            self.hits += 1


def device_info(chips: int) -> dict:
    import jax

    devs = jax.devices()[:chips]
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def memory_peak(chips: int) -> int:
    """Peak bytes in use on the fullest of the cell's chips."""
    import jax

    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in jax.devices()[:chips])


def require_chips(chips: int) -> str | None:
    """Why this process may not run a ``chips``-chip cell, or None."""
    import jax

    backend = jax.default_backend()
    if backend != "tpu":
        return f"needs a TPU; JAX found {backend!r}"
    if len(jax.devices()) < chips:
        return f"needs {chips} chips; JAX found {len(jax.devices())}"
    return None


def result(cell: Cell, record: Record, trace: bool, device: dict) -> dict:
    """The result object (the last line of standard output)."""
    from chip import compare

    rows = compare.checks(record.readings, cell.limits)
    correct = compare.passed(rows) and record.failed == 0
    metrics = {}
    if trace:
        for m in metrics_of(cell.name, "per_layer"):
            value = reader(m["name"])(record.trace, record)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        for m in metrics_of(cell.name, "end_to_end"):
            metrics[m["name"]] = {"value": record.e2e[m["name"]],
                                  "unit": m["unit"]}
    device = dict(device, memory_peak_bytes=record.memory_peak_bytes)
    out = {"correct": bool(correct), "attempted": int(record.attempted),
           "failed": int(record.failed), "metrics": metrics,
           "device": device}
    if trace:
        device["busy_s"] = record.trace["busy_s"]
        device["window_s"] = record.trace["window_s"]
        out["breakdown"] = {"device_ops": record.trace["device_ops"],
                            "idle_gaps": record.trace["idle_gaps"]}
    out["checks"] = {name: {"value": value, "limit": limit}
                     for name, value, limit in rows}
    return out


def drive(cell: Cell, seed: int, seconds: float, trace: bool, t0: float,
          counter: CompileCounter | None = None) -> Record:
    """Run the cell's driver once; trace its window when asked."""
    run = driver(cell).run
    if not trace:
        return run(cell, seed, seconds, t0, counter=counter)
    from chip import trace_reduce

    with tempfile.TemporaryDirectory(prefix="chipbench-trace-") as tdir:
        record = run(cell, seed, seconds, t0, counter=counter,
                     trace_dir=tdir)
        record.trace = trace_reduce.reduce(trace_reduce.find_xplane(tdir))
    return record


class Refused(Exception):
    """This process may not run the cell: no TPU, or too few chips."""


def start(cell: Cell) -> CompileCounter:
    """Set up JAX for a run of ``cell`` on this machine's chips.

    The compile cache lives at a fixed path inside the checkout, whatever
    the environment says, so that each checkout reuses only its own.  A
    size bound (JAX_COMPILATION_CACHE_MAX_SIZE) turns on eviction, under
    which entries failed to be written on the chip's machine and every run
    compiled again; unbounded, each program of the cell stays cached.
    Raises `Refused` off a TPU or on fewer chips than the cell asks for.
    """
    os.environ["JAX_COMPILATION_CACHE_DIR"] = CACHE_DIR
    import jax

    why = require_chips(cell.chips)
    if why is not None:
        raise Refused(why)
    jax.config.update("jax_compilation_cache_max_size", -1)
    from repro.launch.cache import enable_compile_cache

    enable_compile_cache()
    return CompileCounter()


def main(args, t0: float) -> int:
    cell = load_cell(args.workload)
    try:
        counter = start(cell)
    except Refused as why:
        print(f"chipbench: {why}", file=sys.stderr)
        return 2
    import jax

    record = drive(cell, args.seed, args.seconds, bool(args.trace), t0,
                   counter=counter)
    device = device_info(cell.chips)
    out = result(cell, record, bool(args.trace), device)
    for line in record.notes:
        print(f"chipbench: {line}", flush=True)
    print(f"chipbench: device {device['platform']} {device['kind']} "
          f"x{device['count']}; jax {jax.__version__}; setup_s "
          f"{record.setup_s:.3f}; persistent cache hits {counter.hits} of "
          f"{counter.requests} compile requests", flush=True)
    for name, check in out["checks"].items():
        ok = "ok" if check["value"] <= check["limit"] else "FAIL"
        print(f"check {name} {check['value']!r} limit {check['limit']!r} "
              f"{ok}", file=sys.stderr, flush=True)
    print(json.dumps(out), flush=True)
    return 0


def elapsed(t0: float) -> float:
    return time.perf_counter() - t0
