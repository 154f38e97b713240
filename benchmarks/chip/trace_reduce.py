"""Reduce a profiler trace (``.xplane.pb``) to what the metrics read.

The trace is read with `jax.profiler.ProfileData` alone.  Device operations
are the events of the ``XLA Ops`` line of each ``/device:TPU:<i>`` plane
(asynchronous copies sit on another line and are left out); host spans are
the events of the ``/host:CPU`` plane.  Both are on one clock.  Only what
falls inside the benchmark's window span (``bench.window``) counts.

- busy: the union of the intervals in which an operation ran on a device.
  A loop or a conditional is itself an operation that spans its body, so
  the device's own loop control counts as busy; a gap is time in which the
  device waited for the host.
- self time of an operation: its duration less that of the operations
  nested in it; the top operations are ranked by it.
- collective share: the time of collective operations (all-gather,
  all-reduce, all-to-all, collective-permute, reduce-scatter) over all
  operation time, on each device.
- idle gaps: the stretches of the window outside ``busy``, each named by the
  innermost host span open at its midpoint (``host.other`` where none is).
"""

from __future__ import annotations

import glob
import os
import re

COLLECTIVES = ("all-gather", "all-reduce", "all-to-all", "collective-permute",
               "reduce-scatter")
SPAN_PREFIXES = ("bench.", "interface.", "serve.")


def find_xplane(trace_dir: str) -> str:
    """The newest ``.xplane.pb`` under ``trace_dir``."""
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(paths, key=os.path.getmtime)


def op_name(hlo: str) -> str:
    """Short name of an operation: its HLO name and result type."""
    head, _, rest = hlo.partition(" = ")
    shape = re.sub(r"\{[^}]*\}", "", rest.split(" ", 1)[0]) if rest else ""
    return f"{head.lstrip('%')} {shape}".strip()


def is_collective(hlo: str) -> bool:
    name = hlo.partition(" = ")[0].lstrip("%")
    return name.startswith(COLLECTIVES)


def union(intervals) -> list:
    """Merged, sorted ``[(start, end)]`` of possibly overlapping intervals."""
    out = []
    for start, end in sorted(intervals):
        if out and start <= out[-1][1]:
            out[-1][1] = max(out[-1][1], end)
        else:
            out.append([start, end])
    return [tuple(iv) for iv in out]


def self_times(ops) -> list:
    """``[(name, self_ns)]`` of ``(name, start, end)`` ops that may nest."""
    ordered = sorted(ops, key=lambda o: (o[1], -o[2]))
    selfs = [o[2] - o[1] for o in ordered]
    stack = []
    for i, (_, start, end) in enumerate(ordered):
        while stack and ordered[stack[-1]][2] <= start:
            stack.pop()
        if stack and end <= ordered[stack[-1]][2]:
            selfs[stack[-1]] -= end - start
        stack.append(i)
    return [(o[0], s) for o, s in zip(ordered, selfs)]


def _label(mid, spans) -> str:
    inner = None
    for name, start, end in spans:
        if start <= mid <= end and (inner is None or
                                    end - start < inner[2] - inner[1]):
            inner = (name, start, end)
    return inner[0] if inner else "host.other"


def reduce_events(host, devices, window="bench.window", top=10) -> dict:
    """The reduction of already-read events.

    host: ``[(name, start_ns, end_ns)]``; devices: ``{plane: [(hlo,
    start_ns, end_ns)]}`` of ``XLA Ops`` events.
    """
    marks = [(s, e) for name, s, e in host if name == window]
    if not marks:
        raise ValueError(f"no {window!r} span in the trace")
    w0, w1 = marks[0]
    spans = [(n, max(s, w0), min(e, w1)) for n, s, e in host
             if n.startswith(SPAN_PREFIXES) and n != window and e > w0
             and s < w1]
    used, busy, coll, totals, gaps = 0, 0.0, 0.0, {}, {}
    for ops in devices.values():
        ops = [(n, max(s, w0), min(e, w1)) for n, s, e in ops
               if e > w0 and s < w1]
        if not ops:
            continue
        used += 1
        merged = union((s, e) for _, s, e in ops)
        busy += sum(e - s for s, e in merged)
        selfs = self_times(ops)
        op_time = sum(t for _, t in selfs)
        coll_time = sum(t for n, t in selfs if is_collective(n))
        coll += coll_time / op_time if op_time > 0 else 0.0
        for name, t in selfs:
            key = op_name(name)
            totals[key] = totals.get(key, 0.0) + t
        edges = [w0] + [x for iv in merged for x in iv] + [w1]
        for start, end in zip(edges[::2], edges[1::2]):
            if end > start:
                label = _label((start + end) / 2, spans)
                gaps[label] = gaps.get(label, 0.0) + (end - start)
    if not used:
        raise ValueError("no device operation inside the window")
    span_s = {}
    for name, start, end in spans:
        span_s.setdefault(name, []).append((end - start) / 1e9)

    def ranked(d):
        return [[k, v / used / 1e9] for k, v in
                sorted(d.items(), key=lambda kv: -kv[1])[:top]]

    window_s = (w1 - w0) / 1e9
    busy_s = busy / used / 1e9
    return {"window_s": window_s, "busy_s": busy_s, "devices": used,
            "idle_share": 1.0 - busy_s / window_s,
            "collective_share": coll / used,
            "device_ops": ranked(totals), "idle_gaps": ranked(gaps),
            "spans": span_s}


def read(path: str):
    """``(host, devices)`` events of one ``.xplane.pb``."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    host, devices = [], {}
    for plane in data.planes:
        if plane.name == "/host:CPU":
            for line in plane.lines:
                host.extend((ev.name, ev.start_ns, ev.end_ns)
                            for ev in line.events)
        elif plane.name.startswith("/device:TPU:"):
            for line in plane.lines:
                if line.name == "XLA Ops":
                    devices[plane.name] = [(ev.name, ev.start_ns, ev.end_ns)
                                           for ev in line.events]
    return host, devices


def reduce(path: str, window: str = "bench.window") -> dict:
    """`reduce_events` of the trace file at ``path``."""
    host, devices = read(path)
    return reduce_events(host, devices, window=window)
