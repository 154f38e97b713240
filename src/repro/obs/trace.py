"""Host-side span tracing with Chrome-trace (Perfetto) JSON output.

A `Tracer` collects *complete* events (``ph: "X"``) from context-manager
spans and serialises them in the Chrome trace-event format, so a run can
be dropped straight into ``chrome://tracing`` / https://ui.perfetto.dev
and read next to a device profile:

    from repro.obs import trace

    tracer = trace.Tracer()
    with tracer:                               # activates the tracer
        with trace.span("compile", cores=16):
            session = Interface(cfg).compile(params)
        with trace.span("run"):
            out = session.run(spikes)
        with trace.span("block_until_ready"):
            jax.block_until_ready(out)
    tracer.save("trace.json")

``trace.span(...)`` is the module-level entry point the instrumented code
paths use (`InterfaceSession.compile`/``run``, ``benchmarks/noc_bench.py
--trace``, the serve engine's pump): it records into the innermost
*active* tracer, and when none is active it costs one `active_tracer`
check and returns a shared null context - instrumentation can stay in
library code permanently.  While a tracer is active every span also opens
a `jax.profiler.TraceAnnotation`, so when a device profile is being
captured (``jax.profiler.trace``) the host spans show up on its timeline
under the same names and the two traces align.

Spans nest: each event records its depth on its own thread so stack-track
UIs lay them out; `Tracer.instant` adds zero-duration marker events and
`Tracer.interval` an async begin/end pair (a request from submit to
commit, say) that may overlap anything on any thread.

Timestamps are microseconds (the Chrome format's native unit) since the
Unix epoch on ``CLOCK_REALTIME`` (`time.time_ns`), the clock the profiler
stamps its host events with: an event of the ``/host:CPU`` plane of a
``.xplane.pb`` starts at the plane file's ``profile_start_time`` (a stat
of its ``Task Environment`` plane) plus its ``start_ns``.  So a saved
tracer JSON and a device profile of the same run overlay without
shifting; ``otherData.clock`` in the JSON says so.
"""

from __future__ import annotations

import contextlib
import json
import os
import threading
import time

import jax

_STACK: list = []  # innermost active tracer last; module-level by design
_OFF = contextlib.nullcontext()  # what `span` returns while tracing is off
CLOCK = "CLOCK_REALTIME (time.time_ns), microseconds since the Unix epoch"


def active_tracer():
    """The innermost active `Tracer`, or None."""
    return _STACK[-1] if _STACK else None


class Tracer:
    """Collects span events; context-manager activation; Chrome JSON out."""

    def __init__(self, process_name: str = "repro"):
        self.process_name = process_name
        self.events: list = []
        self._local = threading.local()  # per-thread span depth

    # ---- activation ------------------------------------------------------

    def __enter__(self) -> "Tracer":
        _STACK.append(self)
        return self

    def __exit__(self, *exc) -> None:
        # remove this tracer even if spans misnested around activation
        for i in range(len(_STACK) - 1, -1, -1):
            if _STACK[i] is self:
                del _STACK[i]
                break

    # ---- recording -------------------------------------------------------

    @staticmethod
    def _now_us() -> float:
        return time.time_ns() / 1e3

    @contextlib.contextmanager
    def span(self, name: str, **args):
        """Record a complete event around the body (plus a jax annotation)."""
        local = self._local
        depth = getattr(local, "depth", 0)
        local.depth = depth + 1
        start = self._now_us()
        try:
            with jax.profiler.TraceAnnotation(name):
                yield self
        finally:
            end = self._now_us()
            local.depth = depth
            self.events.append(
                {
                    "name": name,
                    "ph": "X",
                    "ts": start,
                    "dur": end - start,
                    "pid": os.getpid(),
                    "tid": threading.get_ident(),
                    "args": {**args, "depth": depth},
                }
            )

    def interval(self, name: str, ident: int, seconds: float, **args) -> None:
        """An async begin/end pair that ends now and began ``seconds`` ago.

        The begin is backdated, so a caller that measured the interval on
        another clock (the serve engine's injectable one) records it when
        it ends.  These events exist only in this tracer's JSON.
        """
        end = self._now_us()
        common = {"name": name, "cat": name, "id": ident, "pid": os.getpid(),
                  "tid": threading.get_ident()}
        self.events.append({**common, "ph": "b", "ts": end - seconds * 1e6, "args": args})
        self.events.append({**common, "ph": "e", "ts": end})

    def instant(self, name: str, **args) -> None:
        """Zero-duration marker event."""
        self.events.append(
            {
                "name": name,
                "ph": "i",
                "s": "t",
                "ts": self._now_us(),
                "pid": os.getpid(),
                "tid": threading.get_ident(),
                "args": args,
            }
        )

    # ---- output ----------------------------------------------------------

    def to_chrome_trace(self) -> dict:
        """The full payload in Chrome trace-event format."""
        meta = {
            "name": "process_name",
            "ph": "M",
            "pid": os.getpid(),
            "tid": 0,
            "args": {"name": self.process_name},
        }
        # ts-sorted: Perfetto tolerates disorder but diffing the JSON is nicer
        events = sorted(self.events, key=lambda e: e["ts"])
        return {
            "traceEvents": [meta, *events],
            "displayTimeUnit": "ms",
            "otherData": {"clock": CLOCK},
        }

    def save(self, path: str) -> str:
        with open(path, "w") as f:
            json.dump(self.to_chrome_trace(), f, indent=1)
        return path


def span(name: str, **args):
    """Span on the active tracer; a shared null context when none is active."""
    tracer = active_tracer()
    if tracer is None:
        return _OFF
    return tracer.span(name, **args)


__all__ = ["Tracer", "span", "active_tracer"]
