"""The readers of the serve pump's spans, on traces counted by hand."""

import pytest

from chip import harness

READERS = ("pump_host_ms.fleet", "pump_wait_pct.fleet", "lock_wait_ms.fleet")
SPANS = {"serve.pump": [0.010, 0.004, 0.001], "serve.step": [0.006, 0.002],
         "serve.pump.wait": [0.5, 0.25],
         "serve.lock_wait": [0.0002, 0.0001, 0.0003]}


def trace(spans):
    return {"window_s": 2.0, "spans": spans}


@pytest.mark.parametrize("name, value", [
    # (15 ms of pumps - 8 ms of steps) / 2 steps
    ("pump_host_ms.fleet", 3.5),
    # 0.75 s of waits in a 2 s window
    ("pump_wait_pct.fleet", 37.5),
    # 0.6 ms of lock waits / 2 steps
    ("lock_wait_ms.fleet", 0.3),
])
def test_pump_span_reader_gives_the_hand_count(name, value):
    assert harness.reader(name)(trace(SPANS), None) == pytest.approx(value)


def test_a_pump_that_never_waited_waited_0_percent():
    spans = {k: v for k, v in SPANS.items() if k != "serve.pump.wait"}
    assert harness.reader("pump_wait_pct.fleet")(trace(spans), None) == 0.0


@pytest.mark.parametrize("name", READERS)
def test_pump_span_reader_finds_nothing_without_pump_spans(name):
    # what an engine without pump spans leaves in a traced window
    spans = {"serve.step": [0.006, 0.002], "serve.device_transfer": [0.001],
             "bench.submit": [0.0001]}
    assert harness.reader(name)(trace(spans), None) is None
