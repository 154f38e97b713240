"""The reduction from a profiler trace to the per-layer numbers."""

import os

import pytest

from chip import trace_reduce

FIXTURE = os.path.join(os.path.dirname(__file__), "fixtures",
                       "tpu_two_ops.xplane.pb")


def test_recorded_tpu_trace_reduces_to_its_hand_count():
    # Recorded on a TPU v5 lite: one jitted fusion called twice inside a
    # "bench.call" annotation.
    host, devices = trace_reduce.read(FIXTURE)
    assert list(devices) == ["/device:TPU:0"]
    ops = devices["/device:TPU:0"]
    assert len(ops) == 2
    (w0, w1), = [(s, e) for n, s, e in host if n == "bench.call"]
    # the device's clock is aligned to the host's only roughly: the first
    # call's op starts before the annotation does, so only what lies
    # inside the window counts
    busy = sum(max(0, min(e, w1) - max(s, w0)) for _, s, e in ops)
    assert 0 < busy < sum(e - s for _, s, e in ops)
    r = trace_reduce.reduce_events(host, devices, window="bench.call")
    assert r["devices"] == 1
    assert r["window_s"] == pytest.approx((w1 - w0) / 1e9)
    assert r["busy_s"] == pytest.approx(busy / 1e9)
    assert r["idle_share"] == pytest.approx(1 - busy / (w1 - w0))
    assert r["collective_share"] == 0.0
    assert r["device_ops"] == [["fusion f32[128,128]", pytest.approx(
        busy / 1e9)]]
    assert r["idle_gaps"][0][0] == "host.other"
    assert sum(g for _, g in r["idle_gaps"]) == pytest.approx(
        (w1 - w0 - busy) / 1e9)
    assert trace_reduce.reduce(FIXTURE, window="bench.call") == r


def test_nested_ops_count_busy_once_and_rank_by_self_time():
    host = [("bench.window", 0, 100), ("interface.run_batched", 0, 10),
            ("bench.call", 0, 60)]
    ops = [("%while.1 = (f32[2]) while(...)", 10, 50),
           ("%fusion.2 = f32[4]{0} fusion(...)", 12, 30),
           ("%fusion.2 = f32[4]{0} fusion(...)", 32, 40),
           ("%all-reduce.3 = f32[4]{0} all-reduce(...)", 70, 80),
           ("%copy.9 = f32[4]{0} copy(...)", 95, 120)]
    r = trace_reduce.reduce_events(host, {"/device:TPU:0": ops})
    assert r["window_s"] == pytest.approx(100e-9)
    # [10, 50] + [70, 80] + [95, 100] (clipped to the window)
    assert r["busy_s"] == pytest.approx(55e-9)
    assert r["device_ops"][0] == ["fusion.2 f32[4]", pytest.approx(26e-9)]
    assert dict((k, v) for k, v in r["device_ops"])["while.1 (f32[2])"] == \
        pytest.approx(14e-9)
    assert r["collective_share"] == pytest.approx(10 / 55)
    gaps = dict((k, v) for k, v in r["idle_gaps"])
    # [0, 10] inside run_batched (the innermost span), [50, 70] named by
    # bench.call at its midpoint, [80, 95] outside every span
    assert gaps["interface.run_batched"] == pytest.approx(10e-9)
    assert gaps["bench.call"] == pytest.approx(20e-9)
    assert gaps["host.other"] == pytest.approx(15e-9)
    assert r["spans"]["interface.run_batched"] == [pytest.approx(10e-9)]


def test_busy_is_averaged_over_the_devices_used():
    host = [("bench.window", 0, 100)]
    devices = {"/device:TPU:0": [("%a = f32[1] add()", 0, 50)],
               "/device:TPU:1": [("%a = f32[1] add()", 0, 30)],
               "/device:TPU:2": [("%a = f32[1] add()", 200, 300)]}
    r = trace_reduce.reduce_events(host, devices)
    assert r["devices"] == 2
    assert r["busy_s"] == pytest.approx(40e-9)


def test_a_trace_without_window_or_device_work_is_refused():
    with pytest.raises(ValueError, match="bench.window"):
        trace_reduce.reduce_events([("x", 0, 1)], {})
    with pytest.raises(ValueError, match="no device operation"):
        trace_reduce.reduce_events([("bench.window", 0, 10)],
                                   {"/device:TPU:0": [("%a = f32[1] a()",
                                                       20, 30)]})
