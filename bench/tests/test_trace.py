"""The trace reduction on a small synthetic trace."""

import pytest

from bench.lib import trace

MS = 1_000_000  # ns


def _events():
    # window 0-100 ms; two device ops overlap, then a gap during step_once,
    # then a megastep call, then a gap during idle, then a call that runs
    # past the window's close
    device = [("fusion.1", 5 * MS, 10 * MS), ("copy.2", 10 * MS, 10 * MS),
              ("megastep.1", 40 * MS, 20 * MS),
              ("megastep.1", 95 * MS, 10 * MS)]
    spans = [("window", 0, 100 * MS), ("step_once", 20 * MS, 25 * MS),
             ("submit", 25 * MS, 2 * MS), ("idle", 60 * MS, 30 * MS)]
    return trace.Events(devices={"/device:TPU:0": device}, spans=spans)


def test_busy_is_the_union_of_device_intervals_inside_the_window():
    red = trace.reduce(_events())
    assert red.window_s == pytest.approx(0.1)
    # [5, 20) + [40, 60) + [95, 100) = 40 ms
    assert red.busy_s == pytest.approx(0.040)
    assert red.idle_share == pytest.approx(0.6)


def test_kernel_time_by_name():
    red = trace.reduce(_events())
    secs, calls = red.kernel("megastep")
    assert calls == 2 and secs == pytest.approx(0.025)
    assert red.top_ops(1) == [("megastep.1", pytest.approx(0.025))]


def test_gaps_are_named_by_the_innermost_host_span():
    red = trace.reduce(_events())
    # gaps: [0,5) outside, [20,40) mid 30: step_once, [60,95) mid 77.5: idle
    assert red.idle_gaps[0] == ("idle", pytest.approx(0.035))
    assert red.idle_gaps[1] == ("step_once", pytest.approx(0.020))
    assert red.idle_gaps[2] == ("outside_spans", pytest.approx(0.005))


def test_busy_averages_over_devices():
    ev = _events()
    ev.devices["/device:TPU:1"] = [("megastep.1", 0, 100 * MS)]
    red = trace.reduce(ev)
    assert red.busy_s == pytest.approx((0.040 + 0.100) / 2)


def test_a_trace_without_a_window_span_is_refused():
    ev = _events()
    ev.spans = [s for s in ev.spans if s[0] != "window"]
    with pytest.raises(ValueError):
        trace.reduce(ev)


def test_op_names_drop_the_operands():
    assert trace.op_name("%fusion = f32[512,256,1920]{2,1,0:T(8,128)} "
                         "fusion(f32[512,256,1920]{2,1,0} %ring.1)") == (
        "fusion f32[512,256,1920]")
    assert trace.op_name("%megastep.1 = (f32[2,512,128]{2,1,0}, f32[512,"
                         "128]{1,0}) custom-call(%megastep.0)") == (
        "megastep.1 f32[2,512,128]")
    assert trace.op_name("jit_scatter(123)") == "jit_scatter(123)"
