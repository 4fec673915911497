"""The reduction of the program's own spans on a small synthetic trace, and
on a real profiler trace of annotations made on the CPU."""

import jax
import pytest

from bench.lib import program_trace as pt
from bench.lib import trace

MS = 1_000_000  # ns


def _span(name, a, b, **stats):
    return pt.Span(name, a * MS, b * MS, stats)


def _events():
    # window 0-100 ms; a step before it, a step with every phase, a step
    # whose bookkeeping runs outside rsnn.step, and a step that runs past
    # the window's close
    spans = [
        _span("window", 0, 100),
        _span("step_once", -20, -5), _span("rsnn.step", -18, -6),
        _span("step_once", 10, 60), _span("rsnn.step", 12, 58),
        _span("rsnn.refill", 12, 20), _span("rsnn.reset_slot", 14, 18,
                                            sid=4, slot=1),
        _span("rsnn.dispatch", 20, 22),
        _span("rsnn.complete", 22, 30, sid=3, slot=0),
        _span("rsnn.reset_slot", 24, 28, sid=3, slot=0),
        _span("rsnn.retire", 30, 50), _span("rsnn.fence_wait", 31, 45),
        _span("rsnn.egress", 45, 49, sid=3, bytes=1000, valid_bytes=720),
        _span("step_once", 60, 94), _span("rsnn.step", 62, 70),
        _span("rsnn.dispatch", 63, 64),
        _span("step_once", 94, 110), _span("rsnn.step", 95, 108),
        _span("rsnn.retire", 96, 106), _span("rsnn.fence_wait", 97, 105),
    ]
    device = [("fusion.1", 0, 10 * MS), ("megastep.1", 22 * MS, 1 * MS),
              ("copy.2", 50 * MS, 2 * MS), ("megastep.1", 100 * MS, 5 * MS)]
    return pt.Events(devices={"/device:TPU:0": device}, threads=[spans])


def test_span_seconds_are_clipped_to_the_window():
    red = pt.reduce(_events())
    assert red.window_s == pytest.approx(0.1)
    secs, _, n = red.spans["rsnn.step"]
    # 12-58, 62-70 and 95-100 of 95-108; the step before the window is out
    assert n == 3 and secs == pytest.approx(0.059)
    assert red.spans["rsnn.fence_wait"][0] == pytest.approx(0.017)
    assert red.count("rsnn.fence_wait") == 2


def test_self_time_leaves_out_the_children():
    red = pt.reduce(_events())
    # step 1: 46 - (8 + 2 + 8 + 20); step 2: 8 - 1; step 3: 5 - 4
    assert red.spans["rsnn.step"][1] == pytest.approx(0.016)
    # 20 - (14 + 4), and 4 - 3 inside the window
    assert red.spans["rsnn.retire"][1] == pytest.approx(0.003)
    assert red.spans["rsnn.refill"][1] == pytest.approx(0.004)
    assert red.spans["rsnn.complete"][1] == pytest.approx(0.004)
    assert red.spans["rsnn.egress"][1] == pytest.approx(0.004)


def test_children_and_stats_follow_the_nesting():
    red = pt.reduce(_events())
    assert red.children[("rsnn.refill", "rsnn.reset_slot")] == 1
    assert red.children[("rsnn.complete", "rsnn.reset_slot")] == 1
    assert red.children[("rsnn.retire", "rsnn.egress")] == 1
    assert red.children[("step_once", "rsnn.step")] == 3
    assert red.stat_sums["rsnn.egress"]["bytes"] == 1000
    assert red.stat_sums["rsnn.egress"]["valid_bytes"] == 720


def test_gaps_are_named_by_harness_then_program_span():
    red = pt.reduce(_events())
    # gaps: [10,22) mid 16 in rsnn.reset_slot, [23,50) mid 36.5 in
    # rsnn.fence_wait, [52,100) mid 76 in step_once outside rsnn.step
    assert red.idle_gaps == [
        ("step_once", pytest.approx(0.048)),
        ("step_once/rsnn.fence_wait", pytest.approx(0.027)),
        ("step_once/rsnn.reset_slot", pytest.approx(0.012))]
    assert red.idle_by_name == {
        "step_once": pytest.approx(0.048),
        "step_once/rsnn.fence_wait": pytest.approx(0.027),
        "step_once/rsnn.reset_slot": pytest.approx(0.012)}


def test_a_bare_gap_keeps_the_harness_reduction_name():
    ev = _events()
    spans = [(s.name, s.start, s.end - s.start) for s in ev.threads[0]
             if not s.name.startswith(pt.PREFIX)]
    theirs = trace.reduce(trace.Events(devices=ev.devices, spans=spans))
    assert theirs.idle_gaps[0] == pt.reduce(ev).idle_gaps[0]


def test_gaps_shorter_than_a_millisecond_are_not_summed_by_name():
    ev = _events()
    ev.devices["/device:TPU:0"].append(("copy.3", 75 * MS, 24 * MS + MS // 2))
    red = pt.reduce(ev)
    # [52,75) mid 63.5 in rsnn.dispatch; [99.5,100): 0.5 ms
    assert red.idle_by_name["step_once/rsnn.dispatch"] == pytest.approx(0.023)
    assert red.idle_gaps[-1] == ("step_once/rsnn.fence_wait",
                                 pytest.approx(5e-4))
    assert red.idle_by_name["step_once/rsnn.fence_wait"] == pytest.approx(
        0.027)
    assert sum(red.idle_by_name.values()) == pytest.approx(
        0.023 + 0.027 + 0.012)


def test_a_trace_without_program_spans_has_none():
    ev = _events()
    ev.threads = [[s for s in ev.threads[0]
                   if not s.name.startswith(pt.PREFIX)]]
    red = pt.reduce(ev)
    assert not red.has_program_spans()
    assert red.count("rsnn.step") == 0 and red.seconds("rsnn.egress") == 0
    assert set(red.idle_by_name) == {"step_once"}


def test_dispatch_lags_pair_each_call_with_its_dispatch():
    ev = _events()
    assert pt.dispatch_lags(ev) == [pytest.approx(0.002),
                                    pytest.approx(0.037)]
    ev.devices["/device:TPU:0"].append(("megastep.1", 120 * MS, MS))
    assert pt.dispatch_lags(ev) == []


def test_load_reads_names_and_stats_from_a_profiler_trace(tmp_path):
    with jax.profiler.trace(str(tmp_path)):
        with jax.profiler.TraceAnnotation("window"):
            with jax.profiler.TraceAnnotation("step_once"):
                with jax.profiler.TraceAnnotation("rsnn.egress", sid=7,
                                                  bytes=96, valid_bytes=48):
                    jax.block_until_ready(jax.numpy.ones(3) + 1)
            with jax.profiler.TraceAnnotation("not_ours"):
                pass
    ev = pt.load(str(tmp_path))
    spans = {s.name: s for t in ev.threads for s in t}
    assert set(spans) == {"window", "step_once", "rsnn.egress"}
    assert spans["rsnn.egress"].stats == {"sid": 7, "bytes": 96,
                                          "valid_bytes": 48}
    red = pt.reduce(ev)
    assert red.children == {("step_once", "rsnn.egress"): 1}
    assert red.stat_sums["rsnn.egress"]["valid_bytes"] == 48
