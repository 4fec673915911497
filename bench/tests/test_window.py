"""Window accounting of the drivers, on a stand-in loop with a fake clock."""

import collections
import types

import numpy as np

from bench.lib import drive, spec, traffic
from bench.tests import tiny

RSNN = spec.Bench(tiny.ROOT).family("rsnn")


class Clock:
    def __init__(self):
        self.t = 100.0

    def __call__(self):
        self.t += 1e-3  # every reading moves time on by 1 ms
        return self.t


class StandInLoop:
    """Slots advance one frame per step; a request's logits reach the host
    one step after its last frame (as the pipelined loop's do).
    ``lost`` names requests whose logits it never fetches."""

    def __init__(self, slots, clock, lost=()):
        self.slots, self.clock = slots, clock
        self.pipeline_depth = 2
        self.queue = collections.deque()
        self.finished, self.slot_req = [], [None] * slots
        self.frames_served = self.steps = 0
        self._sid, self._retire, self.lost = 0, [], set(lost)

    def submit(self, frames):
        r = types.SimpleNamespace(sid=self._sid, left=len(frames),
                                  n=len(frames), logits=[],
                                  t_submit=self.clock(), t_start=None)
        self._sid += 1
        self.queue.append(r)
        return r.sid

    def _harvest(self):
        for r in self._retire:
            if r.sid not in self.lost:
                r.logits.extend([0.0] * r.n)
        self._retire = []

    def step_once(self):
        self._harvest()
        for i in range(self.slots):
            if self.slot_req[i] is None and self.queue:
                self.slot_req[i] = self.queue.popleft()
                self.slot_req[i].t_start = self.clock()
        active = [r for r in self.slot_req if r is not None]
        if not active:
            return False
        self.steps += 1
        self.frames_served += len(active)
        for i, r in enumerate(self.slot_req):
            if r is not None:
                r.left -= 1
                if r.left == 0:
                    self.finished.append(r)
                    self._retire.append(r)
                    self.slot_req[i] = None
        return True

    def flush(self):
        self._harvest()


def _plan(name, seconds, **lengths):
    mix = tiny.load("traffic", name + ".json")
    mix["lengths"].update(lengths)
    mix["features"]["bank_frames"] = 64
    if mix["loop"] == "open":
        mix.update(rate_per_s=50.0, preroll_s=0.2, drain_timeout_s=5.0)
    return traffic.Plan(mix, slots=4, seconds=seconds, seed=7,
                        make_bank=lambda features, rng: RSNN.FeatureBank(
                            features, 2, -5, rng))


class NoCompiles:
    n = 0


def test_frames_per_s_counts_slot_frames_not_completions():
    clock = Clock()
    plan = _plan("timit_backlog", 0.5, min=900, max=900, median=900)
    loop = StandInLoop(4, clock)
    w = drive.closed_loop(loop, plan, 0.5, drive.Host(False, clock),
                          NoCompiles())
    # every slot holds a 900-frame utterance or its tail; some tails end
    assert w.frames == 4 * w.steps == w.program_frames
    assert w.frames / w.seconds > 0


def test_slot_frames_are_the_harness_count_not_the_programs():
    clock = Clock()
    plan = _plan("commands_backlog", 0.3, min=5, max=9)
    loop = StandInLoop(4, clock)
    loop.__class__ = type("Boasting", (StandInLoop,), {
        "step_once": lambda self: (StandInLoop.step_once(self),
                                   setattr(self, "frames_served",
                                           self.frames_served + 99))[0]})
    w = drive.closed_loop(loop, plan, 0.3, drive.Host(False, clock),
                          NoCompiles())
    assert w.frames == 4 * w.steps
    assert w.program_frames == w.frames + 99 * w.steps
    assert w.slot_steps_off == 0


def test_open_window_counts_requests_due_in_it_even_when_late():
    clock = Clock()
    plan = _plan("timit_ptt_rate", 1.0, min=30, max=30, median=30)
    loop = StandInLoop(64, clock)
    w = drive.open_loop(loop, plan, 1.0, drive.Host(False, clock),
                        NoCompiles())
    window = [r for r in plan.arrivals if r.segment == "window"]
    assert sorted(s.req.idx for s in w.measured) == sorted(
        r.idx for r in window)
    assert w.unfinished == 0
    # requests due near the close finish after it, and still count
    assert max(s.t_host for s in w.measured) > w.t_close
    assert all(s.req.segment == "window" for s in w.measured)
    # requests due after the window were served but are not measured
    assert any(s.req.segment == "post" for s in w.served)
    lat = [s.t_host - s.t_due for s in w.measured]
    assert min(lat) >= 30 * 1e-3  # at least one clock tick per frame


def test_logits_landing_in_a_call_that_dispatches_nothing_are_stamped():
    clock = Clock()
    plan = _plan("timit_ptt_rate", 2.0, min=10, max=10, median=10)
    plan.arrivals = [r for r in plan.arrivals if r.segment != "window"][:1] \
        + [r for r in plan.arrivals if r.segment == "window"][::10]
    w = drive.open_loop(StandInLoop(4, clock), plan, 2.0,
                        drive.Host(False, clock), NoCompiles())
    assert w.measured and w.unfinished == 0
    # alone in the loop, a request's logits land in the call after its
    # last step, which finds no slot to fill: stamped then, not later
    assert max(s.t_host - s.t_left for s in w.measured) < 0.01


def test_closed_window_measures_completions_inside_it():
    clock = Clock()
    plan = _plan("commands_backlog", 0.3, min=5, max=9)
    w = drive.closed_loop(StandInLoop(4, clock), plan, 0.3,
                          drive.Host(False, clock), NoCompiles())
    assert w.measured
    assert all(w.t_open <= s.t_left <= w.t_close for s in w.measured)
    done_in = [s for s in w.served if s.t_left is not None
               and w.t_open <= s.t_left <= w.t_close]
    assert len(done_in) == len(w.measured)
    assert all(s.t_host >= s.t_left for s in w.measured)
    assert w.unfinished == 0 and w.slot_steps_off == 0
    assert np.isfinite(w.seconds) and w.seconds >= 0.3


def test_closed_window_counts_completions_whose_logits_never_came():
    clock = Clock()
    plan = _plan("commands_backlog", 0.3, min=5, max=9)
    loop = StandInLoop(4, clock, lost=range(1, 10_000, 5))
    w = drive.closed_loop(loop, plan, 0.3, drive.Host(False, clock),
                          NoCompiles())
    lost = [s for s in w.measured if s.sid in loop.lost]
    assert lost and w.unfinished == len(lost)
    assert all(s.t_host is None for s in lost)
