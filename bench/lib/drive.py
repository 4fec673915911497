"""The measured window: traffic from a ``Plan`` into the served loop that
the configuration's model family builds (a ``ServedLoop``; for the RSNN the
program's ``StreamLoop``), timed on the host clock.

The window drives ``submit`` -> ``step_once`` -> the logits that the
request's ``stacked_logits`` returns, which the check compares.

Every number the window reports is the harness's own: slot-frames, the
requests due or done in the window, and when their logits reached the
host, all counted and stamped by the ``Tracker`` from what it sees of the
program between steps, on the harness's clock.  The program's own
counters and timestamps are read only as diagnostics.
"""

from __future__ import annotations

import contextlib
import dataclasses
import time
from typing import Protocol, Sequence, Sized, runtime_checkable

import jax
import numpy as np

from bench.lib import traffic

BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
CACHE_HIT = "/jax/compilation_cache/cache_hits"


@runtime_checkable
class ServedRequest(Protocol):
    """One request as the loop serves it (the RSNN's ``StreamRequest``)."""

    sid: int  # what ``ServedLoop.submit`` returned for it
    logits: Sequence  # its logit rows on the host so far, one per frame

    def stacked_logits(self) -> np.ndarray:
        """All its logit rows, (frames, classes), fetched if need be."""


@runtime_checkable
class ServedLoop(Protocol):
    """What the harness drives and reads of a model family's served loop
    (the RSNN's ``StreamLoop``).  A step advances every request that holds
    a slot by one frame; a request takes a slot in the step that first
    advances it and is out of ``slot_req`` after the step that advances its
    last frame (the ``Tracker`` counts slot-frames by this)."""

    queue: Sized  # submitted requests waiting for a slot
    slot_req: Sequence  # per slot, the ServedRequest that holds it or None
    finished: list  # completed requests
    frames_served: int  # the loop's own count of slot-frames (diagnostic)
    pipeline_depth: int  # steps in flight before their logits land

    def submit(self, frames: np.ndarray) -> int:
        """Queue an utterance; returns its ``sid``."""

    def step_once(self) -> bool:
        """Refill free slots and advance one frame; False when it had
        nothing to dispatch."""

    def flush(self) -> None:
        """Land the logits of every step in flight."""

    def run(self) -> list:
        """Serve until the queue and the slots are empty."""

    def reset_metrics(self) -> None:
        """Zero the loop's counters (``frames_served`` among them)."""


class CompileCounter:
    """Counts compiles and persistent-cache loads in this process; ``n``
    is both together."""

    def __init__(self):
        self.compiles = self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    @property
    def n(self) -> int:
        return self.compiles + self.cache_hits

    def _duration(self, event, duration, **kw):
        if event == BACKEND_COMPILE:
            self.compiles += 1

    def _event(self, event, **kw):
        if event == CACHE_HIT:
            self.cache_hits += 1


class Host:
    """Host spans: profiler annotations when tracing, and, always, the
    longest single call of each kind."""

    def __init__(self, tracing: bool, clock):
        self.tracing = tracing
        self.clock = clock
        self.longest: dict = {}
        self._window = None

    def open_window(self) -> None:
        if self.tracing:
            self._window = jax.profiler.TraceAnnotation("window")
            self._window.__enter__()

    def close_window(self) -> None:
        if self._window is not None:
            self._window.__exit__(None, None, None)
            self._window = None

    @contextlib.contextmanager
    def span(self, name: str):
        t = self.clock()
        if self.tracing:
            with jax.profiler.TraceAnnotation(name):
                yield
        else:
            yield
        d = self.clock() - t
        if d > self.longest.get(name, 0.0):
            self.longest[name] = d


class Seen:
    """One request as the harness saw it, on the harness's clock.

    ``handle`` is the program's request; of it the harness reads only its
    place in the slot table (``ServedLoop.slot_req``) and the logit rows
    it holds on the host (``logits``)."""

    __slots__ = ("req", "sid", "handle", "t_submit", "t_due", "step_start",
                 "step_left", "t_left", "t_host")

    def __init__(self, req: traffic.Request, sid: int, t_submit: float,
                 t_due: float | None = None):
        self.req, self.sid = req, sid
        self.t_submit = t_submit
        self.t_due = t_due  # open loop: when it was due
        self.handle = None
        self.step_start = self.step_left = None  # harness step indices
        self.t_left = None  # when it left its slot (last frame dispatched)
        self.t_host = None  # when all its logits were first seen on the host

    def on_host(self) -> bool:
        return len(self.handle.logits) >= self.req.length


class Tracker:
    """The harness's own record of the requests it submits: after every
    ``step_once`` it reads the slot table and stamps, on its own clock,
    which requests took a slot, which left one, and when each one's logits
    are all on the host.  The slot-frames a step advanced are the requests
    that held a slot before it or after it: a request that takes a slot
    in a step is in the table after it, and one that leaves was in it
    before (no utterance is one frame long)."""

    def __init__(self, loop: ServedLoop, clock):
        self.loop, self.clock = loop, clock
        self.seen: dict = {}  # sid -> Seen, every request submitted
        self.steps = 0  # step_once calls that advanced a frame
        self.frames = 0  # slot-frames advanced by them
        self._slots: dict = {}  # sid -> Seen holding a slot after the step
        self._leaving: list = []  # left a slot, logits not all on the host

    def submit(self, req: traffic.Request, frames, t_due=None) -> Seen:
        sid = self.loop.submit(frames)
        s = self.seen[sid] = Seen(req, sid, self.clock(), t_due)
        return s

    def step(self) -> bool:
        """One ``step_once``, observed; returns what it returned."""
        progressed = self.loop.step_once()
        self.observe()
        return progressed

    def observe(self) -> None:
        now = self.clock()
        held = {}
        for r in self.loop.slot_req:
            if r is not None:
                s = held[r.sid] = self.seen[r.sid]
                if s.handle is None:
                    s.handle, s.step_start = r, self.steps + 1
        left = [s for sid, s in self._slots.items() if sid not in held]
        if held or left:  # else the call dispatched nothing: idle, draining
            self.steps += 1
            self.frames += len(held) + len(left)
        for s in left:
            s.step_left, s.t_left = self.steps, now
        self._slots = held
        self._leaving.extend(left)
        if self._leaving:  # a call that only retires can land logits too
            self._leaving = [s for s in self._leaving if not self._landed(s)]

    def _landed(self, s: Seen) -> bool:
        if s.on_host():
            s.t_host = self.clock()
            return True
        return False

    def flush(self) -> None:
        """The program's ``flush``, then a last look for logits."""
        self.loop.flush()
        self._leaving = [s for s in self._leaving if not self._landed(s)]


@dataclasses.dataclass
class Window:
    """What the measured window saw."""

    t_open: float
    t_close: float
    frames: int  # slot-frames advanced by the steps dispatched in it
    steps: int
    step_periods: list
    compiles: int
    served: list  # every Seen of the run
    measured: list  # the Seen the window's metrics and check cover
    unfinished: int  # measured requests whose logits never reached the host
    host_longest: dict
    prep: dict = dataclasses.field(default_factory=dict)  # seconds spent
    # before the window opened, by what the harness was doing
    program_frames: int = 0  # the program's own count of the same, a
    # diagnostic
    slot_steps_off: int = 0  # measured requests that held a slot for
    # another number of steps than they have frames: a step that advanced
    # no frame, or two, would make ``frames`` wrong

    @property
    def seconds(self) -> float:
        return self.t_close - self.t_open


def _finish(tracker: Tracker, measured: list) -> tuple:
    """(unfinished, slot_steps_off) of the measured requests."""
    unfinished = sum(s.t_host is None for s in measured)
    off = sum(s.step_start is not None and s.step_left is not None
              and s.step_left - s.step_start + 1 != s.req.length
              for s in measured)
    return unfinished, off


def closed_loop(loop: ServedLoop, plan: traffic.Plan, seconds: float,
                host: Host, counter: CompileCounter) -> Window:
    """A queue that never runs dry: slots start with residual-life tails
    and the queue is topped up to ``plan.queue_floor`` after every step.
    The window measures the requests that left their slot in it."""
    clock = host.clock
    track = Tracker(loop, clock)

    def submit(req):
        with host.span("submit"):
            track.submit(req, plan.frames(req))

    t0 = clock()
    for req in plan.first:
        submit(req)
    while len(loop.queue) < plan.queue_floor:
        submit(plan.next_pooled())
    t1 = clock()
    for _ in range(max(loop.pipeline_depth, 1)):  # fill slots, fill pipe
        track.step()
    prep = {"submit_s": t1 - t0, "first_steps_s": clock() - t1}
    host.open_window()
    c0, f0, s0, p0 = counter.n, track.frames, track.steps, loop.frames_served
    periods = []
    t_open = last = clock()
    while last - t_open < seconds:
        with host.span("step_once"):
            track.step()
        with host.span("top_up"):
            while len(loop.queue) < plan.queue_floor:
                submit(plan.next_pooled())
        now = clock()
        periods.append(now - last)
        last = now
    t_close = last
    host.close_window()
    frames, steps = track.frames - f0, track.steps - s0
    program_frames = loop.frames_served - p0
    compiles = counter.n - c0
    with host.span("harvest"):
        track.flush()
    served = list(track.seen.values())
    measured = [s for s in served
                if s.step_left is not None and s0 < s.step_left <= s0 + steps]
    unfinished, off = _finish(track, measured)
    return Window(t_open, t_close, frames, steps, periods, compiles, served,
                  measured, unfinished, host.longest, prep, program_frames,
                  off)


def open_loop(loop: ServedLoop, plan: traffic.Plan, seconds: float,
              host: Host, counter: CompileCounter) -> Window:
    """Arrivals at their due times from the generator's start; the window
    opens after the pre-roll, and the load stays on after it closes until
    every request due in it has its logits on the host (or the drain
    timeout passes)."""
    clock = host.clock
    track = Tracker(loop, clock)
    arrivals = sorted(plan.arrivals, key=lambda r: r.due)
    drain = float(plan.traffic["drain_timeout_s"])
    g0 = clock()
    w0, w1 = g0 + plan.preroll_s, g0 + plan.preroll_s + seconds
    i, n = 0, len(arrivals)
    t_open = t_close = None
    c0 = f0 = s0 = p0 = frames = steps = compiles = program_frames = 0
    periods, waiting = [], []
    last = g0
    while True:
        now = clock()
        if t_open is None and now >= w0:
            host.open_window()
            t_open = last = clock()
            c0, f0, s0 = counter.n, track.frames, track.steps
            p0 = loop.frames_served
        while i < n and g0 + arrivals[i].due <= now:
            req = arrivals[i]
            with host.span("submit"):
                s = track.submit(req, plan.frames(req), g0 + req.due)
            if req.segment == "window":
                waiting.append(s)
            i += 1
        if t_open is not None and t_close is None and now >= w1:
            t_close = now
            host.close_window()
            frames, steps = track.frames - f0, track.steps - s0
            program_frames = loop.frames_served - p0
            compiles = counter.n - c0
        if t_close is not None:
            waiting = [s for s in waiting if s.t_host is None]
            if not waiting or now > w1 + drain:
                break
        with host.span("step_once"):
            progressed = track.step()
        if not progressed:
            nxt = g0 + arrivals[i].due if i < n else now + 1e-3
            with host.span("idle"):
                time.sleep(min(max(nxt - clock(), 0.0), 1e-3))
        if t_open is not None and t_close is None:
            now = clock()
            periods.append(now - last)
            last = now
    with host.span("harvest"):
        track.flush()
    served = list(track.seen.values())
    measured = [s for s in served if s.req.segment == "window"]
    unfinished, off = _finish(track, measured)
    return Window(t_open, t_close, frames, steps, periods, compiles, served,
                  measured, unfinished, host.longest,
                  {"preroll_s": t_open - g0}, program_frames, off)
