"""The program's own host spans in the profiler trace of the window.

``serving/stream.py`` records its step path as ``jax.profiler``
annotations whose names start with ``rsnn.`` (``rsnn.step`` and, inside
it, ``rsnn.refill``, ``rsnn.assemble``, ``rsnn.dispatch``,
``rsnn.complete``, ``rsnn.reset_slot``, ``rsnn.fence``, ``rsnn.retire``,
``rsnn.fence_wait``, ``rsnn.egress``); per-request spans carry ``sid`` and
``slot``, and ``rsnn.egress`` the ``bytes`` and ``valid_bytes`` it
fetched, as event stats.  They sit on the profiler's host plane, on the
same clock as the device operations and inside the harness's own spans
(``bench/lib/trace.py``).  From the trace this module takes:

* per span name: seconds, self seconds (the duration less the part that
  its child spans cover) and count, clipped to the window;
* how often each span is the direct child of each other one;
* the sum of each numeric stat per span name;
* every idle gap of the device, named by the innermost harness span over
  its middle, then ``/`` and the innermost program span there, if any
  (``step_once/rsnn.fence_wait``; bare ``step_once`` where the program
  records nothing over it).

A trace of a program without these spans reduces to empty tables, and
every reader of them then returns None.
"""

from __future__ import annotations

import collections
import dataclasses
import functools
import glob
import os

from bench.lib import trace

PREFIX = "rsnn."
MIN_GAP_S = 1e-3  # the shortest gap counted in ``idle_by_name``


@dataclasses.dataclass
class Span:
    name: str
    start: int  # ns
    end: int  # ns
    stats: dict


@dataclasses.dataclass
class Events:
    """Device operations per device and the harness's and the program's
    host spans, each host thread's in a list of its own, on one clock."""

    devices: dict  # device name -> list of (op name, start_ns, dur_ns)
    threads: list  # [[Span]] per host line


def _numeric(stats) -> dict:
    return {k: v for k, v in stats if isinstance(v, (int, float))}


def load(trace_dir: str) -> Events:
    """Read the newest ``.xplane.pb`` under ``trace_dir``."""
    from jax.profiler import ProfileData

    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not files:
        raise FileNotFoundError(f"no profiler trace under {trace_dir}")
    data = ProfileData.from_file(max(files, key=os.path.getmtime))
    devices, threads = {}, []
    for plane in data.planes:
        ops = [ln for ln in plane.lines if ln.name == "XLA Ops"]
        if plane.name.startswith("/device:") and ops:
            devices[plane.name] = [(trace.op_name(e.name), e.start_ns,
                                    e.duration_ns)
                                   for ln in ops for e in ln.events]
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                spans = [Span(e.name, e.start_ns, e.start_ns + e.duration_ns,
                              _numeric(e.stats) if e.name.startswith(PREFIX)
                              else {})
                         for e in ln.events
                         if e.name in trace.HOST_SPANS
                         or e.name.startswith(PREFIX)]
                if spans:
                    threads.append(spans)
    return Events(devices=devices, threads=threads)


def _nested(spans: list) -> list:
    """``spans`` in start order, outer before inner, each with the index of
    its innermost enclosing span (or None).  The spans of one thread nest,
    as annotations entered and exited in call order do."""
    order = sorted(spans, key=lambda s: (s.start, -s.end,
                                         s.name.startswith(PREFIX)))
    parents, stack = [], []
    for k, s in enumerate(order):
        while stack and order[stack[-1]].end <= s.start:
            stack.pop()
        parents.append(stack[-1] if stack else None)
        stack.append(k)
    return list(zip(order, parents))


@dataclasses.dataclass
class ProgramReduction:
    window_s: float
    spans: dict  # name -> (seconds, self seconds, count), in the window
    children: dict  # (parent name, child name) -> count, in the window
    stat_sums: dict  # name -> {stat: sum}, over the spans in the window
    idle_by_name: dict  # gap name -> idle seconds, gaps >= MIN_GAP_S
    idle_gaps: list  # [(gap name, seconds)], longest first

    def seconds(self, name: str) -> float:
        return self.spans.get(name, (0.0, 0.0, 0))[0]

    def count(self, name: str) -> int:
        return self.spans.get(name, (0.0, 0.0, 0))[2]

    def has_program_spans(self) -> bool:
        return any(n.startswith(PREFIX) for n in self.spans)


def _gap_names(threads: list, mids: list) -> list:
    """The name of the gap at each of ``mids`` (sorted): the innermost
    harness span over it (not the window), then ``/`` and the innermost
    program span, if one covers it."""
    order = sorted((s for spans in threads for s in spans
                    if s.name != "window"),
                   key=lambda s: (s.start, -s.end,
                                  s.name.startswith(PREFIX)))
    names, stack, k = [], [], 0
    for mid in mids:
        while k < len(order) and order[k].start <= mid:
            while stack and stack[-1].end <= order[k].start:
                stack.pop()
            stack.append(order[k])
            k += 1
        while stack and stack[-1].end <= mid:
            stack.pop()
        host = next((s.name for s in reversed(stack)
                     if not s.name.startswith(PREFIX)), "outside_spans")
        prog = next((s.name for s in reversed(stack)
                     if s.name.startswith(PREFIX)), None)
        names.append(host if prog is None else f"{host}/{prog}")
    return names


def reduce(ev: Events, n_gaps: int = 10) -> ProgramReduction:
    wins = [(s.start, s.end) for spans in ev.threads for s in spans
            if s.name == "window"]
    if not wins:
        raise ValueError("the trace holds no 'window' span")
    lo, hi = max(wins, key=lambda w: w[1] - w[0])
    secs, self_s, count = (collections.Counter(), collections.Counter(),
                           collections.Counter())
    children = collections.Counter()
    stat_sums: dict = collections.defaultdict(collections.Counter)
    for spans in ev.threads:
        nested = _nested([s for s in spans if s.name != "window"])
        clipped = [max(0, min(s.end, hi) - max(s.start, lo))
                   for s, _ in nested]
        for (s, parent), d in zip(nested, clipped):
            if s.end <= lo or s.start >= hi:
                continue
            secs[s.name] += d * 1e-9
            self_s[s.name] += d * 1e-9
            count[s.name] += 1
            stat_sums[s.name].update(s.stats)
            if parent is not None:
                p = nested[parent][0]
                self_s[p.name] -= d * 1e-9
                children[(p.name, s.name)] += 1
    gaps = []
    for events in ev.devices.values():
        merged = trace.busy_intervals(events, lo, hi)
        edges = [lo] + [x for ab in merged for x in ab] + [hi]
        gaps.extend(((a + b) / 2, (b - a) * 1e-9)
                    for a, b in zip(edges[0::2], edges[1::2]) if b > a)
    gaps.sort()
    names = _gap_names(ev.threads, [mid for mid, _ in gaps])
    idle = collections.Counter()
    for name, (_, s) in zip(names, gaps):
        if s >= MIN_GAP_S:
            idle[name] += s
    longest = sorted(zip(names, (s for _, s in gaps)), key=lambda g: -g[1])
    return ProgramReduction(
        window_s=(hi - lo) * 1e-9,
        spans={n: (secs[n], self_s[n], count[n]) for n in count},
        children=dict(children), stat_sums={n: dict(c) for n, c in
                                            stat_sums.items()},
        idle_by_name=dict(idle), idle_gaps=longest[:n_gaps])


def dispatch_lags(ev: Events, kernel: str = "megastep") -> list:
    """Seconds from the start of each ``rsnn.dispatch`` span to the start
    of the device operation it enqueued, the ``kernel`` call of that step:
    the i-th call on a device is paired with the i-th dispatch.  Empty
    where the counts differ (a call or a span outside the trace)."""
    starts = sorted(s.start for spans in ev.threads for s in spans
                    if s.name == PREFIX + "dispatch")
    lags = []
    for events in ev.devices.values():
        calls = sorted(s for name, s, _ in events if kernel in name)
        if len(calls) != len(starts):
            return []
        lags.extend((c - d) * 1e-9 for c, d in zip(calls, starts))
    return lags


@functools.lru_cache(maxsize=1)
def _reduce_file(trace_dir: str, mtime_ns: int) -> ProgramReduction:
    return reduce(load(trace_dir))


def of_run(run) -> ProgramReduction | None:
    """The reduction of a traced run's program spans, or None where the run
    was not traced or its trace holds none."""
    if run.reduction is None:
        return None
    from bench.lib import harness

    trace_dir = os.path.join(harness.TRACE_DIR, run.cell.name)
    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not files:
        return None
    newest = max(files, key=os.path.getmtime)
    red = _reduce_file(trace_dir, os.stat(newest).st_mtime_ns)
    return red if red.has_program_spans() else None
