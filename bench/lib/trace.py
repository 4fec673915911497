"""Reduction of a profiler trace of the measured window to device metrics.

Devices are the trace's ``/device:`` planes that hold an ``XLA Ops`` line
(the TPU's, not the profiler's own).  The harness brackets the window with
a ``window`` span and each call into the system with a host span (``jax.profiler.TraceAnnotation``): ``submit``,
``step_once``, ``top_up``, ``harvest`` and ``idle`` (the generator waiting
for the next arrival).  From the trace this module takes, per device:

* busy seconds: the union of the intervals in which an operation ran on the
  device, inside the window;
* the device time of each operation by name, and of the operations whose
  name holds a kernel's name;
* the idle gaps between device operations, each named by the innermost
  host span that covers the gap's middle.
"""

from __future__ import annotations

import collections
import dataclasses
import glob
import os
import re

HOST_SPANS = ("window", "submit", "step_once", "top_up", "harvest", "idle")
_HLO = re.compile(r"^%?(\S+) = \(?(\w+\[[\d,]*\])?")


def op_name(event_name: str) -> str:
    """``%fusion.3 = f32[512,1920]{...} fusion(...)`` -> ``fusion.3
    f32[512,1920]``: the operation's name and the type of its (first)
    result, without the HLO text of its operands."""
    m = _HLO.match(event_name)
    if not m:
        return event_name
    return m.group(1) + (" " + m.group(2) if m.group(2) else "")


@dataclasses.dataclass
class Events:
    """Device operations per device, and host spans: ``(name, start_ns,
    duration_ns)`` on one clock."""

    devices: dict  # device name -> list of (name, start, dur)
    spans: list


def load(trace_dir: str) -> Events:
    """Read the newest ``.xplane.pb`` under ``trace_dir``."""
    from jax.profiler import ProfileData

    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not files:
        raise FileNotFoundError(f"no profiler trace under {trace_dir}")
    data = ProfileData.from_file(max(files, key=os.path.getmtime))
    devices, spans = {}, []
    for plane in data.planes:
        ops = [ln for ln in plane.lines if ln.name == "XLA Ops"]
        if plane.name.startswith("/device:") and ops:
            devices[plane.name] = [(op_name(e.name), e.start_ns,
                                    e.duration_ns)
                                   for ln in ops for e in ln.events]
        elif plane.name.startswith("/host:"):
            spans.extend((e.name, e.start_ns, e.duration_ns)
                         for ln in plane.lines for e in ln.events
                         if e.name in HOST_SPANS)
    return Events(devices=devices, spans=spans)


def window_of(ev: Events) -> tuple[float, float]:
    wins = [(s, s + d) for n, s, d in ev.spans if n == "window"]
    if not wins:
        raise ValueError("the trace holds no 'window' span")
    return max(wins, key=lambda w: w[1] - w[0])


def _clip(events, lo: float, hi: float):
    for name, s, d in events:
        a, b = max(s, lo), min(s + d, hi)
        if b > a:
            yield name, a, b


def busy_intervals(events, lo: float, hi: float) -> list:
    """Merged ``[a, b)`` intervals in which some operation ran."""
    merged: list = []
    for _, a, b in sorted(_clip(events, lo, hi), key=lambda e: e[1]):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return merged


@dataclasses.dataclass
class DeviceReduction:
    window_s: float
    busy_s: float  # averaged over the devices
    op_seconds: dict  # op name -> seconds, summed over the devices
    op_calls: dict  # op name -> number of events
    idle_gaps: list  # [(host span, seconds)], longest first

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def kernel(self, name: str) -> tuple[float, int]:
        """(device seconds, calls) of the operations whose name holds
        ``name``."""
        secs = sum(s for op, s in self.op_seconds.items() if name in op)
        calls = sum(c for op, c in self.op_calls.items() if name in op)
        return secs, calls

    def top_ops(self, n: int = 10) -> list:
        return sorted(self.op_seconds.items(), key=lambda kv: -kv[1])[:n]


def _span_at(spans, t: float) -> str:
    """The innermost host span (other than the window) covering ``t``."""
    best = None
    for name, s, d in spans:
        if name != "window" and s <= t < s + d and (best is None
                                                    or d < best[1]):
            best = (name, d)
    return best[0] if best else "outside_spans"


def reduce(ev: Events, n_gaps: int = 10) -> DeviceReduction:
    lo, hi = window_of(ev)
    window_s = (hi - lo) * 1e-9
    busy, secs, calls, gaps = [], collections.Counter(), \
        collections.Counter(), []
    for events in ev.devices.values():
        merged = busy_intervals(events, lo, hi)
        busy.append(sum(b - a for a, b in merged) * 1e-9)
        for name, a, b in _clip(events, lo, hi):
            secs[name] += (b - a) * 1e-9
            calls[name] += 1
        edges = [lo] + [x for ab in merged for x in ab] + [hi]
        for a, b in zip(edges[0::2], edges[1::2]):
            if b > a:
                gaps.append(((a + b) / 2, (b - a) * 1e-9))
    gaps.sort(key=lambda g: -g[1])
    named = [(_span_at(ev.spans, mid), s) for mid, s in gaps[:n_gaps]]
    return DeviceReduction(
        window_s=window_s,
        busy_s=sum(busy) / len(busy) if busy else 0.0,
        op_seconds=dict(secs), op_calls=dict(calls), idle_gaps=named)
