"""Reduction of a profiler trace to device busy time, program and
kernel times, and idle gaps named by what the host was doing.

``events(xspace)`` reads a JAX profiler ``.xplane.pb`` (through
``jax.profiler.ProfileData``) into plain tuples; ``Trace`` does the
arithmetic on them, so the arithmetic can be tested on hand-made
events.  All times are nanoseconds on the trace's one clock, which the
profiler shares between host threads and device timelines.

- Device ops: the events of each device plane's ``XLA Ops`` line.
  Busy time is the union of their intervals inside the traced window.
- Programs: the events of the ``XLA Modules`` line, matched by name.
- Host spans: the benchmark's own ``bench.*`` annotations on the host
  plane.  The traced window runs from the first such span's start to
  the last one's end.
- Idle gaps: the holes between merged busy intervals of the first
  device, each named by the innermost ``bench.*`` span open at its
  middle (``host.other`` where none is).
"""

from __future__ import annotations

import dataclasses
import re
from collections import defaultdict
from typing import Optional

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
SPAN_PREFIX = "bench."


@dataclasses.dataclass
class Event:
    name: str
    start: float          # ns
    end: float            # ns


@dataclasses.dataclass
class Events:
    ops: dict             # device id -> [Event] (XLA Ops)
    modules: dict         # device id -> [Event] (XLA Modules)
    spans: list           # [Event] bench.* host spans


def events(profile) -> Events:
    """Plain events of a ``jax.profiler.ProfileData``."""
    ops, modules, spans = defaultdict(list), defaultdict(list), []
    for plane in profile.planes:
        m = DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            if m:
                dev = int(m.group(1))
                dest = {"XLA Ops": ops, "XLA Modules": modules}.get(line.name)
                if dest is not None:
                    dest[dev].extend(Event(e.name, e.start_ns, e.end_ns)
                                     for e in line.events)
            elif plane.name.startswith("/host"):
                spans.extend(Event(e.name, e.start_ns, e.end_ns)
                             for e in line.events
                             if e.name.startswith(SPAN_PREFIX))
    return Events(dict(ops), dict(modules), spans)


def load(path: str) -> Events:
    from jax.profiler import ProfileData
    return events(ProfileData.from_file(path))


def merge(intervals, lo: float, hi: float) -> list:
    """Union of ``(start, end)`` intervals clipped to [lo, hi], sorted."""
    out = []
    for s, e in sorted(intervals):
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def overlap(a: list, b: list) -> float:
    """Length of the intersection of two merged interval lists."""
    i = j = 0
    total = 0.0
    while i < len(a) and j < len(b):
        s, e = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if e > s:
            total += e - s
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


_CONTAINER = re.compile(r"^%?\S+ = .*? (while|conditional|call)\(")
_HLO = re.compile(r"^%?(\S+) = (\S+).*? ([\w\-]+)\(")


def short(name: str) -> str:
    """``opcode name result-shape`` of an op event named by its HLO text
    (``%sort.6 = (f32[64,5,49152]...) sort(...)``); other names as they
    are."""
    m = _HLO.match(name)
    if not m:
        return name
    return f"{m.group(3)} {m.group(1)} {m.group(2).lstrip('(').split('{')[0]}"


_SHAPE = re.compile(r"(\w+)\[([\d,]*)\](\{[^}]*\})?")


def operands(name: str) -> list:
    """``[(dtype, dims, memory space)]`` of the operands of a
    custom call named by its HLO text, in order; memory space 0 is the
    chip's HBM, ``S(1)`` in a layout its on-core fast memory."""
    at = name.find("custom-call(")
    if at < 0:
        return []
    i = at + len("custom-call(")
    depth, j = 1, i
    while j < len(name) and depth:
        depth += {"(": 1, ")": -1}.get(name[j], 0)
        j += 1
    out = []
    for m in _SHAPE.finditer(name[i:j - 1]):
        dims = tuple(int(x) for x in m.group(2).split(",") if x)
        space = re.search(r"S\((\d+)\)", m.group(3) or "")
        out.append((m.group(1), dims, int(space.group(1)) if space else 0))
    return out


class Trace:
    """Reduction of ``Events`` over the traced window."""

    def __init__(self, ev: Events, window: Optional[tuple] = None):
        self.ev = ev
        self.devices = sorted(ev.ops)
        if window is None:
            if not ev.spans:
                raise ValueError("no bench.* span in the trace")
            window = (min(s.start for s in ev.spans),
                      max(s.end for s in ev.spans))
        self.lo, self.hi = window
        self.busy = {d: merge([(e.start, e.end) for e in ev.ops[d]],
                              self.lo, self.hi) for d in self.devices}

    @property
    def window_s(self) -> float:
        return (self.hi - self.lo) * 1e-9

    def busy_s(self) -> float:
        """Busy seconds, averaged over the devices."""
        if not self.devices:
            return 0.0
        return sum(e - s for d in self.devices for s, e in self.busy[d]) \
            / len(self.devices) * 1e-9

    def span_total(self, name: str) -> list:
        return merge([(s.start, s.end) for s in self.ev.spans
                      if s.name == name], self.lo, self.hi)

    def busy_outside(self, name: str) -> tuple:
        """(busy s, window s) over the time no ``name`` span is open,
        averaged over the devices."""
        cut = self.span_total(name)
        cut_len = sum(e - s for s, e in cut)
        busy = sum(sum(e - s for s, e in self.busy[d])
                   - overlap(self.busy[d], cut) for d in self.devices)
        n = max(len(self.devices), 1)
        return busy / n * 1e-9, (self.hi - self.lo - cut_len) * 1e-9

    def _time(self, table: dict, pattern: str) -> tuple:
        """(seconds averaged over devices, events on the first device)
        of the events whose name matches ``pattern``, inside the
        window."""
        rx = re.compile(pattern)
        total, count = 0.0, 0
        for d in self.devices:
            for e in table.get(d, ()):
                s, t = max(e.start, self.lo), min(e.end, self.hi)
                if t > s and rx.search(e.name):
                    total += t - s
                    if d == self.devices[0]:
                        count += 1
        return total / max(len(self.devices), 1) * 1e-9, count

    def module_time(self, pattern: str) -> tuple:
        return self._time(self.ev.modules, pattern)

    def op_time(self, pattern: str) -> tuple:
        return self._time(self.ev.ops, pattern)

    def op_names(self, pattern: str) -> set:
        """Names of the first device's ops inside the window that match
        ``pattern``."""
        rx = re.compile(pattern)
        return {e.name for e in self.ev.ops.get(self.devices[0], ())
                if e.end > self.lo and e.start < self.hi
                and rx.search(e.name)} if self.devices else set()

    def top_ops(self, n: int = 10) -> list:
        """[[op name, seconds per device]] of the n costliest ops, leaving
        out loops and calls, whose bodies' ops are events of their own."""
        acc = defaultdict(float)
        for d in self.devices:
            for e in self.ev.ops[d]:
                s, t = max(e.start, self.lo), min(e.end, self.hi)
                if t > s and not _CONTAINER.search(e.name):
                    acc[short(e.name)] += (t - s) * 1e-9 / len(self.devices)
        return [[k, v] for k, v in sorted(acc.items(),
                                          key=lambda kv: -kv[1])[:n]]

    def span_at(self, t: float) -> str:
        """Innermost (latest-starting) bench span open at ``t``."""
        best = None
        for s in self.ev.spans:
            if s.start <= t < s.end and (best is None
                                         or s.start > best.start):
                best = s
        return best.name if best else "host.other"

    def idle_gaps(self, n: int = 10) -> list:
        """[[span open at the gap's middle, seconds]] of the n longest
        idle gaps on the first device, longest first."""
        if not self.devices:
            return []
        busy = self.busy[self.devices[0]]
        edges = [self.lo] + [x for iv in busy for x in iv] + [self.hi]
        gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                if edges[i + 1] > edges[i]]
        gaps.sort(key=lambda g: g[0] - g[1])
        return [[self.span_at((s + e) / 2), (e - s) * 1e-9]
                for s, e in gaps[:n]]
