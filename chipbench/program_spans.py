"""The program's own spans and counters, read for the per-layer metrics.

The program records its spans (``repro.serving.spans``) in a ring that
the readers import from ``src``; a program without that module gives
every such metric nothing to read.  Records are ``(name, start_ns,
end_ns, parent, attrs, id)`` stamped with ``time.time_ns()``.

The measured window runs, on that clock, from the first due request to
the last (``window``): the warm-up before it and the grace's drain
after it are left out.

- ``round_pairs``: the host time between the packed fetch of round N
  (``engine.fetch`` ends) and the dispatch of round N+1
  (``engine.round`` ends), for consecutive rounds that advance a
  request in common, so that the server never stood idle between them.
- ``host_gaps``: those pairs inside the measured window, leaving out
  the time the profiler was on (its Python cost and its start and stop
  would be read as the program's).
- ``traced_gaps``: the pairs inside the traced window on the trace's
  clock, leaving out any that overlap a ``bench.wait``.  ``reduce``
  keeps the profiler's events in ns since the profile started (the
  xplane's ``profile_start_time``, which it drops).  Every
  ``serve.step`` runs inside one ``bench.step``, and the first step
  that starts after the profiler was asked to start
  (``ctx["trace_started"]``) is the trace's first ``bench.step``:
  lining the two sequences up from there gives the offset between the
  clocks, to within the microseconds between entering the two spans.
- ``measured``: the records of a name that start inside the measured
  window (counts, which the profiler's stall does not change).
"""

from __future__ import annotations

import bisect
import math
from typing import Optional

from chipbench import reduce

# Most a serve.step may start after, or end after, its bench.step on
# the trace's clock.
NEST_TOL_NS = 1_000_000


def records(ctx: dict) -> Optional[list]:
    """The run's span records (``ctx["spans"]`` where a test hands them
    in), or None where the program records none."""
    if "spans" in ctx:
        return ctx["spans"]
    try:
        from repro.serving.spans import recorder
    except ImportError:
        return None
    return recorder()


def window(ctx: dict) -> Optional[tuple]:
    """``(first, last)`` due time in ns of the window's requests, or
    None where there are none."""
    due = [r.due for r in ctx.get("records", ())]
    if not due:
        return None
    return min(due) * 1e9, max(due) * 1e9


def measured(ctx: dict, name: str) -> Optional[list]:
    """Attrs of the ``name`` records that start inside the measured
    window, or None."""
    recs, win = records(ctx), window(ctx)
    if recs is None or win is None:
        return None
    return [r.attrs for r in recs
            if r.name == name and win[0] <= r.start_ns < win[1]]


def round_pairs(recs: list) -> list:
    """``(end of round N's fetch, end of round N+1's dispatch)`` in ns
    for consecutive ``engine.round``s that advance a request in
    common."""
    rounds = sorted((r for r in recs if r.name == "engine.round"),
                    key=lambda r: r.start_ns)
    fetches = sorted((r for r in recs if r.name == "engine.fetch"),
                     key=lambda r: r.start_ns)
    starts = [f.start_ns for f in fetches]
    pairs = []
    for a, b in zip(rounds, rounds[1:]):
        if not set(a.attrs.get("uids", ())) & set(b.attrs.get("uids", ())):
            continue
        i = bisect.bisect_left(starts, a.end_ns)
        if i < len(fetches) and fetches[i].end_ns <= b.start_ns:
            pairs.append((fetches[i].end_ns, b.end_ns))
    return pairs


def profiled(ctx: dict, recs: list) -> Optional[tuple]:
    """``(start, end)`` in ns of the time the profiler was on: from when
    it was asked to start to the first ``serve.step`` after the traced
    ones (the profiler stops just before that step), or None where
    nothing was traced."""
    started, tr = ctx.get("trace_started"), ctx.get("trace")
    if started is None or tr is None:
        return None
    lo = started * 1e9
    steps = sorted(r.start_ns for r in recs
                   if r.name == "serve.step" and r.start_ns >= lo)
    n = sum(1 for s in tr.ev.spans if s.name == "bench.step")
    return lo, steps[n] if n < len(steps) else math.inf


def host_gaps(ctx: dict) -> Optional[list]:
    """``[start, end]`` in ns of each host gap between rounds inside the
    measured window and outside the profiler's time (module
    docstring), or None."""
    recs, win = records(ctx), window(ctx)
    if not recs or win is None:
        return None
    off = profiled(ctx, recs) or (math.inf, math.inf)
    return [[f, e] for f, e in round_pairs(recs)
            if win[0] <= f and e < win[1] and (e <= off[0] or f >= off[1])]


def clock_offset(ctx: dict) -> Optional[int]:
    """``time.time_ns()`` minus the trace's clock, or None where the
    program's steps cannot be lined up with the trace's."""
    recs, tr = records(ctx), ctx.get("trace")
    started = ctx.get("trace_started")
    if not recs or tr is None or started is None:
        return None
    bench = sorted((s for s in tr.ev.spans if s.name == "bench.step"),
                   key=lambda s: s.start)
    steps = sorted((r for r in recs if r.name == "serve.step"),
                   key=lambda r: r.start_ns)
    j = bisect.bisect_left([r.start_ns for r in steps], started * 1e9)
    pairs = list(zip(bench, steps[j:]))
    if not pairs or len(pairs) < len(bench):
        return None
    off = min(r.start_ns - int(b.start) for b, r in pairs)
    for b, r in pairs:
        if r.start_ns - off - b.start > NEST_TOL_NS \
                or r.end_ns - off - b.end > NEST_TOL_NS:
            return None
    return off


def traced_gaps(ctx: dict) -> Optional[list]:
    """``[start, end]`` on the trace's clock of each host gap between
    rounds inside the traced window (module docstring), or None."""
    off = clock_offset(ctx)
    if off is None:
        return None
    tr = ctx["trace"]
    waits = tr.span_total("bench.wait")
    gaps = []
    for f, e in round_pairs(records(ctx)):
        gap = [f - off, e - off]
        if gap[0] >= tr.lo and gap[1] <= tr.hi \
                and not reduce.overlap([gap], waits):
            gaps.append(gap)
    return gaps


def idle(tr) -> list:
    """Idle intervals of the first device inside the traced window,
    leaving out ``bench.wait``."""
    if not tr.devices:
        return []
    taken = reduce.merge(
        tr.busy[tr.devices[0]] + tr.span_total("bench.wait"), tr.lo, tr.hi)
    edges = [tr.lo] + [x for iv in taken for x in iv] + [tr.hi]
    return [[edges[i], edges[i + 1]] for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]
