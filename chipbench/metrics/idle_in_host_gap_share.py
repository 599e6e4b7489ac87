"""Share of the first device's idle time in the traced window (outside
``bench.wait``) that falls inside the host gaps between rounds (see
``host_gap_ms``), placed on the trace's clock: the idle time that
overlapping round N+1 with the host's work on round N could take back.
It reads the few round pairs of the traced window, with the profiler's
cost in them."""

from chipbench import program_spans, reduce


def read(ctx):
    tr = ctx["trace"]
    gaps = program_spans.traced_gaps(ctx)
    idle = program_spans.idle(tr)
    total = sum(e - s for s, e in idle)
    if not gaps or total <= 0:
        return None
    return 100.0 * reduce.overlap(idle, reduce.merge(gaps, tr.lo, tr.hi)) \
        / total
