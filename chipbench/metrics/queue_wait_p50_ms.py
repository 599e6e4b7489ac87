"""Median of admission minus due time (``Request.t_admit``): the
scheduler's queueing.

Read in the traced run, over the requests due before the profiler
started: stopping the profiler writes its trace and holds the serving
loop for seconds, so requests due after that read the profiler's stall
and not the scheduler's queue."""

import numpy as np


def read(ctx):
    start = ctx.get("trace_started")
    wait = [r.request.t_admit - r.due for r in ctx.get("records", ())
            if start is not None and r.due < start
            and r.request.t_admit is not None]
    return float(np.median(wait)) * 1e3 if wait else None
