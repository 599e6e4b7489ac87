"""Median of first token minus admission (``Request.t_first -
t_admit``): the round in flight, the overlapped prefill and the
request's first round.  With ``queue_wait_p50_ms`` it splits the time
to the first token.  Read over the requests due before the profiler
started, as ``queue_wait_p50_ms`` is."""

import numpy as np


def read(ctx):
    start = ctx.get("trace_started")
    took = [r.request.t_first - r.request.t_admit
            for r in ctx.get("records", ())
            if start is not None and r.due < start
            and r.request.t_admit is not None
            and r.request.t_first is not None]
    return float(np.median(took)) * 1e3 if took else None
