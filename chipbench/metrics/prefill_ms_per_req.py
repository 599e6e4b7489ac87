"""Device time of the admission prefill programs in the traced window
per request admitted in it."""

from chipbench.programs import PREFILL


def read(ctx):
    tr = ctx["trace"]
    n = ctx["admitted_in_trace"]
    secs, count = tr.module_time(PREFILL)
    if not count or not n:
        return None
    return secs * 1e3 / n
