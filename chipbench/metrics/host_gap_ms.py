"""Host time between rounds: the median, over consecutive rounds of the
measured window that advance a request in common, of round N+1's
dispatch (its ``engine.round`` span ends) minus the end of round N's
packed fetch (``engine.fetch``), both on the program's clock.  Pairs
while the profiler was on are left out, so the traced run reads the
gap of an untraced one.  What the host does there (output check,
commit, admission, key folds, input staging) is what overlapping round
N+1 with commit N would hide."""

import numpy as np

from chipbench import program_spans


def read(ctx):
    gaps = program_spans.host_gaps(ctx)
    if not gaps:
        return None
    return float(np.median([e - s for s, e in gaps])) * 1e-6
