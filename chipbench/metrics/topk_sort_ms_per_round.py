"""Device time of the sorts per fused round: XLA lowers the top-k of
``probs_from_logits`` over the 49152-entry vocabulary to a full sort,
once per drafter step and once for the target's verify logits."""

from chipbench.programs import ROUND, SORT


def read(ctx):
    tr = ctx["trace"]
    secs, n = tr.op_time(SORT)
    _, rounds = tr.module_time(ROUND)
    return secs * 1e3 / rounds if rounds and n else None
