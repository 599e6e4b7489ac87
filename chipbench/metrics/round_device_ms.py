"""Device time of one fused round (``build_round_core``'s program),
averaged over the rounds in the traced window."""

from chipbench.programs import ROUND


def read(ctx):
    secs, count = ctx["trace"].module_time(ROUND)
    return secs * 1e3 / count if count else None
