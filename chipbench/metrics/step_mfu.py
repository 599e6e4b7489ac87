"""Useful FLOPs of the traced window over the chips' bf16 peak for the
window's length, leaving out time the generator waited with no request
in the server (``bench.wait``).  Useful FLOPs: each fused round's
(``chipbench.counts.round_flops`` at the window's mean per-round
contexts) and one prefill per admitted prompt of each model
(``counts.prefill_flops``)."""

from chipbench import counts
from chipbench.programs import ROUND


def read(ctx):
    tr = ctx["trace"]
    _, rounds = tr.module_time(ROUND)
    if not rounds:
        return None
    flops = rounds * ctx["round_flops"] + ctx["prefill_flops_in_trace"]
    _, span = tr.busy_outside("bench.wait")
    return 100.0 * flops / (span * ctx["peak"]["bf16_flops_per_s"]
                            * ctx["chips"])
