"""Share of its roofline the ``gls_row_race`` kernel reaches: the least
time the chip could take for its bytes (it is memory-bound: about two
operations per 8 bytes read) over its measured device time, per
device, over the calls in the traced window.

The bytes are ``counts.round_race``'s: two (slots x (L+1), K, N) f32
inputs read from HBM.  The reader checks each traced call's operands
against that: where the kernel pads the rows (a row count that is not a
multiple of its row block) or the compiler places the inputs in the
core's fast memory (``S(1)`` in their layout), as at 2 slots, HBM bytes
do not bound the call, and the reader reads nothing."""

from chipbench import counts, reduce
from chipbench.programs import RACE


def read(ctx):
    tr = ctx["trace"]
    want = (ctx["slots"] * (ctx["L"] + 1), ctx["K"], ctx["vocab"])
    names = tr.op_names(RACE)
    for name in names:
        ops = reduce.operands(name)
        if [dims for _, dims, _ in ops] != [want, want] \
                or any(space for _, _, space in ops):
            return None
    secs, calls = tr.op_time(RACE)
    if not names or not calls:
        return None
    c = counts.round_race(ctx["slots"], ctx["K"], ctx["L"], ctx["vocab"])
    peak = ctx["peak"]
    least = max(c["bytes"] / peak["hbm_bytes_per_s"],
                c["flops"] / peak["bf16_flops_per_s"])
    return 100.0 * least * calls / secs
