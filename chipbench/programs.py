"""Names by which the trace reduction finds the program's device work.

Each is a regular expression searched in an event name of the trace:
``XLA Modules`` events for whole programs, ``XLA Ops`` events (named by
their HLO text) for kernels and ops.
"""

ROUND = r"round_core"        # the fused round (build_round_core)
# The admission prefill is the top-level jit of the function the
# program names ``fn`` (``_build_slot_prefill``): any other top-level
# jit of a function so named would be counted with it.
PREFILL = r"^jit_fn\b"
RACE = r"^%gls_row_race\b"   # the gls_row_race Pallas kernel call
SORT = r"\bsort\("           # XLA's sorts (lax.top_k lowers to them)
