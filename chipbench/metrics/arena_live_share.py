"""Share of the KV arenas' reserved bytes that live requests use, summed
over the fused rounds (``engine.round``) inside the measured window
(its first to its last due request): every round sweeps, copies and
selects the whole arena, so this is the share of that traffic that
carries live tokens."""

from chipbench import program_spans


def read(ctx):
    spans = program_spans.measured(ctx, "engine.round")
    reserved = sum(a["arena_bytes"] for a in spans or ())
    if not reserved:
        return None
    return 100.0 * sum(a["live_bytes"] for a in spans) / reserved
