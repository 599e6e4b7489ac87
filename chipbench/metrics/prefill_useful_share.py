"""Share of the admission prefill's work that is prompt: the prompt
tokens the ``engine.prefill`` dispatches write (once per request) over
the arena cells they compute (every arena row x the bucket width),
inside the measured window (its first to its last due request)."""

from chipbench import program_spans


def read(ctx):
    spans = program_spans.measured(ctx, "engine.prefill")
    cells = sum(a["rows"] * a["bucket"] for a in spans or ())
    if not cells:
        return None
    return 100.0 * sum(a["tokens"] for a in spans) / cells
