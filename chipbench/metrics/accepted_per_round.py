"""Tokens a request gains per round (accepted drafts + 1), over the
whole window: ``ServerMetrics.total_tokens / total_blocks``."""


def read(ctx):
    c = ctx["counters"]
    return c["total_tokens"] / c["total_blocks"] if c["total_blocks"] else None
