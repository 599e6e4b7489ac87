"""Share of the traced window in which no operation ran on the device,
averaged over the chips, leaving out time the generator waited with no
request in the server (``bench.wait``)."""


def read(ctx):
    busy, span = ctx["trace"].busy_outside("bench.wait")
    return 100.0 * (1.0 - busy / span) if span > 0 else None
