"""Share of the traced slice in which no operation ran on the device while
the host was inside an ``engine.retire`` span (the fetch of a step's tokens
and their bookkeeping), device times on the host's clock
(``bench/spans.py``), in %."""

from bench import spans


def read(ctx):
    return spans.idle_share(ctx, spans.RETIRE)
