"""Share of the traced slice in which no operation ran on the device while
the host was inside an ``engine.admit`` span (prefill dispatch, the
first-token sample and its host fetch, the slot's bookkeeping), device
times on the host's clock (``bench/spans.py``), in %."""

from bench import spans


def read(ctx):
    return spans.idle_share(ctx, spans.ADMIT)
