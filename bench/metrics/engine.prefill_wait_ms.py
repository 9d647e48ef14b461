"""How long a prefill queues behind device work already dispatched: mean
over the ``engine.prefill`` host spans in the traced slice of the time from
the span's start to the start of the prefill execution
(``jit__prefill_jit``) it launched, on the host's clock (``bench/spans.py``),
in ms."""

from bench import spans


def read(ctx):
    if not ctx.devices:
        return None
    w = spans.waits(ctx.trace, ctx.names["prefill_module"], ctx.devices[0])
    return 1e3 * sum(w) / len(w) if w else None
