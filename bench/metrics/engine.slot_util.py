"""Mean share of the engine's slots that held a live request, over the
decode steps booked inside the window (EngineStats.slot_util), in %."""


def read(ctx):
    util = ctx.layer["slot_util"]
    return 100.0 * sum(util) / len(util) if util else None
