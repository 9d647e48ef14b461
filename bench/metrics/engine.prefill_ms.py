"""Mean time of one admission's prefill in the window: the engine's own
host clock from admission to the first token on the host, after the
prefill has finished on the device (EngineStats timeline), in ms."""


def read(ctx):
    times = ctx.layer["prefill_s"]
    return 1e3 * sum(times) / len(times) if times else None
