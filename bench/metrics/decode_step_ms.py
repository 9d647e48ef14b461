"""Device time of one execution of the engine's decode-step program
(``_decode_jit``) in the traced slice, mean over executions, in ms."""


def read(ctx):
    if not ctx.devices:
        return None
    runs = ctx.trace.modules_matching(ctx.names["decode_module"],
                                      ctx.devices[0])
    if not runs:
        return None
    return 1e3 * sum(e - s for s, e in runs) / len(runs)
