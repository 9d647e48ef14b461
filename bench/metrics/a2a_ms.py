"""Device time of the all-to-all per sort on each chip, the median over
the chips, in ms. Only sorts wholly inside the traced slice count: a sort
cut by the slice's start or end would count as one with only part of its
exchange."""

import statistics


def read(ctx):
    per = []
    for dev in ctx.devices:
        sorts = ctx.trace.whole_modules(ctx.names["sort_module"], dev)
        total = ctx.trace.op_time_within(ctx.names["all_to_all"], dev,
                                         sorts)
        if sorts and total:
            per.append(1e3 * total / len(sorts))
    return statistics.median(per) if per else None
