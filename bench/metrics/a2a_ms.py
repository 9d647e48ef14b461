"""Device time of the all-to-all per sort on each chip, the median over
the chips, in ms."""

import statistics


def read(ctx):
    per = []
    for dev in ctx.devices:
        sorts = len(ctx.trace.modules_matching(ctx.names["sort_module"],
                                               dev))
        total, _ = ctx.trace.exposed_s(ctx.names["all_to_all"], dev)
        if sorts and total:
            per.append(1e3 * total / sorts)
    return statistics.median(per) if per else None
