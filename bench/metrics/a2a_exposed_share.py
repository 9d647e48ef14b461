"""Share of the all-to-all's device time during which no other operation
ran on that chip, the median over the chips, in %."""

import statistics


def read(ctx):
    per = []
    for dev in ctx.devices:
        total, exposed = ctx.trace.exposed_s(ctx.names["all_to_all"], dev)
        if total:
            per.append(100.0 * exposed / total)
    return statistics.median(per) if per else None
