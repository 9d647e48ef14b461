"""Device time a sort of SIHSort's XLA operations: every operation inside
a ``jit_sihsort`` execution that is neither a Pallas kernel nor the
exchange (the gathers that build the send buffer and merge what comes
back, reversals, buffer copies), the median over the chips, in ms. Only
sorts wholly inside the traced slice count."""

import statistics


def read(ctx):
    other = (f"(?s)^(?!.*(?:{ctx.names['sort_kernels']}"
             f"|{ctx.names['all_to_all']}))")
    per = []
    for dev in ctx.devices:
        sorts = ctx.trace.whole_modules(ctx.names["sihsort_module"], dev)
        total = ctx.trace.op_time_within(other, dev, sorts)
        if sorts and total:
            per.append(1e3 * total / len(sorts))
    return statistics.median(per) if per else None
