"""The sort-family kernels' share of their roofline: the bytes a sort
must move (each chip's keys and payload read once and written once) at
the HBM peak, over the device time of the sort-family Pallas kernels per
sort, averaged over the chips, in %. Bound: bytes."""

from bench import work


def read(ctx):
    shares = []
    for dev in ctx.devices:
        sorts = len(ctx.trace.modules_matching(ctx.names["sort_module"],
                                               dev))
        t = ctx.trace.op_time_in(ctx.names["sort_kernels"], dev,
                                 ctx.names["sort_module"])
        if not sorts or not t:
            continue
        need = work.sort_bytes(ctx.layer["n_per_chip"])
        shares.append(need / ctx.peaks["hbm_bytes_per_s"] / (t / sorts))
    return 100.0 * sum(shares) / len(shares) if shares else None
