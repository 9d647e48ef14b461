"""The sampler kernels' share of their roofline: the bytes a sampler
call must move (each slots x padded-vocab logits row read once, in the
dtype the engine hands the sampler, and one int32 token per row written)
at the HBM peak, over the kernels' device time per decode step, in %.
Bound: bytes."""

import numpy as np

from bench import work


def read(ctx):
    if not ctx.devices or ctx.layer["logits_dtype"] is None:
        return None
    dev = ctx.devices[0]
    steps = len(ctx.trace.modules_matching(ctx.names["decode_module"], dev))
    t = ctx.trace.op_time_in(ctx.names["sampler_kernels"], dev,
                             ctx.names["sampler_modules"])
    if not steps or not t:
        return None
    need = work.sampler_bytes(ctx.layer["slots"],
                              ctx.layer["dims"]["padded_vocab"],
                              np.dtype(ctx.layer["logits_dtype"]).itemsize)
    return 100.0 * need / ctx.peaks["hbm_bytes_per_s"] / (t / steps)
