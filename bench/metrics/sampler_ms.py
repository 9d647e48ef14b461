"""Device time per decode step of the sampler's Pallas kernels (the
bitonic network of ``topk`` and of ``nucleus_mask``, and the nucleus cut
kernel) in the traced slice, in ms. The batch-1 samples of the first
tokens after each prefill are counted in."""


def read(ctx):
    if not ctx.devices:
        return None
    dev = ctx.devices[0]
    steps = len(ctx.trace.modules_matching(ctx.names["decode_module"], dev))
    t = ctx.trace.op_time_in(ctx.names["sampler_kernels"], dev,
                             ctx.names["sampler_modules"])
    if not steps or not t:
        return None
    return 1e3 * t / steps
