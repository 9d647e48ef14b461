"""The whole serving step's share of the chip's bf16 peak: the model
FLOPs of every token served inside the window (2 x parameters per token,
attention at each token's live length, one prefill pass per prompt at
its true length, counted with its first token; padding counts as waste)
over the window's wall time and the peak, in %."""

from bench import work


def read(ctx):
    lay = ctx.layer
    flops = sum(work.request_flops(lay["dims"], p, n)
                for p, n in lay["requests"] if n)
    return 100.0 * flops / lay["window_s"] / ctx.peaks["bf16_flops"]
