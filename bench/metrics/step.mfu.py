"""The whole step's share of the chip's peak: the model operations of the
queries served in the profiled window, over its length, over the peak
bf16 rate."""


def read(ctx):
    prof, q = ctx.profile, ctx.profiled.queries
    if prof is None or not q or prof.window_s <= 0 or ctx.peak is None:
        return None
    rate = ctx.costs.flops_per_query(ctx.config) * q / prof.window_s
    return 100.0 * rate / ctx.peak["flops_bf16"]
