"""The pooling's share of its roofline in the profiled window: the least
bytes one ``jit_pool_bags`` execution moves (every served row read once,
every bag written once: ``ctx.costs.pool_bytes``) over the memory
bandwidth, times its executions, over their device time.  Nothing where
the configuration's cost module counts no pooling bytes, or where no
program by that name ran (``launch/serve.py`` ``pool_bags``)."""

PROGRAM = "jit_pool_bags"


def read(ctx):
    pool_bytes = getattr(ctx.costs, "pool_bytes", None)
    if ctx.profile is None or ctx.peak is None or pool_bytes is None:
        return None
    t, n = ctx.program_seconds(lambda op: op.module == PROGRAM)
    if not t:
        return None
    need = pool_bytes(ctx.config, ctx.profiled.batch_queries)
    return 100.0 * need * n / ctx.peak["hbm_bytes_per_s"] / t
