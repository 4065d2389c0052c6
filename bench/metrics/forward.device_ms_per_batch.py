"""Device milliseconds per profiled batch of the dense forward: the
executions of the pooling program ``jit_pool_bags`` and of the forward
``jit_dense_forward`` (``launch/serve.py`` ``pool_bags`` and
``dense_forward``), summed over the profiled window, over its batches.
Nothing where the program runs no program by those names."""

PROGRAMS = ("jit_pool_bags", "jit_dense_forward")


def read(ctx):
    if ctx.profile is None or not ctx.profiled.batches:
        return None
    t, n = ctx.program_seconds(lambda op: op.module in PROGRAMS)
    return t * 1e3 / ctx.profiled.batches if n else None
