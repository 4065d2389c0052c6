"""Device milliseconds per profiled batch writing admitted rows into the
fast tier: the executions of the store's write programs ``jit_store_write``
and its quantized variants (``core/tiered.py`` ``store_write*``), summed
over the profiled window, over its batches.  Nothing where the program
runs no program by those names."""

PREFIX = "jit_store_write"


def read(ctx):
    if ctx.profile is None or not ctx.profiled.batches:
        return None
    t, n = ctx.program_seconds(lambda op: op.module.startswith(PREFIX))
    return t * 1e3 / ctx.profiled.batches if n else None
