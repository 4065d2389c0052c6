"""Window batches, read by a metric that only the test fixture names."""


def read(ctx):
    return ctx.window.batches
