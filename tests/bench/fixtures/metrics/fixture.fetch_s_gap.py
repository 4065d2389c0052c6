"""The untraced window's miss-path seconds as the served batches' step
sums give them, less the same from the store's counters: nought to
rounding where both cover the same batches."""


def read(ctx):
    return ctx.window.steps["fetch_s"] - ctx.window.delta["fetch_s"]
