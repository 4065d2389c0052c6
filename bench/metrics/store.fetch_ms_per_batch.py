"""Milliseconds per untraced window batch in the store's miss path: the
host gather of the missed rows, admission and the scatter's dispatch
(``TierStats.fetch_s``)."""


def read(ctx):
    w = ctx.window
    return w.delta["fetch_s"] * 1e3 / w.batches if w.batches else None
