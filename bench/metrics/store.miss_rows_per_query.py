"""Rows fetched on demand from the slow tier per query served in the
untraced window (``TierStats.on_demand_rows``)."""


def read(ctx):
    w = ctx.window
    return w.delta["on_demand_rows"] / w.queries if w.queries else None
