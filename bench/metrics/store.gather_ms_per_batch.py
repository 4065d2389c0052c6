"""Milliseconds per untraced window batch from the gather's dispatch to
the device sync that ends the lookup (``TierStats.gather_s``)."""


def read(ctx):
    w = ctx.window
    return w.delta["gather_s"] * 1e3 / w.batches if w.batches else None
