"""Milliseconds per untraced window batch applying the RecMG models'
outputs: priorities and prefetches (``TierStats.model_s``).  Reported in
the cells that the metric's ``workloads`` list names: those served under
the RecMG policy."""


def read(ctx):
    w = ctx.window
    return w.delta["model_s"] * 1e3 / w.batches if w.batches else None
