"""Rows per untraced window batch served through the store's overflow
select: missed rows that the lookup folds into the gather from a staging
array, past the fast tier (``ServedBatch.steps["overflow_rows"]``)."""


def read(ctx):
    w = ctx.window
    if not w.batches or "overflow_rows" not in w.steps:
        return None
    return w.steps["overflow_rows"] / w.batches
