"""Megabytes (10^6 bytes) per untraced window batch of the host arrays the
batch sends to the device: the store's lookups and writes (the ids, the
overflow staging array, the missed rows) and the forward's dense input
(``ServedBatch.steps["h2d_bytes"]``)."""


def read(ctx):
    w = ctx.window
    if not w.batches or "h2d_bytes" not in w.steps:
        return None
    return w.steps["h2d_bytes"] / 1e6 / w.batches
