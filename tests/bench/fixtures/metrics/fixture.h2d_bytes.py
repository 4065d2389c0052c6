"""Bytes sent to the device in the untraced window, from the sums of the
served batches' steps (``ctx.window.steps``)."""


def read(ctx):
    return ctx.window.steps.get("h2d_bytes")
