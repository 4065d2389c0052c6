"""The Pallas row gather's share of its roofline in the profiled window:
the least time its bytes need (each unique row read and written once, at
the chip's memory bandwidth) over the device time of the kernel.

The kernel is the ``tpu_custom_call`` op inside the store's gather
programs, ``jit_g`` and ``jit_gov`` (``core/tiered.py`` ``_kernel_gathers``:
the plain gather and the one that folds in overflow rows)."""

PROGRAMS = ("jit_g", "jit_gov")


def is_kernel(op) -> bool:
    return op.module in PROGRAMS and "tpu_custom_call" in op.name


def read(ctx):
    if ctx.profile is None or ctx.peak is None:
        return None
    t = ctx.profile_seconds(is_kernel)
    if not t:
        return None
    need = ctx.costs.gather_bytes(ctx.profiled.unique_rows, ctx.config)
    return 100.0 * need / ctx.peak["hbm_bytes_per_s"] / t
