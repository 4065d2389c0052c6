"""The cross network's share of its roofline in the profiled window: the
least time the cross layers of one batch need (``ctx.costs``
``cross_seconds_bound``: the larger of their bytes over the memory
bandwidth and their two matmuls' operations over the peak rate), times
the profiled batches, over the device time of the cross network's ops.

Those are the ops of the forward ``jit_dense_forward`` whose HLO text
holds a cross weight's shape, ``[X,r]`` (``V``) or ``[r,X]`` (``W``), with
``X`` the cross width and ``r`` the rank (``[3456,512]`` and
``[512,3456]`` for dlrm-dcnv2), and not the first top-MLP weight's
``[X,top_mlp[0]]``: at a batch of ``r`` queries an activation of the
cross network has ``W``'s shape, and the top MLP's first matmul reads the
last one.  Nothing where the cost module counts no cross network."""

PROGRAM = "jit_dense_forward"


def read(ctx):
    bound = getattr(ctx.costs, "cross_seconds_bound", None)
    if (ctx.profile is None or ctx.peak is None or bound is None
            or not ctx.profiled.batches):
        return None
    cfg = ctx.config
    x, r = ctx.costs.top_inputs(cfg), int(cfg["cross_rank"])
    shapes = (f"[{x},{r}]", f"[{r},{x}]")
    top = f"[{x},{cfg['top_mlp'][0]}]"

    def is_cross(op):
        return (op.module == PROGRAM and top not in op.name
                and any(s in op.name for s in shapes))

    t = ctx.profile_seconds(is_cross)
    if not t:
        return None
    need = bound(cfg, ctx.profiled.batch_queries, ctx.peak)
    return 100.0 * need * ctx.profiled.batches / t
