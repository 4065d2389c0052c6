"""The operations a query costs, as the cost module the harness hands the
readers (``ctx.costs``) counts them."""


def read(ctx):
    return ctx.costs.flops_per_query(ctx.config)
