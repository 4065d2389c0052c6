"""The dense forward's share of its roofline in the profiled window: the
least time one forward needs (the larger of its bytes over the memory
bandwidth and its operations over the peak rate), times the forwards run,
over the device time of those runs.

The forward's program is the one whose ops read the first top-MLP weight,
found by that weight's shape ``[top inputs, top_mlp[0]]`` in the ops' HLO
text (``bf16[366924,1024]`` for dlrm-recmg): the jitted ``_dense_forward``
of ``launch/serve.py``, which runs as ``jit__lambda``."""


def read(ctx):
    if ctx.profile is None or ctx.peak is None:
        return None
    shape = f"[{ctx.costs.top_inputs(ctx.config)},{ctx.config['top_mlp'][0]}]"
    t, n = ctx.program_seconds(lambda op: shape in op.name)
    if not t:
        return None
    bound = ctx.costs.forward_seconds_bound(
        ctx.config, ctx.profiled.batch_queries, ctx.peak)
    return 100.0 * bound * n / t
