"""Operation and byte counts that only the fixture configuration
``tiny-dlrm.own_costs`` names: the harness's own, but with every query
counted at ``FLOPS_PER_QUERY``, so a reader shows which module it got."""
from bench.costs import (forward_bytes, forward_seconds_bound,  # noqa: F401
                         gather_bytes, mlp_dims, top_inputs)

FLOPS_PER_QUERY = 12345


def flops_per_query(cfg: dict) -> int:
    return FLOPS_PER_QUERY
