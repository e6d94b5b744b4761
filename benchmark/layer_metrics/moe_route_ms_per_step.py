"""Device time of routing per decode step of the traced slice, all
expert layers together: the router's float32 product at "highest" and
its sigmoid, the three top-k of the group-limited choice, and the
ordering of token slots by expert in front of the grouped matmul — the
operations the configuration lists as ``trace_names.route_ops`` (every
sort, and every result shaped rows x experts or rows x groups x ...)."""
from benchmark import latent_moe_costs as costs

NAME, UNIT, LAYER = "moe_route_ms_per_step", "ms", "Expert layer"


def compute(ctx):
    s = costs.patterns_s_per_step(ctx, "route_ops")
    return None if s is None else 1e3 * s
