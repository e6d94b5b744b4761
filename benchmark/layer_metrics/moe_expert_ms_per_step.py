"""Device time inside the experts' grouped matmul (the Pallas kernel the
configuration names as ``trace_names.expert_kernel``; both calls of
every expert layer) per decode step of the traced slice."""
from benchmark import latent_moe_costs as costs

NAME, UNIT, LAYER = "moe_expert_ms_per_step", "ms", "Expert layer"


def compute(ctx):
    s = costs.kernel_s_per_step(ctx, "expert_kernel")
    return None if s is None else 1e3 * s
