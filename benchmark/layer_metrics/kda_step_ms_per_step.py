"""Device time inside the delta-rule step kernel
(``trace_names.kda_kernel``; one call a linear-attention layer) per
decode step of the traced slice."""
from benchmark import latent_moe_costs as costs

NAME, UNIT, LAYER = "kda_step_ms_per_step", "ms", "Kernels"


def compute(ctx):
    s = costs.kernel_s_per_step(ctx, "kda_kernel")
    return None if s is None else 1e3 * s
