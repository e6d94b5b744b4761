"""Device time inside the selective-scan step kernel
(``trace_names.ssm_step_kernel``; one call a state-space layer, over
every row of the window) per step program of the traced slice, plain
and mixed."""
from benchmark import latent_moe_costs as costs

NAME, UNIT, LAYER = "ssm_step_ms_per_step", "ms", "Kernels"


def compute(ctx):
    s = costs.kernel_s_per_step(ctx, "ssm_step_kernel")
    return None if s is None else 1e3 * s
