"""Device time inside the ring-decode attention kernel
(``trace_names.ring_kernel``; one call a sliding layer) per decode step
of the traced slice."""
from benchmark import latent_moe_costs as costs

NAME, UNIT, LAYER = "ring_attn_ms_per_step", "ms", "Kernels"


def compute(ctx):
    s = costs.kernel_s_per_step(ctx, "ring_kernel")
    return None if s is None else 1e3 * s
