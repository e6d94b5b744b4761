"""Device time inside the paged latent decode-attention kernel
(``trace_names.latent_kernel``; one call a layer) per decode step of the
traced slice."""
from benchmark import latent_moe_costs as costs

NAME, UNIT, LAYER = "mla_decode_ms_per_step", "ms", "Kernels"


def compute(ctx):
    s = costs.kernel_s_per_step(ctx, "latent_kernel")
    return None if s is None else 1e3 * s
