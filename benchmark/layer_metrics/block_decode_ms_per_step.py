"""Device time inside the paged block-decode attention kernel
(``trace_names.block_kernel``; one call a layer) per block step of the
traced slice."""
from benchmark import latent_moe_costs as costs

NAME, UNIT, LAYER = "block_decode_ms_per_step", "ms", "Kernels"


def compute(ctx):
    s = costs.kernel_s_per_step(ctx, "block_kernel")
    return None if s is None else 1e3 * s
