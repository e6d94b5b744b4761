"""Device time inside the full-attention layers' paged kernel at one
query position a row (``trace_names.global_kernel``: the multi-query
block kernel over a packed pool; one call a full-attention layer) per
decode step of the traced slice."""
from benchmark import latent_moe_costs as costs

NAME, UNIT, LAYER = "global_attn_ms_per_step", "ms", "Kernels"


def compute(ctx):
    s = costs.kernel_s_per_step(ctx, "global_kernel")
    return None if s is None else 1e3 * s
