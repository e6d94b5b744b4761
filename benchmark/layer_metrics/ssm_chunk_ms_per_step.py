"""Device time inside the selective scan of a prompt's chunk
(``trace_names.ssm_chunk_kernel``; one call a state-space layer) per
MIXED step program of the traced slice (``trace_names.chunk_module``)."""
from benchmark import latent_moe_costs as costs

NAME, UNIT, LAYER = "ssm_chunk_ms_per_step", "ms", "Kernels"


def compute(ctx):
    s = costs.kernel_s_per_step(ctx, "ssm_chunk_kernel", "chunk_module")
    return None if s is None else 1e3 * s
