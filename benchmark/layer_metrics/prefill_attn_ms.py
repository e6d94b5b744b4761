"""Device time of the attention inside one prefill (queries and keys 192
wide, values 128: the flash kernel the configuration names as
``trace_names.prefill_attn_kernel``, every layer's call) per prefill
program of the traced slice."""
from benchmark import latent_moe_costs as costs

NAME, UNIT, LAYER = "prefill_attn_ms", "ms", "Kernels"


def compute(ctx):
    s = costs.kernel_s_per_step(ctx, "prefill_attn_kernel",
                                "prefill_module")
    return None if s is None else 1e3 * s
