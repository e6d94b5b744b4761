"""Device time of the residual streams' mixing per step of the traced
slice, every sublayer of every block together: the coefficient path
under the program's ``mx_mhc`` scope (the norm over all ``n C`` values,
the float32 product at "highest", sigmoid, the Sinkhorn iterations) and
the two stream mixes (``u = H_pre X``, ``X' = H_res X + H_post^T y``) —
the operations the configuration lists as ``trace_names.mhc_ops``, found
by the shapes they make (a profile's events carry no ``op_name``). What
a fused kernel for the mix would have to beat."""
from benchmark import latent_moe_costs as costs

NAME, UNIT, LAYER = "mhc_ms_per_step", "ms", "Model step"


def compute(ctx):
    s = costs.patterns_s_per_step(ctx, "mhc_ops")
    return None if s is None else 1e3 * s
