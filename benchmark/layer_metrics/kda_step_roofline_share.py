"""The delta-rule step kernel against its roofline: the larger of the
least time to READ AND WRITE the live rows' recurrent state (``2 * H * d
* d`` float32 values a row a linear layer, and the step's vectors) and
the least time to compute the rule's multiply-adds, over the kernel's
device time per decode step. Memory binds by three orders: 4.2 MB
against 3.7 MFLOP a row a layer."""
from benchmark import hybrid_linear_costs as costs
from benchmark import latent_moe_costs

NAME, UNIT, LAYER = "kda_step_roofline_share", "%", "Kernels"


def compute(ctx):
    s = latent_moe_costs.kernel_s_per_step(ctx, "kda_kernel")
    if s is None or ctx.peak is None:
        return None
    rows = costs.rows_live_per_step(ctx)
    if rows is None:
        return None
    sizes = costs.sizes(ctx)
    least = max(
        costs.kda_step_bytes(sizes, rows,
                             ctx.config["bytes_per_value"]["state"])
        / ctx.peak["hbm_bytes_per_s"],
        costs.kda_step_flops(sizes, rows) / ctx.peak["flops_per_s"])
    return 100.0 * least / s
