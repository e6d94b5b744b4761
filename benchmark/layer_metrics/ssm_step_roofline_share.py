"""The selective-scan step kernel against its roofline: the larger of
the least time to READ AND WRITE the live rows' recurrent state (``2 * N
* E`` float32 values a row a state-space layer, the step's vectors and
``A`` once a layer) and the least time for the rule's elementwise
operations at the chip's published peak, over the kernel's device time
per step. Memory binds: 0.66 MB against 0.57 M operations a row a layer.
The operations are the VPU's and the EUP's (an exponential a state), so
the published peak — the MXU's — flatters that side, and it never
binds."""
from benchmark import latent_moe_costs
from benchmark import ssm_hybrid_costs as costs

NAME, UNIT, LAYER = "ssm_step_roofline_share", "%", "Kernels"


def compute(ctx):
    s = latent_moe_costs.kernel_s_per_step(ctx, "ssm_step_kernel")
    steps = costs.dispatched(ctx)
    if s is None or ctx.peak is None or not steps:
        return None
    rows = sum(r for r, _ in steps) / len(steps)
    sizes = costs.sizes(ctx)
    least = max(
        costs.ssm_step_bytes(sizes, rows,
                             ctx.config["bytes_per_value"]["state"])
        / ctx.peak["hbm_bytes_per_s"],
        costs.ssm_step_ops(sizes, rows) / ctx.peak["flops_per_s"])
    return 100.0 * least / s
