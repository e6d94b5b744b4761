"""The experts' grouped matmul against the memory roofline: the least
time the chip needs to read the three matrices of every expert that a
token of the step CHOSE (the program's own count in the traced steps,
``experts_touched`` on ``mx:decode.readback``, summed over the expert
layers; never all that are held), over the device time inside the kernel
per decode step. Memory-bound: an expert sees a few tokens a step, so a
weight is used a few times a read."""
from benchmark import latent_moe_costs as costs

NAME, UNIT, LAYER = "moe_expert_roofline_share", "%", "Expert layer"


def compute(ctx):
    s = costs.kernel_s_per_step(ctx, "expert_kernel")
    touched = costs.touched_per_step(ctx)
    if s is None or touched is None or ctx.peak is None:
        return None
    least = touched * costs.expert_bytes(
        ctx.raw["model"], ctx.config["bytes_per_value"]["weights"]) \
        / ctx.peak["hbm_bytes_per_s"]
    return 100.0 * least / s
