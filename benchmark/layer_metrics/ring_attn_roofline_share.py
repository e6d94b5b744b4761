"""The ring-decode kernel against its roofline: the larger of the least
time to READ the keys and values the live rows' positions have reached
in their rings (``min(p + 1, W)`` a row a sliding layer; the step's own
count, ``ring_bytes`` on ``mx:decode.readback``) and the least time to
COMPUTE every query head's score and weighted sum over them, over the
kernel's device time per decode step. With 9 query heads a key head a
cached byte is used 9 times: memory binds. The kernel fetches all ``W``
slots of a row, valid or not, so a queue of short rows reads low."""
from benchmark import latent_moe_costs, window_moe_costs as costs

NAME, UNIT, LAYER = "ring_attn_roofline_share", "%", "Kernels"


def compute(ctx):
    s = latent_moe_costs.kernel_s_per_step(ctx, "ring_kernel")
    if s is None or ctx.peak is None or not costs.sizes_known(ctx):
        return None
    ring_bytes = costs.per_step(ctx, "ring_bytes")
    if ring_bytes is None:
        return None
    least = max(
        ring_bytes / ctx.peak["hbm_bytes_per_s"],
        costs.ring_attn_flops(ctx.raw["model"], ring_bytes,
                              ctx.config["bytes_per_value"]["ring"])
        / ctx.peak["flops_per_s"])
    return 100.0 * least / s
