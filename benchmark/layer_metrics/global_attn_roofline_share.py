"""The full-attention layers' paged kernel against the memory roofline,
its bytes counted from the slice it times: the pages that hold the live
rows' keys in the TRACED steps (the step's own count,
``global_pages_live`` on ``mx:decode.readback``: ``ceil((p + 1) / page
size)`` a live row), each read whole in every full-attention layer — K
and V of 8 key/value heads of 128 — at the chip's bandwidth, or the
operations of 6 query heads a key head over them if those take longer,
over the kernel's device time per decode step. A window-wide count over
a slice's kernel time can pass 100% (``flash_decode_roofline_share``'s
lesson); the slice's own cannot."""
from benchmark import latent_moe_costs, window_moe_costs as costs

NAME, UNIT, LAYER = "global_attn_roofline_share", "%", "Kernels"


def compute(ctx):
    s = latent_moe_costs.kernel_s_per_step(ctx, "global_kernel")
    if s is None or ctx.peak is None or not costs.sizes_known(ctx):
        return None
    pages = costs.per_step(ctx, "global_pages_live")
    if pages is None:
        return None
    tokens = pages * ctx.config["server"]["kwargs"]["page_size"]
    model = ctx.raw["model"]
    least = max(
        costs.global_attn_bytes(model, tokens,
                                ctx.config["bytes_per_value"]["kv"])
        / ctx.peak["hbm_bytes_per_s"],
        costs.global_attn_flops(model, tokens) / ctx.peak["flops_per_s"])
    return 100.0 * least / s
