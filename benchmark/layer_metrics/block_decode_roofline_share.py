"""The paged block-decode kernel against its roofline: the larger of the
least time to READ the keys and values committed in front of the rows'
blocks (2 x kv heads x head size values a token a layer; the step's own
count of them, ``keys_live`` on ``mx:decode.dispatch``) and the least
time to COMPUTE every query position's and head's score and weighted sum
over them, over the kernel's device time per block step. At 4 positions
and 8 query heads a key/value head a cached byte is used 32 times:
memory binds (2.5 ns of bytes against 0.33 ns of operations a token a
layer on a v5e)."""
from benchmark import block_diffusion_costs as costs
from benchmark.latent_moe_costs import kernel_s_per_step

NAME, UNIT, LAYER = "block_decode_roofline_share", "%", "Kernels"


def compute(ctx):
    s = kernel_s_per_step(ctx, "block_kernel")
    live = costs.keys_live_per_step(ctx)
    if s is None or live is None or ctx.peak is None:
        return None
    model = ctx.raw["model"]
    tokens = live * model["n_layers"]
    least = max(
        tokens * costs.kv_token_bytes(
            model, ctx.config["bytes_per_value"]["kv"])
        / ctx.peak["hbm_bytes_per_s"],
        tokens * costs.kv_token_flops(model) / ctx.peak["flops_per_s"])
    return 100.0 * least / s
