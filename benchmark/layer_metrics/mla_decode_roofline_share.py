"""The paged latent decode kernel against its roofline: the larger of
the least time to READ the latent of the live tokens (576 values a token
a layer) and the least time to COMPUTE every head's score and weighted
sum over them (2 x heads x (576 + 512) operations a token a layer), over
the kernel's device time per decode step. At 128 heads the two are
within 1% of each other (1.407 ns of bytes against 1.414 ns of
operations a token a layer on a v5e): compute binds, by a hair."""
from benchmark import latent_moe_costs as costs

NAME, UNIT, LAYER = "mla_decode_roofline_share", "%", "Kernels"


def compute(ctx):
    s = costs.kernel_s_per_step(ctx, "latent_kernel")
    if s is None or ctx.peak is None:
        return None
    live = costs.live_tokens_per_step(ctx)
    if live is None:
        return None
    model = ctx.raw["model"]
    tokens = live * model["n_layers"]
    least = max(
        tokens * costs.latent_token_bytes(
            model, ctx.config["bytes_per_value"]["kv"])
        / ctx.peak["hbm_bytes_per_s"],
        tokens * costs.latent_token_flops(model) / ctx.peak["flops_per_s"])
    return 100.0 * least / s
