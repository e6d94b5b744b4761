"""The two-query paged latent kernel (``trace_names.latent_kernel`` in
its ``q2`` form: both positions of a row against each live page in one
product; one call a block, the module's among them) against its
roofline: the larger of the least time to READ the latent of the keys
live in front of the step's rows (576 values a key a layer) and the
least time to COMPUTE both positions' every head's score and weighted
sum over them (``benchmark/spec_latent_costs.verify_least_s``), over the
kernel's device time per step. The keys are the host's own count on
``mx:decode.dispatch`` (a lower bound), so the share cannot pass 100%.
At 32 heads memory binds, 1.41 against 0.71 ns a key a layer."""
from benchmark import latent_moe_costs, spec_latent_costs as costs

NAME, UNIT, LAYER = "mla_verify_roofline_share", "%", "Kernels"


def compute(ctx):
    model = ctx.raw.get("model") or {}
    if "verify_positions" not in model or ctx.peak is None:
        return None
    s = latent_moe_costs.kernel_s_per_step(ctx, "latent_kernel")
    keys = costs.keys_live_per_step(ctx)
    if s is None or keys is None:
        return None
    least = costs.verify_least_s(model, keys, ctx.peak,
                                 ctx.config["bytes_per_value"]["kv"])
    return 100.0 * least / s
