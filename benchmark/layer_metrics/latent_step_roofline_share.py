"""The decode step against the memory roofline: the least time the chip
needs to read every matrix the step multiplies by once — with the
experts its tokens TOUCHED, not all that are held — and the latent of
the live tokens (``benchmark/latent_moe_costs.step_bytes``), over the
step program's median device time. Memory-bound at 64 rows."""
import statistics

from benchmark import latent_moe_costs as costs
from benchmark.layer_metrics import decode_step_device_ms

NAME, UNIT, LAYER = "latent_step_roofline_share", "%", "Model step"


def compute(ctx):
    d = decode_step_device_ms.durations_s(ctx)
    if not d or ctx.peak is None or "moe_delta" not in ctx.raw:
        return None
    touched = costs.touched_per_step(ctx)
    live = costs.live_tokens_per_step(ctx)
    if touched is None or live is None:
        return None
    sizes = ctx.config["bytes_per_value"]
    least = costs.step_bytes(ctx.raw["model"], touched, live,
                             sizes["weights"], sizes["kv"]) \
        / ctx.peak["hbm_bytes_per_s"]
    return 100.0 * least / statistics.median(d)
