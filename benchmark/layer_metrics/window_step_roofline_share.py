"""The decode step of a window model against the memory roofline: the
least time the chip needs to read every matrix the step multiplies by
once — with the experts its tokens TOUCHED, not all that are held — the
full-attention layers' keys and values of the live tokens and the rings'
visible keys and values (``benchmark/window_moe_costs.step_bytes``), over
the step program's median device time. Memory-bound at 64 rows."""
import statistics

from benchmark import latent_moe_costs, window_moe_costs as costs
from benchmark.layer_metrics import decode_step_device_ms

NAME, UNIT, LAYER = "window_step_roofline_share", "%", "Model step"


def compute(ctx):
    d = decode_step_device_ms.durations_s(ctx)
    if not d or ctx.peak is None or not costs.sizes_known(ctx):
        return None
    touched = latent_moe_costs.touched_per_step(ctx)
    live = latent_moe_costs.live_tokens_per_step(ctx)
    ring_bytes = costs.per_step(ctx, "ring_bytes")
    if touched is None or live is None or ring_bytes is None:
        return None
    per = ctx.config["bytes_per_value"]
    least = costs.step_bytes(ctx.raw["model"], touched, live, ring_bytes,
                             per["weights"], per["kv"]) \
        / ctx.peak["hbm_bytes_per_s"]
    return 100.0 * least / statistics.median(d)
