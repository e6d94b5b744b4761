"""The speculative step against the memory roofline: the least time the
chip needs to read every matrix the step multiplies by once — with the
experts its positions TOUCHED, not all that are held, and the head once
— and the latent of the keys live in front of its rows
(``benchmark/spec_latent_costs.step_bytes``), over the step program's
median device time. Memory-bound at 64 rows of 2 positions."""
import statistics

from benchmark import spec_latent_costs as costs
from benchmark.latent_moe_costs import touched_per_step
from benchmark.layer_metrics import decode_step_device_ms

NAME, UNIT, LAYER = "spec_step_roofline_share", "%", "Model step"


def compute(ctx):
    d = decode_step_device_ms.durations_s(ctx)
    if not d or ctx.peak is None or "spec_delta" not in ctx.raw:
        return None
    touched = touched_per_step(ctx)
    keys = costs.keys_live_per_step(ctx)
    if touched is None or keys is None:
        return None
    sizes = ctx.config["bytes_per_value"]
    least = costs.step_bytes(ctx.raw["model"], touched, keys,
                             sizes["weights"], sizes["kv"]) \
        / ctx.peak["hbm_bytes_per_s"]
    return 100.0 * least / statistics.median(d)
