"""The block step against the memory roofline: the least time the chip
needs to read every matrix the step multiplies by once — with the
experts its positions TOUCHED, not all that are held — and the keys and
values committed in front of its rows' blocks
(``benchmark/block_diffusion_costs.step_bytes``), over the step
program's median device time. Memory-bound at 32 rows of 4 positions."""
import statistics

from benchmark import block_diffusion_costs as costs
from benchmark.latent_moe_costs import touched_per_step
from benchmark.layer_metrics import decode_step_device_ms

NAME, UNIT, LAYER = "block_step_roofline_share", "%", "Model step"


def compute(ctx):
    d = decode_step_device_ms.durations_s(ctx)
    if not d or ctx.peak is None or "block_delta" not in ctx.raw:
        return None
    touched = touched_per_step(ctx)
    live = costs.keys_live_per_step(ctx)
    if touched is None or live is None:
        return None
    sizes = ctx.config["bytes_per_value"]
    least = costs.step_bytes(ctx.raw["model"], touched, live,
                             sizes["weights"], sizes["kv"]) \
        / ctx.peak["hbm_bytes_per_s"]
    return 100.0 * least / statistics.median(d)
