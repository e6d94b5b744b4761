"""The PLAIN decode step of a model that a mesh shares against ONE
chip's roofline: the larger of the least time ONE chip needs to read its
part — its quarter of attention, the router, its experts that a token
chose (chip 0's own count), its key/value head's live keys and values,
its rings' visible ones, its columns of the head
(``benchmark/mellum_costs.step_bytes``) — and the least time to run its
operations (``step_flops``), at ONE chip's peaks, over the plain step
program's median time on device 0. One chip's work against one chip's
time: the exchange and the waiting for a straggler are what keeps it
from 100%. Memory-bound at 64 rows."""
import statistics

from benchmark import latent_moe_costs, mellum_costs as costs
from benchmark import window_moe_costs as window

NAME, UNIT, LAYER = "sharded_step_roofline_share", "%", "Model step"


def compute(ctx):
    steps = costs.plain_steps(ctx)
    if not steps or ctx.peak is None or not costs.sharded(ctx):
        return None
    touched = latent_moe_costs.touched_per_step(ctx)
    slots = costs.slots_per_step(ctx)
    live = latent_moe_costs.live_tokens_per_step(ctx)
    ring_bytes = window.per_step(ctx, "ring_bytes")
    if None in (touched, slots, live, ring_bytes):
        return None
    per, model = ctx.config["bytes_per_value"], ctx.raw["model"]
    least = max(
        costs.step_bytes(model, touched, live, ring_bytes, per["weights"],
                         per["kv"]) / ctx.peak["hbm_bytes_per_s"],
        costs.step_flops(model, model["window"], slots, live, ring_bytes,
                         per["ring"]) / ctx.peak["flops_per_s"])
    return 100.0 * least / (statistics.median(e - s for s, e in steps)
                            / 1e9)
