"""The decode step of a hybrid model against the memory roofline: the
least time the chip needs to read every matrix the step multiplies by
once — with the experts its tokens TOUCHED, not all that are held — to
read and write the live rows' recurrent state and to read the latent of
the live tokens (``benchmark/hybrid_linear_costs.step_bytes``), over the
step program's median device time. Memory-bound at 64 rows."""
import statistics

from benchmark import hybrid_linear_costs as costs
from benchmark import latent_moe_costs
from benchmark.layer_metrics import decode_step_device_ms

NAME, UNIT, LAYER = "hybrid_step_roofline_share", "%", "Model step"


def compute(ctx):
    d = decode_step_device_ms.durations_s(ctx)
    if not d or ctx.peak is None or "moe_delta" not in ctx.raw \
            or "layer_group_size" not in ctx.config["model"]["kwargs"]:
        return None
    touched = latent_moe_costs.touched_per_step(ctx)
    live = latent_moe_costs.live_tokens_per_step(ctx)
    rows = costs.rows_live_per_step(ctx)
    if touched is None or live is None or rows is None:
        return None
    least = costs.step_bytes(ctx, touched, live, rows) \
        / ctx.peak["hbm_bytes_per_s"]
    return 100.0 * least / statistics.median(d)
