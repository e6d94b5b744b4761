"""The decode step against the memory roofline: the least time the chip
needs to read every matrix weight once and the K and V of the tokens
that are LIVE in the batch (bytes from shapes, ``benchmark/flops.py``;
mean live tokens per step from the streams), over the step's median
device time. The step is bound by memory bandwidth, not by operations:
at 8 rows a weight is used 8 times per read."""
import statistics

from benchmark import flops
from benchmark.layer_metrics import decode_step_device_ms

NAME, UNIT, LAYER = "decode_step_roofline_share", "%", "Kernels"


def compute(ctx):
    d = decode_step_device_ms.durations_s(ctx)
    if not d or ctx.peak is None:
        return None
    a, b = ctx.raw["stats0"], ctx.raw["stats1"]
    steps = b["decode_steps"] - a["decode_steps"]
    w = ctx.raw["window_s"]
    # a stream's i-th token (i >= 1) came from a decode step that
    # attended to its prompt and the i tokens before it
    live = sum(s["prompt_len"] + i for s in ctx.raw["streams"]
               for i, t in enumerate(s["times"]) if i and 0.0 <= t < w)
    if not steps:
        return None
    m = ctx.raw["model"]
    sizes = ctx.config["bytes_per_value"]
    least = flops.decode_step_bytes(
        m["n_layers"], m["d_model"], m["d_ff"], m["vocab"], live / steps,
        sizes["weights"], sizes["kv"]) / ctx.peak["hbm_bytes_per_s"]
    return 100.0 * least / statistics.median(d)
