"""The decode-attention kernel against the memory roofline: the least
time the chip needs to read the K and V of the tokens that are LIVE in
the batch (``benchmark/kernel_costs.py``; mean live tokens a step from
the streams, as ``decode_step_roofline_share`` counts them), over the
device time inside ``mx_flash_decode`` calls per decode step of the
slice. The kernel is bound by memory bandwidth: one query a row."""
from benchmark import kernel_costs

NAME, UNIT, LAYER = "flash_decode_roofline_share", "%", "Kernels"


def compute(ctx):
    names = ctx.config.get("trace_names", {})
    if ctx.trace is None or not ctx.trace.devices or ctx.peak is None \
            or "step_module" not in names:
        return None
    kernel_s = ctx.trace.op_s(kernel_costs.pattern("flash_decode"))
    traced = len(ctx.trace.module_durations_s(names["step_module"]))
    a, b = ctx.raw["stats0"], ctx.raw["stats1"]
    steps = b["decode_steps"] - a["decode_steps"]
    if not kernel_s or not traced or not steps:
        return None
    w = ctx.raw["window_s"]
    # a stream's i-th token (i >= 1) came from a decode step that
    # attended to its prompt and the i tokens before it
    live = sum(s["prompt_len"] + i for s in ctx.raw["streams"]
               for i, t in enumerate(s["times"]) if i and 0.0 <= t < w)
    m = ctx.raw["model"]
    least = kernel_costs.flash_decode_bytes(
        m["n_layers"], m["d_model"], live / steps,
        ctx.config["bytes_per_value"]["kv"]) / ctx.peak["hbm_bytes_per_s"]
    return 100.0 * least / (kernel_s / traced)
