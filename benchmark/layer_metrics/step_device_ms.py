"""Device time of one optimizer step: seconds inside XLA programs on
device 0 during the traced steps, per step."""
NAME, UNIT, LAYER = "step_device_ms", "ms", "Fused step"


def compute(ctx):
    if ctx.trace is None or not ctx.trace.devices \
            or not ctx.raw.get("traced_steps"):
        return None
    return 1e3 * ctx.trace.module_s() / ctx.raw["traced_steps"]
