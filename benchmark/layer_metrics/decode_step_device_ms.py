"""Median device duration of the decode-step program in the traced
slice (device 0). The program's name comes from the configuration."""
import statistics

NAME, UNIT, LAYER = "decode_step_device_ms", "ms", "Model step"


def durations_s(ctx):
    names = ctx.config.get("trace_names", {})
    if ctx.trace is None or not ctx.trace.devices \
            or "step_module" not in names:
        return []
    return ctx.trace.module_durations_s(names["step_module"])


def compute(ctx):
    d = durations_s(ctx)
    return 1e3 * statistics.median(d) if d else None
