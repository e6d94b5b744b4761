"""Share of the device's busy time inside prefill programs (the traced
slice, device 0). The program's name comes from the configuration."""
NAME, UNIT, LAYER = "prefill_step_share", "%", "Decode scheduler"


def compute(ctx):
    names = ctx.config.get("trace_names", {})
    if ctx.trace is None or not ctx.trace.devices \
            or "prefill_module" not in names:
        return None
    busy = ctx.trace.module_s()
    if not busy:
        return None
    return 100.0 * ctx.trace.module_s(names["prefill_module"]) / busy
