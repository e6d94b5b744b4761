"""Share of the device's busy time inside Mosaic custom calls, the
Pallas kernels (traced slice, device 0)."""
from benchmark import trace_reduce

NAME, UNIT, LAYER = "mosaic_time_share", "%", "Kernels"


def compute(ctx):
    if ctx.trace is None or not ctx.trace.devices:
        return None
    busy = trace_reduce.total(ctx.trace.busy(ctx.trace.devices[0])) / 1e9
    if not busy:
        return None
    return 100.0 * ctx.trace.op_s(trace_reduce.MOSAIC) / busy
