"""The prefill attention kernel against the compute roofline: the
operations of causal attention at each ``mx_flash_fwd`` call's shapes
(read from the call's name, ``benchmark/kernel_costs.py``) at the chip's
peak, over the calls' device time, summed over the slice. Bound by
operations: every key block is used by a whole block of queries."""
from benchmark import kernel_costs, trace_reduce

NAME, UNIT, LAYER = "flash_fwd_roofline_share", "%", "Kernels"


def compute(ctx):
    if ctx.trace is None or not ctx.trace.devices or ctx.peak is None:
        return None
    flops = seconds = 0.0
    for name, s, e in ctx.trace.events(
            ctx.trace.devices[0], trace_reduce.OPS_LINE,
            kernel_costs.pattern("flash_fwd")):
        call = kernel_costs.shapes(name)
        if call is not None:
            flops += kernel_costs.causal_attention_flops(**call)
            seconds += (e - s) / 1e9
    if not seconds:
        return None
    return 100.0 * flops / ctx.peak["flops_per_s"] / seconds
