"""The BANDED calls of the grouped forward kernel (``mx_grouped_fwd
...w<window>``: a sliding layer's prefill attention) against the compute
roofline: the operations of causal attention over the keys inside the
window at each call's shapes (read from the call's name:
``benchmark/window_moe_costs.banded_fwd_flops``) at the chip's peak, over
the calls' device time, summed over the slice. Bound by operations: a key
block is used by a whole block of queries. The blocks behind the band are
never named, so what is over the operations is the band's two partly
masked blocks and the small products of one head at a time."""
from benchmark import kernel_costs, trace_reduce
from benchmark import window_moe_costs as costs

NAME, UNIT, LAYER = "banded_fwd_roofline_share", "%", "Kernels"


def compute(ctx):
    if ctx.trace is None or not ctx.trace.devices or ctx.peak is None:
        return None
    flops = seconds = 0.0
    for name, s, e in ctx.trace.events(
            ctx.trace.devices[0], trace_reduce.OPS_LINE,
            kernel_costs.pattern("grouped_fwd")):
        call, window = kernel_costs.shapes(name), costs.call_window(name)
        if call is not None and window is not None:
            flops += costs.banded_fwd_flops(call["bh"], call["q"],
                                            call["d"], window)
            seconds += (e - s) / 1e9
    if not seconds:
        return None
    return 100.0 * flops / ctx.peak["flops_per_s"] / seconds
