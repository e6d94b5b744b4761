"""The prefill attention kernel against the compute roofline: the
operations of causal attention at each ``mx_flash_fwd`` call's shapes
(read from the call's name, ``benchmark/kernel_costs.py``) at the chip's
peak, over the calls' device time, summed over the slice. Bound by
operations: every key block is used by a whole block of queries.

Listed where a prefill program still calls the kernel: the latent
prefill of the speculative and the state form (``serving/latent_moe.py``
hands ``flash_attention`` heads of 128 + 64 / 128 zero-padded to 256,
``mx_flash_fwd.bh32.q256.k256.d256.bfloat16`` and ``...q1024.k1024...``).
Counted are the widths the MODEL has (the driver's ``raw["model"]``
``nope`` + ``rope`` for the scores, ``v_dim`` for the values), not the
padded 256 of the call: 62.5% of what the call multiplies. A model that
names no such widths is reckoned at the call's own ``d`` (the plain
decoder, whose heads are not padded)."""
from benchmark import kernel_costs, trace_reduce

NAME, UNIT, LAYER = "flash_fwd_roofline_share", "%", "Kernels"


def compute(ctx):
    if ctx.trace is None or not ctx.trace.devices or ctx.peak is None:
        return None
    model = ctx.raw.get("model", {})
    flops = seconds = 0.0
    for name, s, e in ctx.trace.events(
            ctx.trace.devices[0], trace_reduce.OPS_LINE,
            kernel_costs.pattern("flash_fwd")):
        call = kernel_costs.shapes(name)
        if call is not None:
            if "v_dim" in model:
                call.update(d=model["nope"] + model["rope"],
                            v=model["v_dim"])
            flops += kernel_costs.causal_attention_flops(**call)
            seconds += (e - s) / 1e9
    if not seconds:
        return None
    return 100.0 * flops / ctx.peak["flops_per_s"] / seconds
