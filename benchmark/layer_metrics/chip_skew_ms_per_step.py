"""The spread between the chips' COMPUTE time a decode step: each
chip's seconds with an operation that is no collective running inside
its own executions of the step programs, over their number; the busiest
chip's less the idlest's. A straggler — the chip whose experts took most
slots — is what every all-reduce waits for (``raw.moe_by_chip`` has the
chips' own slots); a chip that waits is busy IN the all-reduce, so whole
busy time is the same on every chip and says nothing."""
from benchmark import mellum_costs as costs

NAME, UNIT, LAYER = "chip_skew_ms_per_step", "ms", "Cross-chip exchange"


def compute(ctx):
    if ctx.trace is None or len(ctx.trace.devices) < 2:
        return None
    busy = [costs.compute_per_step_s(ctx, d) for d in ctx.trace.devices]
    if any(b is None for b in busy):
        return None
    return 1e3 * (max(busy) - min(busy))
