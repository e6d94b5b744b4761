"""Device 0's time in collective operations (the all-reduces behind
every attention and every expert layer, the arg-max's gather) a PLAIN
decode step of a model that a mesh shares: the union of the collective
operations' intervals — synchronous ones, and from ``-start`` to
``-done`` of asynchronous ones — inside the plain step program's
executions, over their number. Hidden or exposed alike:
``exchange_exposed_share`` says which."""
from benchmark import mellum_costs as costs

NAME, UNIT, LAYER = "exchange_ms_per_step", "ms", "Cross-chip exchange"


def compute(ctx):
    steps = costs.plain_steps(ctx)
    if not steps or ctx.chips < 2:
        return None
    device = ctx.trace.devices[0]
    ns = costs.inside(costs.collective_intervals(ctx.trace, device), steps)
    return ns / 1e6 / len(steps) if ns else None
