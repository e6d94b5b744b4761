"""Share of the traced window in which device 0 ran a collective
operation and nothing else, in a SERVING cell whose model a mesh shares:
what the step's all-reduces cost that no other work hides.
``Trace.exposed_collective_s``'s arithmetic (which
``collective_exposed_share`` reads for training) with the collectives
found by the KIND of their HLO line (``benchmark/mellum_costs.
exposed_collective_s``): under ``shard_map`` a ``psum`` is the
instruction ``%psum.N = ... all-reduce(...)``, which a pattern over the
name does not see."""
from benchmark import mellum_costs as costs

NAME, UNIT, LAYER = "exchange_exposed_share", "%", "Cross-chip exchange"


def compute(ctx):
    if ctx.trace is None or not ctx.trace.devices or ctx.chips < 2 \
            or not ctx.trace.window_s:
        return None
    return 100.0 * costs.exposed_collective_s(
        ctx.trace, ctx.trace.devices[0]) / ctx.trace.window_s
