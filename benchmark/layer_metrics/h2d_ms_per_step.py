"""Milliseconds a training step the input pipeline's placer thread
spent inside ``mx:pipeline.h2d`` (``device_put`` to the transfer's
end), summed over the arrays of the traced steps."""
from benchmark import program_spans

NAME, UNIT, LAYER = "h2d_ms_per_step", "ms", "Train front end"


def compute(ctx):
    spans = program_spans.of(ctx)
    found = spans.named("pipeline.h2d") if spans else []
    if not found or not ctx.raw.get("traced_steps"):
        return None
    return sum(sp.ns for sp in found) / 1e6 / ctx.raw["traced_steps"]
