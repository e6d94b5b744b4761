"""Median duration of ``mx:trainer.step``: the host's part of one
optimizer step, from the trainer's entry to its programs' dispatch."""
from benchmark import program_spans

NAME, UNIT, LAYER = "step_dispatch_ms", "ms", "Fused step"


def compute(ctx):
    return program_spans.median_ms(ctx, "trainer.step")
