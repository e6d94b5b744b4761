"""Share of the traced slice the training loop spent inside
``mx:pipeline.wait``, the queue-dry branch of the pipeline's ``next()``:
the program's own ``data_wait``, on the profiler's clock."""
from benchmark import program_spans

NAME, UNIT, LAYER = "pipeline_wait_share", "%", "Train front end"


def compute(ctx):
    spans = program_spans.of(ctx)
    if not spans or not spans.named("trainer.step") \
            or not ctx.trace.window_s:
        return None         # a program that writes no spans
    waited = sum(sp.ns for sp in spans.named("pipeline.wait"))
    return 100.0 * waited / 1e9 / ctx.trace.window_s
