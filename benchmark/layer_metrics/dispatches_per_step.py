"""Fused update dispatches per optimizer step
(``profiler.counters()['fused_step_dispatches']`` over the window)."""
NAME, UNIT, LAYER = "dispatches_per_step", "count", "Train front end"


def compute(ctx):
    if not ctx.raw.get("steps"):
        return None
    return ctx.raw["dispatches"] / ctx.raw["steps"]
