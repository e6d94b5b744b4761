"""Share of the window the training loop spent inside the input
pipeline's ``next()`` (the benchmark's clock around the call). The
seconds in which the profiler held the loop are taken off the window."""
NAME, UNIT, LAYER = "data_wait_share", "%", "Train front end"


def compute(ctx):
    if "data_wait_s" not in ctx.raw:
        return None
    seconds = ctx.raw["window_s"] - ctx.raw.get("profiler_held_s", 0.0)
    return 100.0 * ctx.raw["data_wait_s"] / seconds
