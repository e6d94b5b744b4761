"""Programs JAX compiled or loaded inside the measured window (JAX's own
monitoring event). Anything but 0 also makes the run incorrect: the work
belongs in set-up."""
NAME, UNIT, LAYER = "compiles_in_window", "count", "Compile"


def compute(ctx):
    return ctx.raw.get("compiles_in_window")
