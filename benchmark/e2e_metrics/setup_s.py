"""Process start to the first measured step or request: import, weights,
warm-up, compile or cache load, lead-in traffic."""
NAME, UNIT = "setup_s", "s"


def compute(ctx):
    return ctx.raw.get("setup_s")
