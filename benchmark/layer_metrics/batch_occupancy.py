"""How full the decode step's batch ran: tokens the decode steps emitted
over decode steps times the window's rows (``stats()`` over the
window; a prefill emits its request's first token, which is taken off)."""
NAME, UNIT, LAYER = "batch_occupancy", "%", "Decode scheduler"


def compute(ctx):
    if "stats1" not in ctx.raw:
        return None
    a, b = ctx.raw["stats0"], ctx.raw["stats1"]
    steps = b["decode_steps"] - a["decode_steps"]
    if not steps:
        return None
    emitted = (b["tokens_out"] - a["tokens_out"]) \
        - (b["prefill_steps"] - a["prefill_steps"])
    return 100.0 * emitted / (steps * b["window"])
