"""Share of the window's decode steps that were dispatched while the
step before them was still unread, so that what the host does with a
step's tokens, and the next launch, ran under the device's step: the
server's ``decode_steps_ahead`` over ``decode_steps``, ``stats()`` after
the window less before it. A program that does not count it (one that
reads every step back at once) leaves the metric out."""
NAME, UNIT, LAYER = "decode_ahead_share", "%", "Decode scheduler"


def compute(ctx):
    a, b = ctx.raw.get("stats0", {}), ctx.raw.get("stats1", {})
    if "decode_steps_ahead" not in a or "decode_steps_ahead" not in b:
        return None
    steps = b["decode_steps"] - a["decode_steps"]
    if not steps:
        return None
    return 100.0 * (b["decode_steps_ahead"] - a["decode_steps_ahead"]) / steps
