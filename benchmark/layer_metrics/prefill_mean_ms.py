"""Mean host time of one prefill (``mx:decode.prefill``: the program's
dispatch to its token on the host) over the whole window: the server's
``prefill_s`` over ``prefill_steps``, ``stats()`` after the window less
before it. ``prefill_stall_ms`` is the traced slice's median of the
same span."""
NAME, UNIT, LAYER = "prefill_mean_ms", "ms", "Decode scheduler"


def compute(ctx):
    a, b = ctx.raw.get("stats0", {}), ctx.raw.get("stats1", {})
    if "prefill_s" not in a or "prefill_s" not in b:
        return None
    prefills = b["prefill_steps"] - a["prefill_steps"]
    if not prefills:
        return None
    return 1e3 * (b["prefill_s"] - a["prefill_s"]) / prefills
