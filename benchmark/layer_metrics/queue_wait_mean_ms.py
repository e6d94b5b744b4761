"""Mean wait of a request between ``submit`` and its admission, over
the whole window: the server's ``queue_wait_s`` over ``admitted``,
``stats()`` after the window less before it."""
NAME, UNIT, LAYER = "queue_wait_mean_ms", "ms", "Decode scheduler"


def compute(ctx):
    a, b = ctx.raw.get("stats0", {}), ctx.raw.get("stats1", {})
    if "admitted" not in a or "admitted" not in b:
        return None
    admitted = b["admitted"] - a["admitted"]
    if not admitted:
        return None
    return 1e3 * (b["queue_wait_s"] - a["queue_wait_s"]) / admitted
