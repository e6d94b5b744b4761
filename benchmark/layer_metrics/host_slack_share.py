"""Share of the window the scheduler stood in ``mx:decode.readback``
waiting for the device, its own work done: the server's
``readback_wait_s``, ``stats()`` after the window less before it, over
the window's seconds. Near 0 the host paces the loop again. A program
that does not count it leaves the metric out."""
NAME, UNIT, LAYER = "host_slack_share", "%", "Decode scheduler"


def compute(ctx):
    a, b = ctx.raw.get("stats0", {}), ctx.raw.get("stats1", {})
    if "readback_wait_s" not in a or "readback_wait_s" not in b \
            or not ctx.raw.get("window_s"):
        return None
    return 100.0 * (b["readback_wait_s"] - a["readback_wait_s"]) \
        / ctx.raw["window_s"]
