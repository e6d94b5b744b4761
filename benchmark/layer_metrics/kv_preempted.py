"""Requests the scheduler preempted for want of KV pages, in the
window."""
NAME, UNIT, LAYER = "kv_preempted", "count", "KV pool"


def compute(ctx):
    if "stats1" not in ctx.raw:
        return None
    return ctx.raw["stats1"]["preempted"] - ctx.raw["stats0"]["preempted"]
