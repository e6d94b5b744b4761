"""Share of the traced steps' time in which device 0 ran a collective
operation (all-reduce, all-gather, reduce-scatter) and nothing else."""
NAME, UNIT = "collective_exposed_share", "%"
LAYER = "Collectives (parallel/grad_sync.py)"


def compute(ctx):
    if ctx.trace is None or not ctx.trace.devices or ctx.chips < 2:
        return None
    return 100.0 * ctx.trace.exposed_collective_s() / ctx.trace.window_s
