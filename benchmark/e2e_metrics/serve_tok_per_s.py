"""Output tokens a second, as clients received them: every token
received inside the window over the window's seconds, all the work over
all the time. A stop of the machine, a stall of the program and a slow
stretch of the host all move it, as they move what an operator is paid
for. The steadier reading that leaves such stretches out, the median
over blocks of consecutive tokens, stands beside it as the per-layer
metric ``serve_block_tok_per_s``, and ``serve_stall_share`` is the
distance between the two."""

NAME, UNIT = "serve_tok_per_s", "tokens/s"


def received(ctx):
    """The times, from the window's start, of every token received
    inside it, in order; None for a run that served no stream."""
    if "streams" not in ctx.raw:
        return None
    w = ctx.raw["window_s"]
    return sorted(t for s in ctx.raw["streams"] for t in s["times"]
                  if 0.0 <= t < w)


def compute(ctx):
    times = received(ctx)
    if times is None:
        return None
    return len(times) / ctx.raw["window_s"]
