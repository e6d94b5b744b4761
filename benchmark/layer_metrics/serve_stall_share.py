"""Share of the window's tokens lost to stretches slower than the median
one: 1 - (tokens received in the window over its seconds) /
``serve_tok_per_s``. The end-to-end rate is a median over runs of
consecutive tokens, so that a stop of the whole machine does not move
it; a stall the program itself causes for a part of the window
(preemption, a collection, a periodic host task) does not move it
either, and shows here."""
from benchmark.e2e_metrics import serve_tok_per_s

NAME, UNIT, LAYER = "serve_stall_share", "%", "Decode scheduler"


def compute(ctx):
    rate = serve_tok_per_s.compute(ctx)
    if not rate:
        return None
    w = ctx.raw["window_s"]
    tokens = sum(1 for s in ctx.raw["streams"] for t in s["times"]
                 if 0.0 <= t < w)
    return 100.0 * (1.0 - tokens / w / rate)
