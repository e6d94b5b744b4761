"""Output tokens a second, as clients received them inside the window:
the median, over every run of ``BLOCK`` consecutive tokens, of ``BLOCK``
over the time the run took. A median of some thousands of readings,
because the one-chip machine's host now and then stops the whole process
for one to three seconds (PERF.md, findings of PR 22), and tokens over
the window's seconds then swings by what the machine did, not the
program. With fewer than two blocks of tokens it is tokens over
seconds."""
import numpy as np

NAME, UNIT = "serve_tok_per_s", "tokens/s"
BLOCK = 400


def compute(ctx):
    if "streams" not in ctx.raw:
        return None
    w = ctx.raw["window_s"]
    t = np.sort([t for s in ctx.raw["streams"] for t in s["times"]
                 if 0.0 <= t < w])
    if len(t) < 2 * BLOCK:
        return len(t) / w
    return float(np.median(BLOCK / (t[BLOCK:] - t[:-BLOCK])))
