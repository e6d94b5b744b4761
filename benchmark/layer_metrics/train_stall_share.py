"""Share of the window's images lost to stretches slower than the
median one: 1 - (images over the window's seconds) / ``train_img_per_s``.
The end-to-end rate is a median over the stretches between two syncs, so
that a stop of the whole machine does not move it; a stall the program
itself causes for a part of the window (an input hiccup, a collection, a
periodic host task) does not move it either, and shows here. The seconds
in which the profiler held the loop are taken off the window."""
from benchmark.e2e_metrics import train_img_per_s

NAME, UNIT, LAYER = "train_stall_share", "%", "Train front end"


def compute(ctx):
    rate = train_img_per_s.compute(ctx)
    if not rate:
        return None
    seconds = ctx.raw["window_s"] - ctx.raw.get("profiler_held_s", 0.0)
    return 100.0 * (1.0 - ctx.raw["images"] / seconds / rate)
