"""Model FLOP/s utilization: images per second times the forward and
backward operations one image needs (from shapes, ``benchmark/flops.py``)
over chips times the chip's published peak. End to end: idle time and
recompute count against it. Taken in the traced run, whose profiler
slice slows a few steps."""
from benchmark import flops, harness
from benchmark.e2e_metrics import train_img_per_s

NAME, UNIT, LAYER = "train_mfu", "%", "Fused step"


def compute(ctx):
    rate = train_img_per_s.compute(ctx)
    if rate is None or ctx.peak is None:
        return None
    spec = ctx.config["forward_macs"]
    macs = harness.load_object(spec["import"])(**spec["kwargs"])
    return 100.0 * rate * flops.train_flops_per_image(macs) \
        / (ctx.chips * ctx.peak["flops_per_s"])
