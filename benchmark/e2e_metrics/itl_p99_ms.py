"""99th percentile over all gaps between consecutive tokens of a stream
whose later token arrived inside the window, on the benchmark's clock."""
from benchmark.harness import percentile

NAME, UNIT = "itl_p99_ms", "ms"


def gaps_ms(ctx):
    w = ctx.raw["window_s"]
    return [(b - a) * 1e3 for s in ctx.raw["streams"]
            for a, b in zip(s["times"], s["times"][1:]) if 0.0 <= b < w]


def compute(ctx):
    if "streams" not in ctx.raw:
        return None
    return percentile(gaps_ms(ctx), 99)
