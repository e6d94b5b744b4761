"""95th percentile of (first token received - time the request was DUE)
over the requests due inside the window, on the benchmark's clock. Not
end to end: a window holds some 120 requests, and the percentile then
swings by its own sampling (PERF.md, findings of PR 22)."""
from benchmark.harness import percentile

NAME, UNIT, LAYER = "ttft_p95_ms", "ms", "Decode scheduler"


def first_token_ms(ctx):
    return [(s["times"][0] - s["due"]) * 1e3
            for s in ctx.raw.get("streams", ())
            if s["in_window"] and s["times"]]


def compute(ctx):
    return percentile(first_token_ms(ctx), 95)
