"""Median of (first token received - time the request was DUE) over the
requests due inside the window, on the benchmark's clock."""
from benchmark.harness import percentile
from benchmark.layer_metrics.ttft_p95_ms import first_token_ms

NAME, UNIT, LAYER = "ttft_p50_ms", "ms", "Decode scheduler"


def compute(ctx):
    return percentile(first_token_ms(ctx), 50)
