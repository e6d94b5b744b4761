"""How late the open-loop generator ran: sent - due, 99th percentile. A
starved generator must not read as a fast server."""
from benchmark.harness import percentile

NAME, UNIT, LAYER = "gen_late_p99_ms", "ms", "load generator (benchmark)"


def compute(ctx):
    if ctx.traffic.get("arrivals", {}).get("kind") != "poisson":
        return None
    return percentile([(s["sent"] - s["due"]) * 1e3
                       for s in ctx.raw["streams"] if s["in_window"]], 99)
