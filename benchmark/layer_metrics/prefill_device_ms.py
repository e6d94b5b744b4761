"""Median device duration of the programs joined to ``program="prefill"``
launches (``benchmark/launch_join.py``): a prefill's own time, without
the wait behind the step in flight that ``mx:decode.prefill`` holds.
``raw["prefill_device_ms_by_rung"]`` has the median of each rung."""
import statistics

from benchmark import launch_join

NAME, UNIT, LAYER = "prefill_device_ms", "ms", "Model step"


def compute(ctx):
    joined = launch_join.of(ctx)
    prefills = joined.prefills() if joined else []
    if not prefills:
        return None
    by_rung = {}
    for p in prefills:
        by_rung.setdefault(p.launch.stats.get("rung"), []).append(p.ns / 1e6)
    ctx.raw["prefill_device_ms_by_rung"] = {
        str(rung): statistics.median(ms) for rung, ms in by_rung.items()}
    return statistics.median(p.ns for p in prefills) / 1e6
