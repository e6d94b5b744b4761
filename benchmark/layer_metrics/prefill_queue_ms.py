"""Median wait of a prefill behind the step in flight: its joined
program's start, moved onto the host's clock, less the end of its
``mx:decode.prefill.launch`` (``benchmark/launch_join.py``). With the
launch, the program's device time and the read of its token it adds up
to the span ``mx:decode.prefill`` (PERF.md section 6, PR 35)."""
import statistics

from benchmark import launch_join

NAME, UNIT, LAYER = "prefill_queue_ms", "ms", "Decode scheduler"


def compute(ctx):
    joined = launch_join.of(ctx)
    prefills = joined.prefills() if joined else []
    if not prefills:
        return None
    return statistics.median(
        p.start + p.lead - p.launch.end for p in prefills) / 1e6
