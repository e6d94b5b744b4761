"""Median idle time of device 0 between the end of one program and the
start of the next decode-step program (traced slice): what the host
adds to every token gap."""
import statistics

from benchmark import trace_reduce

NAME, UNIT, LAYER = "decode_gap_ms", "ms", "Decode scheduler"


def compute(ctx):
    step = ctx.config.get("trace_names", {}).get("step_module")
    if ctx.trace is None or not ctx.trace.devices or step is None:
        return None
    events = sorted(
        ctx.trace.events(ctx.trace.devices[0], trace_reduce.MODULES_LINE),
        key=lambda ev: ev[1])
    gaps = [max(0.0, s - before[2]) / 1e6
            for before, (name, s, _) in zip(events, events[1:])
            if step in name]
    return statistics.median(gaps) if gaps else None
