"""Median duration of ``mx:decode.prefill`` (the prefill program's
dispatch to its token on the host): what one admitted prompt adds to the
next token gap of every live stream."""
from benchmark import program_spans

NAME, UNIT, LAYER = "prefill_stall_ms", "ms", "Decode scheduler"


def compute(ctx):
    return program_spans.median_ms(ctx, "decode.prefill")
