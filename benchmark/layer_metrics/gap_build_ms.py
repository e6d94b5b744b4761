"""Device-idle milliseconds a decode step under ``mx:decode.pages``,
``.build`` and ``.dispatch``: growing the page tables, the step's numpy
arguments, the call into the program until the device starts."""
from benchmark import program_spans

NAME, UNIT, LAYER = "gap_build_ms", "ms", "Decode scheduler"
SPANS = ("decode.pages", "decode.build", "decode.dispatch")


def compute(ctx):
    return program_spans.idle_ms_per_step(ctx, SPANS)
