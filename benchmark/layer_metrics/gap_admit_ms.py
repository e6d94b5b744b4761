"""Device-idle milliseconds a decode step under ``mx:decode.reap`` and
``mx:decode.admit`` (with the ``.prefill`` inside it): only the idle
part, the prefill program's own time is busy."""
from benchmark import program_spans

NAME, UNIT, LAYER = "gap_admit_ms", "ms", "Decode scheduler"
SPANS = ("decode.reap", "decode.admit", "decode.prefill")


def compute(ctx):
    return program_spans.idle_ms_per_step(ctx, SPANS)
