"""Device-idle milliseconds a decode step under the scheduler's
``mx:decode.readback``, ``.emit`` and ``.record`` spans: reading the
tokens back, handing them to the clients, the records."""
from benchmark import program_spans

NAME, UNIT, LAYER = "gap_emit_ms", "ms", "Decode scheduler"
SPANS = ("decode.readback", "decode.emit", "decode.record")


def compute(ctx):
    return program_spans.idle_ms_per_step(ctx, SPANS)
