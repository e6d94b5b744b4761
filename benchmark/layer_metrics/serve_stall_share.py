"""Share of the window's tokens lost to stretches slower than the median
one: 1 - ``serve_tok_per_s`` (tokens received in the window over its
seconds) / ``serve_block_tok_per_s`` (the median over blocks of
consecutive tokens). A stop of the whole machine, and a stall the
program itself causes for a part of the window (preemption, a
collection, a periodic host task), both lower the first and leave the
second: they show here."""
from benchmark.e2e_metrics import serve_tok_per_s
from benchmark.layer_metrics import serve_block_tok_per_s

NAME, UNIT, LAYER = "serve_stall_share", "%", "Decode scheduler"


def compute(ctx):
    median = serve_block_tok_per_s.compute(ctx)
    if not median:
        return None
    return 100.0 * (1.0 - serve_tok_per_s.compute(ctx) / median)
