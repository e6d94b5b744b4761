"""Output tokens a second over the median stretch of the window: the
median, over every run of ``BLOCK`` consecutive tokens received inside
it, of ``BLOCK`` over the time the run took. It is what
``serve_tok_per_s`` (all tokens over all seconds) would read had no
stretch of the window been slower than the median one: a stop of the
whole machine for seconds (PERF.md, finding 5 of PR 22) does not move
it, and ``serve_stall_share`` is the distance between the two. With
fewer than two blocks of tokens there is no median to take and the
metric is left out.

``BLOCK`` is 2.4 s of tokens, 180 steps of a window of 8. A median of
block rates reads the prefill stalls in whole stalls a block: 400
tokens (PR 22's block, 0.67 s of them at today's rate) hold one or two
stalls of 14 ms, so a reading at the boundary jumps by 2%; 2.4 s hold
five and jump by 0.6% (``benchmark/tests/test_readers.py``). It stays
under a tenth of the window so that a stop spoils under a half of the
blocks. From run to run it spreads as tokens over seconds does (PERF.md
section 6, PR 26): the block guards the level, not the noise."""
import numpy as np

from benchmark.e2e_metrics import serve_tok_per_s

NAME, UNIT, LAYER = "serve_block_tok_per_s", "tokens/s", "Decode scheduler"
BLOCK = 1440


def compute(ctx):
    times = serve_tok_per_s.received(ctx)
    if times is None or len(times) < 2 * BLOCK:
        return None
    t = np.asarray(times)
    return float(np.median(BLOCK / (t[BLOCK:] - t[:-BLOCK])))
