"""Metric readers and checks that are plain arithmetic, on made-up runs."""
import types

import numpy as np
import pytest

from benchmark.drivers import train_common
from benchmark.e2e_metrics import serve_tok_per_s
from benchmark.layer_metrics import (data_wait_share,
                                     serve_block_tok_per_s,
                                     serve_stall_share, train_stall_share)


def _ctx(**raw):
    return types.SimpleNamespace(raw=raw)


def test_train_stall_share_is_what_the_median_hides():
    # 30 steady stretches of 1 s and one of 4 s: 3 s of 34 were a stall
    t, syncs = 0.0, [(0, 0.0)]
    for i in range(31):
        t += 4.0 if i == 12 else 1.0
        syncs.append((10 * (i + 1), t))
    ctx = _ctx(syncs=syncs, steps=310, images=310 * 256, window_s=t)
    assert abs(train_stall_share.compute(ctx) - 100 * 3 / 34) < 1e-6
    # seconds the profiler held the loop are not the program's stall
    ctx.raw["profiler_held_s"] = 3.0
    assert abs(train_stall_share.compute(ctx)) < 1e-6
    ctx.raw["data_wait_s"] = 15.5
    assert abs(data_wait_share.compute(ctx) - 50.0) < 1e-6


def test_serve_stall_share_is_what_the_median_hides():
    times = np.arange(0, 20, 0.002)
    times = np.concatenate([times, 25 + np.arange(0, 5, 0.002)])  # 5 s gap
    ctx = _ctx(streams=[{"times": times.tolist()}], window_s=30.0)
    assert abs(serve_stall_share.compute(ctx) - 100 * 5 / 30) < 0.5
    steady = _ctx(streams=[{"times": np.arange(0, 30, 0.002).tolist()}],
                  window_s=30.0)
    assert abs(serve_stall_share.compute(steady)) < 0.1
    assert serve_stall_share.compute(_ctx(window_s=30.0)) is None


def _served(stall_every, stop_at=None, step_s=0.0133, stall_s=0.018,
            window_s=30.0, rows=8):
    """Token times of a full window of ``rows`` streams: one step every
    ``step_s``, every ``stall_every``-th step behind a prefill of
    ``stall_s``, and at ``stop_at`` a stop of the machine of 3 s."""
    t, times, n = 0.0, [], 0
    while t < window_s:
        n += 1
        t += step_s + (stall_s if n % stall_every == 0 else 0.0)
        if stop_at is not None and stop_at <= t < stop_at + step_s:
            t += 3.0
        times += [t] * rows
    return _ctx(streams=[{"times": times}], window_s=window_s)


def test_the_served_rate_is_all_tokens_over_all_the_windows_seconds():
    """End to end: a stop of 3 s in a window of 30 costs a tenth of the
    rate, whoever caused it; tokens outside the window do not count."""
    steady = _served(30)
    times = steady.raw["streams"][0]["times"]
    inside = sum(1 for t in times if t < 30.0)
    assert serve_tok_per_s.compute(steady) == inside / 30.0
    stopped = serve_tok_per_s.compute(_served(30, stop_at=12.0))
    assert 0.09 < 1.0 - stopped / serve_tok_per_s.compute(steady) < 0.11
    assert serve_tok_per_s.compute(_ctx(window_s=30.0)) is None


def test_a_stop_of_the_machine_does_not_move_the_block_median():
    """The per-layer reading beside it: the median over blocks leaves
    the stop out, and the stall share says what it left out."""
    steady = serve_block_tok_per_s.compute(_served(30))
    stopped = serve_block_tok_per_s.compute(_served(30, stop_at=12.0))
    assert abs(stopped / steady - 1.0) < 1e-3
    assert serve_stall_share.compute(_served(30, stop_at=12.0)) > 9.0
    # under two blocks of tokens there is no median: left out, never 0
    few = _ctx(streams=[{"times": np.arange(0, 3, 0.002).tolist()}],
               window_s=30.0)
    assert serve_block_tok_per_s.compute(few) is None
    assert serve_stall_share.compute(few) is None


def test_the_block_median_follows_the_stalls_a_block_in_small_steps():
    """From a prefill every 40 steps to one every 20 the true rate
    falls by 3.2%, by 0.1-0.3% a time. A median of block rates reads
    that fall in jumps of one stall over one block: 2.6% with the 400
    tokens PR 22 chose (0.67 s of them today), 0.75% with a block of
    2.4 s. So the reading stays within 0.4% of tokens over seconds at
    every density and no jump of it is over 0.75%."""
    reads, true = [], []
    for every in range(40, 19, -1):
        reads.append(serve_block_tok_per_s.compute(_served(every)))
        true.append(8.0 / (0.0133 + 0.018 / every))
    reads, true = np.array(reads), np.array(true)
    assert np.all(np.abs(reads / true - 1.0) < 4e-3), reads / true
    assert np.all(np.abs(np.diff(reads) / reads[:-1]) < 7.5e-3)
    assert 0.028 < 1.0 - reads[-1] / reads[0] < 0.036


@pytest.mark.parametrize("block_s", [0.67, 2.4])
def test_why_the_block_is_long(block_s):
    """The same fall read with the block PR 22 chose (400 tokens: 2.4 s
    of them then, 0.67 s at today's rate) against one of 2.4 s."""
    block = int(round(block_s / 0.0133)) * 8
    worst = 0.0
    for every in range(40, 19, -1):
        t = np.array(_served(every).raw["streams"][0]["times"])
        read = np.median(block / (t[block:] - t[:-block]))
        worst = max(worst, abs(read / (8.0 / (0.0133 + 0.018 / every)) - 1))
    assert bool(worst > 0.01) == (block_s < 1.0), worst


def test_update_error_is_relative_to_the_references_move():
    before = {"a_beta": np.zeros(4), "b_beta": np.ones(4)}
    after = {"a_beta": np.full(4, -0.01), "b_beta": np.ones(4) - 0.02}
    ref = {"before": before, "after": after}
    assert train_common._update_error(ref, after)[0] == 0.0
    served = {"a_beta": np.full(4, -0.011), "b_beta": after["b_beta"]}
    err, at = train_common._update_error(ref, served)
    assert at == "a_beta" and abs(err - 0.1) < 1e-9
    # no step at all is off by the whole move; a NaN is the worst
    assert abs(train_common._update_error(ref, before)[0] - 1.0) < 1e-9
    served["a_beta"] = np.full(4, np.nan)
    err, at = train_common._update_error(ref, served)
    assert at == "a_beta" and np.isnan(err)
