"""Metric readers and checks that are plain arithmetic, on made-up runs."""
import types

import numpy as np

from benchmark.drivers import train_common
from benchmark.layer_metrics import (data_wait_share, serve_stall_share,
                                     train_stall_share)


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
    times = np.arange(0, 20, 0.01)
    times = np.concatenate([times, 25 + np.arange(0, 5, 0.01)])  # 5 s gap
    ctx = _ctx(streams=[{"times": times.tolist()}], window_s=30.0)
    assert abs(serve_stall_share.compute(ctx) - 100 * 5 / 30) < 0.5
    steady = _ctx(streams=[{"times": np.arange(0, 30, 0.01).tolist()}],
                  window_s=30.0)
    assert abs(serve_stall_share.compute(steady)) < 0.1
    assert serve_stall_share.compute(_ctx(window_s=30.0)) is None


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
