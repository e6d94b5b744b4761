"""The generator: the same seed gives the same load, another seed
another, and the parameters of a traffic file mean what they say."""
import itertools
import json
import os

import numpy as np
import pytest

from benchmark import traffic

HERE = os.path.dirname(os.path.abspath(__file__))
TRAFFIC_DIR = os.path.join(os.path.dirname(HERE), "traffic")
SERVING = sorted(f for f in os.listdir(TRAFFIC_DIR)
                 if "arrivals" in json.load(open(os.path.join(TRAFFIC_DIR, f))))


def _load(name):
    with open(os.path.join(TRAFFIC_DIR, name)) as f:
        return json.load(f)


def _take(seed, stream, spec, n=50):
    return [(p.tolist(), k) for p, k in itertools.islice(
        traffic.requests(seed, stream, spec, 50272), n)]


@pytest.mark.parametrize("name", SERVING)
def test_same_seed_same_requests_other_seed_others(name):
    spec = _load(name)
    assert _take(7, 0, spec) == _take(7, 0, spec)
    assert _take(7, 0, spec) != _take(8, 0, spec)
    assert _take(7, 0, spec) != _take(7, 1, spec)


@pytest.mark.parametrize("name", SERVING)
def test_lengths_stay_inside_their_clips(name):
    spec = _load(name)
    got = _take(3, 0, spec, 400)
    lens = np.array([len(p) for p, _ in got])
    outs = np.array([k for _, k in got])
    assert lens.min() >= spec["prompt_len"]["min"]
    assert lens.max() <= spec["prompt_len"]["max"]
    assert outs.min() >= spec["output_len"]["min"]
    assert outs.max() <= spec["output_len"]["max"]
    assert len(set(lens.tolist())) > 20      # a distribution, not a point


def test_lognormal_median_is_the_median():
    gen = traffic.rng(0, 9)
    spec = {"dist": "lognormal", "median": 900, "sigma": 0.5,
            "min": 1, "max": 10 ** 6}
    draws = [traffic.draw(gen, spec) for _ in range(4000)]
    assert abs(np.median(draws) - 900) < 45


def test_image_ring_repeats():
    x1, y1 = traffic.image_ring(3, 4, 8, 10)
    x2, y2 = traffic.image_ring(3, 4, 8, 10)
    x3, _ = traffic.image_ring(4, 4, 8, 10)
    assert x1.shape == (4, 3, 8, 8) and str(x1.dtype) == "bfloat16"
    assert np.array_equal(x1.view(np.uint16), x2.view(np.uint16))
    assert np.array_equal(y1, y2)
    assert not np.array_equal(x1.view(np.uint16), x3.view(np.uint16))
    assert 0 <= y1.min() and y1.max() < 10
    big, _ = traffic.image_ring(5, 16, 32, 10)
    values = big.astype(np.float32)
    assert np.isfinite(values).all()
    assert 2 ** -7 <= np.abs(values).min() and np.abs(values).max() < 2
    assert abs(values.mean()) < 0.02


def test_an_open_loop_offers_every_seed_the_same_schedule_of_other_tokens():
    """``schedule``: every seed offers the same requests at the same
    times in the same order, and differs in the token ids alone; a
    closed loop's clients draw their own. Seeds past 2**31 are seeds
    like any other."""
    spec = _load("longprompt-steady.json")
    horizon, rate = 32.0, spec["arrivals"]["rate_per_s"]
    runs = []
    for seed in (3, 3, 4, 2 ** 31 + 11):
        got = list(traffic.schedule(seed, spec, 50272, horizon))
        # as many requests as the rate says, whatever the seed
        assert len(got) == round(rate * horizon)
        times = np.array([at for at, _, _ in got])
        assert 0.0 < times[0] and times[-1] < horizon
        assert (np.diff(times) > 0).all()
        assert all(p.dtype == np.int32 and 0 <= p.min() and p.max() < 50272
                   for _, p, _ in got)
        runs.append((times, [(len(p), k) for _, p, k in got],
                     [p for _, p, _ in got]))
    (times_a, pairs_a, ids_a), again, (times_b, pairs_b, ids_b), \
        (times_c, pairs_c, ids_c) = runs
    # a seed repeats itself to the token
    assert np.array_equal(times_a, again[0]) and pairs_a == again[1]
    assert all(np.array_equal(p, q) for p, q in zip(ids_a, again[2]))
    # two seeds: the same times, lengths and order ...
    assert np.array_equal(times_a, times_b) \
        and np.array_equal(times_a, times_c)
    assert pairs_a == pairs_b == pairs_c
    # ... and other token ids, request for request
    assert not any(np.array_equal(p, q) for p, q in zip(ids_a, ids_b))
    assert not any(np.array_equal(p, q) for p, q in zip(ids_a, ids_c))
    # the gaps are a Poisson process's: their deviation is their mean
    gaps = np.diff(times_a, prepend=0.0)
    assert 0.8 < gaps.std() / gaps.mean() < 1.25
    assert abs(gaps.mean() - 1.0 / rate) < 0.01 / rate
    # the lengths keep to the file's distributions
    lens = np.array([n for n, _ in pairs_a])
    assert lens.min() >= spec["prompt_len"]["min"]
    assert lens.max() == spec["prompt_len"]["max"]
    assert abs(np.median(lens) - spec["prompt_len"]["median"]) < 90
    assert len(set(pairs_a)) > 100           # a distribution, not a point
    # a closed loop's clients: fresh draws from the seed
    closed = _load("decode-batch.json")
    a = [len(p) for p, _ in _take(3, 0, closed, 200)]
    b = [len(p) for p, _ in _take(4, 0, closed, 200)]
    assert sorted(a) != sorted(b)


@pytest.mark.parametrize("horizon", [8.0, 32.0, 62.0])
def test_the_number_of_requests_is_rate_times_horizon(horizon):
    spec = _load("longprompt-steady.json")
    rate = spec["arrivals"]["rate_per_s"]
    got = list(traffic.schedule(5, spec, 50272, horizon))
    assert len(got) == int(round(rate * horizon))
    assert got[-1][0] < horizon
    # a longer horizon offers the shorter one's lengths first
    short = list(traffic.schedule(5, spec, 50272, 4.0))
    assert [(len(p), k) for _, p, k in short] \
        == [(len(p), k) for _, p, k in got[:len(short)]]


def test_the_tiny_preset_differs_by_seed_in_nothing_but_ids():
    """What a rehearsal offers (the file's ``tiny`` block laid over it)
    keeps the rule: two seeds, one schedule, other tokens."""
    spec = _load("longprompt-steady.json")
    tiny = {**spec, **spec["tiny"]}
    a = list(traffic.schedule(11, tiny, 64, 2.5))
    b = list(traffic.schedule(12, tiny, 64, 2.5))
    assert len(a) == len(b) == round(
        tiny["arrivals"]["rate_per_s"] * 2.5) > 20
    assert [at for at, _, _ in a] == [at for at, _, _ in b]
    assert [(len(p), k) for _, p, k in a] == [(len(p), k) for _, p, k in b]
    assert any(not np.array_equal(p, q)
               for (_, p, _), (_, q, _) in zip(a, b))
    assert all(tiny["prompt_len"]["min"] <= len(p)
               <= tiny["prompt_len"]["max"] for _, p, _ in a)
