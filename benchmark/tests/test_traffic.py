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


def test_poisson_arrivals_repeat_and_hold_their_rate():
    arr = {"kind": "poisson", "rate_per_s": 10.0}
    a, b = (traffic.arrival_times(5, arr, 200.0) for _ in range(2))
    assert np.array_equal(a, b)
    assert not np.array_equal(a, traffic.arrival_times(6, arr, 200.0)[:len(a)])
    assert abs(len(a) / 200.0 - 10.0) < 0.5
    assert (np.diff(a) > 0).all() and a.max() < 200.0


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
