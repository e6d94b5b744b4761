"""The one general load generator: everything a run is asked to do is
drawn here from ``--seed`` and the parameters of a traffic file.

A traffic file (``benchmark/traffic/<name>.json``) is data only. For a
serving cell it gives ``arrivals`` (``closed`` with ``clients``, or
``poisson`` with ``rate_per_s``) and the distributions of ``prompt_len``
and ``output_len``; for a training cell the batch and the size of the
host ring. A distribution is ``{"dist": "uniform" | "lognormal", ...}``;
every draw is an integer clipped to ``min``..``max``.
"""
from __future__ import annotations

import numpy as np


def rng(seed, *stream):
    """Independent streams of one seed: ``rng(seed, 3)`` never collides
    with ``rng(seed, 4)`` or with another seed's streams."""
    return np.random.default_rng([int(seed), *(int(s) for s in stream)])


def draw(gen, spec):
    """One integer from the distribution ``spec``."""
    kind = spec["dist"]
    if kind == "uniform":
        value = gen.integers(spec["min"], spec["max"] + 1)
    elif kind == "lognormal":
        value = np.exp(gen.normal(np.log(spec["median"]), spec["sigma"]))
    else:
        raise ValueError("traffic: unknown distribution %r" % (kind,))
    return int(min(max(int(round(float(value))), spec["min"]), spec["max"]))


def requests(seed, stream, traffic, vocab):
    """Endless requests of one stream: ``(prompt int32 array,
    max_new_tokens)``. Token ids are uniform over the vocabulary."""
    gen = rng(seed, 1, stream)
    while True:
        n = draw(gen, traffic["prompt_len"])
        prompt = gen.integers(0, vocab, size=n, dtype=np.int32)
        yield prompt, draw(gen, traffic["output_len"])


def arrival_times(seed, arrivals, horizon_s):
    """Times in ``[0, horizon_s)`` of a Poisson process of the rate
    ``rate_per_s``."""
    rate = float(arrivals["rate_per_s"])
    gen = rng(seed, 2)
    n = int(rate * horizon_s * 1.5 + 50)
    times = np.cumsum(gen.exponential(1.0, size=n)) / rate
    return times[times < horizon_s]


def image_ring(seed, images, image, classes):
    """The host ring of a training cell: ``images`` seeded bfloat16
    images ``(3, image, image)`` and their labels, as numpy arrays in
    host memory, where an input pipeline expects its data. Pixels are
    random bits with the exponent held to 2**-7 .. 2: finite, symmetric
    about 0, and made at the speed of the random generator (a normal
    draw converted to bfloat16 costs several seconds of set-up per
    gigabyte, a round trip through the device more)."""
    import ml_dtypes
    gen = rng(seed, 4)
    shape = (images, 3, image, image)
    n = int(np.prod(shape))
    bits = gen.bit_generator.random_raw((n + 3) // 4) \
        .view(np.uint16)[:n].reshape(shape)      # four pixels a draw
    bits &= np.uint16(0x83FF)       # sign, 3 exponent bits, 7 of mantissa
    bits += np.uint16(120 << 7)     # exponent 120..127 of bias 127
    labels = gen.integers(0, classes, size=(images,)).astype(np.float32)
    return bits.view(ml_dtypes.bfloat16), labels
