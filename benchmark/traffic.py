"""The one general load generator: everything a run is asked to do is
drawn here from ``--seed`` and the parameters of a traffic file.

A traffic file (``benchmark/traffic/<name>.json``) is data only. For a
serving cell it gives ``arrivals`` (``closed`` with ``clients``, or
``poisson`` with ``rate_per_s``) and the distributions of ``prompt_len``
and ``output_len``; for a training cell the batch and the size of the
host ring. A distribution is ``{"dist": "uniform" | "lognormal", ...}``;
every draw is an integer clipped to ``min``..``max``.

A closed loop's clients draw their requests from ``--seed`` without
end (``requests``). An open loop offers ONE fixed schedule of work
(``schedule``): the gaps of a Poisson process at the file's rate and as
many pairs of lengths are drawn once from ``OPEN_LOOP_DRAW``, whatever
the seed, and are offered in the order drawn; ``--seed`` draws the token
ids (and the weights) only. With a fresh draw for every seed, two seeds
differ in how many requests a window holds and how many of them on the
longest rung (PR 26); with one draw in an order of the seed's, in which
long prompts' deep chunks meet how many decoding rows (PR 42): a tail
reads either as a difference between runs (PERF.md section 2).
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


OPEN_LOOP_DRAW = 26     # the one draw of an open loop's work (PR 26, PR 42)


def requests(seed, stream, traffic, vocab):
    """Endless requests of one stream: ``(prompt int32 array,
    max_new_tokens)``. Token ids are uniform over the vocabulary."""
    gen = rng(seed, 1, stream)
    while True:
        n = draw(gen, traffic["prompt_len"])
        prompt = gen.integers(0, vocab, size=n, dtype=np.int32)
        yield prompt, draw(gen, traffic["output_len"])


def schedule(seed, traffic, vocab, horizon_s):
    """The requests of an open loop over ``horizon_s`` seconds, in
    order: ``(time, prompt int32 array, max_new_tokens)``, ``rate *
    horizon`` of them. The gaps between arrivals are those of a Poisson
    process of the rate ``arrivals.rate_per_s``, scaled so that the last
    arrival falls inside the horizon; gaps, pairs of lengths and their
    order are the fixed draw, ``seed`` draws the token ids: every seed
    offers the same requests at the same times, of other tokens."""
    n = int(round(float(traffic["arrivals"]["rate_per_s"]) * horizon_s))
    gaps = rng(OPEN_LOOP_DRAW, 2).exponential(1.0, size=n)
    gaps *= horizon_s * n / (n + 1.0) / gaps.sum()
    lengths = rng(OPEN_LOOP_DRAW, 1, 0)
    gen = rng(seed, 1, 0)
    for at in np.cumsum(gaps):
        size = draw(lengths, traffic["prompt_len"])
        asked = draw(lengths, traffic["output_len"])
        yield float(at), gen.integers(0, vocab, size=size,
                                      dtype=np.int32), asked


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
