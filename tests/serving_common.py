"""What the serving tests share: the greedy oracle a served stream is held
to, and how a test drives an unstarted ``DecodeServer`` and reads a
request back. Not collected (no ``test_`` prefix); the test files import
it as they import one another.

The oracle compiles ONCE a (model configuration, width). Most of the
suite's seconds used to be reference streams that built a new ``jax.jit``
a call and compiled a program for every length they fed it."""
import numpy as np

WIDTH = 32          # a reference sequence is padded to a multiple of this

_PREFILLS = {}      # model configuration -> jax.jit(model.prefill)


def jit_prefill(model):
    """``jax.jit(model.prefill)``, made once a model CONFIGURATION for
    the process (JAX keeps one compiled program a shape under it): the
    class and every attribute where they all hash, else the object
    itself. Either way a model is held alive by what is kept, so no key
    is an ``id`` that a collected model hands to the next."""
    import jax
    try:
        key = (type(model), tuple(sorted(vars(model).items())))
        hash(key)
    except TypeError:
        key = model
    if key not in _PREFILLS:
        _PREFILLS[key] = jax.jit(model.prefill)
    return _PREFILLS[key]


def greedy_reference(model, params, prompt, n):
    """Greedy generation by one FULL-sequence forward a token and no
    cache — the oracle stepwise cached decode must reproduce token for
    token. ``model.prefill`` alone: no server, no pool. The sequence is
    zero-padded to ONE width, the next multiple of ``WIDTH`` that holds
    prompt and answer (never past the model's ``max_len``), so that all
    ``n`` forwards are one compiled program; the model is causal, so what
    lies behind a position cannot reach it."""
    import jax.numpy as jnp
    toks = [int(t) for t in prompt]
    width = -(-(len(toks) + n) // WIDTH) * WIDTH
    width = min(width, getattr(model, "max_len", width))
    prefill = jit_prefill(model)
    seq = np.zeros((1, width), np.int32)
    for _ in range(n):
        seq[0, :len(toks)] = toks
        logits = prefill(params, jnp.asarray(seq))[0]
        toks.append(int(np.argmax(np.asarray(logits)[0, len(toks) - 1])))
    return toks[len(prompt):]


def drain(srv, *reqs, limit=2000):
    """Drive an unstarted server's scheduler deterministically until the
    requests are done; the number of passes it took."""
    n = 0
    while not all(r.done() for r in reqs):
        srv._tick()
        n += 1
        assert n < limit, "scheduler made no progress"
    return n


def served(req):
    """The request's tokens twice: the future's and the stream's (a
    failed request's stream raises after the tokens that landed)."""
    got = [int(t) for t in req.generated]
    streamed = []
    try:
        for t in req.tokens(timeout=1):
            streamed.append(int(t))
    except Exception as exc:
        assert exc is req._error
    assert streamed == got
    return got
