"""``test_hyper_latent_moe.py``, continued (a file of its own so that no
file is the floor of a ``--dist loadfile`` run): the served stream on
seeded random weights against the one-token programs' greedy stream (the
speculative server's loop is in ``test_hyper_latent_moe_loop.py``).
Model, sizes and helpers are that file's, its autouse
``_clean_state`` among them (imported, it is this file's fixture too)."""
import functools
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

from benchmark.reference import hyper_latent_moe_lm as ref    # noqa: E402
from mxnet_tpu.serving import KVCachePool, kvcache             # noqa: E402
from serving_common import jit_prefill                         # noqa: E402
from test_hyper_latent_moe import (CFG, SIZES, _clean_state,   # noqa: E402,F401
                                   _model, _plain, _prompts, _serve)
from test_latent_moe_serving import router_flips               # noqa: E402


# ---------------------------------------------------------------------------
# the invariant: the served stream is the greedy stream, whatever the
# drafter says
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=None)
def _one_token_step(model):
    """``decode`` a token over the latent layout's own ``attend`` and row
    write, jitted once a model: pool and page table are arguments."""
    @jax.jit
    def step(params, pages, table, tok, pos):
        attend = kvcache.layout_for(model, (pages,)).attend(
            (pages,), table[None], pos)
        logits, new, _ = model.decode(params, tok, pos, attend)
        return logits[0].argmax(), kvcache.write_token_rows(
            pages, table[None], pos, new, model.use_pallas)
    return step


def _one_token_greedy(model, params, prompt, n):
    """The greedy stream of the one-token programs from a whole-prompt
    prefill — the prefill a speculative server runs, then ``decode`` a
    token over the server's own ``attend`` and row write, with no server
    and no drafter: what a speculative stream has to reproduce. The pool
    is as many pages as the 32-rung and ``n`` tokens take, whatever the
    prompt's length: one prefill and one step program a model."""
    S, P = 16, len(prompt)
    n_pages = -(-(32 + n) // S)
    pool = KVCachePool(model.n_layers, arrays=[c[:2] for c in
                                               model.cache_arrays],
                       dtype=model.cache_arrays[0][2], page_size=S,
                       n_pages=n_pages + 1)
    table = np.arange(1, n_pages + 1, dtype=np.int32)
    padded = np.zeros((1, 32), np.int32)
    padded[0, :P] = prompt
    logits, rows = jit_prefill(model)(params, padded)
    pages = kvcache.write_prefill_pages(pool.arrays[0], table, rows[:, 0], P)
    out = [int(np.asarray(logits[0, P - 1]).argmax())]
    step = _one_token_step(model)
    while len(out) < n:
        tok, pages = step(params, pages, table,
                          jnp.asarray(out[-1:], jnp.int32),
                          jnp.asarray([P + len(out) - 1], jnp.int32))
        out.append(int(tok))
    return out


@pytest.mark.parametrize("use_pallas", [False, True], ids=["jnp", "pallas"])
def test_the_served_stream_is_the_greedy_stream_on_random_weights(
        use_pallas, monkeypatch):
    """Seeded random weights accept next to nothing: every step hands
    out one token a row. On the jnp path the speculative stream is the
    one-token programs' from the same prefill, token for token.
    Interpreted kernels fold a step's own rows in float32 where the next
    step reads them back from the pool in bfloat16, so with random
    weights a near-tie may flip: a stream may leave the greedy one only
    at a token the reference holds within a fraction of a deviation of
    its best (a mean gap of 0.02 over the 20 tokens: one flip of 0.4,
    where a wrong token is 2-4 off). The one-token SERVER is held to the
    same stream by the same bound: its prompt rides a step in the
    cached, absorbed form where the prefill runs the published one, so
    its rows differ from the prefill's by a rounding and a tie may flip
    there too — and where a row is further off than the bound, it has to
    be an expert off from a position of its prompt at which the
    reference's own router holds a tie (``router_flips``)."""
    model, params = _model(use_pallas=use_pallas)
    plain = _plain(use_pallas)
    prompts = _prompts(0, SIZES)
    streams, st, reqs = _serve(model, params, prompts, n=20)
    served, st_plain, _ = _serve(plain, params, prompts, n=20)
    assert st["spec"]["tokens_out"] == sum(len(s) - 1 for s in streams)
    assert st["spec"]["drafts_verified"] >= st["spec"]["tokens_out"] \
        - st["spec"]["drafts_accepted"]
    assert st_plain["chunk_steps"] == len(prompts)

    def gaps(prompt, stream):
        return ref.teacher_forced(params, prompt, np.asarray(stream),
                                  np.full((20,), -1), 64, 20, CFG,
                                  model.held)

    flipped = []
    for prompt, stream, mine, req in zip(prompts, streams, served, reqs):
        want = _one_token_greedy(plain, params, prompt, 20)
        assert len(stream) == len(req.drafts) == 20
        if stream != want:
            assert use_pallas, (stream, want)
            for one in (stream, want):
                out = gaps(prompt, one)
                assert out["mean"] < 0.02, out
        if mine == want:
            continue
        out = gaps(prompt, mine)
        if out["mean"] < 0.02:
            continue
        flips = router_flips(monkeypatch, ref.hidden_states, plain, params,
                             CFG, prompt)
        assert flips and out["exact"] >= 18 and out["worst"] < 0.5, \
            (flips, out)
        flipped.append(len(prompt))
    # (one row of the six, on the interpreted kernels' path)
    assert flipped == ([15] if use_pallas else []), flipped
