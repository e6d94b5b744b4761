"""``test_hyper_latent_moe.py``, continued (a file of its own so that no
file is the floor of a ``--dist loadfile`` run): the speculative server's
loop on constructed weights — budget and end token, a step dispatched
ahead, the stats, cancel, preemption and a weight swap in mid-stream.
Model, sizes and helpers are that file's, its autouse
``_clean_state`` among them (imported, it is this file's fixture too)."""
import jax.numpy as jnp
import pytest

from mxnet_tpu import compile_watch
from serving_common import drain as _drain
from test_hyper_latent_moe import (_clean_state,            # noqa: F401
                                   _constructed, _prompts, _server)


@pytest.mark.parametrize("cut", ["max_new_tokens", "eos_id"])
def test_a_second_token_is_cut_by_the_budget_or_the_end_token(cut):
    """Every draft accepted: a step hands out two tokens, and the one
    that passes ``max_new_tokens`` or follows ``eos_id`` is not."""
    model, params, f, _ = _constructed(1.0)
    prompt = _prompts(2, (9,), vocab=64)[0]
    chain = [int(f[prompt[-1]])]
    while len(chain) < 12:
        chain.append(int(f[chain[-1]]))
    srv = _server(model, params)
    if cut == "max_new_tokens":
        # the prefill's token, then pairs: an even budget ends on a first
        req = srv.submit(prompt, max_new_tokens=6)
        want = chain[:6]
    else:
        # the end token is the first of a pair; its second is dropped
        req = srv.submit(prompt, max_new_tokens=12, eos_id=chain[3])
        want = chain[:4]
    _drain(srv, req)
    assert [int(t) for t in req.result()] == want
    st = srv.stats()
    # the cut pair's second token was accepted on the device and never
    # handed out
    assert st["spec"]["tokens_out"] == len(want) - 1
    assert st["spec"]["drafts_accepted"] * 2 > st["spec"]["tokens_out"]
    assert st["tokens_out"] == len(want)
    assert srv.pool.stats()["used"] == 0
    srv.stop()


def test_a_step_dispatched_ahead_takes_its_position_from_the_unread_one():
    """What the host says when it dispatches: a row whose step before
    is unread is ``undecided`` (tokens, draft AND position come from
    that step's output), and ``keys_live`` counts the least its rows
    can attend to. Pages are provisioned for the furthest case."""
    model, params, f, _ = _constructed(0.5)
    srv = _server(model, params, page_size=16)
    said = []
    dispatch = srv._dispatch_step

    def spy(ver, rows, emits, feed, src, pages_live, what, prev):
        held = [len(r.pages) for r in rows]
        known = [len(r.prompt) + len(r.generated) - 1 for r in rows]
        said.append((dict(what), [int(s) for s in src[:len(rows)]],
                     held, known, [r.unread for r in rows]))
        return dispatch(ver, rows, emits, feed, src, pages_live, what, prev)

    srv._dispatch_step = spy
    reqs = [srv.submit(p, max_new_tokens=30)
            for p in _prompts(3, (14, 15), vocab=64)]
    _drain(srv, *reqs)
    assert any(what["undecided"] == 2 for what, *_ in said)
    for what, src, held, known, unread in said:
        assert what["undecided"] == sum(s >= 0 for s in src)
        assert what["keys_live"] == sum(k + u for k, u in zip(known, unread))
        for pages, k, u in zip(held, known, unread):
            # through the furthest position the step can write
            assert pages * 16 > k + 1 + 2 * u
    assert srv.pool.stats()["used"] == 0
    srv.stop()


def test_spec_stats_reconcile_with_the_tokens_clients_received():
    model, params, _, _ = _constructed(0.5)
    compile_watch.enable()
    srv = _server(model, params, seq_ladder=[16, 32], name="xing")
    prompts = _prompts(4, (3, 16, 20, 31, 8, 27), vocab=64)
    reqs = [srv.submit(p, max_new_tokens=n)
            for p, n in zip(prompts, (32, 7, 19, 1, 2, 30))]
    _drain(srv, *reqs)
    got = [r.result() for r in reqs]
    assert [len(t) for t in got] == [32, 7, 19, 1, 2, 30]
    st = srv.stats()
    spec = st["spec"]
    # every token but each request's first came out of a step
    assert spec["tokens_out"] == sum(len(t) - 1 for t in got)
    assert st["tokens_out"] == sum(len(t) for t in got)
    assert spec["positions_run"] == 2 * spec["drafts_verified"]
    # a verified draft hands out its step's first token; an accepted
    # one a second, unless the budget cut it
    assert spec["drafts_verified"] <= spec["tokens_out"] \
        <= spec["drafts_verified"] + spec["drafts_accepted"]
    for r in reqs:
        assert len(r.drafts) == len(r.generated)
        assert r.drafts[0] == -1
    # the model's counters cover the module's expert layer too
    assert st["moe"]["steps"] == st["decode_steps"]
    assert model.n_moe_layers == 3 and model.cache_layers == 4
    assert st["kv"]["token_bytes"] == 4 * model.row_width * 2
    # ONE step program and one prefill a rung, whatever was accepted
    sites = compile_watch.site_stats("decode:xing")
    assert sorted(sites) == ["decode:xing:prefill:s16",
                             "decode:xing:prefill:s32", "decode:xing:step"]
    assert all(s["count"] == 1 for s in sites.values())
    srv.stop()


def test_cancel_preemption_and_a_weight_swap_in_mid_stream():
    model, params, f, _ = _constructed(0.5)
    srv = _server(model, params, pool_pages=8, window=2)
    a, b = (srv.submit(p, max_new_tokens=32)
            for p in _prompts(6, (30, 31), vocab=64))
    for _ in range(6):
        srv._tick()
    a.cancel()
    _drain(srv, a)
    assert a.state == "cancelled" and 0 < len(a.generated) < 32
    # the survivor finishes on the weights it started with, a newcomer
    # on the swapped ones
    other = dict(params, head=jnp.roll(params["head"], 1, axis=1))
    srv.swap_weights(other)
    c = srv.submit(_prompts(6, (12,), vocab=64)[0], max_new_tokens=6)
    _drain(srv, b, c)
    chain = [int(f[b.prompt[-1]])]
    while len(chain) < 32:
        chain.append(int(f[chain[-1]]))
    assert [int(t) for t in b.result()] == chain
    assert int(c.result()[0]) == (int(f[c.prompt[-1]]) + 1) % 64
    assert srv.pool.stats()["used"] == 0
    srv.stop()
    # pool pressure: 7 usable pages hold one long row, not two
    srv = _server(model, params, pool_pages=6, window=2)
    a, b = (srv.submit(p, max_new_tokens=32)
            for p in _prompts(6, (30, 31), vocab=64))
    _drain(srv, a, b)
    assert srv.stats()["preempted"] >= 1
    assert sum(r.state == "done" for r in (a, b)) >= 1
    assert srv.pool.stats()["used"] == 0
    srv.stop()
