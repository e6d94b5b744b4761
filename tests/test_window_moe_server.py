"""``test_window_moe.py``, continued (a file of its own so that no file is
the floor of a ``--dist loadfile`` run): the window form through the
server — slots, tenants, chunks, stats, typed errors. Model, sizes and
helpers are that file's, its autouse
``_clean_state`` among them (imported, it is this file's fixture too)."""
import os
import sys

import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

from mxnet_tpu import compile_watch                            # noqa: E402
from mxnet_tpu.base import MXNetError                          # noqa: E402
from mxnet_tpu.serving import (KVCachePool, WindowMoEDecoderLM,  # noqa: E402
                               window_moe)
from serving_common import drain as _drain, jit_prefill        # noqa: E402
from test_window_moe import (CFG, W, _clean_state,             # noqa: E402,F401
                             _model, _reference, _server, _tokens)


# ---------------------------------------------------------------------------
# the server: slots, tenants, stats, typed errors
# ---------------------------------------------------------------------------

def _is_greedy(params, cfg, held, prompt, served, length=96):
    """Whether ``served`` is the reference's greedy stream after
    ``prompt``: one teacher-forced forward over both, padded to a fixed
    length (causal: what follows a position cannot reach it) — position
    ``P - 1 + i`` puts served token ``i`` first."""
    seq = np.zeros((length,), np.int32)
    n = len(prompt) + len(served)
    seq[:n] = np.concatenate([prompt, served])
    rows = _reference(params, seq, cfg, held)[len(prompt) - 1:n - 1]
    return [int(t) for t in rows.argmax(axis=1)] == list(served)


def test_served_streams_are_the_references_greedy_streams():
    """Short and long prompts in one queue over a two-rung ladder, more
    requests than rows: every stream is the reference's greedy stream,
    the program set is one step and one mixed step (no prefill: a ring
    takes a chunk), ``stats()`` counts what rode, and the spans say whose
    chunk a step carried, how many rows of the rings were live and what
    the step counted."""
    from mxnet_tpu import tracing
    compile_watch.enable()
    model, params, cfg = _model()
    prompts = [_tokens(s, n) for s, n in enumerate((5, 40, 9, 33, 60, 3))]
    srv = _server(model, params, name="win")
    assert srv.warmup() == 2
    tracing.enable()
    try:
        reqs = [srv.submit(p, max_new_tokens=12) for p in prompts]
        _drain(srv, *reqs)
        spans = [e for e in tracing.export()["traceEvents"]
                 if e.get("ph") == "X"]
    finally:
        tracing.disable()
        tracing.reset()
    sites = compile_watch.site_stats("decode:win")
    assert sorted(sites) == ["decode:win:step", "decode:win:step:chunk:c16"]
    assert all(site["count"] == 1 for site in sites.values())
    st = srv.stats()
    srv.stop()
    for p, r in zip(prompts, reqs):
        assert _is_greedy(params, cfg, model.held, p,
                          [int(t) for t in r.result()])
    assert st["prefill_programs"] == 0 == st["prefill_steps"]
    assert st["chunk_sizes"] == [16]
    assert st["chunk_tokens"] == sum(len(p) for p in prompts)
    assert st["chunk_steps"] == sum(-(-len(p) // 16) for p in prompts)
    # the window layers hold no pages: the pool's layers are the full
    # ones, the rings are state
    assert st["kv"]["arrays"]["k"][0] == model.cache_layers == 2
    assert st["state"]["arrays"] == {"ring_k": [W, 32], "ring_v": [W, 32]}
    assert st["state"]["rows"] == 4 and st["state"]["writes"] == 6
    assert st["state"]["bytes"] == 2 * 3 * 4 * W * 32 * 4
    assert st["kv"]["token_bytes"] == 2 * 2 * 32 * 4
    counted = st["moe"]
    assert counted["ring_rows_wrapped"] > 0 and counted["ring_bytes"] > 0
    by_name = {}
    for sp in spans:
        by_name.setdefault(sp["name"], []).append(sp.get("args") or {})
    assert "decode.prefill" not in by_name
    ids = {r.request_id: len(p) for p, r in zip(prompts, reqs)}
    fed = {}
    for said in by_name["decode.dispatch"]:
        # a mixed step's span carries all three; the chunk's request is
        # none of the live rows (at most three of four while it is fed)
        assert 0 <= said["state_rows_live"] <= 4
        if "chunk" in said:
            assert 1 <= said["chunk"] <= 16 and said["chunk_of"] in ids
            assert said["state_rows_live"] <= 3
            fed[said["chunk_of"]] = fed.get(said["chunk_of"], 0) \
                + said["chunk"]
    assert fed == ids
    for said in by_name["decode.readback"]:
        live = said["state_rows_live"]
        assert 0 <= said["ring_rows_wrapped"] <= live <= 4
        assert live <= said["global_pages_live"] <= live * 9
        # what the rings' visible keys and values weigh: at most W of
        # them a live row, K and V of 2 x 16 float32, 3 sliding layers —
        # of the rows that DECODE: a chunk's lanes are not in it
        assert bool(live) == bool(said["ring_bytes"])
        assert said["ring_bytes"] <= live * W * 3 * 2 * 32 * 4
        assert said["ring_bytes"] % (3 * 2 * 32 * 4) == 0


class _WholePrompt(WindowMoEDecoderLM):
    """The same model, not declaring that its state takes a chunk: its
    server keeps the whole-prompt prefill (the oracle of the chunks)."""
    chunk_lanes = False


@pytest.mark.parametrize("ladder,chunks", [
    ((16, 64), [16]), ((8, 32, 64), [8]), ((8, 16, 64), [8, 16])],
    ids=["C_twice_W", "C_is_W", "two_sizes"])
def test_served_tokens_through_chunks_are_the_prefill_paths(ladder, chunks):
    """Prompts shorter than, equal to and 3-5 times the window, lengths
    that are and are not multiples of the chunk, more requests than rows,
    over chunks twice the window, of the window, and of two sizes: token
    for token what the SAME model serves through the whole-prompt prefill
    and the step (a server of a model that does not declare
    ``chunk_lanes``), and the reference's greedy stream."""
    sizes = (5, W, 3 * W + 3, 5 * W, 16, 3, 33, 2 * W)
    prompts = [_tokens(40 + s, n) for s, n in enumerate(sizes)]
    served = {}
    for cls in (WindowMoEDecoderLM, _WholePrompt):
        model, params, cfg = _model(cls=cls)
        srv = _server(model, params, seq_ladder=list(ladder), window=3)
        assert srv.stats()["chunk_sizes"] == \
            (chunks if cls is WindowMoEDecoderLM else [])
        reqs = [srv.submit(p, max_new_tokens=14) for p in prompts]
        _drain(srv, *reqs)
        st = srv.stats()
        srv.stop()
        assert st["completed"] == len(prompts)
        if cls is _WholePrompt:
            assert st["prefill_programs"] == len(prompts)
            assert st["chunk_tokens"] == 0
        else:
            assert st["prefill_programs"] == 0
            assert st["chunk_tokens"] == sum(sizes)
        served[cls] = [[int(t) for t in r.result()] for r in reqs]
    assert served[WindowMoEDecoderLM] == served[_WholePrompt]
    for p, got in zip(prompts, served[WindowMoEDecoderLM]):
        assert _is_greedy(params, cfg, model.held, p, got)


def test_a_chunks_request_is_no_live_row_of_its_step():
    """One row decodes; behind it a prompt of 20 rides two chunks (16 and
    4 lanes) and a third request waits for its turn. Every row of the
    rings is set to a sentinel first. In the mixed steps the span says ONE
    row of the rings is live; the fed request's row changes by the chunk's
    writes alone — after the first chunk it is what the model's own
    whole-prompt prefill of 16 positions leaves, after the second the
    slots of positions 16-19 moved on and the other four stayed — and the
    waiting request's row keeps the sentinel in every slot (a dummy lane
    at position 0, were the row live, would have written slot 0)."""
    from mxnet_tpu import tracing
    model, params, _ = _model()
    srv = _server(model, params)
    first = srv.submit(_tokens(1, 5), max_new_tokens=30)
    while not first.generated:
        srv._tick()
    n_pages = len(srv.pool.layout.specs)
    for i in range(n_pages, len(srv.pool.arrays)):
        srv.pool.arrays[i] = jnp.full_like(srv.pool.arrays[i], 7.0)
    fed_prompt = _tokens(2, 20)
    fed = srv.submit(fed_prompt, max_new_tokens=4)
    waiting = srv.submit(_tokens(3, 6), max_new_tokens=4)

    def whole(n):
        padded = np.zeros((1, 64), np.int32)
        padded[0, :n] = fed_prompt[:n]
        return [np.asarray(a[:, 0]) for a in jit_prefill(model)(
            params, padded, jnp.asarray([n]))[3:]]

    tracing.enable()
    try:
        srv._tick()                       # admits ``fed``: chunk of 16
        srv._tick()                       # admits ``waiting``: chunk of 4
        assert fed.slot is not None and waiting.slot is not None
        rings = [np.asarray(a) for a in srv.pool.arrays[n_pages:]]
        said = [e["args"] for e in tracing.export()["traceEvents"]
                if e.get("ph") == "X" and e["name"] == "decode.dispatch"]
    finally:
        tracing.disable()
        tracing.reset()
    assert [(a["chunk"], a["chunk_of"], a["state_rows_live"])
            for a in said] == [(16, fed.request_id, 1),
                               (4, fed.request_id, 1)]
    after_16, after_20 = whole(16), whole(20)
    for ring, a16, a20 in zip(rings, after_16, after_20):
        assert np.abs(ring[:, fed.slot] - a20).max() < 1e-5
        # positions 12-15 lie where the first chunk put them
        assert np.abs(ring[:, fed.slot, 4:] - a16[:, 4:]).max() < 1e-5
        assert (ring[:, waiting.slot] == 7.0).all()
        free = [r for r in range(4)
                if r not in (first.slot, fed.slot, waiting.slot)]
        assert (ring[:, free] == 7.0).all()
    _drain(srv, fed, waiting)
    srv.stop()


def test_a_slots_second_tenant_reads_nothing_of_the_first():
    """One row in the window: a long request wraps its ring many times,
    then a short one takes the same slot — its prefill writes the ring
    whole and its position masks the rest, so its stream is what it is on
    a fresh server, and the reference's."""
    model, params, cfg = _model()
    long_, short = _tokens(1, 60), _tokens(2, 4)
    srv = _server(model, params, window=1)
    first = srv.submit(long_, max_new_tokens=20)
    second = srv.submit(short, max_new_tokens=20)
    _drain(srv, first, second)
    st = srv.stats()["state"]
    assert (st["rows"], st["writes"]) == (1, 2)
    srv.stop()
    fresh = _server(model, params, window=1)
    alone = fresh.submit(short, max_new_tokens=20)
    _drain(fresh, alone)
    fresh.stop()
    got = [int(t) for t in second.result()]
    assert got == [int(t) for t in alone.result()]
    assert _is_greedy(params, cfg, model.held, short, got)
    assert _is_greedy(params, cfg, model.held, long_,
                      [int(t) for t in first.result()])


def test_the_docstrings_ten_lines_serve():
    """The entry point a user copies, as the module's docstring has it."""
    text = window_moe.__doc__.split("::\n", 1)[1]
    lines = [l[4:] for l in text.splitlines() if l.startswith("    ")]
    printed = []
    exec("\n".join(lines), {"print": printed.append})
    assert len(printed) == 1 and len(printed[0]) == 24
    assert all(0 <= t < 96 for t in printed[0])


def test_what_the_state_form_refuses_stays_refused():
    model, params, _ = _model()
    with pytest.raises(MXNetError, match="prefix sharing"):
        _server(model, params, prefix_cache=True)
    pool = KVCachePool(2, arrays=[c[:2] for c in model.cache_arrays],
                       dtype="int8", page_size=8, n_pages=16)
    with pytest.raises(MXNetError):
        _server(model, params, pool=pool, pool_pages=None, page_size=None)


@pytest.mark.parametrize("key,value", [
    ("attention_bias", True), ("tie_word_embeddings", True),
    ("gating", "per-token"), ("decoder_sparse_step", 2),
    ("moe_apply_router_weight_on_input", True),
    ("moe_router_logit_softcapping", 30.0), ("model_type", "llama"),
    ("gating_types", ["per_head", "per_token", "per_head", "per_head",
                      "per_head"]),
    ("mlp_layer_types", ["sparse"] * 5),
    ("layer_types", ["full_attention", "chunked_attention"] * 3),
    ("num_attention_heads_per_layer", [4, 5, 6, 6, 4]),
    ("num_attention_heads", 8),
    ("layer_types", ["full_attention"] * 5),
])
def test_a_config_key_that_is_not_honoured_is_refused(key, value):
    with pytest.raises(MXNetError):
        WindowMoEDecoderLM(**dict(CFG, **{key: value}))


def test_rope_parameters_and_unknown_keys_are_refused_not_ignored():
    rp = CFG["rope_parameters"]
    for bad in (dict(rp, full_attention=dict(rp["full_attention"],
                                             rope_type="linear")),
                dict(rp, sliding_attention=dict(rp["sliding_attention"],
                                                factor=4)),
                {"full_attention": rp["full_attention"]},
                dict(rp, full_attention=dict(rp["full_attention"],
                                             partial_rotary_factor=0.3))):
        with pytest.raises(MXNetError):
            WindowMoEDecoderLM(**dict(CFG, rope_parameters=bad))
    with pytest.raises(TypeError, match="unexpected keyword"):
        WindowMoEDecoderLM(**dict(CFG, q_lora_rank=8))
    # the published values themselves are taken
    WindowMoEDecoderLM(**dict(
        CFG, model_type="laguna", attention_bias=False, gating="per-head",
        tie_word_embeddings=False, decoder_sparse_step=1,
        moe_apply_router_weight_on_input=False,
        moe_router_logit_softcapping=0))
