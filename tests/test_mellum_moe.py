"""``serving.window_moe``'s second published block (``model_type``
``mellum``: one head count, no output gate, no shared expert, no dense
layer) and its SHARDED form — every layer shared by the chips of one
mesh axis: experts by their leading dimension, attention, pages and
rings by key/value head, the head by columns, two all-reduces a layer —
on ``DecodeServer``'s step programs under ``shard_map``, against the
benchmark's plain float32 reference
(``benchmark/reference/mellum_moe_lm.py``: no mesh, the window a mask
over whole sequences, every expert computed for every token) at a small
size with seeded weights, on 4 of the 8 host devices.

Every oracle and every step program here compiles once a (model, mesh)
and is handed its arrays as arguments (``ROADMAP.md`` D0)."""
import functools
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

from benchmark.reference import mellum_moe_lm as ref             # noqa: E402
from mxnet_tpu import compile_watch, fault, telemetry            # noqa: E402
from mxnet_tpu.base import MXNetError                            # noqa: E402
from mxnet_tpu.parallel import moe                               # noqa: E402
from mxnet_tpu.parallel.mesh import create_mesh                  # noqa: E402
from mxnet_tpu.serving import (DecodeServer, KVCachePool,         # noqa: E402
                               ToyDecoderLM, WindowMoEDecoderLM, kvcache,
                               window_moe)
from serving_common import drain                                 # noqa: E402

W = 8                                   # the tiny window
CFG = dict(window_moe.tiny_config("mellum"), dtype="float32")
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
LOGIT_TOLERANCE = 1e-3      # tests/test_window_moe.py says why


@pytest.fixture(autouse=True)
def _clean_state():
    fault.reset()
    telemetry.reset()
    compile_watch.disable()
    yield
    fault.reset()
    telemetry.reset()
    compile_watch.disable()


@functools.lru_cache(maxsize=None)
def _mesh(n):
    return create_mesh({"tp": n}, devices=jax.devices()[:n])


@functools.lru_cache(maxsize=None)
def _model(chips=4, seed=3):
    """``(model, params)``: the tiny model, bound to a mesh of ``chips``
    (0: no mesh), its weights drawn into their shardings — the same
    values whatever the mesh."""
    model = WindowMoEDecoderLM(**CFG)
    if chips:
        model = model.sharded_over(_mesh(chips))
    return model, model.init_params(seed=seed)


def _tokens(seed, n, vocab=96):
    return np.random.default_rng(seed).integers(0, vocab, size=n) \
        .astype(np.int32)


def _pool_for(model, rows, per_row, page_size):
    """A pool (sharded where the model is) with a row of the rings and
    ``per_row`` pages a sequence: ``(arrays, layout, page tables)``."""
    state, layers = kvcache.declared_state(model)
    where = None
    if model.mesh is not None:
        def where(layout):
            return [NamedSharding(model.mesh, spec) for spec in
                    kvcache.shard_specs(model, layout, model.axis)]
    pool = KVCachePool(model.cache_layers,
                       arrays=[c[:2] for c in model.cache_arrays],
                       dtype=model.cache_arrays[0][2], page_size=page_size,
                       n_pages=rows * per_row + 1, state=state,
                       state_layers=layers, state_rows=rows,
                       shardings=where)
    return tuple(pool.arrays), pool.layout, 1 + np.arange(
        rows * per_row, dtype=np.int32).reshape(rows, per_row)


@functools.lru_cache(maxsize=None)
def _mixed_step(model, layout):
    """One MIXED step with the logits of EVERY lane kept — the window's
    rows a token each and one prompt's chunk behind them — as
    ``DecodeServer._state_decode_fn_chunk`` runs it: over a mesh one
    chip's model under ``shard_map``, the logits gathered by columns."""
    chip = model.local() if model.mesh is not None else model

    def step(params, pools, toks, poss, pts, order, n_live, fed, table,
             start, n, slot):
        lay = kvcache.layout_for(chip, pools)
        n_pages, rows, C = len(lay.specs), len(toks), len(fed)
        attend = lay.attend_chunk(pools, pts, poss, table, start)
        state = lay.row_state(pools, order, jnp.arange(rows) < n_live)
        lanes = jnp.arange(C, dtype=jnp.int32)
        logits, *new = chip.decode(
            params, jnp.concatenate([toks, fed]),
            jnp.concatenate([poss, start + lanes]), attend, state,
            head=jnp.arange(rows + C),
            live=jnp.concatenate([state.live, lanes < n]),
            chunk=(slot, start, n))
        held = tuple(new[n_pages:n_pages + len(state.arrays)])
        pages = lay.write_tokens(
            pools, pts, poss, [a[:, :rows] for a in new[:n_pages]],
            chip.use_pallas)
        pages = lay.write_chunk(pages + held, table, start, n,
                                [a[:, rows:] for a in new[:n_pages]])
        return logits, (*pages, *held)

    if model.mesh is None:
        return jax.jit(step)
    pools = tuple(kvcache.shard_specs(model, layout, model.axis))
    return jax.jit(model.on_mesh(
        step, (pools,) + (P(),) * 10, (P(None, model.axis), pools)))


def _served_logits(model, params, seqs, C=16, page_size=8):
    """Logits of every position of ``seqs = [(tokens, n_prompt), ...]``
    from the SERVING path: every prompt fed in chunks of ``C`` lanes
    beside the rows that decode (FIFO, one request's chunk a step), then
    a decode step a token — pages, rings and the mixed step as the
    server's, with the logits kept. ``[(L, V) a sequence]``."""
    rows = len(seqs)
    per_row = -(-max(len(t) for t, _ in seqs) // page_size)
    pools, layout, tables = _pool_for(model, rows, per_row, page_size)
    step = functools.partial(_mixed_step(model, layout), params)
    fed_to = [0] * rows
    done = [n for _t, n in seqs]
    heads, tails = [[] for _ in seqs], [[] for _ in seqs]
    while any(done[r] < len(seqs[r][0]) or fed_to[r] < seqs[r][1]
              for r in range(rows)):
        pending = [r for r in range(rows) if fed_to[r] < seqs[r][1]]
        decoding = [r for r in range(rows) if fed_to[r] == seqs[r][1]
                    and done[r] < len(seqs[r][0])]
        order = decoding + [r for r in range(rows) if r not in decoding]
        toks = np.zeros((rows,), np.int32)
        poss = np.zeros((rows,), np.int32)
        pts = np.zeros_like(tables)
        for i, r in enumerate(decoding):
            toks[i], poss[i], pts[i] = seqs[r][0][done[r]], done[r], \
                tables[r]
        fed = np.zeros((C,), np.int32)
        start = n = 0
        r = pending[0] if pending else order[-1]
        if pending:
            start = fed_to[r]
            n = min(C, seqs[r][1] - start)
            fed[:n] = seqs[r][0][start:start + n]
        logits, pools = step(pools, toks, poss, pts,
                             np.asarray(order, np.int32), len(decoding),
                             fed, tables[r], start, n, r)
        logits = np.asarray(logits)
        for i, d in enumerate(decoding):
            tails[d].append(logits[i])
            done[d] += 1
        if pending:
            heads[r].append(logits[rows:rows + n])
            fed_to[r] += n
    return [np.concatenate(h + ([np.stack(t)] if t else []))
            for h, t in zip(heads, tails)]


def _reference(params, tokens, **over):
    return ref.logits_rows(params, jnp.asarray(tokens), 0, len(tokens),
                           dict(CFG, **over), (0, CFG["num_experts"]))


def _worst(got, want):
    return np.abs(got - want).max(axis=1) / want.std()


# -- the sharded serving path against the reference -----------------------

@pytest.mark.parametrize("chips", [4, 1, 0], ids=["mesh4", "mesh1", "none"])
def test_chunks_then_decode_are_the_reference_at_every_position(chips):
    """Three requests in one window — a prompt shorter than the window,
    one three and one five windows long, each fed in chunks of 16 lanes
    beside the rows that decode, then 20-30 decoded positions through
    pages and rings: EVERY position's logits against the reference's one
    forward over the whole sequence, within 1e-3 deviations. Over a mesh
    of 4 every chip holds one key/value head's pages and rings, two
    experts and 24 columns of the head."""
    model, params = _model(chips)
    seqs = [(_tokens(1, 5 * W + 30), 5 * W), (_tokens(2, 3 * W + 3 + 25),
                                              3 * W + 3),
            (_tokens(3, 5 + 20), 5)]
    for (tokens, _n), got in zip(seqs, _served_logits(model, params, seqs)):
        err = _worst(got, _reference(params, tokens))
        assert len(err) == len(tokens) and err.max() < LOGIT_TOLERANCE, err


@pytest.mark.parametrize("window", [W - 1, W + 1], ids=["short", "long"])
def test_a_window_off_by_one_fails_the_sharded_path(window):
    """The comparison is tight enough to hold the mask: against a
    reference that sees ``W - 1`` or ``W + 1`` keys in a sliding layer
    the mesh of 4's logits are 1e-2 deviations and more off past the
    window (and within 1e-3 before it, where the window hides nothing)."""
    model, params = _model(4)
    tokens = _tokens(7, 5 * W + 20)
    got, = _served_logits(model, params, [(tokens, 5 * W)])
    err = _worst(got, _reference(params, tokens, sliding_window=window))
    assert err[:W - 1].max() < LOGIT_TOLERANCE
    assert err[2 * W:].max() > 1e-2, err


def _serve(chips, prompts, n_new=16, **kw):
    model, params = _model(chips)
    mesh = model.mesh
    srv = DecodeServer(model, params, seq_ladder=[16, 64],
                       max_new_tokens=32, page_size=8, window=4,
                       pool_pages=96, prefix_cache=False, start=False,
                       mesh=mesh, **kw)
    reqs = [srv.submit(p, max_new_tokens=n_new) for p in prompts]
    drain(srv, *reqs)
    st = srv.stats()
    srv.stop()
    return [[int(t) for t in r.result()] for r in reqs], st


@functools.lru_cache(maxsize=None)
def _streams(chips):
    prompts = tuple(tuple(_tokens(s, n)) for s, n in enumerate(
        (5, 40, 9, 33, 60, 3)))
    return _serve(chips, [np.asarray(p, np.int32) for p in prompts])


@pytest.mark.parametrize("chips", [1, 0], ids=["mesh1", "none"])
def test_the_mesh_of_four_serves_the_same_tokens(chips):
    """``DecodeServer`` over a mesh of 4 — submit, chunks, pages, rings,
    the loop that runs a step ahead, the arg-max over the chips' (max,
    index) pairs — against a mesh of 1 and against no mesh: six requests
    in a window of four, token for token."""
    got, st = _streams(4)
    want, _ = _streams(chips)
    assert got == want and all(len(t) == 16 for t in got)
    assert st["mesh"] == 4 and st["prefill_programs"] == 0
    assert st["chunk_steps"] > 0


def test_stats_and_spans_say_one_chips_bytes_and_every_chips_counters():
    """Under a mesh ``stats()["moe"]`` is CHIP 0's (what a reader
    divides chip 0's kernel time by), ``moe_by_chip`` every chip's own:
    the chips' slots add up to every live lane's top-k in every layer,
    never chip 0's four times; the pool's ``token_bytes`` and the
    rings' bytes are one chip's; ``mx:decode.dispatch`` carries ``mesh``
    and ``exchange_bytes`` by the lanes of its step."""
    from mxnet_tpu import tracing
    model, params = _model(4)
    tracing.enable()
    try:
        _got, st = _serve(4, [_tokens(9, 40), _tokens(8, 7)], n_new=8)
        spans = [e for e in tracing.export()["traceEvents"]
                 if e.get("ph") == "X" and e["name"] == "decode.dispatch"]
    finally:
        tracing.disable()
        tracing.reset()
    _none, plain = _serve(0, [_tokens(9, 40), _tokens(8, 7)], n_new=8)
    chips = st["moe_by_chip"]
    assert len(chips) == 4 and chips[0] == st["moe"]
    for name in ("moe_slots", "experts_touched"):
        assert sum(c[name] for c in chips) == plain["moe"][name]
        assert st["moe"][name] < plain["moe"][name]
    # pages and rings: every chip counts its own head's, a quarter
    assert 4 * st["moe"]["ring_bytes"] == plain["moe"]["ring_bytes"]
    assert st["moe"]["global_pages_live"] \
        == plain["moe"]["global_pages_live"]
    assert st["kv"]["shards"] == 4
    assert 4 * st["kv"]["token_bytes"] == plain["kv"]["token_bytes"]
    assert 4 * st["state"]["bytes"] == plain["state"]["bytes"]
    assert "mesh" not in plain and "moe_by_chip" not in plain
    a_layer = 2 * model.n_layers * model.d_model * 4
    for sp in spans:
        said = sp["args"]
        lanes = 4 + (16 if "chunk" in said else 0)
        assert said["mesh"] == 4
        assert said["exchange_bytes"] == lanes * a_layer \
            == model.exchange_bytes(lanes)


def test_the_docstrings_ten_lines_serve_the_sharded_form(monkeypatch):
    """The entry point a user copies, as the module's docstring has it
    (a host of four chips is this process's first four devices)."""
    from mxnet_tpu.parallel import mesh as mesh_mod
    text = window_moe.__doc__.split(".. code-block:: python\n", 1)[1] \
        .split("\nTen lines", 1)[0]
    lines = [l[4:] for l in text.splitlines() if l.startswith("    ")]
    assert 10 <= len(lines) <= 13
    made = mesh_mod.create_mesh
    monkeypatch.setattr(mesh_mod, "create_mesh", lambda axes: made(
        axes, devices=jax.devices()[:4]))
    printed = []
    exec("\n".join(lines), {"print": printed.append})
    assert len(printed) == 1 and len(printed[0]) == 24
    assert all(0 <= t < 96 for t in printed[0])


# -- the pieces -----------------------------------------------------------

def test_the_chips_expert_parts_add_up_to_the_uncut_layer():
    """The guide's share test, now the ``psum`` itself: each chip's
    experts' part for ALL lanes (``expert_ffn`` told ``lo`` from
    ``axis_index``, two experts a chip) — no two alike, none zero — add
    up to the layer over all eight experts, which is the reference's;
    and the model's own ``_ffn`` under the mesh hands every chip that
    sum."""
    model, params = _model(4)
    whole, _ = _model(0)
    chip = model.local()
    x = jnp.asarray(np.random.default_rng(5).normal(size=(24, 32)),
                    jnp.float32)
    names = ("w_gate", "w_up", "w_down")
    stacks = {n: params["l1.experts." + n] for n in names}
    topi, topw = moe.route_softmax_topk(x, params["l1.router_w"], top_k=3)

    def parts(stacks, x, topi, topw):
        return moe.expert_ffn(x, stacks, topi, topw, chip._held())[None]

    lead = {n: P("tp", None, None) for n in names}
    got = np.asarray(jax.jit(jax.shard_map(
        parts, mesh=model.mesh, in_specs=(lead, P(), P(), P()),
        out_specs=P("tp"), check_vma=False))(stacks, x, topi, topw))
    assert got.shape == (4, 24, 32)
    assert all(np.abs(got[r]).max() > 0 for r in range(4))
    assert not np.allclose(got[0], got[1])
    uncut = np.asarray(moe.expert_ffn(x, stacks, topi, topw, (0, 8)))
    np.testing.assert_allclose(got.sum(0), uncut, atol=1e-5)
    want, _ids = ref.moe_layer(x, params, "l1.", CFG, (0, 8))
    np.testing.assert_allclose(got.sum(0), np.asarray(want), atol=1e-4)
    summed = jax.jit(model.on_mesh(
        lambda p, x: chip._ffn(1, x, p)[0][None], (P(),), P("tp")))(
            params, x)
    np.testing.assert_allclose(np.asarray(summed[0]), uncut, atol=1e-5)
    assert all(np.array_equal(np.asarray(summed[0]), np.asarray(summed[r]))
               for r in range(4))
    np.testing.assert_allclose(
        np.asarray(summed[0]), np.asarray(whole._ffn(1, x, params)[0]),
        atol=1e-5)


def test_every_chip_holds_the_same_bits_after_each_all_reduce():
    """The hazard of a router computed whole on every chip: two chips
    that held different bits of ``h`` would route one token differently.
    Every chip's sum of four different float32 parts is bit for bit the
    others', and every chip's router choice at every layer of a whole
    forward pass is the others'."""
    model, params = _model(4)
    chip = model.local()
    parts = jnp.asarray(np.random.default_rng(1).normal(
        size=(4, 64, 32)) * 1e3, jnp.float32)
    sums = np.asarray(jax.jit(jax.shard_map(
        lambda x: chip._sum(x[0])[None], mesh=model.mesh,
        in_specs=(P("tp"),), out_specs=P("tp"), check_vma=False))(parts))
    assert all(np.array_equal(sums[0], sums[r]) for r in range(1, 4))

    def choices(params, tokens):
        routed = []
        logits = chip._forward(params, tokens, jnp.full(
            (1,), tokens.shape[1], jnp.int32), routed)[0]
        return jnp.stack(routed)[None], logits

    tokens = _tokens(4, 48)[None]
    every, logits = jax.jit(model.on_mesh(
        choices, (P(),), (P("tp"), P(None, None, "tp"))))(params, tokens)
    every = np.asarray(every)
    assert every.shape == (4, 4, 48, 3)
    assert all(np.array_equal(every[0], every[r]) for r in range(1, 4))
    # and they are the model's own over the mesh, and the reference's
    assert np.array_equal(every[0], np.asarray(
        jax.jit(model.routing)(params, tokens)))
    err = _worst(np.asarray(logits[0]), _reference(params, tokens[0]))
    assert err.max() < LOGIT_TOLERANCE


def test_weights_are_born_sharded_and_are_the_same_values():
    """``init_params`` over a mesh draws every array INTO its sharding —
    an expert stack by its leading dimension, ``W_q`` / ``W_k`` / ``W_v``
    and the head by columns, ``W_o`` by rows, the embedding, the router
    and the gains whole — and the values are those of one chip's draw."""
    model, params = _model(4)
    _whole, plain = _model(0)
    assert sorted(params) == sorted(plain)
    assert not any(n.endswith(("wg", "w_gate")) and "experts" not in n
                   for n in params)           # no gate, no shared, no dense
    for name, spec in model.param_specs().items():
        assert params[name].sharding.is_equivalent_to(
            NamedSharding(model.mesh, spec), params[name].ndim), name
        assert np.array_equal(np.asarray(params[name]),
                              np.asarray(plain[name])), name
    shard = {n: params[n].addressable_shards[0].data.shape for n in (
        "l0.wq", "l0.wk", "l0.wo", "l0.experts.w_up", "head", "embed",
        "l0.router_w")}
    assert shard == {"l0.wq": (32, 32), "l0.wk": (32, 16),
                     "l0.wo": (32, 32), "l0.experts.w_up": (2, 32, 16),
                     "head": (32, 24), "embed": (96, 32),
                     "l0.router_w": (32, 8)}


def test_the_published_model_is_whole_and_a_chip_holds_a_quarter():
    """The configuration's arithmetic by ``jax.eval_shape`` at the
    published widths: 12.15B parameters, 24.3 GB at 2 bytes (the router
    float32), 6.43 GB a chip over a mesh of 4 by the model's own
    declaration; 5.55 GB of them its 16 experts of 28 layers."""
    import json
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "Mellum2-12B-A2.5B-Instruct.json")) as f:
        cfg = json.load(f)
    kwargs = cfg["model"]["kwargs"]
    assert all(cfg[k] == v for k, v in kwargs.items())
    model = WindowMoEDecoderLM(**kwargs).sharded_over(_mesh(4))
    assert (model.n_layers, model.n_experts, model.vocab, model.held) \
        == (28, 64, 98304, (0, 64))
    assert model.kinds.count("full_attention") == 7 and not model.gated
    shapes = jax.eval_shape(model.init_params, 0)
    count = sum(int(np.prod(a.shape)) for a in shapes.values())
    whole = sum(int(np.prod(a.shape)) * a.dtype.itemsize
                for a in shapes.values())
    assert 12.14e9 < count < 12.16e9 and 24.30e9 < whole < 24.32e9

    def on_chip(name):
        a, spec = shapes[name], model.param_specs()[name]
        split = 4 if any(s is not None for s in spec) else 1
        return int(np.prod(a.shape)) * a.dtype.itemsize // split

    assert 6.42e9 < sum(on_chip(n) for n in shapes) < 6.44e9
    assert 5.54e9 < sum(on_chip(n) for n in shapes
                        if ".experts." in n) < 5.56e9
    chip = model.local()
    assert (chip.n_kv_heads, set(chip.heads)) == (1, {8})
    assert chip.cache_arrays[0][1] == (1, 128)
    assert chip.state_arrays[0][1] == (1024, 128)
    assert model.exchange_bytes(64) == 56 * 64 * 2304 * 4


# -- typed refusals -------------------------------------------------------

@pytest.mark.parametrize("key,value", [
    ("hidden_act", "gelu"), ("use_sliding_window", False),
    ("max_window_layers", 4), ("attention_bias", True),
    ("tie_word_embeddings", True), ("shared_expert_intermediate_size", 16),
    ("num_attention_heads_per_layer", [8, 8, 8, 8]),
    ("mlp_only_layers", [0]), ("moe_routed_scaling_factor", 2.5),
    ("gating_types", ["per_head"] * 4), ("model_type", "other")])
def test_an_unpublished_value_is_refused_with_a_typed_error(key, value):
    """A key whose published value is the only one written is refused at
    any other value, and a key of the other block (a gate, a shared
    expert, a head count a layer, a dense layer, a routed scale) at any
    value: ``MXNetError``, never ignored."""
    with pytest.raises(MXNetError, match=key):
        WindowMoEDecoderLM(**dict(CFG, **{key: value}))


def test_what_no_mesh_can_share_is_refused_when_it_is_asked():
    """An unknown keyword is a ``TypeError``; a key of the ``laguna``
    block given to ``mellum`` by its published name too (``gating``);
    heads, experts or vocabulary that do not divide over the axis, a
    mesh of two axes without a word which, a model with no sharded form
    and a pool that lies elsewhere are ``MXNetError`` when asked."""
    with pytest.raises(TypeError, match="gating"):
        WindowMoEDecoderLM(**dict(CFG, gating="per-head"))
    with pytest.raises(TypeError, match="qk_norm"):
        WindowMoEDecoderLM(**dict(CFG, qk_norm=True))
    model = WindowMoEDecoderLM(**CFG)
    with pytest.raises(MXNetError, match="do not divide"):
        model.sharded_over(_mesh(3))
    two = create_mesh({"a": 2, "b": 2}, devices=jax.devices()[:4])
    with pytest.raises(MXNetError, match="which ONE"):
        model.sharded_over(two)
    assert model.sharded_over(two, "b").shards == 2
    bound = model.sharded_over(_mesh(4))
    assert bound.sharded_over(_mesh(4)) is bound
    with pytest.raises(MXNetError, match="already bound"):
        bound.sharded_over(_mesh(2))
    toy = ToyDecoderLM()
    with pytest.raises(MXNetError, match="sharded_over"):
        DecodeServer(toy, toy.init_params(0), mesh=_mesh(4), start=False)
    pool = KVCachePool(1, arrays=(("kv", (64,)),), dtype="float32",
                       n_pages=4, page_size=8)
    with pytest.raises(MXNetError, match="no mesh splits"):
        kvcache.shard_specs(bound, pool.layout, "tp")
