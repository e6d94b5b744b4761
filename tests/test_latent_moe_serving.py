"""The latent-attention / routed-expert decoder on ``DecodeServer``:
the model (``serving.latent_moe``), the one-array latent pool
(``serving.kvcache``), the dropless expert layer (``parallel.moe``), the
``ep`` rule (``parallel.sharding_rules``) and the Pallas kernels
(interpret mode), against the benchmark's plain float32 reference
(``benchmark/reference/latent_moe_lm.py``) at a small size with seeded
bf16 weights. The widened decode-model contract leaves ``ToyDecoderLM``'s
programs as they were."""
import functools
import math
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

from benchmark.reference import latent_moe_lm as ref      # noqa: E402
from mxnet_tpu import compile_watch, fault, profiler, telemetry  # noqa: E402
from mxnet_tpu.parallel import moe, sharding_rules        # noqa: E402
from mxnet_tpu.serving import (DecodeServer, KVCachePool,   # noqa: E402
                               ServerOverloadedError, ToyDecoderLM,
                               kvcache)
from mxnet_tpu.serving.latent_moe import (LatentMoEDecoderLM,  # noqa: E402
                                          yarn_inv_freq, yarn_mscale)
from serving_common import drain as _drain, jit_prefill   # noqa: E402

YARN = {"beta_fast": 32, "beta_slow": 1, "factor": 40, "mscale": 1,
        "mscale_all_dim": 1, "original_max_position_embeddings": 4096,
        "type": "yarn"}
# the published block's shape at a test's size: latent rank 128 (whole
# lane tiles, so the Pallas paths tile as at the real 512), 32 experts
# in 4 groups, 2 kept, top 4, one dense layer in front
CFG = dict(vocab_size=256, hidden_size=128, num_hidden_layers=3,
           num_attention_heads=4, q_lora_rank=64, kv_lora_rank=128,
           qk_nope_head_dim=32, qk_rope_head_dim=16, v_head_dim=32,
           intermediate_size=256, moe_intermediate_size=128,
           n_routed_experts=32, n_shared_experts=1, num_experts_per_tok=4,
           n_group=4, topk_group=2, routed_scaling_factor=2.5,
           first_k_dense_replace=1, rope_theta=10000, rope_scaling=YARN,
           rms_norm_eps=1e-6, max_position_embeddings=512)


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
def _model(ep=(0, 2), use_pallas=False, seed=3):
    model = LatentMoEDecoderLM(**CFG, ep=ep, use_pallas=use_pallas)
    return model, model.init_params(seed=seed)


def _cached_logits(model, params, tokens, n_prompt, page_size=16):
    """Logits of positions ``n_prompt - 1 ..`` from the SERVING path:
    one prefill over the prompt written into a paged latent pool, then
    one decode step a token through the server's own ``attend`` and row
    writes — what ``DecodeServer``'s two programs compute, with the
    logits kept."""
    L = len(tokens)
    rung = -(-n_prompt // page_size) * page_size
    n_pages = -(-L // page_size) + 1
    pool = KVCachePool(model.n_layers, arrays=[c[:2] for c in
                                               model.cache_arrays],
                       dtype=model.cache_arrays[0][2], page_size=page_size,
                       n_pages=n_pages + 1)
    pages = pool.arrays[0]
    table = np.zeros((n_pages,), np.int32)
    table[:] = np.arange(1, n_pages + 1)
    padded = np.zeros((1, rung), np.int32)
    padded[0, :n_prompt] = tokens[:n_prompt]
    logits, rows = jit_prefill(model)(params, padded)
    pages = kvcache.write_prefill_pages(pages, table, rows[:, 0], n_prompt)
    out = [np.asarray(logits[0, n_prompt - 1])]

    @jax.jit
    def step(pages, tok, pos):
        attend = pool.layout.attend((pages,), table[None], pos)
        logits, new, _ = model.decode(params, tok, pos, attend)
        return logits[0], kvcache.write_token_rows(
            pages, table[None], pos, new, model.use_pallas)

    for p in range(n_prompt, L):
        lg, pages = step(pages, jnp.asarray(tokens[p:p + 1]),
                         jnp.asarray([p], jnp.int32))
        out.append(np.asarray(lg))
    return np.stack(out)


# ---------------------------------------------------------------------------
# the model against the reference
# ---------------------------------------------------------------------------

# The program rounds every activation to bf16 in front of a product (8
# bits of mantissa: 2**-9 relative a rounding, a few dozen roundings deep)
# and the reference none: at these widths a position's logits lie within
# 0.03 deviations of the reference's (the worst of a position's logits,
# over seeds and both paths). The router is discrete: where two experts'
# scores are closer than that rounding the choice flips, and the position
# is a whole expert off (0.9 deviations seen) — a flip of a near-tie is
# not an error, so up to one position in twenty may be over. The control
# (the reference with weights and latent rounded to float8_e4m3fn, 3 bits
# of mantissa) is 0.3 deviations and more off at EVERY position. 0.08 is
# three times the program's worst unflipped position and a quarter of the
# control's best.
LOGIT_TOLERANCE = 0.08


def _position_errors(got, want):
    """Per position: the worst logit's distance, in deviations of the
    reference's logits."""
    return np.abs(got - want).max(axis=1) / want.std()


@pytest.mark.parametrize("use_pallas", [False, True], ids=["jnp", "pallas"])
def test_prefill_then_cached_decode_agrees_with_the_reference_on_logits(
        use_pallas):
    model, params = _model(use_pallas=use_pallas)
    tokens = np.random.default_rng(1).integers(
        0, model.vocab, size=56).astype(np.int32)
    n_prompt = 21
    n_rows = len(tokens) - n_prompt + 1
    got = _cached_logits(model, params, tokens, n_prompt)
    want = np.asarray(ref.logits_rows(
        params, jnp.asarray(tokens), n_prompt - 1, n_rows, CFG, model.held))
    err = _position_errors(got, want)
    assert np.percentile(err, 90) < LOGIT_TOLERANCE, err
    assert (err > LOGIT_TOLERANCE).mean() <= 0.05, err
    # tight enough that the next precision down fails it, everywhere
    low = np.asarray(ref.logits_rows(
        params, jnp.asarray(tokens), n_prompt - 1, n_rows, CFG, model.held,
        low=True))
    assert _position_errors(low, want).min() > 2 * LOGIT_TOLERANCE


def test_absorbed_and_published_attention_forms_agree():
    """Decode (absorbed: the query through the key up-projection, the
    weighted latent through the value up-projection) against prefill
    (published: keys and values expanded for every head) at the same
    positions of one sequence."""
    model, params = _model()
    tokens = np.random.default_rng(2).integers(
        0, model.vocab, size=40).astype(np.int32)
    cached = _cached_logits(model, params, tokens, 9)
    padded = np.zeros((1, 48), np.int32)
    padded[0, :40] = tokens
    full = np.asarray(jit_prefill(model)(params, padded)[0][0, 8:40])
    err = _position_errors(cached, full)
    assert np.percentile(err, 90) < 0.05, err
    assert (err > 0.05).mean() <= 0.05, err


@pytest.mark.parametrize("draw", [4, 5])
def test_a_chunks_logits_are_the_one_token_steps(draw):
    """A prompt as ONE chunk of a mixed step against the same prompt a
    token a step — the path every generated token takes, and a prefix
    hit's suffix took before chunks: the same absorbed form over the
    same rounded rows, so the logits of every position agree to a
    quarter of what either lies off the reference (0.025 deviations:
    the products run at other shapes), but for a router's near-tie
    flipped (one position in twenty at the most)."""
    model, params = _model()
    rng = np.random.default_rng(draw)
    S, C, errs = 16, 32, []
    pool = KVCachePool(model.n_layers, arrays=[c[:2] for c in
                                               model.cache_arrays],
                       dtype=model.cache_arrays[0][2], page_size=S,
                       n_pages=4)
    table = jnp.asarray([1, 2], jnp.int32)
    lanes = jnp.arange(C, dtype=jnp.int32)

    @jax.jit
    def step(pages, tok, pos):
        attend = pool.layout.attend((pages,), table[None], pos)
        logits, new, _ = model.decode(params, tok, pos, attend)
        return logits[0], kvcache.write_token_rows(
            pages, table[None], pos, new, model.use_pallas)

    @jax.jit
    def chunk(pages, tokens, n):
        idle = jnp.zeros((1,), jnp.int32)
        attend = pool.layout.attend_chunk(
            (pages,), jnp.zeros((1, 2), jnp.int32), idle, table,
            jnp.int32(0))
        return model.decode(
            params, jnp.concatenate([idle, tokens]),
            jnp.concatenate([idle, lanes]), attend,
            live=jnp.concatenate([jnp.ones((1,), bool), lanes < n]))[0][1:]

    for P in (7, 19, 32):
        prompt = rng.integers(0, model.vocab, size=P).astype(np.int32)
        pages, one = pool.arrays[0], []
        for p in range(P):
            logits, pages = step(pages, jnp.asarray(prompt[p:p + 1]),
                                 jnp.asarray([p], jnp.int32))
            one.append(np.asarray(logits))
        rode = np.asarray(chunk(
            pool.arrays[0], jnp.asarray(np.pad(prompt, (0, C - P))),
            jnp.int32(P)))[:P]
        errs.append(_position_errors(rode, np.stack(one)))
    err = np.concatenate(errs)
    assert np.percentile(err, 90) < 0.025, err
    assert (err > 0.025).mean() <= 0.05, err


def router_flips(monkeypatch, hidden_states, model, params, cfg, prompt,
                 eps=2e-3):
    """``[(expert layer, position), ...]`` of ``prompt`` at which the
    router of the path a prompt's chunk runs (the cached, absorbed form
    over a mixed step's lanes: ``attend_chunk``, ``live``) leaves the
    choice of the reference's (``hidden_states``, a module's of
    ``benchmark/reference``: both go through ``latent_moe_lm.moe_layer``)
    — each one asserted a near-tie OF THE REFERENCE'S: its own scores at
    that position, moved by ``eps`` (a bfloat16 rounding of a score of
    0.5..1) toward the experts the served path chose, choose them. A
    flip of such a tie is no error, and it is the only thing that may
    leave a row an expert off."""
    seen = []
    real = ref.moe_layer

    def spy(x, params, prefix, *args, **kw):
        out, ids = real(x, params, prefix, *args, **kw)
        seen.append((prefix, x, np.asarray(ids)))
        return out, ids

    P, C = len(prompt), 32
    seq = np.zeros((64,), np.int32)
    seq[:P] = prompt
    monkeypatch.setattr(ref, "moe_layer", spy)
    hidden_states(params, jnp.asarray(seq), cfg, model.held)
    monkeypatch.undo()
    # the prompt as ONE chunk from an empty pool, beside one idle row
    pool = KVCachePool(model.n_layers, arrays=[c[:2] for c in
                                               model.cache_arrays],
                       dtype=model.cache_arrays[0][2], page_size=16,
                       n_pages=4)
    lanes = np.arange(C)
    tokens = np.concatenate([[0], seq[:C]]).astype(np.int32)

    @jax.jit
    def served(params):
        attend = pool.layout.attend_chunk(
            pool.arrays, jnp.zeros((1, 2), jnp.int32),
            jnp.zeros((1,), jnp.int32), jnp.asarray([1, 2], jnp.int32),
            jnp.int32(0))
        positions = jnp.concatenate([jnp.zeros((1,), jnp.int32), lanes])
        X = model._streams(params["embed"][tokens].astype(jnp.float32))
        attention = model._absorbed(params, positions, attend)
        routed = []
        for i in range(model.n_layers):
            X, _row, _load = model._block(
                i, X, params, attention, routed,
                jnp.concatenate([jnp.ones((1,), bool), lanes < P]))
        return jnp.stack(routed)[:, 1:1 + P]

    flips = []
    how = dict(n_group=cfg["n_group"], topk_group=cfg["topk_group"],
               top_k=cfg["num_experts_per_tok"],
               scaling=float(cfg["routed_scaling_factor"]))
    for layer, (mine, (prefix, x, theirs)) in enumerate(
            zip(np.asarray(served(params)), seen)):
        for p in range(P):
            got, want = set(mine[p].tolist()), set(theirs[p].tolist())
            if got == want:
                continue
            toward = np.zeros((cfg["n_routed_experts"],), np.float32)
            toward[sorted(got - want)] = eps
            toward[sorted(want - got)] = -eps
            moved, _w = ref.route(
                x[p:p + 1], params[prefix + "router_w"],
                params[prefix + "router_b"] + toward, **how)
            assert set(np.asarray(moved)[0].tolist()) == got, \
                (prefix, p, sorted(got), sorted(want))
            flips.append((layer, p))
    return flips


def test_served_tokens_are_the_references_own_or_near_ties(monkeypatch):
    model, params = _model()
    srv = DecodeServer(model, params, seq_ladder=[32], max_new_tokens=24,
                       page_size=16, window=4, pool_pages=32, start=False)
    rng = np.random.default_rng(4)
    prompts = [rng.integers(0, model.vocab, size=n).astype(np.int32)
               for n in (7, 19, 32)]
    reqs = [srv.submit(p, max_new_tokens=24) for p in prompts]
    _drain(srv, *reqs)
    flipped = []
    for prompt, req in zip(prompts, reqs):
        out = ref.teacher_forced(params, prompt, req.result(), 64, 24, CFG,
                                 model.held)
        # a served token is the reference's own, or lies a rounding
        # under it: the mean gap is a hundredth of a deviation at most
        if out["mean"] < 0.01 and out["exact"] >= 20:
            continue
        # ... or the row is an expert off, from a position of its
        # prompt at which the reference's own router holds a tie that
        # the served path resolved the other way (shown per position):
        # its tokens are the reference's own as often, none as far off
        # as a wrong token lies (2-4 deviations)
        flips = router_flips(monkeypatch, ref.hidden_states, model, params,
                             CFG, prompt)
        assert flips and out["exact"] >= 20 and out["worst"] < 0.5, \
            (flips, out)
        flipped.append((len(prompt), flips))
    # (this draw holds ONE such tie: the seven-token prompt's last
    # position, where two groups of experts lie 0.0011 apart)
    assert flipped == [(7, [(0, 6)])], flipped
    st = srv.stats()
    assert st["kv"]["arrays"] == {"kv": [model.row_width]}
    assert st["kv"]["token_bytes"] == model.n_layers * model.row_width * 2
    assert st["kv"]["dtype"] == "bfloat16"
    moe_st = st["moe"]
    assert moe_st["steps"] == st["decode_steps"] > 0
    # 4 rows, and a chunk's live lanes, x top 4 x 2 expert layers; a
    # held share of 16 of 32
    assert 0 < moe_st["moe_slots"] \
        <= (moe_st["steps"] * 4 + st["chunk_tokens"]) * 4 * 2
    assert 0 < moe_st["experts_touched"] <= moe_st["steps"] * 16 * 2
    # (a step's 4 rows, and the 32 lanes of a chunk it may carry)
    assert 1 <= moe_st["max_load"] <= 4 + st["chunk"]
    assert set(moe_st["last"]) == {"moe_slots", "experts_touched",
                                   "max_load"}
    srv.stop()


# ---------------------------------------------------------------------------
# the router, YaRN, the shares
# ---------------------------------------------------------------------------

def _route_both(x, w, b, **kw):
    mine = moe.route_grouped_sigmoid(x, w, b, **kw)
    theirs = ref.route(x, w, b, **kw)
    return [np.asarray(a) for a in (*mine, *theirs)]


def test_router_against_the_reference_groups_scaling_and_ties():
    kw = dict(n_group=4, topk_group=2, top_k=4, scaling=2.5)
    keys = jax.random.split(jax.random.PRNGKey(0), 3)
    x = jax.random.normal(keys[0], (64, 32))
    w = jax.random.normal(keys[1], (32, 32)) * 0.3
    b = jax.random.normal(keys[2], (32,)) * 0.05
    ti, tw, ri, rw = _route_both(x, w, b, **kw)
    assert (np.sort(ti, -1) == np.sort(ri, -1)).all()
    np.testing.assert_allclose(np.sort(tw, -1), np.sort(rw, -1), rtol=1e-6)
    # the weights are the chosen scores over their sum, times the factor
    np.testing.assert_allclose(tw.sum(-1), 2.5, rtol=1e-6)
    # every chosen expert lies in one of 2 groups of 8
    assert all(len(set(row // 8)) <= 2 for row in ti)
    # the bias moves the CHOICE, never the weights
    s = np.asarray(jax.nn.sigmoid(x @ w))
    np.testing.assert_allclose(
        tw, 2.5 * np.take_along_axis(s, ti, 1)
        / np.take_along_axis(s, ti, 1).sum(-1, keepdims=True), rtol=1e-5)
    pushed, _, _, _ = _route_both(x, w, b.at[31].set(10.0), **kw)
    assert (pushed == 31).any(axis=1).all()
    # all scores tied: the lower index wins, groups 0 and 1, experts 0-3
    ti, tw, ri, rw = _route_both(x, jnp.zeros((32, 32)), jnp.zeros(32), **kw)
    assert (ti == np.arange(4)).all() and (ri == np.arange(4)).all()
    np.testing.assert_allclose(tw, 2.5 / 4, rtol=1e-6)


def _route_by_three_top_k(x, w_gate, bias, *, n_group, topk_group, top_k,
                          scaling=1.0):
    """``route_grouped_sigmoid`` as it was until PR 51 — the choice by
    three ``jax.lax.top_k`` (full sorts on the TPU) — kept as the oracle
    of the one that chooses by reductions; ``grouped``, ``group_score``
    and ``masked`` come back too, for a case to show the ties it holds."""
    T = x.shape[0]
    E = w_gate.shape[-1]
    with jax.default_matmul_precision("highest"):
        s = jax.nn.sigmoid(jnp.dot(x.astype(jnp.float32),
                                   w_gate.astype(jnp.float32)))
    choice = s + bias.astype(jnp.float32)
    grouped = choice.reshape(T, n_group, E // n_group)
    group_score = jax.lax.top_k(grouped, 2)[0].sum(-1)   # (T, G)
    _, keep = jax.lax.top_k(group_score, topk_group)
    group_mask = jnp.zeros((T, n_group), bool).at[
        jnp.arange(T)[:, None], keep].set(True)
    masked = jnp.where(group_mask[:, :, None], grouped,
                       0.0).reshape(T, E)
    _, topi = jax.lax.top_k(masked, top_k)
    topw = jnp.take_along_axis(s, topi, axis=1)
    topw = topw / (topw.sum(-1, keepdims=True) + 1e-20) * scaling
    return topi.astype(jnp.int32), topw, grouped, group_score, masked


def _primitives(jaxpr):
    """The names of a jaxpr's primitives, those of inner jaxprs too (the
    jaxpr's TEXT will not do: a gather's ``indices_are_sorted`` is in
    it)."""
    found = set()
    for eqn in jaxpr.eqns:
        found.add(eqn.primitive.name)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            found |= _primitives(sub)
    return found


def _router_case(scores, T, E):
    """``(x, w_gate, bias)`` of one kind of scores. ``random``: a normal
    gate. ``tied``: every score 0.5. ``boundary``: logits on a grid of
    1/4 through an identity gate (exact at "highest") under a bias of
    three levels — a group's two best equal, two groups' sums equal at
    the last group kept, equal scores at the last expert chosen, in one
    group and across two. ``zeros``: that, under a bias so negative that
    the 0.0 of a dropped group beats every score that stayed."""
    keys = jax.random.split(jax.random.PRNGKey(T + E), 3)
    if scores == "random":
        return (jax.random.normal(keys[0], (T, 48)),
                jax.random.normal(keys[1], (48, E)) * 0.3,
                jax.random.normal(keys[2], (E,)) * 0.05)
    if scores == "tied":
        return (jax.random.normal(keys[0], (T, 48)), jnp.zeros((48, E)),
                jnp.zeros(E))
    x = jnp.round(jax.random.normal(keys[0], (T, E)) * 4) / 4
    b = jax.random.randint(keys[1], (E,), -1, 2) / 16.0
    return (x, jnp.eye(E, dtype=jnp.float32),
            b - 2.0 if scores == "zeros" else b)


# the served shapes: ling's step and its mixed step, dots' mixed step,
# xing's verify positions (one group: the group stage is not traced)
@pytest.mark.parametrize("scores", ["random", "tied", "boundary", "zeros"])
@pytest.mark.parametrize("T,E,n_group,topk_group,top_k", [
    (64, 512, 8, 4, 8), (1088, 512, 8, 4, 8), (320, 256, 8, 4, 8),
    (128, 64, 1, 1, 4)])
def test_the_router_chooses_by_reductions_what_three_top_k_chose(
        T, E, n_group, topk_group, top_k, scores):
    """``topi`` element for element in order and ``topw`` bit for bit,
    and no ``sort`` or ``top_k`` in the traced router."""
    kw = dict(n_group=n_group, topk_group=topk_group, top_k=top_k,
              scaling=2.5)
    x, w, b = _router_case(scores, T, E)
    ri, rw, grouped, group_score, masked = (
        np.asarray(a) for a in jax.jit(functools.partial(
            _route_by_three_top_k, **kw))(x, w, b))
    route = functools.partial(moe.route_grouped_sigmoid, **kw)
    ti, tw = (np.asarray(a) for a in jax.jit(route)(x, w, b))
    assert ti.dtype == ri.dtype == np.int32
    assert tw.dtype == rw.dtype == np.float32
    assert np.array_equal(ti, ri)
    assert np.array_equal(tw.view(np.uint32), rw.view(np.uint32))
    # the case holds what it is there for
    drops = n_group != topk_group
    best = -np.sort(-masked, axis=-1)
    if scores == "tied":
        assert (masked == 0.5).sum(-1).min() >= top_k
        assert (ti == np.arange(top_k)).all()
    if scores == "boundary":
        at_k = best[:, top_k - 1] == best[:, top_k]
        assert at_k.any()
        if drops:
            two = -np.sort(-grouped, axis=-1)[..., :2]
            assert (two[..., 0] == two[..., 1]).any()
            rank = -np.sort(-group_score, axis=-1)
            assert (rank[:, topk_group - 1] == rank[:, topk_group]).any()
            # the k-th score is held in two groups of some row
            holds = (masked == best[:, top_k - 1:top_k]).reshape(
                T, n_group, -1).any(-1)
            assert (holds.sum(-1)[at_k] > 1).any()
    if scores == "zeros":
        assert (best[:, 0] <= 0.0).all()
        if drops:
            assert (np.take_along_axis(masked, ti, 1) == 0.0).all()
            assert (masked < 0).any(axis=1).all()
    found = _primitives(jax.make_jaxpr(route)(x, w, b).jaxpr)
    assert "argmax" in found
    assert not {p for p in found if "sort" in p or "top_k" in p}, found


def test_yarn_frequencies_and_scale_against_hand_values():
    f = yarn_inv_freq(64, 10000.0, 40.0, 4096, 32.0, 1.0)
    assert f.shape == (32,)
    # correction range: 64 ln(4096 / (32 * 2 pi)) / (2 ln 1e4) = 10.47,
    # 64 ln(4096 / (2 pi)) / (2 ln 1e4) = 22.51 -> dims 0..10 keep their
    # frequency, 23..31 are slowed 40 times, a ramp of 13 steps between
    hand = 10000.0 ** (-np.arange(0, 64, 2) / 64.0)
    np.testing.assert_allclose(f[:11], hand[:11], rtol=1e-6)
    np.testing.assert_allclose(f[23:], hand[23:] / 40.0, rtol=1e-6)
    ramp = (16 - 10) / 13.0
    np.testing.assert_allclose(
        f[16], hand[16] * (1 - ramp) + hand[16] / 40.0 * ramp, rtol=1e-6)
    np.testing.assert_allclose(f, ref.inv_freq(dict(CFG, qk_rope_head_dim=64)),
                               rtol=1e-6)
    assert abs(yarn_mscale(40, 1) - (0.1 * math.log(40) + 1)) < 1e-12
    assert yarn_mscale(1, 1) == 1.0
    model = LatentMoEDecoderLM(**dict(CFG, qk_nope_head_dim=128,
                                      qk_rope_head_dim=64))
    assert abs(model.scale - 192 ** -0.5 * 1.3688879 ** 2) < 1e-6
    assert abs(model.scale - ref.score_scale(dict(
        CFG, qk_nope_head_dim=128, qk_rope_head_dim=64))) < 1e-9
    assert model.rope_gain == 1.0


def test_the_sixteen_shares_add_up_to_the_uncut_layer():
    """Expert parallelism's contract at a small size: the 16 shares'
    routed parts, and the shared expert counted ONCE, add up to what
    the uncut reference gives for the whole layer."""
    model, params = _model(ep=(0, 1))
    assert model.held == (0, 32)
    x = jax.random.normal(jax.random.PRNGKey(5), (24, CFG["hidden_size"]))
    whole, _ = ref.moe_layer(x, params, "l1.", CFG, (0, 32))
    topi, topw = moe.route_grouped_sigmoid(
        x, params["l1.router_w"], params["l1.router_b"], n_group=4,
        topk_group=2, top_k=4, scaling=2.5)
    total = model._gated(x, params, "l1.shared.")
    for rank in range(16):
        lo, hi = sharding_rules.held_experts(32, 16, rank)
        share = {n: params["l1.experts." + n][lo:hi]
                 for n in ("w_gate", "w_up", "w_down")}
        total = total + moe.expert_ffn(x, share, topi, topw, (lo, hi))
        assert int(moe.expert_load(topi, (lo, hi)).sum()) \
            == int(((topi >= lo) & (topi < hi)).sum())
    assert np.abs(np.asarray(total - whole)).max() \
        / np.asarray(whole).std() < 0.03
    # one share alone is NOT the layer
    one = model._gated(x, params, "l1.shared.") + moe.expert_ffn(
        x, {n: params["l1.experts." + n][:2]
            for n in ("w_gate", "w_up", "w_down")}, topi, topw, (0, 2))
    assert np.abs(np.asarray(one - whole)).max() \
        / np.asarray(whole).std() > 0.3


def test_ep_rule_and_held_experts():
    from jax.sharding import PartitionSpec as P
    layout = sharding_rules.SpecLayout(ep_axis="ep")
    spec = sharding_rules.parameter_spec_from_name
    assert spec("l3.experts.w_gate", (16, 8, 4), layout) == P("ep")
    # no live ep axis (a mesh of one): the stack stays whole
    assert spec("l3.experts.w_gate", (16, 8, 4)) == P()
    assert spec("l3.shared.w_gate", (8, 4), layout) != P("ep")
    assert sharding_rules.held_experts(256, 16, 0) == (0, 16)
    assert sharding_rules.held_experts(256, 16, 15) == (240, 256)
    assert sharding_rules.held_experts(16) == (0, 16)
    with pytest.raises(ValueError):
        sharding_rules.held_experts(10, 4, 0)
    mesh = jax.sharding.Mesh(np.array(jax.devices()[:4]).reshape(2, 2),
                             ("dp", "ep"))
    rules = sharding_rules.ShardingRules(mesh)
    assert rules.layout.ep_axis == "ep"
    plan = rules.plan("l1.experts.w_down", (32, 128, 128))
    assert plan.spec == P("ep", None, None)
    one = sharding_rules.ShardingRules(jax.sharding.Mesh(
        np.array(jax.devices()[:1]), ("ep",)))
    assert one.plan("l1.experts.w_down", (32, 128, 128)).spec \
        == P(None, None, None)


# ---------------------------------------------------------------------------
# the latent pool: page accounting unchanged
# ---------------------------------------------------------------------------

def test_latent_pool_layout_and_bytes():
    pool = KVCachePool(6, arrays=(("kv", (640,)),), dtype="bfloat16",
                       page_size=128, n_pages=4)
    assert [a.shape for a in pool.arrays] == [(6, 4, 128, 640)]
    assert pool.arrays[0].dtype == jnp.bfloat16
    assert pool.token_bytes == 6 * 640 * 2
    kv = KVCachePool(2, 2, 8, page_size=8, n_pages=4)
    assert kv.array_specs == (("k", (2, 8)), ("v", (2, 8)))
    assert kv.k.shape == kv.v.shape == (2, 4, 8, 2, 8)
    assert kv.token_bytes == 2 * 2 * 2 * 8 * 4
    with pytest.raises(Exception):
        KVCachePool(2, arrays=(("kv", (64,)),), dtype="int8",
                    page_size=8, n_pages=4)


def test_prefill_writes_do_not_widen_a_bf16_pool():
    """The latent pool's prefill writes are in-place page writes: no
    scatter (XLA's TPU scatter widens a 16-bit pool to float32, whole),
    and no float32 copy of the pool anywhere in the program."""
    pages = jnp.zeros((2, 5, 16, 128), jnp.bfloat16)
    seq = jax.random.normal(jax.random.PRNGKey(0), (2, 32, 128))
    table = jnp.asarray([3, 1, 0, 0], jnp.int32)
    fn = jax.jit(kvcache.write_prefill_pages)
    text = fn.lower(pages, table, seq, 20).as_text()
    assert "scatter" not in text and "dynamic_update_slice" in text
    assert "tensor<2x5x16x128xf32>" not in text
    out = np.asarray(fn(pages, table, seq, 20).astype(jnp.float32))
    want = np.asarray(seq.astype(jnp.bfloat16).astype(jnp.float32))
    assert (out[:, 3] == want[:, :16]).all()
    assert (out[:, 1, :4] == want[:, 16:20]).all()
    assert (out[:, [2, 4]] == 0).all()
    # a page wholly past the prompt goes to the dump page
    out = np.asarray(fn(pages, table, seq, 16).astype(jnp.float32))
    assert (out[:, 1] == 0).all() and (out[:, 0] == want[:, 16:]).all()


@pytest.mark.parametrize("use_pallas", [False, True], ids=["jnp", "pallas"])
def test_prefix_lookup_and_copy_on_write_on_a_latent_pool(use_pallas):
    model, params = _model(use_pallas=use_pallas)
    rng = np.random.default_rng(6)
    system = rng.integers(0, model.vocab, size=32).astype(np.int32)
    prompts = [np.concatenate([system, rng.integers(
        0, model.vocab, size=n).astype(np.int32)]) for n in (5, 9)]
    prompts.append(system.copy())      # fully cached, page-aligned: COW

    def run(prefix_cache):
        srv = DecodeServer(model, params, seq_ladder=[48],
                           max_new_tokens=10, page_size=16, window=2,
                           pool_pages=24, prefix_cache=prefix_cache,
                           start=False)
        outs = []
        for p in prompts:               # one after the other: the first
            r = srv.submit(p, max_new_tokens=10)   # fills the index
            _drain(srv, r)
            outs.append(r.result().tolist())
        st = srv.stats()
        srv.stop()
        return outs, st

    shared, st = run(True)
    private, _ = run(False)
    # a hit feeds the un-cached suffix through the decode step (the
    # absorbed form), a miss through prefill (the published form): the
    # same model under another rounding, so the two streams are each
    # the reference's own up to near-ties, not token-identical as
    # ToyDecoderLM's bit-matched float32 paths are
    for prompt, a, b in zip(prompts, shared, private):
        for served in (a, b):
            out = ref.teacher_forced(params, prompt, np.asarray(served),
                                     64, 10, CFG, model.held)
            # the reference's own token at 8 of 10 positions at the
            # least: a router's near-tie that flips costs one
            assert out["exact"] >= 8, out
        assert a[0] == b[0]
    assert st["prefix"]["hits"] == 2 and st["prefix"]["hit_tokens"] == 64
    assert st["prefix"]["cow_splits"] >= 1
    assert st["prefix"]["bytes_saved"] == 64 * st["kv"]["token_bytes"]


def test_pool_pressure_preempts_on_a_latent_pool():
    model, params = _model()
    srv = DecodeServer(model, params, seq_ladder=[16], max_new_tokens=40,
                       page_size=16, window=3, pool_pages=6, start=False)
    rng = np.random.default_rng(8)
    low = [srv.submit(rng.integers(0, model.vocab, size=12).astype(np.int32),
                      max_new_tokens=40, priority=0) for _ in range(2)]
    high = srv.submit(rng.integers(0, model.vocab, size=12).astype(np.int32),
                      max_new_tokens=40, priority=1)
    _drain(srv, *low, high)
    assert len(high.result()) == 40
    failed = [r for r in low if r._error is not None]
    assert failed and all(isinstance(r._error, ServerOverloadedError)
                          for r in failed)
    st = srv.stats()
    assert st["preempted"] == len(failed)
    assert st["kv"]["used"] == 0 and st["kv"]["free"] == 5
    srv.stop()


# ---------------------------------------------------------------------------
# the widened contract leaves ToyDecoderLM where it was
# ---------------------------------------------------------------------------

def _old_decode_fn(model, params, tokens, positions, page_tables, k_pages,
                   v_pages):
    """``DecodeServer._decode_fn`` as it stood before the contract was
    widened (PR 24), kept here as the oracle."""
    attend = functools.partial(kvcache.paged_attention, k_pages, v_pages,
                               page_tables, positions)
    logits, k_new, v_new = model.decode(params, tokens, positions, attend)
    k_pages = kvcache.scatter_token(k_pages, page_tables, positions, k_new)
    v_pages = kvcache.scatter_token(v_pages, page_tables, positions, v_new)
    return jnp.argmax(logits, axis=-1).astype(jnp.int32), k_pages, v_pages


def _old_prefill_fn(model, params, tokens, n_valid, page_table, k_pages,
                    v_pages):
    logits, k_seq, v_seq = model.prefill(params, tokens)
    k_pages = kvcache.scatter_prefill(k_pages, page_table, k_seq[:, 0],
                                      n_valid)
    v_pages = kvcache.scatter_prefill(v_pages, page_table, v_seq[:, 0],
                                      n_valid)
    last = jnp.take(logits[0], n_valid - 1, axis=0)
    return jnp.argmax(last).astype(jnp.int32), k_pages, v_pages


def test_toy_decoder_runs_the_programs_it_ran():
    model = ToyDecoderLM(vocab=32, n_layers=2, n_heads=2, head_dim=8,
                         max_len=128)
    assert model.cache_arrays == (("k", (2, 8)), ("v", (2, 8)))
    params = model.init_params(seed=3)
    holder = type("S", (), {"_model": model,
                            "_step_fn": DecodeServer._step_fn})()
    pool = jnp.zeros((2, 24, 8, 2, 8), jnp.float32)
    step_args = (params, jnp.zeros((3,), jnp.int32),
                 jnp.zeros((3,), jnp.int32), jnp.zeros((3, 6), jnp.int32),
                 pool, pool)
    # the step's body; the program around it (``_decode_fn``, PR 30)
    # only picks each row's input token: the step before's, where it
    # lies on the device, or the host's
    new = jax.make_jaxpr(functools.partial(DecodeServer._step_fn,
                                           holder))(*step_args)
    old = jax.make_jaxpr(functools.partial(_old_decode_fn,
                                           model))(*step_args)
    assert str(new) == str(old)
    prev = jnp.asarray([7, 8, 9], jnp.int32)
    fed = DecodeServer._decode_fn(
        holder, params, jnp.asarray([1, 2, 3], jnp.int32), *step_args[2:4],
        prev, jnp.asarray([2, -1, 0], jnp.int32), pool, pool)
    same = DecodeServer._step_fn(
        holder, params, jnp.asarray([9, 2, 7], jnp.int32), *step_args[2:])
    assert all(bool((a == b).all()) for a, b in zip(fed, same))
    pre_args = (params, jnp.zeros((1, 16), jnp.int32), jnp.int32(5),
                jnp.zeros((6,), jnp.int32), pool, pool)
    new = jax.make_jaxpr(functools.partial(DecodeServer._prefill_fn,
                                           holder))(*pre_args)
    old = jax.make_jaxpr(functools.partial(_old_prefill_fn,
                                           model))(*pre_args)
    assert str(new) == str(old)


# tokens the parent commit (a0dcd2b) served for this model, these prompts
GOLDEN = [[19, 25, 19, 25, 19, 25, 19, 25, 1, 12, 11, 12],
          [29, 14, 4, 19, 25, 19, 25, 19, 25, 19, 25, 19],
          [7, 5, 12, 11, 12, 11, 12, 21, 6, 19, 22, 29]]


@pytest.mark.parametrize("use_pallas", [False, True], ids=["jnp", "pallas"])
def test_toy_decoder_same_program_set_same_tokens(use_pallas):
    compile_watch.enable()
    model = ToyDecoderLM(vocab=32, n_layers=2, n_heads=2, head_dim=8,
                         max_len=128, use_pallas=use_pallas)
    name = "gold%d" % use_pallas
    srv = DecodeServer(model, model.init_params(seed=3),
                       seq_ladder=[16, 32], max_new_tokens=12, page_size=8,
                       window=3, pool_pages=24, name=name, start=False)
    rng = np.random.default_rng(7)
    reqs = [srv.submit(rng.integers(0, 32, size=n).astype(np.int32),
                       max_new_tokens=12) for n in (5, 17, 30)]
    _drain(srv, *reqs)
    assert [r.result().tolist() for r in reqs] == GOLDEN
    sites = compile_watch.site_stats("decode:" + name)
    # (the tokens the prefill programs served; the prompts now ride the
    # step in chunks, and no prefill rung is built)
    assert sorted(sites) == ["decode:%s:step" % name,
                             "decode:%s:step:chunk:c16" % name,
                             "decode:%s:step:chunk:c32" % name]
    assert all(s["count"] == 1 for s in sites.values())
    assert "moe" not in srv.stats()
    srv.stop()


def test_latent_model_fixed_program_set():
    compile_watch.enable()
    model, params = _model()
    srv = DecodeServer(model, params, seq_ladder=[16, 32],
                       max_new_tokens=8, page_size=16, window=2,
                       pool_pages=16, name="lat", start=False)
    rng = np.random.default_rng(9)
    reqs = [srv.submit(rng.integers(0, model.vocab, size=n).astype(np.int32),
                       max_new_tokens=8) for n in (3, 16, 20, 31)]
    _drain(srv, *reqs)
    sites = compile_watch.site_stats("decode:lat")
    assert sorted(sites) == ["decode:lat:step", "decode:lat:step:chunk:c16",
                             "decode:lat:step:chunk:c32"]
    assert all(s["count"] == 1 for s in sites.values())
    srv.stop()


def test_contract_errors_name_the_widened_contract():
    class NoCache:
        n_layers = 1

        def prefill(self, *a):
            pass

        def decode(self, *a):
            pass

    with pytest.raises(Exception, match="cache_arrays"):
        DecodeServer(NoCache(), {}, start=False)
    with pytest.raises(Exception, match="cache_arrays"):
        DecodeServer(object(), {}, start=False)
    model, params = _model()
    pool = KVCachePool(3, 2, 8, page_size=16, n_pages=8)
    with pytest.raises(Exception, match="geometry"):
        DecodeServer(model, params, pool=pool, seq_ladder=[16],
                     max_new_tokens=4, start=False)


# ---------------------------------------------------------------------------
# the Pallas kernels, interpreted, against the jnp paths
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("positions", [[40, 16, 0], [63, 1, 15], [0, 0, 0],
                                       "ragged", "poisoned_tail"])
def test_latent_decode_kernel_matches_gather_reference(positions,
                                                       ragged_pages):
    L, P, S, W, R, H = 2, 11, 16, 256, 128, 4
    table = [[1, 2, 3, 7], [4, 5, 0, 0], [6, 0, 0, 0]]
    poisoned = positions == "poisoned_tail"
    if isinstance(positions, str):
        # 0, 1, S-1, S, S+1 keys and a full table in one batch; the
        # poisoned table is 4x wider than any row needs
        table, positions = ragged_pages(S, 1, widen=4 if poisoned else 1)
        W, R = 384, 256    # the running max and sum repeated over 2 tiles
    B = len(positions)
    k = jax.random.split(jax.random.PRNGKey(0), 4)
    pool = jax.random.normal(k[0], (L, P, S, W)).astype(jnp.bfloat16)
    q = jax.random.normal(k[1], (B, H, W))
    new = jax.random.normal(k[2], (B, W))
    table = jnp.asarray(table, jnp.int32)
    pos = jnp.asarray(positions, jnp.int32)
    before = dict(profiler.counters())
    for layer in (0, 1):
        a = kvcache.paged_latent_attention(pool, table, pos, layer, q, new,
                                           rank=R, scale=0.05)
        b = kvcache.paged_latent_attention(pool, table, pos, layer, q, new,
                                           rank=R, scale=0.05,
                                           force_pallas=True)
        assert a.shape == b.shape == (B, H, R) and b.dtype == jnp.float32
        # the kernel rounds the softmax weights to bf16 for the MXU
        assert np.abs(np.asarray(a) - np.asarray(b)).max() < 0.02
        if poisoned:
            # every dead column names a page of NaN: a masked fold would
            # not do (0 x NaN survives the value product), the walk must
            # not read it — and reads what the clean table's walk reads
            bad = jnp.where(table == 0, P - 1, table)
            c = kvcache.paged_latent_attention(
                pool.at[:, P - 1].set(jnp.nan), bad, pos, layer, q, new,
                rank=R, scale=0.05, force_pallas=True)
            assert bool(jnp.isfinite(c).all()) and bool((c == b).all())
    after = profiler.counters()
    assert after.get("mla_decode_jnp", 0) - before.get("mla_decode_jnp", 0) \
        == 2
    assert after.get("mla_decode_pallas", 0) \
        - before.get("mla_decode_pallas", 0) == 2 + 2 * poisoned
    # position 0: nothing in the pool, the new token attends to itself
    for row in np.flatnonzero(np.asarray(positions) == 0):
        assert np.abs(np.asarray(b[row])
                      - np.asarray(new[row, :R].astype(jnp.bfloat16),
                                   np.float32)).max() < 1e-6


def test_latent_row_write_kernel_is_the_row_writes():
    L, P, S, W, B = 2, 9, 16, 128, 3
    k = jax.random.split(jax.random.PRNGKey(1), 2)
    pool = jax.random.normal(k[0], (L, P, S, W)).astype(jnp.bfloat16)
    new = jax.random.normal(k[1], (L, B, W))
    table = jnp.asarray([[1, 2, 3, 7], [4, 5, 0, 0], [0, 0, 0, 0]],
                        jnp.int32)
    pos = jnp.asarray([40, 16, 0], jnp.int32)
    a = kvcache.write_token_rows(pool, table, pos, new)
    b = kvcache.write_token_rows(pool, table, pos, new, force_pallas=True)
    assert a.dtype == b.dtype == jnp.bfloat16 and bool((a == b).all())
    changed = np.asarray((a != pool).any(axis=-1))
    assert changed.sum() == L * B
    assert changed[:, 3, 8].all() and changed[:, 5, 0].all() \
        and changed[:, 0, 0].all()


def _expert_case(T, topi_of):
    """Tokens, bf16 expert stacks of the held range (8, 16) and a
    router's choice over 32 experts for ``expert_ffn``'s tests."""
    D, F, E = 128, 256, 8
    k = jax.random.split(jax.random.PRNGKey(2), 6)
    x = jax.random.normal(k[0], (T, D))
    w = {"w_gate": jax.random.normal(k[1], (E, D, F)) * D ** -0.5,
         "w_up": jax.random.normal(k[2], (E, D, F)) * D ** -0.5,
         "w_down": jax.random.normal(k[3], (E, F, D)) * F ** -0.5}
    w = {n: v.astype(jnp.bfloat16) for n, v in w.items()}
    topw = jax.random.uniform(k[5], (T, 4)) + 0.1
    return x, w, topi_of(k[4], T), topw


_TOPI = {
    "spread": lambda key, T: jax.random.randint(key, (T, 4), 0, 32),
    # every token on one held expert: several row tiles
    "one_expert": lambda key, T: jnp.full((T, 4), 11).at[:, 1:].set(
        jnp.asarray([0, 1, 2])),
    "none_held": lambda key, T: jax.random.randint(key, (T, 4), 16, 32),
    # Laguna's ``live`` mask: the last third of the rung is padding, and
    # a padded position chooses expert 32, past the last, which nobody holds
    "padded": lambda key, T: jnp.where(
        (jnp.arange(T) < T - T // 3)[:, None],
        jax.random.randint(key, (T, 4), 0, 32), 32),
}


@pytest.mark.parametrize("case", [
    "spread", "one_expert", "none_held", "padded",
    "tall_spread", "tall_one_expert", "tall_none_held", "tall_padded"])
def test_grouped_matmul_kernel_matches_ragged_dot(case):
    """A step's width (12 slots an expert: tiles of 16 rows) and a
    prompt's (256: the tallest tile, 128), each against the plain loop
    on both paths."""
    tall = case.startswith("tall_")
    T, E, held = 512 if tall else 24, 8, (8, 16)
    assert moe._gmm_rows(T * 4, E) == (128 if tall else 16)
    x, w, topi, topw = _expert_case(T, _TOPI[case[5 * tall:]])
    before = dict(profiler.counters())
    a = moe.expert_ffn(x, w, topi, topw, held)
    b = moe.expert_ffn(x, w, topi, topw, held, force_pallas=True)
    after = profiler.counters()
    for name, n in (("grouped_matmul_jnp", 1), ("grouped_matmul_pallas", 1),
                    ("grouped_matmul_rows_%d" % (128 if tall else 16), 2),
                    ("grouped_matmul_rows_%d" % (16 if tall else 128), 0)):
        assert after.get(name, 0) - before.get(name, 0) == n, name
    # a plain loop, float32 from the same bf16 operands
    want = np.zeros((T, x.shape[1]), np.float32)
    xb = np.asarray(x.astype(jnp.bfloat16).astype(jnp.float32))
    wf = {n: np.asarray(v.astype(jnp.float32)) for n, v in w.items()}
    topi_h, topw_h = np.asarray(topi), np.asarray(topw)
    for t in range(T):
        for j in range(4):
            e = int(topi_h[t, j]) - held[0]
            if 0 <= e < E:
                g, u = xb[t] @ wf["w_gate"][e], xb[t] @ wf["w_up"][e]
                h = (g / (1 + np.exp(-g)) * u).astype(
                    jnp.bfloat16).astype(np.float32)
                want[t] += float(topw_h[t, j]) * (h @ wf["w_down"][e])
    scale = max(np.abs(want).max(), 1.0)
    assert np.abs(np.asarray(a) - want).max() / scale < 2e-3
    assert np.abs(np.asarray(b) - want).max() / scale < 2e-3
    if case.endswith("none_held"):
        assert not np.asarray(a).any() and not np.asarray(b).any()
    if case.endswith("padded"):
        dead = T - T // 3
        assert np.asarray(a)[:dead].any() and not np.asarray(a)[dead:].any()
        assert not np.asarray(b)[dead:].any()


@pytest.mark.parametrize("force_pallas", [True, False])
def test_a_tokens_rows_do_not_depend_on_the_tile_height(force_pallas):
    """The same 24 tokens alone (12 slots an expert: tiles of 16 rows)
    and at the head of a prompt of 512 (256: tiles of 128) get the same
    rows bit for bit, and the trace-time counter says which height each
    program was given."""
    held = (8, 16)
    x, w, topi, topw = _expert_case(512, _TOPI["spread"])
    before = dict(profiler.counters())
    tall = moe.expert_ffn(x, w, topi, topw, held, force_pallas=force_pallas)
    mid = dict(profiler.counters())
    short = moe.expert_ffn(x[:24], w, topi[:24], topw[:24], held,
                           force_pallas=force_pallas)
    after = profiler.counters()
    assert mid.get("grouped_matmul_rows_128", 0) \
        - before.get("grouped_matmul_rows_128", 0) == 1
    assert after.get("grouped_matmul_rows_16", 0) \
        - mid.get("grouped_matmul_rows_16", 0) == 1
    assert np.asarray(short).any()
    assert (np.asarray(tall)[:24] == np.asarray(short)).all()


# ---------------------------------------------------------------------------
# a prompt rides the decode step in chunks, over the latent pool
# ---------------------------------------------------------------------------

LCHUNK = 8


def _chunk_srv(**kw):
    """Chunks of 8, the ladder's smallest rung (its next is past a
    step's budget of two of them), over pages of 8."""
    model, params = _model()
    kw.setdefault("seq_ladder", [LCHUNK, 32])
    kw.setdefault("max_new_tokens", 12)
    kw.setdefault("window", 3)
    kw.setdefault("page_size", 8)
    kw.setdefault("pool_pages", 32)
    return model, params, DecodeServer(model, params, start=False, **kw)


def _lat_prompts(sizes, seed):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, CFG["vocab_size"], size=n).astype(np.int32)
            for n in sizes]


def _near_reference(model, params, prompt, served):
    """Served tokens are the reference's own or lie a rounding under
    them (random bf16 weights: a token for token oracle does not
    exist; one flipped router near-tie costs a third of a deviation)."""
    n = len(served)
    out = ref.teacher_forced(params, prompt, np.asarray(served), 64, n, CFG,
                             model.held)
    assert out["mean"] < 0.03 and out["exact"] >= n - 2, out


def _lat_lengths():
    """1, C - 1, C, C + 1 and 3C + 7 tokens: the reference's tokens to a
    near-tie, the SAME tokens whatever the chunk's size (8 a step, or
    the prompt whole), ceil(P / C) mixed steps and no prefill program."""
    sizes = (1, LCHUNK - 1, LCHUNK, LCHUNK + 1, 3 * LCHUNK + 7)
    prompts = _lat_prompts(sizes, seed=5)
    streams = {}
    for chunk in (LCHUNK, 32):
        model, params, srv = _chunk_srv(seq_ladder=sorted({chunk, 32}))
        try:
            reqs = []
            for p in prompts:
                reqs.append(srv.submit(p, max_new_tokens=10))
                _drain(srv, reqs[-1])
            streams[chunk] = [r.result().tolist() for r in reqs]
            st = srv.stats()
            assert st["prefill_programs"] == 0 == st["prefill_steps"]
            assert st["chunk_tokens"] == sum(sizes)
            assert st["chunk_steps"] == sum(-(-n // chunk) for n in sizes)
            assert st["moe"]["steps"] == st["decode_steps"]
        finally:
            srv.stop()
    assert streams[LCHUNK] == streams[32]
    for p, served in zip(prompts, streams[LCHUNK]):
        _near_reference(model, params, p, served)


def _lat_straddles_and_rides_ahead():
    """Chunks of 12 over pages of 4 (three pages a chunk; five chunks of
    a 50-token prompt) arriving while two rows decode one step ahead:
    nothing drains, both kinds of stream are the reference's, the mates
    emit in every step that carries a chunk."""
    model, params, srv = _chunk_srv(seq_ladder=[12, 64], page_size=4,
                                    pool_pages=96)
    mates = _lat_prompts((6, 9), seed=6)
    long_p, = _lat_prompts((50,), seed=7)
    try:
        reqs = [srv.submit(m, max_new_tokens=12) for m in mates]
        for _ in range(4):
            srv._tick()
        late = srv.submit(long_p, max_new_tokens=8)
        _drain(srv, late, *reqs)
        for p, r in zip(mates + [long_p], reqs + [late]):
            _near_reference(model, params, p, r.result().tolist())
        st = srv.stats()
        assert st["decode_drains"] == {}
        assert st["chunk_steps"] == 2 + 5 and st["chunk_tokens"] == 65
        assert st["decode_steps_ahead"] == st["decode_steps"] - 1
    finally:
        srv.stop()


def _lat_fifo_and_cancel():
    """Two prompts queued: one request's chunk a step, head-most first.
    The second is cancelled with chunks pending: its pages come back,
    nothing is pushed, the first finishes as if alone."""
    model, params, srv = _chunk_srv()
    a, b = _lat_prompts((20, 27), seed=8)
    fed, build = [], srv._build_chunk

    def spying(slot, r):
        out = build(slot, r)
        fed.append((r.request_id, out[1]))
        return out

    srv._build_chunk = spying
    try:
        free0 = srv._pool.stats()["free"]
        ra = srv.submit(a, max_new_tokens=6)
        rb = srv.submit(b, max_new_tokens=6)
        for _ in range(5):
            srv._tick()
        assert fed == [(ra.request_id, 8), (ra.request_id, 8),
                       (ra.request_id, 4), (rb.request_id, 8),
                       (rb.request_id, 8)]
        assert rb.pending and rb.pending_pos == 16
        rb.cancel()
        _drain(srv, ra, rb)
        assert rb.state == "cancelled" and rb.generated == []
        assert rb.pending is None and not rb.pages
        _near_reference(model, params, a, ra.result().tolist())
        while srv._has_work():
            srv._tick()
        assert srv._pool.stats()["free"] == free0
        assert srv.stats()["chunk_tokens"] == 20 + 16
    finally:
        srv.stop()


def _lat_prefix_hit_and_cow():
    """Prefix sharing over the latent pool: a hit feeds its suffix in
    chunks from ``cached`` on — the same tokens as the unshared run —
    and a fully cached page-aligned prompt re-runs its last token as a
    chunk of one, whose write splits the shared page."""
    model, params, srv = _chunk_srv(prefix_cache=True)
    base, = _lat_prompts((16,), seed=9)                # two full pages
    tail, = _lat_prompts((13,), seed=10)
    longer = np.concatenate([base, tail]).astype(np.int32)
    _, _, alone = _chunk_srv()
    try:
        want = []
        for p, n in ((base, 6), (longer, 9)):
            r = alone.submit(p, max_new_tokens=n)
            _drain(alone, r)
            want.append(r.result().tolist())
        first = srv.submit(base, max_new_tokens=6)
        _drain(srv, first)
        again = srv.submit(base, max_new_tokens=6)
        hit = srv.submit(longer, max_new_tokens=9)
        _drain(srv, again, hit)
        assert again.prefix_cached == 16 == hit.prefix_cached
        assert first.result().tolist() == want[0]
        assert again.result().tolist() == want[0]
        assert hit.result().tolist() == want[1]
        st = srv.stats()
        assert st["prefix"]["cow_splits"] == 1
        assert st["chunk_tokens"] == 16 + 1 + 13
        assert st["prefill_programs"] == 0
    finally:
        srv.stop()
        alone.stop()


def _lat_fixed_programs():
    compile_watch.enable()
    model, params, srv = _chunk_srv(name="latchunk")
    try:
        assert srv.warmup() == 2
        warm = compile_watch.site_stats("decode:latchunk")
        assert sorted(warm) == ["decode:latchunk:step",
                                "decode:latchunk:step:chunk:c8"]
        reqs = [srv.submit(p, max_new_tokens=4)
                for p in _lat_prompts((3, 8, 17, 32, 1), seed=11)]
        _drain(srv, *reqs)
        assert compile_watch.site_stats("decode:latchunk") == warm
        assert all(v["count"] == 1 for v in warm.values())
    finally:
        srv.stop()


def _lat_dead_lanes_choose_no_expert():
    """A last chunk of ONE token: the mixed step's experts are the plain
    step's and that token's, nothing for the chunk's seven dead lanes —
    ``moe_slots`` adds up exactly, ``experts_touched`` is the union's,
    and the dead lanes' rows are not written."""
    model, params, srv = _chunk_srv()
    W, C, M = srv._window, LCHUNK, srv._max_pages
    mixed_prog = srv._chunk_progs[C]
    tok = 77

    def run(prog, tokens, chunk=()):
        pos = np.zeros((W,), np.int32)
        out = prog(srv._params.tree, np.asarray(tokens, np.int32), pos,
                   np.zeros((W, M), np.int32), srv._no_prev,
                   np.full((W,), -1, np.int32), *chunk, *srv._pool.arrays)
        return dict(zip(model.step_counters[1],
                        (int(c) for c in np.asarray(
                            srv._adopt_pool(out)[0])[W:])))

    try:
        plain = run(srv._decode_prog, [0] * W)
        # the token as a decode row at position 0: what it alone chooses
        solo = run(srv._decode_prog, [tok] + [0] * (W - 1))
        chunk = np.zeros((C + M + 3,), np.int32)
        chunk[0], chunk[C], chunk[C + M:] = tok, 5, (0, 1, -1)
        before = np.asarray(srv._pool.arrays[0].astype(jnp.float32))
        mixed = run(mixed_prog, [0] * W, (chunk,))
        after = np.asarray(srv._pool.arrays[0].astype(jnp.float32))
        own = solo["moe_slots"] - plain["moe_slots"] * (W - 1) // W
        assert plain["moe_slots"] % W == 0 and own > 0
        assert mixed["moe_slots"] == plain["moe_slots"] + own
        assert plain["experts_touched"] <= mixed["experts_touched"] \
            <= plain["experts_touched"] + own
        # live lanes alone: with the dead ones 7 x 8 slots more
        everyone = dict(chunk=chunk.copy())
        everyone["chunk"][C + M + 1] = C
        full = run(mixed_prog, [0] * W, (everyone["chunk"],))
        assert full["moe_slots"] > mixed["moe_slots"]
        changed = (after != before).any(axis=(0, 3))
        assert changed[5].tolist() == [True] + [False] * 7
        assert not changed[1:5].any() and not changed[6:].any()
    finally:
        srv.stop()


_LAT_CHUNKS = {
    "lengths": _lat_lengths,
    "straddles_pages_beside_rows_ahead": _lat_straddles_and_rides_ahead,
    "two_prompts_fifo_then_cancel": _lat_fifo_and_cancel,
    "prefix_hit_and_cow": _lat_prefix_hit_and_cow,
    "fixed_programs": _lat_fixed_programs,
    "dead_lanes_choose_no_expert": _lat_dead_lanes_choose_no_expert,
}


@pytest.mark.parametrize("case", sorted(_LAT_CHUNKS))
def test_a_prompt_in_chunks_over_the_latent_pool(case):
    _LAT_CHUNKS[case]()
