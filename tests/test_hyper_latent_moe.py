"""Four residual streams mixed by Sinkhorn-constrained hyper-connections
around every latent-attention and expert sublayer, and the model's own
next-token module drafting one token that a two-position verify step
accepts or rolls back — ``serving.latent_moe`` with ``hc_mult`` 4 and
``num_nextn_predict_layers`` 1 on ``DecodeServer``'s SPECULATIVE form
of the model contract, the latent layout's causal block form
(``serving.kvcache``) and its Pallas kernels (interpret mode), against
the benchmark's plain float32 reference
(``benchmark/reference/hyper_latent_moe_lm.py``) at a small size with
seeded bf16 weights. At the defaults (one stream, no module) the model's
programs are the parent's."""
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
from mxnet_tpu import compile_watch, fault, profiler, telemetry  # noqa: E402
from mxnet_tpu.base import MXNetError                          # noqa: E402
from mxnet_tpu.serving import (DecodeServer, KVCachePool,       # noqa: E402
                               ToyDecoderLM, kvcache)
from mxnet_tpu.serving.block_diffusion import (                # noqa: E402
    BlockDiffusionMoEDecoderLM)
from mxnet_tpu.serving.latent_moe import LatentMoEDecoderLM    # noqa: E402
from test_latent_moe_serving import router_flips               # noqa: E402

YARN = {"beta_fast": 32, "beta_slow": 1, "factor": 64, "mscale": 1,
        "mscale_all_dim": 1, "original_max_position_embeddings": 4096,
        "type": "yarn"}
# the published block's shape at a test's size: latent rank 128 (whole
# lane tiles, so the Pallas paths tile as at the real 512), 16 experts in
# ONE group (n_group 1: plain top-4), one dense layer in front, four
# streams, 20 Sinkhorn iterations, the next-token module
BASE = dict(vocab_size=256, hidden_size=128, num_hidden_layers=3,
            num_attention_heads=4, q_lora_rank=64, kv_lora_rank=128,
            qk_nope_head_dim=32, qk_rope_head_dim=16, v_head_dim=32,
            intermediate_size=256, moe_intermediate_size=128,
            n_routed_experts=16, n_shared_experts=1, num_experts_per_tok=4,
            n_group=1, topk_group=1, routed_scaling_factor=2.0,
            first_k_dense_replace=1, rope_theta=10000, rope_scaling=YARN,
            rms_norm_eps=1e-6, max_position_embeddings=512)
CFG = dict(BASE, hc_mult=4, hc_sinkhorn_iters=20, hc_eps=1e-6,
           mhc_h_res_clamp_min=-30, mhc_h_res_clamp_max=30,
           num_nextn_predict_layers=1)


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
def _model(use_pallas=False, seed=3):
    model = LatentMoEDecoderLM(**CFG, use_pallas=use_pallas)
    return model, model.init_params(seed=seed)


def _plain(use_pallas=False):
    """The same model WITHOUT its drafter: the one-token step, whose
    greedy stream the speculative one has to reproduce. It is handed the
    same parameter dict (the module's entries are not read)."""
    return LatentMoEDecoderLM(**dict(CFG, num_nextn_predict_layers=0),
                              use_pallas=use_pallas)


def _server(model, params, **kw):
    kw = {"seq_ladder": [32], "max_new_tokens": 32, "page_size": 16,
          "window": 4, "pool_pages": 64, "start": False, **kw}
    return DecodeServer(model, params, **kw)


def _drain(srv, *reqs, limit=800):
    n = 0
    while not all(r.done() for r in reqs):
        srv._tick()
        n += 1
        assert n < limit, "scheduler made no progress"


def _prompts(seed, sizes, vocab=256):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, vocab, size=n).astype(np.int32) for n in sizes]


def _serve(model, params, prompts, n=24, **kw):
    srv = _server(model, params, **kw)
    reqs = [srv.submit(p, max_new_tokens=n) for p in prompts]
    _drain(srv, *reqs)
    st = srv.stats()
    srv.stop()
    return [[int(t) for t in r.result()] for r in reqs], st, reqs


# ---------------------------------------------------------------------------
# constructed weights: what the drafter says is decided by hand
# ---------------------------------------------------------------------------

def _constructed(share, use_pallas=False):
    """A model whose greedy stream and whose drafts are known by
    construction. Every block is ZEROED (``wo`` and every ``w_down``),
    so the four streams carry the token's embedding unchanged (a doubly
    stochastic ``H_res`` keeps their sum, identical streams stay
    identical) and ``h = 4 e(x)``. The head is a PERMUTATION of the
    embedding, ``head[:, f(v)] = e(v)`` for one cycle ``f`` over the
    whole vocabulary, so the main model continues ``x`` with ``f(x)`` by
    a margin of |e|^2 against cross products a tenth of it. The module's
    projection ignores ``h`` and maps the next token's normed embedding
    to the embedding of ``g(t)``, so it drafts ``f(g(t_{i+1}))`` for
    position ``i + 2``: right where ``g(t) = t`` — for ``share`` of the
    vocabulary — and wrong elsewhere. (64 tokens in 128 dimensions: the
    embeddings are independent, so any map of tokens is a linear map.)"""
    cfg = dict(CFG, vocab_size=64)
    model = LatentMoEDecoderLM(**cfg, use_pallas=use_pallas)
    p = dict(model.init_params(seed=5))
    for name in p:
        if name.endswith(".wo") or name.endswith("w_down"):
            p[name] = jnp.zeros_like(p[name])
    V, D = 64, model.d_model
    rng = np.random.default_rng(7)
    order = rng.permutation(V)
    f = np.empty(V, np.int64)
    f[order] = np.roll(order, -1)                  # one cycle of all 64
    e = np.asarray(p["embed"].astype(jnp.float32))
    head = np.zeros((D, V), np.float32)
    head[:, f] = e.T
    p["head"] = jnp.asarray(head, jnp.bfloat16)
    right = rng.random(V) < share
    g = np.where(right, np.arange(V), (np.arange(V) + 1) % V)
    normed = e / np.sqrt((e * e).mean(-1, keepdims=True) + 1e-6)
    proj = np.zeros((2 * D, D), np.float32)
    proj[D:] = np.linalg.pinv(normed) @ e[g]
    p["mtp.proj"] = jnp.asarray(proj, jnp.bfloat16)
    return model, p, f, right


# ---------------------------------------------------------------------------
# the model against the reference, in logits
# ---------------------------------------------------------------------------

def _cached_logits(model, params, tokens, n_prompt, wrong, page_size=16):
    """Main and module logits of positions ``n_prompt - 1 ..`` from the
    SERVING path: one prefill of both caches over the prompt, then
    speculative steps of two positions through the server's own causal
    ``attend`` and row writes — what ``DecodeServer``'s two speculative
    programs compute, with the logits kept. The sequence is fixed
    (teacher-forced); a step whose start is in ``wrong`` is given a
    WRONG draft, so its second position is computed, written and then
    overwritten by the next step, which starts one position on; every
    other step is given the true next token (an accepted draft) and the
    next starts two on. Returns ``{position: logits}`` twice."""
    L = len(tokens)
    rung = -(-n_prompt // page_size) * page_size
    n_pages = -(-(L + 2) // page_size) + 1
    pool = KVCachePool(model.cache_layers,
                       arrays=[c[:2] for c in model.cache_arrays],
                       dtype=model.cache_arrays[0][2], page_size=page_size,
                       n_pages=n_pages + 1)
    pages = pool.arrays[0]
    table = np.arange(1, n_pages + 1, dtype=np.int32)
    padded = np.zeros((1, rung), np.int32)
    padded[0, :n_prompt] = tokens[:n_prompt]
    after = np.zeros((1, rung), np.int32)
    after[0, :n_prompt] = tokens[1:n_prompt + 1]

    @jax.jit
    def prefill(padded, after):
        logits, hidden, rows = model.prefill_draft(params, padded)
        d_logits, d_rows = model.draft_prefill(params, hidden, after)
        return logits[0], d_logits[0], jnp.concatenate([rows, d_rows])

    logits, d_logits, rows = prefill(padded, after)
    pages = kvcache.write_prefill_pages(pages, table, rows[:, 0], n_prompt)
    main = {n_prompt - 1: np.asarray(logits[n_prompt - 1])}
    module = {n_prompt - 2: np.asarray(d_logits[n_prompt - 2]),
              n_prompt - 1: np.asarray(d_logits[n_prompt - 1])}

    @jax.jit
    def step(pages, fed, nxt, pos):
        attend = pool.layout.attend_causal((pages,), table[None], pos)
        logits, hidden, new, _ = model.verify(params, fed, pos, attend)
        d_logits, d_new, _ = model.draft(params, hidden, nxt, pos, attend)
        (pages,) = pool.layout.write_causal(
            (pages,), table[None], pos, [jnp.concatenate([new, d_new])],
            model.use_pallas)
        return logits[0], d_logits[0], pages

    p = n_prompt
    while p + 2 < L:
        fed = [tokens[p], tokens[p + 1]]
        if p in wrong:
            fed[1] = (fed[1] + 1) % model.vocab
        lg, dl, pages = step(pages, jnp.asarray([fed], jnp.int32),
                             jnp.asarray([tokens[p + 1:p + 3]], jnp.int32),
                             jnp.asarray([p], jnp.int32))
        main[p], module[p] = np.asarray(lg[0]), np.asarray(dl[0])
        if p in wrong:
            p += 1
        else:
            main[p + 1], module[p + 1] = np.asarray(lg[1]), np.asarray(dl[1])
            p += 2
    return main, module


# The program rounds every activation to bf16 in front of a product and
# the reference none; the streams, their mixing coefficients, norms,
# softmax and router are float32 in both. At these widths the median
# position's logits lie within 0.025 deviations of the reference's for
# the main model (the worst logit of a position; jnp and Pallas paths,
# seeds 1 and 2) and within 0.07 for the module, whose input has passed
# every main layer and its own. The router is discrete and 16 experts'
# sigmoid scores are dense in near-ties: where two scores are closer
# than a rounding the choice flips and the position is a whole expert
# off (0.2-1.1 deviations seen, at 8-13% of the positions), which is no
# error, so up to a fifth of the positions may be over three times the
# tolerance. The float8 control (weights and cached latent in
# float8_e4m3fn) is 0.27 deviations and more off at EVERY position of
# the main model and 0.31 of the module: the tolerances are 2 and 1.4
# times the program's medians and under half the control's best.
#
# What logits CANNOT tell apart is the second control, the reference
# with only the mixing coefficients in bfloat16: its logits are 0.012-
# 0.03 off at the median position, the same as the program's own
# rounding of its operands (a coefficient off by 2**-9 and an
# activation off by 2**-9 perturb a stream alike, and they add in
# quadrature). So the coefficient path is held where it can be seen:
# ``test_the_mixing_coefficients_are_float32`` compares the
# coefficients themselves, which agree with the reference's to 1e-6
# where the bfloat16 path is 4e-3 and more off.
LOGIT_TOLERANCE = {"main": 0.05, "module": 0.1}


def _position_errors(got, want, positions):
    """Per position: the worst logit's distance, in deviations of the
    reference's logits."""
    err = np.stack([np.abs(got[p] - want[p]).max() for p in positions])
    return err / np.stack([want[p] for p in positions]).std()


@pytest.mark.parametrize("use_pallas", [False, True], ids=["jnp", "pallas"])
def test_prefill_then_speculative_decode_agrees_with_the_reference_on_logits(
        use_pallas):
    model, params = _model(use_pallas=use_pallas)
    tokens = np.random.default_rng(1).integers(
        0, model.vocab, size=58).astype(np.int32)
    n_prompt = 21
    # rejected drafts at both parities, one of them with its second
    # position on the far side of a page boundary (31 | 32)
    main, module = _cached_logits(model, params, tokens, n_prompt,
                                  wrong={23, 31, 40})
    rows = len(tokens) - n_prompt + 1
    seq = jnp.asarray(tokens)

    def reference(control=None):
        m, d = ref.logits_rows(params, seq, n_prompt - 2, rows, CFG,
                               model.held, control=control)
        return {"main": {n_prompt - 2 + i: m[i] for i in range(rows)},
                "module": {n_prompt - 2 + i: d[i] for i in range(rows)}}

    want, low = reference(), reference("float8")
    for name, got in (("main", main), ("module", module)):
        positions, tol = sorted(got), LOGIT_TOLERANCE[name]
        assert len(positions) >= len(tokens) - n_prompt - 2
        err = _position_errors(got, want[name], positions)
        assert np.median(err) < tol, err
        assert (err > 3 * tol).mean() <= 0.2, err
        # tight enough that the next precision down fails it, everywhere
        assert _position_errors(low[name], want[name], positions).min() \
            > 2 * tol


def test_the_mixing_coefficients_are_float32():
    """The coefficient path (the norm over all n C values, the product
    at "highest", sigmoid, 20 Sinkhorn iterations) against the
    reference's, on states as large as a deep layer's: the program's
    agree to 1e-4 (2e-7 seen) where the reference's own path in
    bfloat16 — the second control — is 4e-3 to 9e-3 off, forty times
    the tolerance."""
    model, params = _model()
    X = 3.0 * jax.random.normal(jax.random.PRNGKey(0), (40, 4, 128))
    kw = dict(n=4, iters=20, hc_eps=1e-6, clamp=(-30.0, 30.0), eps=1e-6)
    for name in ("l0.attn_", "l2.ffn_", "l3.ffn_"):
        u, (post, res) = jax.jit(
            lambda X: model._read(name, X, params))(X)      # noqa: B023
        w = [params[name + k] for k in ("hc_w", "hc_a", "hc_b")]
        pre_r, post_r, res_r = ref.mixing(X, *w, low=False, **kw)
        assert float(jnp.abs(post - post_r).max()) < 1e-4
        assert float(jnp.abs(res - res_r).max()) < 1e-4
        assert float(jnp.abs(u - ref.read(X, pre_r)).max()) < 1e-4
        np.testing.assert_allclose(
            np.asarray(model._write(X, u, (post, res))),
            np.asarray(ref.write(X, u, post_r, res_r)), atol=1e-4)
        _, post_l, res_l = ref.mixing(X, *w, low=True, **kw)
        assert float(jnp.abs(post_l - post_r).max()) > 2e-3
        assert float(jnp.abs(res_l - res_r).max()) > 2e-3


# ---------------------------------------------------------------------------
# the invariant: the served stream is the greedy stream, whatever the
# drafter says
# ---------------------------------------------------------------------------

SIZES = (5, 17, 30, 15, 16, 9)       # prompts ending on both sides of a
                                     # page boundary, more than a window


@pytest.mark.parametrize("use_pallas", [False, True], ids=["jnp", "pallas"])
@pytest.mark.parametrize("share", [1.0, 0.5, 0.0],
                         ids=["all_accepted", "half_accepted",
                              "all_rejected"])
def test_the_served_stream_is_the_greedy_stream_on_constructed_weights(
        share, use_pallas):
    model, params, f, right = _constructed(share, use_pallas)
    prompts = _prompts(11, SIZES, vocab=64)
    streams, st, reqs = _serve(model, params, prompts, n=23)
    plain = LatentMoEDecoderLM(
        **dict(CFG, vocab_size=64, num_nextn_predict_layers=0),
        use_pallas=use_pallas)
    greedy, st_plain, _ = _serve(plain, params, prompts, n=23)
    assert streams == greedy
    for prompt, stream, req in zip(prompts, streams, reqs):
        # ... which is the cycle, by construction
        chain = [int(f[prompt[-1]])]
        while len(chain) < 23:
            chain.append(int(f[chain[-1]]))
        assert stream == chain
        # a draft for token i was made from token i-1: right iff g kept it
        for i, d in enumerate(req.drafts):
            if d >= 0:
                assert (d == stream[i]) == bool(right[stream[i - 1]])
    spec = st["spec"]
    assert spec["positions_run"] == 2 * spec["drafts_verified"]
    if share == 1.0:
        assert spec["drafts_accepted"] == spec["drafts_verified"]
        # two tokens a row a step: half the steps of the one-token server
        assert st["decode_steps"] <= st_plain["decode_steps"] // 2 + 2
    elif share == 0.0:
        assert spec["drafts_accepted"] == 0
        # one token a row a step on both servers: a row holds its slot
        # 22 steps after the prefill that emits its first token, and 23
        # where its prompt rides a step and that step emits it — six
        # rows through four slots are two in a row
        assert st["decode_steps"] == st_plain["decode_steps"] - 2
        assert st_plain["chunk_steps"] == len(prompts)
    else:
        assert 0.25 < spec["drafts_accepted"] / spec["drafts_verified"] \
            < 0.75
    # the step ran ahead of the host with positions it did not know
    assert st["decode_steps_ahead"] >= st["decode_steps"] - 2


def _one_token_greedy(model, params, prompt, n):
    """The greedy stream of the one-token programs from a whole-prompt
    prefill — the prefill a speculative server runs, then ``decode`` a
    token over the server's own ``attend`` and row write, with no server
    and no drafter: what a speculative stream has to reproduce."""
    S, P = 16, len(prompt)
    n_pages = -(-(P + n) // S)
    pool = KVCachePool(model.n_layers, arrays=[c[:2] for c in
                                               model.cache_arrays],
                       dtype=model.cache_arrays[0][2], page_size=S,
                       n_pages=n_pages + 1)
    table = np.arange(1, n_pages + 1, dtype=np.int32)
    padded = np.zeros((1, 32), np.int32)
    padded[0, :P] = prompt
    logits, rows = jax.jit(model.prefill)(params, padded)
    pages = kvcache.write_prefill_pages(pool.arrays[0], table, rows[:, 0], P)
    out = [int(np.asarray(logits[0, P - 1]).argmax())]

    @jax.jit
    def step(pages, tok, pos):
        attend = pool.layout.attend((pages,), table[None], pos)
        logits, new, _ = model.decode(params, tok, pos, attend)
        return logits[0].argmax(), kvcache.write_token_rows(
            pages, table[None], pos, new, model.use_pallas)

    while len(out) < n:
        tok, pages = step(pages, jnp.asarray(out[-1:], jnp.int32),
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


# ---------------------------------------------------------------------------
# the streams
# ---------------------------------------------------------------------------

def test_h_res_is_doubly_stochastic_and_neither_uniform_nor_the_identity():
    model, params = _model()
    X = jax.random.normal(jax.random.PRNGKey(0), (3, 40, 4, 128))
    for name in ("l0.attn_", "l2.ffn_", "l3.attn_"):
        u, (post, res) = model._read(name, X, params)
        res = np.asarray(res)
        assert res.shape == (3, 40, 4, 4) and (res > 0).all()
        assert np.abs(res.sum(-1) - 1).max() < 1e-4
        assert np.abs(res.sum(-2) - 1).max() < 1e-4
        assert 0.4 < res.max(-1).mean() < 0.9
        assert (np.asarray(post) > 0).all() and (np.asarray(post) < 2).all()
        assert u.shape == (3, 40, 128)
        # the write-back keeps what the streams sum to, plus sum(H_post) y
        y = jnp.ones((3, 40, 128))
        back = model._write(X, y, (post, jnp.asarray(res)))
        np.testing.assert_allclose(
            np.asarray(back.sum(-2)),
            np.asarray(X.sum(-2) + post.sum(-1)[..., None]), atol=2e-3)
    # a Sinkhorn by hand, rows before columns
    z = np.asarray(jax.random.normal(jax.random.PRNGKey(1), (4, 4)))
    m = np.exp(np.clip(z, -30, 30))
    for _ in range(20):
        m = m / (m.sum(-1, keepdims=True) + 1e-6)
        m = m / (m.sum(-2, keepdims=True) + 1e-6)
    got = ref.mixing(
        jnp.ones((1, 4, 8)), jnp.zeros((32, 24)), jnp.ones((3,)),
        jnp.concatenate([jnp.zeros((8,)), jnp.asarray(z).reshape(-1)]),
        n=4, iters=20, hc_eps=1e-6, clamp=(-30.0, 30.0), eps=1e-6,
        low=False)[2][0]
    np.testing.assert_allclose(np.asarray(got), m, rtol=1e-5)


def _old_forward(self, params, tokens):
    """``LatentMoEDecoderLM._forward`` as it stood before the streams."""
    from mxnet_tpu.parallel.flash_attention import flash_attention
    p = params
    B, L = tokens.shape
    H, R = self.n_heads, self.kv_rank
    pos = jnp.broadcast_to(jnp.arange(L, dtype=jnp.int32), (B, L))
    h = p["embed"][tokens].astype(jnp.float32)
    wide = -(-max(self.nope + self.rope, self.v_dim) // 128) * 128

    def pad(a):
        return jnp.pad(a.astype(jnp.bfloat16), (
            (0, 0), (0, 0), (0, 0), (0, wide - a.shape[-1])))

    rows = []
    for i in range(self.n_layers):
        l = "l%d." % i
        x = self._rms(h, p[l + "attn_g"])
        q_nope, q_r, row = self._latent(i, x, p, pos)
        row = row.astype(jnp.bfloat16)
        c_kv, k_r = row[..., :R], row[..., R:self.latent]
        k_nope = self._mm(c_kv, p[l + "wk_b"]).reshape(B, L, H, self.nope)
        v = self._mm(c_kv, p[l + "wv_b"]).reshape(B, L, H, self.v_dim)
        q = jnp.concatenate([q_nope, q_r], -1)
        k = jnp.concatenate(
            [k_nope, jnp.broadcast_to(k_r[:, :, None].astype(
                jnp.float32), (B, L, H, self.rope))], -1)
        a = flash_attention(pad(q), pad(k), pad(v), causal=True,
                            scale=self.scale, force_pallas=self.use_pallas)
        a = a[..., :self.v_dim].reshape(B, L, H * self.v_dim)
        h = h + self._mm(a, p[l + "wo"])
        x = self._rms(h, p[l + "ffn_g"])
        out, _ = self._ffn(i, x.reshape(B * L, -1), p, None)
        h = h + out.reshape(B, L, -1)
        rows.append(row)
    logits = self._mm(self._rms(h, p["out_g"]), p["head"])
    return logits, jnp.stack(rows)


def _old_decode(self, params, tokens, positions, attend):
    """``LatentMoEDecoderLM.decode`` as it stood before the streams."""
    p = params
    B = tokens.shape[0]
    H, R = self.n_heads, self.kv_rank
    h = p["embed"][tokens].astype(jnp.float32)
    rows, loads = [], []
    for i in range(self.n_layers):
        l = "l%d." % i
        x = self._rms(h, p[l + "attn_g"])
        q_nope, q_r, row = self._latent(i, x, p, positions)
        wk = p[l + "wk_b"].reshape(R, H, self.nope)
        q_lat = jnp.einsum("bhn,rhn->bhr", q_nope.astype(wk.dtype),
                           wk, preferred_element_type=jnp.float32)
        q_row = jnp.pad(jnp.concatenate([q_lat, q_r], -1), (
            (0, 0), (0, 0), (0, self.row_width - self.latent)))
        o_lat = attend(i, q_row, row, rank=R, scale=self.scale,
                       force_pallas=self.use_pallas)
        wv = p[l + "wv_b"].reshape(R, H, self.v_dim)
        a = jnp.einsum("bhr,rhv->bhv", o_lat.astype(wv.dtype), wv,
                       preferred_element_type=jnp.float32)
        h = h + self._mm(a.reshape(B, H * self.v_dim), p[l + "wo"])
        x = self._rms(h, p[l + "ffn_g"])
        out, load = self._ffn(i, x, p)
        h = h + out
        rows.append(row)
        if load is not None:
            loads.append(load)
    logits = self._mm(self._rms(h, p["out_g"]), p["head"])
    load = jnp.stack(loads)
    counters = jnp.stack([load.sum(), (load > 0).sum(), load.max()])
    return logits, jnp.stack(rows), counters


@pytest.mark.parametrize("program", ["prefill", "step"])
def test_one_stream_and_no_module_leave_the_parents_programs_unchanged(
        program):
    """``hc_mult`` 1 and ``num_nextn_predict_layers`` 0 (the defaults,
    ``dots.vlm1.inst``'s model) trace to the jaxprs the model had before
    the streams and the module: the oracles above are its two methods as
    they stood, and the server's programs around them are the one-token
    forms."""
    cfg = dict(BASE, n_routed_experts=32, n_group=4, topk_group=2)
    model = LatentMoEDecoderLM(**cfg)
    assert not hasattr(model, "draft_length")
    assert model.cache_layers == model.n_layers
    params = model.init_params(seed=3)
    assert not any("hc_" in k or k.startswith("mtp.") for k in params)
    holder = type("S", (), {"_model": model, "_window": 3})()
    pools = (jnp.zeros((3, 24, 8, model.row_width), jnp.bfloat16),)
    if program == "prefill":
        args = (params, jnp.zeros((1, 16), jnp.int32), jnp.int32(5),
                jnp.zeros((6,), jnp.int32), *pools)
        new = jax.make_jaxpr(functools.partial(DecodeServer._prefill_fn,
                                               holder))(*args)
        model.prefill = functools.partial(_old_forward, model)
        was = jax.make_jaxpr(functools.partial(DecodeServer._prefill_fn,
                                               holder))(*args)
    else:
        args = (params, jnp.zeros((3,), jnp.int32),
                jnp.zeros((3,), jnp.int32), jnp.zeros((3, 6), jnp.int32),
                *pools)
        new = jax.make_jaxpr(functools.partial(DecodeServer._step_fn,
                                               holder))(*args)
        model.decode = functools.partial(_old_decode, model)
        was = jax.make_jaxpr(functools.partial(DecodeServer._step_fn,
                                               holder))(*args)
    assert str(new) == str(was)


def test_streams_without_a_module_serve_through_the_one_token_step():
    """``hc_mult`` 4 alone is no new contract: the one-token programs,
    the same tokens as a prefill over the whole sequence."""
    model = _plain()
    _, params = _model()
    prompt = _prompts(8, (11,))[0]
    (stream,), st, _ = _serve(model, params, [prompt], n=12)
    # (the step that carried the prompt, and eleven after it)
    assert "spec" not in st and st["decode_steps"] == 12
    assert st["chunk_steps"] == 1 and st["prefill_programs"] == 0
    seq = np.concatenate([prompt, stream]).astype(np.int32)
    out = ref.teacher_forced(params, prompt, np.asarray(stream),
                             np.full((12,), -1), 64, 12, CFG, model.held)
    assert out["mean"] < 0.01, out
    padded = np.zeros((1, 32), np.int32)
    padded[0, :len(seq)] = seq
    full = np.asarray(jax.jit(model.prefill)(params, padded)[0][0])
    own = full[len(prompt) - 1:len(seq) - 1].argmax(-1)
    assert (own == np.asarray(stream)).mean() >= 0.9


# ---------------------------------------------------------------------------
# what is refused, when the server is built
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("what", [
    "prefix_sharing", "per_head_pool", "int8_pool", "two_modules",
    "a_block_over_a_latent_pool", "contract"])
def test_what_a_self_drafting_model_cannot_do_is_refused_with_a_typed_error(
        what):
    model, params = _model()
    if what == "prefix_sharing":
        with pytest.raises(MXNetError, match="prefix sharing"):
            _server(model, params, prefix_cache=True)
    elif what == "per_head_pool":
        pool = KVCachePool(model.cache_layers,
                           arrays=(("k", (2, 8)), ("v", (2, 8))),
                           page_size=16, n_pages=8)
        toy = ToyDecoderLM(vocab=32, n_layers=model.cache_layers, n_heads=2,
                           head_dim=8)
        toy.draft_length = 1
        toy.verify = toy.draft = toy.prefill_draft = toy.draft_prefill = None
        with pytest.raises(MXNetError, match="causal"):
            DecodeServer(toy, toy.init_params(0), pool=pool,
                         seq_ladder=[16], max_new_tokens=4, start=False)
    elif what == "int8_pool":
        toy = ToyDecoderLM(vocab=32, n_layers=2, n_heads=2, head_dim=8)
        toy.draft_length = 1
        toy.verify = toy.draft = toy.prefill_draft = toy.draft_prefill = None
        pool = KVCachePool(2, 2, 8, page_size=16, n_pages=8, dtype="int8")
        with pytest.raises(MXNetError, match="requantize"):
            DecodeServer(toy, toy.init_params(0), pool=pool,
                         seq_ladder=[16], max_new_tokens=4, start=False)
    elif what == "two_modules":
        with pytest.raises(MXNetError, match="depth 1"):
            LatentMoEDecoderLM(**dict(CFG, num_nextn_predict_layers=2))
    elif what == "a_block_over_a_latent_pool":
        # a block model that declares a latent row: PR 31's refusal said
        # "there is no block form of latent attention", which is no
        # longer true — what is still missing is the all-see-all block
        block = BlockDiffusionMoEDecoderLM(
            vocab_size=64, hidden_size=32, num_hidden_layers=2,
            num_attention_heads=2, num_key_value_heads=1, head_dim=16,
            moe_intermediate_size=16, num_experts=4, num_experts_per_tok=2,
            rope_theta=10000, block_length=4, mask_token_id=63)
        block.cache_arrays = (("kv", (128,), "bfloat16"),)
        pool = KVCachePool(2, arrays=(("kv", (128,)),), page_size=16,
                           n_pages=8, dtype="bfloat16")
        assert pool.layout.causal_blocks and not pool.layout.blocks
        with pytest.raises(MXNetError, match="all-see-all"):
            DecodeServer(block, block.init_params(0), pool=pool,
                         seq_ladder=[16], max_new_tokens=8,
                         prefix_cache=False, start=False)
    else:
        class Half:
            n_layers, draft_length = 1, 1
            cache_arrays = (("kv", (128,), "bfloat16"),)

            def prefill(self, *a):
                pass

            def verify(self, *a):
                pass

        with pytest.raises(MXNetError, match="draft_length has verify"):
            DecodeServer(Half(), {}, start=False)


# ---------------------------------------------------------------------------
# the Pallas kernels, interpreted, against the jnp paths
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("positions", [[40, 15, 0], [63, 1, 31], [0, 0, 0],
                                       "ragged", "poisoned_tail"])
def test_verify_kernel_matches_the_two_query_reference(positions,
                                                       ragged_pages):
    """Both queries of a row against each live page in one product, the
    two new rows folded in triangularly; 15, 31 and 63 put the second
    new row on the far side of a page boundary. ``ragged``: 0, 1, S-1,
    S, S+1 keys and a full table in one batch; ``poisoned_tail``: the
    same rows under a table 4x wider whose dead columns name a page of
    NaN, which a walk that read it would carry into the output (0 x NaN
    survives the value product)."""
    L, P, S, W, R, H, Q = 2, 11, 16, 256, 128, 4, 2
    table = [[1, 2, 3, 7, 8], [4, 5, 9, 0, 0], [6, 10, 0, 0, 0]]
    poisoned = positions == "poisoned_tail"
    if isinstance(positions, str):
        table, positions = ragged_pages(S, Q, widen=4 if poisoned else 1)
    B = len(positions)
    k = jax.random.split(jax.random.PRNGKey(0), 4)
    pool = jax.random.normal(k[0], (L, P, S, W)).astype(jnp.bfloat16)
    q = jax.random.normal(k[1], (B, Q, H, W))
    new = jax.random.normal(k[2], (B, Q, W))
    table = jnp.asarray(table, jnp.int32)
    pos = jnp.asarray(positions, jnp.int32)
    profiler.reset_counters()
    run = functools.partial(kvcache.paged_latent_causal_attention, pool,
                            table, pos, 1, q, new, rank=R, scale=0.11)
    want = run()
    got = run(force_pallas=True)
    assert got.shape == (B, Q, H, R) and got.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-2, rtol=2e-2)
    counts = profiler.counters()
    assert counts["mla_verify_jnp"] == counts["mla_verify_pallas"] == 1
    if poisoned:
        bad = kvcache.paged_latent_causal_attention(
            pool.at[:, P - 1].set(jnp.nan), jnp.where(table == 0, P - 1,
                                                      table),
            pos, 1, q, new, rank=R, scale=0.11, force_pallas=True)
        assert bool(jnp.isfinite(bad).all()) and bool((bad == got).all())
    # query 0 of the pair is the one-query form; query 1 sees new row 0
    one = kvcache.paged_latent_attention(pool, table, pos, 1, q[:, 0],
                                         new[:, 0], rank=R, scale=0.11)
    np.testing.assert_allclose(np.asarray(want[:, 0]), np.asarray(one),
                               atol=1e-5)
    hidden = kvcache.paged_latent_causal_attention(
        pool, table, pos, 1, q, new.at[:, 0].add(3.0), rank=R, scale=0.11)
    assert np.abs(np.asarray(hidden[:, 1] - want[:, 1])).max() > 1e-3
    later = kvcache.paged_latent_causal_attention(
        pool, table, pos, 1, q, new.at[:, 1].add(3.0), rank=R, scale=0.11)
    np.testing.assert_allclose(np.asarray(later[:, 0]),
                               np.asarray(want[:, 0]), atol=1e-6)


@pytest.mark.parametrize("positions", [[40, 15, 0], [63, 31, 47]])
def test_row_pair_write_kernel_is_the_row_writes(positions):
    """Two rows a row, in place, where they straddle a page boundary
    (15 | 16, 31 | 32, 47 | 48, 63 | 64) and where they do not."""
    L, P, S, W, B = 3, 12, 16, 256, 3
    k = jax.random.split(jax.random.PRNGKey(1), 2)
    pool = jax.random.normal(k[0], (L, P, S, W)).astype(jnp.bfloat16)
    new = jax.random.normal(k[1], (L, B, 2, W))
    table = jnp.asarray([[1, 2, 3, 7, 8], [4, 5, 9, 0, 0], [6, 10, 11, 2, 0]],
                        jnp.int32)
    pos = jnp.asarray(positions, jnp.int32)
    profiler.reset_counters()
    want = kvcache.write_latent_rows(pool, table, pos, new)
    got = kvcache.write_latent_rows(pool, table, pos, new, force_pallas=True)
    assert (np.asarray(got) == np.asarray(want)).all()
    counts = profiler.counters()
    assert counts["latent_write2_jnp"] == counts["latent_write2_pallas"] == 1
    changed = (np.asarray(want) != np.asarray(pool)).any(axis=(0, 3))
    assert changed.sum() == 2 * B
    for b, p in enumerate(positions):
        for j in (p, p + 1):
            page = int(table[b, j // S])
            assert changed[page, j % S]
            assert (np.asarray(want[:, page, j % S])
                    == np.asarray(new[:, b, j - p].astype(jnp.bfloat16))).all()


@pytest.mark.parametrize("start,n_live", [(0, 11), (5, 11), (16, 4),
                                          (30, 11)])
def test_the_latent_layouts_chunk_is_the_causal_form_of_one_row(start,
                                                               n_live):
    """A chunk of ``C`` consecutive positions of ONE row beside a step's
    decode rows (``DecodeServer``'s mixed step): its lanes read what the
    causal block form reads for that row at ``Q = C`` — the row's pages
    before ``start`` and the chunk's own rows ``<= j`` (``gather_pages``
    and a dense causal softmax under it) — the decode rows what the
    one-query form gives them, and the write lands the live rows where
    ``write_latent_rows`` lands them, from inside a page and across
    boundaries, every other row of the pool as it was."""
    L, P, S, W, R, H, B, C = 2, 11, 16, 256, 128, 4, 2, 11
    k = jax.random.split(jax.random.PRNGKey(2), 5)
    pool = jax.random.normal(k[0], (L, P, S, W)).astype(jnp.bfloat16)
    q = jax.random.normal(k[1], (B + C, H, W))
    new = jax.random.normal(k[2], (B + C, W))
    tables = jnp.asarray([[1, 2, 3, 0], [4, 5, 0, 0]], jnp.int32)
    positions = jnp.asarray([40, 17], jnp.int32)
    row = jnp.asarray([9, 6, 10, 7], jnp.int32)
    layout = kvcache.cache_layout((("kv", (W,)),), jnp.dtype(jnp.bfloat16))
    assert layout.chunks and type(layout).__name__ == "_Latent"
    attend = layout.attend_chunk((pool,), tables, positions, row,
                                 jnp.int32(start))
    for layer in range(L):
        got = attend(layer, q, new, rank=R, scale=0.11)
        assert got.shape == (B + C, H, R) and got.dtype == jnp.float32
        rows = kvcache.paged_latent_attention(
            pool, tables, positions, layer, q[:B], new[:B], rank=R,
            scale=0.11)
        np.testing.assert_array_equal(np.asarray(got[:B]), np.asarray(rows))
        want = kvcache.paged_latent_causal_attention(
            pool, row[None], jnp.asarray([start], jnp.int32), layer,
            q[None, B:], new[None, B:], rank=R, scale=0.11)[0]
        np.testing.assert_allclose(np.asarray(got[B:]), np.asarray(want),
                                   atol=2e-5, rtol=2e-5)
    rows = jax.random.normal(k[3], (L, C, W))
    got, = layout.write_chunk((pool,), row, jnp.int32(start),
                              jnp.int32(n_live), [rows])
    want = kvcache.write_latent_rows(
        pool, row[None], jnp.asarray([start], jnp.int32),
        rows[:, None, :n_live])
    assert got.dtype == pool.dtype
    assert (np.asarray(got) == np.asarray(want)).all()
    changed = (np.asarray(got) != np.asarray(pool)).any(axis=(0, 3))
    assert changed.sum() == n_live
