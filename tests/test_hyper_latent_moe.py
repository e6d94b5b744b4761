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
programs are the parent's.

Here: the model, the constructed weights and what the benchmark's files
cite under this name (the mixing coefficients, the constructed-weights
streams, the Sinkhorn sums). ``test_hyper_latent_moe_model.py`` has the
logits, the defaults, the refusals and the kernels;
``test_hyper_latent_moe_streams.py`` the random-weights streams and the
server's loop."""
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
from mxnet_tpu import compile_watch, fault, telemetry          # noqa: E402
from mxnet_tpu.serving import DecodeServer                     # noqa: E402
from mxnet_tpu.serving.latent_moe import LatentMoEDecoderLM    # noqa: E402
from serving_common import drain as _drain                     # noqa: E402

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
# the model against the reference: the coefficients themselves (in logits:
# test_hyper_latent_moe_model.py)
# ---------------------------------------------------------------------------

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
# drafter says (on random weights: test_hyper_latent_moe_streams.py)
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
