"""``test_hyper_latent_moe.py``, continued (a file of its own so that no
file is the floor of a ``--dist loadfile`` run): the model against the
benchmark's plain float32 reference in logits, its programs at the
defaults (one stream, no module: the parent's), what a self-drafting
model refuses, and the Pallas kernels (interpret mode) against the jnp
paths. Model, sizes and helpers are that file's, its autouse
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
from mxnet_tpu import profiler                                 # noqa: E402
from mxnet_tpu.base import MXNetError                          # noqa: E402
from mxnet_tpu.serving import (DecodeServer, KVCachePool,       # noqa: E402
                               ToyDecoderLM, kvcache)
from mxnet_tpu.serving.block_diffusion import (                # noqa: E402
    BlockDiffusionMoEDecoderLM)
from mxnet_tpu.serving.latent_moe import LatentMoEDecoderLM    # noqa: E402
from serving_common import jit_prefill                         # noqa: E402
from test_hyper_latent_moe import (BASE, CFG, _clean_state,    # noqa: E402,F401
                                   _model, _plain, _prompts, _serve,
                                   _server)


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


# ---------------------------------------------------------------------------
# one stream and no module: the programs the parent ran
# ---------------------------------------------------------------------------

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
    full = np.asarray(jit_prefill(model)(params, padded)[0][0])
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
