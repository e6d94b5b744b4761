"""``test_window_moe.py``, continued (a file of its own so that no file is
the floor of a ``--dist loadfile`` run): the model's pieces — RoPE by
layer type, the two head counts, a rung's padding, the shares — and the
two kernels, interpreted, against their jnp compositions. Model, sizes
and helpers are that file's, its autouse
``_clean_state`` among them (imported, it is this file's fixture too)."""
import functools
import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

from benchmark.reference import window_moe_lm as ref           # noqa: E402
from mxnet_tpu.parallel import moe, sharding_rules             # noqa: E402
from mxnet_tpu.serving import WindowMoEDecoderLM               # noqa: E402
from test_window_moe import (CFG, W, _clean_state,             # noqa: E402,F401
                             _model, _tokens, fa)


# ---------------------------------------------------------------------------
# the pieces: RoPE by layer type, the two head counts, the shares
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("kind,rot", [(ref.FULL, 8), (ref.SLIDING, 16)],
                         ids=["yarn_half_rotated", "plain_whole"])
def test_the_rope_tables_are_the_references(kind, rot):
    """YaRN over the first half of a head with cos and sin times the
    attention factor, plain RoPE over all of it: the program's table and
    rotation against the reference's, and the half that is passed
    through is."""
    model, _, cfg = _model()
    freqs, width, gain = model.rope[kind]
    want = ref.rope_table(cfg["rope_parameters"][kind], cfg["head_dim"])
    assert width == want[1] == rot and gain == want[2]
    np.testing.assert_allclose(freqs, want[0], rtol=1e-6)
    assert gain == (1.4852030263919618 if kind == ref.FULL else 1.0)
    x = jax.random.normal(jax.random.PRNGKey(0), (40, 3, 16))
    got = model._rotate(kind, x, jnp.arange(40))
    np.testing.assert_allclose(
        got, ref._rope(x, jnp.asarray(want[0]), rot, want[2]), atol=1e-5)
    assert bool((got[..., rot:] == x[..., rot:]).all())
    assert float(jnp.abs(got[1:, :, :rot] - x[1:, :, :rot]).max()) > 0.1


def test_the_published_tables_at_their_published_sizes():
    """``attention_factor`` is ``0.1 ln 128 + 1``; the slow half of the
    YaRN frequencies is slowed by the factor, the fast ones kept."""
    rp = {"rope_theta": 500000, "rope_type": "yarn", "factor": 128,
          "original_max_position_embeddings": 8192, "beta_slow": 1,
          "beta_fast": 32, "attention_factor": 1.4852030263919618,
          "partial_rotary_factor": 0.5}
    freqs, rot, gain = ref.rope_table(rp, 128)
    assert rot == 64 and len(freqs) == 32
    assert abs(gain - (0.1 * np.log(128) + 1)) < 1e-12
    plain = 500000.0 ** (-np.arange(0, 64, 2) / 64)
    np.testing.assert_allclose(freqs[0], plain[0], rtol=1e-6)
    np.testing.assert_allclose(freqs[-1], plain[-1] / 128, rtol=1e-6)
    model = WindowMoEDecoderLM(**dict(
        CFG, head_dim=128, rope_parameters=dict(
            CFG["rope_parameters"], full_attention=rp)))
    np.testing.assert_allclose(model.rope[ref.FULL][0], freqs, rtol=1e-6)


def test_the_two_head_counts_project_to_their_own_shapes():
    model, params, cfg = _model()
    D, d, kv = cfg["hidden_size"], cfg["head_dim"], 2
    assert model.heads == (4, 6, 6, 6, 4)
    assert (model.cache_layers, model.state_layers) == (2, 3)
    assert [model.cache_layer(i) for i in range(5)] \
        == [0, None, None, None, 1]
    assert [model.state_layer(i) for i in (1, 2, 3)] == [0, 1, 2]
    for i, H in enumerate(model.heads):
        l = "l%d." % i
        assert params[l + "wq"].shape == (D, H * d)
        assert params[l + "wo"].shape == (H * d, D)
        assert params[l + "wg"].shape == (D, H)
        assert params[l + "wk"].shape == params[l + "wv"].shape \
            == (D, kv * d)
    assert "l0.w_gate" in params and "l0.router_w" not in params
    assert params["l1.router_w"].dtype == jnp.float32
    assert params["l1.router_w"].shape == (D, 8)
    assert model.state_arrays == (("ring_k", (W, kv * d), "float32"),
                                  ("ring_v", (W, kv * d), "float32"))


def test_a_rungs_padding_chooses_no_expert():
    """A prompt of 5 on a rung of 64: the 59 padded positions hold one
    token and would all pile onto one choice of experts; they are sent to
    none (the expert layer is handed 5 x top-3 slots, not 64 x 3), and
    the true positions' logits and the rings are what they are without
    the padding."""
    model, params, _ = _model()
    tokens = np.zeros((1, 64), np.int32)
    tokens[0, :5] = _tokens(7, 5)
    seen = []
    was = moe.expert_ffn

    def counting(x, weights, topi, topw, held, **kw):
        seen.append(int((np.asarray(topi) < model.n_experts).sum()))
        return was(x, weights, topi, topw, held, **kw)

    moe.expert_ffn = counting
    try:
        padded = model.prefill(params, tokens, jnp.asarray([5]))
        short = model.prefill(params, tokens[:, :8], jnp.asarray([5]))
    finally:
        moe.expert_ffn = was
    assert seen == [5 * 3] * 8            # 4 expert layers, two prefills
    np.testing.assert_allclose(padded[0][0, :5], short[0][0, :5], atol=1e-5)
    for a, b in zip(padded[3:], short[3:]):
        np.testing.assert_allclose(a[:, :, :5], b[:, :, :5], atol=1e-5)


def test_the_four_shares_add_up_to_the_uncut_layer():
    """Expert parallelism's contract at a small size: the 4 shares'
    routed parts, and the shared expert counted ONCE, add up to what the
    uncut reference gives for the whole layer."""
    model, params, cfg = _model()
    assert model.held == (0, 8)
    x = jax.random.normal(jax.random.PRNGKey(5), (24, cfg["hidden_size"]))
    whole, _ = ref.moe_layer(x, params, "l1.", cfg, (0, 8))
    topi, topw = moe.route_softmax_topk(x, params["l1.router_w"], top_k=3)
    shared = model._gated(x, params, "l1.shared.")
    total = shared
    for rank in range(4):
        lo, hi = sharding_rules.held_experts(8, 4, rank)
        share = {n: params["l1.experts." + n][lo:hi]
                 for n in ("w_gate", "w_up", "w_down")}
        total = total + moe.expert_ffn(x, share, topi, topw * 2.5, (lo, hi))
    assert np.abs(np.asarray(total - whole)).max() \
        / np.asarray(whole).std() < 1e-3
    one = shared + moe.expert_ffn(
        x, {n: params["l1.experts." + n][:2]
            for n in ("w_gate", "w_up", "w_down")}, topi, topw * 2.5, (0, 2))
    assert np.abs(np.asarray(one - whole)).max() \
        / np.asarray(whole).std() > 0.3
    # the chip's share of the published axis
    assert sharding_rules.held_experts(256, 4, 0) == (0, 64)
    # and the program's own share is the reference's on the same share
    part = WindowMoEDecoderLM(**dict(CFG, ep=(1, 4)))
    assert part.held == (2, 4)
    mine, _ = part._ffn(1, x, {**params, **{
        "l1.experts." + n: params["l1.experts." + n][2:4]
        for n in ("w_gate", "w_up", "w_down")}})
    theirs, _ = ref.moe_layer(x, {**params, **{
        "l1.experts." + n: params["l1.experts." + n][2:4]
        for n in ("w_gate", "w_up", "w_down")}}, "l1.", cfg, (2, 4))
    assert np.abs(np.asarray(mine - theirs)).max() \
        / np.asarray(theirs).std() < 1e-3


# ---------------------------------------------------------------------------
# the kernels, interpreted, against their jnp compositions
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("position", [0, 510, 511, 512, 5000],
                         ids=["count1", "count511", "count512", "wrapped",
                              "wrapped_far"])
def test_ring_decode_kernel_is_its_jnp_composition(position):
    """``mx_ring_decode`` with 9 query heads a key head over a ring of
    512: the valid slots follow from the position alone (1 key with its
    own at position 0, 511 and 512, then a full ring whose slot ``p %
    512`` is the one overwritten), the output and BOTH rings equal the
    composition's, the step's key lands in slot ``p % 512`` of the row's
    own ring, and a row that is not live changes nothing."""
    B, Hkv, G, D, Wd, rows = 3, 2, 9, 128, 512, 4
    k = jax.random.split(jax.random.PRNGKey(position), 5)
    ring_k = jax.random.normal(k[0], (2, rows, Wd, Hkv * D))
    ring_v = jax.random.normal(k[1], (2, rows, Wd, Hkv * D))
    q = jax.random.normal(k[2], (B, Hkv * G, D))
    k_new = jax.random.normal(k[3], (B, Hkv, D))
    v_new = jax.random.normal(k[4], (B, Hkv, D))
    slots = jnp.asarray([2, 0, 3], jnp.int32)
    pos = jnp.asarray([position, position + 700, 77], jnp.int32)
    live = jnp.asarray([True, True, False])
    args = (q, k_new, v_new, ring_k, ring_v, 1, slots, pos, live)
    o_j, k_j, v_j = fa.ring_decode(*args)
    o_p, k_p, v_p = fa.ring_decode(*args, force_pallas=True)
    assert np.abs(np.asarray(o_j - o_p))[:2].max() < 1e-5
    assert bool((k_j == k_p).all()) and bool((v_j == v_p).all())
    at = position % Wd
    assert bool((k_p[1, 2, at] == k_new[0].reshape(-1)).all())
    changed = np.asarray((k_p != ring_k).any(-1))
    assert changed.sum() == 2 and changed[1, 2, at] \
        and changed[1, 0, (position + 700) % Wd]
    # by hand: what position ``position`` may see of its ring
    seen = np.arange(Wd) < position
    seen[at] = False
    assert seen.sum() == min(position, Wd - 1)
    keys = np.concatenate([np.asarray(ring_k[1, 2]).reshape(Wd, Hkv, D)[
        seen], np.asarray(k_new[0])[None]])
    vals = np.concatenate([np.asarray(ring_v[1, 2]).reshape(Wd, Hkv, D)[
        seen], np.asarray(v_new[0])[None]])
    qh = np.asarray(q[0]).reshape(Hkv, G, D) / np.sqrt(D)
    s = np.einsum("hgd,khd->hgk", qh, keys)
    p = np.exp(s - s.max(-1, keepdims=True))
    want = np.einsum("hgk,khd->hgd", p / p.sum(-1, keepdims=True), vals)
    assert np.abs(want.reshape(Hkv * G, D) - np.asarray(o_p[0])).max() < 1e-4


@pytest.mark.parametrize("heads,window", [(72, 512), (48, None), (48, 200)],
                         ids=["72over8_banded", "48over8_full",
                              "48over8_window200"])
def test_grouped_forward_kernel_is_its_jnp_composition(heads, window):
    """``mx_grouped_fwd``, banded under a window, with the published head
    mappings over 768 positions against the composition and against the
    plain masked softmax over repeated key heads; under a window of 512
    the grid names 3 key blocks of 256 a query block, not all."""
    T, Hkv, D = 768, 8, 128
    k = jax.random.split(jax.random.PRNGKey(heads), 3)
    q = jax.random.normal(k[0], (1, T, heads, D))
    kk = jax.random.normal(k[1], (1, T, Hkv, D))
    v = jax.random.normal(k[2], (1, T, Hkv, D))
    got = fa.flash_attention(q, kk, v, causal=True, window=window,
                             force_pallas=True, block_q=256, block_k=256)
    comp = fa.flash_attention(q, kk, v, causal=True, window=window)
    assert np.abs(np.asarray(got - comp)).max() < 2e-5
    G = heads // Hkv
    s = jnp.einsum("bqhd,bkhd->bhqk", q, jnp.repeat(kk, G, axis=2)) \
        / np.sqrt(D)
    at = np.arange(T)
    seen = at[None, :] <= at[:, None]
    if window:
        seen &= at[None, :] > at[:, None] - window
    want = jnp.einsum("bhqk,bkhd->bqhd",
                      jax.nn.softmax(jnp.where(seen, s, -1e30), -1),
                      jnp.repeat(v, G, axis=2))
    assert np.abs(np.asarray(got - want)).max() < 2e-5
    jaxpr = str(jax.make_jaxpr(lambda *a: fa.flash_attention(
        *a, causal=True, window=window, force_pallas=True, block_q=256,
        block_k=256))(q, kk, v))
    name = "mx_grouped_fwd.bh%d.q768.k768.d128.float32.kv8%s" % (
        heads, ".w%d" % window if window else "")
    assert name in jaxpr
    # the band's steps: 3 key blocks of 256 under a window of 512 (of 3:
    # 768 positions are too few to tell), 2 under one of 200, all 3
    # without one
    steps = {512: 3, 200: 2, None: 3}[window]
    assert re.search(r"grid=\(%d, 3, %d\)" % (heads, steps), jaxpr), \
        re.findall(r"grid=\([^)]*\)", jaxpr)


def _dense_window_attention(q, k, v, window):
    """Sliding-window attention over a whole sequence by a masked
    softmax, in float64: ``q (T, Hq, D)``, ``k``/``v (T, Hkv, D)``."""
    q, k, v = (np.asarray(a, np.float64) for a in (q, k, v))
    T, Hq, D = q.shape
    G = Hq // k.shape[1]
    s = np.einsum("qhd,khd->hqk", q, np.repeat(k, G, axis=1)) / np.sqrt(D)
    at = np.arange(T)
    seen = (at[None, :] <= at[:, None]) & (at[None, :] > at[:, None] - window)
    s = np.where(seen, s, -np.inf)
    p = np.exp(s - s.max(-1, keepdims=True))
    return np.einsum("hqk,khd->qhd", p / p.sum(-1, keepdims=True),
                     np.repeat(v, G, axis=1))


@pytest.mark.parametrize("path,D,Wd,C,T", [
    ("jnp", 16, 8, 16, 5), ("jnp", 16, 8, 16, 8), ("jnp", 16, 8, 16, 29),
    ("jnp", 16, 8, 8, 27), ("jnp", 16, 8, 8, 40), ("jnp", 16, 8, 3, 22),
    ("pallas", 128, 128, 128, 300), ("pallas", 128, 128, 256, 300),
    ("pallas", 128, 128, 64, 200), ("pallas", 128, 128, 128, 100)],
    ids=["jnp-C2W-shorter", "jnp-C2W-equal", "jnp-C2W-3x", "jnp-CisW-3x",
         "jnp-CisW-5x", "jnp-CunderW", "pallas-CisW", "pallas-C2W",
         "pallas-CunderW", "pallas-shorter"])
def test_ring_chunk_is_the_window_over_the_whole_prompt(path, D, Wd, C, T):
    """A prompt of ``T`` positions fed ``C`` lanes a call through
    ``ring_chunk`` (the banded grouped forward with the queries offset
    behind the ring's ``W`` keys; Pallas interpreted, or its ``jnp``
    composition), the last chunk short: every position's output is the
    masked softmax over the whole prompt, whatever ``C`` is against
    ``W``; the row's ring holds, after each call, the last ``W`` positions
    in slots ``t % W`` and what no position reached is what the slot's
    last tenant left — 1e4 times a key's size, so one stale key read
    would show; no other row, no other layer is touched; and the kernel
    is its composition."""
    Hkv, G, rows, layer, slot = 2, 3, 3, 1, 2
    keys = jax.random.split(jax.random.PRNGKey(T + C), 5)
    q = jax.random.normal(keys[0], (T, Hkv * G, D))
    k = jax.random.normal(keys[1], (T, Hkv, D))
    v = jax.random.normal(keys[2], (T, Hkv, D))
    stale_k = 1e4 * jax.random.normal(keys[3], (2, rows, Wd, Hkv * D))
    stale_v = 1e4 * jax.random.normal(keys[4], (2, rows, Wd, Hkv * D))
    want = _dense_window_attention(q, k, v, Wd)
    ring_k, ring_v = stale_k, stale_v
    run = jax.jit(functools.partial(fa.ring_chunk, layer=layer,
                                    force_pallas=path == "pallas"))
    for start in range(0, T, C):
        n = min(C, T - start)

        def lanes(a):
            return jnp.zeros((C,) + a.shape[1:]).at[:n].set(
                a[start:start + n])

        args = (lanes(q), lanes(k), lanes(v), ring_k, ring_v)
        how = dict(slot=jnp.int32(slot), start=jnp.int32(start),
                   n_live=jnp.int32(n))
        out, ring_k, ring_v = run(*args, **how)
        assert np.abs(np.asarray(out[:n]) - want[start:start + n]).max() \
            < 2e-5, (start, n)
        if path == "pallas":
            comp = fa.ring_chunk(*args, layer=layer, **how)
            assert np.abs(np.asarray(out[:n] - comp[0][:n])).max() < 2e-5
            assert bool((comp[1] == ring_k).all()) \
                and bool((comp[2] == ring_v).all())
        # the ring after ``start + n`` positions, slot by slot
        for ring, stale, seq in ((ring_k, stale_k, k), (ring_v, stale_v, v)):
            for s_ in range(Wd):
                held = [t for t in range(start + n) if t % Wd == s_]
                expect = np.asarray(seq[held[-1]]).reshape(-1) if held \
                    else np.asarray(stale[layer, slot, s_])
                assert (np.asarray(ring[layer, slot, s_]) == expect).all(), \
                    (start, s_)
            others = np.ones((2, rows), bool)
            others[layer, slot] = False
            assert (np.asarray(ring)[others] == np.asarray(stale)[others]) \
                .all()
    if path == "pallas":
        jaxpr = str(jax.make_jaxpr(lambda *a: fa.ring_chunk(
            *a, layer=layer, force_pallas=True, **how))(*args))
        assert "mx_grouped_fwd.bh%d.q%d.k%d.d128.float32.kv%d.w%d.o%d" % (
            Hkv * G, -(-C // 128) * 128, -(-(Wd + C) // 128) * 128, Hkv,
            Wd, Wd) in jaxpr


def test_grouped_forward_refuses_what_it_is_not_written_for():
    q = jnp.zeros((1, 16, 4, 8))
    kv = jnp.zeros((1, 16, 2, 8))
    with pytest.raises(ValueError, match="causal self-attention"):
        fa.flash_attention(q, kv, kv, causal=False)
    with pytest.raises(ValueError, match="causal self-attention"):
        fa.flash_attention(q, q, q, causal=True, window=4,
                           segment_ids=jnp.ones((1, 16), jnp.int32))
