"""Sliding-window layers whose last keys and values are a ring a row,
beside full-attention layers' pages, two counts of gated query heads over
one of key/value heads, softmax-routed experts and a shared one —
``serving.window_moe`` on ``DecodeServer``'s STATE form of the model
contract, the rings beside the pool (``serving.kvcache``) and the two new
kernels of ``parallel.flash_attention`` (ring decode, grouped and banded
forward; Pallas in interpret mode), against the benchmark's plain float32
reference (``benchmark/reference/window_moe_lm.py``: the window a mask
over whole sequences) at a small size with seeded weights."""
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
from mxnet_tpu import compile_watch, fault, telemetry          # noqa: E402
from mxnet_tpu.base import MXNetError                          # noqa: E402
from mxnet_tpu.parallel import moe, sharding_rules             # noqa: E402
from mxnet_tpu.serving import (DecodeServer, KVCachePool,       # noqa: E402
                               WindowMoEDecoderLM, kvcache, window_moe)

import mxnet_tpu.parallel  # noqa: E402,F401 — the package re-exports the
fa = sys.modules["mxnet_tpu.parallel.flash_attention"]  # function

W = 8                                   # the tiny window
CFG = dict(window_moe.tiny_config(), dtype="float32")
# heads of 128 and a window of 128: what the Pallas kernels tile; 9 and 6
# query heads a key/value head, as published
WIDE = dict(CFG, head_dim=128, num_key_value_heads=1, sliding_window=128,
            num_attention_heads=6,
            num_attention_heads_per_layer=[6, 9, 9, 9, 6],
            num_hidden_layers=3)


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
def _model(use_pallas=False, seed=3, cls=WindowMoEDecoderLM, **over):
    cfg = dict(WIDE if use_pallas else CFG, **over)
    model = cls(**cfg, use_pallas=use_pallas)
    return model, model.init_params(seed=seed), cfg


def _server(model, params, **kw):
    kw = {"seq_ladder": [16, 64], "max_new_tokens": 32, "page_size": 8,
          "window": 4, "pool_pages": 96, "start": False,
          "prefix_cache": False, **kw}
    return DecodeServer(model, params, **kw)


def _drain(srv, *reqs, limit=2000):
    n = 0
    while not all(r.done() for r in reqs):
        srv._tick()
        n += 1
        assert n < limit, "scheduler made no progress"


def _tokens(seed, n, vocab=96):
    return np.random.default_rng(seed).integers(0, vocab, size=n) \
        .astype(np.int32)


@functools.lru_cache(maxsize=None)
def _jit_prefill(model):
    return jax.jit(model.prefill)


def _pool_for(model, seqs, page_size):
    """A pool with a row of the state arrays and a run of pages a
    sequence: ``(its arrays, its layout, the page tables (rows, pages a
    row))``."""
    rows = len(seqs)
    per_row = -(-max(len(t) for t, _ in seqs) // page_size)
    state, layers = kvcache.declared_state(model)
    pool = KVCachePool(model.cache_layers,
                       arrays=[c[:2] for c in model.cache_arrays],
                       dtype=model.cache_arrays[0][2], page_size=page_size,
                       n_pages=rows * per_row + 1, state=state,
                       state_layers=layers, state_rows=rows)
    assert pool.layout is kvcache.layout_for(model, pool.arrays)
    return tuple(pool.arrays), pool.layout, 1 + np.arange(
        rows * per_row, dtype=np.int32).reshape(rows, per_row)


def _served_logits(model, params, seqs, page_size=8, slots=None):
    """Logits from the SERVING path for several sequences at once,
    ``seqs = [(tokens, n_prompt), ...]``, one a row of the window: a
    prefill a sequence — its keys and values written into the paged pool
    and its rings WHOLE into its slot — then one decode step a token over
    ALL rows together through the layout's own ``attend``, row state and
    writes, a row live while it has tokens left: what ``DecodeServer``'s
    two state programs compute, with the logits kept. Returns, a
    sequence, ``(prompt logits (n_prompt, V), step logits (L - n_prompt,
    V))``."""
    rows = len(seqs)
    slots = list(slots or range(rows))
    pools, layout, tables = _pool_for(model, seqs, page_size)
    n_pages = len(layout.specs)

    def prefill(pools, tokens, n_prompt, table, slot):
        rung = -(-n_prompt // page_size) * page_size
        padded = np.zeros((1, rung), np.int32)
        padded[0, :n_prompt] = tokens[:n_prompt]
        logits, *out = _jit_prefill(model)(
            params, padded, jnp.asarray([n_prompt]))
        return logits[0, :n_prompt], (
            *layout.write_prefill(pools, table, out[:n_pages], n_prompt),
            *layout.write_state(pools, slot, out[n_pages:], True))

    @jax.jit
    def step(pools, toks, poss, n_live):
        attend = layout.attend(pools, tables, poss)
        state = layout.row_state(pools, jnp.asarray(slots, jnp.int32),
                                 jnp.arange(rows) < n_live)
        logits, *new = model.decode(params, toks, poss, attend, state)
        return logits, (
            *layout.write_tokens(pools, tables, poss, new[:n_pages],
                                 model.use_pallas),
            *new[n_pages:n_pages + len(state.arrays)])

    # the rows that decode longest come first: the live rows of a step
    # are its first
    order = sorted(range(rows), key=lambda r: len(seqs[r][0]) - seqs[r][1],
                   reverse=True)
    assert order == list(range(rows)), "give the longest answers first"
    heads, tails = [], [[] for _ in seqs]
    for r, (tokens, n_prompt) in enumerate(seqs):
        first, pools = prefill(pools, tokens, n_prompt, tables[r], slots[r])
        heads.append(np.asarray(first))
    for i in range(max(len(t) - n for t, n in seqs)):
        live = [r for r, (t, n) in enumerate(seqs) if n + i < len(t)]
        toks = np.zeros((rows,), np.int32)
        poss = np.zeros((rows,), np.int32)
        for r in live:
            toks[r], poss[r] = seqs[r][0][seqs[r][1] + i], seqs[r][1] + i
        logits, pools = step(pools, toks, poss, len(live))
        for r in live:
            tails[r].append(np.asarray(logits[r]))
    return [(h, np.stack(t) if t else np.zeros((0, h.shape[1])))
            for h, t in zip(heads, tails)]


def _chunked_logits(model, params, seqs, C, page_size=8, slots=None):
    """:func:`_served_logits` with every prompt fed in CHUNKS of ``C``
    lanes beside the rows that decode, as ``DecodeServer``'s mixed state
    program runs them (``_state_decode_fn_chunk``, with the logits of
    every lane kept): the requests are admitted in order, every step
    carries the next chunk of the head-most prompt still pending (FIFO,
    one request's chunk a step) on ``C`` lanes behind the window's rows,
    the rows whose prompt is in decode a token each — the live rows
    first, a pending request's row of the rings behind them, not live.
    Returns what :func:`_served_logits` does, and a request's own row of
    the rings as its last chunk left it."""
    rows = len(seqs)
    slots = list(slots or range(rows))
    pools, layout, tables = _pool_for(model, seqs, page_size)
    assert layout.chunks and model.chunk_lanes
    n_pages, n_state = len(layout.specs), len(layout.state)

    @jax.jit
    def step(pools, toks, poss, pts, order, n_live, fed, table, start, n,
             slot):
        attend = layout.attend_chunk(pools, pts, poss, table, start)
        state = layout.row_state(pools, order, jnp.arange(rows) < n_live)
        lanes = jnp.arange(C, dtype=jnp.int32)
        logits, *new = model.decode(
            params, jnp.concatenate([toks, fed]),
            jnp.concatenate([poss, start + lanes]), attend, state,
            head=jnp.arange(rows + C),
            live=jnp.concatenate([state.live, lanes < n]),
            chunk=(slot, start, n))
        held = tuple(new[n_pages:n_pages + len(state.arrays)])
        pages = layout.write_tokens(
            pools, pts, poss, [a[:, :rows] for a in new[:n_pages]],
            model.use_pallas)
        pages = layout.write_chunk(pages + held, table, start, n,
                                   [a[:, rows:] for a in new[:n_pages]])
        return logits, (*pages, *held)

    fed_to = [0] * rows                   # prompt positions fed, a request
    done = [n for _t, n in seqs]          # positions run in all
    heads, tails = [[] for _ in seqs], [[] for _ in seqs]
    rings = [None] * rows
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
        logits, pools = step(
            pools, toks, poss, pts,
            np.asarray([slots[o] for o in order], np.int32), len(decoding),
            fed, tables[r], start, n, slots[r])
        for i, d in enumerate(decoding):
            tails[d].append(np.asarray(logits[i]))
            done[d] += 1
        if pending:
            heads[r].append(np.asarray(logits[rows:rows + n]))
            fed_to[r] += n
            if fed_to[r] == seqs[r][1]:
                rings[r] = [np.asarray(a[:, slots[r]])
                            for a in pools[-n_state:]]
    return [(np.concatenate(h), np.stack(t) if t
             else np.zeros((0, h[0].shape[1])))
            for h, t in zip(heads, tails)], rings


def _reference(params, tokens, cfg, held, control=None, **over):
    return ref.logits_rows(params, jnp.asarray(tokens), 0, len(tokens),
                           dict(cfg, **over), held, control=control)


def _worst(got, want):
    """The widest distance of a position's logits from the reference's,
    in deviations of the reference's logits, a position."""
    return np.abs(got - want).max(axis=1) / want.std()


def _against_reference(model, params, cfg, seqs, **kw):
    served = _served_logits(model, params, seqs, **kw)
    errs = []
    for (tokens, n_prompt), (head, tail) in zip(seqs, served):
        want = _reference(params, tokens, cfg, model.held)
        # position n_prompt - 1 predicts the first served token and every
        # step's logits the next: the steps' rows follow the prompt's
        errs.append(_worst(np.concatenate([head, tail]), want))
    return errs


# Matrices, pages and rings are float32 here and so is the reference:
# what separates them is float32 rounding in another order — a ring's
# slots against a masked row of scores, the grouped matmul against a
# loop, the flash kernel's running softmax against one softmax — a few
# 1e-6 of a logit's deviation a product, a few dozen products deep: a
# position's worst logit lies within 3e-5 deviations here. The limit is
# 1e-3, thirty times that (a router's near-tie that flips costs a whole
# expert, about a deviation: at float32 none does in these sequences); a
# window one key short or long moves most positions by 0.02 and more.
LOGIT_TOLERANCE = 1e-3


@pytest.mark.parametrize("n_prompt", [5, W, 3 * W + 3, 5 * W],
                         ids=["shorter", "equal", "3x", "5x"])
def test_prefill_then_decode_is_the_reference_at_every_position(n_prompt):
    """A prompt shorter than, equal to and 3-5 times the window, then 30
    decoded positions: the ring fills, wraps several times, and the short
    row crosses the window's edge mid-answer. EVERY position's logits —
    the prompt's from the prefill, the answer's a step each — against the
    reference's one forward over the whole sequence."""
    model, params, cfg = _model()
    tokens = _tokens(n_prompt, n_prompt + 30)
    err, = _against_reference(model, params, cfg, [(tokens, n_prompt)])
    assert len(err) == len(tokens) and err.max() < LOGIT_TOLERANCE, err


@pytest.mark.parametrize("n_prompt", [100, 300], ids=["inside", "wrapped"])
def test_the_pallas_path_is_the_reference_at_every_position(n_prompt):
    """The same through the kernels (interpreted): the grouped forward, banded and
    full, in the prefill, ``mx_ring_decode`` with 9 query heads
    a key head and the paged block kernel with 6 in the step, a window of
    128 that the prompt lies inside, or has wrapped twice."""
    model, params, cfg = _model(use_pallas=True)
    tokens = _tokens(n_prompt, n_prompt + 36)
    err, = _against_reference(model, params, cfg, [(tokens, n_prompt)],
                              page_size=128)
    assert err.max() < LOGIT_TOLERANCE, err


@pytest.mark.parametrize("n_prompt,C", [
    (5, 16), (W, 16), (3 * W + 3, 16), (5 * W, 16), (16, 16), (3 * W + 3, W),
    (5 * W, W), (3 * W + 3, 3)],
    ids=["shorter-C2W", "equal-C2W", "3x-C2W", "5x-C2W", "one_chunk-C2W",
         "3x-CisW", "5x-CisW", "3x-CunderW"])
def test_chunks_then_decode_are_the_reference_at_every_position(n_prompt, C):
    """The prompt through the mixed step's chunk lanes — shorter than,
    equal to and 3-5 times the window, a length that is no multiple of
    the chunk and one that is, ``C`` twice the window, the window, and
    under it — then 30 decoded positions: EVERY position's logits are the
    reference's, and the whole-prompt prefill's (the oracle), and the
    rings the chunks leave are the rings the prefill writes, slot for
    slot, wherever a position has reached."""
    model, params, cfg = _model()
    tokens = _tokens(n_prompt, n_prompt + 30)
    ((head, tail),), (rings,) = _chunked_logits(
        model, params, [(tokens, n_prompt)], C)
    want = _reference(params, tokens, cfg, model.held)
    err = _worst(np.concatenate([head, tail]), want)
    assert len(err) == len(tokens) and err.max() < LOGIT_TOLERANCE, err
    (o_head, o_tail), = _served_logits(model, params, [(tokens, n_prompt)])
    assert _worst(head, o_head).max() < LOGIT_TOLERANCE
    assert _worst(tail, o_tail).max() < LOGIT_TOLERANCE
    # the rings as the last chunk left them, against the prefill's
    padded = np.zeros((1, 64), np.int32)
    padded[0, :n_prompt] = tokens[:n_prompt]
    whole = _jit_prefill(model)(params, padded, jnp.asarray([n_prompt]))[3:]
    reached = np.arange(W) < n_prompt
    for got, want_ring in zip(rings, whole):
        assert np.abs(got - np.asarray(want_ring[:, 0]))[
            :, reached].max() < 1e-5


def test_chunks_beside_rows_on_both_sides_of_the_window():
    """Four requests admitted in order over a window of four, each on a
    ring of its own (the slots a permutation): while one prompt's chunks
    ride the step, the prompts before it decode — a row well past the
    window, one that crosses its edge mid-answer, one that never reaches
    it — and the prompt behind it waits, its row of the rings not live.
    Every position of every request is the reference's."""
    model, params, cfg = _model()
    seqs = [(_tokens(10, 4 * W + 22), 4 * W), (_tokens(11, 3 + 20), 3),
            (_tokens(12, W + 12), W), (_tokens(13, 2 * W + 5 + 4), 2 * W + 5)]
    served, _ = _chunked_logits(model, params, seqs, 16, slots=[2, 0, 3, 1])
    for (tokens, _n), (head, tail) in zip(seqs, served):
        err = _worst(np.concatenate([head, tail]),
                     _reference(params, tokens, cfg, model.held))
        assert err.max() < LOGIT_TOLERANCE, err


def test_the_pallas_path_through_chunks_is_the_reference():
    """The same through the kernels (interpreted): the offset banded
    grouped forward over ``[ring ; chunk]`` with 9 query heads a key head
    and a window of 128, ``mx_ring_decode`` and the paged block kernel for
    the rows beside it; a prompt of 300 wraps the ring twice, in chunks of
    128 lanes."""
    model, params, cfg = _model(use_pallas=True)
    tokens = _tokens(300, 300 + 12)
    (head, tail), = _chunked_logits(model, params, [(tokens, 300)], 128,
                                    page_size=128)[0]
    err = _worst(np.concatenate([head, tail]),
                 _reference(params, tokens, cfg, model.held))
    assert err.max() < LOGIT_TOLERANCE, err


def test_one_step_whose_rows_stand_on_both_sides_of_the_window():
    """Four rows in one window: one well past the window, one that
    crosses its edge mid-answer, one that never reaches it, one exactly
    on it — every step runs them together, each on a ring of its own (the
    slots a permutation), and each is the reference's row."""
    model, params, cfg = _model()
    seqs = [(_tokens(10, 4 * W + 22), 4 * W), (_tokens(11, 3 + 20), 3),
            (_tokens(12, W + 12), W), (_tokens(13, 2 + 4), 2)]
    errs = _against_reference(model, params, cfg, seqs, slots=[2, 0, 3, 1])
    assert max(e.max() for e in errs) < LOGIT_TOLERANCE, errs


def test_the_off_by_one_windows_and_a_late_ring_slot_each_fail():
    """The tolerance is tight enough for what this model adds: the
    reference with ``W - 1`` and with ``W + 1`` keys visible, and a
    program whose prefill leaves its ring one slot late, each fail it at
    most positions past the window."""
    model, params, cfg = _model()
    n_prompt = 3 * W + 3
    tokens = _tokens(n_prompt, n_prompt + 30)
    want = _reference(params, tokens, cfg, model.held)
    for off in (-1, 1):
        moved = _worst(_reference(params, tokens, cfg, model.held,
                                  sliding_window=W + off), want)
        assert np.median(moved[W:]) > 10 * LOGIT_TOLERANCE, (off, moved)
        assert moved[:W - 1].max() < LOGIT_TOLERANCE
    assert np.median(_worst(_reference(
        params, tokens, cfg, model.held, control="window_minus_one"),
        want)[W:]) > 10 * LOGIT_TOLERANCE

    class LateRing(WindowMoEDecoderLM):
        def _ring_of(self, seq, lengths):
            return jnp.roll(super()._ring_of(seq, lengths), 1, axis=1)

    late, _, _ = _model(cls=LateRing)
    (head, tail), = _served_logits(late, params, [(tokens, n_prompt)])
    assert _worst(head, want[:n_prompt]).max() < LOGIT_TOLERANCE
    assert np.median(_worst(tail, want[n_prompt:])) \
        > 10 * LOGIT_TOLERANCE


# At the configuration's own precisions — bfloat16 matrices, pages and
# rings, float32 accumulation — the MEDIAN position's worst logit lies
# 0.02 deviations from the float32 reference at these widths (bf16 keeps 8
# bits: 0.4% an operand, a few dozen products deep) and the same model
# with float8_e4m3fn pages and rings (3 bits of mantissa: 6% a key) 0.09-
# 0.11: the limit, 0.045, is their geometric middle. The median and not
# the widest position: a router's near-tie that flips under rounded
# operands swaps one expert of three, 0.8 deviations at one position of
# 57 in either precision.
BF16_TOLERANCE = 0.045


def test_bf16_passes_a_tolerance_that_float8_rings_and_pages_fail():
    n_prompt = 3 * W + 3
    tokens = _tokens(21, n_prompt + 30)
    errs = {}
    for cache in ("bfloat16", "float8_e4m3fn"):
        model, params, cfg = _model(dtype="bfloat16", cache_dtype=cache)
        errs[cache], = _against_reference(model, params, cfg,
                                          [(tokens, n_prompt)])
    assert np.median(errs["bfloat16"]) < BF16_TOLERANCE, errs
    assert np.median(errs["float8_e4m3fn"]) > BF16_TOLERANCE, errs
    assert np.percentile(errs["float8_e4m3fn"], 90) \
        > 3 * np.percentile(errs["bfloat16"], 90), errs


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
        return [np.asarray(a[:, 0]) for a in _jit_prefill(model)(
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
