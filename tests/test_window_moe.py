"""Sliding-window layers whose last keys and values are a ring a row,
beside full-attention layers' pages, two counts of gated query heads over
one of key/value heads, softmax-routed experts and a shared one —
``serving.window_moe`` on ``DecodeServer``'s STATE form of the model
contract, the rings beside the pool (``serving.kvcache``) and the two new
kernels of ``parallel.flash_attention`` (ring decode, grouped and banded
forward; Pallas in interpret mode), against the benchmark's plain float32
reference (``benchmark/reference/window_moe_lm.py``: the window a mask
over whole sequences) at a small size with seeded weights.

Here: the model, the serving path with the logits kept, and what the
benchmark's files cite under this name (the reference at every position,
the off-by-one windows). ``test_window_moe_kernels.py`` has the pieces and
the kernels, ``test_window_moe_server.py`` the server."""
import functools
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

from benchmark.reference import window_moe_lm as ref           # noqa: E402
from mxnet_tpu import compile_watch, fault, telemetry          # noqa: E402
from mxnet_tpu.serving import (DecodeServer, KVCachePool,       # noqa: E402
                               WindowMoEDecoderLM, kvcache, window_moe)
from serving_common import jit_prefill                         # noqa: E402

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


def _tokens(seed, n, vocab=96):
    return np.random.default_rng(seed).integers(0, vocab, size=n) \
        .astype(np.int32)


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


@functools.lru_cache(maxsize=None)
def _decode_step(model):
    """One decode step of every row through the layout's own ``attend``,
    row state and writes, jitted once a model: weights, page tables,
    slots and pools are arguments, so a (model, shape) compiles once
    however many cases ask for it."""
    @jax.jit
    def step(params, tables, slots, pools, toks, poss, n_live):
        layout = kvcache.layout_for(model, pools)
        n_pages = len(layout.specs)
        attend = layout.attend(pools, tables, poss)
        state = layout.row_state(pools, slots,
                                 jnp.arange(len(slots)) < n_live)
        logits, *new = model.decode(params, toks, poss, attend, state)
        return logits, (
            *layout.write_tokens(pools, tables, poss, new[:n_pages],
                                 model.use_pallas),
            *new[n_pages:n_pages + len(state.arrays)])
    return step


@functools.lru_cache(maxsize=None)
def _mixed_step(model):
    """One MIXED step — the window's rows a token each and one prompt's
    chunk on ``len(fed)`` lanes behind them — jitted once a model."""
    @jax.jit
    def step(params, pools, toks, poss, pts, order, n_live, fed, table,
             start, n, slot):
        layout = kvcache.layout_for(model, pools)
        n_pages, rows, C = len(layout.specs), len(toks), len(fed)
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
    return step


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
        logits, *out = jit_prefill(model)(
            params, padded, jnp.asarray([n_prompt]))
        return logits[0, :n_prompt], (
            *layout.write_prefill(pools, table, out[:n_pages], n_prompt),
            *layout.write_state(pools, slot, out[n_pages:], True))

    step = functools.partial(_decode_step(model), params, tables,
                             np.asarray(slots, np.int32))

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
    n_state = len(layout.state)

    step = functools.partial(_mixed_step(model), params)

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
    whole = jit_prefill(model)(params, padded, jnp.asarray([n_prompt]))[3:]
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
