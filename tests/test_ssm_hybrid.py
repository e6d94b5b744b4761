"""State-space layers whose selective scan keeps a fixed array a row,
beside the pages of a full-attention layer a period, over a dense MLP
with the head tied to the embedding — ``serving.ssm_hybrid`` on
``DecodeServer``'s STATE form of the model contract, the row state beside
the pool (``serving.kvcache``) and the selective-scan kernels
(``parallel.selective_scan``; Pallas in interpret mode), against the
benchmark's plain float32 reference
(``benchmark/reference/ssm_hybrid_lm.py``, the recurrence one token at a
time) at a small size with seeded weights; a prompt as chunks on a mixed
step's lanes, the scan from the row's state, against the whole-prompt
prefill and the same reference. Logits are compared, never sampled
tokens, but where a served stream is held to the model's own greedy
stream (one teacher-forced forward a request, compiled once a width by
``serving_common.jit_prefill``)."""
import functools
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

from benchmark.reference import ssm_hybrid_lm as ref            # noqa: E402
from mxnet_tpu import (compile_watch, fault, profiler,          # noqa: E402
                       telemetry)
from mxnet_tpu.base import MXNetError                           # noqa: E402
from mxnet_tpu.parallel import selective_scan                   # noqa: E402
from mxnet_tpu.serving import (DecodeServer, KVCachePool,        # noqa: E402
                               SSMHybridDecoderLM, kvcache)
from mxnet_tpu.serving.ssm_hybrid import DT_RANGE, tiny_config  # noqa: E402
from serving_common import drain, jit_prefill                   # noqa: E402

# the published block's shape at a test's size: six layers in a period of
# three (layers 1 and 4 attend, the other four are state-space), 4 query
# heads over ONE key/value head, 8 states a channel, a step rank of 8;
# float32 matrices, pages and convolution rows
CFG = dict(tiny_config(), vocab_size=256, hidden_size=64,
           intermediate_size=128, num_hidden_layers=6, attn_layer_period=3,
           attn_layer_offset=1, mamba_dt_rank=8, max_position_embeddings=512,
           dtype="float32")
# channels that fill a lane tile and 16 states: what the kernels tile
WIDE = dict(CFG, mamba_d_state=16)


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
def _model(use_pallas=False, seed=3, **over):
    cfg = dict(WIDE if use_pallas else CFG, **over)
    model = SSMHybridDecoderLM(**cfg, use_pallas=use_pallas)
    return model, model.init_params(seed=seed), cfg


# ---------------------------------------------------------------------------
# the recurrence: one token a row, and a chunk from the row's state
# ---------------------------------------------------------------------------

def _scan_inputs(T, E=128, N=16, seed=0):
    k = jax.random.split(jax.random.PRNGKey(seed), 5)
    delta = jax.nn.softplus(jax.random.normal(k[1], (T, E)) - 3.0)
    a = -jnp.exp(jax.random.normal(k[4], (N, E)))
    return (jax.random.normal(k[0], (T, E)), delta,
            jax.random.normal(k[2], (T, N)), jax.random.normal(k[3], (T, N)),
            a)


def _scrambled(B, seed):
    """``(slots, inverse, dead)``: a step's rows on scrambled slots of a
    window of ``B`` (row ``i`` works on slot ``slots[i]``; lane ``s`` of
    the slot order is row ``inverse[s]``), a third of the rows not
    live."""
    rng = np.random.default_rng(seed)
    slots = rng.permutation(B).astype(np.int32)
    inverse = np.argsort(slots).astype(np.int32)
    dead = np.zeros((B,), bool)
    dead[rng.permutation(B)[:max(1, B // 3)]] = True
    return slots, inverse, dead


@pytest.mark.parametrize("B", [6, 16, 24, 20], ids=[
    "one_block_of_6", "two_blocks", "three_blocks", "20_rows_one_block"])
def test_step_kernel_agrees_with_jnp_in_place_and_leaves_dead_rows(B):
    """The Pallas step (interpreted; blocks of 8 consecutive slots, or
    ONE block of the whole window where it is not whole blocks) against
    the jnp one and against the recurrence written by ROW through a
    scrambled ``slots`` — the lanes reach either in slot order, ``x[
    inverse]`` — on 2 state layers: the same outputs, a row's state
    updated where it lies, every other layer and the rows that are not
    live (``delta = 0``) exactly as they were."""
    E, N, layers = 256, 16, 2
    u, delta, b, c, a = _scan_inputs(B, E, N, seed=1)
    state = jax.random.normal(jax.random.PRNGKey(9), (layers, B, N, E))
    slots, inverse, dead = _scrambled(B, seed=B)
    delta = jnp.where(dead[:, None], 0.0, delta)
    # by row, through the slots: what the step computed before it walked
    # the state in slot order
    h = jnp.exp(delta[:, None, :] * a) * state[1, slots] \
        + b[:, :, None] * (delta * u)[:, None, :]
    y_row = jnp.sum(h * c[:, :, None], axis=1)
    s_row = state.at[1, slots].set(h)
    args = (state, 1, *(x[inverse] for x in (u, delta, b, c)), a)
    before = dict(profiler.counters())
    y_j, s_j = selective_scan.ssm_step(*args)
    y_p, s_p = jax.jit(functools.partial(
        selective_scan.ssm_step, force_pallas=True),
        static_argnums=(1,))(*args)
    assert profiler.counters()["ssm_step_jnp"] \
        == before.get("ssm_step_jnp", 0) + 1
    assert profiler.counters()["ssm_step_pallas"] \
        == before.get("ssm_step_pallas", 0) + 1
    # float32 in another order of operations: a few 1e-7 of the largest
    for y, s_ in ((y_j, s_j), (y_p, s_p)):
        assert np.abs(np.asarray(y[slots] - y_row)).max() < 1e-5 * float(
            np.abs(y_row).max())
        assert np.abs(np.asarray(s_ - s_row)).max() < 1e-5
        assert bool(jnp.isfinite(s_).all())
        assert bool((s_[0] == state[0]).all())             # another layer
        for row, gone in zip(slots, dead):
            same = bool((s_[1, row] == state[1, row]).all())
            assert same == bool(gone), row


@pytest.mark.parametrize("B", [6, 16, 32, 20], ids=[
    "one_block_of_6", "one_block", "two_blocks", "20_rows_one_block"])
@pytest.mark.parametrize("K", [4, 2])
def test_conv_kernel_shifts_the_rows_in_place_bit_for_bit(K, B):
    """``mx_ssm_conv`` (interpreted; blocks of 16 consecutive slots, or
    ONE block of the whole window where it is not whole blocks) against
    ``_jnp_conv_step`` and against the convolution written by ROW through
    a scrambled ``slots``, on bfloat16 rows of 2 state layers: the same
    float32 sums; a live slot's rows shifted by one with the step's input
    behind them, bit for bit; a dead slot — the chunk's request row is
    one: no live row of its step — and the other layer exactly as they
    were."""
    E, layers = 128, 2
    keys = jax.random.split(jax.random.PRNGKey(K * 100 + B), 3)
    conv = jax.random.normal(keys[0], (layers, B, (K - 1) * E)).astype(
        jnp.bfloat16)
    raw = jax.random.normal(keys[1], (B, E)).astype(jnp.bfloat16)
    w = jax.random.normal(keys[2], (K, E))
    slots, inverse, dead = _scrambled(B, seed=K + B)
    live = jnp.asarray(~dead)
    # by row, through the slots: the step's convolution before it walked
    # the plane in slot order
    held = conv[1, slots]
    window = jnp.concatenate([held.reshape(B, K - 1, E), raw[:, None]], 1)
    y_row = (w * window.astype(jnp.float32)).sum(1)
    after = jnp.where(live[:, None], window[:, 1:].reshape(B, -1), held)
    args = (conv, 1, raw[inverse], live[inverse], w)
    before = dict(profiler.counters())
    y_j, c_j = selective_scan.ssm_conv_step(*args)
    y_p, c_p = jax.jit(functools.partial(
        selective_scan.ssm_conv_step, force_pallas=True),
        static_argnums=(1,))(*args)
    assert profiler.counters()["ssm_conv_jnp"] \
        == before.get("ssm_conv_jnp", 0) + 1
    assert profiler.counters()["ssm_conv_pallas"] \
        == before.get("ssm_conv_pallas", 0) + 1
    for y, c_ in ((y_j, c_j), (y_p, c_p)):
        assert y.dtype == jnp.float32 and c_.dtype == jnp.bfloat16
        assert np.abs(np.asarray(y[slots] - y_row)).max() < 1e-6 * float(
            np.abs(y_row).max())
        assert bool((c_[1, slots] == after).all())         # bit for bit
        assert bool((c_[0] == conv[0]).all())              # another layer
        for row, gone in zip(slots, dead):
            assert bool((c_[1, row] == conv[1, row]).all()) == bool(gone)
    live_slots = slots[~dead]
    assert bool((c_p[1, live_slots, (K - 2) * E:] == raw[~dead]).all())
    assert bool((c_p[1, live_slots, :(K - 2) * E]
                 == conv[1, live_slots, E:]).all())


@pytest.mark.parametrize("use_pallas", [False, True], ids=["jnp", "pallas"])
@pytest.mark.parametrize("n", [24, 13], ids=["whole", "ragged"])
def test_a_chunk_is_its_steps_from_the_rows_state(use_pallas, n):
    """``ssm_chunk`` over 24 positions from a row's state against 24
    (or, ragged, 13) calls of ``ssm_step`` on it: every live position's
    ``y`` and the state after the last live lane — the lanes behind it
    leave the state as it was — and against the reference's ``lax.scan``
    from zeros."""
    C, E, N = 24, 128, 16
    u, delta, b, c, a = _scan_inputs(C, E, N)
    h0 = jax.random.normal(jax.random.PRNGKey(7), (N, E))
    y, S = jax.jit(functools.partial(
        selective_scan.ssm_chunk, force_pallas=use_pallas))(
        u, delta, b, c, a, h0, n)
    state = h0[None, None]
    for t in range(n):
        yt, state = selective_scan.ssm_step(
            state, 0, *(x[t:t + 1] for x in (u, delta, b, c)), a)
        assert np.abs(np.asarray(yt[0] - y[t])).max() < 1e-5 * float(
            np.abs(y).max()), t
    assert np.abs(np.asarray(S - state[0, 0])).max() < 1e-5 * float(
        np.abs(S).max())
    y0, _ = selective_scan.ssm_chunk(u, delta, b, c, a,
                                     force_pallas=use_pallas)
    want = ref.selective_scan(u, delta, b, c, a)
    assert np.abs(np.asarray(y0 - want)).max() < 1e-5 * float(
        np.abs(want).max())


def test_the_steps_draw_remembers():
    """``A_log``, ``b_dt`` and ``W_dt`` as ``init_params`` draws them: a
    step's decay ``exp(delta A)`` spans from under 0.5 (the last state of
    a fast channel) to over 0.995 (the first of a slow one) with its
    median above 0.8 — a state that forgot in two tokens would hide a
    wrong slot or a lost state — and a dead lane's is exactly 1."""
    model, params, _ = _model()
    x = jax.random.normal(jax.random.PRNGKey(2), (64, model.d_inner))
    _u, delta, _b, _c = model._scan_inputs(0, x, params,
                                           jnp.ones((64,), bool))
    step = np.asarray(delta)
    assert DT_RANGE[0] / 10 < step.min() and step.max() < DT_RANGE[1] * 30
    decay = np.exp(step[:, None, :]
                   * -np.exp(np.asarray(params["l0.A_log"]))[None])
    assert 0.8 < np.median(decay) < 0.999
    assert np.percentile(decay, 1) < 0.5 and np.percentile(decay, 99) > 0.995
    _u, delta, _b, _c = model._scan_inputs(0, x, params,
                                           jnp.zeros((64,), bool))
    assert float(jnp.abs(delta).max()) == 0.0
    assert params["embed"] is not None and "head" not in params


# ---------------------------------------------------------------------------
# the model against the reference
# ---------------------------------------------------------------------------

def _pool(model, n_pages, page_size=16, window=4):
    state, layers = kvcache.declared_state(model)
    return KVCachePool(model.cache_layers,
                       arrays=[c[:2] for c in model.cache_arrays],
                       dtype=model.cache_arrays[0][2], page_size=page_size,
                       n_pages=n_pages + 1, state=state, state_layers=layers,
                       state_rows=window)


@functools.lru_cache(maxsize=None)
def _steps(model, by_row=False):
    """``(the plain step, the MIXED step)`` through the layout's own
    ``attend``, row state and writes, as ``DecodeServer``'s two state
    step programs run them (the mixed one with the logits of every lane
    kept), jitted once a model; ``by_row``: through
    :func:`_row_order_decode` in the model's place."""
    decode = functools.partial(_row_order_decode, model) if by_row \
        else model.decode

    @jax.jit
    def plain(params, pools, toks, poss, pts, order, n_live):
        layout = kvcache.layout_for(model, pools)
        attend = layout.attend(pools, pts, poss)
        state = layout.row_state(pools, order,
                                 jnp.arange(len(order)) < n_live)
        logits, k, v, *held = decode(params, toks, poss, attend, state)
        return logits, (*layout.write_tokens(pools, pts, poss, [k, v],
                                             model.use_pallas), *held)

    @jax.jit
    def mixed(params, pools, toks, poss, pts, order, n_live, fed, table,
              start, n, slot):
        layout = kvcache.layout_for(model, pools)
        rows, C = len(toks), len(fed)
        attend = layout.attend_chunk(pools, pts, poss, table, start)
        state = layout.row_state(pools, order, jnp.arange(rows) < n_live)
        lanes = jnp.arange(C, dtype=jnp.int32)
        logits, k, v, *held = decode(
            params, jnp.concatenate([toks, fed]),
            jnp.concatenate([poss, start + lanes]), attend, state,
            head=jnp.arange(rows + C),
            live=jnp.concatenate([state.live, lanes < n]),
            chunk=(slot, start, n))
        held = tuple(held)
        pages = layout.write_tokens(pools, pts, poss,
                                    [k[:, :rows], v[:, :rows]],
                                    model.use_pallas)
        pages = layout.write_chunk(pages + held, table, start, n,
                                   [k[:, rows:], v[:, rows:]])
        return logits, (*pages, *held)

    return plain, mixed


def _decode_from(model, params, pools, tokens, first, table, slot,
                 window=4):
    """One plain step a token of ``tokens[first:]`` on row ``slot``, the
    only live row of a window of ``window``, from ``pools`` as a prefill
    or a prompt's chunks left them: ``(the steps' logits, pools)``."""
    plain = _steps(model)[0]
    order = np.asarray([slot] + [r for r in range(window) if r != slot],
                       np.int32)
    pts = np.zeros((window, len(table)), np.int32)
    pts[0] = table
    out = []
    for pos in range(first, len(tokens)):
        toks, poss = (np.zeros((window,), np.int32) for _ in range(2))
        toks[0], poss[0] = tokens[pos], pos
        logits, pools = plain(params, pools, toks, poss, pts, order, 1)
        out.append(np.asarray(logits[0]))
    return out, pools


def _tenants(pools, seed=5):
    """``pools`` with every row of the state arrays holding what a last
    tenant might have left: nothing of it may reach the next."""
    keys = jax.random.split(jax.random.PRNGKey(seed), 2)
    return (*pools[:2], *(jax.random.normal(k, a.shape).astype(a.dtype)
                          for k, a in zip(keys, pools[2:])))


def _served_logits(model, params, tokens, n_prompt, page_size=16, window=4,
                   slot=2):
    """Logits of positions ``n_prompt - 1 ..`` from the SERVING path: one
    prefill over the prompt, its keys and values written into the pages
    and its state into row ``slot`` of the state arrays — over a last
    tenant's — then one decode step a token through the layout's own
    ``attend``, row state and writes: what ``DecodeServer``'s state
    prefill and step programs compute, with the logits kept."""
    rung = -(-n_prompt // page_size) * page_size
    n_pages = -(-len(tokens) // page_size) + 1
    pool = _pool(model, n_pages, page_size, window)
    layout = pool.layout
    assert layout is kvcache.layout_for(model, pool.arrays)
    table = np.arange(1, n_pages + 1, dtype=np.int32)
    padded = np.zeros((1, rung), np.int32)
    padded[0, :n_prompt] = tokens[:n_prompt]

    @jax.jit
    def prefill(pools):
        logits, k, v, *st = model.prefill(params, padded,
                                          jnp.asarray([n_prompt]))
        return logits[0, n_prompt - 1], (
            *layout.write_prefill(pools, table, [k, v], n_prompt),
            *layout.write_state(pools, slot, st, True))

    first, pools = prefill(_tenants(tuple(pool.arrays)))
    return np.stack([np.asarray(first)] + _decode_from(
        model, params, pools, tokens, n_prompt, table, slot, window)[0])


# Matrices, pages and convolution rows are float32 here and so is the
# reference: what separates them is float32 rounding in another order —
# the step's fused decay against the scan's, the paged attention's
# running softmax against the full one, XLA's default float32 product
# against "highest" on this CPU — and a position's worst logit lies
# within 7e-6 of the logits' deviation in these sequences, on either
# path. The limit is 2e-4, thirty times that. The reference with ONLY
# its recurrent state kept in bfloat16 between tokens is 3.5e-3
# deviations off at its best position (7e-3 at the median), and with
# every product's operands in bfloat16 2.7e-2 at its best.
LOGIT_TOLERANCE = 2e-4


@pytest.mark.parametrize("use_pallas", [False, True], ids=["jnp", "pallas"])
def test_prefill_then_decode_agrees_with_the_reference_on_logits(use_pallas):
    model, params, cfg = _model(use_pallas=use_pallas)
    tokens = np.random.default_rng(1).integers(
        0, model.vocab, size=58).astype(np.int32)
    n_prompt = 21
    n_rows = len(tokens) - n_prompt + 1
    got = _served_logits(model, params, tokens, n_prompt)

    def reference(control=None):
        return ref.logits_rows(params, jnp.asarray(tokens), n_prompt - 1,
                               n_rows, cfg, control=control)

    want = reference()
    err = np.abs(got - want).max(axis=1) / want.std()
    assert err.max() < LOGIT_TOLERANCE, err
    # tight enough that the state, or the products, a precision down fail
    low = np.abs(reference("state_bf16") - want).max(axis=1) / want.std()
    assert low.min() > 10 * LOGIT_TOLERANCE, low
    low = np.abs(reference("bf16") - want).max(axis=1) / want.std()
    assert low.min() > 100 * LOGIT_TOLERANCE, low


# ---------------------------------------------------------------------------
# a prompt on a mixed step's lanes: the scan from the row's state
# ---------------------------------------------------------------------------

def _feed_chunks(model, params, pools, prompt, C, table, slot, window=4):
    """``prompt`` through the mixed step's chunk lanes, ``C`` at a time,
    into row ``slot`` — no row of the window decodes: ``(the prompt
    positions' logits, pools)``."""
    mixed = _steps(model)[1]
    zeros = np.zeros((window,), np.int32)
    out = []
    for start in range(0, len(prompt), C):
        n = min(C, len(prompt) - start)
        fed = np.zeros((C,), np.int32)
        fed[:n] = prompt[start:start + n]
        logits, pools = mixed(
            params, pools, zeros, zeros, np.zeros((window, len(table)),
                                                  np.int32),
            np.arange(window, dtype=np.int32), 0, fed, table, start, n, slot)
        out.append(np.asarray(logits[window:window + n]))
    return np.concatenate(out), pools


@pytest.mark.parametrize("n_prompt,C", [(21, 16), (21, 32), (34, 16)],
                         ids=["dead_lanes-C16", "one_rung-C32",
                              "fewer_than_the_kernel-C16"])
@pytest.mark.parametrize("use_pallas", [False, True], ids=["jnp", "pallas"])
def test_chunks_are_the_prefill_and_the_reference(use_pallas, n_prompt, C):
    """A prompt fed as chunks of two rungs on a mixed step's lanes — two
    chunks whose last has dead lanes behind it, one chunk of a whole
    rung, and three whose last has 2 live lanes, fewer than the
    convolution reaches back (its rows are partly the ones the chunk
    before left) — into a row whose slot holds a last tenant's ``h`` and
    ``conv``: the logits of every prompt position, then of the steps that
    decode from what the chunks left, are the token-by-token reference's;
    ``h``, the ``conv`` rows and the keys are what ``prefill`` writes for
    the whole prompt; no other row of the state moved."""
    model, params, cfg = _model(use_pallas=use_pallas)
    tokens = np.random.default_rng(1).integers(
        0, model.vocab, size=n_prompt + 6).astype(np.int32)
    L, S, window, slot = len(tokens), 16, 4, 2
    n_pages = -(-L // S)
    table = np.arange(1, n_pages + 1, dtype=np.int32)
    start = _tenants(tuple(_pool(model, n_pages).arrays))
    head, pools = _feed_chunks(model, params, start, tokens[:n_prompt], C,
                               table, slot)
    rung = -(-n_prompt // S) * S
    padded = np.zeros((1, rung), np.int32)
    padded[0, :n_prompt] = tokens[:n_prompt]
    logits, k, _v, h, conv = jit_prefill(model)(params, padded,
                                                jnp.asarray([n_prompt]))
    want = ref.logits_rows(params, jnp.asarray(tokens), 0, L, cfg)
    std = want.std()
    assert np.abs(head - want[:n_prompt]).max() / std < LOGIT_TOLERANCE
    assert np.abs(head - np.asarray(logits[0, :n_prompt])).max() / std \
        < LOGIT_TOLERANCE
    keys = pools[0][:, 1:].reshape(model.cache_layers, -1,
                                   model.n_kv_heads, model.head_dim)
    for got, whole in ((pools[2][:, slot], h[:, 0]),
                       (pools[3][:, slot], conv[:, 0]),
                       (keys[:, :n_prompt], k[:, 0, :n_prompt])):
        got, whole = np.asarray(got, np.float32), np.asarray(whole,
                                                             np.float32)
        assert np.abs(got - whole).max() < 1e-4 * np.abs(whole).max()
    for before, after in zip(start[2:], pools[2:]):
        others = [r for r in range(window) if r != slot]
        assert bool((before[:, others] == after[:, others]).all())
    tail, _ = _decode_from(model, params, pools, tokens, n_prompt, table,
                           slot)
    assert np.abs(np.stack(tail) - want[n_prompt:]).max() / std \
        < LOGIT_TOLERANCE


def test_a_dead_row_and_a_position_past_the_length_leave_state_untouched():
    """A plain step with ONE live row moves that row's ``h`` and ``conv``
    and no other row's, in any layer; a prefill's state after a prompt of
    21 tokens on a rung of 32 is the state after the same 21 on a rung of
    48 behind other padding: positions at or past the true length leave
    it untouched."""
    model, params, _ = _model()
    pools = _tenants(tuple(_pool(model, 4).arrays))
    table = np.arange(1, 5, dtype=np.int32)
    _, after = _decode_from(model, params, pools, np.arange(3), 2, table, 1)
    for a, b in zip(pools[2:], after[2:]):
        assert bool((a[:, [0, 2, 3]] == b[:, [0, 2, 3]]).all())
        assert not bool((a[:, 1] == b[:, 1]).all())
    prompt = np.random.default_rng(4).integers(0, 256, size=21)
    states = []
    for rung, fill in ((32, 0), (48, 7)):
        padded = np.full((1, rung), fill, np.int32)
        padded[0, :21] = prompt
        states.append(jit_prefill(model)(params, padded,
                                         jnp.asarray([21]))[3:])
    for a, b in zip(*states):
        assert np.abs(np.asarray(a - b, np.float32)).max() \
            < 1e-5 * float(np.abs(np.asarray(a, np.float32)).max())


# ---------------------------------------------------------------------------
# the order a step runs in: slots, not rows
# ---------------------------------------------------------------------------

def _row_order_decode(model, p, tokens, positions, attend, state, head=None,
                      live=None, chunk=None):
    """``SSMHybridDecoderLM.decode`` as it ran before the mixers walked
    the state in slot order: every lane in the STEP's order, a layer's
    rows of ``conv`` and of ``h`` gathered through ``state.slots`` and
    scattered back, no kernel — the oracle of the change of order."""
    del positions
    h_all, conv_all = state.arrays
    slots, B = state.slots, state.slots.shape[0]
    K, E = model.conv, model.d_inner
    live = state.live if live is None else live
    h = p["embed"][tokens].astype(jnp.float32)
    ks, vs = [], []
    for i, attends in enumerate(model.kinds):
        l = "l%d." % i
        x = model._rms(h, p[l + "mix_g"])
        if attends:
            q, k, v = model._qkv(i, x, p)
            a = attend(model.cache_layer(i), q, k, v, scale=model.scale,
                       force_pallas=False)
            h = h + model._attn_out(i, a, p)
            ks.append(k)
            vs.append(v)
        else:
            j = model.state_layer(i)
            raw, z = model._split_in(i, x, p)
            before = conv_all[j, slots]
            window = jnp.concatenate(
                [before.reshape(B, K - 1, E), raw[:B, None]], axis=1)
            y = (p[l + "conv_w"] * window.astype(jnp.float32)).sum(1)
            conv_all = conv_all.at[j, slots].set(jnp.where(
                state.live[:, None], window[:, 1:].reshape(B, -1), before))
            if chunk is not None:
                tail, rows = model._chunk_conv(
                    raw[B:], p[l + "conv_w"], conv_all[j, chunk[0]], chunk)
                y = jnp.concatenate([y, tail])
                conv_all = conv_all.at[j, chunk[0]].set(rows)
            u, delta, b, c = model._scan_inputs(i, y, p, live)
            a = -jnp.exp(p[l + "A_log"])
            S = jnp.exp(delta[:B, None, :] * a) * h_all[j, slots] \
                + b[:B, :, None] * (delta[:B] * u[:B])[:, None, :]
            y = jnp.sum(S * c[:B, :, None], axis=1)
            h_all = h_all.at[j, slots].set(S)
            if chunk is not None:
                tail, h_all = model._chunk_scan(
                    j, (u[B:], delta[B:], b[B:], c[B:], a), h_all, chunk)
                y = jnp.concatenate([y, tail])
            h = h + model._mix_out(i, y, u, z, p)
        h = h + model._mlp(i, h, p)
    if head is not None:
        h = h[head]
    return (model._logits(h, p), jnp.stack(ks), jnp.stack(vs), h_all,
            conv_all)


@pytest.mark.parametrize("use_pallas", [False, True], ids=["jnp", "pallas"])
@pytest.mark.parametrize("lanes", [0, 16], ids=["plain", "mixed-c16"])
def test_a_step_in_slot_order_is_the_step_by_row(use_pallas, lanes):
    """One step of a window of 16 whose rows sit on SCRAMBLED slots, 11
    of them live, over a last tenant's state in every slot — plain, and
    MIXED with a chunk of 16 lanes (13 live, from position 32: the
    convolution's first inputs and the scan's start are the request's
    row, which is a dead row's slot) — through ``decode``, which brings
    the rows' lanes into slot order once and goes back around ``attend``
    and for the head, against the same step run by row
    (:func:`_row_order_decode`): the logits of every lane, the keys and
    values as the pages hold them, ``h`` and ``conv`` whole — no other
    slot moved, none took another's."""
    model, params, _ = _model(use_pallas=use_pallas)
    W, S, n_live, per = 16, 16, 11, 4
    rng = np.random.default_rng(11)
    pools = _tenants(tuple(_pool(model, W * per, S, W).arrays))
    order = rng.permutation(W).astype(np.int32)
    toks = rng.integers(0, model.vocab, size=W).astype(np.int32)
    poss = np.where(np.arange(W) < n_live, rng.integers(3, 40, size=W),
                    0).astype(np.int32)
    pts = (1 + np.arange(W * per, dtype=np.int32)).reshape(W, per)
    pts[n_live:] = 0
    args = (params, pools, toks, poss, pts, order, n_live)
    if lanes:
        fed = rng.integers(0, model.vocab, size=lanes).astype(np.int32)
        # the request's row: the slot of a row that is not live, its
        # pages that row's
        args += (fed, np.arange(W * per - per + 1, W * per + 1,
                                dtype=np.int32), 32, 13,
                 int(order[n_live + 2]))
    got, want = (_steps(model, by_row)[bool(lanes)](*args)
                 for by_row in (False, True))
    std = float(np.asarray(want[0]).std())
    assert np.abs(np.asarray(got[0] - want[0])).max() / std \
        < LOGIT_TOLERANCE / 10
    for name, a, b in zip(("k", "v", "h", "conv"), got[1], want[1]):
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        assert np.abs(a - b).max() < 1e-5 * np.abs(b).max(), name
    # the step moved the live rows' slots (and the request's) and no other
    moved = set(order[:n_live].tolist()) | ({args[-1]} if lanes else set())
    for before, after in zip(pools[2:], got[1][2:]):
        for slot in range(W):
            same = bool((before[:, slot] == after[:, slot]).all())
            assert same == (slot not in moved), slot


# ---------------------------------------------------------------------------
# the server
# ---------------------------------------------------------------------------

def _is_greedy(model, params, prompt, out, width=96):
    """Whether ``out`` is the model's own greedy stream after ``prompt``:
    one teacher-forced forward over both (``model.prefill``, compiled
    once a width), the argmax at every position that predicts a served
    token."""
    n = len(prompt) + len(out)
    seq = np.zeros((1, width), np.int32)
    seq[0, :n] = np.concatenate([prompt, out])
    logits = np.asarray(jit_prefill(model)(params, seq,
                                           jnp.asarray([n]))[0][0])
    return [int(t) for t in logits[len(prompt) - 1:n - 1].argmax(-1)] \
        == [int(t) for t in out]


@pytest.mark.parametrize("use_pallas", [False, True], ids=["jnp", "pallas"])
def test_served_streams_are_the_models_own_and_a_slot_starts_from_zeros(
        use_pallas):
    """Seven requests through ``DecodeServer`` on a window of 3: every
    prompt rides the mixed step in chunks of 16 or 32 lanes (no prefill
    program is built), every slot is re-used by a second and a third
    tenant, and every stream is the model's own greedy stream — a second
    tenant that started from what the first left, or a chunk that missed
    its row, would leave it within a few tokens. ``stats()["state"]``
    splits its bytes by array."""
    model, params, _ = _model(use_pallas=use_pallas)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, model.vocab, size=n).astype(np.int32)
               for n in (11, 5, 29, 40, 17, 3, 33)]
    srv = DecodeServer(model, params, seq_ladder=[16, 32, 64],
                       max_new_tokens=16, page_size=16, window=3,
                       pool_pages=32, prefix_cache=False, start=False)
    reqs = [srv.submit(p, max_new_tokens=12) for p in prompts]
    drain(srv, *reqs)
    st = srv.stats()
    srv.stop()
    assert st["prefill_programs"] == 0 and st["chunk_steps"] >= 8
    state = st["kv"]["state"]
    N, E, K = model.d_state, model.d_inner, model.conv
    assert state["bytes_by_array"] == {
        "h": model.state_layers * 3 * N * E * 4,
        "conv": model.state_layers * 3 * (K - 1) * E * 4}
    assert state["bytes"] == sum(state["bytes_by_array"].values())
    assert state["rows"] == 3
    for prompt, req in zip(prompts, reqs):
        out = [int(t) for t in req.result()]
        assert len(out) == 12
        assert _is_greedy(model, params, prompt, out), len(prompt)


def test_what_the_block_cannot_do_is_refused_not_guessed():
    """Routed experts, a sliding window, a convolution with no row to
    hold, another projection bias, an untied head and a model with no
    layer of one kind are typed errors when the model is built; prefix
    sharing over its state when the server is."""
    base = {k: v for k, v in CFG.items()}
    for over, said in (
            (dict(num_experts=16), "one-expert"),
            (dict(num_experts_per_tok=2), "one-expert"),
            (dict(sliding_window=4096), "sliding_window"),
            (dict(mamba_d_conv=1), "mamba_d_conv"),
            (dict(mamba_proj_bias=True), "mamba_proj_bias"),
            (dict(mamba_conv_bias=False), "mamba_conv_bias"),
            (dict(tie_word_embeddings=False), "tie_word_embeddings"),
            (dict(hidden_act="gelu"), "hidden_act"),
            (dict(attn_layer_offset=9), "a layer of each kind"),
            (dict(num_key_value_heads=3), "do not divide")):
        with pytest.raises(MXNetError, match=said):
            SSMHybridDecoderLM(**dict(base, **over))
    with pytest.raises(TypeError, match="unexpected keyword"):
        SSMHybridDecoderLM(**dict(base, rope_theta=1e4))
    # read as they stand: none changes a served token
    SSMHybridDecoderLM(**dict(base, num_logits_to_keep=1,
                              use_mamba_kernels=True, expert_layer_period=2,
                              expert_layer_offset=1))
    model, params, _ = _model()
    with pytest.raises(MXNetError, match="prefix sharing"):
        DecodeServer(model, params, seq_ladder=[16], max_new_tokens=8,
                     page_size=16, window=2, pool_pages=8,
                     prefix_cache=True, start=False)


def test_the_layer_maps_follow_the_published_period():
    """At the published depth and period (28 layers, period 14, offset
    7) layers 7 and 21 attend and the other 26 are state-space: 13 to 1;
    a row's state is ``(16, 5120)`` float32 and 3 rows of 5,120."""
    model = SSMHybridDecoderLM(**dict(
        tiny_config(), num_hidden_layers=28, attn_layer_period=14,
        attn_layer_offset=7, hidden_size=2560, num_attention_heads=20,
        mamba_d_state=16, mamba_dt_rank=160, intermediate_size=8192,
        vocab_size=65536))
    attends = [i for i in range(28) if model.cache_layer(i) is not None]
    assert attends == [7, 21]
    assert (model.cache_layers, model.state_layers) == (2, 26)
    assert [model.cache_layer(i) for i in attends] == [0, 1]
    assert [model.state_layer(i) for i in (0, 6, 8, 20, 22, 27)] \
        == [0, 6, 7, 19, 20, 25]
    assert model.head_dim == 128
    assert model.state_arrays == (("h", (16, 5120), "float32"),
                                  ("conv", (3 * 5120,), "bfloat16"))
    assert model.cache_arrays == (("k", (1, 128), "bfloat16"),
                                  ("v", (1, 128), "bfloat16"))
    shapes = jax.eval_shape(model.init_params, 0)
    assert "head" not in shapes
    n = sum(int(np.prod(a.shape)) for a in shapes.values())
    assert 3.02e9 < n < 3.04e9, n


def test_the_ten_lines_of_the_docstring_serve():
    """The module docstring's and ``README.md``'s ten lines, as written."""
    model = SSMHybridDecoderLM(**tiny_config(), dtype="float32")
    params = model.init_params(seed=0)
    srv = DecodeServer(model, params, seq_ladder=[16, 32],
                       max_new_tokens=24, page_size=8, window=4,
                       pool_pages=64, prefix_cache=False)
    try:
        req = srv.submit([5, 9, 2, 7] * 5, max_new_tokens=24)
        out = list(req.tokens(timeout=120))
    finally:
        srv.stop()
    assert len(out) == 24 and all(0 <= int(t) < 96 for t in out)
    assert _is_greedy(model, params, np.asarray([5, 9, 2, 7] * 5), out)
