"""Linear-attention layers whose state is a fixed array a row, beside one
latent-attention layer's pages, over routed experts —
``serving.hybrid_linear_moe`` on ``DecodeServer``'s STATE form of the
model contract, the row state beside the pool (``serving.kvcache``) and
the delta-rule kernels (``parallel.delta_rule``; Pallas in interpret
mode), against the benchmark's plain float32 reference
(``benchmark/reference/hybrid_linear_moe_lm.py``, the recurrence one
token at a time) at a small size with seeded weights; a prompt as
chunks on a mixed step's lanes, the delta rule from the row's state,
against the whole-prompt prefill and the same reference. The programs of
the models that keep no such state are the parent's, jaxpr for jaxpr.
The server's cases but one are in ``test_hybrid_linear_moe_server.py``."""
import functools
import hashlib
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

from benchmark.reference import hybrid_linear_moe_lm as ref    # noqa: E402
from mxnet_tpu import compile_watch, fault, telemetry          # noqa: E402
from mxnet_tpu.base import MXNetError                          # noqa: E402
from mxnet_tpu.parallel import delta_rule, moe, sharding_rules  # noqa: E402
from mxnet_tpu.serving import (DecodeServer, KVCachePool,       # noqa: E402
                               ToyDecoderLM, kvcache)
from mxnet_tpu.serving.block_diffusion import (                # noqa: E402
    BlockDiffusionMoEDecoderLM)
from mxnet_tpu.serving.hybrid_linear_moe import (              # noqa: E402
    HybridLinearMoEDecoderLM)
from mxnet_tpu.serving.latent_moe import LatentMoEDecoderLM    # noqa: E402
from serving_common import jit_prefill                         # noqa: E402

# the published block's shape at a test's size: two groups of three
# layers (two linear, one latent), 16 experts in 4 groups, 2 kept, top 4,
# one dense layer in front; float32 matrices, pool and convolution rows
CFG = dict(vocab_size=256, hidden_size=128, num_hidden_layers=6,
           num_attention_heads=4, head_dim=32, layer_group_size=3,
           kv_lora_rank=128, qk_nope_head_dim=32, qk_rope_head_dim=16,
           v_head_dim=32, intermediate_size=256, moe_intermediate_size=64,
           num_experts=16, num_shared_experts=1, num_experts_per_tok=4,
           n_group=4, topk_group=2, routed_scaling_factor=2.5,
           first_k_dense_replace=1, rope_theta=10000, kda_lower_bound=-5,
           max_position_embeddings=512, dtype="float32")
# heads of 128: what the Pallas step kernel tiles
WIDE = dict(CFG, num_attention_heads=2, head_dim=128)


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
    model = HybridLinearMoEDecoderLM(**cfg, use_pallas=use_pallas)
    return model, model.init_params(seed=seed), cfg


def _server(model, params, **kw):
    kw = {"seq_ladder": [32], "max_new_tokens": 32, "page_size": 16,
          "window": 4, "pool_pages": 64, "start": False, **kw}
    return DecodeServer(model, params, **kw)


def _drain_with_each(srv, *reqs, limit=800, each=None):
    """``serving_common.drain`` with ``each(srv)`` called in front of
    every pass."""
    n = 0
    while not all(r.done() for r in reqs):
        if each is not None:
            each(srv)
        srv._tick()
        n += 1
        assert n < limit, "scheduler made no progress"


def _prompts(seed, sizes, vocab=256):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, vocab, size=n).astype(np.int32) for n in sizes]


def _serve(model, params, prompts, n=12, each=None, **kw):
    srv = _server(model, params, **kw)
    reqs = [srv.submit(p, max_new_tokens=n) for p in prompts]
    _drain_with_each(srv, *reqs, each=each)
    st = srv.stats()
    srv.stop()
    return [[int(t) for t in r.result()] for r in reqs], st


# ---------------------------------------------------------------------------
# the recurrence: chunkwise, and one token a row
# ---------------------------------------------------------------------------

def _rule_inputs(L, H=3, d=32, seed=0):
    k = jax.random.split(jax.random.PRNGKey(seed), 5)
    unit = lambda a: a / jnp.linalg.norm(a, axis=-1, keepdims=True)  # noqa
    q = unit(jax.random.normal(k[0], (L, H, d))) * d ** -0.5
    g = -5.0 * jax.nn.sigmoid(4.0 * jax.random.normal(k[3], (L, H, d)))
    return q, unit(jax.random.normal(k[1], (L, H, d))), \
        jax.random.normal(k[2], (L, H, d)), g, \
        jax.nn.sigmoid(jax.random.normal(k[4], (L, H)))


@pytest.mark.parametrize("case", ["mixed_gates", "gates_at_the_bound",
                                  "ragged_true_length"])
def test_chunkwise_prefill_is_the_token_by_token_reference(case):
    """``kda_chunk`` against the reference's ``lax.scan`` over tokens. At
    the bound every channel decays by ``e^-5`` a position: 80 such
    positions reach ``e^-400``, which a form that divides by the running
    decay of a 64-long chunk cannot hold in float32. Past a ragged true
    length (``beta = 0``, ``g = 0``) the state stays what it was."""
    L, n = 96, 96
    q, k, v, g, beta = _rule_inputs(L)
    if case == "gates_at_the_bound":
        g = g.at[:80].set(-5.0)
    if case == "ragged_true_length":
        n = 53
        live = jnp.arange(L) < n
        g = jnp.where(live[:, None, None], g, 0.0)
        beta = jnp.where(live[:, None], beta, 0.0)
    o, S = delta_rule.kda_chunk(*(a[None] for a in (q, k, v, g, beta)))
    assert bool(jnp.isfinite(o).all()) and bool(jnp.isfinite(S).all())
    want = ref.delta_rule(q, k, v, g, beta)
    assert np.abs(np.asarray(o[0] - want))[:n].max() \
        < 1e-4 * float(np.abs(want).max())
    # the state after the true length, by the step form from zero
    state = jnp.zeros((1, 1) + S.shape[1:])
    for t in range(n):
        _, state = delta_rule.kda_step(
            state, 0, jnp.zeros((1,), jnp.int32),
            *(a[t][None] for a in (q, k, v, g, beta)))
    assert np.abs(np.asarray(S - state[0])).max() \
        < 1e-5 * max(float(np.abs(state).max()), 1e-3)


def test_a_chunk_whose_running_decay_overflows_is_refused():
    q, k, v, g, beta = (a[None] for a in _rule_inputs(64))
    with pytest.raises(MXNetError, match="overflows float32"):
        delta_rule.kda_chunk(q, k, v, g, beta, chunk=64, g_floor=-5.0)
    with pytest.raises(MXNetError, match="no multiple"):
        delta_rule.kda_chunk(q[:, :40], k[:, :40], v[:, :40], g[:, :40],
                             beta[:, :40])


def test_step_kernel_agrees_with_jnp_in_place_and_leaves_dead_rows():
    """The Pallas step (interpreted) against the jnp one on a window of
    6 rows and 2 state layers: the same outputs, the rows' states
    updated where they lie, every other layer and the rows that are not
    live exactly as they were."""
    B, H, d, layers = 6, 8, 128, 2
    k = jax.random.split(jax.random.PRNGKey(1), 6)
    state = jax.random.normal(k[0], (layers, B, H, d, d))
    unit = lambda a: a / jnp.linalg.norm(a, axis=-1, keepdims=True)  # noqa
    q, kk, v = (jax.random.normal(k[i], (B, H, d)) for i in (1, 2, 3))
    g = -5.0 * jax.nn.sigmoid(3.0 * jax.random.normal(k[4], (B, H, d)) - 2)
    beta = jax.nn.sigmoid(jax.random.normal(k[5], (B, H)))
    slots = jnp.asarray([3, 0, 5, 1, 4, 2], jnp.int32)
    dead = jnp.asarray([False, False, True, False, True, True])
    g = jnp.where(dead[:, None, None], 0.0, g)
    beta = jnp.where(dead[:, None], 0.0, beta)
    args = (state, 1, slots, q * d ** -0.5, unit(kk), v, g, beta)
    o_j, s_j = delta_rule.kda_step(*args)
    o_p, s_p = delta_rule.kda_step(*args, force_pallas=True)
    assert np.abs(np.asarray(o_j - o_p)).max() < 1e-4
    assert np.abs(np.asarray(s_j - s_p)).max() < 1e-5
    for got in (s_j, s_p):
        assert bool(jnp.isfinite(got).all())
        assert bool((got[0] == state[0]).all())            # another layer
        for row, gone in zip(np.asarray(slots), np.asarray(dead)):
            same = bool((got[1, row] == state[1, row]).all())
            assert same == bool(gone), row


def test_the_gates_draw_remembers():
    """``A_log`` and ``dt_bias`` as ``init_params`` draws them: a step's
    ``alpha`` has its median over channels between 0.9 and 0.999, and is
    neither 1 nor ``e^-5`` everywhere."""
    model, params, _ = _model()
    x = jax.random.normal(jax.random.PRNGKey(2), (64, model.d_model))
    g, beta = model._gates(1, x, params, jnp.ones((64,), bool))
    alpha = np.exp(np.asarray(g))
    assert 0.9 < np.median(alpha) < 0.999
    assert np.percentile(alpha, 2) < 0.8 and np.percentile(alpha, 98) > 0.995
    assert alpha.min() > np.exp(-5.0) and alpha.max() < 1.0
    assert 0.01 < float(beta.min()) and float(beta.max()) < 0.99
    g, beta = model._gates(1, x, params, jnp.zeros((64,), bool))
    assert float(jnp.abs(g).max()) == 0.0 and float(beta.max()) == 0.0


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
def _steps(model):
    """``(the plain step, the MIXED step)`` through the layout's own
    ``attend``, row state and writes, as ``DecodeServer``'s two state
    step programs run them (the mixed one with the logits of every lane
    kept), jitted once a model: weights, tables, slots and pools are
    arguments."""
    @jax.jit
    def plain(params, pools, toks, poss, pts, order, n_live):
        layout = kvcache.layout_for(model, pools)
        attend = layout.attend(pools, pts, poss)
        state = layout.row_state(pools, order,
                                 jnp.arange(len(order)) < n_live)
        logits, new, *held = model.decode(params, toks, poss, attend, state)
        return logits, (*layout.write_tokens(pools, pts, poss, [new],
                                             model.use_pallas), *held[:2])

    @jax.jit
    def mixed(params, pools, toks, poss, pts, order, n_live, fed, table,
              start, n, slot):
        layout = kvcache.layout_for(model, pools)
        rows, C = len(toks), len(fed)
        attend = layout.attend_chunk(pools, pts, poss, table, start)
        state = layout.row_state(pools, order, jnp.arange(rows) < n_live)
        lanes = jnp.arange(C, dtype=jnp.int32)
        logits, new, *held = model.decode(
            params, jnp.concatenate([toks, fed]),
            jnp.concatenate([poss, start + lanes]), attend, state,
            head=jnp.arange(rows + C),
            live=jnp.concatenate([state.live, lanes < n]),
            chunk=(slot, start, n))
        held = tuple(held[:2])
        pages = layout.write_tokens(pools, pts, poss, [new[:, :rows]],
                                    model.use_pallas)
        pages = layout.write_chunk(pages + held, table, start, n,
                                   [new[:, rows:]])
        return logits, (*pages, *held)

    return plain, mixed


def _decode_from(model, params, pools, tokens, first, table, slot,
                 window=4):
    """One plain step a token of ``tokens[first:]`` on row ``slot``, the
    only live row of a window of ``window``, from ``pools`` as a prefill
    or a prompt's chunks left them: the steps' logits."""
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
    return out


def _served_logits(model, params, tokens, n_prompt, page_size=16, window=4,
                   slot=2):
    """Logits of positions ``n_prompt - 1 ..`` from the SERVING path: one
    prefill over the prompt, its rows written into a paged latent pool
    and its state into row ``slot`` of the state arrays, then one decode
    step a token through the layout's own ``attend``, row state and
    writes — what ``DecodeServer``'s state prefill and step programs
    compute, with the logits kept. The step runs a window of ``window``
    rows of which one is live."""
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
        logits, rows, *st = model.prefill(params, padded,
                                          jnp.asarray([n_prompt]))
        return logits[0, n_prompt - 1], (
            *layout.write_prefill(pools, table, [rows], n_prompt),
            *layout.write_state(pools, slot, st, True))

    first, pools = prefill(tuple(pool.arrays))
    return np.stack([np.asarray(first)] + _decode_from(
        model, params, pools, tokens, n_prompt, table, slot, window))


# Matrices, pool and convolution rows are float32 here and so is the
# reference: what separates them is float32 rounding in another order —
# the chunkwise form against one token at a time, the absorbed attention
# against the published one, a grouped matmul against a loop — a few
# 1e-6 of a logit's deviation a product, a few dozen products deep: a
# position's worst logit lies within 2e-4 deviations. The limit is 2e-3,
# ten times that (a router's near-tie that flips costs a whole expert,
# about one deviation: at float32 none does in these sequences). The
# reference with ONLY its recurrent state kept in bfloat16 between tokens
# is 0.02 deviations and more off at most positions.
LOGIT_TOLERANCE = 2e-3


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
                               n_rows, cfg, model.held, control=control)

    want = reference()
    err = np.abs(got - want).max(axis=1) / want.std()
    assert err.max() < LOGIT_TOLERANCE, err
    # tight enough that the state a precision down fails it
    low = np.abs(reference("state_bf16") - want).max(axis=1) / want.std()
    assert np.median(low) > 2 * LOGIT_TOLERANCE, low
    assert np.abs(reference("float8") - want).max(axis=1).min() \
        / want.std() > 20 * LOGIT_TOLERANCE


# ---------------------------------------------------------------------------
# a prompt on a mixed step's lanes: the delta rule from the row's state
# ---------------------------------------------------------------------------

def _tenants(pools, seed=5):
    """``pools`` with every row of the state arrays holding what a last
    tenant might have left: nothing of it may reach the next."""
    keys = jax.random.split(jax.random.PRNGKey(seed), 2)
    return (pools[0], *(jax.random.normal(k, a.shape).astype(a.dtype)
                        for k, a in zip(keys, pools[1:])))


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
    """A prompt fed as chunks on a mixed step's lanes — two chunks whose
    last has dead lanes behind it, one chunk of a whole rung, and three
    whose last has 2 live lanes, fewer than the convolution reaches back
    (its rows are partly the ones the chunk before left) — into a row
    whose slot holds a last tenant's ``s`` and ``conv``: the logits of
    every prompt position, then of the steps that decode from what the
    chunks left, are the token-by-token reference's; ``s``, the ``conv``
    rows and the latent rows are what ``prefill`` writes for the whole
    prompt; no other row of the state moved."""
    model, params, cfg = _model(use_pallas=use_pallas)
    tokens = np.random.default_rng(1).integers(
        0, model.vocab, size=n_prompt + 6).astype(np.int32)
    L, S, window, slot = len(tokens), 16, 4, 2
    n_pages = -(-L // S)
    table = np.arange(1, n_pages + 1, dtype=np.int32)
    start = _tenants(tuple(_pool(model, n_pages).arrays))
    head, pools = _feed_chunks(model, params, start, tokens[:n_prompt], C,
                               table, slot)
    # what the whole-prompt prefill gives
    rung = -(-n_prompt // S) * S
    padded = np.zeros((1, rung), np.int32)
    padded[0, :n_prompt] = tokens[:n_prompt]
    logits, rows, s, conv = jit_prefill(model)(params, padded,
                                               jnp.asarray([n_prompt]))
    want = ref.logits_rows(params, jnp.asarray(tokens), 0, L, cfg,
                           model.held)
    std = want.std()
    assert np.abs(head - want[:n_prompt]).max() / std < LOGIT_TOLERANCE
    assert np.abs(head - np.asarray(logits[0, :n_prompt])).max() / std \
        < LOGIT_TOLERANCE
    for got, whole in ((pools[1][:, slot], s[:, 0]),
                       (pools[2][:, slot], conv[:, 0]),
                       (pools[0][:, 1:].reshape(model.cache_layers, -1,
                                                model.row_width)[:, :n_prompt],
                        rows[:, 0, :n_prompt])):
        got, whole = np.asarray(got, np.float32), np.asarray(whole,
                                                             np.float32)
        assert np.abs(got - whole).max() < 1e-4 * np.abs(whole).max()
    for before, after in zip(start[1:], pools[1:]):
        others = [r for r in range(window) if r != slot]
        assert bool((before[:, others] == after[:, others]).all())
    # and the steps that decode from it
    tail = _decode_from(model, params, pools, tokens, n_prompt, table, slot)
    assert np.abs(np.stack(tail) - want[n_prompt:]).max() / std \
        < LOGIT_TOLERANCE


@pytest.mark.parametrize("use_pallas", [False, True], ids=["jnp", "pallas"])
def test_the_rows_beside_a_chunk_step_as_they_step_alone(use_pallas):
    """One mixed step — two rows that decode, in slots 3 and 0, and a
    third request's second chunk (13 live lanes of 16, from position 16)
    into slot 1 — against the plain step from the same pools. A linear
    layer gives the rows BIT FOR BIT what it gives them alone: increment,
    ``s`` and ``conv`` rows (layer 3, called by itself), and so does the
    whole step as far as the first latent layer (state layers 0 and 1).
    Behind it the rows agree to float32 rounding, not to the bit, on this
    CPU: the latent layer's value up-projection (``_absorbed``'s batched
    product, the parent class's, dots' since PR 40) rounds by the number
    of lanes here. The slot nobody holds is as it was, and the chunk's
    slot is what feeding the chunk with no row beside it leaves."""
    model, params, _ = _model(use_pallas=use_pallas)
    rng = np.random.default_rng(2)
    window, per = 4, 3
    seqs = [rng.integers(0, model.vocab, size=n).astype(np.int32)
            for n in (19, 7, 29)]
    slots = (3, 0, 1)
    tables = np.arange(1, 3 * per + 1, dtype=np.int32).reshape(3, per)
    pools = _tenants(tuple(_pool(model, 3 * per).arrays))
    layout = kvcache.layout_for(model, pools)
    assert layout.chunks and model.chunk_lanes
    for seq, slot, table in zip(seqs[:2], slots, tables):
        _, pools = _feed_chunks(model, params, pools, seq, 32, table, slot)
    _, alone = _feed_chunks(model, params, pools, seqs[2], 16, tables[2], 1)
    _, pools = _feed_chunks(model, params, pools, seqs[2][:16], 16,
                            tables[2], 1)
    plain, mixed = _steps(model)
    toks, poss = (np.zeros((window,), np.int32) for _ in range(2))
    toks[:2], poss[:2] = (5, 9), (19, 7)
    pts = np.zeros((window, per), np.int32)
    pts[:2] = tables[:2]
    order = np.asarray([3, 0, 1, 2], np.int32)
    fed = np.zeros((16,), np.int32)
    fed[:13] = seqs[2][16:]
    want, stepped = plain(params, pools, toks, poss, pts, order, 2)
    got, both = mixed(params, pools, toks, poss, pts, order, 2, fed,
                      tables[2], 16, 13, 1)

    def close(a, b, eps=1e-5):
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        return np.abs(a - b).max() <= eps * np.abs(b).max()

    assert close(got[:2], want[:2])
    held = tables[:2].reshape(-1)
    assert close(both[0][:, held], stepped[0][:, held])
    assert close(both[0][:, tables[2]], alone[0][:, tables[2]])
    for a, b, c, d in zip(pools[1:], stepped[1:], both[1:], alone[1:]):
        assert bool((b[:2, [3, 0]] == c[:2, [3, 0]]).all())
        assert close(c[:, [3, 0]], b[:, [3, 0]])
        assert bool((a[:, 2] == c[:, 2]).all())
        assert bool((a[:, 1] == b[:, 1]).all())           # not the plain's
        assert close(c[:, 1], d[:, 1])

    # one linear layer by itself, behind the latent layer
    @functools.partial(jax.jit, static_argnums=1)
    def layer(u, chunked):
        state = layout.row_state(pools, order, jnp.arange(window) < 2)
        if not chunked:
            return model._linear_step(3, u[:window], params, state,
                                      state.arrays)
        return model._linear_step(
            3, u, params, state, state.arrays,
            jnp.concatenate([state.live, jnp.arange(16) < 13]), (1, 16, 13))

    u = jax.random.normal(jax.random.PRNGKey(7), (window + 16,
                                                  model.d_model))
    (out_p, held_p), (out_m, held_m) = layer(u, False), layer(u, True)
    assert bool((out_p[:2] == out_m[:2]).all())
    for a, b, c in zip(pools[1:], held_p, held_m):
        assert bool((b[:, [3, 0, 2]] == c[:, [3, 0, 2]]).all())
        assert bool((a[2, 1] == b[2, 1]).all()) \
            and not bool((a[2, 1] == c[2, 1]).all())


def test_the_four_shares_add_up_to_the_uncut_layer():
    """Expert parallelism's contract at a small size: the 4 shares'
    routed parts, and the shared expert counted ONCE, add up to what the
    uncut reference gives for the whole layer."""
    model, params, cfg = _model(ep=(0, 1))
    assert model.held == (0, 16)
    x = jax.random.normal(jax.random.PRNGKey(5), (24, cfg["hidden_size"]))
    whole, _ = ref.base.moe_layer(x, params, "l1.", cfg, (0, 16))
    topi, topw = moe.route_grouped_sigmoid(
        x, params["l1.router_w"], params["l1.router_b"], n_group=4,
        topk_group=2, top_k=4, scaling=2.5)
    shared = model._gated(x, params, "l1.shared.")
    total = shared
    for rank in range(4):
        lo, hi = sharding_rules.held_experts(16, 4, rank)
        share = {n: params["l1.experts." + n][lo:hi]
                 for n in ("w_gate", "w_up", "w_down")}
        total = total + moe.expert_ffn(x, share, topi, topw, (lo, hi))
    assert np.abs(np.asarray(total - whole)).max() \
        / np.asarray(whole).std() < 1e-3
    one = shared + moe.expert_ffn(
        x, {n: params["l1.experts." + n][:4]
            for n in ("w_gate", "w_up", "w_down")}, topi, topw, (0, 4))
    assert np.abs(np.asarray(one - whole)).max() \
        / np.asarray(whole).std() > 0.3
    # the chip's share of the published axis: groups 0 and 1 of 8
    assert sharding_rules.held_experts(512, 4, 0) == (0, 128)


# ---------------------------------------------------------------------------
# the server: a slot's second tenant (the rest of the server's cases:
# test_hybrid_linear_moe_server.py)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("use_pallas", [False, True], ids=["jnp", "pallas"])
def test_a_slots_second_tenant_streams_what_it_streams_alone(use_pallas):
    """A window of ONE row: every request is its slot's next tenant, and
    its first chunk starts from zeros whatever the slot holds — a short
    prompt after a long one inherits neither state nor convolution
    rows."""
    model, params, _ = _model(use_pallas=use_pallas)
    prompts = _prompts(4, (30, 2, 9))
    together, st = _serve(model, params, prompts, n=8, window=1)
    assert st["state"]["rows"] == 1 and st["state"]["writes"] == 3
    for prompt, stream in zip(prompts, together):
        alone, _ = _serve(model, params, [prompt], n=8, window=1)
        assert alone[0] == stream


# ---------------------------------------------------------------------------
# what is refused, when the model or the server is built
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("what", [
    "prefix_sharing", "int8_pool", "speculative_form", "block_form",
    "next_token_module", "clamped_swiglu", "the_original_gate",
    "no_whole_group", "a_shared_pool_without_the_state"])
def test_what_state_makes_impossible_is_refused_with_a_typed_error(what):
    model, params, cfg = _model()
    if what == "prefix_sharing":
        with pytest.raises(MXNetError, match="prefix sharing"):
            _server(model, params, prefix_cache=True)
    elif what == "int8_pool":
        class PerHead(ToyDecoderLM):
            state_arrays = (("s", (2, 8, 8), "float32"),)
            state_layers = 2
        toy = PerHead(vocab=32, n_layers=2, n_heads=2, head_dim=8)
        pool = KVCachePool(2, 2, 8, page_size=16, n_pages=8, dtype="int8",
                           state=kvcache.declared_state(toy)[0],
                           state_layers=2, state_rows=4)
        with pytest.raises(MXNetError, match="int8 pool"):
            DecodeServer(toy, toy.init_params(0), pool=pool, window=4,
                         seq_ladder=[16], max_new_tokens=8, start=False)
    elif what in ("speculative_form", "block_form"):
        attr = {"speculative_form": "draft_length",
                "block_form": "block_length"}[what]
        fake = type("M", (HybridLinearMoEDecoderLM,), {
            attr: 1, "verify": None, "draft": None, "prefill_draft": None,
            "draft_prefill": None, "decode_block": None, "unmask": None,
            "mask_token_id": 0})(**cfg)
        with pytest.raises(MXNetError, match="fixed state a row"):
            _server(fake, params, page_size=16)
    elif what == "next_token_module":
        with pytest.raises(MXNetError, match="num_nextn_predict_layers 1"):
            HybridLinearMoEDecoderLM(**dict(cfg, num_nextn_predict_layers=1))
    elif what == "clamped_swiglu":
        with pytest.raises(MXNetError, match="clamped SwiGLU"):
            HybridLinearMoEDecoderLM(**dict(
                cfg, expert_swiglu_limit_list=[0, 0, 0, 0, 4, 4]))
        # a limit on a layer that is not held is none of this chip's
        HybridLinearMoEDecoderLM(**dict(
            cfg, share_expert_swiglu_limit_list=[0] * 6 + [7, 7]))
    elif what == "the_original_gate":
        with pytest.raises(MXNetError, match="kda_safe_gate"):
            HybridLinearMoEDecoderLM(**dict(cfg, kda_safe_gate=False))
    elif what == "no_whole_group":
        with pytest.raises(MXNetError, match="no whole group"):
            HybridLinearMoEDecoderLM(**dict(cfg, num_hidden_layers=2))
    else:
        pool = KVCachePool(model.cache_layers,
                           arrays=[c[:2] for c in model.cache_arrays],
                           dtype="float32", page_size=16, n_pages=16)
        with pytest.raises(MXNetError, match="shared pool geometry"):
            _server(model, params, pool=pool, pool_pages=None,
                    page_size=None)


def test_null_rope_scaling_and_query_rank_are_plain_rope_and_one_matrix():
    """The repair in ``LatentMoEDecoderLM``: ``rope_scaling`` null is
    plain RoPE at ``rope_theta`` with the plain score scale, and
    ``q_lora_rank`` null a query without a rank."""
    model, params, cfg = _model()
    want = 10000.0 ** (-np.arange(0, 16, 2) / 16.0)
    assert np.abs(model.inv_freq - want).max() < 1e-7
    assert model.rope_gain == 1.0 and model.scale == 48 ** -0.5
    assert model.q_rank == 0 and "l2.wq" in params \
        and "l2.wq_a" not in params and "l2.wg" in params
    assert params["l2.wq"].shape == (128, 4 * 48)
    assert model.cache_layers == 2 and model.state_layers == 4
    assert [model.latent_layer(i) for i in range(6)] \
        == [None, None, 0, None, None, 1]
    assert [model.state_layer(i) for i in (0, 1, 3, 4)] == [0, 1, 2, 3]


# ---------------------------------------------------------------------------
# the models that keep no such state run the programs they ran
# ---------------------------------------------------------------------------

YARN = {"beta_fast": 32, "beta_slow": 1, "factor": 40, "mscale": 1,
        "mscale_all_dim": 1, "original_max_position_embeddings": 4096,
        "type": "yarn"}
LATENT = dict(vocab_size=256, hidden_size=128, num_hidden_layers=3,
              num_attention_heads=4, q_lora_rank=64, kv_lora_rank=128,
              qk_nope_head_dim=32, qk_rope_head_dim=16, v_head_dim=32,
              intermediate_size=256, moe_intermediate_size=128,
              n_routed_experts=32, n_shared_experts=1,
              num_experts_per_tok=4, n_group=4, topk_group=2,
              routed_scaling_factor=2.5, first_k_dense_replace=1,
              rope_theta=10000, rope_scaling=YARN, rms_norm_eps=1e-6,
              max_position_embeddings=512)
BLOCK = dict(vocab_size=256, hidden_size=128, num_hidden_layers=2,
             num_attention_heads=4, num_key_value_heads=2, head_dim=32,
             moe_intermediate_size=64, num_experts=8,
             num_experts_per_tok=2, rope_theta=10000, block_length=4,
             mask_token_id=255, max_position_embeddings=512)
# sha1 of the jaxprs' text on the commit PR 38 started from (4c0d884),
# by ``_program_jaxprs`` there; the six expert programs re-pinned by
# PR 45, whose ``expert_ffn`` numbers its slots choice-major; the four
# of the sigmoid router (dots, xing) re-made by PR 51, whose
# ``route_grouped_sigmoid`` chooses by reductions where it sorted (held
# to the three-``top_k`` form bit for bit by
# ``test_latent_moe_serving.py``). sdar's programs, whose router is
# ``route_softmax_topk``, are PR 45's still, and the toy programs, which
# have no expert layer, 4c0d884's: the proof that PR 51 touched no other
# router's cells
PARENT_PROGRAMS = {
    "toy.prefill": "b5050806fa1fe556", "toy.step": "d4ebfb3472495617",
    "dots.prefill": "0dcf420bbe7fae71", "dots.step": "c00a1e6e53e5e2b0",
    "xing.prefill": "609d6a9023994f47", "xing.step": "0744fac67c03efa9",
    "sdar.prefill": "da37af03131dfff8", "sdar.step": "0d68eba0c238c13c"}


def _program_jaxprs():
    """``{name: text of the jaxpr}`` of the prefill and step programs of
    the four kinds of model that keep no state a row, at a test's size."""
    W, M, S = 3, 6, 8

    def text(fn, holder, *args):
        return str(jax.make_jaxpr(functools.partial(fn, holder))(*args))

    i32 = lambda *shape: jnp.zeros(shape, jnp.int32)      # noqa: E731
    out = {}
    toy = ToyDecoderLM(vocab=32, n_layers=2, n_heads=2, head_dim=8)
    dots = LatentMoEDecoderLM(**LATENT)
    xing = LatentMoEDecoderLM(**dict(
        LATENT, n_routed_experts=16, n_group=1, topk_group=1, hc_mult=4,
        num_nextn_predict_layers=1))
    sdar = BlockDiffusionMoEDecoderLM(**BLOCK)
    for name, model, pools in (
            ("toy", toy, (jnp.zeros((2, 24, S, 2, 8)),) * 2),
            ("dots", dots, (jnp.zeros((3, 24, S, dots.row_width),
                                      jnp.bfloat16),)),
            ("xing", xing, (jnp.zeros((4, 24, S, xing.row_width),
                                      jnp.bfloat16),)),
            ("sdar", sdar, (jnp.zeros((2, 24, S, 2, 32), jnp.float32),) * 2)):
        params = model.init_params(seed=0)
        counters = getattr(model, "step_counters", None)
        holder = type("S", (), {
            "_model": model, "_window": W, "_counters": counters,
            "_block": getattr(model, "block_length", 0),
            "_step_fn": DecodeServer._step_fn})()
        n_counts = len(counters[1]) if counters else 0
        pre = (params, i32(1, 16), jnp.int32(8), i32(M), *pools)
        if name == "sdar":
            Q = model.block_length
            out["sdar.prefill"] = text(DecodeServer._block_prefill_fn,
                                       holder, *pre)
            out["sdar.step"] = text(
                DecodeServer._block_decode_fn, holder, params, i32(W, Q),
                i32(W), i32(W), i32(W), i32(W, M),
                i32(W * (Q + 2) + n_counts), i32(W), *pools)
        elif name == "xing":
            out["xing.prefill"] = text(DecodeServer._spec_prefill_fn,
                                       holder, *pre)
            out["xing.step"] = text(
                DecodeServer._spec_decode_fn, holder, params, i32(W, 2),
                i32(W), i32(W, M), i32(W * 5 + n_counts), i32(W), *pools)
        else:
            out[name + ".prefill"] = text(DecodeServer._prefill_fn, holder,
                                          *pre)
            out[name + ".step"] = text(
                DecodeServer._decode_fn, holder, params, i32(W), i32(W),
                i32(W, M), i32(W + n_counts), i32(W), *pools)
    return out


@functools.lru_cache(maxsize=None)
def _program_hashes():
    return {name: hashlib.sha1(text.encode()).hexdigest()[:16]
            for name, text in _program_jaxprs().items()}


@pytest.mark.parametrize("program", [
    "toy.prefill", "toy.step", "dots.prefill", "dots.step", "xing.prefill",
    "xing.step", "sdar.prefill", "sdar.step"])
def test_models_without_row_state_run_the_parents_programs(program):
    """``ToyDecoderLM``, the latent model with and without its streams
    and module, and the block-diffusion model trace to the jaxprs they
    traced to before a server could hold state beside its pages."""
    assert _program_hashes()[program] == PARENT_PROGRAMS[program]
