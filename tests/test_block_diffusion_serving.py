"""A block model on ``DecodeServer``: generation by diffusion over blocks
through ONE block-step program, grouped-query K/V pages read by the
multi-query paged kernel, softmax top-k routing — against the plain
float32 reference (``benchmark/reference/block_diffusion_lm.py``), on
seeded weights, LOGITS and not tokens, at tiny sizes on the CPU (the
Pallas kernels interpreted)."""
import functools
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmark.reference import block_diffusion_lm as ref   # noqa: E402
from mxnet_tpu import compile_watch, fault, profiler, telemetry  # noqa: E402
from mxnet_tpu.parallel import moe                          # noqa: E402
from mxnet_tpu.serving import (DecodeServer, KVCachePool,   # noqa: E402
                               ServerOverloadedError, ToyDecoderLM,
                               kvcache)
from mxnet_tpu.serving.block_diffusion import (             # noqa: E402
    BlockDiffusionMoEDecoderLM)
from mxnet_tpu.serving.latent_moe import LatentMoEDecoderLM  # noqa: E402
from serving_common import drain as _drain, jit_prefill     # noqa: E402

# the published block's shape at a test's size: 4 query heads over 2
# key/value heads of 128 (whole lane tiles, so the bf16 pool is the
# packed one and the Pallas paths tile as at the real 32 over 4), 8
# experts, top 2, blocks of 4
CFG = dict(vocab_size=96, hidden_size=64, num_hidden_layers=2,
           num_attention_heads=4, num_key_value_heads=2, head_dim=128,
           moe_intermediate_size=128, num_experts=8, num_experts_per_tok=2,
           rope_theta=1e6, block_length=4, mask_token_id=95,
           denoising_steps=4, remasking_strategy="low_confidence_dynamic",
           confidence_threshold=0.9, rms_norm_eps=1e-6,
           max_position_embeddings=512)
B = CFG["block_length"]
MASK = CFG["mask_token_id"]


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
    model = BlockDiffusionMoEDecoderLM(**dict(CFG, **dict(over)),
                                       use_pallas=use_pallas)
    return model, model.init_params(seed=seed)


def _cfg(model):
    return dict(CFG, block_length=model.block_length,
                denoising_steps=model.denoising_steps,
                remasking_strategy=model.remasking_strategy,
                confidence_threshold=model.confidence_threshold)


def _server(model, params, **kw):
    kw = dict(dict(seq_ladder=[16, 32], max_new_tokens=24, page_size=16,
                   window=3, pool_pages=24, start=False), **kw)
    return DecodeServer(model, params, **kw)


def _tail(req):
    """The rest of the last block where the answer was cut inside it."""
    used = (len(req.prompt) + len(req.generated)) % B
    if not used:
        return np.zeros((0,), np.int32), np.zeros((0,), int)
    return (np.asarray(req.blk_x[used:], np.int32),
            np.asarray(req.blk_when[used:], int))


@functools.lru_cache(maxsize=None)
def _block_pass(model):
    """One pass of one row's block through the layout's one-block forms,
    ``(params, table, pools, x, start, commit) -> (logits, pools)``,
    jitted once a model: weights, pool and page table are arguments."""
    @jax.jit
    def one_pass(params, table, pools, x, start, commit):
        layout = kvcache.layout_for(model, pools)
        attend = layout.attend_block(pools, table[None], start)
        logits, k, v, _ = model.decode_block(params, x, start, attend)
        return logits, layout.write_block(
            pools, table[None], start, [k, v], commit, model.use_pallas)
    return one_pass


def _against_reference(model, params, req, padded=64, control=False):
    return ref.teacher_forced(
        params, req.prompt, np.asarray(req.generated, np.int32),
        np.asarray(req.unmask_pass, int), _tail(req), padded, _cfg(model),
        control=control)


# ---------------------------------------------------------------------------
# the model's passes through the pool, against the reference's logits
# ---------------------------------------------------------------------------

# The program rounds every activation, key and value to bf16 in front of
# a product (2**-9 relative a rounding) and the reference none: at these
# widths a position's worst logit lies within 0.04 deviations of the
# reference's (seen: 0.012 at the worst over seeds, paths and prompt
# remainders). The router is discrete: a near-tie that flips under that
# rounding puts the position a whole expert off (0.5 deviations seen), so
# one position in ten may be over. The float8 control is 0.2 deviations
# and more off at every position. 0.06 is five times the program's worst
# unflipped position and a third of the control's best.
LOGIT_TOLERANCE = 0.06


def _position_errors(got, want):
    return np.abs(np.asarray(got) - np.asarray(want)).max(axis=1) \
        / np.asarray(want).std()


@pytest.mark.parametrize("remainder", [0, 1, 2, 3])
@pytest.mark.parametrize("use_pallas", [False, True], ids=["jnp", "pallas"])
def test_prefill_and_block_passes_agree_with_the_reference_on_logits(
        use_pallas, remainder):
    """One prefill over the prompt's whole blocks written into the paged
    pool, then every block of the answer: its denoising passes (one
    position unmasked a pass, in a drawn order) and its commit, through
    the layout's own ``attend_block`` and ``write_block`` — what the two
    programs compute, with the logits kept — against the reference's
    noisy and clean passes over the same final tokens."""
    model, params = _model(use_pallas)
    S, P, L = 16, 8 + remainder, 24
    rng = np.random.default_rng(remainder)
    final = rng.integers(0, MASK, size=L).astype(np.int32)
    first = P // B * B
    pool = KVCachePool(model.n_layers,
                       arrays=[c[:2] for c in model.cache_arrays],
                       dtype=model.cache_arrays[0][2], page_size=S,
                       n_pages=4)
    assert type(pool.layout).__name__ == "_PackedHeadKV"
    pools = tuple(pool.arrays)
    table = np.asarray([1, 2, 0], np.int32)
    padded = np.zeros((1, S), np.int32)
    padded[0, :P] = final[:P]
    logits, *seqs = jit_prefill(model)(params, padded)
    want = ref.forward(params, final, _cfg(model))
    # the whole blocks of the prompt are final: their logits are the
    # clean pass's
    errs = list(_position_errors(logits[0, :first], want[:first]))
    pools = pool.layout.write_prefill(pools, table, seqs, first)

    def one_pass(pools, x, start, commit):
        logits, pools = _block_pass(model)(params, table, pools, x, start,
                                           commit)
        return logits[0], pools

    for start in range(first, L, B):
        held = max(P - start, 0)
        order = held + rng.permutation(B - held)
        when = np.full((L,), 1 << 30)
        when[:start + held] = -1
        for p, j in enumerate(order):
            masked = np.zeros((L,), bool)
            masked[start:start + B] = when[start:start + B] > p - 1
            masked[start + B:] = True
            x = np.where(masked, MASK, final)[None, start:start + B]
            got, _ = one_pass(pools, jnp.asarray(x),
                              jnp.asarray([start], jnp.int32),
                              jnp.asarray([False]))
            noisy = ref.denoising_logits(params, final, masked, _cfg(model))
            errs += list(_position_errors(got, noisy[start:start + B]))
            when[start + j] = p
        got, pools = one_pass(pools, jnp.asarray(final[None, start:start + B]),
                              jnp.asarray([start], jnp.int32),
                              jnp.asarray([True]))
        errs += list(_position_errors(got, want[start:start + B]))
    errs = np.asarray(errs)
    assert np.median(errs) < LOGIT_TOLERANCE / 3, np.median(errs)
    assert (errs > LOGIT_TOLERANCE).mean() <= 0.1, np.sort(errs)[-8:]


@pytest.mark.parametrize("use_pallas", [False, True], ids=["jnp", "pallas"])
def test_served_tokens_are_the_references_own_or_near_ties(use_pallas):
    """Through the scheduler: prompts with every remainder, answers cut
    inside a block, each served token held against the reference's
    logits of the pass that chose it; the float8 control in the
    program's place reads many times more."""
    model, params = _model(use_pallas)
    srv = _server(model, params)
    rng = np.random.default_rng(5)
    reqs = [srv.submit(rng.integers(0, MASK, size=n).astype(np.int32),
                       max_new_tokens=k)
            for n, k in ((8, 16), (9, 7), (14, 9), (3, 5), (31, 24))]
    _drain(srv, *reqs)
    mine = control = tokens = 0
    for r in reqs:
        assert r.state == "done" and len(r.generated) == r.max_new
        assert len(r.unmask_pass) == len(r.generated)
        assert set(r.unmask_pass) <= {0, 1, 2, 3}
        out = _against_reference(model, params, r, control=True)
        # a near-tie may go the other way, never a token a deviation off
        assert out["worst"] < 0.3, out
        mine += out["mean"] * out["tokens"]
        control += out["control_mean"] * out["tokens"]
        tokens += out["tokens"]
    # mean gap a token, in deviations of the reference's logits: the
    # program 4e-5 (61 tokens, most the reference's own; a flipped
    # near-tie costs under 0.01), the float8 control 3e-3, 80 times more.
    # 1e-3 lies between with room on both sides; 10 parts the two
    assert mine / tokens < 1e-3, mine / tokens
    assert control > 10 * mine and control / tokens > 1e-3, (mine, control)
    st = srv.stats()
    assert st["kv"]["used"] == 0 and st["completed"] == len(reqs)
    srv.stop()


# ---------------------------------------------------------------------------
# the unmasking rule, and what the scheduler hands out
# ---------------------------------------------------------------------------

def test_unmask_rule_static_dynamic_and_ties():
    model, _ = _model()
    static, _ = _model(remasking_strategy="low_confidence_static")
    two, _ = _model(remasking_strategy="low_confidence_static",
                    denoising_steps=2)
    V = CFG["vocab_size"]
    z = np.zeros((3, B, V), np.float32)
    z[0, :, 7] = [9.0, 3.0, 3.0, 1.0]     # one position over the threshold
    z[1, :, 5] = [1.0, 2.0, 2.0, 0.5]     # none over; a tie of 1 and 2
    z[2, :, 9] = [30.0, 30.0, 0.0, 30.0]  # three over
    x = np.full((3, B), MASK, np.int32)
    masked = np.ones((3, B), bool)
    masked[2, 3] = False                  # already unmasked: stays as it is
    x[2, 3] = 4
    for m, want in ((model, [[0], [1], [0, 1]]), (static, [[0], [1], [0]]),
                    (two, [[0, 1], [1, 2], [0, 1]])):
        nx, left = m.unmask(jnp.asarray(z), jnp.asarray(x),
                            jnp.asarray(masked))
        nx, left = np.asarray(nx), np.asarray(left)
        for row, picked in enumerate(want):
            gone = [j for j in range(B) if masked[row, j] and not left[row, j]]
            assert gone == picked, (m.remasking_strategy, row, gone)
            assert all(nx[row, j] == (7, 5, 9)[row] for j in picked)
        assert nx[2, 3] == 4 and not left[2, 3]
        assert (nx[left] == MASK).all()
    # nothing masked: returned as it came (a commit pass)
    nx, left = model.unmask(jnp.asarray(z), jnp.asarray(x),
                            jnp.zeros((3, B), bool))
    assert (np.asarray(nx) == x).all() and not np.asarray(left).any()


def test_static_is_dynamic_when_nothing_passes_the_threshold():
    """Seeded random weights: no confidence reaches 0.9, the dynamic
    rule falls back in every pass, and the two strategies serve the same
    tokens in the same passes: one position a pass, 4 passes a block —
    its commit rides with the next block's first pass — and a pass a
    token, but for the positions behind the cut of a row's last block."""
    prompts = [np.arange(1, n + 1, dtype=np.int32) for n in (5, 8, 14)]
    got = []
    for strategy in ("low_confidence_dynamic", "low_confidence_static"):
        model, params = _model(remasking_strategy=strategy)
        srv = _server(model, params)
        reqs = [srv.submit(p, max_new_tokens=12) for p in prompts]
        _drain(srv, *reqs)
        got.append([(r.result().tolist(), r.unmask_pass) for r in reqs])
        st = srv.stats()
        block = st["block"]
        assert block["max_passes_a_block"] == 4
        assert block["tokens_unmasked"] == block["denoise_passes"]
        assert block["denoise_passes"] + block["commit_passes"] \
            == st["tokens_out"] + sum(len(_tail(r)[0]) for r in reqs)
        assert block["fused_commits"] == block["blocks_committed"] > 0
        srv.stop()
    assert got[0] == got[1]
    # the prompt of 8 ends on a block: its answer's first 4 tokens are
    # one whole block, one position a pass
    assert sorted(got[0][1][1][:4]) == [0, 1, 2, 3]


def test_a_confident_head_settles_a_block_in_fewer_passes():
    """A head scaled up until every confidence is over the threshold:
    the dynamic rule unmasks a whole block in one pass or two, the
    static rule still takes 4 (a block's commit rides with the next
    block's first pass: it is no pass of its own); both serve what the
    reference's rule would."""
    counts = {}
    for strategy in ("low_confidence_dynamic", "low_confidence_static"):
        model, params = _model(remasking_strategy=strategy)
        params = dict(params, head=(params["head"].astype(jnp.float32)
                                    * 1000).astype(jnp.bfloat16))
        srv = _server(model, params)
        reqs = [srv.submit(np.arange(1, n + 1, dtype=np.int32),
                           max_new_tokens=k) for n, k in ((8, 12), (6, 10))]
        _drain(srv, *reqs)
        block = srv.stats()["block"]
        counts[strategy] = (block["denoise_passes"],
                            block["max_passes_a_block"])
        for r in reqs:
            # a position whose two best logits nearly tie may read over
            # the threshold here and under it there: one block of three
            # at the most, and never a token that is far from the best
            out = _against_reference(model, params, r)
            assert out["unmask_differs"] <= 1 / 3 and out["worst"] < 0.01, \
                out
        srv.stop()
    assert counts["low_confidence_dynamic"][1] <= 2
    assert counts["low_confidence_static"][1] == 4
    assert counts["low_confidence_dynamic"][0] * 3 \
        < counts["low_confidence_static"][0]


def test_tokens_come_a_block_at_a_time_and_the_last_block_is_cut():
    model, params = _model()
    srv = _server(model, params)
    # prompt 6: the first block holds 2 prompt tokens and gives 2; then
    # whole blocks of 4; max_new 9 cuts the last block after 3
    req = srv.submit(np.arange(1, 7, dtype=np.int32), max_new_tokens=9)
    grew, n = [], 0
    while not req.done():
        srv._tick()
        if len(req.generated) != n:
            grew.append(len(req.generated) - n)
            n = len(req.generated)
    assert grew == [2, 4, 3]
    assert list(req.tokens(timeout=1)) == req.result().tolist()
    # eos inside a block cuts it there, the eos token included
    toks = req.result().tolist()
    again = srv.submit(np.arange(1, 7, dtype=np.int32), max_new_tokens=9,
                       eos_id=toks[3])
    _drain(srv, again)
    assert again.result().tolist() == toks[:toks.index(toks[3]) + 1]
    st = srv.stats()
    assert st["tokens_out"] == 9 + len(again.generated)
    assert st["kv"]["used"] == 0
    # the gaps: a block's tokens share one stamp
    assert st["inter_token_ms"]["p50"] == 0.0
    srv.stop()


def test_cancel_and_preemption_in_mid_block_give_pages_back():
    model, params = _model()
    srv = _server(model, params, pool_pages=6, window=2, seq_ladder=[16],
                  max_new_tokens=40)
    low = srv.submit(np.arange(1, 10, dtype=np.int32), max_new_tokens=40,
                     priority=0)
    for _ in range(3):
        srv._tick()                   # mid-block: something still masked
    assert low.state == "active" and any(low.blk_masked)
    high = srv.submit(np.arange(1, 15, dtype=np.int32), max_new_tokens=40,
                      priority=2)
    more = srv.submit(np.arange(1, 12, dtype=np.int32), max_new_tokens=40,
                      priority=2)
    _drain(srv, low, limit=2000)
    with pytest.raises(ServerOverloadedError):
        low.result()                  # preempted under pool pressure
    for _ in range(4):
        srv._tick()
    more.cancel()
    _drain(srv, high, more, limit=2000)
    assert more.state == "cancelled" and high.state == "done"
    assert len(high.generated) == 40
    st = srv.stats()
    assert st["preempted"] >= 1 and st["cancelled"] == 1
    assert st["kv"]["used"] == 0 and st["kv"]["free"] == 5
    srv.stop()


def test_one_program_set_under_a_mixed_request_mix():
    compile_watch.enable()
    model, params = _model()
    srv = _server(model, params, name="blk")
    assert srv.warmup() == 3
    rng = np.random.default_rng(9)
    reqs = [srv.submit(rng.integers(0, MASK, size=n).astype(np.int32),
                       max_new_tokens=k)
            for n, k in ((3, 5), (16, 24), (20, 1), (31, 9), (7, 12),
                         (12, 4))]
    _drain(srv, *reqs)
    sites = compile_watch.site_stats("decode:blk")
    assert sorted(sites) == ["decode:blk:prefill:s16",
                             "decode:blk:prefill:s32", "decode:blk:step"]
    assert all(s["count"] == 1 for s in sites.values())
    st = srv.stats()
    assert st["decode_steps_ahead"] >= st["decode_steps"] - 2
    block = st["block"]
    # every commit rode with the next block's first pass: none ran alone
    assert block["blocks_committed"] \
        == block["fused_commits"] + block["commit_passes"]
    assert block["commit_passes"] == 0 < block["fused_commits"]
    assert st["kv"]["arrays"] == {"k": [2, 128], "v": [2, 128]}
    assert st["kv"]["token_bytes"] == 2 * 2 * 2 * 128 * 2
    # only live positions choose experts (2 layers, top 2): a block a
    # pass, and the fresh block of a pass that commits; a row that ended
    # while a step was unread ran that step too, fused or not
    passes = block["denoise_passes"] + block["fused_commits"]
    assert 0 <= st["moe"]["moe_slots"] - passes * 2 * B * 2 \
        <= 2 * len(reqs) * 2 * B * 2
    assert st["moe"]["moe_slots"] < st["moe"]["steps"] * 2 * 3 * 2 * B * 2
    counters = profiler.counters()
    assert counters.get("block_decode_jnp", 0) >= 2
    srv.stop()


def test_a_weight_swap_in_mid_block_finishes_on_the_old_weights():
    model, params = _model()
    other = model.init_params(seed=11)
    prompt = np.arange(1, 11, dtype=np.int32)
    alone = _server(model, params)
    want = alone.submit(prompt, max_new_tokens=14)
    _drain(alone, want)
    alone.stop()
    srv = _server(model, params)
    first = srv.submit(prompt, max_new_tokens=14)
    for _ in range(4):
        srv._tick()
    assert any(first.blk_masked)
    srv.swap_weights(other)
    second = srv.submit(prompt, max_new_tokens=14)
    _drain(srv, first, second)
    assert first.result().tolist() == want.result().tolist()
    assert second.result().tolist() != want.result().tolist()
    assert _against_reference(model, other, second)["worst"] < 0.3
    assert srv.stats()["decode_drains"].get("versions", 0) >= 1
    srv.stop()


# ---------------------------------------------------------------------------
# the pool's layout
# ---------------------------------------------------------------------------

def test_narrow_heads_of_a_16_bit_pool_are_packed_into_one_row():
    bf16, f32 = jnp.dtype("bfloat16"), jnp.dtype("float32")

    def kind(heads, width, dtype):
        specs = (("k", (heads, width)), ("v", (heads, width)))
        return type(kvcache.cache_layout(specs, dtype)).__name__

    assert kind(4, 128, bf16) == "_PackedHeadKV"
    assert kind(4, 128, f32) == "_PerHeadKV"      # 32-bit: nothing to pack
    assert kind(32, 128, bf16) == "_PerHeadKV"    # fills the tile
    assert kind(2, 8, bf16) == "_PerHeadKV"       # no whole lanes to pack
    assert kind(4, 128, jnp.dtype("int8")) == "_PerHeadKVInt8"
    pool = KVCachePool(7, 4, 128, page_size=128, n_pages=3, dtype="bfloat16")
    assert [a.shape for a in pool.arrays] == [(7, 3, 128, 512)] * 2
    assert pool.token_bytes == 14336
    assert pool.stats()["arrays"] == {"k": [4, 128], "v": [4, 128]}


def test_a_packed_pool_serves_a_one_position_step_too():
    """``ToyDecoderLM`` (one position a step, equal head counts) over a
    packed pool attends through the block path as a block of one: the
    same numbers as the per-head path on the same cache."""
    L, P, S, H, D, Bn = 1, 5, 8, 2, 128, 3
    k = jax.random.split(jax.random.PRNGKey(4), 5)
    kp = jax.random.normal(k[0], (L, P, S, H, D)).astype(jnp.bfloat16)
    vp = jax.random.normal(k[1], (L, P, S, H, D)).astype(jnp.bfloat16)
    q, kn, vn = (jax.random.normal(k[i], (Bn, H, D)) for i in (2, 3, 4))
    table = jnp.asarray([[1, 2, 3], [4, 0, 0], [2, 0, 0]], jnp.int32)
    pos = jnp.asarray([19, 3, 0], jnp.int32)
    want = kvcache.paged_attention(kp, vp, table, pos, 0, q, kn, vn)
    packed = kvcache.cache_layout((("k", (H, D)), ("v", (H, D))),
                                  jnp.dtype("bfloat16"))
    pools = (kp.reshape(L, P, S, H * D), vp.reshape(L, P, S, H * D))
    for force in (False, True):
        got = packed.attend(pools, table, pos)(0, q, kn, vn,
                                               force_pallas=force)
        assert got.shape == (Bn, H, D)
        assert np.abs(np.asarray(got) - np.asarray(want)).max() < 0.02
    new = [jax.random.normal(k[0], (L, Bn, H, D))] * 2
    wrote = packed.write_tokens(pools, table, pos, new)
    assert wrote[0].shape == pools[0].shape
    row = np.asarray(wrote[0].reshape(L, P, S, H, D)[0, 3, 3])
    assert (row == np.asarray(new[0][0, 0].astype(jnp.bfloat16))).all()


# ---------------------------------------------------------------------------
# the Pallas kernels, interpreted, against the jnp paths
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("positions", [[40, 16, 0], [60, 4, 12], [0, 0, 0],
                                       "ragged", "poisoned_tail"])
def test_block_decode_kernel_matches_gather_reference(positions,
                                                      ragged_pages):
    L, P, S, Hq, Hkv, D, Q = 2, 11, 16, 8, 2, 128, 4
    # ragged rows; a dead table tail (page 0, never live)
    table = [[1, 2, 3, 7], [4, 5, 0, 0], [6, 0, 0, 0]]
    poisoned = positions == "poisoned_tail"
    if isinstance(positions, str):
        # 0, 1, S-1, S, S+1 keys and a full table in one batch; the
        # poisoned table is 4x wider than any row needs
        table, positions = ragged_pages(S, Q, widen=4 if poisoned else 1)
        D = 256            # the running max and sum repeated over 2 tiles
    Bn = len(positions)
    k = jax.random.split(jax.random.PRNGKey(0), 5)
    kp = jax.random.normal(k[0], (L, P, S, Hkv * D)).astype(jnp.bfloat16)
    vp = jax.random.normal(k[1], (L, P, S, Hkv * D)).astype(jnp.bfloat16)
    q = jax.random.normal(k[2], (Bn, Q, Hq, D))
    kn = jax.random.normal(k[3], (Bn, Q, Hkv, D))
    vn = jax.random.normal(k[4], (Bn, Q, Hkv, D))
    table = jnp.asarray(table, jnp.int32)
    pos = jnp.asarray(positions, jnp.int32)
    before = dict(profiler.counters())
    for layer in (0, 1):
        a = kvcache.paged_block_attention(kp, vp, table, pos, layer, q, kn,
                                          vn)
        b = kvcache.paged_block_attention(kp, vp, table, pos, layer, q, kn,
                                          vn, force_pallas=True)
        assert a.shape == b.shape == (Bn, Q, Hq, D)
        assert b.dtype == jnp.float32
        # the kernel rounds the softmax weights to bf16 for the MXU
        assert np.abs(np.asarray(a) - np.asarray(b)).max() < 0.03
        if poisoned:
            # every dead column names a page of NaN: a masked fold would
            # not do (0 x NaN survives the value product), the walk must
            # not read it — and reads what the clean table's walk reads
            nan = jnp.full_like(kp[:, 0], jnp.nan)
            c = kvcache.paged_block_attention(
                kp.at[:, P - 1].set(nan), vp.at[:, P - 1].set(nan),
                jnp.where(table == 0, P - 1, table), pos, layer, q, kn, vn,
                force_pallas=True)
            assert bool(jnp.isfinite(c).all()) and bool((c == b).all())
        # against gather_pages + a masked softmax written out here
        kc = np.asarray(kvcache.gather_pages(kp[layer:layer + 1], table)[0],
                        np.float32).reshape(Bn, -1, Hkv, D)
        vc = np.asarray(kvcache.gather_pages(vp[layer:layer + 1], table)[0],
                        np.float32).reshape(Bn, -1, Hkv, D)
        r32 = lambda x: np.asarray(x.astype(jnp.bfloat16), np.float32)  # noqa: E731,E501
        for row in range(Bn):
            n = positions[row]
            for h in range(Hq):
                g = h // (Hq // Hkv)
                keys = np.concatenate([kc[row, :n, g], r32(kn)[row, :, g]])
                vals = np.concatenate([vc[row, :n, g], r32(vn)[row, :, g]])
                s = r32(q * D ** -0.5)[row, :, h] @ keys.T
                w = np.exp(s - s.max(-1, keepdims=True))
                want = (w / w.sum(-1, keepdims=True)) @ vals
                assert np.abs(np.asarray(a)[row, :, h] - want).max() < 2e-3
    after = profiler.counters()
    for path in ("jnp", "pallas"):
        key = "block_decode_" + path
        assert after.get(key, 0) - before.get(key, 0) \
            == 2 + 2 * (poisoned and path == "pallas")


def test_block_write_kernel_is_the_row_writes():
    L, P, S, W, Bn, Q = 2, 9, 16, 256, 3, 4
    k = jax.random.split(jax.random.PRNGKey(1), 2)
    pool = jax.random.normal(k[0], (L, P, S, W)).astype(jnp.bfloat16)
    new = jax.random.normal(k[1], (L, Bn, Q, 2, W // 2))
    table = jnp.asarray([[1, 2, 3, 7], [4, 5, 0, 0], [6, 0, 0, 0]],
                        jnp.int32)
    pos = jnp.asarray([40, 16, 4], jnp.int32)
    commit = jnp.asarray([True, True, False])
    a = kvcache.write_block_rows(pool, table, pos, new, commit)
    b = kvcache.write_block_rows(pool, table, pos, new, commit,
                                 force_pallas=True)
    assert a.dtype == b.dtype == jnp.bfloat16 and bool((a == b).all())
    changed = np.asarray((a != pool).any(axis=-1))
    assert changed.sum() == L * Bn * Q
    assert changed[:, 3, 8:12].all() and changed[:, 5, 0:4].all()
    # the row that does not commit wrote the dump page, not page 6
    assert changed[:, 0, 4:8].all() and not changed[:, 6].any()


# ---------------------------------------------------------------------------
# the router
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("renormalize", [True, False])
def test_softmax_router_against_the_reference_with_ties(renormalize):
    T, D, E, K = 12, 16, 8, 3
    k = jax.random.split(jax.random.PRNGKey(3), 2)
    x = jax.random.normal(k[0], (T, D))
    w = np.array(jax.random.normal(k[1], (D, E)))
    w[:, 5] = w[:, 2]                   # experts 2 and 5 tie at every token
    w[:, 7] = w[:, 0]
    w = jnp.asarray(w)
    ids, weights = moe.route_softmax_topk(x, w, top_k=K,
                                          renormalize=renormalize)
    rid, rw = ref.route(x, w, top_k=K, renormalize=renormalize)
    assert ids.dtype == jnp.int32 and (np.asarray(ids) == np.asarray(rid)).all()
    assert np.abs(np.asarray(weights) - np.asarray(rw)).max() < 1e-6
    ids = np.asarray(ids)
    # a tie goes to the lower index: 5 never without 2, 7 never without 0
    assert all(2 in row for row in ids if 5 in row)
    assert all(0 in row for row in ids if 7 in row)
    sums = np.asarray(weights).sum(-1)
    assert (np.abs(sums - 1) < 1e-6).all() if renormalize \
        else (sums < 1 - 1e-6).all()


# ---------------------------------------------------------------------------
# the other models' programs are the ones they were
# ---------------------------------------------------------------------------

def _old_toy_step(model, params, tokens, positions, page_tables, k_pages,
                  v_pages):
    attend = functools.partial(kvcache.paged_attention, k_pages, v_pages,
                               page_tables, positions)
    logits, k_new, v_new = model.decode(params, tokens, positions, attend)
    k_pages = kvcache.scatter_token(k_pages, page_tables, positions, k_new)
    v_pages = kvcache.scatter_token(v_pages, page_tables, positions, v_new)
    return jnp.argmax(logits, axis=-1).astype(jnp.int32), k_pages, v_pages


def _old_latent_step(model, params, tokens, positions, page_tables, pages):
    attend = functools.partial(kvcache.paged_latent_attention, pages,
                               page_tables, positions)
    logits, new, counters = model.decode(params, tokens, positions, attend)
    pages = kvcache.write_token_rows(pages, page_tables, positions, new,
                                     model.use_pallas)
    out = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    return jnp.concatenate(
        [out, counters.astype(jnp.int32).reshape(-1)]), pages


@pytest.mark.parametrize("which", ["toy", "latent"])
def test_the_other_models_step_programs_are_unchanged(which):
    """A block model added a program beside the step program, not a
    branch inside it: ``ToyDecoderLM``'s and ``LatentMoEDecoderLM``'s
    step trace to the jaxprs they had (the oracles above are the
    programs as they stood before this model)."""
    if which == "toy":
        model = ToyDecoderLM(vocab=32, n_layers=2, n_heads=2, head_dim=8,
                             max_len=128)
        pool = jnp.zeros((2, 24, 8, 2, 8), jnp.float32)
        pools, old = (pool, pool), _old_toy_step
    else:
        model = LatentMoEDecoderLM(
            vocab_size=64, hidden_size=32, num_hidden_layers=2,
            num_attention_heads=2, q_lora_rank=16, kv_lora_rank=16,
            qk_nope_head_dim=8, qk_rope_head_dim=4, v_head_dim=8,
            intermediate_size=32, moe_intermediate_size=16,
            n_routed_experts=8, n_shared_experts=1, num_experts_per_tok=2,
            n_group=2, topk_group=1, routed_scaling_factor=2.5,
            first_k_dense_replace=1, rope_theta=10000,
            rope_scaling={"beta_fast": 32, "beta_slow": 1, "factor": 40,
                          "mscale": 1, "mscale_all_dim": 1,
                          "original_max_position_embeddings": 4096,
                          "type": "yarn"}, max_position_embeddings=256)
        pools = (jnp.zeros((2, 24, 8, model.row_width), jnp.bfloat16),)
        old = _old_latent_step
    params = model.init_params(seed=3)
    holder = type("S", (), {"_model": model})()
    args = (params, jnp.zeros((3,), jnp.int32), jnp.zeros((3,), jnp.int32),
            jnp.zeros((3, 6), jnp.int32), *pools)
    new = jax.make_jaxpr(functools.partial(DecodeServer._step_fn,
                                           holder))(*args)
    was = jax.make_jaxpr(functools.partial(old, model))(*args)
    assert str(new) == str(was)
