"""``test_block_diffusion_serving.py``, continued (a file of its own so
that no file is the floor of a ``--dist loadfile`` run): a block's commit
rides with the next block's first denoising pass — the fused pass against
the two it replaces, the served tokens against the unfused schedule, and
where a fused pass may and may not land. Model, sizes and helpers are that
file's, its autouse
``_clean_state`` among them (imported, it is this file's fixture too)."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mxnet_tpu.base import MXNetError
from mxnet_tpu.serving import (DecodeServer, KVCachePool,
                               ServerOverloadedError)
from serving_common import drain as _drain, jit_prefill
from test_block_diffusion_serving import (B, CFG, MASK,     # noqa: F401
                                          _against_reference, _block_pass,
                                          _clean_state, _model, _server)


# ---------------------------------------------------------------------------
# a block's commit rides with the next block's first denoising pass
# ---------------------------------------------------------------------------

def _pool_behind(model, firsts, S=16, rung=32, seed=7):
    """A pool in which row ``i`` has ``firsts[i]`` prompt tokens (whole
    blocks) committed through its own two pages: ``(layout, pools,
    tables)``."""
    n = len(firsts)
    pool = KVCachePool(model.n_layers,
                       arrays=[c[:2] for c in model.cache_arrays],
                       dtype=model.cache_arrays[0][2], page_size=S,
                       n_pages=2 * n + 1)
    rng = np.random.default_rng(seed)
    pools = tuple(pool.arrays)
    tables = np.arange(1, 2 * n + 1, dtype=np.int32).reshape(n, 2)
    prefill = jit_prefill(model)
    for i, first in enumerate(firsts):
        padded = np.zeros((1, rung), np.int32)
        padded[0, :first] = rng.integers(0, MASK, size=first)
        _logits, *seqs = prefill(_model(model.use_pallas)[1], padded)
        pools = pool.layout.write_prefill(pools, tables[i], seqs, first)
    return pool.layout, pools, tables


@pytest.mark.parametrize("use_pallas", [False, True], ids=["jnp", "pallas"])
def test_a_fused_pass_is_the_commit_and_the_first_pass_it_replaces(
        use_pallas):
    """Four rows through the step program once: row 0 has nothing masked
    and a block after it (which starts a NEW PAGE), row 1 something
    masked, row 2 nothing masked and its end at the block's, row 3 is no
    row. Against the two passes the fused one replaces, through the
    layout's one-block forms — a commit of every row's block, then a
    first denoising pass of the fresh block behind it: the logits the
    rule reads, the tokens it unmasks, the rows in the pool."""
    model, params = _model(use_pallas)
    D, V = 4, CFG["vocab_size"]
    firsts = [12, 8, 20, 0]
    layout, pools, tables = _pool_behind(model, firsts)
    rng = np.random.default_rng(11)
    x = rng.integers(0, MASK, size=(D, B)).astype(np.int32)
    x[1, [1, 3]] = MASK
    state = np.asarray([0, 0b1010, 0, -1], np.int32)
    positions = np.asarray(firsts, np.int32)
    ends = np.asarray([40, 40, 24, 0], np.int32)
    commit, fresh = state == 0, np.asarray([True, False, False, False])
    opened = np.full((D, B), MASK, np.int32)

    # the two passes it replaces
    @jax.jit
    def two_passes(pools):
        attend = layout.attend_block(pools, tables, positions)
        first, k, v, _ = model.decode_block(params, x, positions, attend)
        pools = layout.write_block(pools, tables, positions, [k, v],
                                   commit, model.use_pallas)
        attend = layout.attend_block(pools, tables, positions + B)
        second, *_ = model.decode_block(params, opened, positions + B,
                                        attend)
        return first, second, pools

    first, second, want_pools = two_passes(pools)
    want = np.where(fresh[:, None, None], second, first)

    # the fused pass, with its logits kept
    @jax.jit
    def fused(pools):
        step = layout.block_step(pools, tables, positions, commit, fresh,
                                 model.use_pallas)
        live = jnp.repeat(jnp.stack([state >= 0, fresh], axis=1), B, axis=1)
        logits, _k, _v, counters = model.decode_block(
            params, jnp.concatenate([x, opened], axis=1), positions, step,
            live=live, head=jnp.asarray(fresh, jnp.int32))
        return logits, counters, step.pools

    got, counters, got_pools = fused(pools)
    assert got.shape == (D, B, V)
    err = np.abs(np.asarray(got)[:3] - want[:3]).max() / want[:3].std()
    assert err < 1e-5, err
    # the dump page takes what is not final, in whatever order
    for a, b, old in zip(got_pools, want_pools, pools):
        a, b, old = (np.asarray(t[:, 1:], np.float32) for t in (a, b, old))
        assert np.abs(a - b).max() < 1e-6 * np.abs(b).max()
        changed = (b != old).any(axis=(0, 3))
        assert changed.sum() == 2 * B       # rows 0 and 2, B tokens each
        assert changed[0, 12:16].all() and changed[5, 4:8].all()
    # dead positions chose no expert: 2 layers x top 2 x (rows 0, 1, 2
    # and row 0's fresh block)
    assert int(counters[0]) == 2 * 2 * 4 * B

    # the step program: what leaves the device
    holder = type("S", (), {"_model": model, "_window": D, "_block": B})()
    n_counts = len(model.step_counters[1])
    out, *step_pools = jax.jit(functools.partial(
        DecodeServer._block_decode_fn, holder))(
        params, x, state, positions, ends, tables,
        np.zeros((D * (B + 2) + n_counts,), np.int32),
        np.full((D,), -1, np.int32), *pools)
    out = np.asarray(out)
    rows = out[:D * (B + 2)].reshape(D, B + 2)
    assert rows[:, B + 1].tolist() == [3, 1, 2, 0]
    masked = np.asarray([[True] * B, [False, True, False, True],
                         [False] * B, [False] * B])
    nx, left = model.unmask(jnp.asarray(want),
                            jnp.where(fresh[:, None], opened, x),
                            jnp.asarray(masked))
    assert (rows[:3, :B] == np.asarray(nx)[:3]).all()
    bits = (np.asarray(left) << np.arange(B)).sum(axis=1)
    assert rows[:, B].tolist() == [*bits[:3], -1]
    assert bin(rows[0, B]).count("1") == B - 1 == bin(rows[1, B]).count("1") + 2
    assert out[D * (B + 2):].tolist() == np.asarray(counters).tolist()
    for a, b in zip(step_pools, got_pools):
        assert bool((a[:, 1:] == b[:, 1:]).all())


def _unfused(model, params, prompt, max_new, S=16):
    """The schedule before the fusion, one request alone: the prefill,
    then for every block its denoising passes under the model's own rule
    and a commit pass BY ITSELF, through the layout's one-block forms.
    ``(tokens, unmask_pass)``."""
    P = len(prompt)
    first = P // B * B
    pool = KVCachePool(model.n_layers,
                       arrays=[c[:2] for c in model.cache_arrays],
                       dtype=model.cache_arrays[0][2], page_size=S,
                       n_pages=6)
    table = np.arange(1, 6, dtype=np.int32)
    padded = np.zeros((1, 32), np.int32)
    padded[0, :P] = prompt
    _logits, *seqs = jit_prefill(model)(params, padded)
    pools = pool.layout.write_prefill(tuple(pool.arrays), table, seqs, first)

    one_pass = functools.partial(_block_pass(model), params, table)

    tokens, when, held = [], [], [int(t) for t in prompt[first:]]
    for start in range(first, P + max_new, B):
        at = jnp.asarray([start], jnp.int32)
        x = jnp.asarray([held + [MASK] * (B - len(held))], jnp.int32)
        masked = jnp.asarray([[False] * len(held)
                              + [True] * (B - len(held))])
        unmasked_in = [-1] * B
        for n in range(B):
            if not bool(masked.any()):
                break
            logits, _ = one_pass(pools, x, at, jnp.asarray([False]))
            x, left = model.unmask(logits, x, masked)
            for j in np.flatnonzero(np.asarray(masked & ~left)[0]):
                unmasked_in[j] = n
            masked = left
        for j in range(len(held), B):
            tokens.append(int(x[0, j]))
            when.append(unmasked_in[j])
        _, pools = one_pass(pools, x, at, jnp.asarray([True]))
        held = []
    return tokens[:max_new], when[:max_new]


@pytest.mark.parametrize("use_pallas", [False, True], ids=["jnp", "pallas"])
def test_served_tokens_are_the_unfused_schedules(use_pallas):
    """The static rule on fixed seeds: what the server hands out, and the
    pass of its block that unmasked each token (the fused pass is pass 0
    of the new block), are what the schedule of separate commit passes
    computes, token for token."""
    model, params = _model(use_pallas,
                           remasking_strategy="low_confidence_static")
    srv = _server(model, params)
    rng = np.random.default_rng(21)
    reqs = [srv.submit(rng.integers(0, MASK, size=n).astype(np.int32),
                       max_new_tokens=k)
            for n, k in ((8, 16), (9, 7), (14, 13), (3, 24))]
    _drain(srv, *reqs)
    for r in reqs:
        tokens, when = _unfused(model, params, r.prompt, r.max_new)
        assert r.result().tolist() == tokens
        assert r.unmask_pass == when
    srv.stop()


def _spans_of(srv, reqs, monkeypatch, name):
    """The ``name`` spans' arguments while ``srv`` serves ``reqs``."""
    from mxnet_tpu import tracing
    seen, real = [], tracing.span

    def spying(span_name, /, *a, **k):
        sp = real(span_name, *a, **k)
        if span_name == name:
            seen.append(sp)
        return sp

    monkeypatch.setattr(tracing, "span", spying)
    _drain(srv, *reqs)
    srv._tick()
    monkeypatch.setattr(tracing, "span", real)
    return [sp.args for sp in seen]


def test_four_passes_for_four_tokens_at_the_static_floor(monkeypatch):
    """Rows that do not end inside a block, under weights no confidence of
    which reaches the threshold: a pass a token, none of them a commit by
    itself, every commit fused; the read-back span says how many of a
    step's rows fused, the dispatch span never more keys than are
    attended."""
    model, params = _model()
    srv = _server(model, params, max_new_tokens=40)
    reqs = [srv.submit(np.arange(1, n + 1, dtype=np.int32),
                       max_new_tokens=k)
            for n, k in ((8, 40), (12, 32), (4, 36))]
    said = _spans_of(srv, reqs, monkeypatch, "decode.readback")
    st = srv.stats()
    block = st["block"]
    assert st["tokens_out"] == 40 + 32 + 36
    assert block["denoise_passes"] + block["commit_passes"] \
        == st["tokens_out"]
    assert block["commit_passes"] == 0 and block["max_passes_a_block"] == 4
    # every block but a row's last is committed, all of them fused
    assert block["fused_commits"] == block["blocks_committed"] \
        == st["tokens_out"] // B - len(reqs)
    assert sum(a["blocks_fused"] for a in said) == block["fused_commits"]
    assert sum(a["blocks_committed"] for a in said) \
        == block["blocks_committed"]
    assert all(r.unmask_pass == sorted(r.unmask_pass[:B]) * (r.max_new // B)
               or sorted(r.unmask_pass[:B]) == [0, 1, 2, 3] for r in reqs)
    assert st["kv"]["used"] == 0
    srv.stop()


def _recorded_steps(srv):
    """Every block step ``srv`` dispatches from here on: ``(positions,
    ends, page tables)`` of its rows."""
    steps, real = [], srv._decode_prog

    def recording(tree, x, state, positions, ends, pts, *rest):
        steps.append((positions.copy(), ends.copy(), pts.copy()))
        return real(tree, x, state, positions, ends, pts, *rest)

    srv._decode_prog = recording
    return steps


def test_a_last_block_is_never_committed_and_no_page_lies_past_the_end():
    """A row that ends with its page (prompt 8 + 8 of 16): the block at
    8 is committed with the first pass of the block at 12, the block at
    12 settles, ends the request and is never committed; the row never
    holds a second page."""
    model, params = _model()
    srv = _server(model, params)
    steps = _recorded_steps(srv)
    req = srv.submit(np.arange(1, 9, dtype=np.int32), max_new_tokens=8)
    held = 0
    while not req.done():
        srv._tick()
        held = max(held, len(req.pages or ()))
    srv._tick()
    assert held == 1
    assert all((pts[0, 1:] == 0).all() and ends[0] == 16
               for _pos, ends, pts in steps)
    assert [int(pos[0]) for pos, _e, _p in steps] \
        == [8] * 5 + [12] * 4       # the fifth at 8 fuses; one step ahead
    block = srv.stats()["block"]
    assert block["blocks_committed"] == block["fused_commits"] == 1
    assert block["commit_passes"] == 0 and block["denoise_passes"] == 8
    tokens, when = _unfused(model, params, req.prompt, 8)
    assert req.result().tolist() == tokens and req.unmask_pass == when
    assert srv.stats()["kv"]["used"] == 0
    srv.stop()


def test_a_next_block_that_starts_a_new_page_has_its_page_a_block_early():
    """Prompt 8, page size 16: the block at 12 commits with the first
    pass of the block at 16, which lies in the next page. Every step that
    may fuse — any step at 12 — is handed the page of 16 already."""
    model, params = _model()
    srv = _server(model, params)
    steps = _recorded_steps(srv)
    req = srv.submit(np.arange(1, 9, dtype=np.int32), max_new_tokens=16)
    _drain(srv, req)
    at_12 = [pts[0] for pos, _e, pts in steps if pos[0] == 12]
    assert len(at_12) == 4 and all(t[1] != 0 for t in at_12)
    assert all(pts[0, 1] == 0 for pos, _e, pts in steps if pos[0] == 8)
    tokens, when = _unfused(model, params, req.prompt, 16)
    assert req.result().tolist() == tokens and req.unmask_pass == when
    block = srv.stats()["block"]
    assert block["fused_commits"] == block["blocks_committed"] == 3
    srv.stop()


def _until_a_fused_pass_is_unread(srv, req, limit=200):
    """Tick until the step in flight commits ``req``'s block and opens
    the next: the host has read the pass that settled the block, the pass
    dispatched behind it is unread."""
    for _ in range(limit):
        srv._tick()
        step = srv._unread
        if req.state == "active" and step is not None \
                and id(req) in step.slots and req.blk_masked \
                and not any(req.blk_masked):
            return
    raise AssertionError("no fused pass came")


@pytest.mark.parametrize("how", ["cancel", "preempt", "swap"])
def test_an_end_that_lands_on_a_fused_pass(how):
    """A request cancelled, preempted or overtaken by a weight swap while
    its fused pass is unread: pages come back, nothing is pushed after
    the end, and a request that goes on finishes on the weights it
    started with."""
    model, params = _model()
    prompt = np.arange(1, 10, dtype=np.int32)
    if how == "swap":
        alone = _server(model, params)
        want = alone.submit(prompt, max_new_tokens=22)
        _drain(alone, want)
        alone.stop()
        srv = _server(model, params)
        first = srv.submit(prompt, max_new_tokens=22)
        _until_a_fused_pass_is_unread(srv, first)
        srv.swap_weights(model.init_params(seed=11))
        second = srv.submit(prompt, max_new_tokens=22)
        _drain(srv, first, second)
        assert first.result().tolist() == want.result().tolist()
        assert first.unmask_pass == want.unmask_pass
        assert second.result().tolist() != want.result().tolist()
        st = srv.stats()
        assert sum(st["decode_drains"].values()) >= 1
    elif how == "cancel":
        srv = _server(model, params)
        first = srv.submit(prompt, max_new_tokens=22)
        other = srv.submit(prompt[:5], max_new_tokens=22)
        _until_a_fused_pass_is_unread(srv, first)
        n = len(first.generated)
        first.cancel()
        _drain(srv, first, other)
        assert first.state == "cancelled" and len(first.generated) == n
        assert other.state == "done" and len(other.generated) == 22
        st = srv.stats()
        assert st["cancelled"] == 1
    else:
        # three pages in all; at its second fused pass (the block at 12
        # commits, the block at 16 opens in the next page) the row holds
        # two, and the prompt of 30 needs two at once
        srv = _server(model, params, pool_pages=4, window=2,
                      seq_ladder=[32], max_new_tokens=8)
        first = srv.submit(prompt, max_new_tokens=8, priority=0)
        _until_a_fused_pass_is_unread(srv, first)
        _until_a_fused_pass_is_unread(srv, first)
        assert first.blk_start == 12 and len(first.pages) == 2
        n = len(first.generated)
        high = srv.submit(np.arange(1, 31, dtype=np.int32),
                          max_new_tokens=2, priority=2)
        srv._tick()
        assert first.done() and len(first.generated) == n
        with pytest.raises(ServerOverloadedError):
            first.result()
        _drain(srv, high)
        assert high.state == "done" and len(high.generated) == 2
        st = srv.stats()
        assert st["preempted"] == 1
    assert st["kv"]["used"] == 0
    srv.stop()


def test_a_confident_head_settles_a_fresh_block_inside_the_fused_pass():
    """A head scaled up until every confidence is over the threshold: the
    dynamic rule settles a fresh block in the pass that commits the block
    before it, and the next step fuses again — one pass a block, a pass
    for ``block_length`` tokens."""
    model, params = _model()
    params = dict(params, head=(params["head"].astype(jnp.float32)
                                * 1000).astype(jnp.bfloat16))
    srv = _server(model, params, max_new_tokens=40)
    req = srv.submit(np.arange(1, 9, dtype=np.int32), max_new_tokens=40)
    _drain(srv, req)
    block = srv.stats()["block"]
    if block["max_passes_a_block"] == 1:
        assert block["denoise_passes"] == 10 == 40 // B
        assert req.unmask_pass == [0] * 40
    # a near-tie under the threshold costs a block a second pass
    assert block["max_passes_a_block"] <= 2
    assert block["denoise_passes"] <= 12
    assert block["fused_commits"] == block["blocks_committed"] == 9
    assert block["commit_passes"] == 0
    out = _against_reference(model, params, req)
    assert out["unmask_differs"] <= 1 / 3 and out["worst"] < 0.01, out
    srv.stop()


def test_what_a_block_model_cannot_do_is_refused_when_the_server_is_built():
    model, params = _model()
    with pytest.raises(MXNetError, match="prefix sharing"):
        _server(model, params, prefix_cache=True)
    with pytest.raises(MXNetError, match="block_length"):
        _server(model, params, page_size=18)
    int8 = KVCachePool(model.n_layers, model.n_kv_heads, model.head_dim,
                       page_size=16, n_pages=8, dtype="int8")
    with pytest.raises(MXNetError, match="int8"):
        _server(model, params, pool=int8, page_size=None, pool_pages=None)

    class Half:
        block_length, n_layers = 4, 1

        def prefill(self, *a):
            pass

    with pytest.raises(MXNetError, match="decode_block"):
        DecodeServer(Half(), {}, start=False)
