"""``test_decode.py``, continued (a file of its own so that no file is the
floor of a ``--dist loadfile`` run): the loop one step behind its
read-back, and a prompt riding the decode step in chunks — every served
stream held to the greedy reference, token for token. The toy model and
the autouse ``_clean_state`` are that file's (imported, the fixture is
this file's too)."""
import functools
import time

import numpy as np
import pytest

from mxnet_tpu import compile_watch, fault
from mxnet_tpu.serving import (DecodeServer, KVCachePool,
                               ServerOverloadedError,
                               RequestTimeoutError, ToyDecoderLM)
from serving_common import (drain as _drain,
                            greedy_reference as _reference,
                            served as _served)
from test_decode import _clean_state, _toy      # noqa: F401


# ---------------------------------------------------------------------------
# one step behind: the next step is dispatched before the last one's
# tokens are read (PR 30). Whatever happens to a row while its step is
# unread, the served tokens are those of a one-row-at-a-time reference:
# none lost, none after the end
# ---------------------------------------------------------------------------

def _cut(tokens, eos):
    """A reference stream as a server with ``eos_id`` serves it."""
    return tokens[:tokens.index(eos) + 1] if eos in tokens else tokens


def _ahead_srv(model, params, **kw):
    kw.setdefault("seq_ladder", [16, 32])
    kw.setdefault("max_new_tokens", 24)
    kw.setdefault("window", 4)
    kw.setdefault("page_size", 8)
    kw.setdefault("pool_pages", 64)
    return DecodeServer(model, params, start=False, **kw)


def _prompts(n, lo=3, hi=14, seed=7):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, 32, size=int(rng.integers(lo, hi)))
            .astype(np.int32) for _ in range(n)]


def _behind_count_and_eos(monkeypatch):
    """Rows ending by count (known before their last token is read:
    they are simply not in the next step) beside rows ending by
    ``eos_id`` (known only once it is read: one step too many, its
    output dropped)."""
    model, params = _toy()
    prompts = _prompts(7)
    budgets = [3, 9, 1, 14, 6, 2, 11]
    refs = [_reference(model, params, p, n)
            for p, n in zip(prompts, budgets)]
    # an eos some way into the reference stream of every other row
    eos = [ref[len(ref) // 2] if i % 2 else None
           for i, ref in enumerate(refs)]
    srv = _ahead_srv(model, params)
    try:
        free0 = srv._pool.stats()["free"]
        reqs = [srv.submit(p, max_new_tokens=n, eos_id=e)
                for p, n, e in zip(prompts, budgets, eos)]
        _drain(srv, *reqs)
        want = [_cut(ref, e) if e is not None else ref
                for ref, e in zip(refs, eos)]
        assert [_served(r) for r in reqs] == want
        st = srv.stats()
        assert st["completed"] == 7 and st["errors"] == 0
        assert st["tokens_out"] == sum(len(w) for w in want)
        assert st["decode_steps_ahead"] > 0
        assert st["decode_drains"] == {}
        assert srv._pool.stats()["free"] == free0
        assert srv._unread is None and not srv._has_work()
    finally:
        srv.stop()


def _behind_row_ends_unread(how, monkeypatch):
    """A row cancelled / past its deadline / preempted while its step
    is unread: that step's output is dropped, its stream holds a prefix
    of the reference and nothing after the end; its batch mate's stream
    is whole."""
    model, params = _toy()
    victim_p = np.arange(1, 11, dtype=np.int32)
    mate_p = np.arange(20, 25, dtype=np.int32)
    big_p = np.arange(1, 16, dtype=np.int32)
    ref_v = _reference(model, params, victim_p, 12)
    ref_m = _reference(model, params, mate_p, 12)
    # five usable pages: two rows of two pages each, then an arrival
    # that needs two and outranks the victim
    kw = {"pool_pages": 6} if how == "preempt" else {}
    srv = _ahead_srv(model, params, seq_ladder=[16], max_new_tokens=12,
                     **kw)
    try:
        srv.warmup()                  # no compile inside the deadline
        free0 = srv._pool.stats()["free"]
        victim = srv.submit(victim_p, max_new_tokens=12, priority=0,
                            deadline_ms=300 if how == "deadline" else None)
        srv._tick()
        mate = srv.submit(mate_p, max_new_tokens=12, priority=1)
        for _ in range(6):
            srv._tick()
        assert srv._unread is not None and victim in srv._unread.rows
        n_before = len(victim.generated)
        assert victim.unread == 1 and n_before >= 2
        if how == "cancel":
            victim.cancel()
        elif how == "deadline":
            time.sleep(0.35)
        else:
            # a higher-priority arrival the pool cannot hold beside it
            big = srv.submit(big_p, max_new_tokens=8, priority=2)
        srv._tick()
        assert victim.done()
        assert victim.state == ("cancelled" if how == "cancel"
                                else "failed")
        if how == "deadline":
            assert isinstance(victim._error, RequestTimeoutError)
        elif how == "preempt":
            assert isinstance(victim._error, ServerOverloadedError)
        _drain(srv, mate, *([big] if how == "preempt" else []))
        got = _served(victim)
        assert len(got) == n_before and got == ref_v[:n_before]
        assert _served(mate) == ref_m
        if how == "preempt":
            assert _served(big) == _reference(model, params, big_p, 8)
            assert srv.stats()["preempted"] == 1
        assert srv._pool.stats()["free"] == free0
    finally:
        srv.stop()


def _behind_prefix_suffix_feed(monkeypatch):
    """A prefix hit feeds its un-cached suffix through the step program
    from the HOST's tokens while its batch mates are fed from the
    device; its first generated token is then fed from the device."""
    model, params = _toy()
    base = np.arange(1, 22, dtype=np.int32)          # 2 full pages + 5
    other = np.concatenate([base[:16], [30, 29, 28, 27, 26]]) \
        .astype(np.int32)
    mate_p = _prompts(1, seed=5)[0]
    srv = _ahead_srv(model, params, prefix_cache=True)
    try:
        first = srv.submit(base, max_new_tokens=6)
        _drain(srv, first)
        mate = srv.submit(mate_p, max_new_tokens=20)
        for _ in range(3):
            srv._tick()
        hit = srv.submit(other, max_new_tokens=8)
        _drain(srv, hit, mate)
        assert hit.prefix_cached == 16
        assert _served(first) == _reference(model, params, base, 6)
        assert _served(hit) == _reference(model, params, other, 8)
        assert _served(mate) == _reference(model, params, mate_p, 20)
        st = srv.stats()
        # no prompt ran a prefill program: each rode the step in chunks,
        # the hit's from its first un-cached position on
        assert st["prefix"]["hits"] == 1 and st["prefill_programs"] == 0
        assert st["chunk_tokens"] == len(base) + len(mate_p) + 5
        assert st["decode_drains"] == {}
    finally:
        srv.stop()


def _behind_cow(degrade, monkeypatch):
    """A fully cached page-aligned prompt re-runs its last token, whose
    write splits the shared page — dispatched behind the unread step;
    with a planned ``kv_cow`` raise the row re-feeds privately from what
    it HAS generated, so the unread step is read first."""
    model, params = _toy()
    base = np.arange(1, 17, dtype=np.int32)          # exactly 2 pages
    mate_p = _prompts(1, seed=6)[0]
    srv = _ahead_srv(model, params, prefix_cache=True)
    if degrade:
        fault.set_plan("kv_cow:step=1:raise")
    try:
        first = srv.submit(base, max_new_tokens=5)
        _drain(srv, first)
        mate = srv.submit(mate_p, max_new_tokens=20)
        for _ in range(3):
            srv._tick()
        again = srv.submit(base, max_new_tokens=9)
        _drain(srv, again, mate)
        ref = _reference(model, params, base, 9)
        assert _served(first) == ref[:5] and _served(again) == ref
        assert _served(mate) == _reference(model, params, mate_p, 20)
        st = srv.stats()
        assert st["prefix"]["cow_degraded"] == int(degrade)
        assert (st["prefix"]["cow_splits"] >= 1) == (not degrade)
        assert st["decode_drains"] == \
            ({"cow_degraded": 1} if degrade else {})
    finally:
        srv.stop()
        fault.set_plan(None)


def _behind_weight_swap(monkeypatch):
    """A swap mid-stream: the unread step is read before the scheduler
    plans with two generations alive, every step is read at once while
    both are, and the loop runs ahead again when one is left."""
    model, params_a = _toy(seed=3)
    params_b = model.init_params(seed=99)
    pa, pb = _prompts(2, seed=13)
    srv = _ahead_srv(model, params_a)
    try:
        old = srv.submit(pa, max_new_tokens=10)
        for _ in range(3):
            srv._tick()
        assert srv._unread is not None
        srv.swap_weights(params_b)
        new = srv.submit(pb, max_new_tokens=22)
        _drain(srv, old, new)
        assert _served(old) == _reference(model, params_a, pa, 10)
        assert _served(new) == _reference(model, params_b, pb, 22)
        st = srv.stats()
        assert st["decode_drains"]["swap_weights"] == 1
        assert st["decode_drains"]["versions"] >= 2 * 6
        # ahead before the swap and after the old generation drained
        assert 0 < st["decode_steps_ahead"] < st["decode_steps"]
    finally:
        srv.stop()


def _behind_two_servers_one_pool(monkeypatch):
    """Two models on one pool, their steps interleaved: each server's
    unread step stays its own, and a page one frees while its step is
    unread may go to the other at once."""
    model, params_a = _toy(seed=3)
    params_b = model.init_params(seed=99)
    pool = KVCachePool(model.n_layers, model.n_heads, model.head_dim,
                       page_size=8, n_pages=24)
    a = _ahead_srv(model, params_a, pool=pool, pool_pages=None,
                   page_size=None, name="a", window=2)
    b = _ahead_srv(model, params_b, pool=pool, pool_pages=None,
                   page_size=None, name="b", window=2)
    try:
        prompts = _prompts(6, seed=17)
        budgets = [5, 12, 3, 9, 7, 4]
        reqs = [(a if i % 2 else b).submit(p, max_new_tokens=n,
                                            eos_id=None)
                for i, (p, n) in enumerate(zip(prompts, budgets))]
        n = 0
        while not all(r.done() for r in reqs):
            a._tick()
            b._tick()
            n += 1
            assert n < 500
        for i, (r, p, k) in enumerate(zip(reqs, prompts, budgets)):
            assert _served(r) == _reference(
                model, params_a if i % 2 else params_b, p, k)
        assert a.stats()["decode_steps_ahead"] > 0
        assert b.stats()["decode_steps_ahead"] > 0
    finally:
        a.stop()
        b.stop()
    assert pool.stats()["used"] == 0


def _behind_int8_pool(monkeypatch):
    """An int8 pool (pages and their scales ride the step): a window of
    rows, ends by count and by eos, against the same rows served one at
    a time."""
    monkeypatch.setenv("MXNET_KV_DTYPE", "int8")
    model, params = _toy()
    prompts = _prompts(5, seed=19)
    budgets = [4, 11, 7, 2, 9]
    alone = []
    one = _ahead_srv(model, params, window=1)
    try:
        for p, n in zip(prompts, budgets):
            r = one.submit(p, max_new_tokens=n)
            _drain(one, r)
            alone.append(_served(r))
    finally:
        one.stop()
    eos = [ref[len(ref) // 2] if i % 2 else None
           for i, ref in enumerate(alone)]
    srv = _ahead_srv(model, params, window=4)
    try:
        assert srv._pool.stats()["dtype"] == "int8"
        reqs = [srv.submit(p, max_new_tokens=n, eos_id=e)
                for p, n, e in zip(prompts, budgets, eos)]
        _drain(srv, *reqs)
        assert [_served(r) for r in reqs] == \
            [_cut(ref, e) if e is not None else ref
             for ref, e in zip(alone, eos)]
        assert srv.stats()["decode_steps_ahead"] > 0
    finally:
        srv.stop()


def _behind_ahead_share_closed_loop(monkeypatch):
    """A full window refilled from a queue as rows end, as a closed
    loop offers it: nine steps in ten and more are dispatched while the
    step before is unread; an admission does not drain."""
    model, params = _toy()
    prompts = _prompts(12, seed=23)
    srv = _ahead_srv(model, params, max_new_tokens=24, max_queue=16)
    try:
        reqs = [srv.submit(p, max_new_tokens=12 + i)
                for i, p in enumerate(prompts)]
        _drain(srv, *reqs)
        for r, p, in zip(reqs, prompts):
            assert _served(r) == _reference(model, params, p, r.max_new)
        st = srv.stats()
        assert st["admitted"] == 12 and st["decode_drains"] == {}
        assert st["decode_steps_ahead"] / st["decode_steps"] > 0.9
        assert st["decode_steps_ahead"] == st["decode_steps"] - 1
    finally:
        srv.stop()


def _behind_every_step_drains(monkeypatch):
    """Two weight generations alive from the second tick to the last:
    every step is read before the next is planned, none runs ahead, and
    the tokens are the same."""
    model, params_a = _toy(seed=3)
    params_b = model.init_params(seed=99)
    pa, pb = _prompts(2, seed=29)
    srv = _ahead_srv(model, params_a)
    try:
        old = srv.submit(pa, max_new_tokens=10)
        srv._tick()
        srv.swap_weights(params_b)
        new = srv.submit(pb, max_new_tokens=9)
        _drain(srv, old, new)
        assert _served(old) == _reference(model, params_a, pa, 10)
        assert _served(new) == _reference(model, params_b, pb, 9)
        st = srv.stats()
        assert st["decode_steps_ahead"] == 0 and st["decode_steps"] >= 16
        assert sum(st["decode_drains"].values()) == st["decode_steps"]
    finally:
        srv.stop()


def _behind_prefix_insert_sees_no_stale_write(monkeypatch):
    """A row that ends by ``eos_id`` has one step too many in flight
    when ``_finish`` registers its run with the prefix index: of every
    step dispatched and not yet read at that moment, no row write may
    land in a page the index publishes. A later prompt that continues
    the conversation on those pages is served the reference's tokens."""
    model, params = _toy()
    srv = _ahead_srv(model, params, prefix_cache=True)
    pool, S = srv._pool, 8
    writes, published, finishing = [], [], []
    prog, insert, finish = srv._decode_prog, pool.prefix_insert, srv._finish
    mixed, M = dict(srv._chunk_progs), srv._max_pages

    def spying_prog(tree, tokens, positions, pts, *rest):
        rows = np.flatnonzero(pts[:, 0])
        writes.append({int(pts[i, positions[i] // S]) for i in rows})
        return prog(tree, tokens, positions, pts, *rest)

    def spying_mixed(tree, tokens, positions, pts, prev, src, chunk, *rest):
        rows = np.flatnonzero(pts[:, 0])
        C = len(chunk) - M - 3
        start, n = (int(v) for v in chunk[C + M:C + M + 2])
        writes.append({int(pts[i, positions[i] // S]) for i in rows}
                      | {int(chunk[C + p // S])
                         for p in range(start, start + n)})
        return mixed[C](tree, tokens, positions, pts, prev, src, chunk,
                        *rest)

    def spying_insert(ns, run, pages):
        # (a chunk's own insert publishes pages it has just written
        # whole, after any stale write in the device's order)
        if finishing:
            unread = writes[srv.stats()["decode_steps"]:]
            full = set(pages[:len(run) // S])
            published.append((full, len(unread)))
            assert not any(full & w for w in unread), (full, unread)
        return insert(ns, run, pages)

    def spying_finish(*args, **kwargs):
        finishing.append(1)
        try:
            return finish(*args, **kwargs)
        finally:
            finishing.pop()

    srv._decode_prog = spying_prog
    srv._chunk_progs = dict.fromkeys(mixed, spying_mixed)
    pool.prefix_insert = spying_insert
    srv._finish = spying_finish
    try:
        prompts = [np.arange(1 + i, 14 + i, dtype=np.int32)
                   for i in range(4)]
        refs = [_reference(model, params, p, 24) for p in prompts]
        # ends that put the stale write first in a page, last, inside
        ends = [3, 10, 11, 14]                # 13 + g - 1 = 15, 22, 23, 26
        eos = [ref[g - 1] for ref, g in zip(refs, ends)]
        reqs = [srv.submit(p, max_new_tokens=24, eos_id=e)
                for p, e in zip(prompts, eos)]
        _drain(srv, *reqs)
        want = [_cut(ref, e) for ref, e in zip(refs, eos)]
        assert [_served(r) for r in reqs] == want
        # every request's finish published pages, with a step unread
        finishes = [p for p in published if p[0]]
        assert len(finishes) >= 4 and any(n for _, n in finishes)
        # the conversation goes on: prompt + answer + a new turn
        for p, w in zip(prompts, want):
            cont = np.concatenate([p, w, [5, 6, 7]]).astype(np.int32)
            if len(cont) > 32:
                continue
            r = srv.submit(cont, max_new_tokens=6)
            _drain(srv, r)
            assert r.prefix_cached >= 8
            assert _served(r) == _reference(model, params, cont, 6)
    finally:
        srv.stop()


def _behind_step_raises(where, monkeypatch):
    """A dispatch or a read-back that raises fails the rows of THAT
    step, after the step before has handed out what it computed; the
    server goes on serving."""
    model, params = _toy()
    prompt = _prompts(1, seed=31)[0]
    ref = _reference(model, params, prompt, 12)
    srv = _ahead_srv(model, params)
    prog = srv._decode_prog
    calls = []

    def failing(*args):
        calls.append(1)
        if len(calls) == 4:
            raise RuntimeError("planned dispatch failure")
        return prog(*args)

    class _Numpy:
        """numpy, but the fourth device array read back raises."""

        def __getattr__(self, name):
            return getattr(np, name)

        def asarray(self, a, *args, **kwargs):
            import jax
            if isinstance(a, jax.Array):
                calls.append(1)
                if len(calls) == 4:
                    raise RuntimeError("planned read-back failure")
            return np.asarray(a, *args, **kwargs)

    if where == "dispatch":
        srv._decode_prog = failing
    else:
        from mxnet_tpu.serving import decode as decode_mod
        monkeypatch.setattr(decode_mod, "_np", _Numpy())
    try:
        req = srv.submit(prompt, max_new_tokens=12)
        _drain(srv, req)
        with pytest.raises(RuntimeError, match="planned"):
            req.result(timeout=1)
        got = _served(req)
        # the token of the step that carried the prompt (the mixed
        # program: not the one made to fail) and those of the steps
        # before the failed one; the step already dispatched behind a
        # failed read-back is read at once and its output dropped
        assert got == ref[:4 if where == "dispatch" else 3]
        assert srv._unread is None
        st = srv.stats()
        assert st["errors"] == 1 and st["decode_drains"] == {"error": 1}
        assert srv._pool.stats()["used"] == 0
        after = srv.submit(prompt, max_new_tokens=12)
        _drain(srv, after)
        assert _served(after) == ref
    finally:
        srv.stop()


_BEHIND = {
    "count_and_eos": _behind_count_and_eos,
    "cancel_unread": functools.partial(_behind_row_ends_unread, "cancel"),
    "deadline_unread": functools.partial(_behind_row_ends_unread,
                                         "deadline"),
    "preempt_unread": functools.partial(_behind_row_ends_unread,
                                        "preempt"),
    "prefix_suffix_feed": _behind_prefix_suffix_feed,
    "cow_split": functools.partial(_behind_cow, False),
    "cow_degraded": functools.partial(_behind_cow, True),
    "weight_swap": _behind_weight_swap,
    "two_servers_one_pool": _behind_two_servers_one_pool,
    "int8_pool": _behind_int8_pool,
    "ahead_share_closed_loop": _behind_ahead_share_closed_loop,
    "every_step_drains": _behind_every_step_drains,
    "prefix_insert_no_stale_write":
        _behind_prefix_insert_sees_no_stale_write,
    "dispatch_raises": functools.partial(_behind_step_raises, "dispatch"),
    "readback_raises": functools.partial(_behind_step_raises, "readback"),
}


@pytest.mark.parametrize("case", sorted(_BEHIND))
def test_one_step_behind_serves_the_reference_tokens(case, monkeypatch):
    _BEHIND[case](monkeypatch)


# ---------------------------------------------------------------------------
# a prompt rides the decode step in chunks: the mixed step program, the
# layouts' chunk operation, the scheduler's feed
# ---------------------------------------------------------------------------

CHUNK = 8


def _chunk_srv(model, params, **kw):
    """Pages of 4 under chunks of 8 (the ladder's smallest rung; its
    next is past a step's budget of two of them): every chunk covers two
    pages."""
    kw.setdefault("seq_ladder", [CHUNK, 32])
    kw.setdefault("max_new_tokens", 12)
    kw.setdefault("window", 4)
    kw.setdefault("page_size", 4)
    kw.setdefault("pool_pages", 64)
    return DecodeServer(model, params, start=False, **kw)


def _long_prompts(sizes, seed=41):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, 32, size=n).astype(np.int32) for n in sizes]


def _fed(srv):
    """Spy on the scheduler's chunks: ``[(request id, tokens), ...]`` in
    the order the steps that carried them were built."""
    fed, build = [], srv._build_chunk

    def spying(slot, r):
        out = build(slot, r)
        fed.append((r.request_id, out[1]))
        return out

    srv._build_chunk = spying
    return fed


def _chunks_lengths(monkeypatch):
    """Prompts of 1, C - 1, C, C + 1 and 3C + 7 tokens, one after
    another: the reference's tokens, ceil(P / C) mixed steps a prompt,
    every prompt token fed once and no prefill program."""
    model, params = _toy(n_layers=2)
    sizes = (1, CHUNK - 1, CHUNK, CHUNK + 1, 3 * CHUNK + 7)
    srv = _chunk_srv(model, params)
    try:
        assert srv._prefill_progs == {} and srv.stats()["chunk"] == CHUNK
        for p in _long_prompts(sizes):
            before = srv.stats()
            req = srv.submit(p, max_new_tokens=7)
            _drain(srv, req)
            assert _served(req) == _reference(model, params, p, 7)
            st = srv.stats()
            assert st["chunk_steps"] - before["chunk_steps"] \
                == -(-len(p) // CHUNK)
            assert st["chunk_tokens"] - before["chunk_tokens"] == len(p)
            # the mixed steps are decode steps, counted once: the last
            # of them emits the first token, six plain steps the rest
            assert st["decode_steps"] - before["decode_steps"] \
                == -(-len(p) // CHUNK) + 6
        st = srv.stats()
        assert st["prefill_programs"] == 0 == st["prefill_steps"]
        assert st["launches"]["prefill"] == 0
        assert st["chunk_tokens"] == sum(sizes)
        assert srv._pool.stats()["used"] == 0
    finally:
        srv.stop()


def _chunks_straddle_pages(how, monkeypatch):
    """Chunks of three pages, of one, and of four or eight (a ladder
    with two rungs inside a step's budget: the 29 tokens ride the wider
    program whole, the mate's five the narrower), on the jnp and the
    interpreted Pallas path of the decode rows. (A chunk starts inside a
    page only where it is a fully cached prompt's one token: the
    layouts' own test starts anywhere.)"""
    model, params = _toy(n_layers=2, use_pallas=how == "pallas")
    p, = _long_prompts((29,), seed=43)
    ref = _reference(model, params, p, 9)
    for page_size, ladder, steps in ((4, [12, 32], 1 + 3),
                                    (8, [8, 32], 1 + 4),
                                    (4, [16, 32], 1 + 1)):
        srv = _chunk_srv(model, params, page_size=page_size,
                         seq_ladder=ladder)
        try:
            mate = srv.submit(p[:5], max_new_tokens=12)
            req = srv.submit(p, max_new_tokens=9)
            _drain(srv, req, mate)
            assert _served(req) == ref, (page_size, ladder)
            assert _served(mate) == _reference(model, params, p[:5], 12)
            assert srv.stats()["chunk_steps"] == steps
        finally:
            srv.stop()


def _chunks_beside_rows_ahead(monkeypatch):
    """A long prompt arrives while other rows decode one step ahead of
    the host: its chunks ride their steps (nothing drains), its first
    token is fed to the next step from the device, and every stream is
    the reference's."""
    model, params = _toy()
    mates = _prompts(2, seed=47)
    p, = _long_prompts((27,), seed=48)
    srv = _chunk_srv(model, params)
    try:
        reqs = [srv.submit(m, max_new_tokens=12) for m in mates]
        for _ in range(4):
            srv._tick()
        assert srv._unread is not None
        late = srv.submit(p, max_new_tokens=8)
        steps0 = srv.stats()["decode_steps"]
        _drain(srv, late, *reqs)
        assert _served(late) == _reference(model, params, p, 8)
        for m, r in zip(mates, reqs):
            assert _served(r) == _reference(model, params, m, 12)
        st = srv.stats()
        assert st["decode_drains"] == {}
        assert st["decode_steps_ahead"] == st["decode_steps"] - 1
        # the mates never waited for the prompt: they emitted a token
        # in each of the four steps that carried it
        assert st["chunk_steps"] == 2 + 4
        assert st["decode_steps"] - steps0 <= 4 + 8
    finally:
        srv.stop()


def _chunks_two_prompts_fifo(monkeypatch):
    """Two prompts queued together: one request's chunk a step, the
    head-most first; the second waits as it would for a prefill."""
    model, params = _toy()
    a, b = _long_prompts((20, 23), seed=53)
    srv = _chunk_srv(model, params)
    fed = _fed(srv)
    try:
        ra = srv.submit(a, max_new_tokens=6)
        rb = srv.submit(b, max_new_tokens=6)
        _drain(srv, ra, rb)
        assert fed == [(ra.request_id, 8), (ra.request_id, 8),
                       (ra.request_id, 4), (rb.request_id, 8),
                       (rb.request_id, 8), (rb.request_id, 7)]
        assert _served(ra) == _reference(model, params, a, 6)
        assert _served(rb) == _reference(model, params, b, 6)
        assert srv.stats()["chunk_steps"] == 6
    finally:
        srv.stop()


def _chunks_row_ends(how, monkeypatch):
    """A request cancelled / past its deadline / preempted / whose
    server swaps weights while chunks of its prompt are pending: the
    first three free its pages, drop the feed and push nothing; a swap
    lets it finish on the weights it started with. The server goes on
    serving."""
    model, params = _toy(seed=3)
    params_b = model.init_params(seed=99)
    p, q = _long_prompts((30, 12), seed=59)
    srv = _chunk_srv(model, params)
    try:
        srv.warmup()                  # no compile inside the deadline
        free0 = srv._pool.stats()["free"]
        req = srv.submit(p, max_new_tokens=6,
                         deadline_ms=300 if how == "deadline" else None)
        srv._tick()
        srv._tick()
        assert req.pending and req.pending_pos == 2 * CHUNK
        assert srv._unread.chunk[0] is req
        if how == "swap_weights":
            srv.swap_weights(params_b)
            after = srv.submit(q, max_new_tokens=6)
            _drain(srv, req, after)
            assert _served(req) == _reference(model, params, p, 6)
            assert _served(after) == _reference(model, params_b, q, 6)
            assert srv._pool.stats()["used"] == 0
            return
        if how == "cancel":
            req.cancel()
        elif how == "deadline":
            time.sleep(0.35)
        else:
            with srv._cond:           # a co-tenant's give-back ask
                srv._preempt_asks = 1
        srv._tick()
        assert req.done() and req.pending is None and not req.pages
        assert req.state == ("cancelled" if how == "cancel" else "failed")
        assert _served(req) == []
        while srv._has_work():
            srv._tick()
        assert srv._pool.stats()["free"] == free0
        after = srv.submit(q, max_new_tokens=6)
        _drain(srv, after)
        assert _served(after) == _reference(model, params, q, 6)
        st = srv.stats()
        assert st["tokens_out"] == 6
        assert st["chunk_tokens"] == 2 * CHUNK + len(q)
    finally:
        srv.stop()


def _chunks_prefix(how, monkeypatch):
    """Prefix sharing over chunks. ``hit``: a prompt that shares two
    full pages feeds its suffix in chunks from ``cached`` on, and the
    pages a chunk completes are published as soon as it is dispatched (a
    third prompt hits on them while the second still generates).
    ``cow``: a fully cached page-aligned prompt re-runs its last token
    as a chunk of one, whose write splits the shared page. ``degrade``:
    a planned ``kv_cow`` raise re-feeds the whole row privately, in
    chunks."""
    model, params = _toy()
    base = np.arange(1, 9, dtype=np.int32)             # two full pages
    tail, = _long_prompts((13,), seed=61)
    longer = np.concatenate([base, tail]).astype(np.int32)
    srv = _chunk_srv(model, params, prefix_cache=True)
    if how == "degrade":
        fault.set_plan("kv_cow:step=1:raise")
    try:
        first = srv.submit(base, max_new_tokens=5)
        _drain(srv, first)
        assert _served(first) == _reference(model, params, base, 5)
        fed = _fed(srv)
        if how == "hit":
            hit = srv.submit(longer, max_new_tokens=12)
            for _ in range(3):
                srv._tick()
            # both of its chunks are in: five full pages are published
            assert hit.prefix_cached == 8 and not hit.pending
            third = srv.submit(longer, max_new_tokens=4)
            _drain(srv, hit, third)
            assert third.prefix_cached == 20
            assert fed == [(hit.request_id, 8), (hit.request_id, 5),
                           (third.request_id, 1)]
            ref = _reference(model, params, longer, 12)
            assert _served(hit) == ref and _served(third) == ref[:4]
            assert srv.stats()["prefix"]["hit_tokens"] == 8 + 20
        else:
            again = srv.submit(base, max_new_tokens=9)
            _drain(srv, again)
            assert _served(again) == _reference(model, params, base, 9)
            st = srv.stats()
            assert st["prefix"]["cow_degraded"] == int(how == "degrade")
            assert st["prefix"]["cow_splits"] == int(how == "cow")
            assert fed == ([(again.request_id, 8)] if how == "degrade"
                           else [(again.request_id, 1)])
        assert srv.stats()["prefill_programs"] == 0
    finally:
        srv.stop()
        fault.set_plan(None)


def _chunks_fixed_programs(monkeypatch):
    """``warmup()`` readies the step and the mixed step; no prompt mix
    compiles anything after it, and ``stats()`` counts what rode."""
    compile_watch.enable()
    model, params = _toy()
    srv = _chunk_srv(model, params, name="chunks")
    try:
        assert srv.warmup() == 2
        warm = compile_watch.site_stats("decode:chunks")
        assert sorted(warm) == ["decode:chunks:step",
                                "decode:chunks:step:chunk:c8"]
        sizes = (3, 8, 17, 32, 1, 25)
        reqs = [srv.submit(p, max_new_tokens=5)
                for p in _long_prompts(sizes, seed=67)]
        _drain(srv, *reqs)
        assert compile_watch.site_stats("decode:chunks") == warm
        st = srv.stats()
        assert st["completed"] == len(sizes)
        assert st["chunk_tokens"] == sum(sizes)
        assert st["chunk_steps"] == sum(-(-n // CHUNK) for n in sizes)
        assert st["prefill_programs"] == 0 and st["admitted"] == len(sizes)
    finally:
        srv.stop()


def _chunks_default_and_refusals(monkeypatch):
    """The mixed program is built at every rung within twice the
    ladder's smallest, and a chunk takes the smallest that holds what is
    pending; a model that does not declare ``chunk_lanes``, and an int8
    pool, keep the prefill."""
    model, params = _toy()
    compile_watch.enable()
    srv = DecodeServer(model, params, seq_ladder=[8, 16, 64], page_size=8,
                       pool_pages=32, max_new_tokens=4, name="two",
                       start=False)
    fed = _fed(srv)
    try:
        assert srv.stats()["chunk_sizes"] == [8, 16]
        assert srv.stats()["chunk"] == 16 and srv.warmup() == 3
        warm = compile_watch.site_stats("decode:two")
        assert sorted(warm) == ["decode:two:step",
                                "decode:two:step:chunk:c16",
                                "decode:two:step:chunk:c8"]
        for p in _long_prompts((40, 5, 13), seed=71):
            req = srv.submit(p, max_new_tokens=4)
            _drain(srv, req)
            assert _served(req) == _reference(model, params, p, 4)
        assert [n for _id, n in fed] == [16, 16, 8, 5, 13]
        # (40 is 16 + 16 + 8: the last on the narrower program)
        assert compile_watch.site_stats("decode:two") == {
            "decode:two:step": warm["decode:two:step"],
            "decode:two:step:chunk:c8": warm["decode:two:step:chunk:c8"],
            "decode:two:step:chunk:c16":
                warm["decode:two:step:chunk:c16"]}
    finally:
        srv.stop()
        compile_watch.disable()
    srv = DecodeServer(model, params, seq_ladder=[16, 64], page_size=8,
                       pool_pages=32, start=False)
    assert srv.stats()["chunk_sizes"] == [16]
    srv.stop()

    plain = ToyDecoderLM(vocab=32, n_layers=1, n_heads=2, head_dim=8,
                         max_len=128)
    plain.chunk_lanes = False
    monkeypatch.setenv("MXNET_KV_DTYPE", "int8")
    quant = DecodeServer(model, params, seq_ladder=[16], page_size=8,
                         pool_pages=32, start=False)
    monkeypatch.delenv("MXNET_KV_DTYPE")
    for srv in (quant, DecodeServer(plain, params, seq_ladder=[16],
                                    page_size=8, pool_pages=32,
                                    start=False)):
        try:
            assert srv.stats()["chunk"] == 0 and not srv._chunk_progs
            p = np.arange(1, 12, dtype=np.int32)
            req = srv.submit(p, max_new_tokens=4)
            _drain(srv, req)
            st = srv.stats()
            assert st["prefill_programs"] == 1 == st["prefill_steps"]
            assert st["chunk_steps"] == 0
        finally:
            srv.stop()


_CHUNKS = {
    "lengths": _chunks_lengths,
    "straddles_pages_jnp": functools.partial(_chunks_straddle_pages, "jnp"),
    "straddles_pages_pallas": functools.partial(_chunks_straddle_pages,
                                                "pallas"),
    "beside_rows_one_step_ahead": _chunks_beside_rows_ahead,
    "two_prompts_fifo": _chunks_two_prompts_fifo,
    "cancel_pending": functools.partial(_chunks_row_ends, "cancel"),
    "deadline_pending": functools.partial(_chunks_row_ends, "deadline"),
    "preempt_pending": functools.partial(_chunks_row_ends, "preempt"),
    "swap_weights_pending": functools.partial(_chunks_row_ends,
                                              "swap_weights"),
    "prefix_hit_then_chunks": functools.partial(_chunks_prefix, "hit"),
    "cow_of_a_shared_last_page": functools.partial(_chunks_prefix, "cow"),
    "degrade_private_in_chunks": functools.partial(_chunks_prefix,
                                                   "degrade"),
    "fixed_programs_and_counts": _chunks_fixed_programs,
    "default_size_and_who_keeps_the_prefill": _chunks_default_and_refusals,
}


@pytest.mark.parametrize("case", sorted(_CHUNKS))
def test_a_prompt_in_chunks_serves_the_reference_tokens(case, monkeypatch):
    _CHUNKS[case](monkeypatch)


def _dense_chunk(q, k_new, v_new, kc, vc, start, scale):
    """A chunk's attention the plain way: the row's gathered cache with
    the chunk's rows put in at their positions, one causal softmax in
    float64. ``q (C, Hq, D)``, ``kc``/``vc (T, Hkv, D)``."""
    C, Hq, D = q.shape
    Hkv = kc.shape[1]
    kc, vc = np.array(kc, np.float64), np.array(vc, np.float64)
    kc[start:start + C], vc[start:start + C] = k_new, v_new
    out = np.zeros((C, Hq, D))
    for j in range(C):
        for h in range(Hq):
            g = h // (Hq // Hkv)
            s = kc[:start + j + 1, g] @ np.asarray(q[j, h], np.float64) \
                * scale
            w = np.exp(s - s.max())
            out[j, h] = (w / w.sum()) @ vc[:start + j + 1, g]
    return out


@pytest.mark.parametrize("kind", ["per_head_f32", "per_head_bf16",
                                  "packed_bf16"])
def test_a_layouts_chunk_attends_and_writes_as_the_dense_form(kind):
    """The layout's chunk operation against ``gather_pages`` and a dense
    causal softmax: chunk lane ``j`` sees the row's pages before the
    chunk and the chunk's own rows ``<= j``, the decode rows beside it
    what the plain step's ``attend`` gives them, and the write lands the
    live rows — across page boundaries, from inside a page — and leaves
    every other row of the pool as it was."""
    import jax.numpy as jnp
    from mxnet_tpu.serving import kvcache
    dtype = jnp.float32 if kind == "per_head_f32" else jnp.bfloat16
    Hq, Hkv, D = (8, 4, 128) if kind == "packed_bf16" else (2, 2, 8)
    L, P, S, M, B, C = 2, 12, 4, 6, 2, 7
    layout = kvcache.cache_layout((("k", (Hkv, D)), ("v", (Hkv, D))),
                                  jnp.dtype(dtype))
    assert layout.chunks
    assert type(layout).__name__ == ("_PackedHeadKV" if "packed" in kind
                                     else "_PerHeadKV")
    rs = np.random.RandomState(5)
    pools = [jnp.asarray(rs.randn(*shape), dt) for _n, shape, dt
             in layout.arrays(L, P, S)]
    tables = np.zeros((B, M), np.int32)
    tables[0, :3], tables[1, :2] = [7, 2, 9], [4, 11]
    positions = np.asarray([9, 5], np.int32)
    row = np.asarray([3, 10, 1, 8, 6, 0], np.int32)
    tol = 2e-5 if dtype == jnp.float32 else 3e-2
    scale = 1.0 / np.sqrt(D)

    def gathered(pool, table, layer):
        got = kvcache.gather_pages(pool[layer:layer + 1],
                                   jnp.asarray(table)[None])[0, 0]
        return np.asarray(got.astype(jnp.float32)).reshape(-1, Hkv, D)

    for start, n_live in ((0, 7), (5, 7), (10, 3), (13, 7)):
        q = rs.randn(B + C, Hq, D).astype(np.float32)
        k_new = rs.randn(B + C, Hkv, D).astype(np.float32)
        v_new = rs.randn(B + C, Hkv, D).astype(np.float32)
        attend = layout.attend_chunk(pools, jnp.asarray(tables),
                                     jnp.asarray(positions),
                                     jnp.asarray(row), jnp.int32(start))
        plain = layout.attend(pools, jnp.asarray(tables),
                              jnp.asarray(positions))
        for layer in range(L):
            got = np.asarray(attend(layer, jnp.asarray(q),
                                    jnp.asarray(k_new), jnp.asarray(v_new),
                                    scale=scale))
            rows = np.asarray(plain(layer, jnp.asarray(q[:B]),
                                    jnp.asarray(k_new[:B]),
                                    jnp.asarray(v_new[:B]), scale=scale))
            np.testing.assert_array_equal(got[:B], rows)
            # (the pool holds, and so the chunk attends, rounded rows)
            rounded = [np.asarray(jnp.asarray(a[B:], dtype)
                                  .astype(jnp.float32))
                       for a in (k_new, v_new)]
            want = _dense_chunk(q[B:], *rounded,
                                gathered(pools[0], row, layer),
                                gathered(pools[1], row, layer), start,
                                scale)
            np.testing.assert_allclose(got[B:], want, atol=tol, rtol=tol)
        new = [rs.randn(L, C, Hkv, D).astype(np.float32) for _ in pools]
        after = layout.write_chunk(pools, jnp.asarray(row),
                                   jnp.int32(start), jnp.int32(n_live),
                                   [jnp.asarray(a) for a in new])
        for pool, was, rows in zip(after, pools, new):
            assert pool.shape == was.shape and pool.dtype == was.dtype
            want = np.array(was.astype(jnp.float32))
            for j in range(n_live):
                page, slot = row[(start + j) // S], (start + j) % S
                want[:, page, slot] = np.asarray(
                    jnp.asarray(rows[:, j], dtype).astype(jnp.float32)
                ).reshape(want[:, page, slot].shape)
            np.testing.assert_array_equal(
                np.asarray(pool.astype(jnp.float32)), want)
