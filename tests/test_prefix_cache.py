"""Prefix-aware KV page sharing (mxnet_tpu.serving.kvcache +
decode): content-hashed index over page-aligned token runs, suffix-only
prefill for hit prompts, refcounted pages with copy-on-write on first
divergence, cold-prefix eviction through the counted kv_evict path, and
multi-model serving on ONE shared pool under quotas and pool-priority
preemption.

The load-bearing contract: a shared-prefix stream is TOKEN-IDENTICAL
to an unshared run — on the jnp AND Pallas attention paths, across a
forced copy-on-write split, and across a planned kv_cow fault that
degrades the row to a private re-prefill."""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import compile_watch, fault, livemetrics, telemetry
from mxnet_tpu.serving import DecodeServer, KVCachePool, ToyDecoderLM
from serving_common import drain as _drain


@pytest.fixture(autouse=True)
def _clean_state():
    fault.reset()
    telemetry.reset()
    compile_watch.disable()
    yield
    fault.reset()
    telemetry.reset()
    compile_watch.disable()


def _toy(n_layers=1, use_pallas=False, seed=3, max_len=128):
    model = ToyDecoderLM(vocab=32, n_layers=n_layers, n_heads=2,
                         head_dim=8, max_len=max_len,
                         use_pallas=use_pallas)
    return model, model.init_params(seed=seed)


def _srv(model, params, prefix=True, **kw):
    kw.setdefault("seq_ladder", [16])
    kw.setdefault("max_new_tokens", 16)
    kw.setdefault("window", 4)
    if "pool" not in kw:
        kw.setdefault("page_size", 4)
        kw.setdefault("pool_pages", 32)
    kw.setdefault("start", False)
    return DecodeServer(model, params, prefix_cache=prefix, **kw)


def _gen(srv, prompt, n=8):
    req = srv.submit(prompt, max_new_tokens=n)
    _drain(srv, req)
    return [int(t) for t in req.result(timeout=1)], req


# page_size=4 everywhere below: BASE is 12 tokens = 3 FULL pages, so
# an identical prompt is fully cached (its re-fed last token COWs the
# final shared page) and LONGER shares all 3 pages + a private suffix
BASE = np.arange(10, 22, dtype=np.int32)
LONGER = np.concatenate([BASE, [5, 6]]).astype(np.int32)


# ---------------------------------------------------------------------------
# the core oracle: shared-prefix decode token-identical to unshared
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("use_pallas", [False, True],
                         ids=["jnp", "pallas"])
def test_shared_prefix_identical_to_unshared(use_pallas):
    """A prefix-hit prompt (suffix fed through the decode-step
    program) generates the SAME tokens as a private full-prefill run,
    on both attention kernel paths — including the fully-cached
    page-aligned prompt whose single re-fed token forces a COW."""
    model, params = _toy(use_pallas=use_pallas)
    ref = _srv(model, params, prefix=False, name="ref")
    try:
        ref_base, _ = _gen(ref, BASE)
        ref_long, _ = _gen(ref, LONGER)
    finally:
        ref.stop()
    srv = _srv(model, params, name="shared")
    try:
        first, r1 = _gen(srv, BASE)          # miss: full prefill
        hit_full, r2 = _gen(srv, BASE)       # full-page hit -> COW
        hit_part, r3 = _gen(srv, LONGER)     # 3-page hit + suffix
        assert first == ref_base
        assert hit_full == ref_base
        assert hit_part == ref_long
        assert r1.prefix_cached == 0
        assert r2.prefix_cached == 12 and r3.prefix_cached == 12
        st = srv.stats()
        assert st["prefix"]["enabled"]
        assert st["prefix"]["hits"] == 2
        assert st["prefix"]["misses"] == 1
        assert st["prefix"]["hit_tokens"] == 24
        assert st["prefix"]["cow_splits"] == 1
        assert st["prefix"]["bytes_saved"] > 0
        # no prompt runs a prefill program: the miss rode the step whole,
        # a hit from its first un-cached position on (the fully cached
        # one re-ran its last token)
        assert st["prefill_programs"] == 0
        assert st["chunk_tokens"] == len(BASE) + 1 + (len(LONGER) - 12)
    finally:
        srv.stop()


def test_prefix_insert_at_finish_extends_the_run():
    """A clean completion registers prompt + generated[:-1] — a later
    prompt that CONTINUES the conversation hits the grown run, not
    just the original prompt's pages."""
    model, params = _toy()
    srv = _srv(model, params, seq_ladder=[16, 32])
    try:
        out, _ = _gen(srv, BASE, n=8)
        follow = np.concatenate([BASE, out, [3]]).astype(np.int32)
        ref = _srv(model, params, prefix=False, name="ref2",
                   seq_ladder=[16, 32])
        try:
            want, _ = _gen(ref, follow, n=6)
        finally:
            ref.stop()
        got, req = _gen(srv, follow, n=6)
        assert got == want
        # 12 prompt + 7 written generated = 19 tokens -> 4 full pages
        assert req.prefix_cached == 16
    finally:
        srv.stop()


def test_prefix_off_no_lookups_no_sharing():
    model, params = _toy()
    srv = _srv(model, params, prefix=False)
    try:
        _gen(srv, BASE)
        _gen(srv, BASE)
        st = srv.stats()
        assert st["prefix"]["enabled"] is False
        assert st["prefix"]["hits"] == 0
        assert st["prefix"]["misses"] == 0
        assert st["kv"]["shared_pages"] == 0
        assert srv._pool.prefix_stats()["entries"] == 0
    finally:
        srv.stop()


# ---------------------------------------------------------------------------
# fault sites: kv_share forces a miss, kv_cow degrades to private
# ---------------------------------------------------------------------------

def test_kv_share_fault_is_a_deterministic_miss():
    """A planned raise at kv_share is a hash-collision-style MISS: the
    request pays a full private prefill and generates identical
    tokens."""
    model, params = _toy()
    # kv_share is visited once per WOULD-BE hit (a plain miss never
    # reaches it): step=1 forces the first would-be hit to miss
    fault.set_plan("kv_share:step=1:raise")
    srv = _srv(model, params)
    try:
        first, _ = _gen(srv, BASE)
        missed, _ = _gen(srv, BASE)      # would-be hit -> forced miss
        third, r3 = _gen(srv, BASE)      # plan spent: hits again
        assert missed == first and third == first
        st = srv.stats()
        assert st["prefix"]["misses"] == 2
        assert st["prefix"]["hits"] == 1
        assert r3.prefix_cached == 12
        assert fault.stats()["injected"].get("kv_share") == 1
        # the forced-miss request fed its WHOLE prompt again
        assert st["chunk_tokens"] == 2 * len(BASE) + 1
    finally:
        srv.stop()
        fault.set_plan(None)


def test_kv_cow_fault_degrades_to_private_copy_never_wrong_token():
    """A planned raise at kv_cow is counted and degrades the row to a
    private-copy re-prefill (everything computed so far re-fed from
    position 0 on fresh pages) — token-identical, never wrong."""
    model, params = _toy()
    fault.set_plan("kv_cow:step=1:raise")
    srv = _srv(model, params)
    try:
        first, _ = _gen(srv, BASE)
        degraded, req = _gen(srv, BASE)  # hit -> COW -> fault -> degrade
        assert degraded == first
        st = srv.stats()
        assert st["prefix"]["cow_degraded"] == 1
        assert st["prefix"]["cow_splits"] == 0   # the split never won
        assert fault.stats()["injected"].get("kv_cow") == 1
        # degraded row dropped its shared refs: nothing shared now
        assert req.pages == []
    finally:
        srv.stop()
        fault.set_plan(None)


# ---------------------------------------------------------------------------
# fixed program set: sharing adds exactly ONE program (the :cow copy)
# ---------------------------------------------------------------------------

def test_fixed_program_set_with_cow_zero_steady_recompiles():
    compile_watch.enable()
    model, params = _toy()
    srv = DecodeServer(model, params, seq_ladder=[16, 32],
                       max_new_tokens=8, window=4, page_size=16,
                       pool_pages=64, prefix_cache=True)
    try:
        srv.warmup()
        warm = compile_watch.site_stats("decode")
        assert set(warm) == {"decode:step", "decode:step:chunk:c16",
                             "decode:step:chunk:c32", "decode:cow"}
        assert all(v["count"] == 1 for v in warm.values())
        # page-aligned prompts so full-page hits force live COWs
        base = np.arange(1, 17)
        for _ in range(3):
            srv.submit(base, max_new_tokens=6).result(timeout=60)
        for _ in range(2):
            srv.submit(np.concatenate([base, [7, 8, 9]]),
                       max_new_tokens=6).result(timeout=60)
        assert srv.stats()["prefix"]["hits"] >= 3
        assert srv.stats()["prefix"]["cow_splits"] >= 1
        assert compile_watch.site_stats("decode") == warm
    finally:
        srv.stop()


# ---------------------------------------------------------------------------
# refcounts, cold eviction, index survival
# ---------------------------------------------------------------------------

def test_pool_refcount_free_and_cow_release():
    pool = KVCachePool(1, 2, 8, page_size=4, n_pages=8)
    pages = pool.alloc(2)
    pool.retain(pages)
    assert pool.ref(pages[0]) == 2
    assert pool.free(pages) == 0           # ref drop, NOT a reclaim
    assert pool.stats()["evicted"] == 0
    assert pool.ref(pages[0]) == 1
    assert pool.free(pages) == 2           # last holder: real reclaim
    assert pool.stats()["evicted"] == 2
    p2 = pool.alloc(1)
    pool.retain(p2)
    pool.cow_release(p2[0])
    assert pool.ref(p2[0]) == 1
    assert pool.stats()["cow_splits"] == 1


def test_cold_prefix_eviction_refcounted_pages_never_victims():
    """Under pool pressure alloc() reclaims COLD index entries (pages
    only the index holds) through the counted kv_evict path — pages a
    live request still shares are never victims."""
    model, params = _toy()
    # 6 usable pages (page 0 is the dump page): a finished BASE run
    # keeps 3 cold prefix pages, so the SECOND distinct prompt's
    # 4-page admission must evict at least one of them
    srv = _srv(model, params, pool_pages=7, max_new_tokens=4)
    try:
        _gen(srv, BASE, n=4)
        st = srv._pool.prefix_stats()
        assert st["entries"] == 3 and st["evicted"] == 0
        other = np.arange(40, 52, dtype=np.int32)
        out, _ = _gen(srv, other, n=4)
        st = srv._pool.prefix_stats()
        assert st["evicted"] >= 1            # cold entries reclaimed
        ref = _srv(model, params, prefix=False, name="coldref")
        try:
            want, _ = _gen(ref, other, n=4)
        finally:
            ref.stop()
        assert out == want
    finally:
        srv.stop()


def test_shared_pages_survive_the_request_that_filled_them():
    model, params = _toy()
    srv = _srv(model, params)
    try:
        _gen(srv, BASE)
        st = srv._pool.stats()
        # the request's private pages came back; the index still holds
        # the 3 full prefix pages (+ finish-time extension)
        assert srv._pool.prefix_stats()["entries"] >= 3
        assert st["used"] == srv._pool.prefix_stats()["entries"]
    finally:
        srv.stop()


@pytest.mark.parametrize("prefix", [False, True], ids=["off", "on"])
def test_stream_ceiling_at_the_same_pool_with_and_without_sharing(prefix):
    """The stream ceiling of one pool: 12 usable pages of 4 tokens, a
    12-token header (3 pages) and a 1-token suffix a stream. Unshared,
    a stream holds 4 pages (3 streams); shared, the header's 3 pages
    once and one private page a stream (9 streams). Either way every
    stream of a full window completes with no preemption and no failed
    allocation."""
    model, params = _toy()
    usable, header_pages = 12, 3
    cap = usable - header_pages if prefix else usable // (header_pages + 1)
    srv = _srv(model, params, prefix=prefix, pool_pages=usable + 1,
               window=cap, max_new_tokens=2, max_queue=cap + 4)
    try:
        if prefix:
            _gen(srv, BASE, n=1)       # seed the index with the header
        reqs = [srv.submit(np.concatenate([BASE, [1 + i]]).astype(np.int32),
                           max_new_tokens=2) for i in range(cap)]
        _drain(srv, *reqs)
        assert all(r.state == "done" and len(r.result(timeout=1)) == 2
                   for r in reqs)
        st = srv.stats()
        assert st["completed"] == cap + int(prefix)
        assert st["preempted"] == 0 and st["kv"]["alloc_failures"] == 0
        assert st["kv"]["peak_used"] <= usable
        assert st["prefix"]["hits"] == (cap if prefix else 0)
    finally:
        srv.stop()


# ---------------------------------------------------------------------------
# multi-model pools: quotas, namespaces, cross-server preemption
# ---------------------------------------------------------------------------

def test_two_models_one_pool_quota_and_namespace_isolation():
    model, params = _toy()
    pool = KVCachePool(1, 2, 8, page_size=4, n_pages=64)
    sa = _srv(model, params, pool=pool, pool_quota=16, name="ma")
    sb = _srv(model, params, pool=pool, pool_quota=16, name="mb")
    try:
        oa, _ = _gen(sa, BASE)
        ob, _ = _gen(sb, BASE)
        assert oa == ob
        # same tokens, same weights — but DIFFERENT share namespaces
        # (no share_group): b must never alias a's pages by accident
        assert sb.stats()["prefix"]["hits"] == 0
        assert sb.stats()["prefix"]["misses"] == 1
        owners = pool.stats()["owners"]
        assert set(owners) == {"ma", "mb"}
        assert owners["ma"]["quota"] == 16
        assert owners["ma"]["used"] > 0 and owners["mb"]["used"] > 0
    finally:
        sa.stop()
        sb.stop()


def test_share_group_hits_across_servers():
    """Two replicas of the SAME model opt into one share group: the
    second server's identical prompt enters decode on pages the first
    one filled."""
    model, params = _toy()
    pool = KVCachePool(1, 2, 8, page_size=4, n_pages=64)
    sa = _srv(model, params, pool=pool, share_group="m0", name="ra")
    sb = _srv(model, params, pool=pool, share_group="m0", name="rb")
    try:
        oa, _ = _gen(sa, BASE)
        ob, req = _gen(sb, BASE)
        assert oa == ob
        assert sb.stats()["prefix"]["hits"] == 1
        assert req.prefix_cached == 12
    finally:
        sa.stop()
        sb.stop()


def test_quota_denial_counted_and_does_not_evict_cotenant():
    model, params = _toy()
    pool = KVCachePool(1, 2, 8, page_size=4, n_pages=64)
    # quota 2 < the 4 pages one BASE request needs: admission stalls
    # on quota, never by raiding the co-tenant's cache
    sa = _srv(model, params, pool=pool, name="big")
    sb = _srv(model, params, pool=pool, pool_quota=2, name="tiny")
    try:
        _gen(sa, BASE)
        used_a = pool.stats()["owners"]["big"]["used"]
        req = sb.submit(BASE, max_new_tokens=4)
        for _ in range(10):
            sb._tick()
        assert not req.done() and req.state == "queued"
        assert pool.stats()["quota_denials"] >= 1
        assert pool.stats()["owners"]["big"]["used"] == used_a
        req.cancel()
        sb._tick()
    finally:
        sa.stop()
        sb.stop()


def test_cross_server_priority_preemption():
    """A higher-pool-priority tenant starved for pages asks the pool;
    the lower-priority co-tenant's own scheduler preempts one of its
    active requests, and the starved admission then succeeds."""
    model, params = _toy()
    # 7 usable pages; low's request holds 4, so high's 4-page
    # admission cannot be satisfied without a give-back
    pool = KVCachePool(1, 2, 8, page_size=4, n_pages=8)
    low = _srv(model, params, pool=pool, pool_priority=0, name="low",
               prefix=False, max_new_tokens=12)
    high = _srv(model, params, pool=pool, pool_priority=1,
                name="high", prefix=False, max_new_tokens=12)
    try:
        r_low = low.submit(BASE, max_new_tokens=12)
        for _ in range(2):
            low._tick()                    # active, holding pages
        assert r_low.state == "active"
        other = np.arange(40, 52, dtype=np.int32)
        r_high = high.submit(other, max_new_tokens=4)
        high._tick()                       # alloc fails -> asks pool
        assert low._preempt_asks == 1
        low._tick()                        # victim preempted, pages back
        with pytest.raises(mx.serving.ServerOverloadedError):
            r_low.result(timeout=1)
        _drain(high, r_high)
        assert r_high.result(timeout=1) is not None
        assert low.stats()["preempted"] == 1
        assert high.stats()["prefix"]["cross_preempts"] == 0
        assert low.stats()["prefix"]["cross_preempts"] == 1
    finally:
        low.stop()
        high.stop()


def test_external_pool_rejects_mismatched_geometry():
    model, params = _toy()
    pool = KVCachePool(1, 2, 8, page_size=4, n_pages=16)
    with pytest.raises(mx.base.MXNetError):
        _srv(model, params, pool=pool, page_size=8)
    other = ToyDecoderLM(vocab=32, n_layers=2, n_heads=2, head_dim=8,
                         max_len=128)
    with pytest.raises(mx.base.MXNetError):
        DecodeServer(other, other.init_params(0), pool=pool,
                     seq_ladder=[16], start=False)
    with pytest.raises(mx.base.MXNetError):
        _srv(model, params, pool=pool, pool_pages=32)


def test_weight_swap_releases_old_namespace():
    """Swapped-out weights can never serve a hit again (the namespace
    carries the version) — their index references come back."""
    model, params = _toy()
    srv = _srv(model, params)
    try:
        _gen(srv, BASE)
        assert srv._pool.prefix_stats()["entries"] >= 3
        srv.swap_weights(params=model.init_params(seed=9))
        assert srv._pool.prefix_stats()["entries"] == 0
        assert srv._pool.stats()["used"] == 0
        # new generation starts cold, then caches again
        _gen(srv, BASE)
        _, req = _gen(srv, BASE)
        assert req.prefix_cached == 12
    finally:
        srv.stop()


# ---------------------------------------------------------------------------
# observability: telemetry record, diagnose table, /metrics gauges
# ---------------------------------------------------------------------------

def test_prefix_cache_telemetry_diagnose_and_metrics(tmp_path):
    sink = str(tmp_path / "run.jsonl")
    telemetry.start(filename=sink, run_id="prefix-test")
    model, params = _toy()
    srv = _srv(model, params, name="pxsrv")
    _gen(srv, BASE)
    _gen(srv, BASE)
    page = livemetrics.render()
    assert 'mxnet_prefix_hits_total{server="pxsrv"} 1' in page
    assert 'mxnet_prefix_hit_tokens_total{server="pxsrv"} 12' in page
    assert ('mxnet_prefix_pool_pages_used'
            '{model="pxsrv",server="pxsrv"}') in page
    srv.stop()                             # final record
    telemetry.stop()
    recs = [json.loads(l) for l in open(sink) if l.strip()]
    pxs = [x for x in recs if x.get("type") == "prefix_cache"]
    assert pxs, "no prefix_cache records in the sink"
    last = pxs[-1]
    assert last["name"] == "pxsrv"
    assert last["hits"] == 1 and last["hit_tokens"] == 12
    assert last["pool"]["entries"] >= 3
    assert last["owners"]["pxsrv"]["used"] >= 3
    summary = [x for x in recs if x.get("type") == "summary"][-1]
    assert summary["prefix_cache"]["pxsrv"]["hits"] == 1
    out = subprocess.run(
        [sys.executable, "-m", "mxnet_tpu.tools.diagnose", sink],
        capture_output=True, text=True,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert out.returncode == 0, out.stderr
    assert "----------Prefix cache----------" in out.stdout
    assert "served from shared pages" in out.stdout
    assert "cow split" in out.stdout


# ---------------------------------------------------------------------------
# a hit's suffix arrives in chunks
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("chunk,steps", [(4, 5), (8, 3), (12, 2), (16, 1)])
def test_a_hits_suffix_arrives_in_chunks_and_serves_the_same_tokens(
        chunk, steps):
    """After a prefix hit the un-cached suffix rides the decode step in
    chunks (the ladder's smallest rung a step: one, two, three pages;
    with 16 the ladder's 32 is within a step's budget too, and holds the
    suffix whole), from the first un-cached position on, and the stream
    is the unshared run's token for token, whatever the chunk's size;
    the pages each chunk completes are shared by the next same-prefix
    prompt."""
    model, params = _toy(n_layers=2)
    prompt = np.concatenate([BASE, np.arange(1, 18)]).astype(np.int32)
    ref = _srv(model, params, prefix=False, seq_ladder=[32], name="ref")
    try:
        want, _ = _gen(ref, prompt)
    finally:
        ref.stop()
    srv = _srv(model, params, seq_ladder=[chunk, 32])
    try:
        _gen(srv, BASE, n=3)                   # publishes 3 full pages
        before = srv.stats()
        got, req = _gen(srv, prompt)
        assert got == want and req.prefix_cached == 12
        st = srv.stats()
        assert st["chunk_tokens"] - before["chunk_tokens"] == 17
        assert st["chunk_steps"] - before["chunk_steps"] == steps
        assert st["prefill_programs"] == 0
        # every full page of the prompt is in the index now: 29 = 7 x 4 + 1
        again, r2 = _gen(srv, prompt)
        assert again == want and r2.prefix_cached == 28
    finally:
        srv.stop()
