"""Int8-quantized paged KV cache (mxnet_tpu.serving.kvcache q8 ops and
int8 layout, DecodeServer's programs over it, flash_decode in-kernel
dequantization).

The contract under test: an int8 pool stores K/V pages at a quarter of
the fp32 bytes with one fp32 scale per (layer, page); the q8 scatter /
gather ops quantize and dequantize IN-PROGRAM (traced, no recompiles),
page scales only ever grow within a tenant (monotone requantization)
and reset on reuse (a freed page's stale scale never leaks), and the
decode logits stay within quantization tolerance of the fp32 path."""
import functools

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import compile_watch, fault, telemetry
from mxnet_tpu.serving import DecodeServer, KVCachePool, ToyDecoderLM
from mxnet_tpu.serving import kvcache


@pytest.fixture(autouse=True)
def _clean_state():
    fault.reset()
    telemetry.reset()
    compile_watch.disable()
    yield
    fault.reset()
    telemetry.reset()
    compile_watch.disable()


INT8_NAMES = ("k", "v", "k_scale", "v_scale")


def _carried(pool):
    """The pool's carried arrays by name."""
    return dict(zip(pool.names, pool.arrays))


def _q8_pool_arrays(L=2, P=8, S=8, H=2, D=8):
    import jax.numpy as jnp
    pages = jnp.zeros((L, P, S, H, D), jnp.int8)
    scales = jnp.zeros((L, P), jnp.float32)
    return pages, scales


# ---------------------------------------------------------------------------
# pool construction
# ---------------------------------------------------------------------------

def test_pool_int8_env_and_explicit_dtype(monkeypatch):
    import jax.numpy as jnp
    pool = KVCachePool(2, 2, 8, page_size=8, n_pages=8)
    assert pool.names == ("k", "v") and pool.dtype == jnp.float32

    monkeypatch.setenv("MXNET_KV_DTYPE", "int8")
    pool = KVCachePool(2, 2, 8, page_size=8, n_pages=8)
    assert pool.names == INT8_NAMES and pool.dtype == jnp.int8
    arrays = _carried(pool)
    assert arrays["k"].dtype == jnp.int8 and arrays["v"].dtype == jnp.int8
    assert arrays["k_scale"].shape == arrays["v_scale"].shape == (2, 8)
    assert arrays["k_scale"].dtype == jnp.float32
    assert pool.k is arrays["k"] and pool.v is arrays["v"]
    assert pool.stats()["dtype"] == "int8"

    monkeypatch.delenv("MXNET_KV_DTYPE")
    pool = KVCachePool(2, 2, 8, page_size=8, n_pages=8, dtype="int8")
    assert pool.names == INT8_NAMES

    monkeypatch.setenv("MXNET_KV_DTYPE", "int7")
    with pytest.raises(mx.MXNetError):
        KVCachePool(2, 2, 8, page_size=8, n_pages=8)


# ---------------------------------------------------------------------------
# q8 scatter / gather ops
# ---------------------------------------------------------------------------

def test_q8_prefill_gather_roundtrip_bound():
    import jax.numpy as jnp
    rs = np.random.RandomState(0)
    L, P, S, H, D = 2, 8, 8, 2, 8
    pages, scales = _q8_pool_arrays(L, P, S, H, D)
    Lr, n_valid = 24, 19
    seq = rs.randn(L, Lr, H, D).astype(np.float32)
    # garbage beyond n_valid must not inflate page scales
    seq[:, n_valid:] = 1e6
    table = np.array([1, 2, 3], np.int32)
    pages, scales = kvcache.scatter_prefill_q8(
        pages, scales, jnp.asarray(table), jnp.asarray(seq), n_valid)
    got = np.asarray(kvcache.gather_pages_q8(
        pages, scales, jnp.asarray(table[None, :])))[:, 0]
    # per-page scale = amax/127 over that page's VALID rows; the
    # quantization error on any element is at most half a step
    sc = np.asarray(scales)
    for page in range(3):
        lo, hi = page * S, min((page + 1) * S, n_valid)
        step = sc[:, table[page]]          # (L,)
        assert np.all(step > 0)
        err = np.abs(got[:, lo:hi] - seq[:, lo:hi])
        assert np.all(err <= step[:, None, None, None] * 0.5 + 1e-6)
    # untouched pages keep zero scale; garbage rows read back as the
    # page's clipped values, never 1e6
    assert np.all(sc[:, 4:] == 0)
    assert np.max(np.abs(got)) < 1e3


def test_q8_scatter_token_fresh_page_and_monotone_growth():
    import jax.numpy as jnp
    L, P, S, H, D = 1, 4, 4, 1, 2
    pages, scales = _q8_pool_arrays(L, P, S, H, D)
    # poison page 2 as if a prior tenant left garbage behind
    pages = pages.at[:, 2].set(127)
    scales = scales.at[:, 2].set(100.0)
    table = jnp.asarray([[2, 3]], jnp.int32)       # one request, B=1

    # slot 0 write = new tenant: scale is set FRESH, body zeroed
    new0 = jnp.full((L, 1, H, D), 0.5, jnp.float32)
    pages, scales = kvcache.scatter_token_q8(
        pages, scales, table, jnp.asarray([0], jnp.int32), new0)
    sc = float(np.asarray(scales)[0, 2])
    assert sc == pytest.approx(0.5 / 127.0)
    body = np.asarray(pages)[0, 2]
    assert np.all(body[1:] == 0)                   # stale rows gone
    got = body[0].astype(np.float32) * sc
    np.testing.assert_allclose(got, 0.5, atol=sc)

    # a louder token at slot 1 grows the scale; slot 0 requantizes
    # in place and stays within the NEW (coarser) step
    new1 = jnp.full((L, 1, H, D), 2.0, jnp.float32)
    pages, scales = kvcache.scatter_token_q8(
        pages, scales, table, jnp.asarray([1], jnp.int32), new1)
    sc2 = float(np.asarray(scales)[0, 2])
    assert sc2 == pytest.approx(2.0 / 127.0)
    body = np.asarray(pages)[0, 2].astype(np.float32) * sc2
    np.testing.assert_allclose(body[0], 0.5, atol=sc2)
    np.testing.assert_allclose(body[1], 2.0, atol=sc2)

    # a quieter token must NOT shrink the scale (monotone growth)
    new2 = jnp.full((L, 1, H, D), 0.1, jnp.float32)
    pages, scales = kvcache.scatter_token_q8(
        pages, scales, table, jnp.asarray([2], jnp.int32), new2)
    assert float(np.asarray(scales)[0, 2]) == pytest.approx(sc2)


def test_q8_gather_matches_fp32_gather_within_tolerance():
    """The model-facing contract: gather_pages_q8 over a quantized
    pool reproduces gather_pages over an fp32 pool holding the same
    rows, elementwise within each page's quantization step."""
    import jax.numpy as jnp
    rs = np.random.RandomState(7)
    L, P, S, H, D = 2, 8, 8, 2, 8
    Lr = 16
    seq = jnp.asarray(rs.randn(L, Lr, H, D).astype(np.float32))
    table = jnp.asarray([1, 2], jnp.int32)

    fpages = jnp.zeros((L, P, S, H, D), jnp.float32)
    fpages = kvcache.scatter_prefill(fpages, table, seq, Lr)
    ref = np.asarray(kvcache.gather_pages(fpages, table[None, :]))

    qpages, qscales = _q8_pool_arrays(L, P, S, H, D)
    qpages, qscales = kvcache.scatter_prefill_q8(
        qpages, qscales, table, seq, Lr)
    got = np.asarray(kvcache.gather_pages_q8(
        qpages, qscales, table[None, :]))

    step = np.asarray(qscales)[:, np.asarray(table)]   # (L, 2)
    step = np.repeat(step, S, axis=1)[:, None]          # (L, 1, 16)
    assert np.all(np.abs(got[:, :, :Lr] - ref[:, :, :Lr])
                  <= step[..., None, None] * 0.5 + 1e-6)


# ---------------------------------------------------------------------------
# model-level logits tolerance (the acceptance criterion)
# ---------------------------------------------------------------------------

def test_q8_decode_logits_close_to_fp32():
    """One decode step attending an int8 pool lands within
    quantization tolerance of the same step over the fp32 pool."""
    import jax.numpy as jnp
    model = ToyDecoderLM(vocab=32, n_layers=1, n_heads=2, head_dim=8,
                         max_len=128)
    params = model.init_params(seed=3)
    prompt = jnp.asarray([[3, 9, 4, 1, 7, 2, 6, 5]], jnp.int32)
    _, k_seq, v_seq = model.prefill(params, prompt)
    L, _, Lr = k_seq.shape[0], k_seq.shape[1], k_seq.shape[2]
    H, D = k_seq.shape[3], k_seq.shape[4]
    S, P, M = 8, 8, 2
    table = jnp.asarray([1, 2], jnp.int32)

    fk = kvcache.scatter_prefill(
        jnp.zeros((L, P, S, H, D), jnp.float32), table, k_seq[:, 0], Lr)
    fv = kvcache.scatter_prefill(
        jnp.zeros((L, P, S, H, D), jnp.float32), table, v_seq[:, 0], Lr)
    qk, qks = kvcache.scatter_prefill_q8(
        *_q8_pool_arrays(L, P, S, H, D), table, k_seq[:, 0], Lr)
    qv, qvs = kvcache.scatter_prefill_q8(
        *_q8_pool_arrays(L, P, S, H, D), table, v_seq[:, 0], Lr)

    tokens = jnp.asarray([11], jnp.int32)
    positions = jnp.asarray([Lr], jnp.int32)
    ref_logits, _, _ = model.decode(
        params, tokens, positions,
        functools.partial(kvcache.paged_attention, fk, fv,
                          table[None, :], positions))
    q8_logits, _, _ = model.decode(
        params, tokens, positions,
        functools.partial(kvcache.paged_attention, qk, qv,
                          table[None, :], positions, k_scale=qks,
                          v_scale=qvs))
    np.testing.assert_allclose(np.asarray(q8_logits),
                               np.asarray(ref_logits),
                               rtol=0, atol=0.05)


# ---------------------------------------------------------------------------
# server-level: int8 pool end to end, fixed program set
# ---------------------------------------------------------------------------

def test_server_int8_completions_fixed_programs(monkeypatch):
    monkeypatch.setenv("MXNET_KV_DTYPE", "int8")
    compile_watch.enable()
    model = ToyDecoderLM(vocab=32, n_layers=1, n_heads=2, head_dim=8,
                         max_len=128)
    params = model.init_params(seed=3)
    srv = DecodeServer(model, params, seq_ladder=[16, 32],
                       max_new_tokens=8, window=4, page_size=8,
                       pool_pages=32, start=False)
    assert srv._pool.names == INT8_NAMES
    free0 = srv._pool.stats()["free"]
    srv.warmup()
    warm = compile_watch.site_stats("decode")
    assert set(warm) == {"decode:step", "decode:prefill:s16",
                         "decode:prefill:s32"}
    assert all(v["count"] == 1 for v in warm.values())

    rs = np.random.RandomState(2)
    reqs = [srv.submit(rs.randint(1, 32, size=rs.randint(2, 28)),
                       max_new_tokens=5) for _ in range(6)]
    n = 0
    while not all(r.done() for r in reqs):
        srv._tick()
        n += 1
        assert n < 500, "scheduler made no progress"
    for r in reqs:
        out = r.result(timeout=5)
        assert r.state == "done"
        assert len(out) == 5
        assert all(0 <= int(t) < 32 for t in out)
    # steady state: the warmup program set, compiled once each
    assert compile_watch.site_stats("decode") == warm
    assert srv._pool.stats()["free"] == free0
    assert srv.stats()["kv"]["dtype"] == "int8"


def test_server_int8_tokens_match_full_forward_q8_oracle(monkeypatch):
    """Greedy tokens from the int8 server match greedy generation by
    full forwards whose attention reads the SAME quantized cache —
    the stepwise-vs-full contract holds under quantization too."""
    import jax.numpy as jnp
    monkeypatch.setenv("MXNET_KV_DTYPE", "int8")
    model = ToyDecoderLM(vocab=32, n_layers=1, n_heads=2, head_dim=8,
                         max_len=128)
    params = model.init_params(seed=3)
    srv = DecodeServer(model, params, seq_ladder=[16],
                       max_new_tokens=6, window=2, page_size=8,
                       pool_pages=16, start=False)
    prompt = np.asarray([3, 9, 4, 1, 7, 2], np.int32)
    req = srv.submit(prompt, max_new_tokens=4)
    n = 0
    while not req.done():
        srv._tick()
        n += 1
        assert n < 200
    got = [int(t) for t in req.result(timeout=5)]

    # oracle: replay the exact q8 cache pipeline step by step
    L, H, D = model.n_layers, model.n_heads, model.head_dim
    S, P, M = 8, 16, 2
    table = jnp.asarray([1, 2], jnp.int32)
    qk, qks = _q8_pool_arrays(L, P, S, H, D)
    qv, qvs = _q8_pool_arrays(L, P, S, H, D)
    toks = jnp.asarray([list(prompt) + [0] * (16 - len(prompt))],
                       jnp.int32)
    logits, k_seq, v_seq = model.prefill(params, toks)
    qk, qks = kvcache.scatter_prefill_q8(qk, qks, table, k_seq[:, 0],
                                         len(prompt))
    qv, qvs = kvcache.scatter_prefill_q8(qv, qvs, table, v_seq[:, 0],
                                         len(prompt))
    cur = int(np.argmax(np.asarray(logits)[0, len(prompt) - 1]))
    want = [cur]
    pos = len(prompt)
    for _ in range(3):
        at = jnp.asarray([pos], jnp.int32)
        lg, k_new, v_new = model.decode(
            params, jnp.asarray([cur], jnp.int32), at,
            functools.partial(kvcache.paged_attention, qk, qv,
                              table[None, :], at, k_scale=qks,
                              v_scale=qvs))
        qk, qks = kvcache.scatter_token_q8(
            qk, qks, table[None, :], jnp.asarray([pos], jnp.int32),
            k_new)
        qv, qvs = kvcache.scatter_token_q8(
            qv, qvs, table[None, :], jnp.asarray([pos], jnp.int32),
            v_new)
        cur = int(np.argmax(np.asarray(lg)[0]))
        want.append(cur)
        pos += 1
    assert got == want


def test_int8_pool_carries_more_streams_at_the_same_bytes(monkeypatch):
    """At the bytes of a 7-page float32 pool (its carried arrays
    summed), the int8 pool — scales counted — holds 27 pages: 8 streams
    of 3 pages where float32 holds 2, each run at its full ceiling with
    no preemption and no failed allocation."""
    model = ToyDecoderLM(vocab=32, n_layers=1, n_heads=2, head_dim=8,
                         max_len=128)
    params = model.init_params(seed=3)

    def pool_bytes(dtype, pages):
        return sum(a.nbytes for a in KVCachePool(
            1, 2, 8, page_size=8, n_pages=pages, dtype=dtype).arrays)

    budget = pool_bytes("float32", 7)
    int8_pages = max(n for n in range(2, 64)
                     if pool_bytes("int8", n) <= budget)
    assert int8_pages == 27

    def ceiling(dtype, pages):
        monkeypatch.setenv("MXNET_KV_DTYPE", dtype)
        cap = (pages - 1) // 3          # 14 prompt + 10 new = 3 pages
        srv = DecodeServer(model, params, seq_ladder=[16],
                           max_new_tokens=10, window=cap, page_size=8,
                           pool_pages=pages, max_queue=cap + 4,
                           start=False)
        rs = np.random.RandomState(7)
        reqs = [srv.submit(rs.randint(1, 32, size=14), max_new_tokens=10)
                for _ in range(cap)]
        n = 0
        while not all(r.done() for r in reqs):
            srv._tick()
            n += 1
            assert n < 500, "scheduler made no progress"
        st = srv.stats()
        srv.stop()
        assert st["completed"] == cap and st["preempted"] == 0
        assert st["kv"]["alloc_failures"] == 0
        assert st["kv"]["dtype"] == dtype
        return cap

    assert (ceiling("float32", 7), ceiling("int8", int8_pages)) == (2, 8)


# ---------------------------------------------------------------------------
# flash_decode int8 kernel path
# ---------------------------------------------------------------------------

def test_flash_decode_q8_pallas_matches_jnp_reference():
    from mxnet_tpu.parallel.flash_attention import flash_decode
    import jax.numpy as jnp
    rs = np.random.RandomState(5)
    B, T, H, D = 2, 128, 2, 8
    q = jnp.asarray(rs.randn(B, 1, H, D).astype(np.float32))
    k = jnp.asarray(rs.randint(-127, 128, size=(B, T, H, D)), jnp.int8)
    v = jnp.asarray(rs.randint(-127, 128, size=(B, T, H, D)), jnp.int8)
    ks = jnp.asarray(rs.uniform(0.005, 0.02, size=(B, T))
                     .astype(np.float32))
    vs = jnp.asarray(rs.uniform(0.005, 0.02, size=(B, T))
                     .astype(np.float32))
    lengths = jnp.asarray([37, 128], jnp.int32)

    ref = flash_decode(q, k, v, lengths, k_scale=ks, v_scale=vs)
    got = flash_decode(q, k, v, lengths, k_scale=ks, v_scale=vs,
                       force_pallas=True, block_k=64)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=0, atol=2e-5)

    # dequantizing by hand must agree with the quantized entry point
    kd = k.astype(jnp.float32) * ks[:, :, None, None]
    vd = v.astype(jnp.float32) * vs[:, :, None, None]
    full = flash_decode(q, kd, vd, lengths)
    np.testing.assert_allclose(np.asarray(ref), np.asarray(full),
                               rtol=0, atol=2e-5)

    with pytest.raises(ValueError):
        flash_decode(q, k, v, lengths, k_scale=ks)


# ---------------------------------------------------------------------------
# prefix sharing over a quantized pool: scales travel with the pages
# ---------------------------------------------------------------------------

def test_q8_shared_prefix_hit_deterministic_scales_untouched(
        monkeypatch):
    """A prefix hit on an int8 pool reuses the shared pages' per-page
    scales as-is: the hit run is deterministic (two hits agree
    exactly) and never rewrites the scales of pages it shares — the
    re-fed tail token COWs its page instead."""
    monkeypatch.setenv("MXNET_KV_DTYPE", "int8")
    model = ToyDecoderLM(vocab=32, n_layers=1, n_heads=2, head_dim=8,
                         max_len=128)
    params = model.init_params(seed=3)
    srv = DecodeServer(model, params, seq_ladder=[16],
                       max_new_tokens=6, window=2, page_size=8,
                       pool_pages=32, prefix_cache=True, start=False)

    def _go(prompt, n=4):
        req = srv.submit(prompt, max_new_tokens=n)
        steps = 0
        while not req.done():
            srv._tick()
            steps += 1
            assert steps < 300
        return [int(t) for t in req.result(timeout=5)], req

    prompt = np.arange(10, 26, dtype=np.int32)     # 2 full pages
    _go(prompt)                                    # miss: fills index
    pages = [p for d, (p, _ns)
             in srv._pool.prefix._entries.items()]
    ks0 = np.asarray(_carried(srv._pool)["k_scale"])[:, pages].copy()
    vs0 = np.asarray(_carried(srv._pool)["v_scale"])[:, pages].copy()
    assert np.all(ks0 > 0) and np.all(vs0 > 0)

    hit1, r1 = _go(prompt)
    hit2, r2 = _go(prompt)
    assert hit1 == hit2                            # deterministic
    assert r1.prefix_cached == 16 and r2.prefix_cached == 16
    assert srv.stats()["prefix"]["hits"] == 2
    assert srv.stats()["prefix"]["cow_splits"] == 2
    # the SHARED pages' scales never moved: hit traffic wrote only
    # COW copies and fresh suffix pages
    np.testing.assert_array_equal(
        np.asarray(_carried(srv._pool)["k_scale"])[:, pages], ks0)
    np.testing.assert_array_equal(
        np.asarray(_carried(srv._pool)["v_scale"])[:, pages], vs0)
    srv.stop()


def test_q8_cow_copy_carries_the_scales(monkeypatch):
    """The one COW program, over an int8 pool's carried arrays, copies
    page BODY and per-page scales together — the private fork
    dequantizes bit-identically to the shared page it split from."""
    import jax.numpy as jnp
    monkeypatch.setenv("MXNET_KV_DTYPE", "int8")
    model = ToyDecoderLM(vocab=32, n_layers=1, n_heads=2, head_dim=8,
                         max_len=128)
    params = model.init_params(seed=3)
    srv = DecodeServer(model, params, seq_ladder=[16],
                       max_new_tokens=4, window=2, page_size=8,
                       pool_pages=8, prefix_cache=True, start=False)
    rs = np.random.RandomState(1)
    shapes = {n: a.shape for n, a in _carried(srv._pool).items()}
    assert tuple(shapes) == INT8_NAMES
    k = jnp.asarray(rs.randint(-127, 128, size=shapes["k"]), jnp.int8)
    v = jnp.asarray(rs.randint(-127, 128, size=shapes["v"]), jnp.int8)
    ks = jnp.asarray(rs.uniform(0.004, 0.02, size=shapes["k_scale"])
                     .astype(np.float32))
    vs = jnp.asarray(rs.uniform(0.004, 0.02, size=shapes["v_scale"])
                     .astype(np.float32))
    k2, v2, ks2, vs2 = srv._cow_fn(k, v, ks, vs, 2, 5)
    np.testing.assert_array_equal(np.asarray(k2)[:, 5],
                                  np.asarray(k)[:, 2])
    np.testing.assert_array_equal(np.asarray(v2)[:, 5],
                                  np.asarray(v)[:, 2])
    np.testing.assert_array_equal(np.asarray(ks2)[:, 5],
                                  np.asarray(ks)[:, 2])
    np.testing.assert_array_equal(np.asarray(vs2)[:, 5],
                                  np.asarray(vs)[:, 2])
    # dequantized content of the fork == the original, bit for bit
    deq = lambda p, s, i: np.asarray(p)[:, i].astype(np.float32) \
        * np.asarray(s)[:, i, None, None, None]
    np.testing.assert_array_equal(deq(k2, ks2, 5), deq(k, ks, 2))
    # every other page untouched
    untouched = [i for i in range(shapes["k"][1]) if i != 5]
    np.testing.assert_array_equal(np.asarray(k2)[:, untouched],
                                  np.asarray(k)[:, untouched])
    np.testing.assert_array_equal(np.asarray(ks2)[:, untouched],
                                  np.asarray(ks)[:, untouched])
    srv.stop()


def test_q8_recycled_shared_page_scale_resets(monkeypatch):
    """A cold shared page evicted under pressure and re-allocated to a
    NEW prompt gets a FRESH scale from the new content — identical to
    a never-shared pool serving the same prompt (no stale-scale
    leak, no monotone carry-over across tenants)."""
    monkeypatch.setenv("MXNET_KV_DTYPE", "int8")
    model = ToyDecoderLM(vocab=32, n_layers=1, n_heads=2, head_dim=8,
                         max_len=128)
    params = model.init_params(seed=3)

    def _serve(srv, prompt, n=3):
        req = srv.submit(prompt, max_new_tokens=n)
        steps = 0
        while not req.done():
            srv._tick()
            steps += 1
            assert steps < 300
        return [int(t) for t in req.result(timeout=5)]

    a = np.arange(10, 26, dtype=np.int32)          # 2 full pages
    b = np.asarray([5, 3, 8, 1, 9, 2, 7, 4, 6, 11, 13, 12, 15, 14,
                    17, 16], np.int32)
    # 4 usable pages: A's run leaves 2 cold index pages; B's 3-page
    # admission must evict them and recycle the SAME page slots
    tight = DecodeServer(model, params, seq_ladder=[16],
                         max_new_tokens=4, window=1, page_size=8,
                         pool_pages=5, prefix_cache=True, start=False)
    _serve(tight, a)
    assert tight._pool.prefix_stats()["entries"] == 2
    got = _serve(tight, b)
    assert tight._pool.prefix_stats()["evicted"] >= 1

    fresh = DecodeServer(model, params, seq_ladder=[16],
                         max_new_tokens=4, window=1, page_size=8,
                         pool_pages=6, prefix_cache=True, start=False)
    want = _serve(fresh, b)
    assert got == want                     # stale state changed nothing
    # B's cached pages (matched by content digest — the tight pool
    # may still hold a leftover A entry) carry IDENTICAL scales in
    # both pools: the recycled page's old-tenant scale left no trace
    te = {d: p for d, (p, _ns) in tight._pool.prefix._entries.items()}
    fe = {d: p for d, (p, _ns) in fresh._pool.prefix._entries.items()}
    common = [d for d in fe if d in te]
    assert len(common) == 2                # both of B's full pages
    tp = [te[d] for d in common]
    fp = [fe[d] for d in common]
    for name in ("k_scale", "v_scale"):
        np.testing.assert_array_equal(
            np.asarray(_carried(tight._pool)[name])[:, tp],
            np.asarray(_carried(fresh._pool)[name])[:, fp])
    tight.stop()
    fresh.stop()


# ---------------------------------------------------------------------------
# one cache kind, one place: the layout is picked once, and the one pair
# of programs over the int8 layout is the pair the int8 twins were
# ---------------------------------------------------------------------------

K_V = (("k", (2, 8)), ("v", (2, 8)))


@pytest.mark.parametrize("arrays, dtype, kind, names, token_bytes", [
    (K_V, "float32", "_PerHeadKV", ("k", "v"), 3 * 2 * 16 * 4),
    (K_V, "bfloat16", "_PerHeadKV", ("k", "v"), 3 * 2 * 16 * 2),
    (K_V, "int8", "_PerHeadKVInt8", INT8_NAMES, 3 * 2 * 16),
    ((("kv", (64,)),), "bfloat16", "_Latent", ("kv",), 3 * 64 * 2),
    ((("kv", (64,)),), "int8", "int8 pages with per-page scales", None,
     None),
    ((("a", (2, 8)), ("b", (2, 8)), ("c", (2, 8))), "float32",
     "no cache layout", None, None),
], ids=["kv-f32", "kv-bf16", "kv-int8", "latent", "latent-int8-refused",
        "three-arrays-refused"])
def test_pool_picks_the_layout_from_declaration_and_dtype(
        arrays, dtype, kind, names, token_bytes):
    import jax.numpy as jnp
    if names is None:
        with pytest.raises(mx.MXNetError, match=kind):
            KVCachePool(3, arrays=arrays, dtype=dtype, page_size=8,
                        n_pages=4)
        return
    pool = KVCachePool(3, arrays=arrays, dtype=dtype, page_size=8,
                       n_pages=4)
    assert type(pool.layout).__name__ == kind
    # chosen once: the same (declaration, dtype) is the same object, for
    # another pool and for a program that asks from its model and arrays
    assert kvcache.cache_layout(pool.array_specs, pool.dtype) \
        is pool.layout
    model = type("M", (), {"cache_arrays": arrays})()
    assert kvcache.layout_for(model, pool.arrays) is pool.layout
    assert pool.names == names and pool.array_specs == arrays
    assert pool.token_bytes == token_bytes
    pages = [a for n, a in _carried(pool).items()
             if not n.endswith("_scale")]
    assert [a.shape for a in pages] == [(3, 4, 8) + t for _n, t in arrays]
    assert all(a.dtype == jnp.dtype(dtype) for a in pages)
    st = pool.stats()
    assert st["dtype"] == dtype and st["token_bytes"] == token_bytes
    assert st["arrays"] == {n: list(t) for n, t in arrays}


def _old_prefill_fn_q8(model, params, tokens, n_valid, page_table,
                       k_pages, v_pages, k_scales, v_scales):
    """``DecodeServer._prefill_fn_q8`` as it stood at 3913add, before
    the int8 pool became a layout behind one ``_prefill_fn`` — the
    oracle."""
    import jax.numpy as jnp
    logits, k_seq, v_seq = model.prefill(params, tokens)
    k_pages, k_scales = kvcache.scatter_prefill_q8(
        k_pages, k_scales, page_table, k_seq[:, 0], n_valid)
    v_pages, v_scales = kvcache.scatter_prefill_q8(
        v_pages, v_scales, page_table, v_seq[:, 0], n_valid)
    last = jnp.take(logits[0], n_valid - 1, axis=0)
    token = jnp.argmax(last).astype(jnp.int32)
    return token, k_pages, v_pages, k_scales, v_scales


def _old_decode_fn_q8(model, params, tokens, positions, page_tables,
                      k_pages, v_pages, k_scales, v_scales):
    import jax.numpy as jnp
    attend = functools.partial(kvcache.paged_attention, k_pages,
                               v_pages, page_tables, positions,
                               k_scale=k_scales, v_scale=v_scales)
    logits, k_new, v_new = model.decode(
        params, tokens, positions, attend)
    k_pages, k_scales = kvcache.scatter_token_q8(
        k_pages, k_scales, page_tables, positions, k_new)
    v_pages, v_scales = kvcache.scatter_token_q8(
        v_pages, v_scales, page_tables, positions, v_new)
    tokens_out = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    return tokens_out, k_pages, v_pages, k_scales, v_scales


def _old_cow_fn_q8(k_pages, v_pages, k_scales, v_scales, src, dst):
    k_pages = k_pages.at[:, dst].set(k_pages[:, src])
    v_pages = v_pages.at[:, dst].set(v_pages[:, src])
    k_scales = k_scales.at[:, dst].set(k_scales[:, src])
    v_scales = v_scales.at[:, dst].set(v_scales[:, src])
    return k_pages, v_pages, k_scales, v_scales


@pytest.mark.parametrize("use_pallas", [False, True],
                         ids=["jnp", "pallas"])
def test_int8_server_runs_the_programs_its_twins_ran(use_pallas):
    """The one ``_prefill_fn`` / ``_step_fn`` (the body of the
    ``_decode_fn`` program) / ``_cow_fn`` over the int8 layout trace to the jaxprs of the deleted ``*_q8`` twins: an
    int8 server runs the programs it ran."""
    import jax
    import jax.numpy as jnp
    model = ToyDecoderLM(vocab=32, n_layers=2, n_heads=2, head_dim=8,
                         max_len=128, use_pallas=use_pallas)
    params = model.init_params(seed=3)
    holder = type("S", (), {"_model": model})()
    pages, scales = _q8_pool_arrays(2, 24, 8, 2, 8)
    pools = (pages, pages, scales, scales)
    step_args = (params, jnp.zeros((3,), jnp.int32),
                 jnp.zeros((3,), jnp.int32), jnp.zeros((3, 6), jnp.int32),
                 *pools)
    new = jax.make_jaxpr(functools.partial(DecodeServer._step_fn,
                                           holder))(*step_args)
    old = jax.make_jaxpr(functools.partial(_old_decode_fn_q8,
                                           model))(*step_args)
    assert str(new) == str(old)
    pre_args = (params, jnp.zeros((1, 16), jnp.int32), jnp.int32(5),
                jnp.zeros((6,), jnp.int32), *pools)
    new = jax.make_jaxpr(functools.partial(DecodeServer._prefill_fn,
                                           holder))(*pre_args)
    old = jax.make_jaxpr(functools.partial(_old_prefill_fn_q8,
                                           model))(*pre_args)
    assert str(new) == str(old)
    cow_args = (*pools, jnp.int32(2), jnp.int32(5))
    new = jax.make_jaxpr(functools.partial(DecodeServer._cow_fn,
                                           holder))(*cow_args)
    old = jax.make_jaxpr(_old_cow_fn_q8)(*cow_args)
    assert str(new) == str(old)


class _CountingLM(ToyDecoderLM):
    """ToyDecoderLM that also declares a step counter (its ``decode``
    is the one-token contract's alone: no ``chunk_lanes``)."""
    step_counters = ("toy", ("rows", "max_batch"))
    chunk_lanes = False

    def decode(self, params, tokens, positions, attend):
        import jax.numpy as jnp
        out = super().decode(params, tokens, positions, attend)
        width = tokens.shape[0]
        return (*out, jnp.asarray([width, width], jnp.int32))


@pytest.mark.parametrize("dtype", ["float32", "int8"])
def test_step_counters_leave_with_the_tokens_on_every_kind(
        monkeypatch, dtype):
    """What the float program had and the int8 twin had lost: a model's
    step counters reach ``stats()`` whatever the pool's kind."""
    monkeypatch.setenv("MXNET_KV_DTYPE", dtype)
    model = _CountingLM(vocab=32, n_layers=1, n_heads=2, head_dim=8,
                        max_len=128)
    srv = DecodeServer(model, model.init_params(seed=3), seq_ladder=[16],
                       max_new_tokens=6, window=2, page_size=8,
                       pool_pages=16, start=False)
    assert srv.stats()["kv"]["dtype"] == dtype
    req = srv.submit(np.asarray([3, 9, 4, 1, 7, 2], np.int32),
                     max_new_tokens=4)
    n = 0
    while not req.done():
        srv._tick()
        n += 1
        assert n < 200
    assert len(req.result(timeout=5)) == 4
    st = srv.stats()
    steps = st["decode_steps"]
    assert steps >= 3
    assert st["toy"] == {"steps": steps, "rows": 2 * steps, "max_batch": 2,
                         "last": {"rows": 2, "max_batch": 2}}
    srv.stop()
