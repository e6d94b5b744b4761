"""Int8-quantized paged KV cache (mxnet_tpu.serving.kvcache q8 ops,
DecodeServer int8 programs, flash_decode in-kernel dequantization).

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
    assert not pool.quantized and pool.dtype == jnp.float32
    assert pool.k_scale is None and pool.v_scale is None

    monkeypatch.setenv("MXNET_KV_DTYPE", "int8")
    pool = KVCachePool(2, 2, 8, page_size=8, n_pages=8)
    assert pool.quantized and pool.dtype == jnp.int8
    assert pool.k.dtype == jnp.int8 and pool.v.dtype == jnp.int8
    assert pool.k_scale.shape == (2, 8)
    assert pool.k_scale.dtype == jnp.float32
    assert pool.stats()["dtype"] == "int8"

    monkeypatch.delenv("MXNET_KV_DTYPE")
    pool = KVCachePool(2, 2, 8, page_size=8, n_pages=8, dtype="int8")
    assert pool.quantized

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
    assert srv._pool.quantized
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
    ks0 = np.asarray(srv._pool.k_scale)[:, pages].copy()
    vs0 = np.asarray(srv._pool.v_scale)[:, pages].copy()
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
        np.asarray(srv._pool.k_scale)[:, pages], ks0)
    np.testing.assert_array_equal(
        np.asarray(srv._pool.v_scale)[:, pages], vs0)
    srv.stop()


def test_q8_cow_copy_carries_the_scales(monkeypatch):
    """The q8 COW program copies page BODY and per-page scales
    together — the private fork dequantizes bit-identically to the
    shared page it split from."""
    import jax.numpy as jnp
    monkeypatch.setenv("MXNET_KV_DTYPE", "int8")
    model = ToyDecoderLM(vocab=32, n_layers=1, n_heads=2, head_dim=8,
                         max_len=128)
    params = model.init_params(seed=3)
    srv = DecodeServer(model, params, seq_ladder=[16],
                       max_new_tokens=4, window=2, page_size=8,
                       pool_pages=8, prefix_cache=True, start=False)
    rs = np.random.RandomState(1)
    k = jnp.asarray(rs.randint(-127, 128, size=srv._pool.k.shape),
                    jnp.int8)
    v = jnp.asarray(rs.randint(-127, 128, size=srv._pool.v.shape),
                    jnp.int8)
    ks = jnp.asarray(rs.uniform(0.004, 0.02,
                                size=srv._pool.k_scale.shape)
                     .astype(np.float32))
    vs = jnp.asarray(rs.uniform(0.004, 0.02,
                                size=srv._pool.v_scale.shape)
                     .astype(np.float32))
    k2, v2, ks2, vs2 = srv._cow_fn_q8(k, v, ks, vs, 2, 5)
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
    untouched = [i for i in range(srv._pool.k.shape[1]) if i != 5]
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
    np.testing.assert_array_equal(
        np.asarray(tight._pool.k_scale)[:, tp],
        np.asarray(fresh._pool.k_scale)[:, fp])
    np.testing.assert_array_equal(
        np.asarray(tight._pool.v_scale)[:, tp],
        np.asarray(fresh._pool.v_scale)[:, fp])
    tight.stop()
    fresh.stop()
