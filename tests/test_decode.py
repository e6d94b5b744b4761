"""Stateful autoregressive serving (mxnet_tpu.serving.decode): paged
KV-cache decode over a fixed program set, continuous prefill/decode
batching, streaming with cancellation, priority admission/preemption,
deterministic fault sites, and zero-downtime weight hot-swap.

The load-bearing contract: N tokens produced by prefill + stepwise
cached decode are IDENTICAL to greedy generation by one full-sequence
forward at each length — on the jnp reference attention path AND the
Pallas flash kernels (interpret mode on CPU)."""
import functools
import json
import os
import subprocess
import sys
import time

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import compile_watch, fault, serving, telemetry
from mxnet_tpu.serving import (DecodeServer, KVCachePool,
                               ServerOverloadedError,
                               RequestTimeoutError, ToyDecoderLM)
from mxnet_tpu.serving.kvcache import pages_for


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


def _reference(model, params, prompt, n):
    """Greedy generation by one FULL-sequence forward at each length —
    the oracle stepwise cached decode must reproduce token-for-token."""
    import jax
    import jax.numpy as jnp
    toks = [int(t) for t in prompt]
    # one compiled program per length: op-by-op dispatch would compile
    # every primitive again at every new length
    prefill = jax.jit(model.prefill)
    for _ in range(n):
        logits, _, _ = prefill(params, jnp.asarray([toks], jnp.int32))
        toks.append(int(np.argmax(np.asarray(logits)[0, len(toks) - 1])))
    return toks[len(prompt):]


def _drain(srv, *reqs, limit=500):
    """Drive an unstarted server's scheduler deterministically."""
    n = 0
    while not all(r.done() for r in reqs):
        srv._tick()
        n += 1
        assert n < limit, "scheduler made no progress"
    return n


# ---------------------------------------------------------------------------
# KV-cache pool
# ---------------------------------------------------------------------------

def test_kvcache_pool_accounting():
    pool = KVCachePool(2, 2, 8, page_size=8, n_pages=8)
    assert pool.usable_pages == 7
    assert pool.pages_for(1) == 1 and pool.pages_for(8) == 1
    assert pool.pages_for(9) == 2
    a = pool.alloc(3)
    assert a == [1, 2, 3]                    # lowest-first, 0 reserved
    b = pool.alloc(4)
    assert pool.alloc(1) is None             # exhausted — not an error
    st = pool.stats()
    assert st["used"] == 7 and st["peak_used"] == 7
    assert st["alloc_failures"] == 1
    pool.free(a)
    st = pool.stats()
    assert st["free"] == 3 and st["evicted"] == 3
    assert st["peak_used"] == 7              # watermark survives frees
    pool.free(b)
    assert pool.stats()["free"] == 7


def test_kvcache_evict_fault_counted_never_leaks():
    """A planned raise at kv_evict is counted and survived — the page
    comes back anyway (a reclaim fault must never leak memory)."""
    pool = KVCachePool(1, 2, 8, page_size=8, n_pages=4)
    pages = pool.alloc(3)
    fault.set_plan("kv_evict:step=2:raise")
    try:
        assert pool.free(pages) == 3
        injected = fault.stats()["injected"].get("kv_evict")
    finally:
        fault.set_plan(None)                 # resets fault stats
    assert pool.stats()["free"] == 3
    assert injected == 1


def test_ladder_aligned_to_page_size():
    lad = serving.BucketLadder([10, 20, 30]).aligned(16)
    assert lad.buckets == [16, 32]           # collisions dedupe
    with pytest.raises(mx.base.MXNetError):
        serving.BucketLadder([8]).aligned(0)


# ---------------------------------------------------------------------------
# decode correctness: bit-exact vs full-sequence forward
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("use_pallas", [False, True],
                         ids=["jnp", "pallas"])
def test_stepwise_decode_matches_full_forward(use_pallas):
    """Prefill + stepwise cached decode reproduces one full-sequence
    forward at each length token-for-token, on both attention paths."""
    model, params = _toy(n_layers=2 if not use_pallas else 1,
                         use_pallas=use_pallas)
    rs = np.random.RandomState(0)
    srv = DecodeServer(model, params, seq_ladder=[16, 32],
                       max_new_tokens=12, window=4, page_size=8,
                       pool_pages=32, start=False)
    try:
        for plen in (1, 7, 13):
            prompt = rs.randint(1, 32, size=plen)
            ref = _reference(model, params, prompt, 10)
            req = srv.submit(prompt, max_new_tokens=10)
            _drain(srv, req)
            got = [int(t) for t in req.result(timeout=1)]
            assert got == ref, (use_pallas, plen)
    finally:
        srv.stop()


def test_decode_result_independent_of_batch_mates():
    """The decode step's fixed batch shape means a request's tokens
    can never depend on which batch-mates rode along: alone vs amid
    concurrent traffic is identical."""
    model, params = _toy()
    rs = np.random.RandomState(1)
    prompt = rs.randint(1, 32, size=9)
    srv = DecodeServer(model, params, seq_ladder=[16], max_new_tokens=8,
                       window=4, page_size=8, pool_pages=64,
                       start=False)
    try:
        alone = srv.submit(prompt, max_new_tokens=8)
        _drain(srv, alone)
        crowd = [srv.submit(rs.randint(1, 32, size=rs.randint(2, 16)),
                            max_new_tokens=8) for _ in range(3)]
        mine = srv.submit(prompt, max_new_tokens=8)
        _drain(srv, mine, *crowd)
        assert [int(t) for t in mine.result(timeout=1)] \
            == [int(t) for t in alone.result(timeout=1)]
    finally:
        srv.stop()


def test_eos_stops_generation_early():
    model, params = _toy()
    prompt = np.arange(1, 6)
    ref = _reference(model, params, prompt, 12)
    # stop at the first token the reference emits exactly once up to
    # there: random weights repeat tokens, and an eos that also shows
    # up earlier rightly stops the stream earlier
    stop = next(i for i in range(1, len(ref)) if ref[i] not in ref[:i])
    eos = ref[stop]
    srv = DecodeServer(model, params, seq_ladder=[16], max_new_tokens=12,
                       window=2, page_size=8, pool_pages=16,
                       start=False)
    try:
        req = srv.submit(prompt, max_new_tokens=12, eos_id=eos)
        _drain(srv, req)
        got = [int(t) for t in req.result(timeout=1)]
        assert 1 < len(got) < len(ref)
        assert got == ref[:stop + 1] and got[-1] == eos
    finally:
        srv.stop()


# ---------------------------------------------------------------------------
# fixed program set (the compile_watch oracle)
# ---------------------------------------------------------------------------

def test_mixed_stream_fixed_programs_zero_steady_recompiles():
    """Under a mixed prompt/decode stream with varied prompt lengths,
    site_stats("decode") holds exactly three programs — the step and the
    mixed step that carries a chunk of a prompt, at the ladder's two
    rungs within a step's budget; no prefill rung is ever built where
    prompts ride the step — each compiled once, with ZERO steady-state
    recompiles."""
    compile_watch.enable()
    model, params = _toy()
    srv = DecodeServer(model, params, seq_ladder=[16, 32, 64],
                       max_new_tokens=8, window=4, page_size=16,
                       pool_pages=64)
    try:
        srv.warmup()
        warm = compile_watch.site_stats("decode")
        assert set(warm) == {"decode:step", "decode:step:chunk:c16",
                             "decode:step:chunk:c32"}
        assert all(v["count"] == 1 for v in warm.values())
        rs = np.random.RandomState(2)
        reqs = [srv.submit(rs.randint(1, 32, size=rs.randint(2, 60)),
                           max_new_tokens=6) for _ in range(10)]
        for r in reqs:
            r.result(timeout=60)
        assert compile_watch.site_stats("decode") == warm
    finally:
        srv.stop()


@pytest.mark.parametrize("ladder,chunks", [((16, 64), (16,)),
                                           ((8, 16, 64), (8, 16))],
                         ids=["one_size", "two_sizes"])
def test_the_window_forms_program_set_is_the_step_and_its_chunks(ladder,
                                                                  chunks):
    """The STATE form of a model whose state takes a chunk (a ring of a
    window's keys: ``serving.window_moe``) has the plain form's program
    set: ``1 + len(chunks)`` — the step and the mixed step at every rung
    within twice the ladder's smallest, no prefill at any rung — each
    compiled once by ``warmup()`` and never again under a mix of prompts
    shorter than, equal to and several times the window."""
    from mxnet_tpu.serving.window_moe import WindowMoEDecoderLM, tiny_config
    compile_watch.enable()
    model = WindowMoEDecoderLM(**tiny_config(), dtype="float32")
    params = model.init_params(seed=0)
    srv = DecodeServer(model, params, seq_ladder=list(ladder),
                       max_new_tokens=6, window=3, page_size=8,
                       pool_pages=64, prefix_cache=False, name="ring",
                       start=False)
    try:
        assert srv.warmup() == 1 + len(chunks)
        warm = compile_watch.site_stats("decode:ring")
        assert set(warm) == {"decode:ring:step"} | {
            "decode:ring:step:chunk:c%d" % c for c in chunks}
        assert all(v["count"] == 1 for v in warm.values())
        rs = np.random.RandomState(5)
        sizes = (3, 8, 9, 16, 17, 40, 64, 27)
        reqs = [srv.submit(rs.randint(1, 96, size=n), max_new_tokens=6)
                for n in sizes]
        _drain(srv, *reqs)
        assert compile_watch.site_stats("decode:ring") == warm
        st = srv.stats()
        assert st["completed"] == len(sizes)
        assert st["prefill_programs"] == 0
        assert st["chunk_tokens"] == sum(sizes)
    finally:
        srv.stop()


# ---------------------------------------------------------------------------
# streaming + cancellation
# ---------------------------------------------------------------------------

def test_streaming_iterator_and_cancel_frees_pages():
    model, params = _toy()
    pool_free0 = None
    srv = DecodeServer(model, params, seq_ladder=[16], max_new_tokens=16,
                       window=2, page_size=8, pool_pages=16,
                       start=False)
    try:
        pool_free0 = srv._pool.stats()["free"]
        req = srv.submit(np.arange(1, 8), max_new_tokens=16)
        srv._tick()                           # the prompt's one chunk
        srv._tick()                           # read: the first token
        srv._tick()                           # one decode step read
        assert req.pages and srv._pool.stats()["free"] < pool_free0
        seen = []
        it = req.tokens(timeout=1)
        seen.append(next(it))
        seen.append(next(it))
        req.cancel()
        srv._tick()                           # reap before next step
        assert req.done() and req.state == "cancelled"
        assert srv._pool.stats()["free"] == pool_free0   # reclaimed
        rest = list(it)                       # stream just ends
        got = [int(t) for t in req.result(timeout=1)]
        # deterministic: each tick dispatches one step and reads the
        # one before it: 3 ticks emitted the token of the step that
        # carried the prompt and the first decode step's, and the third
        # step, unread when the cancel landed, ran one step too many —
        # its token is dropped, nothing is pushed after the end
        assert seen + rest == got and len(got) == 2
        assert srv.stats()["decode_steps"] == 3
        assert srv.stats()["tokens_out"] == 2
        assert srv.stats()["cancelled"] == 1
        # admission covered positions 0..7 with one 8-slot page; the
        # second decode step's write at position 8 grew a second —
        # both provably came back
        assert srv._pool.stats()["evicted"] == 2
    finally:
        srv.stop()


# ---------------------------------------------------------------------------
# faults: a hang ages a streaming request past its deadline; pages
# provably reclaimed through kv_evict
# ---------------------------------------------------------------------------

def test_decode_hang_ages_request_past_deadline_pages_reclaimed(
        monkeypatch):
    monkeypatch.setenv("MXNET_FAULT_HANG_SECONDS", "0.02")
    model, params = _toy()
    srv = DecodeServer(model, params, seq_ladder=[16], max_new_tokens=32,
                       window=2, page_size=8, pool_pages=16)
    free0 = srv._pool.stats()["free"]
    # the kv_evict raise entry fires on EVERY page reclaim (counted,
    # survived) — the proof the dead request's pages went back through
    # the reclaim path, page by page
    fault.set_plan("serve_decode:step=1:hang:count=inf;"
                   "kv_evict:step=1:raise:count=inf")
    try:
        req = srv.submit(np.arange(1, 10), max_new_tokens=32,
                         deadline_ms=120)
        with pytest.raises(RequestTimeoutError, match=req.request_id):
            req.result(timeout=30)
        deadline = time.monotonic() + 30
        while srv._pool.stats()["free"] != free0:
            assert time.monotonic() < deadline, "pages leaked"
            time.sleep(0.01)
        st = srv.stats()
        assert st["timeouts"] == 1
        assert st["decode_faults"] >= 1
        inj = fault.stats()["injected"]
        assert inj.get("serve_decode", 0) >= 1
        assert inj.get("kv_evict", 0) == srv._pool.stats()["evicted"]
        assert inj["kv_evict"] >= 2               # the prompt's pages
    finally:
        fault.set_plan(None)
        srv.stop(drain=False)


# ---------------------------------------------------------------------------
# priorities: admission shedding + KV-pool preemption
# ---------------------------------------------------------------------------

def test_decode_priority_shed_lowest_first():
    model, params = _toy()
    srv = DecodeServer(model, params, seq_ladder=[16], max_new_tokens=4,
                       window=1, page_size=8, pool_pages=16,
                       max_queue=2, start=False)
    try:
        low = [srv.submit(np.arange(1, 4), priority=0)
               for _ in range(2)]
        high = srv.submit(np.arange(1, 4), priority=2)
        # the NEWEST lowest-class member was displaced, not the arrival
        assert low[1].done()
        with pytest.raises(ServerOverloadedError,
                           match=r"priority 0.*priority-2"):
            low[1].result(timeout=1)
        # a second high submit finds only priority-0 low[0] below it
        high2 = srv.submit(np.arange(1, 4), priority=1)
        assert low[0].done()
        # an arrival with nothing below it sheds itself
        with pytest.raises(ServerOverloadedError, match="priority 0"):
            srv.submit(np.arange(1, 4), priority=0)
        st = srv.stats()
        assert st["shed"] == 3
        assert st["shed_by_priority"] == {"0": 3}
        with pytest.raises(mx.base.MXNetError,
                           match="MXNET_SERVING_PRIORITIES"):
            srv.submit(np.arange(1, 4), priority=99)
        _drain(srv, high, high2)
        assert len(high.result(timeout=1)) == 4
    finally:
        srv.stop()


def test_inference_server_priority_shed(tmp_path, monkeypatch):
    """The base one-shot server's bounded queue sheds lowest-priority
    first too, and the victim's error names both priorities."""
    monkeypatch.setenv("MXNET_FAULT_HANG_SECONDS", "0.01")
    d = mx.sym.var("data")
    out = mx.sym.FullyConnected(d, name="fc", num_hidden=3)
    params = {"fc_weight": mx.nd.ones((3, 4)), "fc_bias":
              mx.nd.zeros((3,))}
    path = str(tmp_path / "m.mxp")
    mx.deploy.export_compiled(out, path, params=params,
                              input_shapes={"data": (1, 4)},
                              batch_sizes=[2])
    srv = serving.InferenceServer(path, max_queue=2,
                                  batch_window_ms=0.0)
    fault.set_plan("serve_dispatch:step=1:hang:count=inf")
    try:
        x = np.zeros((4,), np.float32)
        f_low = srv.submit(x, priority=0)
        f_mid = srv.submit(x, priority=1)
        f_high = srv.submit(x, priority=2)     # displaces f_low
        assert f_low.done()
        with pytest.raises(ServerOverloadedError,
                           match=r"priority 0.*priority-2 arrival"):
            f_low.result(timeout=1)
        with pytest.raises(ServerOverloadedError, match="priority 0"):
            srv.submit(x, priority=0)          # nothing below: sheds
        st = srv.stats()
        assert st["shed"] == 2
        assert st["shed_by_priority"] == {"0": 2}
        assert st["queue_depth"] <= 2
        assert not f_mid.done() and not f_high.done()
    finally:
        fault.set_plan(None)
        srv.stop(drain=False)


def test_kv_pool_pressure_preempts_lowest_priority():
    model, params = _toy()
    # pool sized so two max-budget requests cannot coexist: max
    # context 16+8=24 -> 3 pages each; 5 usable pages total
    srv = DecodeServer(model, params, seq_ladder=[16], max_new_tokens=8,
                       window=2, page_size=8, pool_pages=6,
                       start=False)
    try:
        low = srv.submit(np.arange(1, 16), priority=0,
                         max_new_tokens=8)
        srv._tick()                            # low prefills: 2 pages
        assert low.pages == [1, 2]
        high = srv.submit(np.arange(1, 16), priority=2,
                          max_new_tokens=8)
        _drain(srv, high)
        # low was evicted to make room; high completed unharmed
        assert low.done() and low.state == "failed"
        with pytest.raises(ServerOverloadedError, match="preempted"):
            low.result(timeout=1)
        assert len(high.result(timeout=1)) == 8
        st = srv.stats()
        assert st["preempted"] == 1 and st["completed"] == 1
        assert srv._pool.stats()["free"] == 5  # everything reclaimed
    finally:
        srv.stop()


# ---------------------------------------------------------------------------
# weight hot-swap
# ---------------------------------------------------------------------------

def test_hot_swap_mid_traffic_zero_drops():
    """In-flight requests finish on the weights they started with,
    later requests use the new ones, and nothing drops."""
    model, params_a = _toy(seed=3)
    params_b = model.init_params(seed=99)
    prompt = np.arange(1, 8)
    ref_a = _reference(model, params_a, prompt, 8)
    ref_b = _reference(model, params_b, prompt, 8)
    assert ref_a != ref_b                      # the swap is observable
    srv = DecodeServer(model, params_a, seq_ladder=[16],
                       max_new_tokens=8, window=4, page_size=8,
                       pool_pages=32, start=False)
    try:
        inflight = srv.submit(prompt, max_new_tokens=8)
        srv._tick()                            # prefill on A
        srv._tick()                            # decoding on A
        assert not inflight.done()
        v = srv.swap_weights(params_b)
        assert v == 2
        later = srv.submit(prompt, max_new_tokens=8)
        assert srv.stats()["versions_alive"] == 2
        _drain(srv, inflight, later)
        assert [int(t) for t in inflight.result(timeout=1)] == ref_a
        assert [int(t) for t in later.result(timeout=1)] == ref_b
        st = srv.stats()
        assert st["completed"] == 2 and st["errors"] == 0
        assert st["swaps"] == 1 and st["weight_version"] == 2
        assert st["versions_alive"] == 1       # old generation drained
    finally:
        srv.stop()


def test_hot_swap_from_checkpoint_manifest(tmp_path):
    from mxnet_tpu import checkpoint
    model, params_a = _toy(seed=3)
    params_b = model.init_params(seed=7)
    prompt = np.arange(1, 6)
    ref_b = _reference(model, params_b, prompt, 6)
    prefix = str(tmp_path / "lm")
    flat = checkpoint.snapshot_params(
        {k: np.asarray(v) for k, v in params_b.items()})
    checkpoint.save_arrays(prefix, 0, flat)
    srv = DecodeServer(model, params_a, seq_ladder=[16],
                       max_new_tokens=8, window=2, page_size=8,
                       pool_pages=16, start=False)
    try:
        srv.swap_weights(prefix=prefix, epoch=0)
        req = srv.submit(prompt, max_new_tokens=6)
        _drain(srv, req)
        assert [int(t) for t in req.result(timeout=1)] == ref_b
    finally:
        srv.stop()


def test_swap_rejects_mismatched_tree():
    model, params = _toy()
    srv = DecodeServer(model, params, seq_ladder=[16], max_new_tokens=4,
                       window=1, page_size=8, pool_pages=16,
                       start=False)
    try:
        bad = dict(params)
        bad.pop("wout")
        with pytest.raises(mx.base.MXNetError, match="structure"):
            srv.swap_weights(bad)
        bad = dict(params)
        bad["wout"] = np.zeros((3, 3), np.float32)
        with pytest.raises(mx.base.MXNetError, match="never recompile"):
            srv.swap_weights(bad)
        with pytest.raises(mx.base.MXNetError, match="exactly one"):
            srv.swap_weights(params, prefix="x")
    finally:
        srv.stop()


# ---------------------------------------------------------------------------
# telemetry / diagnose / metrics
# ---------------------------------------------------------------------------

def test_decode_telemetry_records_and_diagnose_table(tmp_path):
    sink = str(tmp_path / "run.jsonl")
    telemetry.start(filename=sink, run_id="decode-test")
    model, params = _toy()
    srv = DecodeServer(model, params, seq_ladder=[16], max_new_tokens=6,
                       window=2, page_size=8, pool_pages=16,
                       record_every=2, name="lm")
    rs = np.random.RandomState(0)
    for _ in range(3):
        srv.submit(rs.randint(1, 32, size=5),
                   max_new_tokens=4).result(timeout=30)
    srv.stop()                                # final record
    telemetry.stop()
    recs = [json.loads(l) for l in open(sink) if l.strip()]
    dec = [r for r in recs if r.get("type") == "decode"]
    assert dec, "no decode records in the sink"
    last = dec[-1]
    assert last["name"] == "lm"
    assert last["completed"] == 3 and last["tokens_out"] == 12
    # every prompt rode a decode step: no prefill program ran
    assert last["prefill_steps"] == 0 == last["prefill_programs"]
    assert last["chunk_steps"] == 3 and last["chunk_tokens"] == 15
    assert last["kv"]["evicted"] >= 3
    summary = [r for r in recs if r.get("type") == "summary"][-1]
    assert summary["decode"]["lm"]["completed"] == 3
    out = subprocess.run(
        [sys.executable, "-m", "mxnet_tpu.tools.diagnose", sink],
        capture_output=True, text=True,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert out.returncode == 0, out.stderr
    assert "----------Decode----------" in out.stdout
    assert "tokens" in out.stdout and "kv pool" in out.stdout


def test_decode_metrics_gauges():
    from mxnet_tpu import livemetrics
    model, params = _toy()
    srv = DecodeServer(model, params, seq_ladder=[16], max_new_tokens=4,
                       window=2, page_size=8, pool_pages=16,
                       name="gauges")
    try:
        srv.submit(np.arange(1, 5), max_new_tokens=4).result(timeout=30)
        page = livemetrics.render()
        assert 'mxnet_decode_tokens_out_total{server="gauges"} 4' \
            in page
        assert 'mxnet_decode_completed_total{server="gauges"} 1' \
            in page
        assert 'mxnet_decode_kv_pages{server="gauges"}' in page
        assert 'mxnet_decode_weight_version{server="gauges"} 1' in page
        # the host's slack: three steps were read back and waited for
        wait, = (line for line in page.splitlines() if line.startswith(
            'mxnet_decode_readback_wait_seconds_total{server="gauges"}'))
        assert float(wait.split()[-1]) > 0
    finally:
        srv.stop()
    # a stopped server leaves the scrape
    assert 'server="gauges"' not in livemetrics.render()


def test_flash_decode_matches_full_attention_rows():
    """The query-length-1 cached-KV kernel agrees with the full causal
    forward at every position on both paths. Same math and block
    order, but two differently-shaped programs (a (1, bk) score row vs
    one row of a (bq, bk) tile), so XLA's reduction trees may differ in
    the last ulps: a few-ulp fp32 tolerance, far tighter than any
    lower-precision compute would pass."""
    import jax.numpy as jnp
    from mxnet_tpu.parallel.flash_attention import (flash_attention,
                                                    flash_decode)
    rs = np.random.RandomState(0)
    B, T, H, D = 2, 12, 2, 8
    q, k, v = (jnp.asarray(rs.randn(B, T, H, D).astype(np.float32))
               for _ in range(3))
    full_p = flash_attention(q, k, v, causal=True, force_pallas=True)
    full_j = flash_attention(q, k, v, causal=True)
    Tb = 16                                   # a padded cache bucket
    kc = jnp.zeros((B, Tb, H, D), jnp.float32).at[:, :T].set(k)
    vc = jnp.zeros((B, Tb, H, D), jnp.float32).at[:, :T].set(v)
    for n in (1, 5, 12):
        lens = jnp.full((B,), n, jnp.int32)
        dec_p = flash_decode(q[:, n - 1:n], kc, vc, lens,
                             force_pallas=True)
        dec_j = flash_decode(q[:, n - 1:n], kc, vc, lens)
        np.testing.assert_allclose(np.asarray(dec_p),
                                   np.asarray(full_p[:, n - 1:n]),
                                   rtol=2e-6, atol=2e-7)
        np.testing.assert_allclose(np.asarray(dec_j),
                                   np.asarray(full_j[:, n - 1:n]),
                                   rtol=2e-6, atol=2e-7)
    with pytest.raises(ValueError, match="single query"):
        flash_decode(q[:, :2], kc, vc, jnp.ones((B,), jnp.int32))


# ---------------------------------------------------------------------------
# the paged decode kernel (reads the pool's pages where they lie) against
# the plain reference: gather_pages + insert + _jnp_decode
# ---------------------------------------------------------------------------

_PG = dict(L=2, P=12, S=8, H=2, D=8, M=3)       # pool pages 1..11 usable


def _paged_pools(dtype, seed=0):
    """Random K and V pools of ``dtype`` (and an int8 pool's page
    scales, else None): every page holds finite garbage, the dump
    page too."""
    import jax.numpy as jnp
    g = _PG
    rs = np.random.RandomState(seed)
    shape = (g["L"], g["P"], g["S"], g["H"], g["D"])
    if dtype == "int8":
        k, v = (jnp.asarray(rs.randint(-127, 128, size=shape), jnp.int8)
                for _ in range(2))
        ks, vs = (jnp.asarray(rs.uniform(0.005, 0.03, size=shape[:2]),
                              jnp.float32) for _ in range(2))
        return k, v, ks, vs
    k, v = (jnp.asarray(rs.randn(*shape), dtype) for _ in range(2))
    return k, v, None, None


def _paged_both_paths(dtype, table, positions, layer=1, seed=0):
    """(kernel, reference, v_new) of one layer's decode attention over
    the same pools, table and positions."""
    import jax.numpy as jnp
    from mxnet_tpu.serving import kvcache
    k, v, ks, vs = _paged_pools(dtype, seed)
    rs = np.random.RandomState(seed + 1)
    B = len(positions)
    q, k_new, v_new = (jnp.asarray(rs.randn(B, _PG["H"], _PG["D"]),
                                   jnp.float32) for _ in range(3))
    args = (k, v, jnp.asarray(table, jnp.int32),
            jnp.asarray(positions, jnp.int32), layer, q, k_new, v_new)
    got = kvcache.paged_attention(*args, force_pallas=True, k_scale=ks,
                                  v_scale=vs)
    want = kvcache.paged_attention(*args, k_scale=ks, v_scale=vs)
    assert got.shape == want.shape == (B, _PG["H"], _PG["D"])
    return np.asarray(got), np.asarray(want), np.asarray(v_new)


_POOL_DTYPES = ["float32", "bfloat16", "int8"]
# same values read on both paths, fp32 arithmetic on both: what differs
# is the order of the sums (16 tokens a time against one softmax)
_PAGED_TOL = dict(rtol=2e-5, atol=2e-6)


@pytest.mark.parametrize("dtype", _POOL_DTYPES)
@pytest.mark.parametrize(
    "length", [1, _PG["S"] - 1, _PG["S"], _PG["S"] + 1,
               _PG["M"] * _PG["S"]],
    ids=["len1", "S-1", "S", "S+1", "full_table"])
def test_paged_decode_kernel_matches_gather_reference(length, dtype):
    """Every length around a page boundary, up to the full table: the
    kernel attends ``length - 1`` keys from the pool's pages plus the
    new token, as gather + insert + masked softmax does."""
    table = [[1, 2, 3], [4, 5, 6]]
    got, want, _ = _paged_both_paths(dtype, table, [length - 1] * 2)
    np.testing.assert_allclose(got, want, **_PAGED_TOL)


@pytest.mark.parametrize("dtype", _POOL_DTYPES)
@pytest.mark.parametrize("case", ["mixed_lengths", "inactive_row",
                                  "shared_page"])
def test_paged_decode_kernel_rows_and_tables(case, dtype):
    S = _PG["S"]
    if case == "mixed_lengths":
        # rows of different lengths in one batch, unallocated table
        # tails on the dump page
        table = [[1, 0, 0], [2, 3, 0], [4, 5, 6], [7, 8, 9]]
        positions = [3, S, 3 * S - 1, 2 * S + 1]
    elif case == "inactive_row":
        # an inactive row: all-zero table, position 0 — it attends its
        # own new token and nothing of the dump page
        table = [[0, 0, 0], [1, 2, 0]]
        positions = [0, S + 2]
    else:
        # two rows on one shared (prefix) page, diverging after it
        table = [[1, 2, 0], [1, 3, 4]]
        positions = [S + 3, 2 * S + 5]
    got, want, v_new = _paged_both_paths(dtype, table, positions,
                                         layer=0, seed=3)
    np.testing.assert_allclose(got, want, **_PAGED_TOL)
    if case == "inactive_row":
        alone = v_new[0] if dtype == "int8" else np.asarray(
            v_new[0].astype(dtype), np.float32)
        np.testing.assert_array_equal(got[0], alone)


def test_paged_decode_path_is_counted_and_blocks_fit():
    """``_choose_path`` counts the paged op under its own name, and a
    program instance takes all heads of a page up to 2 MB."""
    import jax.numpy as jnp
    from mxnet_tpu import profiler
    from mxnet_tpu.parallel.flash_attention import _heads_per_block
    before = dict(profiler.counters())
    _paged_both_paths("float32", [[1, 0, 0]], [2])
    after = profiler.counters()
    for path in ("pallas", "jnp"):
        name = "paged_decode_" + path
        assert after[name] == before.get(name, 0) + 1
    assert _heads_per_block(32, 128, 128, jnp.float32) == 32    # 2 MB
    assert _heads_per_block(64, 128, 128, jnp.float32) == 32
    assert _heads_per_block(64, 128, 128, jnp.bfloat16) == 64
    assert _heads_per_block(64, 128, 128, jnp.int8) == 64
    assert _heads_per_block(12, 512, 128, jnp.float32) == 12    # no
    assert _heads_per_block(2, 8, 8, jnp.float32) == 2          # divisor


def test_decode_stats_count_live_pages_of_the_table():
    """``decode_pages_live`` sums ceil((pos + 1) / S) over the rows of
    every step, ``decode_pages_table`` the table the step program is
    compiled for; the dispatch span carries the step's count."""
    model, params = _toy()
    srv = DecodeServer(model, params, seq_ladder=[16], max_new_tokens=8,
                       window=2, page_size=8, pool_pages=16,
                       start=False)
    try:
        req = srv.submit(np.arange(1, 7), max_new_tokens=5)   # 6 tokens
        _drain(srv, req)
        st = srv.stats()
        # the step that carries the prompt writes positions 0..5 (one
        # page), four decode steps positions 6, 7 (one) and 8, 9 (two)
        assert st["decode_steps"] == 5 and st["chunk_steps"] == 1
        assert st["decode_pages_live"] == 1 + 1 + 1 + 2 + 2
        assert st["decode_pages_table"] == 5 * 2 * srv._max_pages
    finally:
        srv.stop()


def test_decode_attention_registered_op():
    import jax.numpy as jnp
    from mxnet_tpu.parallel.flash_attention import _jnp_decode
    rs = np.random.RandomState(1)
    B, T, H, D = 1, 8, 2, 8
    q = jnp.asarray(rs.randn(B, 1, H, D).astype(np.float32))
    kc = jnp.asarray(rs.randn(B, T, H, D).astype(np.float32))
    vc = jnp.asarray(rs.randn(B, T, H, D).astype(np.float32))
    lens = jnp.asarray([5], jnp.int32)
    want = _jnp_decode(q, kc, vc, lens, 1.0 / np.sqrt(D))
    got = mx.nd._contrib_decode_attention(
        mx.nd.array(q), mx.nd.array(kc), mx.nd.array(vc),
        mx.nd.array(np.asarray(lens)))
    np.testing.assert_allclose(np.asarray(got.asnumpy()),
                               np.asarray(want), rtol=1e-6)


# ---------------------------------------------------------------------------
# stop() mid-stream: every outstanding stream terminates with the
# typed error — never a hang — and pages come back counted
# ---------------------------------------------------------------------------

def test_stop_nodrain_midstream_types_out_stream_reclaims_pages():
    """A streaming request whose server is stopped mid-stream must see
    ``tokens()`` end in ServerClosedError after the already-streamed
    prefix — never block forever — with its pages reclaimed through
    the counted kv_evict path."""
    model, params = _toy()
    srv = DecodeServer(model, params, seq_ladder=[16], max_new_tokens=16,
                       window=2, page_size=8, pool_pages=32,
                       start=False)
    req = srv.submit(np.arange(1, 8), max_new_tokens=16)
    for _ in range(5):                       # prefill + a few tokens
        srv._tick()
    assert len(req.generated) >= 1 and not req.done()
    streamed = [int(t) for t in req.generated]
    srv.stop(drain=False)
    got = []
    with pytest.raises(serving.ServerClosedError, match=req.request_id):
        for t in req.tokens(timeout=1):
            got.append(int(t))
    assert got == streamed                   # prefix intact, then typed
    st = srv._pool.stats()
    assert st["used"] == 0 and st["evicted"] >= 1


def test_stop_with_wedged_scheduler_degrades_not_hangs(monkeypatch):
    """stop(drain=True) against a scheduler wedged in a planned
    serve_decode hang must not hang the caller: past
    MXNET_DECODE_STOP_TIMEOUT_MS it degrades to the non-draining path
    and the outstanding stream still fails typed."""
    monkeypatch.setenv("MXNET_FAULT_HANG_SECONDS", "0.4")
    monkeypatch.setenv("MXNET_DECODE_STOP_TIMEOUT_MS", "50")
    model, params = _toy()
    srv = DecodeServer(model, params, seq_ladder=[16], max_new_tokens=8,
                       window=2, page_size=8, pool_pages=16)
    fault.set_plan("serve_decode:step=1:hang:count=inf")
    try:
        req = srv.submit(np.arange(1, 6), max_new_tokens=8)
        deadline = time.monotonic() + 5
        while not fault.stats()["injected"].get("serve_decode"):
            assert time.monotonic() < deadline, "hang never entered"
            time.sleep(0.005)
        t0 = time.monotonic()
        srv.stop()                           # drain=True, but wedged
        assert time.monotonic() - t0 < 0.35  # bounded, not 0.4s hang
        with pytest.raises(serving.ServerClosedError,
                           match=req.request_id):
            req.result(timeout=1)
    finally:
        fault.set_plan(None)
        if srv._thread is not None:          # let the sleeper retire
            srv._thread.join(2)
    assert srv._pool.stats()["used"] == 0    # pages reclaimed anyway


# ---------------------------------------------------------------------------
# one step behind: the next step is dispatched before the last one's
# tokens are read (PR 30). Whatever happens to a row while its step is
# unread, the served tokens are those of a one-row-at-a-time reference:
# none lost, none after the end
# ---------------------------------------------------------------------------

def _cut(tokens, eos):
    """A reference stream as a server with ``eos_id`` serves it."""
    return tokens[:tokens.index(eos) + 1] if eos in tokens else tokens


def _served(req):
    """The request's tokens twice: the future's and the stream's (a
    failed request's stream raises after the tokens that landed)."""
    got = [int(t) for t in req.generated]
    streamed = []
    try:
        for t in req.tokens(timeout=1):
            streamed.append(int(t))
    except Exception as exc:
        assert exc is req._error
    assert streamed == got
    return got


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
