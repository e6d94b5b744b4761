"""Stateful autoregressive serving (mxnet_tpu.serving.decode): paged
KV-cache decode over a fixed program set, continuous prefill/decode
batching, streaming with cancellation, priority admission/preemption,
deterministic fault sites, and zero-downtime weight hot-swap.

The load-bearing contract: N tokens produced by prefill + stepwise
cached decode are IDENTICAL to greedy generation by one full-sequence
forward at each length — on the jnp reference attention path AND the
Pallas flash kernels (interpret mode on CPU). The loop that runs one
step behind its read-back, and a prompt riding the step in chunks, are
in ``test_decode_loop.py``."""
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
from serving_common import drain as _drain, greedy_reference as _reference


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


def _one_program_a_length(model, params, prompt, n):
    """What ``serving_common.greedy_reference`` replaced: the sequence
    as long as it is, so a compile a length."""
    import jax
    import jax.numpy as jnp
    toks = [int(t) for t in prompt]
    prefill = jax.jit(model.prefill)
    for _ in range(n):
        logits, _, _ = prefill(params, jnp.asarray([toks], jnp.int32))
        toks.append(int(np.argmax(np.asarray(logits)[0, len(toks) - 1])))
    return toks[len(prompt):]


@pytest.mark.parametrize("use_pallas,plens", [(False, (23, 25)),
                                              (True, (25,))],
                         ids=["jnp", "pallas"])
def test_the_padded_oracle_is_the_one_program_a_length_oracle(use_pallas,
                                                              plens):
    """Padding reaches no live position: at two padded widths (64 and
    32: the answer's length picks it) the oracle's tokens are the
    unpadded form's, for prompts on both sides of a page boundary (24;
    the interpreted kernels' leg, a compile a length too, takes one) —
    and a second call at a (model, width) compiles nothing."""
    import jax
    model, params = _toy(n_layers=1 if use_pallas else 2,
                         use_pallas=use_pallas, seed=11)
    rs = np.random.RandomState(4)
    compiles = []

    def count(event, duration, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            compiles.append(event)

    jax.monitoring.register_event_duration_secs_listener(count)
    try:
        for plen in plens:
            prompt = rs.randint(1, 32, size=plen)
            want = _one_program_a_length(model, params, prompt, 10)
            assert _reference(model, params, prompt, 10) == want      # 64
            assert _reference(model, params, prompt, 6) == want[:6]   # 32
            seen = len(compiles)
            assert _reference(model, params, prompt, 10) == want
            assert _reference(model, params, prompt, 6) == want[:6]
            assert len(compiles) == seen
    finally:
        jax.monitoring.unregister_event_duration_listener(count)


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
