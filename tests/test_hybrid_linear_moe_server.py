"""``test_hybrid_linear_moe.py``, continued (a file of its own so that no
file is the floor of a ``--dist loadfile`` run): the state form through
the server — slots, a prompt's chunks on the step's lanes, preemption,
the loop one step ahead, cancel and a weight swap, the fixed program set
(a slot's second tenant stayed behind: two cases of a third of this
file's seconds). Model, sizes and helpers are that file's, its autouse
``_clean_state`` among them (imported, it is this file's fixture too)."""
import jax.numpy as jnp
import numpy as np
import pytest

from mxnet_tpu import compile_watch
from mxnet_tpu.serving import DecodeServer, ServerOverloadedError
from mxnet_tpu.serving.hybrid_linear_moe import HybridLinearMoEDecoderLM
from serving_common import drain as _drain, jit_prefill
from test_hybrid_linear_moe import (CFG, _clean_state,      # noqa: F401
                                    _model, _prompts, _serve, _server)


class _WholePrompt(HybridLinearMoEDecoderLM):
    """The same model, not declaring that its state takes a chunk: its
    server keeps the whole-prompt prefill (the oracle of the chunks)."""
    chunk_lanes = False


def _is_greedy(model, params, prompt, out, width=128):
    """Whether ``out`` is the model's own greedy stream after ``prompt``:
    one teacher-forced whole-sequence forward over both (the prefill
    path: the chunkwise rule from zeros, the flash kernel), padded to a
    fixed width — position ``P - 1 + i`` puts served token ``i`` first."""
    n = len(prompt) + len(out)
    seq = np.zeros((1, width), np.int32)
    seq[0, :n] = np.concatenate([prompt, out])
    logits = np.asarray(jit_prefill(model)(params, seq,
                                           jnp.asarray([n]))[0][0])
    return [int(t) for t in logits[len(prompt) - 1:n - 1].argmax(-1)] \
        == [int(t) for t in out]


# ---------------------------------------------------------------------------
# the server: slots, tenants, preemption, the loop
# ---------------------------------------------------------------------------

def test_served_streams_are_the_models_own_greedy_streams():
    model, params, _ = _model()
    prompts = _prompts(0, (11, 5, 29, 17, 8, 3))
    streams, st = _serve(model, params, prompts)
    assert st["state"]["rows"] == 4 and st["state"]["rows_live"] == 0
    # every prompt one chunk of the ladder's one rung, no prefill program
    assert st["state"]["writes"] == 6 == st["chunk_steps"]
    assert st["prefill_steps"] == 0 == st["prefill_programs"]
    assert st["chunk_tokens"] == sum(len(p) for p in prompts)
    assert st["state"]["bytes"] == 4 * 4 * (4 * 32 * 32 + 3 * 384) * 4
    assert st["kv"]["used"] == 0 and st["decode_steps_ahead"] > 0
    for prompt, out in zip(prompts, streams):
        assert _is_greedy(model, params, prompt, out, width=48)


@pytest.mark.parametrize("ladder,chunks", [
    ((16, 32, 96), [16, 32]), ((16, 96), [16])],
    ids=["two_sizes", "one_size"])
def test_prompts_longer_than_a_chunk_stream_the_models_greedy_stream(
        ladder, chunks):
    """Prompts shorter than a chunk, of a chunk, and of two to six of
    the largest, lengths that are and are not multiples of it, more
    requests than rows: the state walks from chunk to chunk through the
    request's row (``start > 0``: the carried ``s`` and ``conv`` rows),
    and every stream is token for token what the SAME model serves
    through the whole-prompt prefill and the step (a server of a twin
    that does not declare ``chunk_lanes``) and the model's own greedy
    stream."""
    sizes = (5, 16, 40, 75, 33, 90, 64)
    prompts = _prompts(12, sizes)
    served = {}
    for cls in (HybridLinearMoEDecoderLM, _WholePrompt):
        model, params = cls(**CFG), _model()[1]
        srv = _server(model, params, seq_ladder=list(ladder), window=3,
                      max_new_tokens=10)
        assert srv.stats()["chunk_sizes"] == \
            (chunks if cls is HybridLinearMoEDecoderLM else [])
        reqs = [srv.submit(p, max_new_tokens=10) for p in prompts]
        _drain(srv, *reqs)
        st = srv.stats()
        srv.stop()
        assert st["completed"] == len(prompts)
        if cls is _WholePrompt:
            assert st["prefill_programs"] == len(prompts)
            assert st["chunk_tokens"] == 0
        else:
            assert st["prefill_programs"] == 0
            assert st["chunk_tokens"] == sum(sizes)
            assert st["chunk_steps"] == sum(
                -(-n // chunks[-1]) for n in sizes)
        served[cls] = [[int(t) for t in r.result()] for r in reqs]
    assert served[HybridLinearMoEDecoderLM] == served[_WholePrompt]
    for p, got in zip(prompts, served[HybridLinearMoEDecoderLM]):
        assert _is_greedy(model, params, p, got)


def test_preemption_drops_the_state_and_its_chunks_rebuild_it():
    """A preempted row loses slot and pages in mid-stream; sent again,
    its prompt (three chunks of 16: a ladder 16 / 48) rebuilds the state
    in whatever slot it is given, from zeros, and the stream is the one
    it streams alone — what it had streamed before was that stream's
    start."""
    model, params, _ = _model()
    prompts = _prompts(6, (44, 44, 44))
    kw = dict(seq_ladder=[16, 48], max_new_tokens=24)
    srv = _server(model, params, pool_pages=9, **kw)
    assert srv.stats()["chunk_sizes"] == [16]
    low = [srv.submit(p, max_new_tokens=24, priority=0)
           for p in prompts[:2]]
    for _ in range(10):
        srv._tick()
    high = srv.submit(prompts[2], max_new_tokens=24, priority=1)
    _drain(srv, high, *low)
    assert srv.stats()["preempted"] >= 1
    lost = [r for r in low if r._error is not None]
    assert lost and all(isinstance(r._error, ServerOverloadedError)
                        for r in lost)
    assert srv.stats()["state"]["rows_live"] == 0
    again = []
    for r in lost:           # one at a time: the pool holds one such row
        before = srv.stats()["chunk_steps"]
        again.append(srv.submit(r.prompt, max_new_tokens=24))
        _drain(srv, again[-1])
        assert srv.stats()["chunk_steps"] - before == 3
    assert srv.stats()["prefill_programs"] == 0
    srv.stop()
    for first, r in zip(lost, again):
        alone, _ = _serve(model, params, [r.prompt], n=24, **kw)
        assert [int(t) for t in r.result()] == alone[0]
        # what the preempted run had streamed was the same stream's start
        assert first.generated \
            and alone[0][:len(first.generated)] == first.generated


def test_one_step_ahead_and_drained_loops_give_the_same_stream():
    model, params, _ = _model()
    prompts = _prompts(7, (9, 20, 4, 15, 27))

    def drain_every_pass(srv):
        srv._drain_ask = "test"

    ahead, st_a = _serve(model, params, prompts, n=16)
    drained, st_d = _serve(model, params, prompts, n=16,
                           each=drain_every_pass)
    assert ahead == drained
    assert st_a["decode_steps_ahead"] > 0.8 * st_a["decode_steps"]
    assert st_d["decode_steps_ahead"] == 0


def test_cancel_and_a_weight_swap_in_mid_stream():
    """A cancelled row's slot comes back; rows on the old weights keep
    their state through a swap and finish the stream they would have
    finished without one."""
    model, params, _ = _model()
    other = model.init_params(seed=11)
    prompts = _prompts(8, (10, 14, 6))
    plain, _ = _serve(model, params, prompts[:2], n=20)
    srv = _server(model, params)
    a, b = (srv.submit(p, max_new_tokens=20) for p in prompts[:2])
    for _ in range(6):
        srv._tick()
    assert srv.stats()["state"]["rows_live"] == 2
    b.cancel()
    srv.swap_weights(other)
    c = srv.submit(prompts[2], max_new_tokens=10)
    _drain(srv, a, b, c)
    st = srv.stats()
    srv.stop()
    assert b.state == "cancelled" and st["swaps"] == 1
    assert st["state"]["rows_live"] == 0 and st["kv"]["used"] == 0
    assert [int(t) for t in a.result()] == plain[0]
    new, _ = _serve(model, other, [prompts[2]], n=10)
    assert [int(t) for t in c.result()] == new[0]


def test_fixed_program_set_and_what_the_spans_say():
    from mxnet_tpu import tracing
    compile_watch.enable()
    model, params, _ = _model()
    srv = _server(model, params, seq_ladder=[16, 32], max_new_tokens=8,
                  window=2, pool_pages=16, name="hyb")
    assert srv.warmup() == 3
    tracing.enable()
    try:
        reqs = [srv.submit(p, max_new_tokens=8)
                for p in _prompts(9, (3, 16, 20, 31))]
        _drain(srv, *reqs)
        spans = [e for e in tracing.export()["traceEvents"]
                 if e.get("ph") == "X"]
    finally:
        tracing.disable()
        tracing.reset()
    sites = compile_watch.site_stats("decode:hyb")
    assert sorted(sites) == ["decode:hyb:step", "decode:hyb:step:chunk:c16",
                             "decode:hyb:step:chunk:c32"]
    assert all(s["count"] == 1 for s in sites.values())
    st = srv.stats()
    srv.stop()
    by_name = {}
    for sp in spans:
        by_name.setdefault(sp["name"], []).append(sp.get("args") or {})
    # no prefill program runs: a prompt is a chunk of a dispatched step,
    # the smallest mixed program that holds it, and its request is no
    # live row of that step (a window of 2: at most one row decodes)
    assert "decode.prefill" not in by_name
    fed = [a for a in by_name["decode.dispatch"] if "chunk" in a]
    assert [(a["chunk"], a["chunk_of"]) for a in fed] \
        == [(len(r.prompt), r.request_id) for r in reqs]
    assert all(0 <= a["state_rows_live"] <= 1 for a in fed)
    assert (st["chunk_steps"], st["chunk_tokens"]) == (4, 3 + 16 + 20 + 31)
    assert all(0 <= a["state_rows_live"] <= 2
               for a in by_name["decode.dispatch"])
    assert any(a["state_rows_live"] == 2 and "chunk" not in a
               for a in by_name["decode.dispatch"])
    assert all("state_rows_live" in a and "experts_touched" in a
               for a in by_name["decode.readback"])


def test_a_recurrence_rides_the_step_in_chunks():
    """Who chunks is observed, not named: the pages beside the state can
    take a chunk (the latent layout's ``chunks``, which the layout around
    it passes on) and this model declares ``chunk_lanes`` — a chunk of a
    delta rule is the same recurrence from the row's state — so its
    server builds ``_state_decode_fn_chunk`` at the rungs within twice
    the smallest and NO prefill program, and a step's span carries the
    chunk. A twin of the same class that does not declare it keeps
    ``_state_prefill_fn`` a rung and no mixed program: the declaration is
    the only switch."""
    from mxnet_tpu import tracing
    compile_watch.enable()
    model, params, _ = _model()
    assert model.chunk_lanes is True
    srv = _server(model, params, seq_ladder=[16, 32], max_new_tokens=6,
                  window=2, pool_pages=16, name="rec")
    assert srv.pool.layout.chunks and srv.pool.layout.pages.chunks
    assert srv._prefill_progs == {} and sorted(srv._chunk_progs) == [16, 32]
    assert all(prog._jitted.__wrapped__.__func__
               is DecodeServer._state_decode_fn_chunk
               for prog in srv._chunk_progs.values())
    st = srv.stats()
    assert st["chunk"] == 32 and st["chunk_sizes"] == [16, 32]
    tracing.enable()
    try:
        reqs = [srv.submit(p, max_new_tokens=6)
                for p in _prompts(11, (5, 30, 17))]
        _drain(srv, *reqs)
        names = {e["name"] for e in tracing.export()["traceEvents"]
                 if e.get("ph") == "X"}
    finally:
        tracing.disable()
        tracing.reset()
    st = srv.stats()
    srv.stop()
    assert st["prefill_programs"] == 0 and st["chunk_steps"] == 3 \
        and st["chunk_tokens"] == 5 + 30 + 17
    assert "decode.dispatch" in names and "decode.prefill" not in names
    assert sorted(compile_watch.site_stats("decode:rec")) == [
        "decode:rec:step", "decode:rec:step:chunk:c16",
        "decode:rec:step:chunk:c32"]

    twin = _WholePrompt(**dict(CFG))
    other = _server(twin, params, seq_ladder=[16, 32], window=2,
                    pool_pages=16)
    assert other.stats()["chunk_sizes"] == [] and other._chunk_progs == {} \
        and sorted(other._prefill_progs) == [16, 32]
    assert all(prog._jitted.__wrapped__.__func__
               is DecodeServer._state_prefill_fn
               for prog in other._prefill_progs.values())
    other.stop()
