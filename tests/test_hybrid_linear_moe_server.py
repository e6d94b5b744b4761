"""``test_hybrid_linear_moe.py``, continued (a file of its own so that no
file is the floor of a ``--dist loadfile`` run): the state form through
the server — slots, preemption, the loop one step ahead, cancel and a
weight swap, the fixed program set (a slot's second tenant stayed behind:
two cases of a third of this file's seconds). Model, sizes and helpers are
that file's, its autouse
``_clean_state`` among them (imported, it is this file's fixture too)."""
import jax.numpy as jnp
import numpy as np

from mxnet_tpu import compile_watch
from mxnet_tpu.serving import DecodeServer, ServerOverloadedError
from serving_common import drain as _drain, jit_prefill
from test_hybrid_linear_moe import (CFG, _clean_state,      # noqa: F401
                                    _model, _prompts, _serve, _server)


# ---------------------------------------------------------------------------
# the server: slots, tenants, preemption, the loop
# ---------------------------------------------------------------------------

def test_served_streams_are_the_models_own_greedy_streams():
    model, params, _ = _model()
    prompts = _prompts(0, (11, 5, 29, 17, 8, 3))
    streams, st = _serve(model, params, prompts)
    assert st["state"]["rows"] == 4 and st["state"]["rows_live"] == 0
    assert st["state"]["writes"] == 6 == st["prefill_steps"]
    assert st["state"]["bytes"] == 4 * 4 * (4 * 32 * 32 + 3 * 384) * 4
    assert st["kv"]["used"] == 0 and st["decode_steps_ahead"] > 0
    full = jit_prefill(model)
    for prompt, out in zip(prompts, streams):
        seq = np.zeros((1, 48), np.int32)
        seq[0, :len(prompt) + len(out)] = np.concatenate([prompt, out])
        logits = np.asarray(full(params, seq, jnp.asarray(
            [len(prompt) + len(out)]))[0][0])
        assert (logits[len(prompt) - 1:len(prompt) + len(out) - 1]
                .argmax(-1) == np.asarray(out)).all()


def test_preemption_drops_the_state_and_a_second_prefill_rebuilds_it():
    model, params, _ = _model()
    prompts = _prompts(6, (12, 12, 12))
    srv = _server(model, params, pool_pages=5, max_new_tokens=24)
    low = [srv.submit(p, max_new_tokens=24, priority=0)
           for p in prompts[:2]]
    for _ in range(6):
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
        again.append(srv.submit(r.prompt, max_new_tokens=24))
        _drain(srv, again[-1])
    srv.stop()
    for first, r in zip(lost, again):
        alone, _ = _serve(model, params, [r.prompt], n=24)
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
    assert sorted(sites) == ["decode:hyb:prefill:s16",
                             "decode:hyb:prefill:s32", "decode:hyb:step"]
    assert all(s["count"] == 1 for s in sites.values())
    srv.stop()
    by_name = {}
    for sp in spans:
        by_name.setdefault(sp["name"], []).append(sp.get("args") or {})
    assert all(0 <= a["state_slot"] < 2 for a in by_name["decode.prefill"])
    assert len(by_name["decode.prefill"]) == 4
    assert all(1 <= a["state_rows_live"] <= 2
               for a in by_name["decode.dispatch"])
    assert all("state_rows_live" in a and "experts_touched" in a
               for a in by_name["decode.readback"])


def test_a_recurrence_keeps_its_whole_prompt_prefill():
    """Who chunks is observed, not named: the pages beside the state can
    take a chunk (the latent layout's ``chunks``, which the layout around
    it passes on), but this model does not declare ``chunk_lanes`` — a
    chunk of a delta rule is another recurrence from the row's state —
    so its server builds ``_state_prefill_fn`` a rung and NO mixed
    program, runs one prefill a request, and its step's span carries no
    chunk. A model of the same class that did declare it would be given
    the mixed programs: the declaration is the only switch."""
    from mxnet_tpu import tracing
    compile_watch.enable()
    model, params, _ = _model()
    assert not getattr(model, "chunk_lanes", False)
    srv = _server(model, params, seq_ladder=[16, 32], max_new_tokens=6,
                  window=2, pool_pages=16, name="rec")
    assert srv.pool.layout.chunks and srv.pool.layout.pages.chunks
    assert srv._chunk_progs == {} and sorted(srv._prefill_progs) == [16, 32]
    assert all(prog._jitted.__wrapped__.__func__
               is DecodeServer._state_prefill_fn
               for prog in srv._prefill_progs.values())
    st = srv.stats()
    assert st["chunk"] == 0 and st["chunk_sizes"] == []
    tracing.enable()
    try:
        reqs = [srv.submit(p, max_new_tokens=6)
                for p in _prompts(11, (5, 30, 17))]
        _drain(srv, *reqs)
        said = [e.get("args") or {} for e in tracing.export()["traceEvents"]
                if e.get("ph") == "X" and e["name"] == "decode.dispatch"]
    finally:
        tracing.disable()
        tracing.reset()
    st = srv.stats()
    srv.stop()
    assert st["prefill_programs"] == 3 and st["chunk_steps"] == 0 \
        == st["chunk_tokens"]
    assert said and not any("chunk" in a for a in said)
    assert sorted(compile_watch.site_stats("decode:rec")) == [
        "decode:rec:prefill:s16", "decode:rec:prefill:s32",
        "decode:rec:step"]

    class Declares(type(model)):
        chunk_lanes = True

    twin = Declares(**dict(CFG))
    other = _server(twin, params, seq_ladder=[16, 32], window=2,
                    pool_pages=16)
    assert other.stats()["chunk_sizes"] == [16, 32] \
        and other._prefill_progs == {}
    other.stop()
