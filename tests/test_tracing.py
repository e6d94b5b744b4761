"""End-to-end tracing (mxnet_tpu.tracing): serving request traces
whose spans nest causally, training step traces with off-thread work
parented by explicit context tokens, Chrome trace-event JSON export,
and the always-cheap-when-off contract (zero-allocation span, sink
byte-identity)."""
import json

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import (checkpoint, compile_watch, fault, telemetry,
                       tracing)
from mxnet_tpu.serving import InferenceServer


@pytest.fixture(autouse=True)
def _clean_state():
    fault.reset()
    telemetry.reset()
    tracing.reset()
    compile_watch.disable()
    yield
    fault.reset()
    telemetry.reset()
    tracing.reset()
    compile_watch.disable()


def _events(name=None, cat=None, ph=None):
    evs = tracing.export()["traceEvents"]
    return [e for e in evs
            if (name is None or e["name"] == name)
            and (cat is None or e.get("cat") == cat)
            and (ph is None or e["ph"] == ph)]


# ---------------------------------------------------------------------------
# off-path contract
# ---------------------------------------------------------------------------

def test_off_by_default_zero_allocation_span():
    assert not tracing.enabled()
    # with no profiler session, no ring and no telemetry run a span is
    # its own small object and two stamps: no annotation, no phase
    # claim, no ring event, nothing kept once it is dropped
    import sys
    with tracing.span("a", phase="compute", k=1) as sp:
        assert sp._ann is None and sp._claim is None
    assert sp.t1 >= sp.t0
    assert sys.getrefcount(sp) == 2          # ours and the call's
    assert tracing.stats() is None and not telemetry.enabled()
    # every other hook is a None-check no-op
    assert tracing.track("x") is None
    assert tracing.context() is None
    assert tracing.stats() is None
    tracing.add("n", "c", 0.0, 1.0)          # silently dropped
    tracing.instant("n", "c")
    with pytest.raises(RuntimeError):
        tracing.export()


def test_all_off_keeps_sink_byte_identical(tmp_path):
    """With tracing, metrics, and the watchdog all off, the JSONL
    sink carries exactly the pre-PR record kinds — no alert/trace
    spillover."""
    sink = str(tmp_path / "run.jsonl")
    telemetry.start(filename=sink)
    telemetry.step_begin()
    with telemetry.span("compute"):
        pass
    telemetry.step_end(samples=2)
    summary = telemetry.stop()
    assert "alerts" not in summary
    with open(sink) as f:
        kinds = {json.loads(line)["type"] for line in f}
    assert kinds <= {"run_start", "step", "memory", "summary"}
    assert not tracing.enabled()


# ---------------------------------------------------------------------------
# serving request traces
# ---------------------------------------------------------------------------

def _contains(parent, child, tol=2.0):
    """Time containment in exported us, with float-rounding slack."""
    return child["ts"] >= parent["ts"] - tol and \
        child["ts"] + child["dur"] <= \
        parent["ts"] + parent["dur"] + tol


def test_serving_request_spans_nest_causally():
    tracing.enable()

    def model(x):
        return x * 2.0

    srv = InferenceServer(model, max_batch=4, max_queue=32,
                          batch_window_ms=1.0)
    try:
        futs = [srv.submit(np.full((3,), i, np.float32))
                for i in range(6)]
        for f in futs:
            assert f.request_id is not None
            f.result(timeout=30)
    finally:
        srv.stop()

    # group this request's spans off its own named track
    rid = futs[0].request_id
    by_req = [e for e in _events(ph="X")
              if (e.get("args") or {}).get("request_id") == rid]
    names = {e["name"] for e in by_req}
    assert names == {"request", "queue", "batch", "dispatch", "pad",
                     "compute", "respond"}, names
    req = next(e for e in by_req if e["name"] == "request")
    children = sorted((e for e in by_req if e["name"] != "request"),
                      key=lambda e: e["ts"])
    # causal nesting: every child inside the request span, and the
    # lifecycle phases are consecutive — no wrong overlap
    for c in children:
        assert _contains(req, c), (req, c)
    order = [c["name"] for c in children]
    assert order == ["queue", "batch", "dispatch", "pad", "compute",
                     "respond"]
    for prev, nxt in zip(children, children[1:]):
        assert nxt["ts"] >= prev["ts"] + prev["dur"] - 2.0, (prev, nxt)
    # all on one track: the request's own tid
    assert len({c["tid"] for c in by_req}) == 1
    # the track is named after the request id (Perfetto metadata)
    metas = [e for e in tracing.export()["traceEvents"]
             if e["ph"] == "M" and
             e["args"]["name"] == "req %s" % rid]
    assert len(metas) == 1


def test_exported_chrome_json_validates(tmp_path):
    tracing.enable()
    srv = InferenceServer(lambda x: x + 1.0, max_batch=2, max_queue=8,
                          batch_window_ms=0.0)
    try:
        srv.submit(np.zeros((2,), np.float32)).result(timeout=30)
    finally:
        srv.stop()
    path = str(tmp_path / "trace.json")
    assert tracing.export(path) == path
    with open(path) as f:
        trace = json.load(f)
    assert isinstance(trace["traceEvents"], list) and trace["traceEvents"]
    for e in trace["traceEvents"]:
        assert "name" in e and "ph" in e and "pid" in e and "tid" in e
        if e["ph"] == "X":
            assert e["dur"] >= 0 and e["ts"] >= 0
        if e["ph"] in ("X", "i"):
            assert "cat" in e
    assert trace["displayTimeUnit"] == "ms"


def test_shed_and_timeout_are_joinable_against_traces(tmp_path,
                                                      monkeypatch):
    """request_id rides the shed/timeout error messages AND the trace
    events, so log lines join against the exported trace."""
    monkeypatch.setenv("MXNET_FAULT_HANG_SECONDS", "0.01")
    tracing.enable()
    srv = InferenceServer(lambda x: x, max_batch=2, max_queue=2,
                          batch_window_ms=0.0)
    # finite count: the ≥30 ms stall outlives the 1 ms deadlines and
    # the plan exhausts on its own (clearing it would race the
    # batcher's first pass)
    fault.set_plan("serve_dispatch:step=1:hang:count=3")
    try:
        x = np.zeros((2,), np.float32)
        futs = [srv.submit(x, deadline_ms=1) for _ in range(2)]
        with pytest.raises(mx.serving.ServerOverloadedError) as exc:
            srv.submit(x)
        assert "r000003 (priority 0) shed" in str(exc.value)   # id in line
        for f in futs:
            with pytest.raises(mx.serving.RequestTimeoutError) as texc:
                f.result(timeout=30)
            assert f.request_id in str(texc.value)
    finally:
        fault.set_plan(None)
        srv.stop(drain=False)
    shed_events = _events(name="shed", ph="i")
    assert len(shed_events) == 1
    timeout_events = _events(name="timeout", ph="i")
    assert {(e["args"] or {})["request_id"] for e in timeout_events} \
        == {f.request_id for f in futs}


# ---------------------------------------------------------------------------
# training step traces + off-thread parents
# ---------------------------------------------------------------------------

def test_step_trace_nests_phases_and_ckpt_parented_by_context(tmp_path):
    tracing.enable()
    telemetry.start()
    mgr = checkpoint.CheckpointManager(str(tmp_path / "ck"),
                                       async_=True)
    for i in range(3):
        telemetry.step_begin()
        with telemetry.span("compute"):
            pass
        if i == 1:
            # triggered mid-step 2: the writer runs on its own thread
            # but the span must parent to step 2 via the token
            mgr.save(7, {"w": mx.nd.ones((4,))})
        telemetry.step_end(samples=1)
    mgr.close()
    telemetry.stop()

    steps = _events(name="step", ph="X")
    assert len(steps) == 3
    assert [e["args"]["seq"] for e in steps] == [1, 2, 3]
    phases = _events(name="compute", cat="phase")
    assert len(phases) == 3
    for ph, st in zip(sorted(phases, key=lambda e: e["ts"]), steps):
        assert ph["args"]["step"] == st["args"]["seq"]
        assert _contains(st, ph)
        assert ph["tid"] == st["tid"]      # same (accounting) track
    cks = _events(cat="checkpoint", ph="X")
    assert len(cks) == 1
    assert cks[0]["name"] == "ckpt:epoch0007"
    assert cks[0]["args"]["step"] == 2      # explicit context token
    assert cks[0]["args"]["ok"] is True
    # the writer's span lives on the named checkpoint track, NOT the
    # accounting thread's
    assert cks[0]["tid"] != steps[0]["tid"]


def test_pipeline_decode_and_h2d_events_carry_context():
    import jax

    from mxnet_tpu.io.pipeline import AsyncInputPipeline
    tracing.enable()
    telemetry.start()
    rs = np.random.RandomState(0)
    it = mx.io.NDArrayIter(data=rs.rand(16, 4).astype(np.float32),
                           batch_size=4)
    pipe = AsyncInputPipeline(it, num_workers=2,
                              placement=jax.devices("cpu")[0])
    try:
        telemetry.step_begin()
        n = 0
        for _ in pipe:
            n += 1
        telemetry.step_end()
        assert n == 4
    finally:
        pipe.close()
    telemetry.stop()
    decodes = _events(name="pipeline.decode", cat="io")
    assert len(decodes) == 4
    h2ds = _events(name="pipeline.h2d", cat="io")
    assert h2ds and all(e["args"]["bytes"] > 0 for e in h2ds)
    assert {e["args"]["name"] for e in h2ds} >= {"data"}
    # both are parented to a step by the explicit token: the one open
    # when the work was triggered (1), or the next once it had closed
    assert all(e["args"]["step"] in (1, 2) for e in decodes + h2ds)
    # decode/h2d tracks are their own (named) synthetic tracks
    meta_names = {e["args"]["name"]
                  for e in tracing.export()["traceEvents"]
                  if e["ph"] == "M"}
    assert {"io:decode", "io:h2d"} <= meta_names


def test_compile_events_land_on_compile_track():
    import jax.numpy as jnp
    compile_watch.enable()
    tracing.enable()
    fn = compile_watch.jit(lambda x: x * 2, "tracetest:mul")
    fn(jnp.ones((3,)))
    compiles = [e for e in _events(cat="compile", ph="X")
                if e["name"] == "compile:tracetest:mul"]
    assert len(compiles) == 1
    assert compiles[0]["args"]["cause"] == "first_compile"


def test_ring_bound_drops_oldest(monkeypatch):
    monkeypatch.setenv("MXNET_TRACE_RING", "16")
    tracing.enable()
    for i in range(50):
        tracing.instant("e%d" % i, "t")
    st = tracing.stats()
    assert st["events"] == 16
    assert st["dropped"] == 34
    names = [e["name"] for e in tracing.export()["traceEvents"]]
    assert names[-1] == "e49"          # newest kept, oldest dropped


def test_track_table_bounded_newest_labels_win(monkeypatch):
    """A long-lived traced server mints one track per request — the
    label table is bounded with FIFO eviction, so like the event ring
    the NEWEST labels keep their names (a recent request must never
    lose its track name to one whose events already rotated out)."""
    monkeypatch.setenv("MXNET_TRACE_TRACKS", "16")
    tracing.enable()
    tids = [tracing.track("req r%06d" % i) for i in range(40)]
    assert len(set(tids)) == 40               # every track distinct
    assert tracing.stats()["tracks"] == 16    # table stays bounded
    metas = [e["args"]["name"] for e in tracing.export()["traceEvents"]
             if e["ph"] == "M"]
    assert metas == ["req r%06d" % i for i in range(24, 40)]
    # a re-used recent label resolves to its existing tid
    assert tracing.track("req r000039") == tids[39]
