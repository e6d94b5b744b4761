"""The program's own spans in a ``jax.profiler`` trace: a tiny
``DecodeServer`` and a tiny ``Trainer`` behind ``AsyncInputPipeline`` run
under one profiler session on the CPU backend, the xplane is read back
with ``ProfileData``, and every ``mx:`` span of the two hot paths is
looked for by name, by nesting and by its arguments. Also the counters
the decode scheduler keeps beside them."""
import glob
import os
import threading
import time

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import autograd, gluon, telemetry, tracing
from mxnet_tpu.io.pipeline import AsyncInputPipeline
from mxnet_tpu.serving import DecodeServer, ToyDecoderLM


def _toy():
    model = ToyDecoderLM(vocab=32, n_layers=1, n_heads=2, head_dim=8,
                         max_len=128)
    return model, model.init_params(seed=3)


def _serve():
    model, params = _toy()
    srv = DecodeServer(model, params, seq_ladder=(16, 32),
                       max_new_tokens=8, window=2, page_size=8,
                       pool_pages=32, name="spans")
    try:
        srv.warmup()
        reqs = [srv.submit(np.arange(1, 6 + i, dtype=np.int32),
                           max_new_tokens=4 + i) for i in range(3)]
        for r in reqs:
            assert len(r.result(timeout=60)) == r.max_new
    finally:
        srv.stop()
    return [r.request_id for r in reqs]


def _train():
    import jax
    net = gluon.nn.Dense(4, in_units=6)
    net.initialize()
    # a store of its own, so that the step has a reduce to time
    trainer = gluon.Trainer(net.collect_params(), "sgd",
                            {"learning_rate": 0.1},
                            kvstore=mx.kv.create("local"),
                            update_on_kvstore=False)
    loss_fn = gluon.loss.L2Loss()
    rs = np.random.RandomState(0)

    class Slow(mx.io.NDArrayIter):
        """A source slower than the loop: the consumer finds the queue
        dry, every time."""

        def decode_raw(self, raw):
            time.sleep(0.02)
            return super().decode_raw(raw)

    it = Slow(data=rs.rand(16, 6).astype(np.float32),
              label=rs.rand(16, 4).astype(np.float32), batch_size=4)
    pipe = AsyncInputPipeline(it, num_workers=2,
                              placement=jax.devices("cpu")[0])
    try:
        for batch in pipe:
            with autograd.record():
                loss = loss_fn(net(batch.data[0]), batch.label[0])
            loss.backward()
            trainer.step(4)
    finally:
        pipe.close()


def _mesh_train():
    import jax
    from mxnet_tpu.parallel import DistributedTrainer, create_mesh
    net = gluon.nn.Dense(4, in_units=6)
    net.initialize()
    trainer = DistributedTrainer(
        net, gluon.loss.SoftmaxCrossEntropyLoss(),
        create_mesh({"dp": 2}, jax.devices()[:2]), learning_rate=0.1)
    rs = np.random.RandomState(0)
    for _ in range(2):
        trainer.fit_batch(mx.nd.array(rs.rand(4, 6).astype(np.float32)),
                          mx.nd.array(rs.randint(0, 4, 4)))


@pytest.fixture(scope="module")
def lines(tmp_path_factory):
    """``[[(name, start_ns, end_ns, stats), ...], ...]``: the ``mx:``
    events of every host line of one profiler session over the two hot
    paths, and the request ids the server gave."""
    import jax
    from jax.profiler import ProfileData
    trace_dir = str(tmp_path_factory.mktemp("xplane"))
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(trace_dir, profiler_options=options)
    try:
        rids = _serve()
        _train()
        _mesh_train()
    finally:
        jax.profiler.stop_trace()
    path = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))[-1]
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            evs = [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns,
                    dict(ev.stats)) for ev in line.events
                   if ev.name.startswith("mx:")]
            if evs:
                out.append(evs)
    return out, rids


def _named(lines, name):
    return [(i, ev) for i, line in enumerate(lines) for ev in line
            if ev[0] == name]


# span -> the span it nests in on its own line (None: top of its thread)
PARENT = {
    "mx:decode.tick": None, "mx:decode.wait": None,
    "mx:decode.reap": "mx:decode.tick",
    "mx:decode.admit": "mx:decode.tick",
    "mx:decode.prefill": "mx:decode.admit",
    "mx:decode.pages": "mx:decode.tick",
    "mx:decode.build": "mx:decode.tick",
    "mx:decode.dispatch": "mx:decode.tick",
    "mx:decode.readback": "mx:decode.tick",
    "mx:decode.emit": "mx:decode.tick",
    "mx:decode.record": "mx:decode.tick",
    "mx:trainer.step": None,
    "mx:step.compute": "mx:trainer.step",
    "mx:step.sync": "mx:trainer.step",
    "mx:step.optimizer": "mx:trainer.step",
    "mx:fused_step.dispatch": "mx:step.optimizer",
    "mx:pipeline.decode": None, "mx:pipeline.h2d": None,
    "mx:pipeline.wait": None,
}


@pytest.mark.parametrize("name", sorted(PARENT))
def test_span_is_in_the_profile_inside_its_parent(lines, name):
    lines, _ = lines
    found = _named(lines, name)
    assert found, "no %s in the profile" % name
    parent = PARENT[name]
    if parent is None:
        return
    for i, (_, s, e, _) in found:
        assert any(n == parent and ps <= s and e <= pe
                   for n, ps, pe, _ in lines[i]), \
            "%s at %d lies in no %s of its line" % (name, s, parent)


def test_the_two_loops_and_the_pipeline_run_on_lines_of_their_own(lines):
    lines, _ = lines
    where = {name: {i for i, _ in _named(lines, name)}
             for name in ("mx:decode.tick", "mx:trainer.step",
                          "mx:pipeline.h2d", "mx:pipeline.decode")}
    assert len(where["mx:decode.tick"]) == 1
    assert not where["mx:decode.tick"] & where["mx:trainer.step"]
    assert not where["mx:pipeline.h2d"] & where["mx:trainer.step"]
    assert not where["mx:pipeline.h2d"] & where["mx:pipeline.decode"]


def test_admit_carries_the_request_and_its_queue_wait(lines):
    lines, rids = lines
    admits = [ev[3] for _, ev in _named(lines, "mx:decode.admit")]
    assert {a["request_id"] for a in admits} == set(rids)
    for a in admits:
        assert a["queue_wait_us"] >= 0 and a["cached"] == 0
        assert a["rung"] == 16 and 5 <= a["prompt_len"] <= 7


def test_root_spans_lie_in_no_other_and_the_tick_says_its_load(lines):
    lines, _ = lines
    for name in ("mx:decode.tick", "mx:trainer.step", "mx:decode.wait"):
        roots = _named(lines, name)
        assert len(roots) >= 2
        for i, (_, s, e, _) in roots:
            assert not any(n.startswith("mx:") and ps <= s and e <= pe
                           and (n, ps, pe) != (name, s, e)
                           for n, ps, pe, _ in lines[i]), name
    ticks = [ev[3] for _, ev in _named(lines, "mx:decode.tick")]
    assert all(set(t) == {"active", "queued"} for t in ticks)


def test_emit_counts_rows_and_tokens(lines):
    lines, _ = lines
    emits = [ev[3] for _, ev in _named(lines, "mx:decode.emit")]
    assert emits and all(1 <= e["emitted"] <= e["rows"] <= 2
                         for e in emits)
    # three requests of 4, 5, 6 tokens; prefill emits the first of each
    assert sum(e["emitted"] for e in emits) == 4 + 5 + 6 - 3


def test_dispatch_carries_the_steps_live_pages(lines):
    lines, _ = lines
    steps = [ev[3] for _, ev in _named(lines, "mx:decode.dispatch")]
    assert steps and all(1 <= s["pages_live"] <= 2 * 2 for s in steps)
    # prompts of 5, 6, 7 tokens decode at positions 5..7, 6..9, 7..11:
    # ceil((pos + 1) / 8) pages each, however the rows shared steps
    assert sum(s["pages_live"] for s in steps) == 3 + 6 + 9


def test_h2d_carries_bytes_and_the_array_name(lines):
    lines, _ = lines
    h2d = [ev[3] for _, ev in _named(lines, "mx:pipeline.h2d")]
    assert {h["name"] for h in h2d} == {"data", "softmax_label"}
    assert {h["bytes"] for h in h2d} == {4 * 6 * 4, 4 * 4 * 4}


def test_no_session_no_ring_nothing_recorded():
    """With no profiler session and the ring off the two paths leave no
    annotation, no ring and no telemetry run behind."""
    from jax.profiler import TraceAnnotation
    telemetry.reset()
    tracing.reset()
    assert not TraceAnnotation.is_enabled()
    seen = []
    real = tracing._Annotation
    tracing._Annotation = type(
        "Spy", (), {"is_enabled": staticmethod(real.is_enabled),
                    "__init__": lambda self, *a, **k: seen.append(a)})
    try:
        _serve()
    finally:
        tracing._Annotation = real
    assert not seen and tracing.stats() is None


# --- the decode scheduler's counters ---------------------------------------

def _drain(srv, *reqs):
    n = 0
    while not all(r.done() for r in reqs):
        srv._tick()
        n += 1
        assert n < 500, "scheduler made no progress"


@pytest.fixture()
def scripted(monkeypatch):
    """An unstarted server driven tick by tick on a scripted clock: every
    read of the tracer's clock advances it by exactly one second."""
    clock = iter(range(1, 100000))
    monkeypatch.setattr(tracing, "now", lambda: float(next(clock)))
    model, params = _toy()
    srv = DecodeServer(model, params, seq_ladder=(16,), max_new_tokens=4,
                       window=2, page_size=8, pool_pages=32, start=False,
                       name="scripted")
    yield srv
    srv.stop(drain=False)


COUNTERS = ("admitted", "queue_wait_s", "prefill_s")


@pytest.mark.parametrize("key", COUNTERS)
def test_counter_starts_at_zero_and_never_falls(scripted, key):
    srv = scripted
    seen = [srv.stats()[key]]
    assert seen[0] == 0
    reqs = [srv.submit(np.arange(1, 6, dtype=np.int32), max_new_tokens=3)
            for _ in range(3)]
    while not all(r.done() for r in reqs):
        srv._tick()
        seen.append(srv.stats()[key])
    assert seen == sorted(seen) and seen[-1] > 0


def test_counters_are_exact_on_a_scripted_clock(scripted):
    srv = scripted
    waits, prefills = [], []
    real_span = tracing.span

    def spying(name, /, *a, **k):
        sp = real_span(name, *a, **k)
        if name == "decode.admit":
            waits.append(sp)
        elif name == "decode.prefill":
            prefills.append(sp)
        return sp

    tracing.span = spying
    try:
        reqs = [srv.submit(np.arange(1, 6, dtype=np.int32),
                           max_new_tokens=3) for _ in range(3)]
        _drain(srv, *reqs)
    finally:
        tracing.span = real_span
    st = srv.stats()
    assert st["admitted"] == 3 == st["prefill_steps"] == len(prefills)
    # admit minus submit, summed, from the admit span's own stamp
    by_id = {r.request_id: r for r in reqs}
    admitted = [sp for sp in waits
                if by_id[sp.args["request_id"]].state != "queued"]
    # every admission of this run succeeds at its first try
    assert len(waits) == 3
    assert st["queue_wait_s"] == sum(
        sp.t0 - by_id[sp.args["request_id"]].t_submit for sp in admitted)
    assert st["prefill_s"] == sum(sp.t1 - sp.t0 for sp in prefills)
    # on a clock of whole seconds both are whole and at least one a span
    assert st["prefill_s"] == int(st["prefill_s"]) >= 3
    assert st["queue_wait_s"] == int(st["queue_wait_s"]) >= 3
    # the latency rings read the same clock as the counters
    ring = list(srv._ttft)
    assert len(ring) == 3 and all(ms >= 1e3 and ms % 1e3 == 0
                                  for ms in ring)


def test_counters_survive_threads_asking(scripted):
    """``stats()`` from other threads while the scheduler admits: the
    counters are updated under the lock ``stats()`` takes."""
    srv = scripted
    stop = threading.Event()
    bad = []

    def ask():
        last = 0
        while not stop.is_set():
            n = srv.stats()["admitted"]
            if n < last:
                bad.append((last, n))
            last = n

    threads = [threading.Thread(target=ask) for _ in range(4)]
    for t in threads:
        t.start()
    try:
        reqs = [srv.submit(np.arange(1, 6, dtype=np.int32),
                           max_new_tokens=2) for _ in range(6)]
        _drain(srv, *reqs)
    finally:
        stop.set()
        for t in threads:
            t.join(timeout=30)
    assert not bad and not any(t.is_alive() for t in threads)
    assert srv.stats()["admitted"] == 6
