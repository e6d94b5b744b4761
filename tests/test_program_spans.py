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
from serving_common import drain as _drain


class _PrefillLM(ToyDecoderLM):
    """``ToyDecoderLM`` as a model that does not declare
    ``chunk_lanes``: its server keeps the whole-prompt prefill program,
    and with it the ``decode.prefill`` spans and counters that the
    block, speculative and state forms still launch (a model that
    declares them rides the step in chunks:
    ``test_a_chunk_rides_the_dispatch_span``)."""

    chunk_lanes = False


def _toy(model=_PrefillLM):
    model = model(vocab=32, n_layers=1, n_heads=2, head_dim=8,
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
    "mx:decode.prefill.launch": "mx:decode.prefill",
    "mx:decode.prefill.read": "mx:decode.prefill",
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


def test_every_launch_carries_its_number_and_every_wait_names_one(lines):
    """The scheduler's launches in the order it made them: numbers from 1
    (warm-up's programs take none), each one more than the last; every
    span that waits names a launch made before it, of its own kind, at
    most once."""
    lines, _ = lines
    launched = sorted(
        (ev for name in ("mx:decode.dispatch", "mx:decode.prefill.launch")
         for _, ev in _named(lines, name)), key=lambda ev: ev[1])
    assert [ev[3]["seq"] for ev in launched] \
        == list(range(1, len(launched) + 1))
    kinds = {"mx:decode.dispatch": "step",
             "mx:decode.prefill.launch": "prefill"}
    assert all(ev[3]["program"] == kinds[ev[0]] for ev in launched)
    assert [ev[3]["rung"] for ev in launched
            if ev[0] == "mx:decode.prefill.launch"] == [16, 16, 16]
    by_seq = {ev[3]["seq"]: ev for ev in launched}
    for name, kind in (("mx:decode.readback", "step"),
                       ("mx:decode.prefill.read", "prefill")):
        waits = [ev for _, ev in _named(lines, name)]
        seqs = [ev[3]["waits"] for ev in waits]
        assert len(set(seqs)) == len(seqs)
        assert set(seqs) == {n for n, ev in by_seq.items()
                             if ev[3]["program"] == kind}
        # a wait begins after the launch it names has returned
        assert all(by_seq[ev[3]["waits"]][2] <= ev[1] for ev in waits)


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
    seen, opened = [], set()
    real, real_span = tracing._Annotation, tracing.span
    tracing._Annotation = type(
        "Spy", (), {"is_enabled": staticmethod(real.is_enabled),
                    "__init__": lambda self, *a, **k: seen.append(a)})

    def spying(name, /, *a, **k):
        opened.add(name)
        return real_span(name, *a, **k)

    tracing.span = spying
    try:
        _serve()
    finally:
        tracing._Annotation, tracing.span = real, real_span
    assert not seen and tracing.stats() is None
    # the spans were opened all the same: they are what the counters read
    assert {"decode.dispatch", "decode.readback", "decode.prefill.launch",
            "decode.prefill.read"} <= opened


# --- the decode scheduler's counters ---------------------------------------

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


COUNTERS = ("admitted", "queue_wait_s", "prefill_s", "readback_wait_s",
            "prefill_read_wait_s")


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


# --- the launch numbers, by the scheduler's own spans ------------------------

def _spied(srv, submit, between=None):
    """Every span ``srv`` opens while it serves what ``submit`` hands it,
    tick by tick, in the order they were opened. ``between`` is called
    with the tick's number before each tick."""
    spans = []
    real_span = tracing.span

    def spying(name, /, *a, **k):
        sp = real_span(name, *a, **k)
        spans.append(sp)
        return sp

    tracing.span = spying
    try:
        reqs = submit(srv)
        n = 0
        while not all(r.done() for r in reqs):
            if between is not None:
                between(n)
            srv._tick()
            n += 1
            assert n < 800, "scheduler made no progress"
        # a row that ended while its last step was unread ran one more
        srv._tick()
        assert srv._unread is None
    finally:
        tracing.span = real_span
    return spans


def _launches_and_waits(spans):
    launches = [sp for sp in spans if "seq" in sp.args]
    waits = [sp for sp in spans if "waits" in sp.args]
    return launches, waits


def _kind_server(kind):
    """A tiny unstarted server of each form of the serving contract, and
    what to ask of it."""
    from mxnet_tpu.serving.block_diffusion import BlockDiffusionMoEDecoderLM
    from mxnet_tpu.serving.latent_moe import LatentMoEDecoderLM
    rs = np.random.RandomState(5)
    if kind in ("token", "shared"):
        model, params = _toy()
        kw = dict(seq_ladder=(16,), page_size=4, pool_pages=32,
                  prefix_cache=kind == "shared")
        prompts = [np.arange(10, 22, dtype=np.int32)] * 3
    elif kind == "block":
        model = BlockDiffusionMoEDecoderLM(
            vocab_size=96, hidden_size=64, num_hidden_layers=1,
            num_attention_heads=4, num_key_value_heads=2, head_dim=128,
            moe_intermediate_size=128, num_experts=8,
            num_experts_per_tok=2, rope_theta=1e6, block_length=4,
            mask_token_id=95, denoising_steps=4,
            remasking_strategy="low_confidence_dynamic",
            confidence_threshold=0.9, rms_norm_eps=1e-6,
            max_position_embeddings=512, use_pallas=False)
        params = model.init_params(seed=3)
        kw = dict(seq_ladder=(16,), page_size=16, pool_pages=24)
        prompts = [rs.randint(0, 90, size=n).astype(np.int32)
                   for n in (5, 9, 6)]
    else:
        yarn = {"beta_fast": 32, "beta_slow": 1, "factor": 64, "mscale": 1,
                "mscale_all_dim": 1, "type": "yarn",
                "original_max_position_embeddings": 4096}
        model = LatentMoEDecoderLM(
            vocab_size=256, hidden_size=128, num_hidden_layers=2,
            num_attention_heads=4, q_lora_rank=64, kv_lora_rank=128,
            qk_nope_head_dim=32, qk_rope_head_dim=16, v_head_dim=32,
            intermediate_size=256, moe_intermediate_size=128,
            n_routed_experts=16, n_shared_experts=1,
            num_experts_per_tok=4, n_group=1, topk_group=1,
            routed_scaling_factor=2.0, first_k_dense_replace=1,
            rope_theta=10000, rope_scaling=yarn, rms_norm_eps=1e-6,
            max_position_embeddings=512, hc_mult=4, hc_sinkhorn_iters=20,
            hc_eps=1e-6, mhc_h_res_clamp_min=-30, mhc_h_res_clamp_max=30,
            num_nextn_predict_layers=1, use_pallas=False)
        params = model.init_params(seed=3)
        kw = dict(seq_ladder=(32,), page_size=16, pool_pages=64)
        prompts = [rs.randint(0, 256, size=n).astype(np.int32)
                   for n in (5, 9, 6)]
    srv = DecodeServer(model, params, max_new_tokens=8, window=2,
                       start=False, name=kind, **kw)
    return srv, lambda s: [s.submit(p, max_new_tokens=6) for p in prompts]


@pytest.mark.parametrize("kind", ["token", "shared", "block", "spec"])
def test_launch_numbers_in_every_form_of_the_contract(kind, monkeypatch):
    """One-token, prefix-shared (a copy-on-write between the steps),
    block and speculative serving on a clock of whole seconds: the
    numbers run from 1 without a hole in launch order, a wait names a
    launch of its kind that has returned, each at most once, steps are
    read in the order they were launched, and ``stats()`` counts what
    the spans say, to the second."""
    clock = iter(range(1, 1000000))
    monkeypatch.setattr(tracing, "now", lambda: float(next(clock)))
    srv, submit = _kind_server(kind)
    try:
        srv.warmup()
        spans = _spied(srv, submit)
        st = srv.stats()
    finally:
        srv.stop(drain=False)
    launches, waits = _launches_and_waits(spans)
    assert [sp.args["seq"] for sp in launches] \
        == list(range(1, len(launches) + 1))
    names = {"step": "decode.dispatch", "prefill": "decode.prefill.launch",
             "cow": "decode.cow.launch"}
    assert all(sp.name == names[sp.args["program"]] for sp in launches)
    by_seq = {sp.args["seq"]: sp for sp in launches}
    kinds = {"decode.readback": "step", "decode.prefill.read": "prefill"}
    seen = [sp.args["waits"] for sp in waits]
    assert len(set(seen)) == len(seen)
    for sp in waits:
        launch = by_seq[sp.args["waits"]]
        assert launch.args["program"] == kinds[sp.name]
        assert launch.t1 <= sp.t0
    steps = [n for n, sp in by_seq.items() if sp.args["program"] == "step"]
    assert [sp.args["waits"] for sp in waits
            if sp.name == "decode.readback"] == steps
    prefills = [sp for sp in launches if sp.args["program"] == "prefill"]
    reads = [sp for sp in waits if sp.name == "decode.prefill.read"]
    if kind == "block":     # it emits no token: nothing to wait for
        assert len(prefills) == 3 and not reads
    elif kind == "shared":  # the second and third prompt hit the first's
        assert len(prefills) == 1 == len(reads)
        assert st["launches"]["cow"] >= 1
    else:
        assert [sp.args["waits"] for sp in reads] \
            == [sp.args["seq"] for sp in prefills]
    # the launch and the read lie inside the prefill span
    outer = [sp for sp in spans if sp.name == "decode.prefill"]
    assert all(any(o.t0 < sp.t0 and sp.t1 < o.t1 for o in outer)
               for sp in prefills + reads)
    # the counters, from the same stamps
    for program in ("step", "prefill", "cow"):
        mine = [sp for sp in launches if sp.args["program"] == program]
        assert st["launches"][program] == len(mine)
        assert st["launch_s"][program] == sum(sp.t1 - sp.t0 for sp in mine)
    assert st["launches"]["step"] == st["decode_steps"]
    assert st["readback_wait_s"] == sum(
        sp.t1 - sp.t0 for sp in waits if sp.name == "decode.readback") > 0
    assert st["prefill_read_wait_s"] == sum(sp.t1 - sp.t0 for sp in reads)


def test_a_drain_reads_the_step_it_launched_last(scripted):
    """A weight swap in mid-run makes the scheduler read the unread step
    before it plans the next: that read-back names the last launch, and
    the step after it is launched with nothing ahead of it."""
    srv = scripted
    model, params = _toy()

    def swap(n):
        if n == 3:
            srv.swap_weights(params)

    spans = _spied(srv, lambda s: [
        s.submit(np.arange(1, 6, dtype=np.int32), max_new_tokens=4)
        for _ in range(2)], between=swap)
    assert srv.stats()["decode_drains"] == {"swap_weights": 1}
    steps = [sp for sp in spans if sp.name in ("decode.dispatch",
                                               "decode.readback")]
    at, = (i for i, sp in enumerate(steps)
           if sp.name == "decode.dispatch" and i
           and steps[i - 1].name == "decode.readback"
           and steps[i - 2].name == "decode.readback")
    drained, before = steps[at - 1], steps[at - 2]
    assert drained.args["waits"] == before.args["waits"] + 1
    assert drained.args["waits"] == max(
        sp.args["seq"] for sp in steps[:at] if sp.name == "decode.dispatch")
    assert steps[at].args["ahead"] == 0
    assert steps[at].args["seq"] > drained.args["waits"]


# --- what the host did to the process ---------------------------------------

def test_stats_says_what_the_host_did_to_the_process(scripted):
    host = scripted.stats()["host"]
    assert host["involuntary_switches"] >= 0
    assert ("throttled_s" in host) == ("nr_throttled" in host)
    if "throttled_s" in host:
        assert host["throttled_s"] >= 0 and host["nr_throttled"] >= 0


@pytest.mark.parametrize("layout,want", [
    ("v2", {"throttled_s": 1.5, "nr_throttled": 12}),
    ("v1", {"throttled_s": 0.25, "nr_throttled": 3}),
    ("no_count_no_quota", {"throttled_s": 0.0, "nr_throttled": 0}),
    ("no_count_a_quota", {}), ("no_cgroup", {})])
def test_throttling_is_read_from_the_process_cgroup(monkeypatch, layout,
                                                    want):
    import io
    from mxnet_tpu.serving import decode
    files = {
        "v2": {"/proc/self/cgroup": "0::/pod/box\n",
               "/sys/fs/cgroup/pod/box/cpu.stat":
               "usage_usec 9\nnr_periods 40\nnr_throttled 12\n"
               "throttled_usec 1500000\n"},
        # the group's own path is not mounted: the mount is the group
        "v1": {"/proc/self/cgroup": "3:memory:/m\n2:cpu,cpuacct:/box\n",
               "/sys/fs/cgroup/cpu,cpuacct/cpu.stat":
               "nr_periods 9\nnr_throttled 3\nthrottled_time 250000000\n"},
        # the chip's machine: a v1 group with no cpu.stat and no quota
        "no_count_no_quota": {
            "/proc/self/cgroup": "2:cpuacct:/box\n1:cpu:/box\n",
            "/sys/fs/cgroup/cpu/cpu.cfs_quota_us": "-1\n"},
        "no_count_a_quota": {"/proc/self/cgroup": "0::/\n",
                             "/sys/fs/cgroup/cpu.stat": "usage_usec 9\n",
                             "/sys/fs/cgroup/cpu.max": "200000 100000\n"},
        "no_cgroup": {}}[layout]

    def fake_open(path, *a, **k):
        if path not in files:
            raise FileNotFoundError(path)
        return io.StringIO(files[path])

    monkeypatch.setattr(decode, "open", fake_open, raising=False)
    got = decode._host_stats()
    assert got.pop("involuntary_switches") >= 0
    assert got == want


def test_a_chunk_rides_the_dispatch_span():
    """A server whose prompts ride the step in chunks launches no
    prefill: ``mx:decode.dispatch`` of a mixed step says how many tokens
    of whose prompt it carried (``chunk``, ``chunk_of``), every launch is
    still numbered and every read-back names one, and ``stats()`` counts
    the same steps and tokens."""
    model, params = _toy(ToyDecoderLM)
    srv = DecodeServer(model, params, seq_ladder=(8, 32),
                       max_new_tokens=6, window=2, page_size=4,
                       pool_pages=32, name="chunkspans", start=False)
    prompts = [np.arange(1, 1 + n, dtype=np.int32) for n in (19, 5)]
    try:
        spans = _spied(srv, lambda s: [s.submit(p, max_new_tokens=4)
                                       for p in prompts])
        ids = ["d000001", "d000002"]
        assert not [sp for sp in spans if sp.name.startswith(
            "decode.prefill")]
        steps = [sp for sp in spans if sp.name == "decode.dispatch"]
        mixed = [(sp.args["chunk_of"], sp.args["chunk"]) for sp in steps
                 if "chunk" in sp.args]
        assert mixed == [(ids[0], 8), (ids[0], 8), (ids[0], 3), (ids[1], 5)]
        assert all(sp.args["program"] == "step" for sp in steps)
        launches, waits = _launches_and_waits(spans)
        assert [sp.args["seq"] for sp in launches] \
            == list(range(1, len(launches) + 1))
        assert sorted(sp.args["waits"] for sp in waits) \
            == [sp.args["seq"] for sp in launches]
        st = srv.stats()
        assert st["chunk_steps"] == 4 and st["chunk_tokens"] == 24
        assert st["decode_steps"] == len(steps)
        assert st["launches"] == {"step": len(steps), "prefill": 0,
                                  "cow": 0}
    finally:
        srv.stop()
