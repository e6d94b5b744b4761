"""``launch_join`` and the readers built on it, on slices written by
hand: a scheduler one step ahead of a device whose stamps lie 1.2 ms
before the host's, laid out by ``Slice`` below (times in ns, 1e6 = 1 ms;
``H`` = the host's clock, ``D`` = the device's, H = D + 1.2 ms)."""
import importlib
import json
import os
import types

import pytest

from benchmark import launch_join, program_spans, trace_reduce

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
MS = 1e6
LEAD = 1.2 * MS
STEP, PREFILL = 10 * MS, 12 * MS
STEP_NAME = "jit__decode_fn(1269473466103926323)"
PREFILL_NAME = "jit__prefill_fn(7711405620211203961)"
CHUNK_NAME = "jit__decode_fn_chunk(4417262033651180257)"
KERNEL = ("%%mx_flash_decode.bh256.q1.k2048.d128.float32.paged.%d = "
          "f32[8,32,128]{2,1,0} custom-call(f32[8,32,128]{2,1,0} %%q)")
NAMES = {"step_module": "_decode_fn", "prefill_module": "_prefill_fn"}
# the readers of a prefill PROGRAM: listed where a form keeps one
OF_A_PREFILL = ("prefill_device_ms", "prefill_queue_ms", "admit_idle_ms")
READERS = {
    "prefill_device_ms": ("program_span", "Model step", "itl_p99_ms"),
    "prefill_queue_ms": ("program_span", "Decode scheduler", "itl_p99_ms"),
    "admit_idle_ms": ("program_span", "Decode scheduler", "itl_p99_ms"),
    "idle_attributed_share": ("program_span", "Decode scheduler",
                              "itl_p99_ms"),
    "process_stopped_ms": ("program_span", "Decode scheduler",
                           "serve_tok_per_s"),
    "host_slack_share": ("program_counter", "Decode scheduler",
                         "serve_tok_per_s"),
    "host_throttled_ms": ("program_counter", "Decode scheduler",
                          "serve_tok_per_s"),
}
JOINED = ("prefill_device_ms", "prefill_queue_ms", "admit_idle_ms",
          "idle_attributed_share", "process_stopped_ms")


def _reader(name):
    return importlib.import_module("benchmark.layer_metrics." + name)


class Slice:
    """A scheduler thread's spans, libtpu's enqueues, a client thread
    that stirs every 5 ms, and the device's programs, by the loop's own
    rules: a launch takes 1 ms (a prefill's 0.6) and enqueues 80% of the
    way in; the program starts 0.1 ms later or when the device is free; a
    wait begins 0.1 ms after the span before it and ends 0.3 ms after
    its program has ended; reap 0.1, pages 0.1, build 0.3, emit 0.4,
    record 0.1."""

    def __init__(self, numbered=True):
        self.numbered = numbered
        self.spans, self.modules, self.enqueues = [], [], []
        self.ops = []
        self.stops = []
        self.t, self.free, self.seq, self.unread = 100 * MS, 0.0, 0, None

    def span(self, name, ms, **stats):
        self.spans.append(("mx:" + name, self.t, self.t + ms * MS, stats))
        self.t += ms * MS

    def launch(self, name, program, module, ns, ms, **stats):
        self.seq += 1
        if self.numbered:
            stats.update(seq=self.seq, program=program)
        enqueue = self.t + 0.8 * ms * MS
        self.enqueues.append(("DoEnqueueProgram", enqueue, enqueue + 5e4))
        start = max(self.free, enqueue + 0.1 * MS - LEAD)
        self.modules.append((module, start, start + ns))
        self.free = start + ns
        self.span(name, ms, **stats)
        return self.seq, start + ns

    def wait(self, name, launched, stop_ms=0.0, **stats):
        seq, end = launched
        if self.numbered:
            stats["waits"] = seq
        self.t += 0.1 * MS
        if stop_ms:     # the whole process stands, from 1 ms into the wait
            self.stops.append((self.t + MS, self.t + MS + stop_ms * MS))
        done = max(self.t + 0.05 * MS, end + LEAD + 0.3 * MS)
        if stop_ms:
            done = max(done, self.stops[-1][1] + 0.05 * MS)
        self.span(name, (done - self.t) / MS, **stats)

    def tick(self, admit=False, stop_ms=0.0, rung=256, **said):
        """``said``: what else the step's ``decode.dispatch`` carries
        (``pages_live``, ``chunk``); a step that says its pages runs 4
        ``mx_flash_decode`` calls of 0.5 ms each."""
        t0 = self.t
        self.span("decode.reap", 0.1)
        if admit:
            a0 = self.t
            self.t += 0.2 * MS
            p0 = self.t
            launched = self.launch("decode.prefill.launch", "prefill",
                                   PREFILL_NAME, PREFILL, 0.6, rung=rung)
            self.wait("decode.prefill.read", launched)
            self.spans.append(("mx:decode.prefill", p0, self.t,
                               {"rung": rung}))
            self.t += 0.1 * MS
            self.spans.append(("mx:decode.admit", a0, self.t, {}))
        self.span("decode.pages", 0.1)
        self.span("decode.build", 0.3)
        prev, self.unread = self.unread, self.launch(
            "decode.dispatch", "step",
            CHUNK_NAME if "chunk" in said else STEP_NAME, STEP, 1.0,
            ahead=int(self.unread is not None), **said)
        if "pages_live" in said:
            start = self.modules[-1][1]
            self.ops += [(KERNEL % k, start + (1 + 2 * k) * MS,
                          start + (1.5 + 2 * k) * MS) for k in range(4)]
        if prev is not None:
            self.wait("decode.readback", prev, stop_ms)
            self.span("decode.emit", 0.4)
        self.span("decode.record", 0.1)
        self.spans.append(("mx:decode.tick", t0, self.t, {}))

    def ctx(self, window=None, **raw):
        """What a run's context holds of a profile taken over ``window``
        (host clock): a host event is kept if it began and ended inside,
        a device event if it began inside."""
        lo, hi = window or (99 * MS, self.t + MS)
        spans = [ev for ev in self.spans if lo <= ev[1] and ev[2] <= hi]
        client, t = [], lo + MS
        while t < hi - MS:
            if not any(a <= t < b for a, b in self.stops):
                client.append(("bench:client", t, t + 0.2 * MS))
            t += 5 * MS
        planes = {
            "/device:TPU:0": {
                trace_reduce.MODULES_LINE: [
                    ev for ev in self.modules if lo <= ev[1] + LEAD < hi],
                trace_reduce.OPS_LINE: [
                    ev for ev in self.ops if lo <= ev[1] + LEAD < hi]},
            "/host:CPU": {
                "python3": [ev[:3] for ev in spans] + client
                + [(trace_reduce.SLICE_SPAN, lo, hi)],
                "tfrt-non-blocking-queue/1": [
                    ev for ev in self.enqueues if lo <= ev[1] < hi]}}
        return types.SimpleNamespace(
            trace=trace_reduce.Trace(planes),
            program_spans=program_spans.Spans([spans]), raw=raw,
            config={"trace_names": NAMES, "bytes_per_value": {"kv": 4},
                    "server": {"kwargs": {"page_size": 128}}},
            peak={"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9})


def _idle_ns(joined):
    """The time between the programs inside the window, the programs
    moved by the leads the join gave them."""
    busy = trace_reduce.union(
        (p.start + p.lead, p.end + p.lead) for p in joined.programs)
    return trace_reduce.total(trace_reduce.gaps(
        busy, max(joined.window[0], busy[0][0]),
        min(joined.window[1], busy[-1][1])))


def _steady(ticks=12, **kw):
    sl = Slice(**kw)
    for _ in range(ticks):
        sl.tick()
    return sl


def _with_a_prefill(**kw):
    sl = Slice(**kw)
    for k in range(9):
        sl.tick(admit=k == 5)
    return sl


# --- identity and the clock -------------------------------------------------

def test_a_loop_one_step_ahead_is_joined_and_its_lead_recovered():
    """Twelve steps back to back, each launched while the one before it
    runs: every program is joined to its own number, and the true lead
    lies inside the slack of every program. The first launch found the
    device idle, so its enqueue bounds the lead from below to 0.1 ms;
    every read-back bounds it from above to 0.3."""
    ctx = _steady().ctx()
    joined = launch_join.of(ctx)
    assert [p.seq for p in joined.programs] == list(range(1, 13))
    assert all(p.launch.stats["seq"] == p.seq for p in joined.programs)
    # the last step is still unread when the slice ends
    assert [p.wait.stats["waits"] for p in joined.programs[:-1]] \
        == list(range(1, 12))
    assert joined.programs[-1].wait is None
    for p in joined.programs:
        assert abs(p.lead - LEAD) <= p.slack + 1
        assert abs(p.lead - (LEAD + 0.1 * MS)) < 1
        assert abs(p.slack - 0.2 * MS) < 1
    assert ctx.raw["launch_join"] == {
        "by": "order", "programs_seen": 12, "programs_joined": 12,
        "first_seq": 1,
        "lead_ms": pytest.approx([1.3, 1.3, 1.3]),
        "widest_slack_ms": pytest.approx(0.2), "enqueues_paired": 12,
        "consistent": True}


def test_without_libtpus_enqueues_the_spans_alone_bound_the_lead():
    """A runtime that names its events otherwise: the lower bound is the
    idle launch's own beginning, 0.9 ms before its program."""
    ctx = _steady().ctx()
    del ctx.trace.planes["/host:CPU"]["tfrt-non-blocking-queue/1"]
    joined = launch_join.of(ctx)
    assert ctx.raw["launch_join"]["enqueues_paired"] == 0
    for p in joined.programs:
        assert abs(p.lead - LEAD) <= p.slack + 1
        assert abs(p.slack - 0.6 * MS) < 1


def test_a_slice_that_opens_in_mid_flight_and_closes_with_a_launch():
    """The profile starts while step 4 runs and stops while step 9 does:
    steps 5..8 began inside it; 5's launch lies before it (its wait is
    inside), and the launches of 9 and 10 are there with no program."""
    sl = _steady()
    starts = {i + 1: s + LEAD for i, (_, s, _) in enumerate(sl.modules)}
    ctx = sl.ctx(window=(starts[4] + 6 * MS, starts[9] + 6 * MS))
    joined = launch_join.of(ctx)
    assert [p.seq for p in joined.programs] == [5, 6, 7, 8, 9]
    assert joined.programs[0].launch is None
    assert joined.programs[0].wait.stats["waits"] == 5
    assert [p.launch.stats["seq"] for p in joined.programs[1:]] \
        == [6, 7, 8, 9]
    assert joined.programs[-1].wait is None     # 9's read-back never ended
    launched = {sp.stats["seq"] for sp in ctx.program_spans.spans
                if "seq" in sp.stats}
    assert 10 in launched and 5 not in launched
    assert ctx.raw["launch_join"]["programs_joined"] == 5
    # no launch of the slice found the device idle: the lower bound is a
    # launch a whole step early, and the slack says so
    for p in joined.programs:
        assert abs(p.lead - LEAD) <= p.slack + 1
        assert p.slack > 4 * MS


def _run_ids(sl, first=41, **kw):
    """What ``launch_join.run_ids`` reads of a profile whose runtime
    numbers its runs from ``first``."""
    return {"programs": {s: first + k
                         for k, (_, s, _) in enumerate(sl.modules)},
            "enqueues": {first + k: s
                         for k, (_, s, _) in enumerate(sl.enqueues)}}


def test_the_runtimes_own_run_ids_name_the_programs():
    """Device programs and enqueues that carry ``run_id``: an enqueue is
    of the launch that began last before it, which fixes ``run_id - seq``
    for the slice (here 40), and each program's enqueue bounds its lead
    whatever the order of the lines. The same slice read by order gives
    the same numbers."""
    sl = _with_a_prefill()
    starts = {i + 1: s + LEAD for i, (_, s, _) in enumerate(sl.modules)}
    window = (starts[2] + 6 * MS, starts[9] + 6 * MS)
    by_order = launch_join.of(sl.ctx(window=window))
    ctx = sl.ctx(window=window)
    ctx.run_ids = _run_ids(sl)
    joined = launch_join.of(ctx)
    assert ctx.raw["launch_join"]["by"] == "run_id"
    assert [p.seq for p in joined.programs] == [3, 4, 5, 6, 7, 8, 9] \
        == [p.seq for p in by_order.programs]
    assert [(p.lead, p.slack) for p in joined.programs] \
        == [(p.lead, p.slack) for p in by_order.programs]
    # run ids that contradict the spans' kinds: nothing, not the order's
    ctx = sl.ctx(window=window)
    ctx.run_ids = _run_ids(sl)
    ctx.run_ids["enqueues"] = {run + 1: at for run, at
                               in ctx.run_ids["enqueues"].items()}
    assert launch_join.of(ctx) is None
    # an enqueue made late, after the NEXT launch began (the runtime's
    # thread lagged): it is read as that launch's, one number short, and
    # the largest number seen is the slice's
    launches = {k: program_spans.Span("decode.dispatch", t, t + 1.0, 0,
                                      {"seq": k, "program": "step"})
                for k, t in ((1, 0.0), (2, 10.0), (3, 11.5))}
    programs = [launch_join.Program(STEP_NAME, t, t + 1.0, "step")
                for t in (2.0, 12.0, 13.0)]
    ids = {"programs": {2.0: 11, 12.0: 12, 13.0: 13},
           "enqueues": {11: 0.5, 12: 11.6, 13: 11.9}}
    assert launch_join._first_by_run_id(programs, launches, ids) == 1


def test_a_server_with_nothing_to_do_is_no_stopped_process():
    """The window empties: the scheduler waits 200 ms in ``decode.wait``
    and every line is silent. That idle time is the wait's, a root's."""
    sl = Slice()
    for _ in range(3):
        sl.tick()
    sl.wait("decode.readback", sl.unread)
    sl.unread = None
    sl.span("decode.wait", 200.0)
    sl.stops.append((sl.t - 199 * MS, sl.t - MS))   # the client is quiet too
    for _ in range(3):
        sl.tick()
    ctx = sl.ctx()
    joined = launch_join.of(ctx)
    assert joined.silences() == []
    by_span = joined.idle()
    assert launch_join.STOPPED not in by_span
    assert by_span["decode.wait"] > 195 * MS
    assert _reader("idle_attributed_share").compute(ctx) < 5


def test_kinds_that_cannot_agree_give_nothing():
    """The device ran a prefill where the spans say a step was launched
    (a program of another server's on the same chip): no offset fits."""
    ctx = _with_a_prefill().ctx()
    line = ctx.trace.planes["/device:TPU:0"][trace_reduce.MODULES_LINE]
    line[:] = [(STEP_NAME if n == PREFILL_NAME else n, s, e)
               for n, s, e in line]
    assert launch_join.of(ctx) is None
    assert "launch_join" not in ctx.raw
    for name in JOINED:
        assert _reader(name).compute(ctx) is None


def test_two_offsets_that_cannot_be_told_apart_give_nothing():
    """Programs of 0.2 ms: the read-back of one ends as near to the
    next's end as to its own."""
    spans = [("mx:decode.dispatch", 10 * MS + k * MS, 10.2 * MS + k * MS,
              {"seq": k + 1, "program": "step"}) for k in range(6)]
    spans += [("mx:decode.readback", 10.3 * MS + k * MS, 10.6 * MS + k * MS,
               {"waits": k + 1}) for k in range(6)]
    modules = [(STEP_NAME, 10.25 * MS + 0.3 * MS * k,
                10.45 * MS + 0.3 * MS * k) for k in range(6)]
    assert launch_join.join(program_spans.Spans([spans]), modules,
                            NAMES) is None


@pytest.mark.parametrize("name", sorted(READERS))
def test_spans_without_numbers_give_every_reader_nothing(name):
    """An earlier commit's spans, with everything else of a traced run
    there: the metric is left out, and nothing is written to ``raw``."""
    ctx = _with_a_prefill(numbered=False).ctx(
        window_s=30.0, stats0={"decode_steps": 1}, stats1={"decode_steps": 9})
    assert _reader(name).compute(ctx) is None
    assert "launch_join" not in ctx.raw and "idle_by_span" not in ctx.raw


# --- the readers -------------------------------------------------------------

def test_a_prefill_between_two_steps_gives_the_three_prefill_metrics():
    """Tick 5 admits: the prefill is launched while step 5 runs, so it
    waits behind it. By hand, from ``Slice``'s rules: the first launch
    begins at H 100.5 and is enqueued at 101.3, so step 1 starts at
    H 101.4 = D 100.2, and step 5 runs D 140.2..150.2; tick 5 begins when
    step 4's read-back, emit and record are done, at H 142.2 (= 140.2 +
    1.2 + 0.3 + 0.4 + 0.1); reap 0.1 and the admit's 0.2 bring the
    prefill's launch to H 142.5..143.1; its program runs D 150.2..162.2,
    which is H 151.4 on: it queued 151.4 - 143.1 = 8.3 ms. Its token is
    read at H 163.7 (162.2 + 1.2 + 0.3); 0.1 of admit, pages 0.1 and
    build 0.3 later step 6 is launched at H 164.2, enqueued at 165.0 and
    starts at H 165.1 = D 163.9: the device stood 1.7 ms after the prefill
    and not at all before it."""
    ctx = _with_a_prefill().ctx()
    assert abs(_reader("prefill_device_ms").compute(ctx) - 12.0) < 1e-6
    assert ctx.raw["prefill_device_ms_by_rung"] == {
        "256": pytest.approx(12.0)}
    # the lead is known to 0.2 ms, and read 0.1 high
    assert abs(_reader("prefill_queue_ms").compute(ctx) - 8.4) < 1e-6
    assert abs(_reader("admit_idle_ms").compute(ctx) - 1.7) < 1e-6
    # launch + queue + device + read make the stall the old reader times
    stall = program_spans.median_ms(ctx, "decode.prefill")
    read, = ctx.program_spans.named("decode.prefill.read")
    launch, = ctx.program_spans.named("decode.prefill.launch")
    prefill, = launch_join.of(ctx).prefills()
    after = read.end - (prefill.end + prefill.lead)
    assert abs(stall - (launch.ns + 8.4 * MS + 12 * MS + after) / MS) < 1e-6
    assert abs(after - 0.2 * MS) < 1


def test_idle_is_charged_to_the_span_the_host_was_in_and_adds_up():
    """The same slice's idle time: the 1.7 ms after the prefill, and not
    the 2.5 ms of the window in front of the first program the device
    line holds (the device ran what the profile did not record); every
    nanosecond of it charged once."""
    ctx = _with_a_prefill().ctx()
    joined = launch_join.of(ctx)
    by_span = joined.idle()
    assert abs(sum(by_span.values()) - _idle_ns(joined)) < 1
    assert abs(_idle_ns(joined) - 1.7 * MS) < 1
    assert launch_join.STOPPED not in by_span
    assert launch_join.UNRESOLVED not in by_span
    # after the prefill (seen 0.1 ms late): the rest of its read 0.2, the
    # admit's own 0.1, pages 0.1, build 0.3, the launch until its program
    # starts 1.0 (0.9 + the 0.1 the lead is off by)
    want = {"decode.prefill.read": 0.2, "decode.admit": 0.1,
            "decode.pages": 0.1, "decode.build": 0.3,
            "decode.dispatch": 1.0}
    assert set(by_span) == set(want)
    for name, ms in want.items():
        assert abs(by_span[name] - ms * MS) < 1, (name, by_span[name])
    assert ctx.raw["idle_by_span"] == [
        [name, pytest.approx(ns / 1e9)] for name, ns in sorted(
            by_span.items(), key=lambda kv: -kv[1])]
    share = _reader("idle_attributed_share").compute(ctx)
    apart = sum(by_span.get(name, 0.0) for name in (
        "decode.tick", "decode.wait", launch_join.STOPPED,
        launch_join.UNRESOLVED, launch_join.UNATTRIBUTED))
    assert apart == 0 and share == pytest.approx(100.0)
    assert _reader("process_stopped_ms").compute(ctx) == 0.0


def test_a_silence_of_every_host_line_is_charged_to_the_stop():
    """The process stands for 120 ms from 1 ms into a read-back (H 134.8;
    the last thing any line did was to open that span, at 133.8): step 5,
    in flight, ends 16.6 ms into the stop (H 151.4, read as 151.5) and the
    device stands until the host moves again at 254.85. Those 103.35 ms
    are the stop's, not ``decode.readback``'s; what the host then needs
    to launch the next step is charged as ever."""
    sl = Slice()
    for k in range(8):
        sl.tick(stop_ms=120.0 if k == 4 else 0.0)
    ctx = sl.ctx()
    (s0, s1), = sl.stops
    joined = launch_join.of(ctx)
    assert (s0, s1) == (134.8 * MS, 254.8 * MS)
    assert joined.silences() == [(pytest.approx(133.8 * MS),
                                  pytest.approx(254.85 * MS))]
    by_span = joined.idle()
    stood = _reader("process_stopped_ms").compute(ctx)
    assert abs(stood - 103.35) < 1e-6
    assert "decode.readback" not in by_span
    for name, ms in {"decode.emit": 0.4, "decode.record": 0.1,
                     "decode.reap": 0.1, "decode.pages": 0.1,
                     "decode.build": 0.3, "decode.dispatch": 1.0}.items():
        assert abs(by_span[name] - ms * MS) < 1, (name, by_span[name])
    assert abs(sum(by_span.values()) - _idle_ns(joined)) < 1
    # the shares account for all of it: 2.0 ms under spans, the stop's
    share = _reader("idle_attributed_share").compute(ctx)
    assert share == pytest.approx(100 * 2.0 / (2.0 + 103.35))
    assert share + 100 * by_span[launch_join.STOPPED] \
        / sum(by_span.values()) == pytest.approx(100.0)
    # a slice that holds no stop reads 0, not nothing
    assert _reader("process_stopped_ms").compute(_steady().ctx()) == 0.0


def test_a_gap_narrower_than_the_leads_slack_is_not_split():
    """The slice that opened in mid-flight knows its lead to +-4.4 ms
    only: the 0 ms between its steps stay 0, and a gap of 1 ms (one step
    stamped 1 ms late) is nobody's."""
    sl = _steady()
    sl.modules[6] = (STEP_NAME, sl.modules[6][1] + MS, sl.modules[6][2])
    starts = {i + 1: s + LEAD for i, (_, s, _) in enumerate(sl.modules)}
    ctx = sl.ctx(window=(starts[4] + 6 * MS, starts[9] + 5 * MS))
    joined = launch_join.of(ctx)
    assert all(p.slack > 4 * MS for p in joined.programs)
    assert joined.idle() == {launch_join.UNRESOLVED: pytest.approx(MS)}
    assert _reader("idle_attributed_share").compute(ctx) == 0.0


def test_the_two_counters_of_stats():
    raw = dict(window_s=30.0,
               stats0={"readback_wait_s": 2.0, "host": {
                   "throttled_s": 0.5, "involuntary_switches": 7}},
               stats1={"readback_wait_s": 23.0, "host": {
                   "throttled_s": 0.75, "involuntary_switches": 9}})
    ctx = types.SimpleNamespace(raw=raw)
    assert _reader("host_slack_share").compute(ctx) == pytest.approx(70.0)
    assert _reader("host_throttled_ms").compute(ctx) == pytest.approx(250.0)
    # a cgroup that keeps no such count
    for st in (raw["stats0"], raw["stats1"]):
        del st["host"]["throttled_s"]
    assert _reader("host_throttled_ms").compute(ctx) is None
    assert _reader("host_slack_share").compute(ctx) == pytest.approx(70.0)


def test_the_paged_kernel_is_held_to_the_pages_of_the_steps_it_ran_in():
    """Eight steps in the slice: five plain ones whose dispatch says 10,
    20, 30, 40 and 50 live pages, two mixed ones (their count holds the
    chunk's pages too: left out, kernel time and pages alike) and one
    whose launch lies in front of the profile (no span: left out). Each
    runs 4 kernel calls of 0.5 ms. At 4 layers of 4096 float32 a live
    page is 128 x 2 x 4 x 4096 x 4 B = 16.8 MB: 150 pages at 819 GB/s
    are 3.07 ms of the 10 ms the kernel took in those five steps."""
    sl = Slice()
    sl.tick(pages_live=99)
    for pages in (10, 20):
        sl.tick(pages_live=pages)
    sl.tick(pages_live=70, chunk=512, chunk_of="d000007")
    for pages in (30, 40):
        sl.tick(pages_live=pages)
    sl.tick(pages_live=80, chunk=256, chunk_of="d000007")
    sl.tick(pages_live=50)
    sl.tick()
    starts = [s + LEAD for _, s, _ in sl.modules]
    ctx = sl.ctx(window=(starts[0] - 0.05 * MS, sl.t + MS),
                 model={"n_layers": 4, "d_model": 4096})
    reader = _reader("flash_decode_roofline_share")
    page = 128 * 2 * 4 * 4096 * 4
    want = 100 * (150 * page / 819e9) / 10e-3
    assert reader.compute(ctx) == pytest.approx(want)
    assert 30 < want < 31
    assert ctx.raw["flash_decode"] == {
        "steps": 5, "pages_live": 150,
        "bytes_per_step": pytest.approx(30 * page),
        "kernel_s_per_step": pytest.approx(2e-3)}
    # a kernel that streamed its pages AT the roofline reads 100%
    even = Slice()
    for _ in range(6):
        even.tick(pages_live=30)
    even.ops = [(n, s, s + 30 * page / 819e9 / 4 * 1e9)
                for n, s, _ in even.ops]
    ctx = even.ctx(model={"n_layers": 4, "d_model": 4096})
    assert reader.compute(ctx) == pytest.approx(100.0)
    # steps that say no pages (an earlier commit), a program that runs
    # no such kernel, an untraced run: nothing
    ctx = _steady().ctx(model={"n_layers": 4, "d_model": 4096})
    assert reader.compute(ctx) is None and "flash_decode" not in ctx.raw
    quiet = Slice()
    for _ in range(4):
        quiet.tick(pages_live=30)
    quiet.ops = []
    assert reader.compute(quiet.ctx(
        model={"n_layers": 4, "d_model": 4096})) is None
    ctx = _with_a_prefill(numbered=False).ctx()
    assert reader.compute(ctx) is None


def test_the_breakdowns_idle_gaps_name_the_programs_spans():
    """``breakdown.idle_gaps`` of a serving cell is the join's
    attribution, largest first, in seconds: the program's own spans,
    not the ``bench:`` spans' "scheduler". Nothing where the program
    numbers no launch (``run.py`` then falls back to the ``bench:``
    spans) or the device never stood between two programs."""
    ctx = _with_a_prefill().ctx()
    gaps = launch_join.idle_gaps(ctx, 3)
    assert [name for name, _ in gaps] == [
        "decode.dispatch", "decode.build", "decode.prefill.read"]
    assert gaps[0][1] == pytest.approx(1.0e-3)
    assert sum(s for _, s in launch_join.idle_gaps(ctx, 10)) \
        == pytest.approx(1.7e-3)
    assert launch_join.idle_gaps(
        _with_a_prefill(numbered=False).ctx(), 10) is None
    assert launch_join.idle_gaps(_steady().ctx(), 10) is None


# --- BENCHMARK.json ---------------------------------------------------------

@pytest.mark.parametrize("name", sorted(READERS))
def test_the_entry_says_what_its_reader_says(name):
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        spec = json.load(f)
    entry, = (m for m in spec["per_layer"] if m["name"] == name)
    mod = _reader(name)
    source, layer, moves = READERS[name]
    assert (mod.NAME, mod.UNIT, mod.LAYER) \
        == (name, entry["unit"], entry["layer"]) and layer == mod.LAYER
    assert (entry["source"], entry["moves"]) == (source, moves)
    # every serving cell that reports the metric it moves; a prefill's
    # readers where prompts do not ride the step in chunks (PR 40)
    reporting = {m["name"]: m.get("workloads") for m in spec["end_to_end"]}
    chunked = next(m for m in spec["per_layer"]
                   if m["name"] == "chunk_step_share")["workloads"]
    serving = [c for c in reporting["itl_p99_ms"] if c in reporting[moves]]
    assert entry["workloads"] == [c for c in serving
                                  if name not in OF_A_PREFILL or c not in chunked]
