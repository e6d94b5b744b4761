"""``program_spans`` and the readers built on it, on a slice written by
hand (``data/program_trace.json``; times there in ns, 1e6 = 1 ms)."""
import importlib
import json
import os
import re
import subprocess
import sys
import types

import pytest

from benchmark import kernel_costs, program_spans, trace_reduce

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
MS = 1e6


def _load(which):
    with open(os.path.join(HERE, "data", "program_trace.json")) as f:
        data = json.load(f)[which]
    planes = {
        plane: {line: [tuple(ev) for ev in evs]
                for line, evs in lines.items()}
        for plane, lines in data["planes"].items()}
    lines = [[tuple(ev) for ev in line] for line in data["lines"]]
    return trace_reduce.Trace(planes), program_spans.Spans(lines)


def _ctx(which, **raw):
    trace, spans = _load(which)
    return types.SimpleNamespace(
        trace=trace, program_spans=spans, raw=raw,
        config={"trace_names": {"step_module": "_decode_fn",
                                "prefill_module": "_prefill_fn"},
                "bytes_per_value": {"kv": 4}},
        peak={"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9})


def _idle(which):
    ctx = _ctx(which)
    idle = program_spans.idle(ctx)
    return idle, ctx.raw["device_lead_ms"]


def _reader(name):
    return importlib.import_module("benchmark.layer_metrics." + name)


# --- program_spans ---------------------------------------------------------

def test_only_the_programs_spans_and_their_nesting_are_kept():
    _, spans = _load("serve")
    assert len(spans.spans) == 28        # the bench: spans are not ours
    ticks = spans.named("decode.tick")
    assert [len(t.children) for t in ticks] == [7, 7, 8]
    prefill, = spans.named("decode.prefill")
    assert prefill.parent.name == "decode.admit"
    assert prefill.parent.parent is ticks[2] and ticks[2].parent is None
    assert prefill.parent.stats["request_id"] == "d000042"
    assert all(w.parent is None for w in spans.named("decode.wait"))


def test_self_time_is_what_no_child_covers():
    _, spans = _load("serve")
    ticks = spans.named("decode.tick")
    assert abs(ticks[0].self_ns - 0.2 * MS) < 1
    assert abs(ticks[2].self_ns) < 1
    admit, = spans.named("decode.admit")
    assert abs(admit.self_ns - 0.9 * MS) < 1
    assert admit.children[0].self_ns == admit.children[0].ns


GAP_READERS = ("gap_admit_ms", "gap_build_ms", "gap_emit_ms",
               "gap_unattributed_share")


def _host_line(trace, name):
    """The one line of the host plane that holds the events ``name``."""
    line, = (evs for evs in trace.planes["/host:CPU"].values()
             if any(n == name for n, _, _ in evs))
    return line


def test_the_devices_lead_is_measured_for_every_program():
    """The slice's device events are stamped 1.5 ms early; the launch
    and the notice of every program bound its lead to 1.4 .. 1.6."""
    trace, _ = _load("serve")
    leads = program_spans.device_leads(trace)
    assert [start for start, _, _ in leads] == [8.5 * MS, 58.5 * MS,
                                                106.5 * MS, 130.5 * MS]
    assert all(abs(lead - 1.5 * MS) < 1 and abs(slack - 0.1 * MS) < 1
               for _, lead, slack in leads)
    # device-only readings stay on the device's clock
    assert abs(trace.module_durations_s("_decode_fn")[0] - 0.045) < 1e-9


def test_a_stray_launch_moves_one_programs_lead_and_no_other():
    """A second launch just before a step's true start (a transfer, a
    program of another server) is taken for the step's own: that step's
    lead is read 0.05 ms high, and the median of the slice is as it
    was."""
    ctx = _ctx("serve")
    _host_line(ctx.trace, program_spans.LAUNCH).append(
        ("DoEnqueueProgram", 60.0 * MS, 60.05 * MS))
    leads = program_spans.device_leads(ctx.trace)
    assert [round(lead / MS, 6) for _, lead, _ in leads] \
        == [1.5, 1.55, 1.5, 1.5]
    program_spans.idle(ctx)
    assert [round(ms, 6) for ms in ctx.raw["device_lead_ms"]] \
        == [1.5, 1.5, 1.55, 0.1]


def test_a_program_without_a_pair_takes_its_neighbours_lead():
    trace, _ = _load("serve")
    del _host_line(trace, program_spans.NOTICE)[1]
    leads = program_spans.device_leads(trace)
    assert [start for start, _, _ in leads] == [8.5 * MS, 106.5 * MS,
                                                130.5 * MS]
    ctx = _ctx("serve")
    ctx.trace = trace
    want, _ = _idle("serve")
    assert program_spans.idle(ctx) == want


@pytest.mark.parametrize("name", GAP_READERS)
@pytest.mark.parametrize("why", ["no_launch_events", "device_late"])
def test_no_split_of_the_gap_where_the_lead_cannot_be_measured(name, why):
    """Without the lead the whole gap would fall under the span the host
    was in when the device's early stamps say the step ended: no number
    is better than that one."""
    ctx = _ctx("serve", **_serve_raw())
    if why == "no_launch_events":   # a runtime that names them otherwise
        for line in ctx.trace.planes["/host:CPU"].values():
            line[:] = [ev for ev in line if ev[0] not in (
                program_spans.LAUNCH, program_spans.NOTICE)]
    else:                   # stamped 3.2 ms later: past its own notice,
        dev = ctx.trace.planes["/device:TPU:0"]   # 45 ms before the next
        for line in dev.values():
            line[:] = [(n, s + 3.2 * MS, e + 3.2 * MS) for n, s, e in line]
    assert program_spans.device_leads(ctx.trace) is None
    assert _reader(name).compute(ctx) is None
    assert ctx.raw["device_lead_ms"] is None
    # the device's own reading needs no lead
    assert abs(_reader("decode_gap_ms").compute(ctx) - 3.5) < 1e-9


def test_idle_time_goes_to_the_innermost_span_piece_by_piece():
    idle, lead = _idle("serve")
    assert [round(ms, 6) for ms in lead] == [1.5, 1.5, 1.5, 0.1]
    want = {"decode.wait": 29.3, None: 0.2, "decode.tick": 0.2,
            "decode.reap": 0.4, "decode.admit": 0.9, "decode.prefill": 1.5,
            "decode.pages": 0.4, "decode.build": 1.1,
            "decode.dispatch": 2.6, "decode.readback": 1.9,
            "decode.emit": 3.3, "decode.record": 1.2}
    assert set(idle) == set(want)
    for name, ms in want.items():
        assert abs(idle[name] - ms * MS) < 1, (name, idle[name])
    assert abs(sum(idle.values()) - 43 * MS) < 1


def test_a_gap_under_no_span_is_nobodys():
    spans = program_spans.Spans([[("mx:a", 10.0, 20.0, {})]])
    assert spans.charged(0.0, 5.0) is None
    assert spans.attribute([(0.0, 30.0)]) == {None: 20.0, "a": 10.0}
    assert program_spans.Spans([]).attribute([(0.0, 4.0)]) == {None: 4.0}


def test_of_two_lines_the_span_that_began_last_is_charged():
    trace, spans = _load("train")
    idle = spans.attribute(trace_reduce.gaps(
        trace.busy(trace.devices[0]), *trace.window))
    want = {"pipeline.wait": 18.0, "pipeline.h2d": 9.0, "trainer.step": 1.0,
            "step.optimizer": 1.0, "fused_step.dispatch": 1.0, None: 20.0}
    assert set(idle) == set(want)
    for name, ms in want.items():
        assert abs(idle[name] - ms * MS) < 1, (name, idle[name])
    assert {sp.line for sp in spans.named("pipeline.h2d")} == {1}
    assert all(sp.parent is None for sp in spans.named("pipeline.h2d"))
    # its host plane has no launch or notice: no lead, so no idle split
    assert _idle("train") == (None, None)


# --- kernel_costs ----------------------------------------------------------

def test_a_kernels_name_gives_its_shapes_and_costs():
    name = ("%mx_flash_fwd.bh32.q512.k512.d128.bfloat16.3 = (bf16[32,512,"
            "128]{2,1,0}) custom-call(bf16[32,512,128] %q)")
    assert kernel_costs.shapes(name) == {"bh": 32, "q": 512, "k": 512,
                                         "d": 128}
    assert kernel_costs.shapes("%fusion.171 = f32[8] fusion()") is None
    grad = "%transpose_jvp_mx_flash_bwd_dq.bh32.q512.k512.d128.bfloat16__.1 ="
    assert kernel_costs.shapes(grad)["bh"] == 32
    assert re.search(kernel_costs.pattern("flash_bwd_dq"), grad)
    assert not re.search(kernel_costs.pattern("flash_bwd_dq"),
                         grad.replace("_dq", "_dkdv"))
    assert re.search(kernel_costs.pattern("flash_fwd"), name)
    assert not re.search(kernel_costs.pattern("flash_decode"),
                         "%mx_flash_decode_q8.bh8.q1.k256.d128.int8.1 = x")
    # two products of two operations over the lower triangle
    assert kernel_costs.causal_attention_flops(1, 4, 4, 8) == 4 * 10 * 8
    # a query block at the end of a longer key sequence sees all before it
    assert kernel_costs.causal_attention_flops(1, 2, 6, 1) == 4 * (5 + 6)
    assert kernel_costs.flash_decode_bytes(4, 4096, 1000, 4) \
        == 2 * 4 * 1000 * 4096 * 4


# --- the readers -----------------------------------------------------------

SERVE = {
    "decode_gap_ms": 3.5,           # 5 after a step, 2 after the prefill
    "gap_emit_ms": 6.4 / 3,
    "gap_admit_ms": 2.8 / 3,
    "gap_build_ms": 4.1 / 3,
    "gap_unattributed_share": 100 * 0.4 / 43,
    "prefill_stall_ms": 23.5,
    "queue_wait_mean_ms": 1e3 * 0.9 / 30,
    "prefill_mean_ms": 1e3 * 0.6 / 25,
    # 4 layers, 1000 live tokens a step: 131 MB at 819 GB/s = 0.16 ms,
    # against 2 ms a step inside the kernel
    "flash_decode_roofline_share":
        100 * (2 * 4 * 1000 * 4096 * 4 / 819e9) / 2e-3,
    "flash_fwd_roofline_share":
        100 * (4 * 4 * (256 * 257 // 2) * 128 / 197e12) / 2e-3,
}
TRAIN = {"h2d_ms_per_step": 33.0 / 2, "pipeline_wait_share": 10.0,
         "step_dispatch_ms": 4.0}


def _serve_raw():
    return dict(
        window_s=30.0,
        stats0={"decode_steps": 100, "admitted": 10, "queue_wait_s": 0.5,
                "prefill_steps": 10, "prefill_s": 0.25},
        stats1={"decode_steps": 102, "admitted": 40, "queue_wait_s": 1.4,
                "prefill_steps": 35, "prefill_s": 0.85},
        model={"n_layers": 4, "d_model": 4096},
        # one stream whose tokens 1 and 2 came from the window's two steps
        streams=[{"prompt_len": 998, "times": [0.1, 0.2, 0.3]}])


@pytest.mark.parametrize("name", sorted(SERVE))
def test_serving_reader_on_the_hand_written_slice(name):
    # tokens 1 and 2 attended to 999 and 1000 positions: 1999 / 2 steps
    want = SERVE[name]
    if name == "flash_decode_roofline_share":
        want *= 999.5 / 1000
    got = _reader(name).compute(_ctx("serve", **_serve_raw()))
    assert abs(got - want) <= 1e-9 * max(1.0, abs(want)), (got, want)


@pytest.mark.parametrize("name", sorted(TRAIN))
def test_training_reader_on_the_hand_written_slice(name):
    got = _reader(name).compute(_ctx("train", traced_steps=2))
    assert abs(got - TRAIN[name]) < 1e-9


@pytest.mark.parametrize("name", sorted(set(SERVE) | set(TRAIN)))
def test_reader_leaves_its_metric_out_where_the_program_has_no_spans(name):
    """The parent commit: the same device trace, no ``mx:`` span, no
    kernel of that name, no new counter."""
    which = "serve" if name in SERVE else "train"
    trace, _ = _load(which)
    for line in trace.planes["/device:TPU:0"].values():
        line[:] = [(n.replace("%mx_flash", "%branch_0_fun"), s, e)
                   for n, s, e in line]
    raw = _serve_raw() if which == "serve" else {"traced_steps": 2}
    for stats in ("stats0", "stats1"):
        if stats in raw:
            raw[stats] = {k: raw[stats][k]
                          for k in ("decode_steps", "prefill_steps")}
    ctx = _ctx(which, **raw)
    ctx.trace, ctx.program_spans = trace, program_spans.Spans([])
    got = _reader(name).compute(ctx)
    if name == "decode_gap_ms":         # the device's own: there already
        assert abs(got - 3.5) < 1e-9
    else:
        assert got is None
    untraced = types.SimpleNamespace(trace=None, program_spans=None,
                                     raw=raw, config=ctx.config, peak=None)
    assert _reader(name).compute(untraced) is None


def test_the_breakdowns_idle_gaps_name_the_programs_spans():
    """``breakdown.idle_gaps`` of a serving cell: the idle time of the
    recorded slice by the program's innermost span, the lead taken off,
    largest first; the benchmark's own spans said "scheduler" 40 ms and
    "submit" 3. Nothing where the lead cannot be measured (the recorded
    training slice) or the configuration names no step whose loop the
    leads are paired for: ``run.py`` then falls back to the ``bench:``
    spans, by what the trace and the configuration hold and by no flag."""
    ctx = _ctx("serve")
    gaps = program_spans.idle_gaps(ctx, 10)
    assert [name for name, _ in gaps[:2]] == ["decode.wait", "decode.emit"]
    assert gaps == sorted(gaps, key=lambda g: -g[1]) and len(gaps) == 10
    by = dict(program_spans.idle_gaps(ctx, 99))
    assert "scheduler" not in by and "unattributed" in by
    assert abs(sum(by.values()) - 0.043) < 1e-9
    emit = by["decode.readback"] + by["decode.emit"] + by["decode.record"]
    assert abs(emit - 6.4e-3) < 1e-9            # gap_emit_ms's three spans
    assert program_spans.idle_gaps(_ctx("train"), 10) is None
    unnamed = _ctx("serve")
    unnamed.config = {"trace_names": {}}
    assert program_spans.idle_gaps(unnamed, 10) is None
    assert ctx.trace.idle_gaps(10, unnamed="scheduler")[0][0] == "scheduler"


def test_rehearsal_prints_the_programs_own_spans_as_null():
    """On the CPU the profile has no device, so what needs device 0 is
    left out; what the program's spans and counters alone give is there,
    and null like every value of a rehearsal."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    want = {"opt-longprompt-steady": {"prefill_stall_ms", "prefill_mean_ms",
                                      "queue_wait_mean_ms"},
            "resnet50-train-b256": {"h2d_ms_per_step", "step_dispatch_ms",
                                    "pipeline_wait_share"}}
    for cell, names in want.items():
        proc = subprocess.run(
            [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
             "--workload", cell, "--seed", "5", "--trace", "1",
             "--rehearse"], capture_output=True, text=True, env=env,
            cwd=ROOT, timeout=900)
        assert proc.returncode == 0, proc.stderr[-2000:]
        metrics = json.loads(proc.stdout.strip().splitlines()[-1])["metrics"]
        assert names <= set(metrics), sorted(metrics)
        assert all(metrics[n]["value"] is None for n in names)
