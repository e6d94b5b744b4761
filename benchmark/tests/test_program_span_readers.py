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
    # heads handed to the kernel zero-padded: scores 192 wide, values 128
    assert kernel_costs.causal_attention_flops(1, 4, 4, 192, 128) \
        == 2 * 10 * (192 + 128)
    # 10 live pages of 128 tokens, K and V, 4 layers of 4096 float32
    assert kernel_costs.paged_decode_bytes(4, 4096, 10, 128, 4) \
        == 2 * 4 * 1280 * 4096 * 4


# --- the readers -----------------------------------------------------------

SERVE = {
    "decode_gap_ms": 3.5,           # 5 after a step, 2 after the prefill
    "queue_wait_mean_ms": 1e3 * 0.9 / 30,
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
    want = SERVE[name]
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


def test_rehearsal_prints_the_programs_own_spans_as_null():
    """On the CPU the profile has no device, so what needs device 0 is
    left out; what the program's spans and counters alone give is there,
    and null like every value of a rehearsal."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    want = {"opt-longprompt-steady": {"queue_wait_mean_ms", "chunk_step_share",
                                      "decode_ahead_share"},
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
