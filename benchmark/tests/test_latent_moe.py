"""The latent-attention / routed-expert cell: its entries in
BENCHMARK.json against the catalog's row and the issue's cut, its cost
functions and readers on a trace written by hand (a kernel that ran AT
its roofline reads 100%, never more), its driver's weights, and
``--control`` through to ``correct: false``."""
import importlib
import json
import os
import shutil
import subprocess
import sys
import types

import numpy as np
import pytest

from benchmark import latent_moe_costs as costs
from benchmark import program_spans, trace_reduce
from benchmark.drivers import serve_latent_moe

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
CELL, CONFIG = "dots-decode-batch", "dots.vlm1.inst"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
NEW_METRICS = {
    "moe_expert_ms_per_step", "moe_expert_roofline_share",
    "moe_route_ms_per_step", "moe_experts_touched_share",
    "moe_slot_imbalance", "mla_decode_ms_per_step",
    "mla_decode_roofline_share", "latent_step_roofline_share",
    "prefill_attn_ms"}
# the model the driver describes at the cell's own sizes
MODEL = {"n_layers": 6, "d_model": 7168, "vocab": 16160,
         "n_dense_layers": 1, "n_moe_layers": 5, "d_ff": 18432,
         "d_expert": 2048, "n_shared": 1, "experts_held": 16,
         "n_routed_experts": 256, "top_k": 8, "n_heads": 128,
         "q_rank": 1536, "kv_rank": 512, "nope": 128, "rope": 64,
         "v_dim": 128, "window": 64}
PEAK = {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def _json(*parts):
    with open(os.path.join(ROOT, *parts)) as f:
        return json.load(f)


# --- the entries -----------------------------------------------------------

def test_the_configuration_keeps_every_published_number():
    spec = _json("BENCHMARK.json")
    entry = next(c for c in spec["configs"] if c["name"] == CONFIG)
    cfg = _json(entry["file"])
    assert entry["source"] == cfg["source"]
    assert sorted(entry["reduced"]) == sorted(cfg["reduced_from"]) == [
        "first_k_dense_replace", "n_routed_experts", "num_hidden_layers",
        "vocab_size"]
    assert cfg["reduced_from"] == {
        "num_hidden_layers": 61, "first_k_dense_replace": 3,
        "n_routed_experts": 256, "vocab_size": 129280}
    assert (cfg["num_hidden_layers"], cfg["first_k_dense_replace"],
            cfg["n_routed_experts"], cfg["vocab_size"]) == (6, 1, 16, 16160)
    # the floors: a whole period and four layers after the dense ones,
    # eight experts a layer, an eighth of the vocabulary
    assert cfg["num_hidden_layers"] - cfg["first_k_dense_replace"] >= 4
    assert cfg["n_routed_experts"] >= 8
    assert cfg["vocab_size"] * 8 >= cfg["reduced_from"]["vocab_size"]
    if os.path.exists(CATALOG):
        with open(CATALOG) as f:
            row = next(r for r in map(json.loads, f)
                       if r["name"] == CONFIG)
        assert entry["source"] == row["source_url"]
        for key, value in row["config"].items():
            if key not in entry["reduced"]:
                assert cfg[key] == value, key
            else:
                assert cfg["reduced_from"][key] == value, key
    # what the model is built with: the published widths, the router's
    # full width, the chip's share of 16 a layer
    kw = cfg["model"]["kwargs"]
    for key in ("hidden_size", "num_attention_heads", "q_lora_rank",
                "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim",
                "v_head_dim", "intermediate_size", "moe_intermediate_size",
                "n_shared_experts", "num_experts_per_tok", "n_group",
                "topk_group", "routed_scaling_factor", "rope_scaling",
                "rope_theta", "num_hidden_layers", "first_k_dense_replace",
                "vocab_size"):
        assert kw[key] == cfg[key], key
    assert kw["n_routed_experts"] == 256 and kw["ep"] == [0, 16]
    assert (kw["hidden_size"], kw["num_attention_heads"], kw["q_lora_rank"],
            kw["kv_lora_rank"], kw["intermediate_size"],
            kw["moe_intermediate_size"]) == (7168, 128, 1536, 512, 18432,
                                             2048)
    assert cfg["bytes_per_value"] == {"weights": 2, "kv": 2}
    assert "16 chips share each layer" in cfg["deployment"]
    for key in ("vision_tower", "multi_token_prediction",
                "e_score_correction_bias", "precision", "weights"):
        assert key in cfg["assumed"]
    assert "GB" in cfg["why_reduced"] and "depth 7" in \
        cfg["why_reduced"].lower()
    srv = cfg["server"]["kwargs"]
    assert (srv["seq_ladder"], srv["max_new_tokens"], srv["page_size"],
            srv["window"], srv["pool_pages"]) == ([256], 1024, 128, 64, 768)


def test_the_cell_its_traffic_and_where_its_metrics_are_listed():
    spec = _json("BENCHMARK.json")
    cell = next(w for w in spec["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["chips"]) == (CONFIG, 1)
    assert CONFIG in [c["name"] for c in spec["configs"]]
    mix = _json("benchmark", "traffic", cell["traffic"] + ".json")
    assert mix["driver"] == "serve_latent_moe"
    assert mix["arrivals"] == {"kind": "closed", "clients": 128}
    assert mix["prompt_len"] == {"dist": "lognormal", "median": 160,
                                 "sigma": 0.4, "min": 64, "max": 256}
    assert mix["output_len"] == {"dist": "lognormal", "median": 640,
                                 "sigma": 0.3, "min": 512, "max": 1024}
    assert (mix["lead_in_s"], mix["unfinished_at_end"],
            mix["trace_s"]) == (2.0, "cut", 3)
    # the slice lies where a closed loop's first answers end: no answer
    # is under 512 tokens, so none ends (and no prefill runs) before then
    assert mix["trace_after_s"] == 12 and "prefill" in mix["trace_why"]
    # every prompt on the one rung; the server's queue holds every client
    cfg = _json("benchmark", "configs", CONFIG + ".json")
    srv = cfg["server"]["kwargs"]
    assert mix["prompt_len"]["max"] <= min(srv["seq_ladder"])
    assert mix["output_len"]["max"] <= srv["max_new_tokens"]
    assert srv["max_queue"] >= mix["arrivals"]["clients"]
    limit = mix["check"]["limits"]["gap_mean_std"]
    assert isinstance(limit, float) and 0 < limit < 0.05
    e2e = {m["name"]: m for m in spec["end_to_end"]}
    assert CELL in e2e["serve_tok_per_s"]["workloads"]
    assert CELL in e2e["itl_p99_ms"]["workloads"]
    assert (e2e["serve_tok_per_s"]["bound"], e2e["itl_p99_ms"]["bound"],
            e2e["setup_s"]["bound"]) == (0.04, 0.02, 0.1)
    layer = {m["name"]: m for m in spec["per_layer"]}
    assert NEW_METRICS <= set(layer)
    # prefill_attn_ms came with the cell and left it with its prefill
    assert all(CELL in layer[n]["workloads"]
               for n in NEW_METRICS - {"prefill_attn_ms"})
    assert layer["compiles_in_window"]["workloads"] == [
        w["name"] for w in spec["workloads"]]
    for name in ("decode_step_roofline_share", "flash_decode_roofline_share",
                 "flash_fwd_roofline_share"):
        assert CELL not in layer[name]["workloads"]     # they count OPT's
    for name in ("decode_step_device_ms", "decode_gap_ms", "chunk_step_share",
                 "batch_occupancy", "kv_preempted", "chunk_step_device_ms",
                 "serve_block_tok_per_s", "mosaic_time_share"):
        assert CELL in layer[name]["workloads"]
    # since PR 40 no prefill program runs here: what reads one lists
    # the cell no longer
    for name in ("prefill_device_ms", "prefill_queue_ms", "admit_idle_ms",
                 "prefill_attn_ms"):
        assert CELL not in layer[name]["workloads"]


# --- operations and bytes --------------------------------------------------

def test_costs_from_shapes():
    assert costs.expert_bytes(MODEL) == 3 * 7168 * 2048 * 2
    assert costs.latent_token_bytes(MODEL) == 1152
    assert costs.latent_token_flops(MODEL) == 2 * 128 * (576 + 512)
    assert costs.attention_params(MODEL) == (
        7168 * 1536 + 1536 * 128 * 192 + 7168 * 576 + 512 * 128 * 256
        + 16384 * 7168)
    # a step that touches no expert and holds no token reads the dense
    # part: attention, the dense MLP, five shared experts, the head, the
    # five float32 routers
    base = costs.step_bytes(MODEL, 0, 0)
    assert base == 2 * (6 * costs.attention_params(MODEL)
                        + 3 * 7168 * 18432 + 5 * 3 * 7168 * 2048
                        + 7168 * 16160) + 5 * 7168 * 256 * 4
    assert costs.step_bytes(MODEL, 70, 30000) - base \
        == 70 * costs.expert_bytes(MODEL) + 6 * 30000 * 1152


# --- the readers on a trace written by hand --------------------------------

US = 1e3        # times in ns
STEP = "jit__decode_fn(123)"
PREFILL = "jit__prefill_fn(456)"
GATED = ("%mx_grouped_matmul.e16.m768.k7168.n2048.bfloat16.gated.{n} = "
         "bf16[768,2048]{{1,0}} custom-call(s32[48]{{0}} %x)")
DOWN = ("%mx_grouped_matmul.e16.m768.k2048.n7168.bfloat16.{n} = "
        "f32[768,7168]{{1,0}} custom-call(s32[48]{{0}} %x)")
MLA = ("%mx_mla_decode.bh8192.q1.k1280.d640.bfloat16.r512.paged.{n} = "
       "f32[64,128,512]{{2,1,0}} custom-call(s32[640]{{0}} %t)")
FLASH = ("%mx_flash_fwd.bh128.q256.k256.d256.bfloat16.{n} = "
         "bf16[128,256,256]{{2,1,0}} custom-call(bf16[128,256,256] %q)")
SORT = "%sort.{n} = (f32[64,8,32]{{1,2,0}}, s32[64,8,32]) sort(f32[64,8,32] %c)"
GATE = "%fusion.{n} = f32[64,256]{{1,0}} fusion(f32[64,7168] %x), kind=kOutput"
OTHER = "%fusion.9{n} = f32[64,7168]{{1,0}} fusion(f32[64,7168] %h)"
TOUCHED, LIVE = 60, 32032      # experts a step (5 layers), live tokens


def _ctx(expert_us, mla_us, step_us):
    """Two decode steps and one prefill on device 0. In each step: the
    grouped matmuls take ``expert_us`` in all, the latent kernel
    ``mla_us``, the router 50 us, the step ``step_us``. The prefill's
    expert kernel and its sort must not count for the step."""
    ops, modules, t = [], [], 0.0

    def put(name, us):
        nonlocal t
        ops.append((name, t, t + us * US))
        t += us * US

    for s in range(2):
        start = t
        put(OTHER.format(n=s), 100)
        put(GATE.format(n=s), 20)
        put(SORT.format(n=s), 30)
        put(GATED.format(n=s), 0.6 * expert_us)
        put(DOWN.format(n=s), 0.4 * expert_us)
        put(MLA.format(n=s), mla_us)
        t = start + step_us * US
        modules.append((STEP, start, t))
        t += 500 * US                       # the host's gap
    start = t
    put(FLASH.format(n=0), 300)
    put(FLASH.format(n=1), 500)
    put(GATED.replace("m768", "m2304").format(n=7), 5000)
    put(SORT.format(n=7), 40)
    modules.append((PREFILL, start, t))
    planes = {"/device:TPU:0": {trace_reduce.MODULES_LINE: modules,
                                trace_reduce.OPS_LINE: ops}}
    lines = [[("mx:decode.readback", 10.0 + i, 20.0 + i,
               {"moe_slots": 160, "experts_touched": TOUCHED,
                "max_load": 8}) for i in range(2)]]
    # the window's streams: one token a stream a step, LIVE cached tokens
    # a step in all (64 streams of prompt 499, tokens 1 and 2 in the window)
    streams = [{"prompt_len": 499, "times": [-1.0, 0.1, 0.2]}] * 64
    assert sum(499 + i for i in (1, 2)) * 64 == 2 * LIVE
    return types.SimpleNamespace(
        trace=trace_reduce.Trace(planes),
        program_spans=program_spans.Spans(lines), peak=PEAK,
        config={"trace_names": {
            "step_module": "_decode_fn", "prefill_module": "_prefill_fn",
            "expert_kernel": "grouped_matmul", "latent_kernel": "mla_decode",
            "prefill_attn_kernel": "flash_fwd",
            "route_ops": ["^%sort", " = \\(?[a-z0-9]+\\[64,256\\]"]},
            "bytes_per_value": {"weights": 2, "kv": 2}},
        raw={"model": MODEL, "window_s": 30.0, "streams": streams,
             "stats0": {"decode_steps": 0}, "stats1": {"decode_steps": 2},
             "moe_delta": {"steps": 2, "moe_slots": 320,
                           "experts_touched": 2 * TOUCHED}})


def _read(name, ctx):
    return importlib.import_module(
        "benchmark.layer_metrics." + name).compute(ctx)


def test_readers_on_a_trace_in_which_every_kernel_ran_at_its_roofline():
    """No share may read over 100%: with each kernel's time set to the
    least the chip could take for what the step touched, each share
    reads 100 and not a hair more."""
    expert_us = TOUCHED * costs.expert_bytes(MODEL) / 819e9 * 1e6
    mla_us = 6 * LIVE * max(1152 / 819e9, 2 * 128 * 1088 / 197e12) * 1e6
    step_us = costs.step_bytes(MODEL, TOUCHED, LIVE) / 819e9 * 1e6
    assert expert_us + mla_us + 150 < step_us
    ctx = _ctx(expert_us, mla_us, step_us)
    assert abs(_read("moe_expert_roofline_share", ctx) - 100.0) < 1e-6
    assert abs(_read("mla_decode_roofline_share", ctx) - 100.0) < 1e-6
    assert abs(_read("latent_step_roofline_share", ctx) - 100.0) < 1e-6
    assert abs(_read("moe_expert_ms_per_step", ctx) - expert_us / 1e3) < 1e-9
    assert abs(_read("mla_decode_ms_per_step", ctx) - mla_us / 1e3) < 1e-9
    # the router: the product and the sort of each step, not the prefill's
    assert abs(_read("moe_route_ms_per_step", ctx) - 0.050) < 1e-9
    # the two flash calls of the one prefill
    assert abs(_read("prefill_attn_ms", ctx) - 0.8) < 1e-9
    assert abs(_read("moe_experts_touched_share", ctx)
               - 100.0 * TOUCHED / 80) < 1e-9
    assert abs(_read("moe_slot_imbalance", ctx) - 8 / (160 / 80)) < 1e-9
    # a slower kernel reads a smaller share, in proportion
    slow = _ctx(2 * expert_us, 4 * mla_us, step_us + 6 * expert_us)
    assert abs(_read("moe_expert_roofline_share", slow) - 50.0) < 1e-6
    assert abs(_read("mla_decode_roofline_share", slow) - 25.0) < 1e-6


def test_the_experts_bytes_are_those_touched_never_all_held():
    expert_us = TOUCHED * costs.expert_bytes(MODEL) / 819e9 * 1e6
    ctx = _ctx(expert_us, 100, 20000)
    ctx.program_spans = program_spans.Spans([[
        ("mx:decode.readback", 10.0, 20.0, {
            "moe_slots": 160, "experts_touched": 40, "max_load": 8})]])
    assert abs(_read("moe_expert_roofline_share", ctx)
               - 100.0 * 40 / TOUCHED) < 1e-6
    # the trace has no counts (the spans of another program): the
    # window's own, from stats()["moe"]
    ctx.program_spans = program_spans.Spans([[]])
    assert abs(_read("moe_expert_roofline_share", ctx) - 100.0) < 1e-6
    assert _read("moe_slot_imbalance", ctx) is None


def test_readers_find_nothing_in_a_program_without_the_kernels():
    """A program that lacks what this configuration adds (the parent
    commit): every new reader returns None and none raises."""
    ctx = _ctx(1000, 100, 20000)
    ctx.trace = trace_reduce.Trace({"/device:TPU:0": {
        trace_reduce.MODULES_LINE: [(STEP, 0.0, 1e7)],
        trace_reduce.OPS_LINE: [(OTHER.format(n=0), 0.0, 1e6)]}})
    ctx.program_spans = program_spans.Spans([[]])
    ctx.raw.pop("moe_delta")
    for name in sorted(NEW_METRICS):
        assert _read(name, ctx) is None, name
    ctx.trace = None
    for name in sorted(NEW_METRICS):
        assert _read(name, ctx) is None, name


# --- the driver ------------------------------------------------------------

def test_a_stack_of_experts_is_scaled_by_its_fan_in_not_its_count():
    from mxnet_tpu.serving.latent_moe import LatentMoEDecoderLM
    cfg = _json("benchmark", "configs", CONFIG + ".json")
    kw = dict(cfg["tiny"]["model"]["kwargs"], hidden_size=256,
              moe_intermediate_size=64)
    model = LatentMoEDecoderLM(**kw)
    params = serve_latent_moe.make_params(model, cfg["weights"], 2 ** 31 + 7)
    stack = np.asarray(params["l1.experts.w_gate"], np.float32)
    assert stack.shape == (16, 256, 64)
    assert abs(stack.std() * 256 ** 0.5 - 1.0) < 0.05    # not 16 ** -0.5
    assert abs(np.asarray(params["l1.experts.w_down"],
                          np.float32).std() * 64 ** 0.5 - 1.0) < 0.05
    assert abs(np.asarray(params["embed"], np.float32).std() - 1.0) < 0.05
    assert params["l1.router_w"].dtype == np.float32
    assert not np.asarray(params["l1.router_b"]).any()
    assert (np.asarray(params["l1.ffn_g"]) == 1).all()
    assert params["l1.wq_a"].dtype.name == "bfloat16"
    again = serve_latent_moe.make_params(model, cfg["weights"], 2 ** 31 + 7)
    assert (np.asarray(again["head"], np.float32)
            == np.asarray(params["head"], np.float32)).all()


def _run(*args, root=ROOT):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    return subprocess.run(
        [sys.executable, os.path.join(root, "benchmark", "run.py"), *args],
        capture_output=True, text=True, env=env, cwd=root, timeout=900)


@pytest.mark.parametrize("seed", [3, 2 ** 31 + 11])
def test_the_float8_control_comes_out_not_correct(seed, tmp_path):
    """``run.py --control`` through the cell's own driver (tiny sizes):
    the float8 control in the program's place reads over the limit the
    same run's program passes, and what is compared are ITS numbers.
    Run from a copy whose tiny sample is 40 finished requests (650
    tokens) where the cell's rehearsal takes 3: on 60 tokens the verdict
    hung on which requests a two-second window finished (PERF.md section
    7, PR 27); the cell's own traffic file is as it was."""
    root = str(tmp_path)
    shutil.copytree(BENCH, os.path.join(root, "benchmark"),
                    ignore=shutil.ignore_patterns("out", "__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
    os.symlink(os.path.join(ROOT, "mxnet_tpu"),
               os.path.join(root, "mxnet_tpu"))
    cell = next(w for w in _json("BENCHMARK.json")["workloads"]
                if w["name"] == CELL)
    path = os.path.join(root, "benchmark", "traffic",
                        cell["traffic"] + ".json")
    mix = _json("benchmark", "traffic", cell["traffic"] + ".json")
    mix["tiny"]["check"]["requests"] = 40
    with open(path, "w") as f:
        json.dump(mix, f)
    proc = _run("--workload", CELL, "--seed", str(seed), "--rehearse",
                "--control", root=root)
    assert proc.returncode == 0, proc.stderr[-2000:]
    lines = proc.stdout.strip().splitlines()
    result, detail = json.loads(lines[-1]), json.loads(lines[-2])
    assert result["correct"] is False and result["failed"] == 0
    gap = result["compared"]["gap_mean_std"]
    assert gap["value"] > gap["limit"]
    check = detail["raw"]["check"]
    assert check["tokens"] >= 300
    assert check["readings"]["gap_mean_std"] == gap["value"]
    assert check["program"]["gap_mean_std"] <= gap["limit"]
    assert 0.0 <= check["routing_differs_share"] <= 1.0
    assert "compared gap_mean_std" in proc.stderr
