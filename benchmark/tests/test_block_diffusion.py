"""The block-diffusion cell: its entries in BENCHMARK.json against the
catalog's row and the issue's cut, its traffic as the issue names it, its
cost functions and readers on a trace written by hand (a kernel that ran
AT its roofline reads 100%, never more; a program without the kernels
reads nothing and raises nothing), ``--rehearse`` of the cell, and
``--control`` through to ``correct: false``."""
import importlib
import json
import os
import subprocess
import sys
import types

import pytest

from benchmark import block_diffusion_costs as costs
from benchmark import program_spans, trace_reduce

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
CELL, CONFIG = "sdar-blockdiff-batch", "SDAR-30B-A3B-Chat"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
NEW_METRICS = {"block_decode_ms_per_step", "block_decode_roofline_share",
               "block_step_roofline_share", "block_passes_per_token"}
APPENDED = {"serve_block_tok_per_s", "serve_stall_share", "batch_occupancy",
            "prefill_step_share", "kv_pages_peak_share", "kv_preempted",
            "decode_step_device_ms", "mosaic_time_share",
            "compiles_in_window", "decode_gap_ms", "queue_wait_mean_ms",
            "decode_ahead_share", "moe_expert_ms_per_step",
            "moe_expert_roofline_share", "moe_route_ms_per_step",
            "moe_experts_touched_share", "moe_slot_imbalance"}
# the model the driver describes at the cell's own sizes
MODEL = {"n_layers": 7, "d_model": 2048, "vocab": 151936, "n_moe_layers": 7,
         "d_expert": 768, "experts_held": 128, "n_routed_experts": 128,
         "top_k": 8, "n_heads": 32, "n_kv_heads": 4, "head_dim": 128,
         "block_length": 4, "window": 32}
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
    assert entry["reduced"] == list(cfg["reduced_from"]) \
        == ["num_hidden_layers"]
    assert cfg["reduced_from"] == {"num_hidden_layers": 48}
    assert cfg["num_hidden_layers"] == 7
    if os.path.exists(CATALOG):
        with open(CATALOG) as f:
            row = next(r for r in map(json.loads, f)
                       if r["name"] == CONFIG)
        assert entry["source"] == row["source_url"]
        for key, value in row["config"].items():
            if key in entry["reduced"]:
                assert cfg["reduced_from"][key] == value, key
            else:
                assert cfg[key] == value, key
    # what the model is built with: every published width, every expert,
    # every row of the vocabulary
    kw = cfg["model"]["kwargs"]
    for key in ("hidden_size", "num_attention_heads", "num_key_value_heads",
                "head_dim", "moe_intermediate_size", "num_experts",
                "num_experts_per_tok", "norm_topk_prob", "rope_theta",
                "rms_norm_eps", "vocab_size", "num_hidden_layers",
                "max_position_embeddings"):
        assert kw[key] == cfg[key], key
    assert (kw["hidden_size"], kw["num_attention_heads"],
            kw["num_key_value_heads"], kw["head_dim"],
            kw["moe_intermediate_size"], kw["num_experts"],
            kw["num_experts_per_tok"], kw["vocab_size"]) \
        == (2048, 32, 4, 128, 768, 128, 8, 151936)
    assert kw["ep"] == [0, 1]
    assert (kw["block_length"], kw["denoising_steps"],
            kw["remasking_strategy"], kw["confidence_threshold"]) \
        == (4, 4, "low_confidence_dynamic", 0.9)
    assert 0 <= kw["mask_token_id"] < kw["vocab_size"]
    assert cfg["bytes_per_value"] == {"weights": 2, "kv": 2}
    assert "7 stages" in cfg["deployment"]
    for key in ("block_length", "denoising_steps", "remasking_strategy",
                "confidence_threshold", "mask_token_id", "no_shift",
                "qk_norm", "rope_pairing", "precision", "weights"):
        assert key in cfg["assumed"], key
    why = cfg["why_reduced"]
    assert "GB" in why and "Depth 8" in why and "7 of the 48" in why
    srv = cfg["server"]["kwargs"]
    assert (srv["seq_ladder"], srv["page_size"], srv["window"],
            srv["max_new_tokens"], srv["pool_pages"], srv["max_queue"]) \
        == ([256], 128, 32, 1536, 512, 64)
    assert cfg["reference"]["import"] \
        == "benchmark.reference.block_diffusion_lm"
    for key in ("prefill_module", "step_module", "expert_kernel",
                "block_kernel", "route_ops"):
        assert key in cfg["trace_names"]


def test_the_cell_its_traffic_and_where_its_metrics_are_listed():
    spec = _json("BENCHMARK.json")
    cell = next(w for w in spec["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) \
        == (CONFIG, "blockdiff-batch-w32", 1)
    assert sum(w["config"] == CONFIG for w in spec["workloads"]) == 1
    mix = _json("benchmark", "traffic", cell["traffic"] + ".json")
    assert mix["driver"] == "serve_block_diffusion"
    assert mix["arrivals"] == {"kind": "closed", "clients": 64}
    assert mix["prompt_len"] == {"dist": "lognormal", "median": 160,
                                 "sigma": 0.4, "min": 64, "max": 256}
    assert mix["output_len"] == {"dist": "lognormal", "median": 896,
                                 "sigma": 0.25, "min": 640, "max": 1536}
    assert (mix["lead_in_s"], mix["unfinished_at_end"],
            mix["trace_s"]) == (2.0, "cut", 3)
    assert mix["trace_after_s"] >= 9 and "prefill" in mix["trace_why"]
    cfg = _json("benchmark", "configs", CONFIG + ".json")
    srv = cfg["server"]["kwargs"]
    assert mix["prompt_len"]["max"] <= min(srv["seq_ladder"])
    assert mix["output_len"]["max"] <= srv["max_new_tokens"]
    assert srv["max_queue"] >= mix["arrivals"]["clients"]
    assert mix["arrivals"]["clients"] == 2 * srv["window"]
    # every row at its longest fits the pool: nothing is preempted
    pages = -(-(max(srv["seq_ladder"]) + srv["max_new_tokens"])
              // srv["page_size"])
    assert srv["window"] * pages < srv["pool_pages"]
    check = mix["check"]
    assert (check["requests"], check["min_tokens"]) == (2, 1500)
    limit = check["limits"]["gap_mean_std"]
    assert isinstance(limit, float) and 0 < limit < 0.05
    assert set(check["limits"]) == {"gap_mean_std"}
    assert "float8" in check["why"] and "chip" in check["why"]
    e2e = {m["name"]: m for m in spec["end_to_end"]}
    assert CELL in e2e["serve_tok_per_s"]["workloads"]
    assert CELL in e2e["itl_p99_ms"]["workloads"]
    assert (e2e["serve_tok_per_s"]["bound"], e2e["itl_p99_ms"]["bound"],
            e2e["setup_s"]["bound"]) == (0.04, 0.02, 0.1)
    layer = {m["name"]: m for m in spec["per_layer"]}
    assert NEW_METRICS <= set(layer)
    assert all(layer[n]["workloads"] == [CELL] for n in NEW_METRICS)
    assert all(layer[n]["moves"] == "serve_tok_per_s" for n in NEW_METRICS)
    listed = {n for n, m in layer.items() if CELL in m["workloads"]}
    assert NEW_METRICS | APPENDED <= listed
    # the other models' rooflines count their own kernels
    for name in ("decode_step_roofline_share", "latent_step_roofline_share",
                 "mla_decode_roofline_share", "flash_decode_roofline_share"):
        assert CELL not in layer[name]["workloads"], name


# --- operations and bytes --------------------------------------------------

def test_costs_from_shapes():
    assert costs.expert_bytes(MODEL) == 3 * 2048 * 768 * 2
    assert costs.kv_token_bytes(MODEL) == 2 * 4 * 128 * 2
    assert 7 * costs.kv_token_bytes(MODEL) == 14336   # stats()["kv"]
    assert costs.kv_token_flops(MODEL) == 2 * 2 * 4 * 32 * 128
    assert costs.attention_params(MODEL) \
        == 2048 * 4096 * 2 + 2048 * 512 * 2
    base = costs.step_bytes(MODEL, 0, 0)
    assert base == 2 * (7 * costs.attention_params(MODEL)
                        + 2048 * 151936) + 7 * 2048 * 128 * 4
    assert costs.step_bytes(MODEL, 890, 20000) - base \
        == 890 * costs.expert_bytes(MODEL) + 7 * 20000 * 2048
    # every expert touched and 20,000 live keys: the issue's 9.0 GB of
    # matrices and 0.3 GB of keys and values
    whole = costs.step_bytes(MODEL, 7 * 128, 20000)
    assert 9.3e9 < whole < 9.7e9


# --- the readers on a trace written by hand --------------------------------

US = 1e3        # times in ns
STEP = "jit__block_decode_fn(123)"
PREFILL = "jit__block_prefill_fn(456)"
GATED = ("%mx_grouped_matmul.e128.m3072.k2048.n768.bfloat16.gated.{n} = "
         "bf16[3072,768]{{1,0}} custom-call(s32[192]{{0}} %x)")
DOWN = ("%mx_grouped_matmul.e128.m3072.k768.n2048.bfloat16.{n} = "
        "f32[3072,2048]{{1,0}} custom-call(s32[192]{{0}} %x)")
BLOCK = ("%mx_block_decode.bh1024.q4.k1792.d128.bfloat16.kv4.paged.{n} = "
         "f32[32,4,32,128]{{3,2,1,0}} custom-call(s32[448]{{0}} %t)")
SORT = "%sort.{n} = (f32[128,128]{{1,0}}, s32[128,128]) sort(f32[128,128] %c)"
OTHER = "%fusion.9{n} = f32[128,2048]{{1,0}} fusion(f32[128,2048] %h)"
TOUCHED, LIVE = 880, 21000      # experts a step (7 layers), live keys


def _ctx(expert_us, block_us, step_us):
    """Two block steps and one prefill on device 0. In each step the
    grouped matmuls take ``expert_us`` in all, the block-decode kernel
    ``block_us``, the step ``step_us``; the prefill's expert kernel must
    not count for the step."""
    ops, modules, t = [], [], 0.0

    def put(name, us):
        nonlocal t
        ops.append((name, t, t + us * US))
        t += us * US

    for s in range(2):
        start = t
        put(OTHER.format(n=s), 100)
        put(SORT.format(n=s), 30)
        put(GATED.format(n=s), 0.6 * expert_us)
        put(DOWN.format(n=s), 0.4 * expert_us)
        put(BLOCK.format(n=s), block_us)
        t = start + step_us * US
        modules.append((STEP, start, t))
    start = t
    put(GATED.replace("m3072", "m4096").format(n=7), 5000)
    modules.append((PREFILL, start, t))
    planes = {"/device:TPU:0": {trace_reduce.MODULES_LINE: modules,
                                trace_reduce.OPS_LINE: ops}}
    lines = [[("mx:decode.readback", 10.0 + i, 20.0 + i,
               {"moe_slots": 7168, "experts_touched": TOUCHED,
                "max_load": 20, "tokens_unmasked": 26,
                "blocks_committed": 6}) for i in range(2)]
             + [("mx:decode.dispatch", 30.0 + i, 31.0 + i,
                 {"pages_live": 180, "ahead": 1, "keys_live": LIVE,
                  "committing": 0, "denoising": 2, "undecided": 30})
                for i in range(2)]]
    return types.SimpleNamespace(
        trace=trace_reduce.Trace(planes),
        program_spans=program_spans.Spans(lines), peak=PEAK,
        config={"trace_names": {
            "step_module": "_block_decode_fn",
            "prefill_module": "_block_prefill_fn",
            "expert_kernel": "grouped_matmul", "block_kernel": "block_decode",
            "route_ops": ["^%sort"]},
            "bytes_per_value": {"weights": 2, "kv": 2}},
        raw={"model": MODEL, "window_s": 30.0,
             "stats0": {"decode_steps": 0, "tokens_out": 10},
             "stats1": {"decode_steps": 2, "tokens_out": 62},
             "moe_delta": {"steps": 2, "moe_slots": 14336,
                           "experts_touched": 2 * TOUCHED},
             "block_delta": {"denoise_passes": 52, "commit_passes": 13,
                             "tokens_unmasked": 52,
                             "blocks_committed": 13}})


def _read(name, ctx):
    return importlib.import_module(
        "benchmark.layer_metrics." + name).compute(ctx)


def test_readers_on_a_trace_in_which_every_kernel_ran_at_its_roofline():
    """No share may read over 100%: with each kernel's time set to the
    least the chip could take for what the step touched, each share
    reads 100 and not a hair more."""
    expert_us = TOUCHED * costs.expert_bytes(MODEL) / 819e9 * 1e6
    block_us = 7 * LIVE * max(2048 / 819e9, 65536 / 197e12) * 1e6
    step_us = costs.step_bytes(MODEL, TOUCHED, LIVE) / 819e9 * 1e6
    assert expert_us + block_us + 130 < step_us
    ctx = _ctx(expert_us, block_us, step_us)
    assert abs(_read("block_decode_roofline_share", ctx) - 100.0) < 1e-6
    assert abs(_read("block_step_roofline_share", ctx) - 100.0) < 1e-6
    assert abs(_read("moe_expert_roofline_share", ctx) - 100.0) < 1e-6
    assert abs(_read("block_decode_ms_per_step", ctx) - block_us / 1e3) \
        < 1e-9
    assert abs(_read("moe_expert_ms_per_step", ctx) - expert_us / 1e3) < 1e-9
    assert abs(_read("moe_route_ms_per_step", ctx) - 0.030) < 1e-9
    assert abs(_read("block_passes_per_token", ctx) - 65 / 52) < 1e-12
    assert abs(_read("moe_experts_touched_share", ctx)
               - 100.0 * TOUCHED / 896) < 1e-9
    assert abs(_read("moe_slot_imbalance", ctx) - 20 / 8) < 1e-9
    assert abs(_read("decode_step_device_ms", ctx) - step_us / 1e3) < 1e-9
    # a slower kernel reads a smaller share, in proportion
    slow = _ctx(2 * expert_us, 4 * block_us, 2 * step_us)
    assert abs(_read("block_decode_roofline_share", slow) - 25.0) < 1e-6
    assert abs(_read("block_step_roofline_share", slow) - 50.0) < 1e-6
    assert abs(_read("moe_expert_roofline_share", slow) - 50.0) < 1e-6


def test_readers_find_nothing_in_a_program_without_the_kernels():
    """A program that lacks what this configuration adds (the parent
    commit): every new reader returns None and none raises."""
    ctx = _ctx(1000, 100, 20000)
    ctx.trace = trace_reduce.Trace({"/device:TPU:0": {
        trace_reduce.MODULES_LINE: [("jit__decode_fn(1)", 0.0, 1e7)],
        trace_reduce.OPS_LINE: [(OTHER.format(n=0), 0.0, 1e6)]}})
    ctx.program_spans = program_spans.Spans([[
        ("mx:decode.dispatch", 1.0, 2.0, {"pages_live": 9, "ahead": 1})]])
    ctx.raw.pop("block_delta")
    for name in sorted(NEW_METRICS):
        assert _read(name, ctx) is None, name
    ctx.trace = None
    ctx.program_spans = None
    for name in sorted(NEW_METRICS):
        assert _read(name, ctx) is None, name


# --- the driver ------------------------------------------------------------

def _run(*args):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    return subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), *args],
        capture_output=True, text=True, env=env, cwd=ROOT, timeout=900)


def test_the_cell_rehearses_with_every_listed_metric_a_key():
    proc = _run("--workload", CELL, "--seed", str(2 ** 31 + 5), "--rehearse",
                "--trace", "1")
    assert proc.returncode == 0, proc.stderr[-2000:]
    lines = proc.stdout.strip().splitlines()
    result, detail = json.loads(lines[-1]), json.loads(lines[-2])
    assert result["correct"] is True and result["failed"] == 0
    assert result["rehearsal"] is True
    # what a CPU run can read is there (the device's are left out), and
    # every value is null: a rehearsal is never a number
    assert {"block_passes_per_token", "batch_occupancy",
            "decode_ahead_share", "moe_experts_touched_share",
            "kv_preempted"} <= set(result["metrics"])
    assert all(m["value"] is None for m in result["metrics"].values())
    raw = detail["raw"]
    block = raw["block_delta"]
    # one position a pass under seeded random weights, a commit a block:
    # since PR 36 it rides with the next block's first denoising pass
    # (``fused_commits``) unless the row has ended (``commit_passes``)
    assert block["tokens_unmasked"] == block["denoise_passes"] > 0
    assert block["blocks_committed"] \
        == block["fused_commits"] + block["commit_passes"] > 0
    assert raw["compiles_in_window"] == 0
    assert raw["check"]["tokens"] > 300
    assert 0.0 <= raw["check"]["unmask_differs_share"] <= 1.0
    # no prompt token is the mask token: ids skip it
    assert raw["model"]["block_length"] == 4


@pytest.mark.parametrize("seed", [3, 2 ** 31 + 11])
def test_the_float8_control_comes_out_not_correct(seed):
    """``run.py --control`` through the cell's own driver (tiny sizes, a
    sample of 40 requests, so that the verdict does not hang on which
    of them finish): the float8 control in the program's place reads
    over the limit the same run's program passes, and what is compared
    are ITS numbers."""
    proc = _run("--workload", CELL, "--seed", str(seed), "--rehearse",
                "--control")
    assert proc.returncode == 0, proc.stderr[-2000:]
    lines = proc.stdout.strip().splitlines()
    result, detail = json.loads(lines[-1]), json.loads(lines[-2])
    assert result["correct"] is False and result["failed"] == 0
    gap = result["compared"]["gap_mean_std"]
    assert gap["value"] > gap["limit"]
    check = detail["raw"]["check"]
    assert check["readings"]["gap_mean_std"] == gap["value"]
    assert check["program"]["gap_mean_std"] <= gap["limit"]
    assert len(check["samples"]) >= 20
    assert 0.0 <= check["routing_differs_share"] <= 1.0
    assert "compared gap_mean_std" in proc.stderr
