"""The hybrid cell: its entries in BENCHMARK.json against the catalog's
row and the issue's cut, its traffic as the issue names it, its cost
functions against hand counts and its five readers on a trace written by
hand (a kernel that ran AT its roofline reads 100%, never more; a
program without the kernel, the state and the counters reads nothing and
raises nothing), the driver's draw of the gate's vectors, ``--rehearse``
of the cell, and ``--control`` through to ``correct: false``."""
import importlib
import json
import os
import subprocess
import sys
import types

import pytest

from benchmark import hybrid_linear_costs as costs
from benchmark import latent_moe_costs, program_spans, trace_reduce

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
CELL, CONFIG = "ling-hybrid-decode-batch", "Ling-3.0-flash"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
NEW_METRICS = {"kda_step_ms_per_step", "kda_step_roofline_share",
               "kda_prefill_ms", "recurrent_state_share",
               "hybrid_step_roofline_share"}
REDUCED = {"num_hidden_layers": 42, "first_k_dense_replace": 2,
           "num_experts": 512, "vocab_size": 157184}
# the model the driver describes at the cell's own sizes
MODEL = {"n_layers": 6, "d_model": 2560, "vocab": 39296,
         "n_dense_layers": 1, "n_moe_layers": 5, "d_ff": 6144,
         "d_expert": 768, "n_shared": 1, "experts_held": 128,
         "n_routed_experts": 512, "top_k": 8, "n_heads": 32, "q_rank": 0,
         "kv_rank": 512, "nope": 128, "rope": 64, "v_dim": 128,
         "window": 64}
PEAK = {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
ROW = 32 * 128 * 128 * 4               # one row's S in one layer


def _json(*parts):
    with open(os.path.join(ROOT, *parts)) as f:
        return json.load(f)


def _config():
    return _json("benchmark", "configs", CONFIG + ".json")


# --- the entries -----------------------------------------------------------

def test_the_configuration_keeps_every_published_number():
    spec = _json("BENCHMARK.json")
    entry = next(c for c in spec["configs"] if c["name"] == CONFIG)
    cfg = _config()
    assert entry["source"] == cfg["source"]
    assert entry["reduced"] == list(cfg["reduced_from"]) == list(REDUCED)
    assert cfg["reduced_from"] == REDUCED
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f) if r["name"] == CONFIG)
    assert entry["source"] == row["source_url"]
    for key, published in row["config"].items():
        if key in REDUCED:
            assert published == REDUCED[key] and cfg[key] < published, key
        else:
            assert cfg[key] == published, key
    assert (cfg["num_hidden_layers"], cfg["first_k_dense_replace"],
            cfg["num_experts"], cfg["vocab_size"]) == (6, 1, 128, 39296)
    # what the model is built with: the published widths, the router's
    # full width (the chip HOLDS 128 of its 512), one whole period
    kw = cfg["model"]["kwargs"]
    for key in ("hidden_size", "num_attention_heads", "head_dim",
                "layer_group_size", "kv_lora_rank", "qk_nope_head_dim",
                "qk_rope_head_dim", "v_head_dim", "intermediate_size",
                "moe_intermediate_size", "num_experts_per_tok", "n_group",
                "topk_group", "routed_scaling_factor", "rope_theta",
                "rope_scaling", "q_lora_rank", "short_conv_kernel_size",
                "kda_lower_bound", "kda_safe_gate", "no_kda_lora",
                "linear_silu", "use_qk_norm", "group_norm_size",
                "expert_swiglu_limit_list",
                "share_expert_swiglu_limit_list"):
        assert kw[key] == row["config"][key], key
    assert kw["num_experts"] == 512 and kw["ep"] == [0, 4]
    assert kw["num_shared_experts"] == row["config"]["num_shared_experts"]
    assert kw["num_hidden_layers"] == 6 == kw["layer_group_size"]
    assert kw["vocab_size"] == 39296 == 157184 // 4
    assert kw["num_nextn_predict_layers"] == 0      # left out: assumed
    assert not any(kw["expert_swiglu_limit_list"][:6]
                   + kw["share_expert_swiglu_limit_list"][:6])
    for said in ("next_token_module", "gate", "no_kda_lora", "use_qk_norm",
                 "group_norm_size", "rope_interleave", "swiglu_limit",
                 "A_log_dt_bias", "training_only", "kda_chunk_ops"):
        assert cfg["assumed"][said], said
    assert "depth 6" in cfg["why_reduced"].lower() \
        and "depth 7" in cfg["why_reduced"].lower()
    assert "4 chips" in cfg["deployment"]
    assert cfg["server"]["kwargs"] == {
        "seq_ladder": [1024], "max_new_tokens": 1024, "page_size": 128,
        "window": 64, "pool_pages": 1152, "max_queue": 128,
        "prefix_cache": False}


def test_the_cell_its_traffic_and_where_its_metrics_are_listed():
    spec = _json("BENCHMARK.json")
    cell = next(w for w in spec["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) \
        == (CONFIG, "hybrid-decode-batch-w64", 1)
    mix = _json("benchmark", "traffic", cell["traffic"] + ".json")
    assert mix["driver"] == "serve_hybrid_linear_moe"
    assert mix["arrivals"] == {"kind": "closed", "clients": 128}
    assert mix["prompt_len"] == {"dist": "lognormal", "median": 768,
                                 "sigma": 0.25, "min": 512, "max": 1024}
    same = _json("benchmark", "traffic", "decode-batch-w64.json")
    assert mix["output_len"] == same["output_len"] == {
        "dist": "lognormal", "median": 640, "sigma": 0.3, "min": 512,
        "max": 1024}
    assert mix["lead_in_s"] == 2.0 and mix["unfinished_at_end"] == "cut"
    assert mix["prompt_len"]["max"] <= max(
        _config()["server"]["kwargs"]["seq_ladder"])
    assert set(mix["check"]["limits"]) == {"gap_mean_std"}
    for m in spec["end_to_end"]:
        listed = CELL in m.get("workloads", [CELL])
        assert listed == (m["name"] in ("serve_tok_per_s", "itl_p99_ms",
                                        "setup_s")), m["name"]
    layer = {m["name"]: m for m in spec["per_layer"]}
    for name in ("kda_step_ms_per_step", "kda_step_roofline_share",
                 "kda_prefill_ms", "recurrent_state_share",
                 "hybrid_step_roofline_share"):
        assert layer[name]["workloads"] == [CELL], name
    # what the latent and speculative cells both read, this cell reads
    # too (the state form keeps its prefill program: the prefill's
    # readers stay, the chunk step's are not listed)
    for name, m in layer.items():
        if {"dots-decode-batch", "xing-specdecode-batch"} \
                <= set(m["workloads"]):
            assert CELL in m["workloads"], name
    for name in ("prefill_device_ms", "prefill_queue_ms", "admit_idle_ms",
                 "prefill_attn_ms"):
        assert CELL in layer[name]["workloads"], name
    for name in ("chunk_step_share", "chunk_step_device_ms"):
        assert CELL not in layer[name]["workloads"], name


# --- the costs -------------------------------------------------------------

def _ctx_sizes():
    return types.SimpleNamespace(config=_config())


def test_costs_against_hand_counts():
    s = costs.sizes(_ctx_sizes())
    assert s == {"d_model": 2560, "heads": 32, "d": 128,
                 "latent_layers": 1, "linear_layers": 5, "conv_rows": 3}
    assert costs.state_row_bytes(s) == ROW == 2097152
    small = 6 * 32 * 128 * 4
    assert costs.kda_step_bytes(s, 64) == 64 * 5 * (2 * ROW + small)
    assert costs.kda_step_flops(s, 64) == 64 * 5 * 32 * 7 * 128 * 128
    # bytes bind: 1.37 GB against 1.2 GFLOP
    assert costs.kda_step_bytes(s, 64) / 819e9 \
        > 100 * costs.kda_step_flops(s, 64) / 197e12
    assert costs.linear_attention_params(s) == 2560 * (
        12288 + 4096 + 64) + 4096 * 2560 == 52592640
    assert costs.latent_attention_params(MODEL, s) == (
        2560 * 32 * 192 + 2560 * 576 + 512 * 32 * 256 + 4096 * 2560
        + 2560 * 32) == 31965184
    ctx = types.SimpleNamespace(config=_config(), raw={"model": MODEL})
    expert = 3 * 2560 * 768 * 2
    assert latent_moe_costs.expert_bytes(MODEL) == expert
    matrices = 2 * (5 * 52592640 + 31965184 + 3 * 2560 * 6144
                    + 5 * 3 * 2560 * 768 + 2560 * 39296)
    router = 5 * 2560 * 512 * 4
    got = costs.step_bytes(ctx, 400, 64 * 1200, 64)
    assert got == (matrices + router + 400 * expert + 2 * 64 * 5 * ROW
                   + 2 * 64 * 5 * 3 * 12288 * 2 + 64 * 1200 * 576 * 2)
    # the state is about a fifth of a step's bytes
    assert 0.15 < 2 * 64 * 5 * ROW / got < 0.25


# --- the readers, on a trace written by hand --------------------------------

STEP = "jit__state_decode_fn(1)"
PREFILL = "jit__state_prefill_fn(2)"
KDA = "%mx_kda_step.b64.h32.d128.{n} = (f32[64,32,128]{{2,1,0}}, " \
      "f32[5,64,32,128,128]{{4,3,2,1,0}}) custom-call(...)"
SCAN = "%while.{n} = (s32[]{{:T(128)}}, f32[1,32,128,128]{{3,2,1,0}}, " \
       "f32[64,1,32,16,128]{{4,3,2,1,0}}) while(%tuple.{n})"
SOLVE = "%fusion.9{n} = f32[64,1,32,16,256]{{4,3,2,1,0}} fusion(%p.{n})"
OTHER = "%fusion.{n} = bf16[64,2560]{{1,0}} fusion(%p.{n})"
US = 1e3
ROWS, TOUCHED, LIVE = 60, 400, 64 * 1200


def _ctx(kda_us, step_us):
    ops, modules, t = [], [], 0.0

    def put(name, us):
        nonlocal t
        ops.append((name, t, t + us * US))
        t += us * US

    for s in range(2):
        start = t
        put(OTHER.format(n=s), 100)
        for layer in range(5):
            put(KDA.format(n=10 * s + layer), kda_us / 5)
        t = start + step_us * US
        modules.append((STEP, start, t))
        t += 500 * US
    start = t
    put(SOLVE.format(n=0), 300)
    put(OTHER.format(n=7), 5000)
    put(SCAN.format(n=1), 700)
    modules.append((PREFILL, start, t))
    planes = {"/device:TPU:0": {trace_reduce.MODULES_LINE: modules,
                                trace_reduce.OPS_LINE: ops}}
    lines = [[("mx:decode.readback", 10.0 + i, 20.0 + i,
               {"moe_slots": 120, "experts_touched": TOUCHED,
                "max_load": 3, "state_rows_live": ROWS})
              for i in range(2)]]
    streams = [{"prompt_len": 1199, "times": [-1.0, 0.1, 0.2]}] * 64
    assert sum(1199 + i for i in (1, 2)) * 64 == 2 * LIVE + 64
    cfg = _config()
    return types.SimpleNamespace(
        trace=trace_reduce.Trace(planes),
        program_spans=program_spans.Spans(lines), peak=PEAK, config=cfg,
        raw={"model": MODEL, "window_s": 30.0, "streams": streams,
             "stats0": {"decode_steps": 0},
             "stats1": {"decode_steps": 2,
                        "state": {"bytes": 64 * 5 * (ROW + 36864 * 2),
                                  "rows": 64, "rows_live": 64,
                                  "writes": 9},
                        "kv": {"used": 600, "page_size": 128,
                               "token_bytes": 1280}},
             "stats_delta": {"decode_steps": 2, "tokens_out": 121,
                             "prefill_steps": 1},
             "moe_delta": {"steps": 2, "moe_slots": 240,
                           "experts_touched": 2 * TOUCHED}})


def _read(name, ctx):
    return importlib.import_module(
        "benchmark.layer_metrics." + name).compute(ctx)


def test_readers_on_a_trace_in_which_the_kernel_ran_at_its_roofline():
    ctx = _ctx(1.0, 1.0)
    s = costs.sizes(ctx)
    kda_us = costs.kda_step_bytes(s, ROWS) / 819e9 * 1e6
    live = latent_moe_costs.live_tokens_per_step(ctx)
    step_us = costs.step_bytes(ctx, TOUCHED, live, ROWS) / 819e9 * 1e6
    assert kda_us + 100 < step_us
    ctx = _ctx(kda_us, step_us)
    assert abs(_read("kda_step_roofline_share", ctx) - 100.0) < 1e-6
    assert abs(_read("hybrid_step_roofline_share", ctx) - 100.0) < 1e-6
    assert abs(_read("kda_step_ms_per_step", ctx) - kda_us / 1e3) < 1e-9
    # the solve and the scan of the one prefill, not its other fusions
    assert abs(_read("kda_prefill_ms", ctx) - 1.0) < 1e-9
    state = 64 * 5 * (ROW + 36864 * 2)
    assert abs(_read("recurrent_state_share", ctx)
               - 100.0 * state / (state + 600 * 128 * 1280)) < 1e-9
    slow = _ctx(4 * kda_us, step_us + 3 * kda_us)
    assert abs(_read("kda_step_roofline_share", slow) - 25.0) < 1e-6
    # without the traced steps' own count: the window's
    ctx.program_spans = program_spans.Spans([[]])
    assert abs(costs.rows_live_per_step(ctx) - 60.0) < 1e-9


def test_readers_find_nothing_in_a_program_without_the_kernel():
    """A program that lacks what this configuration adds (the parent
    commit, another model): every new reader returns None and none
    raises."""
    ctx = _ctx(1000, 20000)
    ctx.trace = trace_reduce.Trace({"/device:TPU:0": {
        trace_reduce.MODULES_LINE: [(STEP, 0.0, 1e7)],
        trace_reduce.OPS_LINE: [(OTHER.format(n=0), 0.0, 1e6)]}})
    ctx.program_spans = program_spans.Spans([[]])
    ctx.raw.pop("moe_delta")
    ctx.raw.pop("stats_delta")
    ctx.raw["stats1"].pop("state")
    for name in sorted(NEW_METRICS):
        assert _read(name, ctx) is None, name
    ctx.trace = None
    ctx.config = _json("benchmark", "configs", "dots.vlm1.inst.json")
    for name in sorted(NEW_METRICS):
        assert _read(name, ctx) is None, name


# --- the driver ------------------------------------------------------------

def test_the_gates_vectors_are_drawn_and_everything_else_is_the_parents():
    import jax
    import numpy as np
    from benchmark.drivers import serve_hybrid_linear_moe as driver
    from benchmark.drivers import serve_latent_moe
    from benchmark import harness
    cfg = _config()
    tiny = cfg["tiny"]["model"]
    model = harness.load_object(tiny["import"])(**tiny["kwargs"])
    params = driver.make_params(model, cfg["weights"], 2 ** 31 + 5)
    plain = serve_latent_moe.make_params(model, cfg["weights"], 2 ** 31 + 5)
    assert sorted(params) == sorted(plain)
    for name in params:
        a = np.asarray(params[name].astype("float32"))
        tail = name.rsplit(".", 1)[-1]
        if tail in cfg["weights"]["vectors"]:
            lo, hi = cfg["weights"]["vectors"][tail]
            assert lo <= a.min() < a.max() <= hi and a.std() > 0.1, name
        else:
            assert (a == np.asarray(plain[name].astype("float32"))).all()
    # the draw the model's own init_params makes, and a step's alpha
    from mxnet_tpu.serving import hybrid_linear_moe
    assert cfg["weights"]["vectors"] == {
        "A_log": list(hybrid_linear_moe.A_LOG_RANGE),
        "dt_bias": list(hybrid_linear_moe.DT_BIAS_RANGE)}
    x = jax.random.normal(jax.random.PRNGKey(0), (64, model.d_model))
    g, _ = model._gates(0, x, params, np.ones((64,), bool))
    assert 0.9 < float(np.median(np.exp(np.asarray(g)))) < 0.999
    assert serve_latent_moe.make_params is driver._make_matrices


def _run(*args):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), *args],
        capture_output=True, text=True, env=env, cwd=ROOT, timeout=900)


def test_the_cell_rehearses_with_every_listed_metric_a_key():
    proc = _run("--workload", CELL, "--seed", str(2 ** 31 + 7),
                "--rehearse", "--trace", "1")
    assert proc.returncode == 0, proc.stderr[-2000:]
    lines = proc.stdout.strip().splitlines()
    result, detail = json.loads(lines[-1]), json.loads(lines[-2])
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] > 0 and result["rehearsal"] is True
    # what a CPU run can read: the program's counters, the state's among
    # them; every value null
    assert {"recurrent_state_share", "moe_experts_touched_share",
            "kv_preempted", "batch_occupancy"} <= set(result["metrics"])
    assert all(m["value"] is None for m in result["metrics"].values())
    state = detail["raw"]["stats1"]["state"] \
        if "stats1" in detail["raw"] else None
    assert state is None or state["rows"] == 4


@pytest.mark.parametrize("seed", [3, 2 ** 31 + 11])
def test_the_control_comes_out_not_correct(seed):
    """``run.py --control`` through the cell's own driver (tiny sizes):
    the lower-precision control in the program's place reads over the
    limit the same run's program passes; both controls are read."""
    proc = _run("--workload", CELL, "--seed", str(seed), "--rehearse",
                "--control")
    assert proc.returncode == 0, proc.stderr[-2000:]
    lines = proc.stdout.strip().splitlines()
    result, detail = json.loads(lines[-1]), json.loads(lines[-2])
    assert result["correct"] is False and result["failed"] == 0
    gap = result["compared"]["gap_mean_std"]
    assert gap["value"] > gap["limit"]
    check = detail["raw"]["check"]
    assert check["program"]["gap_mean_std"] <= gap["limit"]
    for sample in check["samples"]:
        assert sample["control"] == "float8"
        assert sample["state_bf16_mean"] >= 0 <= sample["float8_mean"]
        assert sample["control_mean"] == sample["float8_mean"]
