"""The speculative cell: its entries in BENCHMARK.json against the
catalog's row and the issue's cut, its traffic as the issue names it, its
cost functions against hand counts and its four readers on a trace
written by hand (a kernel that ran AT its roofline reads 100%, never
more; a program without the kernels and counters reads nothing and
raises nothing), ``--rehearse`` of the cell, and ``--control`` through
to ``correct: false`` for both controls."""
import importlib
import json
import os
import subprocess
import sys
import types

import pytest

from benchmark import program_spans, trace_reduce
from benchmark import spec_latent_costs as costs
from benchmark.latent_moe_costs import attention_params, expert_bytes

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
CELL, CONFIG = "xing-specdecode-batch", "Xing4.0-29B-A4B"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
NEW_METRICS = {"mtp_accept_share", "mla_verify_roofline_share",
               "mhc_ms_per_step", "spec_step_roofline_share"}
APPENDED = {"serve_block_tok_per_s", "serve_stall_share", "batch_occupancy",
            "prefill_step_share", "kv_pages_peak_share", "kv_preempted",
            "decode_step_device_ms", "mosaic_time_share",
            "compiles_in_window", "decode_gap_ms", "queue_wait_mean_ms",
            "decode_ahead_share", "moe_expert_ms_per_step",
            "moe_expert_roofline_share", "moe_route_ms_per_step",
            "moe_experts_touched_share", "moe_slot_imbalance",
            "mla_decode_ms_per_step", "prefill_attn_ms"}
# the model the driver describes at the cell's own sizes
MODEL = {"n_layers": 6, "n_draft_layers": 1, "d_model": 3584,
         "vocab": 131072, "n_dense_layers": 1, "n_moe_layers": 6,
         "d_ff": 9216, "d_expert": 1024, "n_shared": 1, "experts_held": 64,
         "n_routed_experts": 64, "top_k": 4, "n_heads": 32, "q_rank": 768,
         "kv_rank": 512, "nope": 128, "rope": 64, "v_dim": 128,
         "streams": 4, "verify_positions": 2, "window": 64}
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
        == ["num_hidden_layers", "first_k_dense_replace"]
    assert cfg["reduced_from"] == {"num_hidden_layers": 40,
                                   "first_k_dense_replace": 2}
    assert (cfg["num_hidden_layers"], cfg["first_k_dense_replace"]) == (6, 1)
    if os.path.exists(CATALOG):
        with open(CATALOG) as f:
            row = next(r for r in map(json.loads, f)
                       if r["name"] == CONFIG)
        assert entry["source"] == row["source_url"]
        # key for key: reduced and nothing else accounts for a difference
        for key, value in row["config"].items():
            if key in entry["reduced"]:
                assert cfg["reduced_from"][key] == value, key
                assert cfg[key] != value, key
            else:
                assert cfg[key] == value, key
    # what the model is built with: every published width, all 64
    # experts, every row of the vocabulary, 4 streams, 20 iterations
    kw = cfg["model"]["kwargs"]
    for key in kw:
        if key != "ep":
            assert kw[key] == cfg[key], key
    assert (kw["hidden_size"], kw["num_attention_heads"], kw["q_lora_rank"],
            kw["kv_lora_rank"], kw["qk_nope_head_dim"],
            kw["qk_rope_head_dim"], kw["v_head_dim"],
            kw["intermediate_size"], kw["moe_intermediate_size"],
            kw["n_routed_experts"], kw["num_experts_per_tok"],
            kw["vocab_size"]) \
        == (3584, 32, 768, 512, 128, 64, 128, 9216, 1024, 64, 4, 131072)
    assert (kw["hc_mult"], kw["hc_sinkhorn_iters"], kw["hc_eps"],
            kw["mhc_h_res_clamp_min"], kw["mhc_h_res_clamp_max"],
            kw["num_nextn_predict_layers"]) == (4, 20, 1e-6, -30, 30, 1)
    assert kw["ep"] == [0, 1] and kw["rope_scaling"]["factor"] == 64
    assert cfg["bytes_per_value"] == {"weights": 2, "kv": 2}
    assert "8 stages of 5 layers" in cfg["deployment"]
    for key in ("mhc_norm_eps", "hc_eps", "sinkhorn_order",
                "streams_in_out", "mtp_hidden", "mtp_block",
                "e_score_correction_bias", "weights", "rope_pairing",
                "kv_b_proj", "precision", "acceptance", "mhc_ops"):
        assert key in cfg["assumed"], key
    assert "0.69" in cfg["assumed"]["weights"]
    why = cfg["why_reduced"]
    assert "11.139 GB" in why and "Depth 7" in why and "6.7x" in why
    srv = cfg["server"]["kwargs"]
    assert (srv["seq_ladder"], srv["page_size"], srv["window"],
            srv["max_new_tokens"], srv["pool_pages"], srv["max_queue"]) \
        == ([256], 128, 64, 1024, 768, 128)
    assert cfg["reference"]["import"] \
        == "benchmark.reference.hyper_latent_moe_lm"
    for key in ("prefill_module", "step_module", "expert_kernel",
                "latent_kernel", "prefill_attn_kernel", "route_ops",
                "mhc_ops"):
        assert key in cfg["trace_names"]
    # the vectors the weights block names are the shapes the model has
    vectors = cfg["weights"]["vectors"]
    assert len(vectors["hc_a"]) == 3 and len(vectors["hc_b"]) == 24
    assert vectors["hc_b"][8:] == [2.0 if i % 5 == 0 else 0.0
                                   for i in range(16)]


def test_the_cell_its_traffic_and_where_its_metrics_are_listed():
    spec = _json("BENCHMARK.json")
    cell = next(w for w in spec["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) \
        == (CONFIG, "specdecode-batch-w64", 1)
    assert sum(w["config"] == CONFIG for w in spec["workloads"]) == 1
    assert "131,072" in cell["why"] and "floor" in cell["why"]
    mix = _json("benchmark", "traffic", cell["traffic"] + ".json")
    assert mix["driver"] == "serve_spec_latent_moe"
    # the lengths are dots-decode-batch's: the two cells differ in model
    # and step form only
    dots = _json("benchmark", "traffic", "decode-batch-w64.json")
    for key in ("arrivals", "prompt_len", "output_len", "lead_in_s",
                "unfinished_at_end", "trace_after_s", "trace_s"):
        assert mix[key] == dots[key], key
    assert mix["arrivals"] == {"kind": "closed", "clients": 128}
    cfg = _json("benchmark", "configs", CONFIG + ".json")
    srv = cfg["server"]["kwargs"]
    assert mix["prompt_len"]["max"] <= min(srv["seq_ladder"])
    assert mix["output_len"]["max"] <= srv["max_new_tokens"]
    assert srv["max_queue"] >= mix["arrivals"]["clients"]
    assert mix["arrivals"]["clients"] == 2 * srv["window"]
    # every row at its longest fits the pool: nothing is preempted (a
    # speculative step writes up to two positions past a row's budget)
    pages = -(-(max(srv["seq_ladder"]) + srv["max_new_tokens"] + 2)
              // srv["page_size"])
    assert srv["window"] * pages < srv["pool_pages"]
    check = mix["check"]
    assert (check["requests"], check["min_tokens"]) == (3, 1500)
    assert set(check["limits"]) == {"gap_mean_std", "draft_gap_mean_std"}
    # between the program's largest reading and the float8 control's
    # smallest (PERF.md section 2): top 4 of 64 held experts makes a
    # flipped near-tie cost a quarter of the routed output, so the gap
    # is 30-40 times dots-decode-batch's
    assert 0.0903 < check["limits"]["gap_mean_std"] < 0.351
    assert 0.179 < check["limits"]["draft_gap_mean_std"] < 0.477
    assert check["controls_compared"] == ["float8"]
    assert "float8" in check["why"] and "mix_bf16" in check["why"] \
        and "chip" in check["why"]
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
    # the one-token model's rooflines reckon one query a row and a token
    # a step
    for name in ("mla_decode_roofline_share", "latent_step_roofline_share"):
        assert CELL not in layer[name]["workloads"], name
    # a reader's file says the unit and the layer its entry says
    for name in NEW_METRICS:
        mod = importlib.import_module("benchmark.layer_metrics." + name)
        assert (mod.NAME, mod.UNIT, mod.LAYER) \
            == (name, layer[name]["unit"], layer[name]["layer"])


# --- operations and bytes --------------------------------------------------

def test_costs_against_hand_counts():
    attention = (3584 * 768 + 768 * 32 * 192 + 3584 * 576
                 + 512 * 32 * 256 + 4096 * 3584)
    assert attention_params(MODEL) == attention == 28409856
    assert expert_bytes(MODEL) == 3 * 3584 * 1024 * 2
    assert costs.blocks(MODEL) == 7
    assert costs.mixing_params(MODEL) == 14336 * 24
    assert costs.mixing_params(dict(MODEL, streams=1)) == 0
    # 2 queries x 2 x 32 heads x (576 + 512): the issue's 1,088
    assert costs.verify_key_flops(MODEL) == 2 * 2 * 32 * 1088
    # a key a layer: 1,152 B -> 1.41 ns, 139,264 FLOP -> 0.71 ns
    one = costs.verify_least_s(MODEL, 1, PEAK) / 7
    assert abs(one - 1152 / 819e9) < 1e-15
    assert 2 * 2 * 32 * 1088 / 197e12 < one
    base = costs.step_bytes(MODEL, 0, 0)
    assert base == 2 * (7 * attention + 3 * 3584 * 9216
                        + 6 * 3 * 3584 * 1024 + 2 * 3584 * 3584
                        + 3584 * 131072) \
        + 4 * (6 * 3584 * 64 + 14 * 14336 * 24)
    assert costs.step_bytes(MODEL, 380, 50000) - base \
        == 380 * expert_bytes(MODEL) + 7 * 50000 * 1152
    # every expert of the 6 expert layers touched: the issue's 5.09 G
    # parameters, 10.2 GB
    whole = costs.step_bytes(MODEL, 6 * 64, 0)
    assert 10.1e9 < whole < 10.3e9


# --- the readers on a trace written by hand --------------------------------

US = 1e3        # times in ns
STEP = "jit__spec_decode_fn(123)"
PREFILL = "jit__spec_prefill_fn(456)"
GATED = ("%mx_grouped_matmul.e64.m1024.k3584.n1024.bfloat16.gated.{n} = "
         "bf16[1024,1024]{{1,0}} custom-call(s32[64]{{0}} %x)")
DOWN = ("%mx_grouped_matmul.e64.m1024.k1024.n3584.bfloat16.{n} = "
        "f32[1024,3584]{{1,0}} custom-call(s32[64]{{0}} %x)")
MLA = ("%mx_mla_decode.bh4096.q2.k1408.d640.bfloat16.r512.paged.{n} = "
       "f32[64,64,512]{{2,1,0}} custom-call(s32[704]{{0}} %t)")
SORT = "%sort.{n} = (f32[128,64]{{0,1}}, s32[128,64]) sort(%c, %iota.1)"
SINKHORN = ("%divide_reduce_fusion.{n} = f32[64,2,4]{{2,1,0}} "
            "fusion(%broadcast_add_fusion.7), kind=kLoop")
WRITE = ("%multiply_reduce_fusion.{n} = f32[64,2,4,3584]{{3,2,1,0}} "
         "fusion(%fusion.9), kind=kLoop")
READ = ("%multiply_reduce_fusion.1{n} = f32[64,2,3584]{{2,1,0}} "
        "fusion(%fusion.9), kind=kLoop")
OTHER = "%fusion.9{n} = f32[64,2,3584]{{2,1,0}} fusion(%h), kind=kLoop"
TOUCHED, LIVE = 380, 51200      # experts a step (6 layers), live keys


def _ctx(mla_us, step_us, mhc_us=(40, 25, 15)):
    """Two speculative steps and one prefill on device 0. In each step
    the verify kernel takes ``mla_us``, the mixing ``mhc_us`` (Sinkhorn,
    write-back, read), the step ``step_us``; a same-shaped operation
    that is no mixing (``OTHER``) and the prefill's do not count."""
    ops, modules, t = [], [], 0.0

    def put(name, us):
        nonlocal t
        ops.append((name, t, t + us * US))
        t += us * US

    for s in range(2):
        start = t
        put(OTHER.format(n=s), 100)
        put(SORT.format(n=s), 30)
        put(SINKHORN.format(n=s), mhc_us[0])
        put(WRITE.format(n=s), mhc_us[1])
        put(READ.format(n=s), mhc_us[2])
        put(GATED.format(n=s), 600)
        put(DOWN.format(n=s), 400)
        put(MLA.format(n=s), mla_us)
        t = start + step_us * US
        modules.append((STEP, start, t))
    start = t
    put(SINKHORN.format(n=7), 5000)
    put(MLA.replace("q2", "q1").format(n=7), 700)
    modules.append((PREFILL, start, t))
    planes = {"/device:TPU:0": {trace_reduce.MODULES_LINE: modules,
                                trace_reduce.OPS_LINE: ops}}
    lines = [[("mx:decode.readback", 10.0 + i, 20.0 + i,
               {"moe_slots": 3072, "experts_touched": TOUCHED,
                "max_load": 16, "accepted": 0, "tokens": 64})
              for i in range(2)]
             + [("mx:decode.dispatch", 30.0 + i, 31.0 + i,
                 {"pages_live": 400, "ahead": 1, "keys_live": LIVE,
                  "undecided": 64}) for i in range(2)]]
    cfg = _json("benchmark", "configs", CONFIG + ".json")
    return types.SimpleNamespace(
        trace=trace_reduce.Trace(planes),
        program_spans=program_spans.Spans(lines), peak=PEAK,
        config={"trace_names": cfg["trace_names"],
                "bytes_per_value": {"weights": 2, "kv": 2}},
        raw={"model": MODEL, "window_s": 30.0,
             "stats0": {"decode_steps": 0, "tokens_out": 10},
             "stats1": {"decode_steps": 2, "tokens_out": 140},
             "moe_delta": {"steps": 2, "moe_slots": 6144,
                           "experts_touched": 2 * TOUCHED},
             "spec_delta": {"drafts_verified": 128, "drafts_accepted": 2,
                            "tokens_out": 130, "positions_run": 256}})


def _read(name, ctx):
    return importlib.import_module(
        "benchmark.layer_metrics." + name).compute(ctx)


def test_readers_on_a_trace_in_which_every_kernel_ran_at_its_roofline():
    """No share may read over 100%: with the kernel's and the step's
    time set to the least the chip could take for what the step touched,
    each share reads 100 and not a hair more."""
    mla_us = 7 * LIVE * 1152 / 819e9 * 1e6
    step_us = costs.step_bytes(MODEL, TOUCHED, LIVE) / 819e9 * 1e6
    assert mla_us + 1210 < step_us
    ctx = _ctx(mla_us, step_us)
    assert abs(_read("mla_verify_roofline_share", ctx) - 100.0) < 1e-6
    assert abs(_read("spec_step_roofline_share", ctx) - 100.0) < 1e-6
    assert abs(_read("mhc_ms_per_step", ctx) - 0.080) < 1e-9
    assert abs(_read("mtp_accept_share", ctx) - 100.0 * 2 / 128) < 1e-12
    # the readers the cell shares with the one-token model, unedited
    assert abs(_read("mla_decode_ms_per_step", ctx) - mla_us / 1e3) < 1e-9
    assert abs(_read("moe_route_ms_per_step", ctx) - 0.030) < 1e-9
    assert abs(_read("moe_expert_ms_per_step", ctx) - 1.0) < 1e-9
    assert abs(_read("moe_experts_touched_share", ctx)
               - 100.0 * TOUCHED / 384) < 1e-9
    assert abs(_read("moe_slot_imbalance", ctx) - 16 / 8) < 1e-9
    assert abs(_read("decode_step_device_ms", ctx) - step_us / 1e3) < 1e-9
    # a slower kernel reads a smaller share, in proportion
    slow = _ctx(4 * mla_us, 2 * step_us, (80, 50, 30))
    assert abs(_read("mla_verify_roofline_share", slow) - 25.0) < 1e-6
    assert abs(_read("spec_step_roofline_share", slow) - 50.0) < 1e-6
    assert abs(_read("mhc_ms_per_step", slow) - 0.160) < 1e-9


def test_readers_find_nothing_in_a_program_without_the_kernels():
    """A program that lacks what this configuration adds (the parent
    commit): every new reader returns None and none raises."""
    ctx = _ctx(1000, 20000)
    ctx.trace = trace_reduce.Trace({"/device:TPU:0": {
        trace_reduce.MODULES_LINE: [("jit__decode_fn(1)", 0.0, 1e7)],
        trace_reduce.OPS_LINE: [(OTHER.format(n=0), 0.0, 1e6)]}})
    ctx.program_spans = program_spans.Spans([[
        ("mx:decode.dispatch", 1.0, 2.0, {"pages_live": 9, "ahead": 1})]])
    ctx.raw.pop("spec_delta")
    ctx.raw["model"] = {k: v for k, v in MODEL.items()
                        if k not in ("verify_positions", "streams",
                                     "n_draft_layers")}
    ctx.config = {"trace_names": {"step_module": "_decode_fn",
                                  "latent_kernel": "mla_decode"},
                  "bytes_per_value": {"weights": 2, "kv": 2}}
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
    assert {"mtp_accept_share", "batch_occupancy", "decode_ahead_share",
            "moe_experts_touched_share", "kv_preempted"} \
        <= set(result["metrics"])
    assert all(m["value"] is None for m in result["metrics"].values())
    raw = detail["raw"]
    spec = raw["spec_delta"]
    assert spec["positions_run"] == 2 * spec["drafts_verified"] > 0
    # a vocabulary of 128: about one draft in 128 is right
    assert spec["drafts_accepted"] < 0.1 * spec["drafts_verified"]
    assert spec["drafts_verified"] <= spec["tokens_out"] \
        <= spec["drafts_verified"] + spec["drafts_accepted"]
    assert raw["compiles_in_window"] == 0
    assert raw["model"]["n_moe_layers"] == 3
    check = raw["check"]
    assert check["tokens"] > 300 and check["drafts"] > 250
    assert set(result["compared"]) >= {"gap_mean_std", "draft_gap_mean_std"}
    assert 0.0 <= check["routing_differs_share"] <= 1.0
    assert 0.0 <= check["reference_accept"] <= 1.0


@pytest.mark.parametrize("seed", [3, 2 ** 31 + 11])
def test_both_controls_come_out_not_correct(seed):
    """``run.py --control`` through the cell's own driver (tiny sizes, a
    sample of 40 requests): each control stands in the program's place
    in turn. The float8 control reads over both limits the same run's
    program passes, and what is compared are ITS numbers; the
    bfloat16-mixing control is read beside it (at these widths its gap
    is the program's own: ``tests/test_hyper_latent_moe.py`` holds the
    coefficients themselves)."""
    proc = _run("--workload", CELL, "--seed", str(seed), "--rehearse",
                "--control")
    assert proc.returncode == 0, proc.stderr[-2000:]
    lines = proc.stdout.strip().splitlines()
    result, detail = json.loads(lines[-1]), json.loads(lines[-2])
    assert result["correct"] is False and result["failed"] == 0
    check = detail["raw"]["check"]
    assert set(check["controls"]) == {"float8", "mix_bf16"}
    for name in ("gap_mean_std", "draft_gap_mean_std"):
        pair = result["compared"]["float8." + name]
        assert pair["value"] > pair["limit"], name
        assert check["controls"]["float8"][name] == pair["value"]
        assert check["program"][name] <= pair["limit"], name
        assert check["controls"]["mix_bf16"][name] is not None
    assert len(check["samples"]) >= 20
    assert "compared float8.gap_mean_std" in proc.stderr
