"""The window cell: its entries in BENCHMARK.json against the catalog's
row and the issue's cut, its traffic as the issue names it (one fixed
draw of shapes whatever the seed), its cost functions against hand
counts and its eight readers on a trace written by hand (a kernel that
ran AT its roofline reads 100%, never more; a program without the
kernels, the rings and the counters reads nothing and raises nothing),
the driver's sample, ``--rehearse`` of the cell, and ``--control``
through to ``correct: false``."""
import importlib
import itertools
import json
import os
import subprocess
import sys
import types

import numpy as np
import pytest

from benchmark import latent_moe_costs, program_spans, trace_reduce
from benchmark import window_moe_costs as costs
from benchmark.drivers import serve_window_moe as driver

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
CELL, CONFIG = "laguna-window-mixed-queue", "Laguna-S-2.1"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
NEW_METRICS = {"ring_attn_ms_per_step", "ring_attn_roofline_share",
               "global_attn_ms_per_step", "global_attn_roofline_share",
               "window_step_roofline_share", "long_prefill_device_ms",
               "banded_fwd_roofline_share", "long_admit_share"}
REDUCED = {"num_hidden_layers": 48, "num_experts": 256,
           "vocab_size": 100352}
KINDS = ["full_attention"] + ["sliding_attention"] * 3 + ["full_attention"]
# the model the driver describes at the cell's own sizes
MODEL = {"n_layers": 5, "d_model": 3072, "vocab": 25088,
         "n_dense_layers": 1, "n_moe_layers": 4, "d_ff": 12288,
         "d_expert": 1024, "d_shared": 1024, "n_shared": 1,
         "experts_held": 64, "n_routed_experts": 256, "top_k": 10,
         "heads": [48, 72, 72, 72, 48], "kinds": KINDS, "n_kv_heads": 8,
         "head_dim": 128, "ring_window": 512, "long_rung": 8192,
         "window": 64}
PEAK = {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
TOKEN = 2 * 8 * 128 * 2                 # K and V of one token, one layer


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
    assert entry["source"].endswith("/config.json")
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
    assert (cfg["num_hidden_layers"], cfg["num_experts"],
            cfg["vocab_size"]) == (5, 64, 25088)
    # what the model is built with: every published width, the router's
    # full width (the chip HOLDS 64 of its 256), the dense layer and one
    # whole period — the per-layer lists whole, of which the first five
    kw = cfg["model"]["kwargs"]
    for key, published in row["config"].items():
        if key not in ("num_hidden_layers", "vocab_size"):
            assert kw[key] == published, key
    assert kw["num_experts"] == 256 and kw["ep"] == [0, 4]
    assert kw["num_hidden_layers"] == 5
    assert kw["layer_types"][:5] == KINDS
    assert kw["num_attention_heads_per_layer"][:5] == [48, 72, 72, 72, 48]
    assert kw["mlp_layer_types"][:5] == ["dense"] + ["sparse"] * 4
    assert kw["vocab_size"] == 25088 == 100352 // 4
    assert (kw["hidden_size"], kw["head_dim"], kw["num_key_value_heads"],
            kw["sliding_window"], kw["moe_intermediate_size"],
            kw["num_experts_per_tok"], kw["intermediate_size"]) \
        == (3072, 128, 8, 512, 1024, 10, 12288)
    for said in ("qk_norm", "attention_factor", "partial_rotary", "window",
                 "gate", "router", "router_draw", "shared_expert",
                 "per_layer_lists", "refused", "precision", "ring_row"):
        assert cfg["assumed"][said], said
    why = cfg["why_reduced"].lower()
    assert "5 of the 48 layers" in why and "depth 6" in why
    assert "4 chips" in cfg["deployment"]
    assert cfg["server"]["kwargs"] == {
        "seq_ladder": [512, 8192], "max_new_tokens": 768, "page_size": 128,
        "window": 64, "pool_pages": 4608, "max_queue": 128,
        "prefix_cache": False}
    assert cfg["bytes_per_value"] == {"weights": 2, "kv": 2, "ring": 2}
    assert cfg["reference"]["import"] == "benchmark.reference.window_moe_lm"


def test_the_weights_are_what_the_file_reckons():
    """``why_reduced``'s arithmetic, by ``jax.eval_shape`` of the model
    the file builds: 6.01 GB held here, the rings 0.40 GB, a page of the
    two full-attention layers 1.05 MB."""
    import jax
    from benchmark import harness
    cfg = _config()
    model = harness.load_object(cfg["model"]["import"])(
        **cfg["model"]["kwargs"])
    shapes = jax.eval_shape(model.init_params, 0)

    def gb(pick):
        return sum(int(np.prod(a.shape)) * a.dtype.itemsize
                   for n, a in shapes.items() if pick(n)) / 1e9

    assert abs(gb(lambda n: True) - 6.010) < 0.001
    assert abs(gb(lambda n: n.startswith("l0.")) - 0.315) < 0.001
    assert abs(gb(lambda n: n.startswith("l1.")) - 1.356) < 0.001
    assert abs(gb(lambda n: n.startswith("l4.")) - 1.318) < 0.001
    assert abs(gb(lambda n: n in ("embed", "head")) - 0.308) < 0.001
    assert (model.cache_layers, model.state_layers, model.held) \
        == (2, 3, (0, 64))
    assert model.state_arrays == (("ring_k", (512, 1024), "bfloat16"),
                                  ("ring_v", (512, 1024), "bfloat16"))
    srv = cfg["server"]["kwargs"]
    assert 64 * 3 * 2 * 512 * 1024 * 2 == 402653184
    assert srv["page_size"] * 2 * TOKEN == 1048576
    assert srv["pool_pages"] >= 64 * -(-(8192 + 768) // 128)
    for said in ("6.010 GB", "0.403 GB", "4.83 GB"):
        assert said in cfg["why_reduced"], said


def test_the_cell_its_traffic_and_where_its_metrics_are_listed():
    spec = _json("BENCHMARK.json")
    cell = next(w for w in spec["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) \
        == (CONFIG, "window-mixed-queue-w64", 1)
    assert spec["workloads"][-1] is cell and spec["configs"][-1]["name"] \
        == CONFIG
    mix = _json("benchmark", "traffic", cell["traffic"] + ".json")
    assert mix["driver"] == "serve_window_moe"
    assert mix["arrivals"] == {"kind": "closed", "clients": 128}
    assert mix["long_share"] == 0.5
    assert mix["prompt_len"] == {"dist": "lognormal", "median": 6144,
                                 "sigma": 0.25, "min": 4096, "max": 8192}
    assert mix["short_prompt_len"] == {"dist": "lognormal", "median": 384,
                                       "sigma": 0.3, "min": 256, "max": 512}
    assert mix["output_len"] == {"dist": "lognormal", "median": 512,
                                 "sigma": 0.3, "min": 384, "max": 768}
    assert mix["lead_in_s"] == 2.0 and mix["unfinished_at_end"] == "cut"
    ladder = _config()["server"]["kwargs"]["seq_ladder"]
    assert mix["prompt_len"]["max"] == max(ladder)
    assert mix["short_prompt_len"]["max"] == min(ladder) == 512
    assert mix["output_len"]["max"] \
        == _config()["server"]["kwargs"]["max_new_tokens"]
    assert set(mix["check"]["limits"]) == {"gap_mean_std"}
    assert (mix["check"]["min_long"], mix["check"]["min_short"],
            mix["check"]["min_tokens"]) == (1, 2, 1500)
    for m in spec["end_to_end"]:
        listed = CELL in m.get("workloads", [CELL])
        assert listed == (m["name"] in ("serve_tok_per_s", "itl_p99_ms",
                                        "setup_s")), m["name"]
    layer = {m["name"]: m for m in spec["per_layer"]}
    for name in NEW_METRICS:
        assert layer[name]["workloads"] == [CELL], name
    # what the two other cells with a prefill program and routed experts
    # both read, this cell reads too — but for the kernels it does not
    # run (the latent decode kernel, the equal-heads flash forward)
    for name, m in layer.items():
        if {"xing-specdecode-batch", "ling-hybrid-decode-batch"} \
                <= set(m["workloads"]):
            assert (CELL in m["workloads"]) == (name not in (
                "flash_fwd_roofline_share", "mla_decode_ms_per_step")), name
    for name in ("prefill_device_ms", "prefill_queue_ms", "admit_idle_ms",
                 "prefill_attn_ms", "moe_expert_ms_per_step",
                 "moe_expert_roofline_share", "moe_route_ms_per_step",
                 "moe_experts_touched_share", "moe_slot_imbalance"):
        assert CELL in layer[name]["workloads"], name
    for name, m in layer.items():
        if name.startswith(("kda_", "chunk_")) or name in (
                "recurrent_state_share", "hybrid_step_roofline_share"):
            assert CELL not in m["workloads"], name
    # every appended cell stands LAST in its list: nothing was reordered
    for m in spec["end_to_end"] + spec["per_layer"]:
        if CELL in m.get("workloads", []):
            assert m["workloads"][-1] == CELL, m["name"]


def test_every_seed_offers_every_client_the_same_shapes():
    """``shapes``: a client's (long or short, prompt length, answer
    length) sequence is one fixed draw; clients differ; lengths keep to
    their clips and half of the requests are long."""
    mix = _json("benchmark", "traffic", "window-mixed-queue-w64.json")
    take = lambda c, n=400: list(itertools.islice(  # noqa: E731
        driver.shapes(c, mix), n))
    assert take(0) == take(0) and take(0) != take(1)
    drawn = [s for c in range(16) for s in take(c, 100)]
    longs = [size for long, size, _ in drawn if long]
    shorts = [size for long, size, _ in drawn if not long]
    assert 0.45 < len(longs) / len(drawn) < 0.55
    assert 4096 == min(longs) and max(longs) == 8192
    assert 256 == min(shorts) and max(shorts) == 512
    assert abs(np.median(longs) - 6144) < 250
    assert abs(np.median(shorts) - 384) < 20
    answers = [asked for _, _, asked in drawn]
    assert min(answers) == 384 and max(answers) == 768
    assert abs(np.median(answers) - 512) < 20

    # two seeds through the driver's own load: the same shapes in the
    # same order, other token ids
    def offered(seed, n=5):
        ctx = types.SimpleNamespace(seed=seed, traffic=mix)
        load, seen = driver.FixedShapesLoad(None, ctx, 25088), []

        def send(rec):
            seen.append((rec.prompt, rec.asked))
            if len(seen) >= n:
                load.stop.set()
            return False

        load._send = send
        load._client(3)
        return seen

    a, b = offered(7), offered(2 ** 31 + 9)
    assert [(len(p), k) for p, k in a] == [(len(p), k) for p, k in b] \
        == [(size, asked) for _, size, asked in take(3, 5)]
    assert not any(np.array_equal(p, q) for (p, _), (q, _) in zip(a, b))
    assert all(p.dtype == np.int32 and 0 <= p.min() and p.max() < 25088
               for p, _ in a)


def test_the_sample_holds_the_longest_a_long_and_two_short_requests():
    rec = lambda n, asked: types.SimpleNamespace(  # noqa: E731
        prompt=np.zeros((n,), np.int32), asked=asked, tokens=[0] * asked)
    done = [rec(300, 400), rec(400, 500), rec(8000, 700), rec(5000, 400),
            rec(350, 384), rec(7000, 600), rec(280, 768)]
    spec = {"requests": 2, "min_tokens": 1500, "min_long": 1,
            "min_short": 2}
    for seed in range(8):
        picks = driver._sample(done, seed, spec, 512)
        assert picks[0] == 2 and len(set(picks)) == len(picks)
        assert sum(len(done[i].prompt) > 512 for i in picks) >= 1
        assert sum(len(done[i].prompt) <= 512 for i in picks) >= 2
        assert len(picks) >= 3
        assert sum(done[i].asked for i in picks) >= 1500
    # nothing short finished: the sample is what there is
    assert driver._sample(done[2:4], 0, spec, 512) == [0, 1]


# --- the costs -------------------------------------------------------------

def test_costs_against_hand_counts():
    m = MODEL
    assert costs.layers(m, costs.SLIDING) == 3
    assert costs.layers(m, costs.FULL) == 2
    assert costs.heads(m, costs.SLIDING) == 216
    assert costs.heads(m, costs.FULL) == 96
    assert costs.kv_token_bytes(m) == TOKEN == 4096
    # a full ring a row: 512 slots in 3 layers is the issue's 2 MB a layer
    full = 64 * 512 * 3 * TOKEN
    assert full == 402653184
    assert costs.ring_keys(m, full) == 64 * 512
    assert costs.ring_attn_flops(m, full) == 64 * 512 * 4 * 216 * 128
    # 9 query heads a key head: 9 operations a byte, memory binds 27 to 1
    assert full / 819e9 > 20 * costs.ring_attn_flops(m, full) / 197e12
    live = 32 * 6400 + 32 * 650
    assert costs.global_attn_bytes(m, live) == 2 * live * TOKEN
    assert costs.global_attn_flops(m, live) == live * 4 * 96 * 128
    assert costs.attention_params(m) == 2 * 44187648 + 3 * 63135744
    expert = 3 * 3072 * 1024 * 2
    assert latent_moe_costs.expert_bytes(m) == expert
    matrices = 2 * (2 * 44187648 + 3 * 63135744 + 3 * 3072 * 12288
                    + 4 * 3 * 3072 * 1024 + 3072 * 25088)
    router = 4 * 3072 * 256 * 4
    got = costs.step_bytes(m, 236, live, full)
    assert got == matrices + router + 236 * expert + 2 * live * TOKEN + full
    # the issue's reckoning: experts 4.45 GB, other weights 1.0 GB, keys
    # and values near 30% of a step's bytes
    assert abs(236 * expert / 1e9 - 4.45) < 0.01
    assert abs((matrices + router) / 1e9 - 1.02) < 0.01
    assert 0.25 < (2 * live * TOKEN + full) / got < 0.33
    # the banded prefill: a window of 512 over 8,192 queries
    assert costs.banded_visible(8192, None) == 8192 * 8193 // 2
    assert costs.banded_visible(8192, 512) == 512 * 513 // 2 + 7680 * 512
    assert costs.banded_visible(300, 512) == 300 * 301 // 2
    assert costs.banded_fwd_flops(72, 8192, 128, 512) \
        == 2 * 72 * (512 * 513 // 2 + 7680 * 512) * 256
    assert costs.call_window(
        "%mx_grouped_fwd.bh72.q8192.k8192.d128.bfloat16.kv8.w512.1 = x") \
        == 512
    assert costs.call_window(
        "%mx_grouped_fwd.bh48.q8192.k8192.d128.bfloat16.kv8.1 = x") is None


# --- the readers, on a trace written by hand --------------------------------

STEP = "jit__state_decode_fn(1)"
PREFILL = "jit__state_prefill_fn(2)"
RING = "%mx_ring_decode.bh4608.q1.k512.d128.bfloat16.kv8.{n} = (f32[64,8," \
       "9,128]{{3,2,1,0}}, bf16[3,64,512,1024]{{3,2,1,0}}) custom-call(...)"
BLOCK = "%mx_block_decode.bh3072.q1.k8960.d128.bfloat16.kv8.paged.{n} = " \
        "f32[64,8,6,128]{{3,2,1,0}} custom-call(...)"
BANDED = "%mx_grouped_fwd.bh72.q8192.k8192.d128.bfloat16.kv8.w512.{n} = " \
         "f32[72,8192,128]{{2,1,0}} custom-call(...)"
GROUPED = "%mx_grouped_fwd.bh48.q8192.k8192.d128.bfloat16.kv8.{n} = " \
          "f32[48,8192,128]{{2,1,0}} custom-call(...)"
OTHER = "%fusion.{n} = bf16[64,3072]{{1,0}} fusion(%p.{n})"
US = 1e3
RING_BYTES, PAGES, TOUCHED = 48 * 512 * 3 * TOKEN, 1200, 236


def _ctx(ring_us, block_us, step_us, banded_us=30000.0):
    ops, modules, t = [], [], 0.0

    def put(name, us):
        nonlocal t
        ops.append((name, t, t + us * US))
        t += us * US

    for s in range(2):
        start = t
        put(OTHER.format(n=s), 100)
        for layer in range(3):
            put(RING.format(n=10 * s + layer), ring_us / 3)
        for layer in range(2):
            put(BLOCK.format(n=10 * s + layer), block_us / 2)
        t = start + step_us * US
        modules.append((STEP, start, t))
        t += 500 * US
    start = t
    put(GROUPED.format(n=0), 9000)
    for layer in range(3):
        put(BANDED.format(n=layer + 1), banded_us / 3)
    put(OTHER.format(n=7), 50000)
    modules.append((PREFILL, start, t))
    planes = {"/device:TPU:0": {trace_reduce.MODULES_LINE: modules,
                                trace_reduce.OPS_LINE: ops}}
    lines = [[("mx:decode.readback", 10.0 + i, 20.0 + i,
               {"moe_slots": 160, "experts_touched": TOUCHED,
                "max_load": 7, "state_rows_live": 48,
                "ring_rows_wrapped": 40, "global_pages_live": PAGES,
                "ring_bytes": RING_BYTES})
              for i in range(2)]]
    streams = [{"prompt_len": 6399, "times": [-1.0, 0.1, 0.2],
                "sent": 3.0}] * 16 \
        + [{"prompt_len": 400, "times": [-1.0, 0.1, 0.2],
            "sent": 4.0}] * 48
    return types.SimpleNamespace(
        trace=trace_reduce.Trace(planes),
        program_spans=program_spans.Spans(lines), peak=PEAK,
        config=_config(),
        raw={"model": MODEL, "window_s": 30.0, "streams": streams,
             "stats0": {"decode_steps": 0}, "stats1": {"decode_steps": 2},
             "moe_delta": {"steps": 2, "moe_slots": 320,
                           "experts_touched": 2 * TOUCHED,
                           "ring_rows_wrapped": 80,
                           "global_pages_live": 2 * PAGES,
                           "ring_bytes": 2 * RING_BYTES}})


def _read(name, ctx):
    return importlib.import_module(
        "benchmark.layer_metrics." + name).compute(ctx)


def test_readers_on_a_trace_in_which_the_kernels_ran_at_their_rooflines():
    ring_us = RING_BYTES / 819e9 * 1e6
    block_us = PAGES * 128 * 2 * TOKEN / 819e9 * 1e6
    ctx = _ctx(1.0, 1.0, 1.0)
    live = latent_moe_costs.live_tokens_per_step(ctx)
    assert live == (16 * (6400 + 6401) + 48 * (401 + 402)) / 2
    step_us = costs.step_bytes(MODEL, TOUCHED, live, RING_BYTES) \
        / 819e9 * 1e6
    banded_us = 3 * costs.banded_fwd_flops(72, 8192, 128, 512) \
        / 197e12 * 1e6
    assert ring_us + block_us + 100 < step_us
    ctx = _ctx(ring_us, block_us, step_us, banded_us)
    assert abs(_read("ring_attn_roofline_share", ctx) - 100.0) < 1e-6
    assert abs(_read("global_attn_roofline_share", ctx) - 100.0) < 1e-6
    assert abs(_read("window_step_roofline_share", ctx) - 100.0) < 1e-6
    assert abs(_read("banded_fwd_roofline_share", ctx) - 100.0) < 1e-6
    assert abs(_read("ring_attn_ms_per_step", ctx) - ring_us / 1e3) < 1e-9
    assert abs(_read("global_attn_ms_per_step", ctx)
               - block_us / 1e3) < 1e-9
    # the prefill's attention: the full layers' call and the banded ones
    assert abs(_read("prefill_attn_ms", ctx)
               - (9000 + banded_us) / 1e3) < 1e-6
    assert abs(_read("long_admit_share", ctx) - 25.0) < 1e-9
    slow = _ctx(4 * ring_us, 2 * block_us, step_us + 3 * ring_us
                + block_us, 5 * banded_us)
    assert abs(_read("ring_attn_roofline_share", slow) - 25.0) < 1e-6
    assert abs(_read("global_attn_roofline_share", slow) - 50.0) < 1e-6
    assert abs(_read("banded_fwd_roofline_share", slow) - 20.0) < 1e-6
    # without the traced steps' own counts: the window's
    ctx.program_spans = program_spans.Spans([[]])
    assert costs.per_step(ctx, "ring_bytes") == RING_BYTES
    assert costs.per_step(ctx, "global_pages_live") == PAGES


def test_long_prefill_device_ms_reads_the_longest_rungs_programs():
    from benchmark import launch_join
    prog = lambda rung, ms: types.SimpleNamespace(  # noqa: E731
        ns=ms * 1e6, launch=types.SimpleNamespace(stats={"rung": rung}))
    joined = types.SimpleNamespace(prefills=lambda: [
        prog(512, 14.0), prog(8192, 118.0), prog(8192, 122.0),
        prog(512, 15.0), prog(8192, 131.0)])
    ctx = _ctx(1.0, 1.0, 1.0)
    was, launch_join.of = launch_join.of, lambda ctx: joined
    try:
        assert _read("long_prefill_device_ms", ctx) == 122.0
        joined.prefills = lambda: [prog(512, 14.0)]
        assert _read("long_prefill_device_ms", ctx) is None
    finally:
        launch_join.of = was


def test_readers_find_nothing_in_a_program_without_the_kernels():
    """A program that lacks what this configuration adds (the parent
    commit, another model): every new reader returns None and none
    raises."""
    ctx = _ctx(1000, 1000, 20000)
    ctx.trace = trace_reduce.Trace({"/device:TPU:0": {
        trace_reduce.MODULES_LINE: [(STEP, 0.0, 1e7)],
        trace_reduce.OPS_LINE: [(OTHER.format(n=0), 0.0, 1e6)]}})
    ctx.program_spans = program_spans.Spans([[]])
    ctx.raw.pop("moe_delta")
    ctx.raw["streams"] = []
    for name in sorted(NEW_METRICS):
        assert _read(name, ctx) is None, name
    # another model's run: its driver's ``raw["model"]`` names no kinds,
    # its configuration no ring kernel, its ladder has one rung
    bare = ctx.trace
    ctx = _ctx(1000, 1000, 20000)
    ctx.trace = bare
    ctx.config = _json("benchmark", "configs", "Ling-3.0-flash.json")
    ctx.raw["model"] = {"n_layers": 6, "d_model": 2560}
    for name in sorted(NEW_METRICS):
        assert _read(name, ctx) is None, name
    ctx.trace = None
    for name in sorted(NEW_METRICS):
        assert _read(name, ctx) is None, name


# --- the driver ------------------------------------------------------------

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
    # what a CPU run can read: the program's counters and the streams;
    # every value null
    assert {"long_admit_share", "moe_experts_touched_share",
            "moe_slot_imbalance", "kv_preempted", "batch_occupancy"} \
        <= set(result["metrics"])
    assert all(m["value"] is None for m in result["metrics"].values())
    raw = detail["raw"]
    assert raw["model"]["kinds"] == KINDS
    assert raw["model"]["heads"] == [4, 6, 6, 6, 4]
    assert raw["moe_delta"]["ring_bytes"] > 0
    assert raw["moe_delta"]["global_pages_live"] >= raw["moe_delta"]["steps"]
    check = raw["check"]
    assert check["long_samples"] >= 1
    assert len(check["samples"]) - check["long_samples"] >= 2
    assert result["compared"]["gap_mean_std"]["value"] < 1e-3


@pytest.mark.parametrize("seed", [3, 2 ** 31 + 11])
def test_the_control_comes_out_not_correct(seed):
    """``run.py --control`` through the cell's own driver (tiny sizes):
    the float8 control in the program's place reads over the limit the
    same run's program passes; the window-off-by-one control is read
    beside it."""
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
    assert check["window_minus_one"]["gap_mean_std"] >= 0
    for sample in check["samples"]:
        assert sample["control"] == "float8"
        assert sample["window_minus_one_mean"] >= 0 <= sample["float8_mean"]
        assert sample["control_mean"] == sample["float8_mean"]
