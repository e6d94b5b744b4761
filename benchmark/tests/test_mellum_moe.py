"""The sharded cell: its entries in BENCHMARK.json against the catalog's
row (nothing cut), its traffic as the issue names it, ``mellum_costs``
against hand counts at the published widths, its four readers on a trace
written by hand with several devices' lines (one chip's work against one
chip's time: four devices' events do not quadruple a share; a program
without a mesh reads nothing and raises nothing), ``--rehearse`` of the
cell on 4 host devices and ``--control`` through to ``correct: false``."""
import importlib
import json
import os
import subprocess
import sys
import types

import pytest

from benchmark import latent_moe_costs, program_spans, trace_reduce
from benchmark import mellum_costs as costs

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
CELL, CONFIG = "mellum-tp4-agent-batch", "Mellum2-12B-A2.5B-Instruct"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
NEW_METRICS = ("exchange_ms_per_step", "exchange_exposed_share",
               "chip_skew_ms_per_step", "sharded_step_roofline_share")
KINDS = (["sliding_attention"] * 3 + ["full_attention"]) * 7
# ONE chip's sizes, as the driver describes them at the cell's own
MODEL = {"n_layers": 28, "d_model": 2304, "vocab": 24576,
         "n_dense_layers": 0, "n_moe_layers": 28, "d_ff": 7168,
         "d_expert": 896, "d_shared": 0, "n_shared": 0, "experts_held": 16,
         "n_routed_experts": 64, "top_k": 8, "heads": [8] * 28,
         "kinds": KINDS, "n_kv_heads": 1, "head_dim": 128,
         "ring_window": 1024, "long_rung": 4096, "window": 64,
         "gated": False, "chips": 4}
PEAK = {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
TOKEN = 2 * 1 * 128 * 2         # K and V of one token, one layer, ONE head


def _json(*parts):
    with open(os.path.join(ROOT, *parts)) as f:
        return json.load(f)


def _config():
    return _json("benchmark", "configs", CONFIG + ".json")


# --- the entries -----------------------------------------------------------

def test_the_configuration_is_the_published_one_whole():
    spec = _json("BENCHMARK.json")
    entry = next(c for c in spec["configs"] if c["name"] == CONFIG)
    cfg = _config()
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f) if r["name"] == CONFIG)
    assert entry["source"] == cfg["source"] == row["source_url"]
    assert entry["reduced"] == [] and cfg["reduced_from"] == {}
    for key, published in row["config"].items():
        assert cfg[key] == published, key
        assert cfg["model"]["kwargs"][key] == published, key
    assert (cfg["num_hidden_layers"], cfg["num_experts"],
            cfg["vocab_size"], len(cfg["layer_types"])) \
        == (28, 64, 98304, 28)
    for said in ("qk_norm", "partial_rotary", "attention_factor", "window",
                 "intermediate_size", "router", "mtp", "published_code"):
        assert cfg["assumed"][said], said
    assert "12.15B parameters, 24.3 GB" in cfg["why_whole"]
    for said in ("16r..16r+15", "key/value head r", "24,576 a chip",
                 "EMBEDDING is replicated", "56 a step"):
        assert said in cfg["deployment"], said
    assert cfg["server"]["kwargs"] == {
        "seq_ladder": [512, 4096], "max_new_tokens": 1024,
        "page_size": 128, "window": 64, "pool_pages": 64 * 40 + 128,
        "max_queue": 128, "prefix_cache": False}
    assert cfg["reference"]["import"] == "benchmark.reference.mellum_moe_lm"
    tiny = cfg["tiny"]["model"]["kwargs"]
    assert (tiny["num_hidden_layers"], tiny["num_key_value_heads"],
            tiny["num_attention_heads"], tiny["num_experts"],
            tiny["dtype"]) == (4, 4, 8, 8, "float32")


def test_the_cell_its_traffic_and_where_its_metrics_are_listed():
    spec = _json("BENCHMARK.json")
    cell = next(w for w in spec["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) \
        == (CONFIG, "sharded-agent-batch-w64", 4)
    assert len(cell["why"]) <= 200
    four = [w for w in spec["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(spec["workloads"]) // 4)
    traffic = _json("benchmark", "traffic", cell["traffic"] + ".json")
    assert traffic["driver"] == "serve_sharded_window_moe"
    assert traffic["arrivals"] == {"kind": "closed", "clients": 128}
    assert traffic["long_share"] == 1.0
    assert traffic["prompt_len"] == {"dist": "lognormal", "median": 2048,
                                     "sigma": 0.4, "min": 1024,
                                     "max": 4096}
    assert traffic["output_len"] == {"dist": "lognormal", "median": 768,
                                     "sigma": 0.25, "min": 512,
                                     "max": 1024}
    assert traffic["lead_in_s"] == 2.0
    assert traffic["unfinished_at_end"] == "cut"
    reported = {m["name"] for m in spec["end_to_end"]
                if CELL in m.get("workloads", [CELL])}
    assert reported == {"serve_tok_per_s", "itl_p99_ms", "setup_s"}
    by_name = {m["name"]: m for m in spec["per_layer"]}
    for name in NEW_METRICS:
        m = by_name[name]
        assert m["workloads"] == [CELL] and m["moves"] == "serve_tok_per_s"
        mod = importlib.import_module("benchmark.layer_metrics." + name)
        assert (mod.NAME, mod.UNIT, mod.LAYER) \
            == (name, m["unit"], m["layer"])
    # the training cell's share cannot be listed here (it moves
    # train_img_per_s), nor the readers that have nothing to read on a
    # model whose prompts ride the step
    for name in ("collective_exposed_share", "window_step_roofline_share",
                 "prefill_attn_ms", "prefill_device_ms", "prefill_queue_ms",
                 "admit_idle_ms", "long_prefill_device_ms"):
        assert CELL not in by_name[name]["workloads"], name


def test_every_seed_offers_every_client_the_same_shapes():
    import itertools
    from benchmark.drivers import serve_window_moe
    traffic = _json("benchmark", "traffic", "sharded-agent-batch-w64.json")
    drawn = [list(itertools.islice(serve_window_moe.shapes(c, traffic), 40))
             for c in range(8)]
    assert drawn[0] != drawn[1]
    for long, size, asked in itertools.chain(*drawn):
        assert long and 1024 <= size <= 4096 and 512 <= asked <= 1024
    sizes = sorted(s for _l, s, _a in itertools.chain(*drawn))
    assert 1800 < sizes[len(sizes) // 2] < 2400


# --- the costs, against hand counts at the published widths ----------------

def test_costs_against_hand_counts():
    # a chip's quarter of attention: W_q, W_o 2304 x 1024 each, W_k, W_v
    # 2304 x 128 each, 28 layers; no gate
    attn = 28 * (2 * 2304 * 1024 + 2 * 2304 * 128)
    assert costs.attention_params(MODEL) == attn == 148635648
    assert costs.attention_params(dict(MODEL, gated=True)) \
        == attn + 28 * 2304 * 8
    head = 2304 * 24576
    assert costs.matrix_params(MODEL) == attn + head
    expert = 3 * 2304 * 896 * 2
    assert latent_moe_costs.expert_bytes(MODEL) == expert == 12386304
    # every expert a chip holds touched: 16 x 28 of them, 5.55 GB
    all_touched = 16 * 28
    assert 5.54e9 < all_touched * expert < 5.56e9
    router = 28 * 2304 * 64 * 4
    live, ring = 64 * 2500, 64 * 1024 * 21 * TOKEN
    assert costs.step_bytes(MODEL, all_touched, live, ring) \
        == (attn + head) * 2 + router + all_touched * expert \
        + 7 * live * TOKEN + ring
    # a chip streams about 7.25 GB a plain step: 5.55 of experts, 0.41
    # of attention and head, 0.57 of its head's pages, 0.70 of its rings
    assert 7.2e9 < costs.step_bytes(MODEL, all_touched, live, ring) < 7.3e9
    slots = 64 * 8 * 28 // 4
    flops = costs.step_flops(MODEL, 64, slots, live, ring)
    assert flops == 2 * 64 * (attn + head + 28 * 2304 * 64) \
        + 2 * slots * 3 * 2304 * 896 \
        + live * 2 * 2 * (7 * 8) * 128 \
        + (ring / (21 * TOKEN)) * 2 * 2 * (21 * 8) * 128
    # memory binds: the operations are a twentieth of the bytes' time
    assert flops / 197e12 < 0.1 * 7.25e9 / 819e9
    # two all-reduces a layer of a float32 (lanes, 2304) array
    assert costs.exchange_bytes(MODEL, 64) == 56 * 64 * 2304 * 4
    assert costs.exchange_bytes(MODEL, 64) // 56 == 589824       # 0.59 MB
    assert costs.exchange_bytes(MODEL, 576) // 56 == 5308416     # 5.3 MB


# --- the readers, on a trace written by hand --------------------------------

STEP = "jit__state_decode_fn({n})"
MIXED = "jit__state_decode_fn_chunk({n})"
GMM = "%mx_grouped_matmul.e16.m768.k2304.n896.bfloat16.r16.gated.{n} = " \
      "bf16[768,896]{{1,0}} custom-call(...)"
# under shard_map a psum is NAMED for the primitive; its kind says what it is
REDUCE = "%psum.{n} = f32[64,2304]{{1,0:T(8,128)S(1)}} all-reduce(%fusion.{n}), " \
         "channel_id=1"
START = "%all-reduce-start.{n} = f32[64,2304]{{1,0}} all-reduce-start(...)"
DONE = "%all-reduce-done.{n} = f32[64,2304]{{1,0}} all-reduce-done(...)"
OTHER = "%fusion.{n} = bf16[64,2304]{{1,0}} fusion(%p.{n})"
US = 1e3
TOUCHED, SLOTS, RING = 440, 3584, 60 * 1024 * 21 * TOKEN


def _device(step_us, busy_us, exchange_us, hidden_us=0.0, mixed_us=30000.0):
    """One device's lines: two plain steps of ``step_us``, in each
    ``busy_us`` of kernels, ``exchange_us`` of a synchronous all-reduce
    with nothing beside it and ``hidden_us`` of an asynchronous one under
    a kernel; then one mixed step."""
    ops, asyncs, modules, t = [], [], [], 0.0
    for s in range(2):
        start = t
        ops.append((GMM.format(n=s), t, t + busy_us * US))
        if hidden_us:
            ops.append((START.format(n=s), t, t + 1 * US))
            asyncs.append((START.format(n=s), t, t + hidden_us * US))
            ops.append((DONE.format(n=s), t + hidden_us * US - 1 * US,
                        t + hidden_us * US))
        t += busy_us * US
        ops.append((REDUCE.format(n=s), t, t + exchange_us * US))
        t = start + step_us * US
        modules.append((STEP.format(n=1), start, t))
        t += 400 * US
    ops.append((OTHER.format(n=9), t, t + mixed_us * US))
    ops.append((REDUCE.format(n=9), t + mixed_us * US,
                t + (mixed_us + 700) * US))
    modules.append((MIXED.format(n=2), t, t + (mixed_us + 700) * US))
    return {trace_reduce.MODULES_LINE: modules, trace_reduce.OPS_LINE: ops,
            trace_reduce.ASYNC_LINE: asyncs}


def _ctx(devices, chips=4, model=MODEL):
    planes = {"/device:TPU:%d" % i: d for i, d in enumerate(devices)}
    lines = [[("mx:decode.readback", 10.0 + i, 20.0 + i,
               {"moe_slots": SLOTS, "experts_touched": TOUCHED,
                "max_load": 14, "state_rows_live": 60,
                "ring_rows_wrapped": 60, "global_pages_live": 1300,
                "ring_bytes": RING}) for i in range(3)]]
    streams = [{"prompt_len": 2499, "times": [-1.0, 0.1, 0.2, 0.3],
                "sent": 3.0}] * 60
    return types.SimpleNamespace(
        trace=trace_reduce.Trace(planes), chips=chips,
        program_spans=program_spans.Spans(lines), peak=PEAK,
        config=_config(),
        raw={"model": model, "window_s": 30.0, "streams": streams,
             "stats0": {"decode_steps": 0}, "stats1": {"decode_steps": 3},
             "moe_delta": {"steps": 3, "moe_slots": 3 * SLOTS,
                           "experts_touched": 3 * TOUCHED,
                           "ring_bytes": 3 * RING}})


def _read(name, ctx):
    return importlib.import_module(
        "benchmark.layer_metrics." + name).compute(ctx)


def test_readers_read_one_chips_work_against_one_chips_time():
    probe = _ctx([_device(1.0, 1.0, 0.0)])
    live = latent_moe_costs.live_tokens_per_step(probe)
    assert live == 60 * (2500 + 2501 + 2502) / 3
    least_us = costs.step_bytes(MODEL, TOUCHED, live, RING) / 819e9 * 1e6
    assert 8000 < least_us < 9000            # 8.6 ms at the roofline
    # one device whose plain step ran AT one chip's roofline
    one = _ctx([_device(least_us, least_us - 300, 300)], chips=4)
    assert abs(_read("sharded_step_roofline_share", one) - 100.0) < 1e-6
    # the same step on FOUR devices' lines: the share is device 0's, one
    # chip's bytes over one chip's time — never four chips' bytes
    four = _ctx([_device(least_us, least_us - 300, 300),
                 _device(least_us, least_us - 900, 900),
                 _device(least_us, least_us - 500, 500),
                 _device(least_us, least_us - 300, 300)])
    assert abs(_read("sharded_step_roofline_share", four) - 100.0) < 1e-6
    assert abs(_read("exchange_ms_per_step", four) - 0.3) < 1e-9
    # compute time a step over the three step programs, a chip: the
    # kernels of the two plain steps differ by up to 600 us between the
    # chips (the waiting is inside the all-reduce): 2 x 0.6 / 3 ms
    assert abs(_read("chip_skew_ms_per_step", four) - 0.4) < 1e-9
    # twice the time: half the share
    slow = _ctx([_device(2 * least_us, least_us, 600)] * 4)
    assert abs(_read("sharded_step_roofline_share", slow) - 50.0) < 1e-6
    assert abs(_read("exchange_ms_per_step", slow) - 0.6) < 1e-9


def test_exchange_hidden_and_exposed_and_the_chips_skew():
    # device 0: 2 plain steps of 10 ms — 8 ms of kernel with a 5 ms
    # asynchronous all-reduce under it, then 1 ms of all-reduce alone —
    # and one mixed step with 0.7 ms alone
    lagging = _device(10000, 8000, 1000, hidden_us=5000)
    quick = _device(10000, 6500, 1000, hidden_us=5000)
    ctx = _ctx([lagging, quick, quick, quick])
    # in a plain step: the union of 5 ms (hidden) and 1 ms (alone)
    assert abs(_read("exchange_ms_per_step", ctx) - 6.0) < 1e-6
    window = ctx.trace.window_s
    exposed = (2 * 1000 + 700) / 1e6
    assert abs(_read("exchange_exposed_share", ctx)
               - 100.0 * exposed / window) < 1e-6
    # compute a step: (2 x 8 + 30) / 3 on device 0, (2 x 6.5 + 30) / 3
    # on the others: the spread is 1 ms
    assert abs(_read("chip_skew_ms_per_step", ctx) - 1.0) < 1e-6
    assert costs.compute_per_step_s(ctx, "/device:TPU:1") \
        < costs.compute_per_step_s(ctx, "/device:TPU:0")
    # the benchmark's name pattern sees the start / done ends, never the
    # psum: the kind pattern sees both
    assert not trace_reduce.COLLECTIVE.search(REDUCE.format(n=1))
    assert costs.is_collective(REDUCE.format(n=1))
    assert costs.is_collective(START.format(n=1))
    assert not costs.is_collective(GMM.format(n=1))
    assert not costs.is_collective(
        "%fusion.3 = f32[64,2304]{1,0} fusion(f32[64,2304]{1,0} %psum.392)")


def test_readers_find_nothing_without_a_mesh():
    """A program that lacks what this PR adds (the parent commit, a
    one-chip cell): every new reader returns None and none raises."""
    one = _ctx([_device(10000, 8000, 0)], chips=1,
               model={k: v for k, v in MODEL.items() if k != "chips"})
    for name in NEW_METRICS:
        assert _read(name, one) is None, name
    # four chips, a program with no step of that name and no collective
    bare = _ctx([{trace_reduce.MODULES_LINE: [("jit_other(1)", 0.0, 1e7)],
                  trace_reduce.OPS_LINE: [(OTHER.format(n=0), 0.0, 1e6)]}]
                * 4)
    for name in NEW_METRICS:
        got = _read(name, bare)
        assert got is None or (name == "exchange_exposed_share"
                               and got == 0.0), name
    bare.trace = None
    for name in NEW_METRICS:
        assert _read(name, bare) is None, name


# --- the driver ------------------------------------------------------------

def _run(*args):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), *args],
        capture_output=True, text=True, env=env, cwd=ROOT, timeout=900)


def test_the_cell_rehearses_on_four_host_devices():
    proc = _run("--workload", CELL, "--seed", str(2 ** 31 + 7),
                "--rehearse", "--trace", "1")
    assert proc.returncode == 0, proc.stderr[-2000:]
    lines = proc.stdout.strip().splitlines()
    result, detail = json.loads(lines[-1]), json.loads(lines[-2])
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] > 0 and result["rehearsal"] is True
    assert result["device"]["count"] == 4
    assert {"moe_experts_touched_share", "moe_slot_imbalance",
            "kv_preempted", "batch_occupancy", "chunk_step_share"} \
        <= set(result["metrics"])
    assert all(m["value"] is None for m in result["metrics"].values())
    raw = detail["raw"]
    assert raw["mesh"] == 4 and raw["model"]["chips"] == 4
    # ONE chip's sizes: a key/value head, two query heads, two experts
    assert raw["model"]["n_kv_heads"] == 1
    assert raw["model"]["heads"] == [2] * 4
    assert raw["model"]["experts_held"] == 2
    assert raw["model"]["vocab"] == 24
    assert raw["model"]["whole"]["experts_held"] == 8
    chips = raw["moe_by_chip"]
    assert len(chips) == 4 and chips[0] == raw["moe_delta"]
    assert len({c["moe_slots"] for c in chips}) > 1
    assert len({c["ring_bytes"] for c in chips}) == 1
    assert result["compared"]["gap_mean_std"]["value"] < 1e-3


@pytest.mark.parametrize("seed", [3, 2 ** 31 + 11])
def test_the_control_comes_out_not_correct(seed):
    proc = _run("--workload", CELL, "--seed", str(seed), "--rehearse",
                "--control")
    assert proc.returncode == 0, proc.stderr[-2000:]
    lines = proc.stdout.strip().splitlines()
    result, detail = json.loads(lines[-1]), json.loads(lines[-2])
    assert result["correct"] is False and result["failed"] == 0
    gap = result["compared"]["gap_mean_std"]
    assert gap["value"] > gap["limit"]
    assert detail["raw"]["check"]["program"]["gap_mean_std"] < gap["limit"]
