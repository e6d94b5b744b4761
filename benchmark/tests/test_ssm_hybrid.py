"""The state-space cell: its entries in BENCHMARK.json against the
catalog's row (nothing cut), its traffic as the issue's table has it, its
cost functions against hand counts at the published widths and its six
readers on a trace written by hand (a kernel that ran AT its roofline
reads 100%, never more; a program without the kernels and the spans
reads nothing and raises nothing), the driver's draw of what a
state-space layer keeps that is no matrix, ``--rehearse`` of the cell,
and ``--control`` through to ``correct: false``."""
import importlib
import json
import os
import subprocess
import sys
import types

import pytest

from benchmark import program_spans, trace_reduce
from benchmark import ssm_hybrid_costs as costs

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
CELL, CONFIG = "jamba-ssm-chat-batch", "AI21-Jamba2-3B"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
NEW_METRICS = {"ssm_step_ms_per_step", "ssm_step_roofline_share",
               "ssm_chunk_ms_per_step", "ssm_chunk_roofline_share",
               "ssm_hybrid_step_mfu", "ssm_hybrid_mixed_step_mfu"}
PEAK = {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
PER = {"weights": 2, "kv": 2, "state": 4}
ROW = 16 * 5120 * 4                    # one row's h in one layer


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
    assert entry["reduced"] == [] and cfg["reduced_from"] == {}
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f) if r["name"] == CONFIG)
    assert entry["source"] == row["source_url"]
    kw = cfg["model"]["kwargs"]
    for key, published in row["config"].items():
        assert cfg[key] == published, key
        assert kw[key] == published, key
    assert set(kw) == set(row["config"])        # nothing beside them
    assert (len(spec["configs"]), len(spec["workloads"])) == (9, 11)
    assert sum(w["chips"] == 4 for w in spec["workloads"]) == 2
    for said in ("published_code", "layer_order", "layer_ratio", "head_dim",
                 "experts", "state_dtype", "mamba_init", "embedding",
                 "convolution", "precision", "state_row", "cache_row",
                 "read_as_they_stand", "weights", "conv_scope"):
        assert cfg["assumed"][said], said
    assert "one chip holds the whole model" in cfg["deployment"]
    assert cfg["server"]["kwargs"] == {
        "seq_ladder": [256, 512], "max_new_tokens": 1024, "page_size": 128,
        "window": 128, "pool_pages": 1664, "max_queue": 256,
        "prefix_cache": False}
    assert cfg["bytes_per_value"] == PER
    names = cfg["trace_names"]
    assert (names["ssm_step_kernel"], names["ssm_chunk_kernel"],
            names["conv_scope"]) == ("ssm_step", "ssm_chunk", "mx_ssm_conv")
    # no prefill program runs; the name is what ``launch_join`` asks a
    # configuration for before it joins the step programs to their spans
    assert names["prefill_module"] == "_state_prefill_fn"


def test_the_cell_its_traffic_and_where_its_metrics_are_listed():
    spec = _json("BENCHMARK.json")
    cell = next(w for w in spec["workloads"] if w["name"] == CELL)
    assert spec["workloads"][-1] is cell
    assert (cell["config"], cell["traffic"], cell["chips"]) \
        == (CONFIG, "ssm-chat-batch-w128", 1)
    mix = _json("benchmark", "traffic", cell["traffic"] + ".json")
    assert mix["driver"] == "serve_ssm_hybrid"
    assert mix["arrivals"] == {"kind": "closed", "clients": 256}
    assert mix["prompt_len"] == {"dist": "lognormal", "median": 192,
                                 "sigma": 0.6, "min": 64, "max": 512}
    assert mix["output_len"] == {"dist": "lognormal", "median": 384,
                                 "sigma": 0.4, "min": 128, "max": 1024}
    assert mix["lead_in_s"] == 2.0 and mix["unfinished_at_end"] == "cut"
    assert mix["prompt_len"]["max"] <= max(
        _config()["server"]["kwargs"]["seq_ladder"])
    assert set(mix["check"]["limits"]) == {"gap_mean_std"}
    for m in spec["end_to_end"]:
        listed = CELL in m.get("workloads", [CELL])
        assert listed == (m["name"] in ("serve_tok_per_s", "itl_p99_ms",
                                        "setup_s")), m["name"]
    layer = {m["name"]: m for m in spec["per_layer"]}
    for name in NEW_METRICS:
        assert layer[name]["workloads"] == [CELL], name
        assert layer[name]["source"] == "device_trace"
    assert {layer[n]["moves"] for n in ("ssm_hybrid_step_mfu",
                                        "ssm_step_roofline_share")} \
        == {"serve_tok_per_s"}
    assert {layer[n]["moves"] for n in ("ssm_hybrid_mixed_step_mfu",
                                        "ssm_chunk_roofline_share")} \
        == {"itl_p99_ms"}
    # no prefill program runs here: none of its readers lists the cell
    for name in ("prefill_attn_ms", "prefill_device_ms", "prefill_queue_ms",
                 "admit_idle_ms", "kda_prefill_ms",
                 "flash_fwd_roofline_share", "prefill_step_share"):
        assert CELL not in layer[name]["workloads"], name
    # a joined list gained the cell at its end and nothing else
    for name, m in layer.items():
        if CELL in m["workloads"] and name not in NEW_METRICS:
            assert m["workloads"][-1] == CELL, name


def test_every_clients_shapes_are_one_draw_whatever_the_seed():
    from benchmark.drivers import serve_ssm_hybrid as driver
    mix = _json("benchmark", "traffic", "ssm-chat-batch-w128.json")
    first = [[next(g) for _ in range(4)] for g in (
        driver.shapes(s, mix) for s in range(256))]
    again = [[next(g) for _ in range(4)] for g in (
        driver.shapes(s, mix) for s in range(256))]
    assert first == again
    prompts = [p for client in first for p, _ in client]
    answers = [a for client in first for _, a in client]
    assert min(prompts) >= 64 and max(prompts) <= 512
    assert min(answers) >= 128 and max(answers) <= 1024
    # about a third of the prompts take the 512-lane chunk
    assert 0.25 < sum(p > 256 for p in prompts) / len(prompts) < 0.4


# --- the costs -------------------------------------------------------------

def _ctx_sizes():
    return types.SimpleNamespace(config=_config())


def test_costs_against_hand_counts_at_the_published_widths():
    s = costs.sizes(_ctx_sizes())
    assert s == {"d_model": 2560, "d_ff": 8192, "vocab": 65536,
                 "n_layers": 28, "attention_layers": 2, "ssm_layers": 26,
                 "heads": 20, "kv_heads": 1, "head_dim": 128,
                 "d_inner": 5120, "d_state": 16, "dt_rank": 160,
                 "d_conv": 4}
    assert costs.state_row_bytes(s) == ROW == 327680
    assert costs.ssm_matrix_params(s) == (
        2560 * 10240 + 5120 * 192 + 160 * 5120 + 5120 * 2560) == 41123840
    assert costs.ssm_vector_params(s) == (
        4 * 5120 + 5120 + 160 + 32 + 5120 + 16 * 5120 + 5120) == 117952
    assert costs.attention_params(s) == 2 * 2560 * 2560 + 2 * 2560 * 128 \
        == 13762560
    assert costs.mlp_params(s) == 3 * 2560 * 8192 == 62914560
    # the issue's 3.03 B parameters, 6.06 GB in bf16
    assert 3.02e9 < costs.params(s) < 3.04e9
    assert 6.05e9 < costs.matrix_bytes(s) < 6.08e9
    assert costs.kv_token_bytes(s) == 512           # 1 KB over two layers
    # a plain step at 128 rows, 500 tokens a row: the issue's 8.4 GB,
    # the state a quarter of it
    got = costs.step_bytes(s, 128, 128 * 500, PER)
    assert got == (costs.matrix_bytes(s) + 2 * 128 * 26 * ROW
                   + 128 * 26 * 4 * 5120 * 2 + 2 * 128 * 500 * 512)
    assert 8.3e9 < got < 8.6e9
    assert 0.24 < 2 * 128 * 26 * ROW / got < 0.28
    assert 10.1e-3 < got / 819e9 < 10.5e-3
    # the step kernel: memory binds by two orders
    small = (3 * 5120 + 32) * 4
    assert costs.ssm_step_bytes(s, 128) == 26 * (
        128 * (2 * ROW + small) + ROW)
    assert costs.ssm_step_ops(s, 128) == 128 * 26 * 7 * 16 * 5120
    assert costs.ssm_step_bytes(s, 128) / 819e9 \
        > 100 * costs.ssm_step_ops(s, 128) / 197e12
    assert costs.ssm_chunk_bytes(s, 512) == 26 * (512 * small + 3 * ROW)
    assert costs.ssm_chunk_ops(s, 512) == 512 * 26 * 7 * 16 * 5120
    # products: 6.06 GFLOP a lane; memory binds a plain step, a 256-lane
    # chunk beside 128 rows rides nearly free under the weights (its
    # products within 15% of the step's bytes), a 512-lane one does not
    # (nearly twice)
    lane = costs.step_flops(s, 1, 0, 0)
    assert 6.05e9 < lane < 6.08e9
    assert costs.step_flops(s, 128, 512, 0) == 2 * (
        640 * (lane // 2 - 65536 * 2560) + 129 * 65536 * 2560)
    b = costs.step_bytes(s, 128, 64000, PER) / 819e9
    for lanes, low, high in ((0, 0.3, 0.45), (256, 1.0, 1.15),
                             (512, 1.7, 2.0)):
        f = costs.step_flops(s, 128, lanes, 64000) / 197e12
        assert low < f / b < high, (lanes, f / b)
        assert costs.least_step_s(s, 128, lanes, 64000, PER, PEAK) \
            == max(b, f)


# --- the readers, on a trace written by hand --------------------------------

STEP = "jit__state_decode_fn(1)"
MIXED = "jit__state_decode_fn_chunk(%d)"
SSM = "%mx_ssm_step.b128.e5120.n16.{n} = (f32[128,1,5120]{{2,1,0}}, " \
      "f32[26,128,16,5120]{{3,2,1,0}}) custom-call(...)"
CHUNK = "%mx_ssm_chunk.c{c}.e5120.n16.{n} = (f32[{c},5120]{{1,0}}, " \
        "f32[16,5120]{{1,0}}) custom-call(...)"
OTHER = "%fusion.{n} = bf16[128,2560]{{1,0}} fusion(%p.{n})"
US = 1e3
ROWS = 120.0
# what ``live_tokens_per_step`` reads of ``_ctx``'s streams: 128 streams
# whose tokens 1-4 attended 500-503 keys, over four steps
LIVE = 128 * (500 + 501 + 502 + 503) / 4


def _ctx(ssm_us, chunk_us, step_us):
    """Two plain steps, one mixed step of 200 live lanes on the 256 rung
    and one of 400 on the 512 rung; ``step_us`` is ``(plain, c256,
    c512)`` device time, ``chunk_us`` ``(c256, c512)``."""
    ops, modules, t = [], [], 0.0

    def put(name, us):
        nonlocal t
        ops.append((name, t, t + us * US))
        t += us * US

    plan = [(STEP, None, step_us[0])] * 2 + [
        (MIXED % 2, 256, step_us[1]), (MIXED % 3, 512, step_us[2])]
    for at, (module, rung, us) in enumerate(plan):
        start = t
        put(OTHER.format(n=at), 100)
        for layer in range(26):
            put(SSM.format(n=100 * at + layer), ssm_us / 26)
        if rung:
            for layer in range(26):
                put(CHUNK.format(c=rung, n=100 * at + layer),
                    chunk_us[rung == 512] / 26)
        assert t <= start + us * US
        t = start + us * US
        modules.append((module, start, t))
        t += 500 * US
    planes = {"/device:TPU:0": {trace_reduce.MODULES_LINE: modules,
                                trace_reduce.OPS_LINE: ops}}
    said = [{}, {}, {"chunk": 200, "chunk_of": 7},
            {"chunk": 400, "chunk_of": 8}]
    lines = [[("mx:decode.dispatch", 10.0 + i, 20.0 + i,
               dict(state_rows_live=int(ROWS), seq=i, **extra))
              for i, extra in enumerate(said)]]
    streams = [{"prompt_len": 499, "times": [-1.0, 0.1, 0.2, 0.3, 0.4]}] \
        * 128
    return types.SimpleNamespace(
        trace=trace_reduce.Trace(planes),
        program_spans=program_spans.Spans(lines), peak=PEAK,
        config=_config(),
        raw={"window_s": 30.0, "streams": streams,
             "stats0": {"decode_steps": 0}, "stats1": {"decode_steps": 4}})


def _read(name, ctx):
    return importlib.import_module(
        "benchmark.layer_metrics." + name).compute(ctx)


def test_readers_on_a_trace_in_which_everything_ran_at_its_roofline():
    s = costs.sizes(_ctx_sizes())
    ssm_us = costs.ssm_step_bytes(s, ROWS) / 819e9 * 1e6
    chunk_us = [costs.ssm_chunk_bytes(s, n) / 819e9 * 1e6
                for n in (200, 400)]
    step_us = [costs.least_step_s(s, ROWS, n, LIVE, PER, PEAK) * 1e6
               for n in (0, 200, 400)]
    ctx = _ctx(ssm_us, chunk_us, step_us)
    assert costs.dispatched(ctx) == [(120.0, 0.0), (120.0, 0.0),
                                     (120.0, 200.0), (120.0, 400.0)]
    assert costs.widest_rung(ctx) == 512
    assert (costs.rung_of(ctx, 200), costs.rung_of(ctx, 400)) == (256, 512)
    assert len(costs.step_modules(ctx)) == 4
    assert len(costs.step_modules(ctx, 512)) == 1
    assert abs(_read("ssm_step_ms_per_step", ctx) - ssm_us / 1e3) < 1e-9
    assert abs(_read("ssm_step_roofline_share", ctx) - 100.0) < 1e-6
    # the chunk's share is read over the MEAN live lanes (300) and the
    # mean kernel time of the mixed steps: bytes are linear in lanes
    assert abs(_read("ssm_chunk_ms_per_step", ctx)
               - sum(chunk_us) / 2e3) < 1e-9
    assert abs(_read("ssm_chunk_roofline_share", ctx) - 100.0) < 1e-6
    assert abs(_read("ssm_hybrid_step_mfu", ctx) - 100.0) < 1e-6
    assert abs(_read("ssm_hybrid_mixed_step_mfu", ctx) - 100.0) < 1e-6
    # twice the time: half the share, never more than the whole
    slow = _ctx(2 * ssm_us, [2 * c for c in chunk_us],
                [2 * u for u in step_us])
    for name in ("ssm_step_roofline_share", "ssm_chunk_roofline_share",
                 "ssm_hybrid_step_mfu", "ssm_hybrid_mixed_step_mfu"):
        assert abs(_read(name, slow) - 50.0) < 1e-6, name
    # the widest rung's steps alone set the mixed share
    tail = _ctx(ssm_us, chunk_us, [step_us[0], step_us[1], 4 * step_us[2]])
    assert abs(_read("ssm_hybrid_mixed_step_mfu", tail) - 25.0) < 1e-6
    assert _read("ssm_hybrid_step_mfu", tail) > 25.0


def test_readers_find_nothing_in_a_program_without_the_kernels():
    """A program that lacks what this configuration adds (the parent
    commit, another model): every new reader returns None and none
    raises."""
    ctx = _ctx(1000, [500, 900], [20000, 21000, 23000])
    ctx.trace = trace_reduce.Trace({"/device:TPU:0": {
        trace_reduce.MODULES_LINE: [(STEP, 0.0, 1e7)],
        trace_reduce.OPS_LINE: [(OTHER.format(n=0), 0.0, 1e6)]}})
    ctx.program_spans = program_spans.Spans([[]])
    for name in sorted(NEW_METRICS):
        assert _read(name, ctx) is None, name
    ctx = _ctx(1000, [500, 900], [20000, 21000, 23000])
    for config in ("dots.vlm1.inst.json", "Ling-3.0-flash.json",
                   "resnet50_v1.json"):
        ctx.config = _json("benchmark", "configs", config)
        for name in sorted(NEW_METRICS):
            assert _read(name, ctx) is None, (config, name)
    ctx.trace = None
    ctx.config = _config()
    for name in sorted(NEW_METRICS):
        assert _read(name, ctx) is None, name


# --- the driver ------------------------------------------------------------

def test_the_steps_vectors_are_drawn_and_everything_else_is_the_parents():
    import jax
    import numpy as np
    from benchmark.drivers import serve_latent_moe, serve_ssm_hybrid
    from benchmark import harness
    from mxnet_tpu.serving import ssm_hybrid
    cfg = _config()
    assert cfg["weights"]["mamba"]["dt"] == list(ssm_hybrid.DT_RANGE)
    assert cfg["weights"]["tables"]["embed"] == ssm_hybrid.EMBED_STD
    tiny = cfg["tiny"]["model"]
    model = harness.load_object(tiny["import"])(**tiny["kwargs"])
    seed = 2 ** 31 + 5
    params = serve_ssm_hybrid.make_params(model, cfg["weights"], seed)
    plain = serve_latent_moe.make_params(model, cfg["weights"], seed)
    assert sorted(params) == sorted(plain) == sorted(
        jax.eval_shape(model.init_params, 0))
    drawn = 0
    for name in params:
        a = np.asarray(params[name].astype("float32"))
        leaf = name.rsplit(".", 1)[-1]
        if leaf == "A_log":
            assert np.allclose(np.exp(a[:, 0]), np.arange(1, 9))
            assert (a == a[:, :1]).all()
        elif leaf == "dt_b":
            step = np.log1p(np.exp(a))
            assert 1e-3 * 0.99 < step.min() < step.max() < 1e-1 * 1.01
            assert step.std() > 0.01
        elif leaf == "D":
            assert (a == 1.0).all()
        elif leaf == "wdt":
            assert -8 ** -0.5 <= a.min() < a.max() <= 8 ** -0.5
        elif leaf == "conv_b":
            assert -0.5 <= a.min() < a.max() <= 0.5 and a.std() > 0.1
        else:
            assert (a == np.asarray(plain[name].astype("float32"))).all()
            continue
        drawn += 1
    assert drawn == 5 * model.state_layers
    assert np.asarray(params["embed"].astype("float32")).std() < 0.03
    # a step's decay reaches from a state that forgets in a token or two
    # to one that remembers a thousand
    x = jax.random.normal(jax.random.PRNGKey(0), (64, model.d_inner))
    _u, delta, _b, _c = model._scan_inputs(0, x, params,
                                           np.ones((64,), bool))
    decay = np.exp(np.asarray(delta)[:, None, :]
                   * -np.exp(np.asarray(params["l0.A_log"]))[None])
    assert np.percentile(decay, 1) < 0.6 < 0.995 < np.percentile(decay, 99)


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
    assert {"recurrent_state_share", "chunk_step_share", "kv_preempted",
            "batch_occupancy", "host_slack_share"} <= set(result["metrics"])
    assert all(m["value"] is None for m in result["metrics"].values())
    stats = detail["raw"].get("stats_delta") or {}
    assert stats.get("chunk_steps", 1) > 0
    assert stats.get("prefill_programs", 0) == 0


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
    assert check["state_bf16"]["gap_mean_std"] >= 0
    for sample in check["samples"]:
        assert sample["control"] == "float8"
        assert sample["control_mean"] == sample["float8_mean"]
