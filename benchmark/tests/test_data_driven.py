"""The harness is driven by data: a fifth cell, a third configuration, a
new traffic mix and a new per-layer metric are new files and new entries
of BENCHMARK.json, and ``run.py`` names none of them."""
import json
import os
import re
import shutil
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)


def _spec():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def _run(root, *args):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    proc = subprocess.run(
        [sys.executable, os.path.join(root, "benchmark", "run.py"), *args],
        capture_output=True, text=True, env=env, cwd=root, timeout=900)
    return proc


def test_run_py_names_no_cell_config_metric_or_model():
    spec = _spec()
    names = {e["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for e in spec[k]}
    names |= {w["traffic"] for w in spec["workloads"]}
    names |= {"resnet", "opt", "ToyDecoderLM", "DecodeServer"}
    with open(os.path.join(BENCH, "run.py")) as f:
        code = f.read()
    with open(os.path.join(BENCH, "harness.py")) as f:
        code += f.read()
    found = [n for n in names if re.search(r"\b%s\b" % re.escape(n), code)]
    assert not found, found


def test_every_entry_has_its_file_and_every_reader_its_entry():
    spec = _spec()
    for cfg in spec["configs"]:
        assert os.path.isfile(os.path.join(ROOT, cfg["file"]))
    for cell in spec["workloads"]:
        path = os.path.join(BENCH, "traffic", cell["traffic"] + ".json")
        with open(path) as f:
            driver = json.load(f)["driver"]
        assert os.path.isfile(os.path.join(BENCH, "drivers", driver + ".py"))
    sys.path.insert(0, ROOT)
    import importlib
    e2e = {m["name"] for m in spec["end_to_end"]}
    for package, key in (("e2e_metrics", "end_to_end"),
                         ("layer_metrics", "per_layer")):
        entries = {m["name"]: m for m in spec[key]}
        files = [f[:-3] for f in os.listdir(os.path.join(BENCH, package))
                 if f.endswith(".py") and not f.startswith("_")]
        assert sorted(files) == sorted(entries)
        for name in files:
            mod = importlib.import_module("benchmark.%s.%s" % (package, name))
            assert mod.NAME == name and mod.UNIT == entries[name]["unit"]
            if key == "per_layer":
                assert mod.LAYER == entries[name]["layer"]
                assert entries[name]["moves"] in e2e


def test_a_new_cell_config_traffic_and_metric_need_only_new_files(tmp_path):
    root = str(tmp_path)
    shutil.copytree(BENCH, os.path.join(root, "benchmark"),
                    ignore=shutil.ignore_patterns("out", "__pycache__"))
    os.symlink(os.path.join(ROOT, "mxnet_tpu"),
               os.path.join(root, "mxnet_tpu"))
    before = {}
    for folder, _, files in os.walk(os.path.join(root, "benchmark")):
        for name in files:
            path = os.path.join(folder, name)
            with open(path, "rb") as f:
                before[path] = f.read()
    spec = _spec()
    # a third configuration: the served model at other (tiny) widths
    with open(os.path.join(BENCH, "configs", "opt-6.7b.json")) as f:
        config = json.load(f)
    config["tiny"]["model"]["kwargs"].update(n_heads=4, d_ff=96)
    with open(os.path.join(root, "benchmark/configs/other-lm.json"), "w") as f:
        json.dump(config, f)
    spec["configs"].append({
        "name": "other-lm", "source": "test",
        "file": "benchmark/configs/other-lm.json", "reduced": [],
        "why": "test"})
    # a new traffic mix: the open loop at another rate and other lengths
    with open(os.path.join(BENCH, "traffic", "longprompt-steady.json")) as f:
        mix = json.load(f)
    mix["tiny"].update(
        arrivals={"kind": "poisson", "rate_per_s": 30.0},
        prompt_len={"dist": "lognormal", "median": 20, "sigma": 0.5,
                    "min": 5, "max": 60})
    with open(os.path.join(root, "benchmark/traffic/brisk.json"), "w") as f:
        json.dump(mix, f)
    # a fifth cell
    spec["workloads"].append({
        "name": "other-brisk", "config": "other-lm", "traffic": "brisk",
        "chips": 1, "why": "test"})
    for metric in spec["end_to_end"] + spec["per_layer"]:
        if "opt-longprompt-steady" in metric.get("workloads", []):
            metric["workloads"].append("other-brisk")
    # a new per-layer metric, its reader a file of its own
    with open(os.path.join(root, "benchmark/layer_metrics/queue_peak.py"),
              "w") as f:
        f.write('NAME, UNIT, LAYER = "queue_peak", "count", '
                '"Decode scheduler"\n\n\n'
                'def compute(ctx):\n'
                '    return ctx.raw["stats1"]["queue_peak"]\n')
    spec["per_layer"].append({
        "name": "queue_peak", "unit": "count", "better": "lower",
        "source": "program_counter", "layer": "Decode scheduler",
        "moves": "itl_p99_ms", "workloads": ["other-brisk"]})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(spec, f)

    for trace, expect in ((0, {"itl_p99_ms", "setup_s"}),
                          (1, {"queue_peak", "gen_late_p99_ms", "ttft_p95_ms",
                               "batch_occupancy", "compiles_in_window"})):
        proc = _run(root, "--workload", "other-brisk", "--seed", "3",
                    "--trace", str(trace), "--rehearse")
        assert proc.returncode == 0, proc.stderr[-2000:]
        result = json.loads(proc.stdout.strip().splitlines()[-1])
        assert result["correct"] is True and result["failed"] == 0
        assert result["attempted"] > 0
        assert expect <= set(result["metrics"])
    # nothing the benchmark already had was edited
    for path, content in before.items():
        with open(path, "rb") as f:
            assert f.read() == content, path


def test_without_the_program_it_fails_and_prints_no_result(tmp_path):
    root = str(tmp_path)
    shutil.copytree(BENCH, os.path.join(root, "benchmark"),
                    ignore=shutil.ignore_patterns("out", "__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
    proc = _run(root, "--workload", _spec()["workloads"][0]["name"],
                "--seed", "0", "--seconds", "1", "--trace", "0")
    assert proc.returncode != 0
    assert '"correct"' not in proc.stdout


def test_on_a_cpu_it_fails_and_prints_no_result():
    proc = _run(ROOT, "--workload", _spec()["workloads"][0]["name"],
                "--seed", "0", "--seconds", "1", "--trace", "0")
    assert proc.returncode != 0
    assert '"correct"' not in proc.stdout
    assert "needs a TPU" in proc.stderr


@pytest.mark.parametrize("cell", [w["name"] for w in _spec()["workloads"]])
@pytest.mark.parametrize("trace", [0, 1])
def test_rehearse_every_cell(cell, trace):
    """Control flow of each cell at its tiny size on the CPU (a cell on
    four chips gets four virtual devices). Never a number: every value
    is null."""
    proc = _run(ROOT, "--workload", cell, "--seed", "2", "--trace",
                str(trace), "--rehearse")
    assert proc.returncode == 0, proc.stderr[-2000:]
    result = json.loads(proc.stdout.strip().splitlines()[-1])
    assert set(result) >= {"correct", "attempted", "failed", "metrics",
                           "device"}
    assert result["correct"] is True, proc.stdout[-3000:]
    assert result["failed"] == 0 and result["attempted"] > 0
    assert result["device"]["platform"] == "cpu"
    assert result["metrics"] and all(
        m["value"] is None for m in result["metrics"].values())
    spec = _spec()
    key = "per_layer" if trace else "end_to_end"
    allowed = {m["name"] for m in spec[key]
               if cell in m.get("workloads", [cell])}
    assert set(result["metrics"]) <= allowed
    if not trace:
        assert set(result["metrics"]) == allowed
