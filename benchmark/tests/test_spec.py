"""BENCHMARK.json against the letter of the benchmark's contract, so that
a PR that adds an entry finds out here and not from the driver."""
import json
import os
import re

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def _spec():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def _line(text):
    return 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text


def test_keys_names_units_and_lengths():
    spec = _spec()
    assert set(spec) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 << 10
    assert 1 <= len(spec["command"]) <= 32 and all(map(_line, spec["command"]))
    assert 1 <= len(spec["paths"]) <= 16 and all(map(PATH.match, spec["paths"]))
    assert isinstance(spec["run_seconds"], int) and 1 <= spec["run_seconds"] <= 51
    assert 1 <= len(spec["configs"]) <= 24
    for c in spec["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and _line(c["source"]) and _line(c["why"])
        assert any(c["file"].startswith(p + "/") for p in spec["paths"])
        assert len(c["reduced"]) <= 16 and all(map(NAME.match, c["reduced"]))
        with open(os.path.join(ROOT, c["file"])) as f:
            body = json.load(f)
        assert all(k in body for k in c["reduced"])
    assert len({c["file"] for c in spec["configs"]}) == len(spec["configs"])
    assert 2 <= len(spec["workloads"]) <= 24
    for w in spec["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4) and _line(w["why"])
    four = sum(w["chips"] == 4 for w in spec["workloads"])
    assert four <= max(1, len(spec["workloads"]) // 4)
    pairs = [(w["config"], w["traffic"]) for w in spec["workloads"]]
    assert len(set(pairs)) == len(pairs)
    used = {w["config"] for w in spec["workloads"]}
    assert used == {c["name"] for c in spec["configs"]}
    for key in ("configs", "workloads"):
        names = [e["name"] for e in spec[key]]
        assert len(set(names)) == len(names)


def test_metrics_and_where_they_are_reported():
    spec = _spec()
    cells = [w["name"] for w in spec["workloads"]]
    e2e, layer = spec["end_to_end"], spec["per_layer"]
    assert 1 <= len(e2e) <= 16 and 1 <= len(layer) <= 128
    names = [m["name"] for m in e2e + layer]
    assert len(set(names)) == len(names)
    for m in e2e:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
    for m in layer:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert m["source"] in SOURCES and _line(m["layer"])
    reported = {c: {m["name"] for m in e2e
                    if c in m.get("workloads", cells)} for c in cells}
    for m in e2e + layer:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert set(m.get("workloads", cells)) <= set(cells)
    for c in cells:
        assert "setup_s" in reported[c] and len(reported[c]) >= 2
        mine = [m for m in layer if c in m.get("workloads", cells)]
        assert mine
        # a per-layer metric is reported only where the metric it moves is
        assert all(m["moves"] in reported[c] for m in mine), c
    assert next(m for m in e2e if m["name"] == "setup_s")["bound"] <= 0.1
    # metrics of one layer name it letter for letter (case included)
    layers = {m["layer"] for m in layer}
    assert len({l.lower() for l in layers}) == len(layers)


def test_the_full_check_fits_its_time():
    spec = _spec()
    runs = 2 + 14 * 24
    assert runs * (spec["run_seconds"] + 60) + 24 * 2 * 90 + 1200 <= 43200


def _load(*parts):
    with open(os.path.join(ROOT, *parts)) as f:
        return json.load(f)


def test_a_configurations_cut_is_said_the_same_everywhere():
    """``reduced`` in BENCHMARK.json, ``reduced_from`` and the key itself
    in the file, the kwargs the model is built with and the sentence
    that gives the reason all name one cut, each configuration its own."""
    served = 0
    for entry in _spec()["configs"]:
        cfg = _load(entry["file"])
        cut = cfg.get("reduced_from", {})
        assert sorted(entry["reduced"]) == sorted(cut), entry["name"]
        for key, published in cut.items():
            assert cfg[key] < published, (entry["name"], key)
        if "num_hidden_layers" not in cut:
            continue
        served += 1
        depth = cfg["num_hidden_layers"]
        kw = cfg["model"]["kwargs"]
        assert depth in (kw.get("n_layers"), kw.get("num_hidden_layers")), \
            entry["name"]
        # the reason names the depth served ("depth 8", "7 of the 48
        # layers") and the next one, with the bytes that decide between
        # them
        why = cfg["why_reduced"].lower()
        assert "depth %d" % depth in why or "%d of the %d layers" % (
            depth, cut["num_hidden_layers"]) in why, entry["name"]
        assert "depth %d" % (depth + 1) in why, entry["name"]
        assert "GB" in cfg["why_reduced"], entry["name"]
        if "n_layers" in kw:
            # the plain decoder's served sizes, which no depth may change
            assert kw["n_heads"] * kw["head_dim"] == cfg["hidden_size"]
            assert kw["d_ff"] == cfg["ffn_dim"]
            assert kw["vocab"] == cfg["vocab_size"]
    assert served >= 1


def test_an_open_loops_rate_is_four_fifths_of_the_knee_its_reason_names():
    for cell in _spec()["workloads"]:
        mix = _load("benchmark", "traffic", cell["traffic"] + ".json")
        arrivals = mix.get("arrivals", {})
        if arrivals.get("kind") != "poisson":
            continue
        rate = arrivals["rate_per_s"]
        knee = re.search(r"[Kk]nee (\d+(?:\.\d+)?) req/s", mix["rate_why"])
        assert knee, mix["rate_why"]
        assert abs(rate - 0.8 * float(knee.group(1))) < 1e-9
        said = "%g req/s" % rate
        assert said in mix["rate_why"] and said in mix["what"]
        assert said in cell["why"], cell["why"]
        # the sweep is printed: every rate tried, with what it read
        assert len(re.findall(r"\d+(?:\.\d+)?: \d+ / \d+",
                              mix["rate_why"])) >= 5, mix["rate_why"]
