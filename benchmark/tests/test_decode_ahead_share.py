"""``decode_ahead_share`` (PR 30): plain arithmetic over the server's
counters on made-up runs, nothing where the program does not count."""
import json
import os
import types

from benchmark.layer_metrics import decode_ahead_share

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _ctx(**raw):
    return types.SimpleNamespace(raw=raw)


def test_share_is_the_windows_steps_ahead_over_its_steps():
    ctx = _ctx(stats0={"decode_steps": 100, "decode_steps_ahead": 90},
               stats1={"decode_steps": 300, "decode_steps_ahead": 280})
    assert abs(decode_ahead_share.compute(ctx) - 95.0) < 1e-9
    # every step of the window drained: a number, and it is 0
    ctx.raw["stats1"]["decode_steps_ahead"] = 90
    assert decode_ahead_share.compute(ctx) == 0.0


def test_nothing_where_the_program_does_not_count_or_did_not_step():
    # the parent commit's ``stats()``
    parent = _ctx(stats0={"decode_steps": 100}, stats1={"decode_steps": 300})
    assert decode_ahead_share.compute(parent) is None
    # a training cell: no server at all
    assert decode_ahead_share.compute(_ctx()) is None
    still = _ctx(stats0={"decode_steps": 7, "decode_steps_ahead": 5},
                 stats1={"decode_steps": 7, "decode_steps_ahead": 5})
    assert decode_ahead_share.compute(still) is None


def test_the_entry_names_the_serving_cells_and_the_schedulers_layer():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        spec = json.load(f)
    entry = next(m for m in spec["per_layer"]
                 if m["name"] == decode_ahead_share.NAME)
    reports = next(m for m in spec["end_to_end"]
                   if m["name"] == "itl_p99_ms")["workloads"]
    # every cell that reports a token gap runs the one loop
    assert entry == {
        "name": decode_ahead_share.NAME, "unit": decode_ahead_share.UNIT,
        "better": "higher", "source": "program_counter",
        "layer": decode_ahead_share.LAYER, "moves": "itl_p99_ms",
        "workloads": reports}
    assert {"opt-decode-batch", "opt-longprompt-steady",
            "dots-decode-batch"} <= set(reports) \
        <= {w["name"] for w in spec["workloads"]}
