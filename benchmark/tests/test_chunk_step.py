"""``chunk_step_share`` and ``chunk_step_device_ms`` (PR 40): a decode
step that carries a chunk of a prompt is the mixed program
``jit__decode_fn_chunk``, a decode step like any other to every reader
that divides by steps and the only one this pair reads. On made-up
counters and a trace written by hand; nothing where the program has
neither (the parent commit)."""
import json
import os
import types

import pytest

from benchmark import trace_reduce
from benchmark.layer_metrics import (chunk_step_device_ms, chunk_step_share,
                                     decode_step_device_ms)

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
MS = 1_000_000
NAMES = {"prefill_module": "_prefill_fn", "step_module": "_decode_fn"}


def _trace(modules):
    """A slice whose device line holds ``[(name, start ms, ms), ...]``."""
    events = [(name, at * MS, (at + ms) * MS) for name, at, ms in modules]
    hi = max(e for _, _, e in events) + MS
    return trace_reduce.Trace({
        "/device:TPU:0": {trace_reduce.MODULES_LINE: events},
        "/host:CPU": {"python3": [(trace_reduce.SLICE_SPAN, 0, hi)]}})


def _ctx(modules=None, names=NAMES, **raw):
    return types.SimpleNamespace(
        raw=raw, config={"trace_names": names},
        trace=None if modules is None else _trace(modules))


STEP = "jit__decode_fn(1234567890123456789)"
MIXED = "jit__decode_fn_chunk(9876543210987654321)"
PREFILL = "jit__prefill_fn(5555555555555555555)"


def test_the_step_reader_counts_both_programs_and_this_one_the_mixed():
    ctx = _ctx([(STEP, 0, 11.0), (MIXED, 11, 14.0), (STEP, 25, 11.2),
                (MIXED, 37, 13.0), (STEP, 50, 11.1), (MIXED, 62, 15.0),
                (STEP, 77, 11.3)])
    both = decode_step_device_ms.durations_s(ctx)
    assert len(both) == 7
    assert abs(decode_step_device_ms.compute(ctx) - 11.3) < 1e-9
    assert len(chunk_step_device_ms.durations_s(ctx)) == 3
    assert abs(chunk_step_device_ms.compute(ctx) - 14.0) < 1e-9


def test_a_block_or_speculative_step_is_not_taken_for_a_mixed_one():
    # other forms' step programs carry ``_decode_fn`` in their names too
    ctx = _ctx([("jit__block_decode_fn(1)", 0, 14.0),
                ("jit__spec_decode_fn(2)", 15, 17.0),
                ("jit__state_decode_fn(3)", 33, 10.0)])
    assert len(decode_step_device_ms.durations_s(ctx)) == 3
    assert chunk_step_device_ms.compute(ctx) is None


def test_nothing_where_no_mixed_program_ran():
    # the parent commit: every prompt a prefill program of its own
    parent = _ctx([(STEP, 0, 11.0), (PREFILL, 11, 12.0), (STEP, 23, 11.0)])
    assert chunk_step_device_ms.compute(parent) is None
    assert chunk_step_device_ms.compute(_ctx()) is None          # untraced
    assert chunk_step_device_ms.compute(
        _ctx([(MIXED, 0, 14.0)], names={})) is None     # a training cell


def test_share_is_the_windows_mixed_steps_over_its_steps():
    ctx = _ctx(stats0={"decode_steps": 100, "chunk_steps": 4},
               stats1={"decode_steps": 2100, "chunk_steps": 64})
    assert abs(chunk_step_share.compute(ctx) - 3.0) < 1e-9
    # a window that admitted nobody: a number, and it is 0
    ctx.raw["stats1"]["chunk_steps"] = 4
    assert chunk_step_share.compute(ctx) == 0.0


def test_no_share_where_the_program_does_not_count_or_did_not_step():
    parent = _ctx(stats0={"decode_steps": 100}, stats1={"decode_steps": 300})
    assert chunk_step_share.compute(parent) is None
    assert chunk_step_share.compute(_ctx()) is None
    still = _ctx(stats0={"decode_steps": 7, "chunk_steps": 2},
                 stats1={"decode_steps": 7, "chunk_steps": 2})
    assert chunk_step_share.compute(still) is None


@pytest.mark.parametrize("reader, source", [
    (chunk_step_share, "program_counter"),
    (chunk_step_device_ms, "device_trace")])
def test_the_entry_says_what_its_reader_says(reader, source):
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        spec = json.load(f)
    entry, = [m for m in spec["per_layer"] if m["name"] == reader.NAME]
    assert entry["unit"] == reader.UNIT and entry["layer"] == reader.LAYER
    assert entry["source"] == source and entry["moves"] == "itl_p99_ms"
    # the cells whose server runs the plain step form over a float pool
    assert entry["workloads"] == ["opt-decode-batch",
                                  "opt-longprompt-steady",
                                  "dots-decode-batch"]
    reports = next(m for m in spec["end_to_end"]
                   if m["name"] == "itl_p99_ms")["workloads"]
    assert set(entry["workloads"]) <= set(reports)
    plain = {c["name"] for c in spec["configs"]
             if c["name"] in ("opt-6.7b", "dots.vlm1.inst")}
    assert {w["config"] for w in spec["workloads"]
            if w["name"] in entry["workloads"]} == plain
