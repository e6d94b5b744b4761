"""What decides ``correct`` in a serving cell can fail: the bfloat16
control put in the program's place reads over the cell's limits, and a
run whose timed path alters a token where it is produced comes out not
correct. Both here at sizes a test run holds; the control's readings at
the cells' own sizes, on the chip, are in PERF.md section 2."""
import json
import os
import types

import numpy as np
import pytest

from benchmark import harness, run, traffic
from benchmark.drivers import serve_decode
from benchmark.reference import decoder_lm

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
# wide enough that rounding decides some tokens, as at the cells' widths
SIZES = dict(vocab=8192, n_layers=2, n_heads=2, head_dim=128, d_ff=1024,
             max_len=1024)
CELLS = ["decode-batch", "longprompt-steady"]


def _limits(name):
    with open(os.path.join(BENCH, "traffic", name + ".json")) as f:
        return json.load(f)["check"]["limits"]


@pytest.fixture(scope="module")
def control_runs():
    """``serve_decode._check`` with ``--control`` over one finished
    request of 256 + 768 positions, three seeds: the bfloat16 control
    stands in the program's place, so what comes back as ``compared`` are
    ITS numbers, at each position the gap of the token it puts first.
    (What stands in the served tokens' place is a seeded sequence that
    no model produced; with ``--control`` it is only the context.)"""
    from mxnet_tpu.serving import ToyDecoderLM
    model = ToyDecoderLM(**SIZES)
    with open(os.path.join(BENCH, "configs", "opt-6.7b.json")) as f:
        weights = json.load(f)["weights"]
    cfg = {"server": {"kwargs": {"seq_ladder": [256]}}}
    out = []
    for seed in (11, 2600000011, 2 ** 31 + 5):
        params = serve_decode.make_params(model, weights, seed)
        seq = traffic.rng(seed, 9).integers(0, model.vocab, size=1024,
                                            dtype=np.int32)
        rec = serve_decode.Stream(0.0, seq[:256], 768)
        rec.tokens = seq[256:].tolist()
        runs = {}
        for control in (False, True):
            ctx = types.SimpleNamespace(
                seed=seed, args=types.SimpleNamespace(control=control),
                traffic={"output_len": {"max": 768}, "check": {
                    "requests": 1, "limits": {"gap_worst_std": None,
                                              "gap_mean_std": None}}})
            runs[control] = serve_decode._check(ctx, cfg, model, params,
                                                [rec])
        out.append(runs)
    return out


@pytest.mark.parametrize("cell", CELLS)
def test_the_bfloat16_control_in_the_programs_place_is_over_a_limit(
        control_runs, cell):
    limits = _limits(cell)
    for runs in control_runs:
        compared = {name: dict(runs[True]["compared"][name], limit=limit)
                    for name, limit in limits.items()}
        assert runs[True]["tokens"] == 768
        assert harness.over_limit(compared), compared
        # the numbers compared ARE the control's, not the sequence's own
        for name in compared:
            assert runs[True]["program"][name] \
                == runs[False]["compared"][name]["value"] \
                != compared[name]["value"] == runs[True]["readings"][name]
        assert "program" not in runs[False]


def test_the_references_own_tokens_read_zero():
    """Tokens that ARE the reference's greedy choice: every gap 0."""
    import jax
    from mxnet_tpu.serving import ToyDecoderLM
    model = ToyDecoderLM(**dict(SIZES, vocab=512, max_len=128))
    with open(os.path.join(BENCH, "configs", "opt-6.7b.json")) as f:
        weights = json.load(f)["weights"]
    params = serve_decode.make_params(model, weights, 7)
    seq = np.zeros((64,), np.int32)
    seq[:16] = traffic.rng(7, 9).integers(0, 512, size=16)
    fn = jax.jit(decoder_lm.logits_rows, static_argnums=(3, 4, 5, 6))
    served = []
    for i in range(24):
        rows = fn(params, seq, np.int32(15 + i), 1, model.n_layers,
                  model.n_heads, model.head_dim)
        served.append(int(np.asarray(rows)[0].argmax()))
        seq[16 + i] = served[-1]
    r = decoder_lm.teacher_forced(params, seq[:16], np.asarray(served), 64,
                                  24, model.n_layers, model.n_heads,
                                  model.head_dim)
    assert r["worst"] == 0.0 and r["mean"] == 0.0 and r["exact"] == 24
    # one served token altered: the gap is of the order of a deviation
    served[5] = (served[5] + 1) % 512
    r = decoder_lm.teacher_forced(params, seq[:16], np.asarray(served), 64,
                                  24, model.n_layers, model.n_heads,
                                  model.head_dim)
    assert r["worst"] > 0.5 and r["exact"] < 24


def _rehearse(capsys, extra=()):
    """One rehearsal of ``opt-decode-batch`` through ``run.main``: the
    result line and what was printed."""
    xla_flags = os.environ.get("XLA_FLAGS")
    try:
        run.main(["--workload", "opt-decode-batch", "--seed", "2600000011",
                  "--rehearse", *extra])
    finally:
        if xla_flags is None:
            os.environ.pop("XLA_FLAGS", None)
        else:
            os.environ["XLA_FLAGS"] = xla_flags
    captured = capsys.readouterr()
    return json.loads(captured.out.strip().splitlines()[-1]), captured


class _AlteredTokens:
    """The server under test with one fault: every decode step's tokens
    are altered where they are produced (the prefill's first token is
    the program's own)."""

    def __new__(cls, model, params, **kwargs):
        from mxnet_tpu.serving import DecodeServer

        class Altered(DecodeServer):
            def _decode_fn(self, *args):
                tokens, k, v = DecodeServer._decode_fn(self, *args)
                return (tokens + 1) % self._model.vocab, k, v
        return Altered(model, params, **kwargs)


@pytest.mark.parametrize("broken", [False, True], ids=["sound", "altered"])
def test_a_run_with_altered_tokens_is_not_correct(monkeypatch, capsys,
                                                  broken):
    """The whole of a run but the look for a chip (``--rehearse``), the
    timed path broken underneath."""
    load_object = harness.load_object
    if broken:
        monkeypatch.setattr(
            harness, "load_object",
            lambda path: _AlteredTokens if path.endswith(":DecodeServer")
            else load_object(path))
    result, captured = _rehearse(capsys)
    assert list(result)[-1] == "compared"
    gap = result["compared"]["gap_mean_std"]
    assert result["correct"] is (not broken)
    assert (gap["value"] > gap["limit"]) is broken
    assert result["failed"] == 0        # every stream ran to its end
    # the numbers compared are the last lines of standard error
    last = captured.err.strip().splitlines()[-len(result["compared"]):]
    assert [line.split()[1] for line in last] == list(result["compared"])


@pytest.mark.parametrize("control", [False, True], ids=["program", "control"])
def test_a_run_with_the_control_in_place_is_not_correct(monkeypatch, capsys,
                                                        control):
    """``run.py --control`` through to the result line: the control's
    readings fill ``compared`` and the run comes out not correct, where
    the same run without it is correct. At a rehearsal's widths bfloat16
    flips too few tokens to read over a limit, so the control's reading
    of each sequence is set here to what it reads at the cell's own size
    on the chip (PERF.md section 2); ``control_runs`` above and the chip
    runs read it for real."""
    teacher_forced = decoder_lm.teacher_forced

    def read(*args, control=False, **kwargs):
        out = teacher_forced(*args, control=control, **kwargs)
        if control:
            out.update(control_worst=0.03, control_mean=3e-4)
        return out

    monkeypatch.setattr(decoder_lm, "teacher_forced", read)
    result, _ = _rehearse(capsys, ["--control"] if control else [])
    gap = result["compared"]["gap_mean_std"]
    assert result["correct"] is (not control)
    assert (abs(gap["value"] - 3e-4) < 1e-9) is control
    assert result["failed"] == 0
