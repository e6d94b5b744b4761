"""AMP: per-parameter dtype policy + multi-precision fused step
(mxnet_tpu/amp.py, the mp step fns in optimizer.py, and the loss-scale
slot of fused_step.py).

Contracts under test:
- policy resolution: ordered substring overrides win, norm-role
  fragments stay fp32, compute dtype covers the rest; env grammar and
  manifest describe/from_describe round-trip;
- the bf16 multi-precision fused step runs COMPILED (zero
  fused_step_fallbacks, one trace) and its fp32-master trajectory is
  bit-identical (rtol=0) to the eager AMP path — mp_sgd / mp_sgd_mom /
  base-class mp Adam — weights and masters both;
- a planned grad poison under the scale_backoff guard skips the step
  and backs the loss scale off INSIDE the compiled program, with no
  recompile (the scale rides the traced scalar block).
"""
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import autograd, fault, gluon, profiler
from mxnet_tpu.amp import DtypePolicy, parse_rules


@pytest.fixture(autouse=True)
def _clean_fault():
    yield
    fault.reset()


# ---------------------------------------------------------------------------
# policy resolution
# ---------------------------------------------------------------------------

def test_policy_resolution_precedence():
    pol = DtypePolicy("bfloat16", rules={"fc1": "float32"})
    assert pol.resolve("fc1_weight") == "float32"       # override
    assert pol.resolve("bn0_gamma") == "float32"        # norm role
    assert pol.resolve("bn0_running_mean") == "float32"
    assert pol.resolve("fc2_weight") == "bfloat16"      # compute
    assert pol.resolve("fc2_bias") == "bfloat16"
    assert pol.is_mixed()
    assert not DtypePolicy("float32").is_mixed()


def test_policy_rules_first_match_wins():
    pol = DtypePolicy("bfloat16", rules={"weight": "float32",
                                         "fc1_weight": "bfloat16"})
    # insertion order: the broad rule comes first and wins
    assert pol.resolve("fc1_weight") == "float32"


def test_parse_rules_and_env(monkeypatch):
    assert parse_rules(" fc1=float32 , embed=bfloat16 ") == {
        "fc1": "float32", "embed": "bfloat16"}
    with pytest.raises(mx.MXNetError):
        parse_rules("fc1:float32")
    with pytest.raises(mx.MXNetError):
        parse_rules("fc1=int8")
    monkeypatch.setenv("MXNET_AMP_POLICY", "")
    assert DtypePolicy.from_env() is None
    monkeypatch.setenv("MXNET_AMP_POLICY", "bfloat16")
    monkeypatch.setenv("MXNET_AMP_RULES", "fc1=float32")
    pol = DtypePolicy.from_env()
    assert pol.compute == "bfloat16"
    assert pol.resolve("fc1_weight") == "float32"


def test_policy_describe_roundtrip():
    pol = DtypePolicy("bfloat16", rules={"fc1": "float32"})
    again = DtypePolicy.from_describe(pol.describe())
    assert again.compute == pol.compute and again.rules == pol.rules
    assert DtypePolicy.from_describe(None) is None


def test_policy_apply_casts_per_param():
    import jax.numpy as jnp
    net = gluon.nn.HybridSequential()
    net.add(gluon.nn.Dense(8, in_units=4))
    net.add(gluon.nn.Dense(2, in_units=8))
    net.initialize(mx.init.Xavier())
    first = list(net.collect_params().values())[0].name
    # pin the first dense layer fp32 by prefix rule
    DtypePolicy("bfloat16",
                rules={first.rsplit("_", 1)[0]: "float32"}).apply(net)
    dts = {p.name: p.data().dtype
           for p in net.collect_params().values()}
    assert dts[first] == jnp.float32
    assert jnp.bfloat16 in dts.values()


# ---------------------------------------------------------------------------
# fused mp parity with the eager AMP path
# ---------------------------------------------------------------------------

def _amp_batch(seed=3):
    rng = np.random.RandomState(seed)
    x = rng.uniform(-1, 1, (8, 6)).astype(np.float32)
    y = rng.randint(0, 4, (8,)).astype(np.float32)
    return x, y


def _run_amp(optimizer, opt_params, fused, monkeypatch, steps=5):
    monkeypatch.setenv("MXNET_FUSED_STEP", "1" if fused else "0")
    x, y = _amp_batch()
    net = gluon.nn.HybridSequential()
    net.add(gluon.nn.Dense(16, activation="relu", in_units=6))
    net.add(gluon.nn.Dense(4, in_units=16))
    net.initialize(mx.init.Xavier())
    params = net.collect_params()
    for i, p in enumerate(params.values()):
        p.set_data(mx.nd.array(np.random.RandomState(20 + i).uniform(
            -0.2, 0.2, p.shape).astype(np.float32)))
    DtypePolicy("bfloat16").apply(net)
    net.hybridize()
    trainer = gluon.Trainer(
        params, optimizer,
        dict(opt_params, multi_precision=True))
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    xb = mx.nd.array(x).astype("bfloat16")
    yb = mx.nd.array(y)
    for _ in range(steps):
        with autograd.record():
            out = net(xb)
            loss = loss_fn(out.astype("float32"), yb)
        loss.backward()
        trainer.step(len(x))
    from mxnet_tpu.amp import master_params
    weights = [p.data().asnumpy().copy() for p in params.values()]
    # in the trainer's parameter order, not by name: names carry a
    # process-wide counter, and dense9 / dense10 sort the other way round
    masters = [m.asnumpy().copy()
               for m in master_params(trainer).values()]
    return weights, masters, trainer


AMP_OPTIMIZERS = [
    ("sgd", {"learning_rate": 0.1}),
    ("sgd", {"learning_rate": 0.1, "momentum": 0.9}),
    ("adam", {"learning_rate": 0.01}),
]


@pytest.mark.parametrize(
    "opt,params", AMP_OPTIMIZERS,
    ids=["sgd", "sgd-momentum", "adam"])
def test_amp_fused_bitexact_with_eager(opt, params, monkeypatch):
    """The bf16-policy fused step is compiled (no fallback, one trace)
    and bit-identical — low-dtype weights AND fp32 masters — with the
    eager multi-precision updater loop."""
    w_e, m_e, _ = _run_amp(opt, params, False, monkeypatch)
    before = profiler.counters().get("fused_step_fallbacks", 0)
    w_f, m_f, trainer = _run_amp(opt, params, True, monkeypatch)
    assert profiler.counters().get("fused_step_fallbacks", 0) == before
    fused = trainer._fused_updater
    assert fused is not None
    assert fused.dispatch_count == 5
    assert fused._trace_count == 1
    assert len(m_e) == len(m_f) > 0
    for i, (a, b) in enumerate(zip(m_e, m_f)):
        assert a.dtype == np.float32
        np.testing.assert_array_equal(a, b, err_msg="master %d" % i)
    for i, (a, b) in enumerate(zip(w_e, w_f)):
        assert str(a.dtype) == "bfloat16"
        np.testing.assert_array_equal(a, b, err_msg="weight %d" % i)


def test_amp_weights_track_masters():
    """Sanity on the mp contract: after seeding masters, the stored
    low-dtype weight is exactly the bf16 cast of its fp32 master."""
    opt = mx.optimizer.create("sgd", learning_rate=0.1, momentum=0.9,
                              multi_precision=True)
    w = mx.nd.array(np.linspace(-1, 1, 8).astype(np.float32)) \
        .astype("bfloat16")
    state = opt.create_state_multi_precision(0, w)
    master = opt.master_from_state(w, state)
    assert master is not None and str(master.dtype) == "float32"
    np.testing.assert_array_equal(
        w.asnumpy(), master.astype("bfloat16").asnumpy())


# ---------------------------------------------------------------------------
# checkpoint: policy in the manifest, cross-policy resume
# ---------------------------------------------------------------------------

def test_checkpoint_cross_policy_resume(tmp_path, monkeypatch):
    """An AMP checkpoint stores fp32 masters + the dtype policy in the
    manifest meta; it resumes under ANY policy (fp32 or back under
    bf16) as a pure cast of the exact masters, and seed_masters makes
    the continued run's optimizer state bit-identical."""
    from mxnet_tpu import checkpoint
    from mxnet_tpu.amp import master_params, seed_masters
    _, _, trainer = _run_amp("sgd", {"learning_rate": 0.1,
                                     "momentum": 0.9}, True, monkeypatch,
                             steps=3)
    params = list(trainer._params)
    pol = DtypePolicy("bfloat16")
    masters = master_params(trainer)
    assert len(masters) == len(params)
    # the roster checkpoints the fp32 MASTERS, not the bf16 casts
    arg = {p.name: p.data() for p in params}
    arg.update(masters)
    prefix = str(tmp_path / "amp")
    checkpoint.save_arrays(
        prefix, 0, checkpoint.snapshot_params(arg),
        meta={"dtype_policy": pol.describe()})

    saved = checkpoint.saved_dtype_policy(prefix, 0)
    assert saved is not None and saved.compute == "bfloat16"

    # resume fp32: every weight IS the master, bit-exact
    a32, _ = checkpoint.restore_params(
        prefix, 0, policy=DtypePolicy("float32"))
    for p in params:
        assert str(a32[p.name].dtype) == "float32"
        np.testing.assert_array_equal(a32[p.name].asnumpy(),
                                      masters[p.name].asnumpy())

    # resume under the manifest's own (bf16) policy: weights are the
    # bf16 cast of the master — exactly what training held
    ab, _ = checkpoint.restore_params(prefix, 0, policy="manifest")
    for p in params:
        assert str(ab[p.name].dtype) == "bfloat16"
        np.testing.assert_array_equal(ab[p.name].asnumpy(),
                                      p.data().asnumpy())

    # continued-training resume: fresh net + trainer, weights from the
    # policy cast, masters seeded bit-for-bit from the raw fp32 load
    raw, _ = checkpoint.restore_params(prefix, 0)
    net2 = gluon.nn.HybridSequential()
    net2.add(gluon.nn.Dense(16, activation="relu", in_units=6))
    net2.add(gluon.nn.Dense(4, in_units=16))
    net2.initialize(mx.init.Xavier())
    params2 = list(net2.collect_params().values())
    for p, src in zip(params2, params):
        p.set_data(raw[src.name].astype("float32"))
    DtypePolicy("bfloat16").apply(net2)
    trainer2 = gluon.Trainer(net2.collect_params(), "sgd",
                             {"learning_rate": 0.1, "momentum": 0.9,
                              "multi_precision": True})
    seeded = seed_masters(
        trainer2, {p2.name: raw[p.name]
                   for p2, p in zip(params2, params)})
    assert seeded == len(params)
    m2 = master_params(trainer2)
    for p2, p in zip(params2, params):
        np.testing.assert_array_equal(m2[p2.name].asnumpy(),
                                      masters[p.name].asnumpy())


def test_amp_poison_backoff_in_program_no_recompile(monkeypatch):
    """Under scale_backoff, a planned grad-site nan poisons one step
    INSIDE the compiled mp program: the update is skipped, the loss
    scale halves, later steps keep training — all on ONE trace (the
    dynamic scale rides the traced scalar block, never the compile
    key)."""
    monkeypatch.setenv("MXNET_FUSED_STEP", "1")
    monkeypatch.setenv("MXNET_NONFINITE_GUARD", "scale_backoff")
    x, y = _amp_batch()
    net = gluon.nn.HybridSequential()
    net.add(gluon.nn.Dense(16, activation="relu", in_units=6))
    net.add(gluon.nn.Dense(4, in_units=16))
    net.initialize(mx.init.Xavier())
    DtypePolicy("bfloat16").apply(net)
    net.hybridize()
    params = net.collect_params()
    n_params = len(list(params.values()))
    trainer = gluon.Trainer(params, "sgd",
                            {"learning_rate": 0.1, "momentum": 0.9,
                             "multi_precision": True})
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    # grad-site visits go per parameter per step: poison ALL of step 3
    fault.set_plan("grad:step=%d:nan:count=%d"
                   % (2 * n_params + 1, n_params))
    scale0 = fault.loss_scale()
    assert scale0 > 1.0
    xb = mx.nd.array(x).astype("bfloat16")
    yb = mx.nd.array(y)
    snaps = []
    for _ in range(5):
        with autograd.record():
            out = net(xb)
            loss = loss_fn(out.astype("float32"), yb) \
                * fault.loss_scale()
        loss.backward()
        trainer.step(len(x))
        snaps.append([p.data().astype("float32").asnumpy().copy()
                      for p in params.values()])
    st = fault.stats()
    assert st["skipped_steps"] == 1
    assert st["injected"]["grad"] == n_params
    assert fault.loss_scale() == scale0 / 2.0
    # step 3 held every weight; step 4 resumed
    for a, b in zip(snaps[1], snaps[2]):
        np.testing.assert_array_equal(a, b)
    assert any(not np.array_equal(a, b)
               for a, b in zip(snaps[2], snaps[3]))
    fused = trainer._fused_updater
    assert fused is not None
    assert fused.dispatch_count == 5
    assert fused._trace_count == 1


def test_diagnose_renders_loss_scale_trajectory(tmp_path, monkeypatch):
    """Every dynamic-scale change lands in the telemetry sink as a
    loss_scale record; tools.diagnose renders the trajectory."""
    from mxnet_tpu import telemetry
    from mxnet_tpu.tools import diagnose
    monkeypatch.setenv("MXNET_NONFINITE_GUARD", "scale_backoff")
    fault.reset()
    sink = str(tmp_path / "run.jsonl")
    telemetry.start(sink)
    s0 = fault.loss_scale()
    fault.fused_step_guard(False)          # backoff: s0 -> s0/2
    fault.fused_step_guard(False)          # backoff: s0/2 -> s0/4
    telemetry.stop()
    tel = diagnose.read_telemetry(sink)
    assert [r["cause"] for r in tel["loss_scale"]] == ["backoff",
                                                       "backoff"]
    assert tel["loss_scale"][-1]["scale"] == s0 / 4
    text = diagnose.format_telemetry(tel)
    assert "----------Loss Scale----------" in text
    assert "2 backoff(s), 0 regrow(s)" in text
    assert "%g (backoff)" % (s0 / 4) in text
