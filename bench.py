"""Benchmark: ResNet-50 throughput + MFU on one chip.

Reference baselines (BASELINE.md, from the reference's docs/faq/perf.md):
  - inference fp32 batch 32 : 1,076.81 img/s on 1x V100 (perf.md:176)
  - training  fp32 batch 32 :   298.51 img/s on 1x V100 (perf.md:234)

Methodology mirrors the reference's benchmark_score.py: a fixed batch
through the single-XLA-program model, steady-state timing. ITERS
iterations are folded into ONE compiled lax.scan — the per-batch device
time is what's measured, exactly the quantity the reference reports
(it, too, excludes host-side input prep).

Training runs the FRAMEWORK'S OWN compiled train program: the bound
Executor's forward+backward (`Executor._get_fn("fwdbwd")` — the same
program `Module.fit`/`ex.backward()` executes) chained into the
registered aggregated `multi_sgd_update` operator (the reference's
multi-tensor aggregation feature), scanned. A 3-step eager run through the Executor +
Updater API is asserted to follow the same loss trajectory, proving
the scanned program IS the framework path, not a hand-rolled twin.

MFU comes from XLA's own cost analysis (compiled.cost_analysis flops)
against the chip's bf16 peak. bf16 weights/activations are the
MXU-native dtype (fp32 accumulation inside XLA conv/dot) — the
apples-to-apples "native precision" config like fp16 tensor cores on
the V100. Conv layout note: NCHW vs NHWC measured identical on TPU
(XLA assigns internal layouts itself), so the lowering keeps the
reference's NCHW convention.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline", ...}
with training, MFU, batch-sweep, and allreduce-bandwidth extras, naming
the device it ran on. The default run needs a TPU and a failed phase
fails the run (non-zero exit); the ``--flag`` modes are CPU
micro-harnesses and say ``"platform": "cpu"`` in their output.
ROADMAP D1/S1 replace this file; speed is quoted only from the ledger.
"""
from __future__ import annotations

import json
import os
import sys
import time

import numpy as np

BASELINE_INFER = 1076.81  # V100 fp32 batch 32 (perf.md:176)
BASELINE_TRAIN = 298.51   # V100 fp32 batch 32 (perf.md:234)
BATCH = 32
IMAGE = 224
ITERS = 128
SWEEP = (128, 256)        # extra inference batch sizes
TRAIN_ITERS = 64

def _flops(compiled):
    try:
        ca = compiled.cost_analysis()
        ca = ca[0] if isinstance(ca, list) else ca
        return float(ca.get("flops", 0.0))
    except Exception:
        return 0.0


def _timed(compiled, *args):
    """Time one call (scalar result); fetching the scalar to the host
    is the completion barrier."""
    float(compiled(*args))                   # compile + warmup
    t0 = time.perf_counter()
    float(compiled(*args))
    return time.perf_counter() - t0


def _build(batch, classes=1000):
    import jax.numpy as jnp
    import mxnet_tpu as mx
    from mxnet_tpu.cached_op import build_graph_callable
    from mxnet_tpu.gluon.model_zoo import vision
    from mxnet_tpu import symbol as sym_mod

    net = vision.resnet50_v1(classes=classes)
    net.initialize(mx.init.Xavier())
    net(mx.nd.zeros((batch, 3, IMAGE, IMAGE)))   # materialize params

    data = sym_mod.var("data")
    out_sym = net(data)
    fn, arg_names, aux_names, _, _ = build_graph_callable(out_sym)
    params = {p.name: p for p in net.collect_params().values()}
    pv = {n: params[n].data()._data.astype(jnp.bfloat16)
          for n in arg_names if n != "data"}
    av = {n: params[n].data()._data.astype(jnp.bfloat16)
          for n in aux_names}
    return out_sym, fn, arg_names, aux_names, pv, av


def _bench_inference(batch, iters, peak):
    import jax
    import jax.numpy as jnp

    _, fn, arg_names, aux_names, pv, av = _build(batch)
    x = jnp.asarray(np.random.uniform(
        0, 1, (batch, 3, IMAGE, IMAGE)).astype(np.float32)
    ).astype(jnp.bfloat16)

    def fwd(x, pv, av):
        vals = [x if n == "data" else pv[n] for n in arg_names]
        vals.extend(av[n] for n in aux_names)
        return fn({"__train__": False}, *vals)[0]

    def many(x, pv, av):
        # serial dependence step->step so XLA can't hoist the forward
        def body(acc, _):
            xi = x + (acc * 1e-12).astype(x.dtype)
            return jnp.mean(fwd(xi, pv, av).astype(jnp.float32)), None
        acc, _ = jax.lax.scan(body, jnp.float32(0), None, length=iters)
        return acc

    from mxnet_tpu.engine import compiler_options
    copts = compiler_options()
    dt = _timed(jax.jit(many, compiler_options=copts), x, pv, av)
    img_s = batch * iters / dt
    fwd_flops = _flops(jax.jit(fwd, compiler_options=copts)
                       .lower(x, pv, av).compile())
    mfu = fwd_flops * iters / dt / peak
    return img_s, mfu, fwd_flops / batch


def _bench_training_framework_path(peak, flops_per_img, batch=None,
                                   check_parity=True):
    """Train step = the Executor's own compiled fwd+bwd program + ONE
    aggregated multi_sgd_update op over every weight, scanned;
    trajectory-checked against the eager Executor + Updater API."""
    import jax
    import jax.numpy as jnp
    import mxnet_tpu as mx
    from mxnet_tpu import symbol as sym_mod
    from mxnet_tpu.ops.registry import get_op, normalize_attrs

    batch = batch if batch is not None else BATCH
    out_sym, _, arg_names, aux_names, pv, av = _build(batch)
    label_sym = sym_mod.var("softmax_label")
    loss_sym = sym_mod.create("SoftmaxOutput", [out_sym, label_sym],
                              {"normalization": "batch"}, name="softmax")

    labels = np.random.randint(0, 1000, (batch,)).astype(np.float32)
    x_np = np.random.uniform(0, 1, (batch, 3, IMAGE, IMAGE)) \
        .astype(np.float32)

    args = {n: mx.nd.array(v) for n, v in pv.items()}
    args["data"] = mx.nd.array(x_np).astype("bfloat16")
    args["softmax_label"] = mx.nd.array(labels)
    grads = {n: mx.nd.zeros(v.shape).astype("bfloat16")
             for n, v in pv.items()}
    aux = {n: mx.nd.array(v) for n, v in av.items()}
    grad_req = {n: ("write" if n in grads else "null")
                for n in loss_sym.list_arguments()}
    ex = loss_sym.bind(mx.current_context(), args, args_grad=grads,
                       grad_req=grad_req, aux_states=aux)

    fwdbwd = ex._get_fn("fwdbwd", True, raw=True)  # the framework program
    gpos = ex._grad_positions
    # aggregated multi-tensor SGD: ONE registered multi_sgd_update call
    # over every weight (the reference's MXNET_OPTIMIZER_AGGREGATION
    # feature, optimizer_op.cc multi_sgd_update)
    msgd = get_op("multi_sgd_update")
    n_w = len(gpos)
    msgd_attrs = normalize_attrs(msgd, {
        "num_weights": n_w, "lrs": (0.05,) * n_w, "wds": (0.0,) * n_w,
        "rescale_grad": 1.0})
    full_names = loss_sym.list_arguments()
    out_shapes = [tuple(o.shape) for o in _probe_outputs(ex)]

    def one_step(arg_vals, aux_vals):
        cots = tuple(jnp.ones(s, jnp.bfloat16) for s in out_shapes)
        outs, new_aux, gs = fwdbwd(tuple(arg_vals), tuple(aux_vals),
                                   (), cots)
        arg_vals = list(arg_vals)
        flat = []
        for p, g in zip(gpos, gs):
            flat.extend((arg_vals[p], g))
        new_ws = msgd.forward(msgd_attrs, *flat)
        for p, w_new in zip(gpos, new_ws):
            arg_vals[p] = w_new
        probs = outs[0].astype(jnp.float32)
        picked = jnp.take_along_axis(
            probs, jnp.asarray(labels[:, None], jnp.int32), axis=1)
        loss = -jnp.mean(jnp.log(jnp.maximum(picked, 1e-10)))
        return arg_vals, list(new_aux), loss

    def many(arg_vals, aux_vals):
        def body(carry, _):
            a, x = carry
            a, x, loss = one_step(a, x)
            return (tuple(a), tuple(x)), loss
        (a, x), losses = jax.lax.scan(
            body, (tuple(arg_vals), tuple(aux_vals)), None,
            length=TRAIN_ITERS)
        tail = sum(jnp.sum(v.astype(jnp.float32)) * 1e-20 for v in a)
        return jnp.mean(losses) + tail, losses[:3]

    arg_vals = tuple(a._data for a in ex.arg_arrays)
    aux_vals = tuple(a._data for a in ex.aux_arrays)

    from mxnet_tpu.engine import compiler_options
    compiled_exec = jax.jit(
        many, compiler_options=compiler_options()) \
        .lower(arg_vals, aux_vals).compile()
    compiled = compiled_exec
    out, first3 = compiled(arg_vals, aux_vals)
    float(out)                                   # warmup + compile
    t0 = time.perf_counter()
    out, first3 = compiled(arg_vals, aux_vals)
    float(out)
    dt = time.perf_counter() - t0
    img_s = batch * TRAIN_ITERS / dt

    # training MFU: the standard fwd+bwd ~ 3x forward convention; the
    # EXECUTED-flop utilization (XLA's own cost analysis of the whole
    # scanned program — what the hardware actually ran) rides alongside
    mfu = 3.0 * flops_per_img * batch * TRAIN_ITERS / dt / peak
    hw_util = _flops(compiled_exec) / dt / peak
    if not check_parity:
        return img_s, mfu, hw_util

    # --- trajectory parity: eager Executor + Updater, 3 steps ----------
    from mxnet_tpu.optimizer import SGD, Updater
    upd = Updater(SGD(learning_rate=0.05, wd=0.0, rescale_grad=1.0))
    eager_losses = []
    for _ in range(3):
        outs = ex.forward(is_train=True)
        probs = outs[0].asnumpy().astype(np.float64)
        picked = probs[np.arange(batch), labels.astype(np.int64)]
        eager_losses.append(-np.mean(np.log(np.maximum(picked, 1e-10))))
        ex.backward()
        for i, n in enumerate(full_names):
            if n in grads:
                upd(i, ex.grad_dict[n], ex.arg_dict[n])
    scan_losses = np.asarray(first3, dtype=np.float64)
    if not np.allclose(scan_losses, eager_losses, rtol=0.05, atol=0.05):
        raise AssertionError(
            "framework-path trajectory mismatch: scanned %s vs eager %s"
            % (scan_losses.tolist(), eager_losses))

    return img_s, mfu, hw_util


def _probe_outputs(ex):
    outs = ex.forward(is_train=True)
    return outs


def _bench_allreduce_bandwidth():
    """KVStore pushpull aggregation bandwidth (BASELINE.md metric #2,
    ref tools/bandwidth/measure.py).

    Measures the IN-PROGRAM aggregation the kvstore actually compiles:
    ``KVStore._tree_sum`` — the CommDevice Reduce kernel every list-push
    runs — scanned so per-dispatch host overhead amortizes and the
    number reflects the device path. (Pull/Broadcast on one
    chip is handle aliasing in this design — no copy — so Reduce IS the
    whole data path of a single-chip pushpull.) On a worker mesh the
    same sum becomes the ICI psum. Accounting: one reduce round moves at
    least N reads + 1 write of the buffer, i.e. (N+1)*nbytes (XLA's own
    bytes_accessed for the compiled fusion is 6*nbytes — it also
    re-reads the carried result — so the reported figure is the
    conservative one)."""
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.engine import compiler_options
    from mxnet_tpu.kvstore import KVStore

    n_workers = 4
    nbytes = 64 << 20
    iters = 1024
    bufs = tuple(jnp.full((nbytes // 4,), float(i + 1), jnp.float32)
                 for i in range(n_workers))

    def pushpull_rounds(bufs, agg0):
        def body(agg, _):
            # serial dependence on the previous round's result keeps
            # XLA from hoisting; the reduce is the kvstore's own kernel
            new = KVStore._tree_sum(
                (agg * jnp.float32(1e-30),) + bufs)
            return new, new[0]
        agg, taps = jax.lax.scan(body, agg0, None, length=iters)
        return agg[0] + taps[-1]

    fn = jax.jit(pushpull_rounds, compiler_options=compiler_options())
    agg0 = jnp.zeros((nbytes // 4,), jnp.float32)
    float(fn(bufs, agg0))                        # compile + warmup
    t0 = time.perf_counter()
    float(fn(bufs, agg0))
    dt = time.perf_counter() - t0
    return (n_workers + 1) * nbytes * iters / dt / 1e9   # GB/s


def _sync_module(mod):
    """Completion barrier for framework-path benches: fetch-free sync
    on a parameter buffer (shared by every train-step bench so the
    sync mechanism can never diverge between them)."""
    mod._exec.arg_dict[mod._param_names[0]]._data.block_until_ready()


def _mlp_sym():
    import mxnet_tpu as mx
    data = mx.sym.var("data")
    x = mx.sym.FullyConnected(data, num_hidden=256, name="fc1")
    x = mx.sym.Activation(x, act_type="relu", name="relu1")
    x = mx.sym.FullyConnected(x, num_hidden=10, name="fc2")
    return mx.sym.SoftmaxOutput(x, mx.sym.var("softmax_label"),
                                name="softmax")


def _convnet_sym():
    import mxnet_tpu as mx
    data = mx.sym.var("data")
    x = mx.sym.Convolution(data, kernel=(3, 3), num_filter=8,
                           name="conv1")
    x = mx.sym.Activation(x, act_type="relu", name="relu1")
    x = mx.sym.Pooling(x, pool_type="max", kernel=(2, 2), stride=(2, 2),
                       name="pool1")
    x = mx.sym.Flatten(x, name="flat")
    x = mx.sym.FullyConnected(x, num_hidden=10, name="fc1")
    return mx.sym.SoftmaxOutput(x, mx.sym.var("softmax_label"),
                                name="softmax")


def _bench_fused_step_case(build_sym, data_shape, steps=60, warmup=5,
                           rounds=3):
    """steps/sec for the eager vs fused train step on one net: the
    fused executor (fused_step.py) runs forward+backward+optimizer as
    ONE donated XLA dispatch per step, vs the eager loop's fused
    fwd+bwd dispatch plus ~2·P per-parameter update launches. Timed
    rounds are INTERLEAVED (eager, fused, eager, fused, ...) and the
    best round per mode is reported, so host-load noise hits both
    modes symmetrically."""
    import numpy as np_
    import mxnet_tpu as mx

    rng = np_.random.RandomState(0)
    batch = mx.io.DataBatch(
        data=[mx.nd.array(
            rng.uniform(0, 1, data_shape).astype(np_.float32))],
        label=[mx.nd.array(
            rng.randint(0, 10, (data_shape[0],)).astype(np_.float32))])

    prior = os.environ.get("MXNET_FUSED_STEP")
    try:
        mods = {}
        for mode in ("eager", "fused"):
            os.environ["MXNET_FUSED_STEP"] = \
                "1" if mode == "fused" else "0"
            mod = mx.module.Module(build_sym(),
                                   context=mx.current_context())
            mod.bind(data_shapes=[("data", data_shape)],
                     label_shapes=[("softmax_label", (data_shape[0],))])
            mod.init_params(initializer=mx.init.Xavier())
            mod.init_optimizer(
                optimizer="sgd",
                optimizer_params={"learning_rate": 0.05,
                                  "momentum": 0.9})
            for _ in range(warmup):
                mod.forward_backward(batch)
                mod.update()
            _sync_module(mod)
            mods[mode] = mod

        best = {"eager": 0.0, "fused": 0.0}
        for _ in range(rounds):
            for mode in ("eager", "fused"):
                os.environ["MXNET_FUSED_STEP"] = \
                    "1" if mode == "fused" else "0"
                mod = mods[mode]
                t0 = time.perf_counter()
                for _ in range(steps):
                    mod.forward_backward(batch)
                    mod.update()
                _sync_module(mod)
                dt = time.perf_counter() - t0
                best[mode] = max(best[mode], steps / dt)

        fused = mods["fused"]._fused
        assert fused, "fused path did not run"
        return {
            "eager_steps_per_sec": round(best["eager"], 2),
            "fused_steps_per_sec": round(best["fused"], 2),
            "fused_dispatches_per_step":
                fused.dispatch_count // (warmup + rounds * steps),
            "fused_traces": fused._trace_count,
            "params": len(mods["fused"]._param_names),
            "speedup": round(best["fused"] / best["eager"], 3),
        }
    finally:
        if prior is None:
            os.environ.pop("MXNET_FUSED_STEP", None)
        else:
            os.environ["MXNET_FUSED_STEP"] = prior


def _fused_step_record():
    """The fused-train-step benchmark record (BENCH_r06.json): MLP +
    small conv net, eager vs fused steps/sec, per-step dispatch count.
    CPU-friendly — runs wherever the tier-1 suite runs."""
    import jax
    record = {"metric": "fused_step_steps_per_sec", "unit": "steps/s",
              "dtype": "float32", "optimizer": "sgd_momentum",
              "platform": jax.default_backend(), "cases": {}}
    errors = {}
    try:
        record["cases"]["mlp"] = _bench_fused_step_case(
            _mlp_sym, (64, 784))
    except Exception as exc:                     # noqa: BLE001
        errors["mlp"] = _err_str(exc)
    try:
        record["cases"]["convnet"] = _bench_fused_step_case(
            _convnet_sym, (32, 1, 28, 28))
    except Exception as exc:                     # noqa: BLE001
        errors["convnet"] = _err_str(exc)
    if errors:
        record["errors"] = errors
    return record


def _bench_telemetry_overhead(steps=80, warmup=5, rounds=3):
    """MLP train-step time with telemetry OFF (the default env — hooks
    must be one module lookup + None check) vs ON (active run, fit-style
    step records + spans, JSONL sink). Rounds are interleaved
    (off, on, off, on, ...) and the best round per mode is reported so
    host-load noise hits both modes symmetrically. The acceptance bar
    is the OFF path: < 2% overhead vs the parent commit's step time
    (compare telemetry_off_steps_per_sec with BENCH_r06's mlp case)."""
    import tempfile

    import numpy as np_
    import mxnet_tpu as mx
    from mxnet_tpu import telemetry

    rng = np_.random.RandomState(0)
    data_shape = (64, 784)
    batch = mx.io.DataBatch(
        data=[mx.nd.array(
            rng.uniform(0, 1, data_shape).astype(np_.float32))],
        label=[mx.nd.array(
            rng.randint(0, 10, (data_shape[0],)).astype(np_.float32))])

    mod = mx.module.Module(_mlp_sym(), context=mx.current_context())
    mod.bind(data_shapes=[("data", data_shape)],
             label_shapes=[("softmax_label", (data_shape[0],))])
    mod.init_params(initializer=mx.init.Xavier())
    mod.init_optimizer(optimizer="sgd",
                       optimizer_params={"learning_rate": 0.05,
                                         "momentum": 0.9})
    for _ in range(warmup):
        mod.forward_backward(batch)
        mod.update()
    _sync_module(mod)

    sink = os.path.join(tempfile.gettempdir(),
                        "bench_telemetry_%d.jsonl" % os.getpid())

    def run_round(mode):
        if mode == "on":
            telemetry.start(filename=sink,
                            meta={"case": "telemetry_overhead"})
        t0 = time.perf_counter()
        for _ in range(steps):
            if mode == "on":
                telemetry.step_begin()
                with telemetry.span("compute"):
                    mod.forward_backward(batch)
                with telemetry.span("optimizer"):
                    mod.update()
                telemetry.step_end(samples=data_shape[0])
            else:
                mod.forward_backward(batch)
                mod.update()
        _sync_module(mod)
        dt = time.perf_counter() - t0
        if mode == "on":
            telemetry.stop()
        return steps / dt

    telemetry.reset()
    best = {"off": 0.0, "on": 0.0}
    for _ in range(rounds):
        for mode in ("off", "on"):
            best[mode] = max(best[mode], run_round(mode))
    try:
        os.remove(sink)
    except OSError:
        pass
    return {
        "telemetry_off_steps_per_sec": round(best["off"], 2),
        "telemetry_on_steps_per_sec": round(best["on"], 2),
        "on_overhead_pct": round(
            100.0 * (best["off"] / best["on"] - 1.0), 2),
        "steps": steps,
        "batch": data_shape[0],
    }


def _telemetry_record():
    """The telemetry-overhead benchmark record (BENCH_r07.json).
    CPU-friendly — runs wherever the tier-1 suite runs."""
    import jax
    record = {"metric": "telemetry_overhead", "unit": "steps/s",
              "dtype": "float32", "optimizer": "sgd_momentum",
              "platform": jax.default_backend(), "cases": {}}
    try:
        record["cases"]["mlp"] = _bench_telemetry_overhead()
    except Exception as exc:                     # noqa: BLE001
        record["errors"] = {"mlp": _err_str(exc)}
    return record


def _bench_compile_watch_overhead(steps=80, warmup=5, rounds=3):
    """Fused-MLP train-step time with the compile watch OFF (the
    default env — a watched call is one module-global None check before
    the plain jit) vs ON (staged compiles + per-dispatch flops/bytes
    accrual + per-step utilization records into a telemetry run with a
    JSONL sink). Rounds are interleaved so host-load noise hits both
    modes symmetrically; each round re-warms after the mode switch so
    one-time staged compiles never pollute steady-state timing. The
    acceptance bar is the OFF path: within noise of the parent
    commit's fused MLP step time (BENCH_r07/r08 era)."""
    import tempfile

    import numpy as np_
    import mxnet_tpu as mx
    from mxnet_tpu import compile_watch, telemetry

    rng = np_.random.RandomState(0)
    data_shape = (64, 784)
    batch = mx.io.DataBatch(
        data=[mx.nd.array(
            rng.uniform(0, 1, data_shape).astype(np_.float32))],
        label=[mx.nd.array(
            rng.randint(0, 10, (data_shape[0],)).astype(np_.float32))])

    mod = mx.module.Module(_mlp_sym(), context=mx.current_context())
    mod.bind(data_shapes=[("data", data_shape)],
             label_shapes=[("softmax_label", (data_shape[0],))])
    mod.init_params(initializer=mx.init.Xavier())
    mod.init_optimizer(optimizer="sgd",
                       optimizer_params={"learning_rate": 0.05,
                                         "momentum": 0.9})
    for _ in range(warmup):
        mod.forward_backward(batch)
        mod.update()
    _sync_module(mod)

    sink = os.path.join(tempfile.gettempdir(),
                        "bench_compile_watch_%d.jsonl" % os.getpid())

    def run_round(mode):
        if mode == "on":
            compile_watch.enable()
            telemetry.start(filename=sink,
                            meta={"case": "compile_watch_overhead"})
        for _ in range(warmup):      # absorb staged compiles/mode flip
            mod.forward_backward(batch)
            mod.update()
        _sync_module(mod)
        t0 = time.perf_counter()
        for _ in range(steps):
            if mode == "on":
                telemetry.step_begin()
                mod.forward_backward(batch)
                mod.update()
                telemetry.step_end(samples=data_shape[0])
            else:
                mod.forward_backward(batch)
                mod.update()
        _sync_module(mod)
        dt = time.perf_counter() - t0
        if mode == "on":
            telemetry.stop()
            compile_watch.disable()
        return steps / dt

    telemetry.reset()
    compile_watch.disable()
    best = {"off": 0.0, "on": 0.0}
    for _ in range(rounds):
        for mode in ("off", "on"):
            best[mode] = max(best[mode], run_round(mode))
    try:
        os.remove(sink)
    except OSError:
        pass
    return {
        "compile_watch_off_steps_per_sec": round(best["off"], 2),
        "compile_watch_on_steps_per_sec": round(best["on"], 2),
        "on_overhead_pct": round(
            100.0 * (best["off"] / best["on"] - 1.0), 2),
        "steps": steps,
        "batch": data_shape[0],
    }


def _compile_watch_record():
    """The compile-watch-overhead benchmark record (BENCH_r09.json).
    CPU-friendly — runs wherever the tier-1 suite runs."""
    import jax
    record = {"metric": "compile_watch_overhead", "unit": "steps/s",
              "dtype": "float32", "optimizer": "sgd_momentum",
              "platform": jax.default_backend(), "cases": {}}
    try:
        record["cases"]["mlp"] = _bench_compile_watch_overhead()
    except Exception as exc:                     # noqa: BLE001
        record["errors"] = {"mlp": _err_str(exc)}
    return record


class _DecodeBoundIter:
    """Split-protocol data source modelling a decode-bound input path:
    each batch costs ``io_wait_ms`` of GIL-free input latency (what a
    storage read / remote fetch / cv2 JPEG decode costs — all of which
    release the GIL and parallelize across pipeline workers) plus real
    numpy assembly work and the ``nd.array`` conversion. With the eager
    iterator every step pays the whole decode serially; a single
    prefetch worker can hide at most one decode per step; the pooled
    pipeline overlaps ``workers`` decodes behind compute."""

    def __init__(self, data_shape, num_batches, io_wait_ms=35.0,
                 sort_k=300, classes=10, seed=0):
        import numpy as np_
        from mxnet_tpu.io.io import DataDesc
        rng = np_.random.RandomState(seed)
        self._base = rng.uniform(0.5, 1.5, data_shape) \
            .astype(np_.float32)
        self._noise = rng.rand(max(1, int(sort_k)) * 1000) \
            .astype(np_.float32)
        self._labels = rng.randint(0, classes, (data_shape[0],)) \
            .astype(np_.float32)
        self._shape = tuple(data_shape)
        self._n = num_batches
        self._io_wait = io_wait_ms / 1e3
        self._seq = 0
        self.batch_size = data_shape[0]
        self.provide_data = [DataDesc("data", data_shape)]
        self.provide_label = [DataDesc("softmax_label",
                                       (data_shape[0],))]

    def reset(self):
        self._seq = 0

    def next_raw(self):
        if self._seq >= self._n:
            raise StopIteration
        seq = self._seq
        self._seq += 1
        return seq

    def decode_raw(self, seq):
        import mxnet_tpu as mx
        time.sleep(self._io_wait)               # the input latency
        srt = np.sort(self._noise)              # GIL-free CPU assembly
        size = int(np.prod(self._shape))
        reps = -(-size // srt.size)
        aug = np.tile(srt, reps)[:size].reshape(self._shape)
        x = self._base + 1e-6 * aug
        return mx.io.DataBatch([mx.nd.array(x)],
                               [mx.nd.array(self._labels)], pad=0)

    def next(self):
        return self.decode_raw(self.next_raw())

    def __iter__(self):
        return self

    def __next__(self):
        return self.next()


def _bench_input_pipeline_case(build_sym, data_shape, io_wait_ms=35.0,
                               steps=30, warmup=4, rounds=3,
                               workers=None):
    """steps/sec + telemetry data_wait share for the SAME decode-bound
    training loop consumed three ways: the eager iterator (decode
    serial with the step), a 1-worker async prefetch (the old
    PrefetchingIter role), and the pooled multi-worker pipeline with
    device prefetch. Rounds are interleaved (eager, prefetch1, pooled,
    eager, ...) so host-load noise hits all modes symmetrically; best
    round per mode is reported with that round's data_wait share."""
    import jax
    import mxnet_tpu as mx
    from mxnet_tpu import telemetry
    from mxnet_tpu.io.pipeline import AsyncInputPipeline, data_workers

    # pool width caps at the machine: decode workers beyond the core
    # count only steal cycles from XLA's own compute threads
    workers = workers or data_workers(
        max(2, min(4, os.cpu_count() or 2)))
    n_batches = steps + 2

    mod = mx.module.Module(build_sym(), context=mx.current_context())
    mod.bind(data_shapes=[("data", data_shape)],
             label_shapes=[("softmax_label", (data_shape[0],))])
    mod.init_params(initializer=mx.init.Xavier())
    mod.init_optimizer(optimizer="sgd",
                       optimizer_params={"learning_rate": 0.05,
                                         "momentum": 0.9})
    warm_src = _DecodeBoundIter(data_shape, warmup,
                                io_wait_ms=io_wait_ms)
    for batch in warm_src:
        mod.forward_backward(batch)
        mod.update()
    _sync_module(mod)

    dev = mx.current_context().jax_device()
    sources = {m: _DecodeBoundIter(data_shape, n_batches,
                                   io_wait_ms=io_wait_ms)
               for m in ("eager", "prefetch1", "pooled_device")}
    feeds = {
        "eager": sources["eager"],
        "prefetch1": AsyncInputPipeline(sources["prefetch1"],
                                        num_workers=1, prefetch_depth=2,
                                        placement=None),
        "pooled_device": AsyncInputPipeline(sources["pooled_device"],
                                            num_workers=workers,
                                            prefetch_depth=2,
                                            placement=dev),
    }

    def run_round(mode):
        telemetry.reset()
        feed = feeds[mode]
        feed.reset()
        telemetry.start(run_id="input_pipeline_%s" % mode)
        t0 = time.perf_counter()
        for _ in range(steps):
            telemetry.step_begin()
            with telemetry.span("data_wait"):
                batch = feed.next()
            with telemetry.span("compute"):
                mod.forward_backward(batch)
            mod.update()
            telemetry.step_end(samples=data_shape[0])
        _sync_module(mod)
        dt = time.perf_counter() - t0
        rep = telemetry.stop()
        telemetry.reset()
        total_ms = rep["steps"] * rep["step_time_ms"]["mean"]
        share = rep["phases_ms"].get("data_wait", 0.0) / total_ms \
            if total_ms else 0.0
        h2d = {k: v for k, v in rep["comms"].items()
               if k.startswith("h2d:")}
        return steps / dt, share, h2d

    best = {m: (0.0, None, None) for m in feeds}
    for _ in range(rounds):
        for mode in ("eager", "prefetch1", "pooled_device"):
            sps, share, h2d = run_round(mode)
            if sps > best[mode][0]:
                best[mode] = (sps, share, h2d)
    for mode in ("prefetch1", "pooled_device"):
        feeds[mode].close()

    out = {"workers": workers, "io_wait_ms": io_wait_ms,
           "steps": steps, "batch": data_shape[0]}
    for mode in feeds:
        out["%s_steps_per_sec" % mode] = round(best[mode][0], 2)
        out["data_wait_share_%s" % mode] = round(best[mode][1], 4)
    h2d = best["pooled_device"][2] or {}
    out["h2d_bytes_pooled"] = sum(c["bytes"] for c in h2d.values())
    out["h2d_ms_pooled"] = round(sum(c["time_ms"]
                                     for c in h2d.values()), 3)
    out["speedup_pooled_vs_eager"] = round(
        best["pooled_device"][0] / best["eager"][0], 3)
    out["speedup_pooled_vs_prefetch1"] = round(
        best["pooled_device"][0] / best["prefetch1"][0], 3)
    return out


def _input_pipeline_record():
    """The async-input-pipeline benchmark record (BENCH_r08.json):
    decode-bound MLP + convnet, eager vs 1-worker prefetch vs pooled
    multi-worker + device prefetch, with the telemetry data_wait share
    per mode. CPU-friendly — runs wherever the tier-1 suite runs."""
    import jax
    record = {"metric": "input_pipeline_steps_per_sec", "unit": "steps/s",
              "dtype": "float32", "optimizer": "sgd_momentum",
              "platform": jax.default_backend(), "cases": {}}
    errors = {}
    # decode is sized so one decode costs MORE than one compute step —
    # the regime the multi-worker pool exists for (a single prefetch
    # thread cannot hide a decode longer than the step it feeds)
    try:
        record["cases"]["mlp"] = _bench_input_pipeline_case(
            _mlp_sym, (64, 784), io_wait_ms=35.0)
    except Exception as exc:                     # noqa: BLE001
        errors["mlp"] = _err_str(exc)
    try:
        record["cases"]["convnet"] = _bench_input_pipeline_case(
            _convnet_sym, (32, 1, 28, 28), io_wait_ms=50.0)
    except Exception as exc:                     # noqa: BLE001
        errors["convnet"] = _err_str(exc)
    if errors:
        record["errors"] = errors
    return record


def _bench_checkpoint_case(build_sym, data_shape, steps=60, warmup=5,
                           ckpt_every=5, rounds=3):
    """Train-step time with checkpointing OFF vs SYNC (the durable
    write on the training thread, MXNET_ASYNC_CHECKPOINT=0 path) vs
    ASYNC (snapshot + bounded enqueue on the training thread, durable
    write on the background writer). Every mode runs the same fit-style
    save cadence (params host-sync + optimizer-state pickle + manager
    save every ``ckpt_every`` steps); per-step wall times are kept so
    the p99 — which is where a blocking save lands — is the headline.
    Rounds are interleaved (off, sync, async, ...) and each mode keeps
    its best (lowest-p99) round so host-load noise hits all three
    symmetrically. The acceptance bar: async p99 impact strictly below
    the synchronous path's."""
    import shutil
    import tempfile

    import numpy as np_
    import mxnet_tpu as mx
    from mxnet_tpu.checkpoint import CheckpointManager
    from mxnet_tpu.telemetry import percentile

    rng = np_.random.RandomState(0)
    batch = mx.io.DataBatch(
        data=[mx.nd.array(
            rng.uniform(0, 1, data_shape).astype(np_.float32))],
        label=[mx.nd.array(
            rng.randint(0, 10, (data_shape[0],)).astype(np_.float32))])

    mod = mx.module.Module(build_sym(), context=mx.current_context())
    mod.bind(data_shapes=[("data", data_shape)],
             label_shapes=[("softmax_label", (data_shape[0],))])
    mod.init_params(initializer=mx.init.Xavier())
    mod.init_optimizer(optimizer="sgd",
                       optimizer_params={"learning_rate": 0.05,
                                         "momentum": 0.9})
    for _ in range(warmup):
        mod.forward_backward(batch)
        mod.update()
    _sync_module(mod)

    workdir = tempfile.mkdtemp(prefix="bench_ckpt_")

    def run_round(mode, tag):
        mgr = None
        if mode != "off":
            mgr = CheckpointManager(
                os.path.join(workdir, "%s_%s" % (mode, tag)),
                async_=(mode == "async"))
        durs = []
        epoch = 0
        for i in range(steps):
            t0 = time.perf_counter()
            mod.forward_backward(batch)
            mod.update()
            if mgr is not None and (i + 1) % ckpt_every == 0:
                args_, auxs_ = mod.get_params()
                mgr.save(epoch, args_, auxs_,
                         states_bytes=mod._optimizer_state_bytes())
                epoch += 1
            durs.append((time.perf_counter() - t0) * 1e3)
        _sync_module(mod)
        if mgr is not None:
            mgr.close()
            assert mgr.stats()["failures"] == 0
        return durs

    best = {}
    try:
        for r in range(rounds):
            for mode in ("off", "sync", "async"):
                durs = run_round(mode, "r%d" % r)
                if mode not in best or percentile(durs, 99) \
                        < percentile(best[mode], 99):
                    best[mode] = durs
    finally:
        shutil.rmtree(workdir, ignore_errors=True)

    out = {"steps": steps, "ckpt_every": ckpt_every,
           "batch": data_shape[0]}
    for mode, durs in best.items():
        total_s = sum(durs) / 1e3
        out["%s_steps_per_sec" % mode] = round(steps / total_s, 2)
        out["%s_p50_ms" % mode] = round(percentile(durs, 50), 3)
        out["%s_p99_ms" % mode] = round(percentile(durs, 99), 3)
    for mode in ("sync", "async"):
        out["%s_p99_impact_pct" % mode] = round(
            100.0 * (out["%s_p99_ms" % mode] / out["off_p99_ms"] - 1.0),
            2)
    out["async_p99_below_sync"] = bool(
        out["async_p99_ms"] < out["sync_p99_ms"])
    return out


def _checkpoint_record():
    """The checkpoint-overhead benchmark record (BENCH_r10.json).
    CPU-friendly — runs wherever the tier-1 suite runs."""
    import jax
    record = {"metric": "checkpoint_overhead", "unit": "ms/step (p99)",
              "dtype": "float32", "optimizer": "sgd_momentum",
              "platform": jax.default_backend(), "cases": {}}
    errors = {}
    try:
        record["cases"]["mlp"] = _bench_checkpoint_case(
            _mlp_sym, (64, 784))
    except Exception as exc:                     # noqa: BLE001
        errors["mlp"] = _err_str(exc)
    try:
        record["cases"]["convnet"] = _bench_checkpoint_case(
            _convnet_sym, (32, 1, 28, 28))
    except Exception as exc:                     # noqa: BLE001
        errors["convnet"] = _err_str(exc)
    if errors:
        record["errors"] = errors
    return record


def _bench_grad_overlap_case(steps=30, warmup=5, rounds=3,
                             batch=64, bucket_mb=0.5):
    """The grad-sync perf oracle on the 8-device CPU mesh: the
    UNBUCKETED baseline (ROADMAP item 4's "one monolithic blob after
    backward completes" — forward+backward dispatch, then a
    host-dispatched blob reduce-scatter + all-gather under a real
    telemetry ``sync`` span, then the update dispatch) vs the OVERLAP
    path from parallel.grad_sync (backward-ordered buckets constrained
    to P('dp') inside ONE compiled step — the partitioner schedules
    each bucket's reduce-scatter against the remaining backward, so
    there is no host-observable sync phase at all). Same MLP, same
    data, same SGD rule; the two trajectories are checked to agree
    before timing. Rounds are interleaved (post, overlap, ...) and
    each mode keeps its best (highest steps/sec) round. The acceptance
    bar: overlap's telemetry sync-phase share strictly below the
    unbucketed baseline's."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from mxnet_tpu import telemetry
    from mxnet_tpu.parallel import collectives, grad_sync
    from mxnet_tpu.parallel.data_parallel import make_data_parallel_step
    from mxnet_tpu.parallel.mesh import local_mesh

    mesh = local_mesh("dp")
    n_dev = int(mesh.devices.size)
    sizes = (256, 512, 512, 512, 10)
    lr = 0.1

    rng = np_random = np.random.RandomState(0)
    init = {}
    for li in range(len(sizes) - 1):
        init["w%d" % li] = (np_random.normal(
            0, 0.1, (sizes[li], sizes[li + 1])).astype(np.float32))
        init["b%d" % li] = np.zeros((sizes[li + 1],), np.float32)
    x_host = rng.normal(0, 1, (batch, sizes[0])).astype(np.float32)
    y_host = rng.normal(0, 1, (batch, sizes[-1])).astype(np.float32)

    def loss_fn(params, b):
        # sum/GLOBAL normalization: per-device partial losses/grads SUM
        # to the global ones, so the post-mode blob reduce needs no
        # rescale and both modes optimize the identical objective
        h = b["x"]
        nl = len(sizes) - 1
        for li in range(nl):
            h = h @ params["w%d" % li] + params["b%d" % li]
            if li < nl - 1:
                h = jnp.tanh(h)
        return jnp.sum((h - b["y"]) ** 2) / (batch * sizes[-1])

    names = sorted(init)
    shapes = [init[n].shape for n in names]
    flat_sizes = [int(np.prod(s)) for s in shapes]
    offs, off = [], 0
    for s in flat_sizes:
        offs.append(off)
        off += s
    rep = NamedSharding(mesh, P())
    dp = NamedSharding(mesh, P("dp"))

    # -- the unbucketed baseline: blob exchange AFTER backward ----------
    def local_fwdbwd(pv, xb, yb):
        loss, g = jax.value_and_grad(loss_fn)(
            pv, {"x": xb, "y": yb})
        return loss[None], [g[n][None] for n in names]

    fwdbwd = jax.jit(collectives._shard_map()(
        local_fwdbwd, mesh=mesh, in_specs=(P(), P("dp"), P("dp")),
        out_specs=(P("dp"), [P("dp") for _ in names])))

    def apply_blob(pv, blob):
        return {n: pv[n] - lr * blob[o:o + s].reshape(pv[n].shape)
                for n, o, s in zip(names, offs, flat_sizes)}

    update = jax.jit(apply_blob)

    def run_post(n_steps, params, with_tel):
        xb = jax.device_put(x_host, dp)
        yb = jax.device_put(y_host, dp)
        losses = []
        t0 = time.perf_counter()
        for _ in range(n_steps):
            if with_tel:
                telemetry.step_begin()
            with telemetry.span("compute"):
                loss, stacked = fwdbwd(params, xb, yb)
                jax.block_until_ready(stacked)
            with telemetry.span("sync"):
                flat = collectives.bucket_reduce_scatter(
                    stacked, mesh, key="blob")
                blob = collectives.bucket_all_gather(flat, mesh,
                                                     key="blob")
                blob.block_until_ready()
            with telemetry.span("optimizer"):
                params = update(params, blob)
                jax.block_until_ready(params)
            losses.append(float(jnp.sum(loss)))
            if with_tel:
                telemetry.step_end(samples=batch)
        return time.perf_counter() - t0, losses, params

    # -- the overlap path: bucketed reduce-scatter INSIDE the step ------
    step_fn, batch_sharding = make_data_parallel_step(
        loss_fn, mesh, optimizer_update=lambda p, g: p - lr * g,
        donate=False, grad_overlap=True, bucket_mb=bucket_mb)
    plan = grad_sync.GradSyncPlan(
        [init[n].shape for n in sorted(init)],
        [init[n].dtype for n in sorted(init)],
        axis_size=n_dev, cap_bytes=int(bucket_mb * (1 << 20)))

    def run_overlap(n_steps, params, with_tel):
        b = {"x": jax.device_put(x_host, batch_sharding),
             "y": jax.device_put(y_host, batch_sharding)}
        losses = []
        t0 = time.perf_counter()
        for _ in range(n_steps):
            if with_tel:
                telemetry.step_begin()
            with telemetry.span("compute"):
                loss, params = step_fn(params, b)
                jax.block_until_ready(params)
            grad_sync.account_in_program_sync(plan)
            losses.append(float(loss))
            if with_tel:
                telemetry.step_end(samples=batch)
        return time.perf_counter() - t0, losses, params

    def fresh():
        return {n: jax.device_put(jnp.asarray(v), rep)
                for n, v in init.items()}

    # warmup (compiles) + trajectory agreement before any timing
    _, l_post, _ = run_post(warmup, fresh(), False)
    _, l_over, _ = run_overlap(warmup, fresh(), False)
    traj = bool(np.allclose(l_post, l_over, rtol=1e-4, atol=1e-6))

    runners = {"post": run_post, "overlap": run_overlap}
    best = {}
    for _ in range(rounds):
        for mode, runner in runners.items():
            telemetry.start()
            dt, _, _ = runner(steps, fresh(), True)
            rep_tel = telemetry.report()
            telemetry.stop()
            sps = steps / dt
            if mode not in best or sps > best[mode][0]:
                best[mode] = (sps, rep_tel["phases_ms"])

    out = {"steps": steps, "batch": batch, "n_dev": n_dev,
           "bucket_mb": bucket_mb, "buckets": len(plan.buckets),
           "params_mb": round(sum(flat_sizes) * 4 / (1 << 20), 2),
           "trajectory_match": traj}
    for mode, (sps, phases) in best.items():
        whole = sum(phases.values()) or 1.0
        out["%s_steps_per_sec" % mode] = round(sps, 2)
        out["%s_sync_share_pct" % mode] = round(
            100.0 * phases.get("sync", 0.0) / whole, 2)
        out["%s_phases_ms" % mode] = {k: round(v, 1)
                                      for k, v in phases.items()}
    out["speedup"] = round(out["overlap_steps_per_sec"]
                           / out["post_steps_per_sec"], 3)
    out["overlap_sync_below_post"] = bool(
        out["overlap_sync_share_pct"] < out["post_sync_share_pct"])
    return out


def _bench_zero1_state_memory(steps=2):
    """The ZeRO-1 memory oracle: per-device resident optimizer-state
    bytes through the DistributedTrainer with Adam, overlap off
    (replicated — the full two-slot f32 copy on every device) vs on
    (flat dp-sharded — 1/N each). The ledger is the same one
    tests/test_grad_sync.py verifies against the actual device
    shards."""
    import mxnet_tpu as mx
    from mxnet_tpu import gluon
    from mxnet_tpu.gluon import nn
    from mxnet_tpu.parallel import DistributedTrainer
    from mxnet_tpu.parallel.mesh import local_mesh

    mesh = local_mesh("dp")
    n_dev = int(mesh.devices.size)
    rng = np.random.RandomState(1)

    def run(overlap):
        net = nn.HybridSequential()
        net.add(nn.Dense(256, activation="relu", in_units=128),
                nn.Dense(10, in_units=256))
        net.initialize()
        tr = DistributedTrainer(
            net, gluon.loss.SoftmaxCrossEntropyLoss(), mesh,
            optimizer="adam", optimizer_params={"learning_rate": 0.01},
            grad_overlap=overlap, bucket_mb=0.125)
        for _ in range(steps):
            data = mx.nd.array(rng.randn(16, 128).astype(np.float32))
            label = mx.nd.array(
                rng.randint(0, 10, (16,)).astype(np.float32))
            tr.fit_batch(data, label).asnumpy()
        return tr.state_bytes_per_device()

    off_b, on_b = run(False), run(True)
    return {"n_dev": n_dev, "optimizer": "adam",
            "off_state_bytes_per_device": off_b,
            "on_state_bytes_per_device": on_b,
            "on_over_off": round(on_b / off_b, 4),
            "reduced_one_over_n": bool(on_b * n_dev == off_b)}


def _bench_param_shard_case(steps=15, warmup=3, rounds=3, batch=64):
    """The FSDP oracle on the 8-device CPU mesh: the same MLP trained
    through DistributedTrainer with replicated vs FSDP-sharded
    resident parameters (MXNET_PARAM_SHARD path, name-rule
    PartitionSpecs). The model is sized so the TOTAL parameter bytes
    exceed a per-device budget that one 1/N shard fits comfortably —
    under a capped allocator the replicated layout would OOM at rest
    while the sharded run completes; the budget, both measured
    per-device figures, and the fit/exceed booleans are recorded.
    Trajectories are checked bit-identical before timing (the FSDP
    step gathers at entry and runs the identical computation).
    Interleaved rounds, best steps/sec per mode."""
    import jax
    import mxnet_tpu as mx
    from mxnet_tpu import gluon
    from mxnet_tpu.gluon import nn
    from mxnet_tpu.parallel import DistributedTrainer
    from mxnet_tpu.parallel.mesh import local_mesh

    mesh = local_mesh("dp")
    n_dev = int(mesh.devices.size)
    hidden = 1024
    in_units = 512

    def fresh(shard):
        net = nn.HybridSequential(prefix="bench_fsdp_")
        with net.name_scope():
            net.add(nn.Dense(hidden, activation="relu",
                             in_units=in_units),
                    nn.Dense(hidden, activation="relu",
                             in_units=hidden),
                    nn.Dense(10, in_units=hidden))
        net.initialize()
        for i, (_, p) in enumerate(sorted(net.collect_params()
                                          .items())):
            v = np.random.RandomState(40 + i).normal(
                0, 0.05, p.shape).astype(np.float32)
            p.set_data(mx.nd.array(v))
        return DistributedTrainer(
            net, gluon.loss.SoftmaxCrossEntropyLoss(), mesh,
            optimizer="adam", optimizer_params={"learning_rate": 1e-3},
            grad_overlap=True, bucket_mb=0.5, param_shard=shard)

    rng = np.random.RandomState(7)
    x_host = rng.normal(0, 1, (batch, in_units)).astype(np.float32)
    y_host = rng.randint(0, 10, (batch,)).astype(np.float32)

    def run(tr, n_steps):
        data = mx.nd.array(x_host)
        label = mx.nd.array(y_host)
        losses = []
        t0 = time.perf_counter()
        for _ in range(n_steps):
            losses.append(float(tr.fit_batch(data, label).asnumpy()))
        return time.perf_counter() - t0, losses

    # warmup + trajectory identity before timing
    trainers = {"replicated": fresh(False), "sharded": fresh(True)}
    warm_losses = {}
    for mode, tr in trainers.items():
        _, warm_losses[mode] = run(tr, warmup)
    traj = warm_losses["replicated"] == warm_losses["sharded"]

    best = {}
    for _ in range(rounds):
        for mode, tr in trainers.items():
            dt, _ = run(tr, steps)
            sps = steps / dt
            if mode not in best or sps > best[mode]:
                best[mode] = sps

    rep_bytes = trainers["replicated"].param_bytes_per_device()
    shd_bytes = trainers["sharded"].param_bytes_per_device()
    # the capped-allocator scenario: a per-device parameter budget one
    # shard fits with headroom but the full replica cannot — the
    # params-too-big-for-one-shard case the ROADMAP asks for
    budget = rep_bytes // 3
    bd = trainers["sharded"]._memory_breakdown()
    out = {
        "steps": steps, "batch": batch, "n_dev": n_dev,
        "total_param_bytes": rep_bytes,
        "param_budget_bytes_per_device": budget,
        "replicated_param_bytes_per_device": rep_bytes,
        "sharded_param_bytes_per_device": shd_bytes,
        "sharded_breakdown": bd,
        "replicated_exceeds_budget": bool(rep_bytes > budget),
        "sharded_fits_budget": bool(shd_bytes <= budget),
        "sharded_run_completed": True,       # run() above would raise
        "param_bytes_ratio": round(shd_bytes / rep_bytes, 4),
        "trajectory_match_bitexact": bool(traj),
        "opt_state_bytes_per_device":
            trainers["sharded"].state_bytes_per_device(),
    }
    for mode, sps in best.items():
        out["%s_steps_per_sec" % mode] = round(sps, 2)
    out["sharded_over_replicated"] = round(
        best["sharded"] / best["replicated"], 3)
    return out


def _param_shard_record():
    """The FSDP benchmark record (BENCH_r12.json): replicated vs
    sharded-resident parameters through the DistributedTrainer on the
    8-device CPU mesh — steps/sec, measured per-device parameter
    bytes (≈1/N up to padding), and the capped-allocator budget the
    replicated layout would blow."""
    import jax
    record = {"metric": "param_shard", "unit": "steps/sec",
              "dtype": "float32",
              "platform": jax.default_backend(),
              "devices": len(jax.devices()), "cases": {}}
    errors = {}
    try:
        record["cases"]["fsdp_mlp"] = _bench_param_shard_case()
    except Exception as exc:                     # noqa: BLE001
        errors["fsdp_mlp"] = _err_str(exc)
    if errors:
        record["errors"] = errors
    return record


def _grad_overlap_record():
    """The gradient-sync benchmark record (BENCH_r11.json): unbucketed
    post-backward blob vs in-program bucketed overlap on the 8-device
    CPU mesh, plus the ZeRO-1 per-device state-memory split."""
    import jax
    record = {"metric": "grad_overlap", "unit": "steps/sec",
              "dtype": "float32",
              "platform": jax.default_backend(),
              "devices": len(jax.devices()), "cases": {}}
    errors = {}
    try:
        record["cases"]["mesh_mlp"] = _bench_grad_overlap_case()
    except Exception as exc:                     # noqa: BLE001
        errors["mesh_mlp"] = _err_str(exc)
    try:
        record["cases"]["zero1_state"] = _bench_zero1_state_memory()
    except Exception as exc:                     # noqa: BLE001
        errors["zero1_state"] = _err_str(exc)
    if errors:
        record["errors"] = errors
    return record


def _serving_mlp_artifact(tdir, ladder, in_dim=512, hidden=2048):
    """The serving benches' shared model: a 512→2048→10 MLP exported
    as one multi-signature artifact (one program per ladder bucket).
    BENCH_r13 and BENCH_r15 figures are comparable BECAUSE both
    benches serve this exact artifact. Returns (path, in_dim)."""
    import numpy as np_
    import mxnet_tpu as mx

    d = mx.sym.var("data")
    h = mx.sym.FullyConnected(d, name="fc1", num_hidden=hidden)
    h = mx.sym.Activation(h, act_type="relu")
    net = mx.sym.FullyConnected(h, name="fc2", num_hidden=10)
    rs = np_.random.RandomState(0)
    params = {"fc1_weight": mx.nd.array(
                  rs.randn(hidden, in_dim).astype(np_.float32) * 0.05),
              "fc1_bias": mx.nd.zeros((hidden,)),
              "fc2_weight": mx.nd.array(
                  rs.randn(10, hidden).astype(np_.float32) * 0.05),
              "fc2_bias": mx.nd.zeros((10,))}
    artifact = os.path.join(tdir, "mlp.mxp")
    mx.deploy.export_compiled(net, artifact, params=params,
                              input_shapes={"data": (1, in_dim)},
                              batch_sizes=list(ladder))
    return artifact, in_dim


def _bench_serving_sweep(rates=(50, 100, 200, 400, 800, 1600, 3200),
                         seconds_per_rate=1.5, ladder=(1, 2, 4, 8),
                         max_queue=32):
    """Offered-load sweep over the continuous-batching inference
    server (BENCH_r13): export an MLP as a multi-signature artifact
    (one program per ladder bucket), then drive open-loop Poisson-ish
    arrivals at increasing rates through ONE server instance (programs
    stay warm across rates; per-rate latencies are measured client
    side, sheds by cumulative diff). Past saturation the bounded queue
    sheds instead of queueing unboundedly, so p99 latency must stay
    bounded — the record carries the curve plus the compile-watch
    oracle that the program cache stayed at the ladder size with zero
    steady-state recompiles."""
    import tempfile

    import numpy as np_
    import mxnet_tpu as mx
    from mxnet_tpu import compile_watch, serving, telemetry

    compile_watch.enable()
    with tempfile.TemporaryDirectory() as tdir:
        artifact, in_dim = _serving_mlp_artifact(tdir, ladder)
        srv = serving.InferenceServer(artifact, max_queue=max_queue,
                                      batch_window_ms=1.0)
        rs = np_.random.RandomState(0)
        try:
            # deterministic warmup: compile every bucket program up
            # front (request bursts can coalesce into OTHER buckets,
            # which would smear compiles into the timed sweep)
            srv.warmup()
            warm_programs = dict(
                compile_watch.site_stats("serving") or {})

            sweep = []
            prev = srv.stats()
            # one request payload reused for the whole sweep: the
            # submit loop must outpace the highest offered rate, and
            # per-request randn would throttle the client, not the
            # server
            x = rs.randn(in_dim).astype(np_.float32)
            for rate in rates:
                n = min(max(10, int(rate * seconds_per_rate)), 1500)
                dt = 1.0 / rate
                futs = []
                shed_client = 0
                t0 = time.perf_counter()
                for i in range(n):
                    target = t0 + i * dt
                    now = time.perf_counter()
                    if target > now:
                        time.sleep(target - now)
                    try:
                        futs.append(srv.submit(x))
                    except serving.ServerOverloadedError:
                        shed_client += 1
                for f in futs:
                    f.result(timeout=60)
                elapsed = time.perf_counter() - t0
                # true queue+service latency, stamped at fulfillment
                # by the worker — not time-to-collection
                lat = [f.latency * 1e3 for f in futs
                       if f.latency is not None]
                cur = srv.stats()
                slots = sum(int(b) * (cur["buckets"].get(str(b), 0)
                                      - prev["buckets"].get(str(b), 0))
                            for b in ladder)
                done = cur["completed"] - prev["completed"]
                entry = {
                    "offered_rps": rate,
                    "submitted": n,
                    "completed": done,
                    "shed": cur["shed"] - prev["shed"],
                    "shed_rate": round((cur["shed"] - prev["shed"])
                                       / float(n), 4),
                    "achieved_rps": round(done / elapsed, 2),
                    "latency_ms_p50": round(
                        telemetry.percentile(lat, 50), 3) if lat
                    else None,
                    "latency_ms_p99": round(
                        telemetry.percentile(lat, 99), 3) if lat
                    else None,
                    "occupancy": round(done / slots, 4) if slots
                    else None,
                    "queue_peak": cur["queue_peak"],
                }
                assert entry["shed"] == shed_client
                sweep.append(entry)
                prev = cur
            final_programs = dict(
                compile_watch.site_stats("serving") or {})
        finally:
            srv.stop()
            compile_watch.disable()

    saturated = [e for e in sweep if e["shed_rate"] > 0.05]
    sat_p99s = [e["latency_ms_p99"] for e in saturated
                if e["latency_ms_p99"] is not None]
    if sat_p99s:
        p99_bounded = all(p <= 3.0 * sat_p99s[0] for p in sat_p99s)
    elif saturated:
        # saturated but zero completed requests carried a latency:
        # no evidence either way — report unknown, never a free pass
        p99_bounded = None
    else:
        p99_bounded = True      # never saturated: vacuously bounded
    return {
        "metric": "serving_offered_load_sweep",
        "ladder": list(ladder),
        "max_queue": max_queue,
        "batch_window_ms": 1.0,
        "sweep": sweep,
        "saturation_offered_rps": saturated[0]["offered_rps"]
        if saturated else None,
        "p99_bounded_past_saturation": p99_bounded,
        "queue_peak_max": max(e["queue_peak"] for e in sweep),
        "queue_bound_honored": bool(
            max(e["queue_peak"] for e in sweep) <= max_queue),
        "serving_programs": {k: v["count"]
                             for k, v in sorted(warm_programs.items())},
        "steady_state_recompiles": sum(
            v["count"] for v in final_programs.values()) - sum(
            v["count"] for v in warm_programs.values()),
    }


def _bench_bucketing_case(n_sentences=240, batch=8,
                          ladder=(11, 22, 32, 42), len_lo=3, len_hi=43,
                          epochs=2):
    """Variable-length LSTM text model (BENCH_r14): bucketed training
    over a small geometric ladder vs the naive one-program-per-
    distinct-length alternative. The win the record captures is the
    COMPILE bill — ladder-size programs vs O(distinct lengths) — and
    the total wall clock including compile time (epoch 1 cold, epoch 2
    warm), via the compile-watch `bucketing:<len>` site oracle."""
    import mxnet_tpu as mx
    from mxnet_tpu import compile_watch

    compile_watch.enable()
    rng = np.random.RandomState(7)
    V, E, H = 24, 12, 16
    sents = [list(rng.randint(1, V, size=L))
             for L in rng.choice(np.arange(len_lo, len_hi),
                                 size=n_sentences)]
    distinct = sorted({len(s) for s in sents})

    def sym_gen(seq_len):
        data = mx.sym.var("data")
        label = mx.sym.var("softmax_label")
        emb = mx.sym.Embedding(data, input_dim=V, output_dim=E,
                               name="embed")
        stack = mx.rnn.SequentialRNNCell()
        stack.add(mx.rnn.LSTMCell(H, prefix="lstm_"))
        outputs, _ = stack.unroll(seq_len, emb, layout="NTC",
                                  merge_outputs=True)
        pred = mx.sym.Reshape(outputs, shape=(-1, H))
        pred = mx.sym.FullyConnected(pred, num_hidden=V, name="pred")
        label_f = mx.sym.Reshape(label, shape=(-1,))
        out = mx.sym.SoftmaxOutput(pred, label_f, name="softmax",
                                   use_ignore=True, ignore_label=0,
                                   normalization="valid")
        return out, ("data",), ("softmax_label",)

    def run(buckets):
        np.random.seed(0)           # iterator shuffles are np.random
        it = mx.rnn.BucketSentenceIter(sents, batch_size=batch,
                                       buckets=list(buckets),
                                       invalid_label=0)
        mod = mx.mod.BucketingModule(
            sym_gen, default_bucket_key=it.default_bucket_key)
        before = {k: v["count"] for k, v in
                  (compile_watch.site_stats("bucketing") or {}).items()}
        before_s = sum(
            v["total_s"] for v in
            (compile_watch.site_stats("bucketing") or {}).values())
        epoch_wall = []
        for epoch in range(epochs):
            t0 = time.perf_counter()
            mod.fit(it, num_epoch=1,
                    eval_metric=mx.metric.Perplexity(ignore_label=0),
                    optimizer="sgd",
                    optimizer_params={"learning_rate": 0.05})
            epoch_wall.append(round(time.perf_counter() - t0, 3))
        after = compile_watch.site_stats("bucketing") or {}
        compiles = sum(v["count"] for v in after.values()) \
            - sum(before.values())
        compile_s = sum(v["total_s"] for v in after.values()) - before_s
        steps = it.bucketing.snapshot()["batches"]
        return {"buckets": len(buckets), "compiles": compiles,
                "compile_s": round(compile_s, 3),
                "wall_s_cold_epoch": epoch_wall[0],
                "wall_s_warm_epoch": epoch_wall[-1],
                "wall_s_total": round(sum(epoch_wall), 3),
                "steps": steps}

    bucketed = run(ladder)
    naive = run(distinct)       # one bucket (= one program) per length
    out = {
        "sentences": n_sentences,
        "distinct_lengths": len(distinct),
        "ladder": list(ladder),
        "bucketed": bucketed,
        "naive_per_length": naive,
        "compile_ratio": round(naive["compiles"]
                               / max(1, bucketed["compiles"]), 2),
        "total_wall_speedup": round(naive["wall_s_total"]
                                    / bucketed["wall_s_total"], 3),
        "oracle_compiles_equal_ladder": bool(
            bucketed["compiles"] == len(ladder)),
    }
    return out


def _bucketing_record():
    """The shape-bucketing benchmark record (BENCH_r14.json):
    variable-length text training bucketed vs naive-per-length —
    compile count (ladder size vs O(distinct lengths)) and total wall
    clock including compiles. CPU backend."""
    record = {"bench": "bucketing", "platform": "cpu"}
    try:
        record.update(_bench_bucketing_case())
    except Exception as exc:                     # noqa: BLE001
        record["errors"] = {"bucketing": _err_str(exc)}
    return record


def _serving_record():
    """The serving benchmark record (BENCH_r13.json): offered-load
    sweep — arrival rate x bucket ladder -> latency/throughput curve,
    shed rate at overload, bounded p99 past saturation, fixed program
    cache. CPU backend."""
    record = {"bench": "serving", "platform": "cpu"}
    try:
        record.update(_bench_serving_sweep())
    except Exception as exc:                     # noqa: BLE001
        record["errors"] = {"serving": _err_str(exc)}
    return record


def _bench_trace_overhead_mlp(steps=100, warmup=5, rounds=5):
    """Fused-MLP train-step time with the live observability stack OFF
    (tracing, /metrics, watchdog all disabled — every hook is one
    module-global None check; this is the default production env) vs
    ON (trace ring recording step/phase/dispatch events, a telemetry
    run with a JSONL sink, the watchdog armed, the /metrics endpoint
    live and scraped once per round). Rounds are interleaved so host-
    load noise hits both modes symmetrically. The acceptance bar is
    the OFF path: within the documented CPU noise band of the
    BENCH_r13/r14-era fused MLP figures."""
    import tempfile
    import urllib.request

    import numpy as np_
    import mxnet_tpu as mx
    from mxnet_tpu import livemetrics, telemetry, tracing

    rng = np_.random.RandomState(0)
    data_shape = (64, 784)
    batch = mx.io.DataBatch(
        data=[mx.nd.array(
            rng.uniform(0, 1, data_shape).astype(np_.float32))],
        label=[mx.nd.array(
            rng.randint(0, 10, (data_shape[0],)).astype(np_.float32))])

    mod = mx.module.Module(_mlp_sym(), context=mx.current_context())
    mod.bind(data_shapes=[("data", data_shape)],
             label_shapes=[("softmax_label", (data_shape[0],))])
    mod.init_params(initializer=mx.init.Xavier())
    mod.init_optimizer(optimizer="sgd",
                       optimizer_params={"learning_rate": 0.05,
                                         "momentum": 0.9})
    for _ in range(warmup):
        mod.forward_backward(batch)
        mod.update()
    _sync_module(mod)

    sink = os.path.join(tempfile.gettempdir(),
                        "bench_trace_%d.jsonl" % os.getpid())
    port = livemetrics.serve(0)
    trace_events = 0

    def run_round(mode):
        nonlocal trace_events
        if mode == "on":
            tracing.enable()
            livemetrics.enable_watchdog()
            telemetry.start(filename=sink,
                            meta={"case": "trace_overhead"})
        for _ in range(warmup):          # absorb the mode flip
            mod.forward_backward(batch)
            mod.update()
        _sync_module(mod)
        t0 = time.perf_counter()
        for _ in range(steps):
            if mode == "on":
                telemetry.step_begin()
                mod.forward_backward(batch)
                mod.update()
                telemetry.step_end(samples=data_shape[0])
            else:
                mod.forward_backward(batch)
                mod.update()
        _sync_module(mod)
        dt = time.perf_counter() - t0
        if mode == "on":
            # one live scrape per round — the operator-visible cost
            urllib.request.urlopen(
                "http://127.0.0.1:%d/metrics" % port, timeout=10).read()
            trace_events = tracing.stats()["events"]
            telemetry.stop()
            livemetrics.disable_watchdog()
            tracing.reset()
        return steps / dt

    telemetry.reset()
    tracing.reset()
    run_round("off")             # settle round, discarded
    best = {"off": 0.0, "on": 0.0}
    for _ in range(rounds):
        for mode in ("off", "on"):
            best[mode] = max(best[mode], run_round(mode))
    livemetrics.stop_server()
    try:
        os.remove(sink)
    except OSError:
        pass
    return {
        "trace_off_steps_per_sec": round(best["off"], 2),
        "trace_on_steps_per_sec": round(best["on"], 2),
        "on_overhead_pct": round(
            100.0 * (best["off"] / best["on"] - 1.0), 2),
        "trace_events_per_round": trace_events,
        "steps": steps,
        "batch": data_shape[0],
    }


def _bench_trace_overhead_serving(n_requests=300, rate=400.0,
                                  ladder=(1, 2, 4, 8), rounds=3):
    """The serving half: one warm server driven open-loop at a fixed
    sub-saturation rate, tracing+metrics OFF vs ON (per-request
    lifecycle spans + a /metrics scrape whose shed/completed counters
    must agree with server.stats() — the acceptance oracle). The OFF
    figures are comparable with the BENCH_r13 sweep entry at the same
    offered rate."""
    import tempfile
    import urllib.request

    import numpy as np_
    from mxnet_tpu import livemetrics, serving, telemetry, tracing

    rs = np_.random.RandomState(0)
    out = {}
    with tempfile.TemporaryDirectory() as tdir:
        artifact, in_dim = _serving_mlp_artifact(tdir, ladder)
        srv = serving.InferenceServer(artifact, max_queue=64,
                                      batch_window_ms=1.0,
                                      name="bench")
        port = livemetrics.serve(0)
        try:
            srv.warmup()
            x = rs.randn(in_dim).astype(np_.float32)
            dt = 1.0 / rate

            def run_round(mode):
                if mode == "on":
                    tracing.enable()
                futs = []
                t0 = time.perf_counter()
                for i in range(n_requests):
                    target = t0 + i * dt
                    now = time.perf_counter()
                    if target > now:
                        time.sleep(target - now)
                    try:
                        futs.append(srv.submit(x))
                    except serving.ServerOverloadedError:
                        pass
                for f in futs:
                    f.result(timeout=60)
                elapsed = time.perf_counter() - t0
                lat = [f.latency * 1e3 for f in futs
                       if f.latency is not None]
                entry = {
                    "achieved_rps": round(len(futs) / elapsed, 2),
                    "latency_ms_p50": round(
                        telemetry.percentile(lat, 50), 3),
                    "latency_ms_p99": round(
                        telemetry.percentile(lat, 99), 3),
                }
                if mode == "on":
                    tracing.reset()
                return entry

            run_round("off")     # settle round, discarded: the first
            # pass after warmup absorbs allocator/thread warm-in that
            # would otherwise bias whichever mode runs first
            best = {}
            for _ in range(rounds):
                for mode in ("off", "on"):
                    e = run_round(mode)
                    if mode not in best or \
                            e["latency_ms_p50"] < \
                            best[mode]["latency_ms_p50"]:
                        best[mode] = e
            # the acceptance oracle: a live scrape's serving counters
            # must agree with the server's own cumulative stats
            text = urllib.request.urlopen(
                "http://127.0.0.1:%d/metrics" % port,
                timeout=10).read().decode()
            st = srv.stats()
            agree = True
            for metric, key in (("mxnet_serving_completed_total",
                                 "completed"),
                                ("mxnet_serving_shed_total", "shed")):
                line = [l for l in text.splitlines()
                        if l.startswith('%s{server="bench"}' % metric)]
                agree = agree and len(line) == 1 and \
                    float(line[0].rsplit(" ", 1)[1]) == st[key]
            out = {"offered_rps": rate, "requests": n_requests,
                   "off": best["off"], "on": best["on"],
                   "trace_on_p50_overhead_pct": round(
                       100.0 * (best["on"]["latency_ms_p50"]
                                / best["off"]["latency_ms_p50"] - 1.0),
                       2),
                   "metrics_agree_with_stats": bool(agree)}
        finally:
            srv.stop()
            livemetrics.stop_server()
            tracing.reset()
    return out


def _bench_packing_case(n_samples=480, batch=8, bucket=64, rounds=3,
                        C=16, E=96, H=192):
    """Packed vs padded training at a SKEWED length mix (mostly-short
    samples under a tall bucket — the distribution where padding burns
    the most FLOPs): the same embedding+dense token model trained on
    the same ragged stream through BucketedPipeline +
    MaskedSoftmaxCELoss (one sample per row) and PackedPipeline +
    PackedSoftmaxCELoss (FFD-packed rows). The loss contract makes the
    per-sample math identical bit-for-bit, so the delta is pure
    throughput: packing fits the epoch into ~real_token_fraction_ratio
    fewer rows. Figures: steps/sec, samples/sec (the honest headline —
    a packed step carries more samples), real-token fraction each."""
    import mxnet_tpu as mx
    from mxnet_tpu import gluon
    from mxnet_tpu.bucketing import (BucketedPipeline,
                                     MaskedSoftmaxCELoss,
                                     PackedPipeline,
                                     PackedSoftmaxCELoss,
                                     masked_batch_loss, position_mask,
                                     segment_gather)
    from mxnet_tpu.gluon import nn

    rng = np.random.RandomState(11)
    # skewed: 85% short (4..12 tokens), 15% long tail (up to bucket)
    lengths = np.where(rng.rand(n_samples) < 0.85,
                       rng.randint(4, 13, size=n_samples),
                       rng.randint(32, bucket + 1, size=n_samples))
    V = 64
    stream = [(rng.randint(1, V, size=int(L)).astype(np.float32),
               rng.randint(0, C, size=int(L)).astype(np.float32))
              for L in lengths]

    def build_net():
        # compute-dominant on purpose: packing's claim is about the
        # FLOPs the hardware runs, so the step must be model-bound,
        # not host-bound (a toy net would just time python overhead)
        net = nn.HybridSequential()
        with net.name_scope():
            net.add(nn.Embedding(V, E))
            net.add(nn.Dense(H, flatten=False, activation="relu"))
            net.add(nn.Dense(H, flatten=False, activation="relu"))
            net.add(nn.Dense(C, flatten=False))
        net.initialize(mx.init.Xavier())
        net(mx.nd.array(np.ones((2, 3), np.float32)))
        return net

    # ONE hybridized loss + net per mode, shared across rounds: the
    # CachedOps compile during the warmup round, so the timed rounds
    # measure compute, not dispatch or compilation
    nets = {m: build_net() for m in ("padded", "packed")}
    losses = {"padded": MaskedSoftmaxCELoss(),
              "packed": PackedSoftmaxCELoss()}
    for fn in losses.values():
        fn.hybridize()

    def run(mode):
        np.random.seed(0)
        net = nets[mode]
        trainer = gluon.Trainer(net.collect_params(), "sgd",
                                {"learning_rate": 0.05})
        loss_fn = losses[mode]
        if mode == "packed":
            pipe = PackedPipeline(stream, batch_size=batch,
                                  ladder=[bucket])
        else:
            pipe = BucketedPipeline(stream, batch_size=batch,
                                    ladder=[bucket])
        steps = samples = 0
        t0 = time.perf_counter()
        for b in pipe:
            data = b.data[0]
            lab = b.label[0]
            if mode == "packed":
                # pow-2 plane budget: O(log) compiled loss programs
                # (settled in warmup) and a scatter-backward sized to
                # the batch, not to the theoretical worst case
                n_pad = 1 << max(3, (b.n_segments - 1).bit_length())
                idx, mask = segment_gather(b.segment_ids, b.n_segments,
                                           n_pad=n_pad)
                n_valid = b.n_segments
                extra = (mx.nd.array(idx, dtype="int32"),
                         mx.nd.array(mask))
            else:
                mask = position_mask(b.valid_lengths, b.bucket_key)
                n_valid = b.valid_rows
                extra = (mx.nd.array(mask),)
            with mx.autograd.record():
                out = net(data)
                vec = loss_fn(out, lab, *extra)
                total = masked_batch_loss(vec, n_valid)
            total.backward()
            trainer.step(1)
            steps += 1
            samples += n_valid
        wall = time.perf_counter() - t0
        snap = pipe.stats.snapshot()
        return {"steps": steps, "samples": samples,
                "wall_s": round(wall, 3),
                "steps_per_sec": round(steps / wall, 2),
                "samples_per_sec": round(samples / wall, 2),
                "real_token_fraction": snap["real_token_fraction"]}

    best = {}
    for rnd in range(rounds + 1):             # interleaved best-of
        for mode in ("padded", "packed"):
            r = run(mode)
            if rnd == 0:
                continue                      # warmup: compiles settle
            if mode not in best or r["samples_per_sec"] \
                    > best[mode]["samples_per_sec"]:
                best[mode] = r
    out = {
        "samples": n_samples, "batch_rows": batch, "bucket": bucket,
        "length_mix": "85%% U[4,12], 15%% U[32,%d]" % bucket,
        "padded": best["padded"], "packed": best["packed"],
        "samples_per_sec_speedup": round(
            best["packed"]["samples_per_sec"]
            / best["padded"]["samples_per_sec"], 3),
        "real_token_fraction_ratio": round(
            best["packed"]["real_token_fraction"]
            / best["padded"]["real_token_fraction"], 3),
        # the acceptance claim: over the SAME stream, packed training
        # progresses faster than padded — a packed step carries ~3x
        # the samples of a padded step of the identical row shape.
        # Raw per-row-batch steps/sec is also reported; packed pays
        # the layout gather + its scatter backward there, a fixed
        # host-scale cost the model FLOPs dwarf off-CPU.
        "oracle_packed_throughput_ge_padded": bool(
            best["packed"]["samples_per_sec"]
            >= best["padded"]["samples_per_sec"]),
    }
    return out


def _packing_record():
    """The sequence-packing benchmark record (BENCH_r16.json, packing
    half): padded vs FFD-packed training on a skewed ragged mix —
    steps/sec, samples/sec, real-token fraction. CPU backend."""
    record = {"bench": "packing", "platform": "cpu"}
    try:
        record.update(_bench_packing_case())
    except Exception as exc:                     # noqa: BLE001
        record["errors"] = {"packing": _err_str(exc)}
    return record


def _trace_overhead_record():
    """The trace/metrics-overhead benchmark record (BENCH_r15.json).
    CPU-friendly — runs wherever the tier-1 suite runs."""
    import jax
    record = {"metric": "trace_overhead", "unit": "steps/s",
              "dtype": "float32", "platform": jax.default_backend(),
              "noise_note": "CPU CI box; the documented ~±40% "
              "host-load noise band (BENCH_r09) applies to every "
              "figure here — per-mode deltas inside it (including "
              "negative 'overheads') are noise. The acceptance "
              "oracles are the OFF path vs the BENCH_r13/r14-era "
              "figures and metrics_agree_with_stats.",
              "cases": {}}

    def clean_slate():
        # a mid-case failure (scrape timeout, serving error) must not
        # leak an active run/tracer/watchdog/endpoint into the next
        # case's OFF rounds — that would silently put on-path cost
        # into the off figures the acceptance bar reads
        from mxnet_tpu import livemetrics, telemetry, tracing
        telemetry.reset()
        tracing.reset()
        livemetrics.disable_watchdog()
        livemetrics.stop_server()

    errors = {}
    try:
        record["cases"]["mlp"] = _bench_trace_overhead_mlp()
    except Exception as exc:                     # noqa: BLE001
        errors["mlp"] = _err_str(exc)
    finally:
        clean_slate()
    try:
        record["cases"]["serving"] = _bench_trace_overhead_serving()
    except Exception as exc:                     # noqa: BLE001
        errors["serving"] = _err_str(exc)
    finally:
        clean_slate()
    if errors:
        record["errors"] = errors
    return record


def _bench_decode_case(n_requests=24, max_new=16, window=8):
    """Mixed prefill/decode load over the stateful autoregressive
    server (BENCH_r17): one request population (varied prompt lengths
    4..63, greedy generation) served two ways on the same toy decoder
    LM —

    - ``sequential``: prefill-then-decode one request at a time
      (window=1, submit-and-wait) — the naive serving loop;
    - ``continuous``: all requests offered at once to the continuous
      batcher (window=8): prefills interleave with batched decode
      steps, so every decode dispatch amortizes across up to 8
      requests.

    Captures tokens/sec and the p99 inter-token latency under the
    mixed load, plus the fixed-program-set oracle
    (``compile_watch.site_stats``): the continuous server's site set
    is exactly 1 + len(ladder) programs with zero steady-state
    recompiles."""
    import numpy as np
    from mxnet_tpu import compile_watch
    from mxnet_tpu.serving import DecodeServer, ToyDecoderLM

    compile_watch.enable()
    model = ToyDecoderLM(vocab=128, n_layers=2, n_heads=4, head_dim=16,
                         max_len=256)
    params = model.init_params(seed=0)
    rs = np.random.RandomState(0)
    prompts = [rs.randint(1, 128, size=int(n))
               for n in rs.randint(4, 64, size=n_requests)]

    def build(name, win):
        srv = DecodeServer(model, params, seq_ladder=[16, 32, 64],
                           max_new_tokens=max_new, window=win,
                           page_size=16, pool_pages=256,
                           max_queue=n_requests + 4, name=name)
        srv.warmup()
        return srv

    out = {"requests": n_requests, "max_new_tokens": max_new,
           "prompt_lengths": sorted({len(p) for p in prompts})}

    # sequential prefill-then-decode: one request runs to completion
    # before the next starts — every decode dispatch serves ONE token
    srv = build("seq", 1)
    t0 = time.perf_counter()
    for p in prompts:
        srv.submit(p, max_new_tokens=max_new).result(timeout=600)
    seq_wall = time.perf_counter() - t0
    seq_st = srv.stats()
    srv.stop()
    out["sequential"] = {
        "wall_s": round(seq_wall, 3),
        "tokens_per_sec": round(seq_st["tokens_out"] / seq_wall, 2),
        "decode_steps": seq_st["decode_steps"],
        "inter_token_p99_ms": (seq_st.get("inter_token_ms")
                               or {}).get("p99"),
    }

    # continuous batching: the whole population offered at once
    srv = build("cont", window)
    warm = compile_watch.site_stats("decode:cont")
    t0 = time.perf_counter()
    reqs = [srv.submit(p, max_new_tokens=max_new) for p in prompts]
    for r in reqs:
        r.result(timeout=600)
    cont_wall = time.perf_counter() - t0
    cont_st = srv.stats()
    steady = compile_watch.site_stats("decode:cont")
    srv.stop()
    out["continuous"] = {
        "wall_s": round(cont_wall, 3),
        "window": window,
        "tokens_per_sec": round(cont_st["tokens_out"] / cont_wall, 2),
        "decode_steps": cont_st["decode_steps"],
        "prefill_fraction": cont_st.get("prefill_fraction"),
        "inter_token_p50_ms": (cont_st.get("inter_token_ms")
                               or {}).get("p50"),
        "inter_token_p99_ms": (cont_st.get("inter_token_ms")
                               or {}).get("p99"),
        "ttft_p50_ms": (cont_st.get("ttft_ms") or {}).get("p50"),
        "kv_peak_pages": cont_st["kv"]["peak_used"],
    }
    out["speedup_tokens_per_sec"] = round(
        out["continuous"]["tokens_per_sec"]
        / out["sequential"]["tokens_per_sec"], 3)
    out["continuous_beats_sequential"] = bool(
        out["continuous"]["tokens_per_sec"]
        > out["sequential"]["tokens_per_sec"])
    out["programs"] = {site: s["count"] for site, s in
                       sorted((steady or {}).items())}
    out["zero_steady_state_recompiles"] = bool(steady == warm)
    compile_watch.disable()
    return out


def _decode_record():
    """The autoregressive-serving benchmark record (BENCH_r17.json):
    sequential prefill-then-decode vs continuous batching on a mixed
    prompt-length population — tokens/sec, p99 inter-token latency,
    fixed program set. CPU backend."""
    record = {"bench": "decode_serving", "platform": "cpu"}
    try:
        record.update(_bench_decode_case())
    except Exception as exc:                     # noqa: BLE001
        record["errors"] = {"decode": _err_str(exc)}
    return record


def _bench_router_case(n_flood=18, n_light=6, max_new=12):
    """Fleet-serving failover drill (BENCH_r19): a Router over FOUR
    live decode replicas under a skewed two-tenant load (``flood``
    offers 3x the sessions of ``light``; light carries a 2x WFQ
    weight), with one replica KILLED abruptly mid-run. Captures
    aggregate tokens/sec, per-tenant p99 session latency and the
    fairness ratio, the failover detection-to-resume latency, and the
    failed-stream count — which must be ZERO: every orphaned stream is
    re-homed by re-prefill replay and finishes token-complete."""
    import numpy as np
    from mxnet_tpu.serving import DecodeServer, Router, ToyDecoderLM

    model = ToyDecoderLM(vocab=128, n_layers=2, n_heads=4, head_dim=16,
                         max_len=256)
    params = model.init_params(seed=0)
    rs = np.random.RandomState(0)

    def replica(i):
        srv = DecodeServer(model, params, seq_ladder=[32, 64],
                           max_new_tokens=max_new, window=8,
                           page_size=16, pool_pages=256,
                           max_queue=n_flood + n_light,
                           name="replica-%d" % i,
                           device=_replica_device(i))
        srv.warmup()
        return srv

    router = Router([replica(i) for i in range(4)],
                    name="bench-fleet", probe_interval_ms=10,
                    max_inflight=8,
                    tenants={"light": {"weight": 2.0},
                             "flood": {"weight": 1.0}})
    out = {"replicas": 4, "max_new_tokens": max_new,
           "load": {"flood": n_flood, "light": n_light}}
    try:
        t0 = time.perf_counter()
        reqs = []
        for i in range(n_flood + n_light):
            tenant = "light" if i % 4 == 3 else "flood"
            p = rs.randint(1, 128, size=int(rs.randint(4, 28)))
            reqs.append(router.submit(p, max_new_tokens=max_new,
                                      tenant=tenant))
        # let streams get going, then kill one replica that owns work
        deadline = time.monotonic() + 30
        while time.monotonic() < deadline:
            bound = [q._replica for q in reqs
                     if q._replica is not None and q.emitted]
            if bound:
                break
            time.sleep(0.002)
        victim = bound[0]
        orphans = sum(1 for q in reqs if q._replica is victim)
        t_kill = time.perf_counter()
        victim.kill()
        failed = 0
        for q in reqs:
            try:
                q.result(timeout=120)
            except Exception:               # noqa: BLE001
                failed += 1
        wall = time.perf_counter() - t0
        st = router.stats()
        tokens = sum(len(q.emitted) for q in reqs)
        lat = {t: (st["tenants"][t].get("latency_ms") or {})
               for t in ("flood", "light")}
        out.update({
            "wall_s": round(wall, 3),
            "kill_at_s": round(t_kill - t0, 3),
            "killed_replica": victim.name,
            "orphaned_sessions": orphans,
            "failed_streams": failed,            # MUST be 0
            "zero_failed_streams": failed == 0,
            "completed": st["completed"],
            "failovers": st["failovers"],
            "replay_tokens": st["replay_tokens"],
            "tokens_per_sec": round(tokens / wall, 2),
            "detect_to_resume_ms": st.get("failover_resume_ms"),
            "tenant_p99_ms": {t: lat[t].get("p99") for t in lat},
            "throttles": st["throttles"],
        })
        if lat["flood"].get("p99") and lat["light"].get("p99"):
            # >1 means the weighted light tenant beat the flood
            out["fairness_p99_ratio"] = round(
                lat["flood"]["p99"] / lat["light"]["p99"], 3)
    finally:
        router.stop()
    return out


def _router_record():
    """The fleet-serving benchmark record (BENCH_r19.json): 4-replica
    router under skewed two-tenant load with one replica killed
    mid-run — zero failed streams, detection-to-resume latency,
    per-tenant fairness. CPU backend."""
    record = {"bench": "router_fleet", "platform": "cpu"}
    try:
        record.update(_bench_router_case())
    except Exception as exc:                     # noqa: BLE001
        record["errors"] = {"router": _err_str(exc)}
    return record


def _fleet_obs_run(n_sessions=16, max_new=12, armed=False, kill=False,
                   workdir=None, record_every=None):
    """One 2-replica routed load, optionally with the whole
    observability stack armed (tracing ring + telemetry sink + flight
    recorder) and optionally with one replica killed mid-run."""
    import numpy as np
    from mxnet_tpu import flightrec, telemetry, tracing
    from mxnet_tpu.serving import DecodeServer, Router, ToyDecoderLM

    model = ToyDecoderLM(vocab=128, n_layers=2, n_heads=4, head_dim=16,
                         max_len=256)
    params = model.init_params(seed=0)
    rs = np.random.RandomState(0)

    def replica(i):
        srv = DecodeServer(model, params, seq_ladder=[32, 64],
                           max_new_tokens=max_new, window=8,
                           page_size=16, pool_pages=256,
                           max_queue=n_sessions,
                           record_every=record_every,
                           name="rep-%d" % i,
                           device=_replica_device(i))
        srv.warmup()
        return srv

    if armed:
        tracing.enable()
        flightrec.enable(workdir)
        telemetry.start(os.path.join(workdir, "telem.jsonl"),
                        run_id="bench-fleet-obs")
    router = Router([replica(i) for i in range(2)],
                    name="obs-fleet", probe_interval_ms=10,
                    max_inflight=8,
                    tenants={"light": {"weight": 2.0},
                             "flood": {"weight": 1.0}})
    out = {}
    try:
        t0 = time.perf_counter()
        reqs = []
        for i in range(n_sessions):
            tenant = "light" if i % 4 == 3 else "flood"
            p = rs.randint(1, 128, size=int(rs.randint(4, 28)))
            reqs.append(router.submit(p, max_new_tokens=max_new,
                                      tenant=tenant))
        if kill:
            deadline = time.monotonic() + 30
            bound = []
            while time.monotonic() < deadline:
                bound = [q._replica for q in reqs
                         if q._replica is not None and q.emitted]
                if bound:
                    break
                time.sleep(0.002)
            bound[0].kill()
        failed = 0
        for q in reqs:
            try:
                q.result(timeout=120)
            except Exception:                    # noqa: BLE001
                failed += 1
        wall = time.perf_counter() - t0
        tokens = sum(len(q.emitted) for q in reqs)
        out = {"wall_s": round(wall, 3),
               "tokens_per_sec": round(tokens / wall, 2),
               "failed_streams": failed,
               "stats": router.stats()}
    finally:
        router.stop()
        if armed:
            out["trace_events"] = (tracing.stats() or {}).get("events")
            telemetry.stop()
            out["flightrec"] = flightrec.disable()
            tracing.disable()
    return out


def _bench_fleet_obs_case(n_sessions=16, max_new=12):
    """Fleet-observability drill (BENCH_r21): the SAME 2-replica
    routed load with the observability stack off vs fully armed
    (per-request spans + telemetry sink + flight recorder) — the armed
    cost must sit inside the CPU noise band — then one armed
    replica_lost drill: the kill must leave exactly ONE flight-recorder
    bundle whose router snapshot reconciles with the live failover
    counters."""
    import shutil
    import tempfile
    from mxnet_tpu import flightrec

    d_on = tempfile.mkdtemp(prefix="bench-obs-on-")
    d_drill = tempfile.mkdtemp(prefix="bench-obs-drill-")
    try:
        # best-of-3 per mode: the runs are ~0.1 s of wall, so one
        # scheduler hiccup (or the first armed run's module warm-up)
        # dominates a single sample
        off = max((_fleet_obs_run(n_sessions, max_new)
                   for _ in range(3)),
                  key=lambda r: r["tokens_per_sec"])
        on = max((_fleet_obs_run(n_sessions, max_new, armed=True,
                                 workdir=d_on)
                  for _ in range(3)),
                 key=lambda r: r["tokens_per_sec"])
        # record_every=1 so the victim's last cumulative counts land
        # in the sink/bundle before the kill; the load-comparison runs
        # above use the default cadence
        drill = _fleet_obs_run(n_sessions, max_new, armed=True,
                               kill=True, workdir=d_drill,
                               record_every=1)
        bundles = flightrec.list_bundles(d_drill)
        st = drill["stats"]
        bundle = {}
        if len(bundles) == 1:
            b = flightrec.read_bundle(bundles[0])
            rec = (b.get("router") or {}).get("obs-fleet") or {}
            bundle = {
                "reason": b.get("reason"),
                "alert_kind": (b.get("alert") or {}).get("kind"),
                "router_replicas_lost": rec.get("replicas_lost"),
                "router_failovers": rec.get("failovers"),
            }
        overhead = 100.0 * (off["tokens_per_sec"] / on["tokens_per_sec"]
                            - 1.0) if on["tokens_per_sec"] else None
        return {
            "replicas": 2, "sessions": n_sessions,
            "max_new_tokens": max_new,
            "noise_note": "CPU CI box; the documented ~±40% "
                          "host-load noise band (BENCH_r09) applies — "
                          "armed-vs-off deltas inside it are noise. "
                          "The acceptance oracle is the drill: exactly "
                          "one bundle, counters reconciled.",
            "off_tokens_per_sec": off["tokens_per_sec"],
            "armed_tokens_per_sec": on["tokens_per_sec"],
            "armed_overhead_pct": round(overhead, 2),
            "within_noise_band": abs(overhead) <= 40.0,
            "armed_trace_events": on["trace_events"],
            "drill": {
                "failed_streams": drill["failed_streams"],
                "zero_failed_streams": drill["failed_streams"] == 0,
                "replicas_lost": st["replicas_lost"],
                "failovers": st["failovers"],
                "replay_tokens": st["replay_tokens"],
                "bundles": len(bundles),
                "exactly_one_bundle": len(bundles) == 1,
                "bundle": bundle,
                # the bundle snapshots the router AT the alert edge —
                # before re-homing — so its failovers field is the
                # pre-recovery value; the reconciliation invariant is
                # one bundle per lost replica with the loss recorded
                "counters_reconciled": (
                    len(bundles) == st["replicas_lost"] == 1
                    and bundle.get("alert_kind") == "replica_lost"
                    and bundle.get("router_replicas_lost") == 1),
            },
        }
    finally:
        shutil.rmtree(d_on, ignore_errors=True)
        shutil.rmtree(d_drill, ignore_errors=True)


def _fleet_obs_record():
    """The fleet-observability benchmark record (BENCH_r21.json):
    2-replica routed load armed vs off, plus one injected replica_lost
    drill — exactly one flight-recorder bundle reconciling with the
    router's failover counters. CPU backend."""
    record = {"bench": "fleet_obs", "platform": "cpu"}
    try:
        record.update(_bench_fleet_obs_case())
    except Exception as exc:                     # noqa: BLE001
        record["errors"] = {"fleet_obs": _err_str(exc)}
    return record


def _metering_run(n_sessions=16, max_new=12, metered=False,
                  ledger=None, kill=False):
    """One 2-replica routed two-tenant load, optionally with the
    usage meter installed and optionally with one replica killed
    mid-run (the exactly-once replay-billing drill)."""
    import numpy as np
    from mxnet_tpu import metering
    from mxnet_tpu.serving import DecodeServer, Router, ToyDecoderLM

    model = ToyDecoderLM(vocab=128, n_layers=2, n_heads=4, head_dim=16,
                         max_len=256)
    params = model.init_params(seed=0)
    rs = np.random.RandomState(0)

    def replica(i):
        srv = DecodeServer(model, params, seq_ladder=[32, 64],
                           max_new_tokens=max_new, window=8,
                           page_size=16, pool_pages=256,
                           max_queue=n_sessions, name="rep-%d" % i,
                           device=_replica_device(i))
        srv.warmup()
        return srv

    if metered:
        metering.start(name="bench-fleet", path=ledger)
    router = Router([replica(i) for i in range(2)],
                    name="meter-fleet", probe_interval_ms=10,
                    max_inflight=8,
                    tenants={"light": {"weight": 2.0},
                             "flood": {"weight": 1.0}})
    out = {}
    try:
        t0 = time.perf_counter()
        reqs = []
        for i in range(n_sessions):
            tenant = "light" if i % 4 == 3 else "flood"
            p = rs.randint(1, 128, size=int(rs.randint(4, 28)))
            reqs.append(router.submit(p, max_new_tokens=max_new,
                                      tenant=tenant))
        if kill:
            deadline = time.monotonic() + 30
            bound = []
            while time.monotonic() < deadline:
                bound = [q._replica for q in reqs
                         if q._replica is not None and q.emitted]
                if bound:
                    break
                time.sleep(0.002)
            bound[0].kill()
        failed = 0
        for q in reqs:
            try:
                q.result(timeout=120)
            except Exception:                    # noqa: BLE001
                failed += 1
        wall = time.perf_counter() - t0
        tokens = sum(len(q.emitted) for q in reqs)
        out = {"wall_s": round(wall, 3),
               "tokens_per_sec": round(tokens / wall, 2),
               "failed_streams": failed,
               "stats": router.stats()}
    finally:
        router.stop()
        if metered:
            out["meter"] = metering.stop()
    return out


def _bench_metering_case(n_sessions=16, max_new=12):
    """Usage-metering drill (BENCH_r23): the SAME 2-replica skewed
    two-tenant load metered off vs on — the metered cost must sit
    inside the CPU noise band — then one metered replica-kill drill
    whose ledger must reconcile: dual-entry books [OK], meter replay
    tokens exactly the router's (billed once), every session billed."""
    import shutil
    import tempfile

    d = tempfile.mkdtemp(prefix="bench-meter-")
    try:
        # best-of-3 per mode: ~0.1 s runs, one scheduler hiccup
        # dominates a single sample (same protocol as BENCH_r21)
        off = max((_metering_run(n_sessions, max_new)
                   for _ in range(3)),
                  key=lambda r: r["tokens_per_sec"])
        on = max((_metering_run(n_sessions, max_new, metered=True,
                                ledger=os.path.join(d, "l%d.jsonl" % i))
                  for i in range(3)),
                 key=lambda r: r["tokens_per_sec"])
        drill = _metering_run(n_sessions, max_new, metered=True,
                              ledger=os.path.join(d, "drill.jsonl"),
                              kill=True)
        st = drill["stats"]
        snap = drill["meter"]
        reconciled = (
            snap["reconcile"]["ok"]
            and snap["admitted"] == st["requests"]
            and snap["totals"]["replay_tokens"] == st["replay_tokens"]
            and snap["totals"]["failovers"] == st["failovers"]
            and snap["closed"] == snap["admitted"])
        overhead = 100.0 * (off["tokens_per_sec"] / on["tokens_per_sec"]
                            - 1.0) if on["tokens_per_sec"] else None
        return {
            "replicas": 2, "sessions": n_sessions,
            "max_new_tokens": max_new,
            "noise_note": "CPU CI box; the documented ~±40% "
                          "host-load noise band (BENCH_r09) applies — "
                          "metered-vs-off deltas inside it are noise. "
                          "The acceptance oracle is the drill: the "
                          "ledger reconciles through a replica kill.",
            "off_tokens_per_sec": off["tokens_per_sec"],
            "metered_tokens_per_sec": on["tokens_per_sec"],
            "metered_overhead_pct": round(overhead, 2),
            "within_noise_band": abs(overhead) <= 40.0,
            "drill": {
                "failed_streams": drill["failed_streams"],
                "zero_failed_streams": drill["failed_streams"] == 0,
                "replicas_lost": st["replicas_lost"],
                "failovers": st["failovers"],
                "router_replay_tokens": st["replay_tokens"],
                "meter_replay_tokens":
                    snap["totals"]["replay_tokens"],
                "billed_sessions": snap["closed"],
                "tenants": {
                    name: {"prompt_tokens": t["prompt_tokens"],
                           "generated_tokens": t["generated_tokens"],
                           "flops": t["flops"],
                           "page_seconds": t["page_seconds"]}
                    for name, t in snap["tenants"].items()},
                "reconcile_checks": snap["reconcile"]["checks"],
                "ledger_reconciled": reconciled,
            },
        }
    finally:
        shutil.rmtree(d, ignore_errors=True)


def _metering_record():
    """The usage-metering benchmark record (BENCH_r23.json):
    2-replica skewed two-tenant routed load metered off vs on, plus
    one metered replica-kill drill whose per-tenant ledger must
    reconcile against the router's counters. CPU backend."""
    record = {"bench": "metering", "platform": "cpu"}
    try:
        record.update(_bench_metering_case())
    except Exception as exc:                     # noqa: BLE001
        record["errors"] = {"metering": _err_str(exc)}
    return record


_MULTIHOST_WORKER = r'''
import os, sys, time
_rank = int(os.environ.get("DMLC_WORKER_ID", "0"))
_gen = int(os.environ.get("MXNET_LAUNCH_RESTART", "0") or 0)
_fault = os.environ.get("BENCH_FAULT_STEP", "")
if _fault and _rank == 1 and _gen == 0:
    os.environ["MXNET_FAULT_PLAN"] = "proc_exit:step=%s:raise" % _fault
import numpy as np
import mxnet_tpu as mx
from mxnet_tpu import gluon, envs
from mxnet_tpu.gluon import nn
from mxnet_tpu.parallel import mesh as mesh_mod, distributed
from mxnet_tpu.parallel.data_parallel import DistributedTrainer

steps = int(os.environ.get("BENCH_STEPS", "30"))
prefix = os.environ.get("BENCH_CKPT_PREFIX", "")
if "DMLC_WORKER_ID" in os.environ:
    kv = mx.kv.create("tpu_sync")
    rank, world = kv.rank, kv.num_workers
else:
    rank, world = 0, 1
devs = distributed.global_devices()
mesh = mesh_mod.create_mesh({"dp": len(devs)}, devices=devs)
np.random.seed(3)
net = nn.HybridSequential()
net.add(nn.Dense(256, activation="relu"), nn.Dense(64))
net.initialize(mx.init.Xavier(rnd_type="gaussian"))
mx.random.seed(7)
tr = DistributedTrainer(net, gluon.loss.SoftmaxCrossEntropyLoss(),
                        mesh, optimizer="sgd", learning_rate=0.05)
resume = envs.get_int("MXNET_LAUNCH_RESUME_EPOCH")
if prefix and resume is not None:
    tr.load_checkpoint(prefix, resume)
B = 64
rng = np.random.RandomState(11)
data = rng.randn(B, 32).astype(np.float32)
lab = rng.randint(0, 64, size=(B,)).astype(np.float32)
lo = rank * (B // world); hi = (rank + 1) * (B // world)
d, l = mx.nd.array(data[lo:hi]), mx.nd.array(lab[lo:hi])
tr.fit_batch(d, l).asnumpy()          # compile + settle
if prefix and resume is None:
    # a manifest BEFORE the injected death so the supervised restart
    # has a real resume point
    tr.save_checkpoint(prefix, 0)
t0 = time.perf_counter()
for _ in range(steps):
    tr.fit_batch(d, l).asnumpy()
dt = time.perf_counter() - t0
if prefix:
    tr.save_checkpoint(prefix, 1)
if rank == 0:
    print("BENCH_STEPS_PER_SEC %.3f" % (steps / dt), flush=True)
'''


def _multihost_record():
    """The multi-host benchmark record (BENCH_r18.json): steps/sec of
    the identical model/batch on a 1-process 8-device mesh vs a
    2-process 4-device-each launched job (the coordination-service DCN
    leg's cost made visible), plus the supervised launcher's
    detection-to-restart wall time for one injected host loss
    (proc_exit fault on rank 1, restart-the-world, resume from the
    last good manifest epoch)."""
    import re
    import subprocess
    import sys as _sys
    import tempfile

    # every leg is a child process pinned to the CPU backend: this
    # mode never measures a chip
    record = {"bench": "multihost", "platform": "cpu", "steps": 30}
    tmp = tempfile.mkdtemp(prefix="mxbench-mh-")
    worker = os.path.join(tmp, "worker.py")
    with open(worker, "w") as f:
        f.write(_MULTIHOST_WORKER)

    repo = os.path.dirname(os.path.abspath(__file__))

    def env_for(n_devices, **extra):
        env = dict(os.environ)
        env["PYTHONPATH"] = repo + os.pathsep + env.get("PYTHONPATH",
                                                        "")
        env["JAX_PLATFORMS"] = "cpu"
        env["XLA_FLAGS"] = \
            "--xla_force_host_platform_device_count=%d" % n_devices
        env["BENCH_STEPS"] = str(record["steps"])
        env.pop("MXNET_FAULT_PLAN", None)
        env.update({k: str(v) for k, v in extra.items()})
        return env

    def parse_sps(out):
        m = re.search(r"BENCH_STEPS_PER_SEC ([0-9.]+)", out)
        return float(m.group(1)) if m else None

    try:
        r1 = subprocess.run([_sys.executable, worker],
                            env=env_for(8), capture_output=True,
                            text=True, timeout=600)
        record["single_proc_8dev_steps_per_sec"] = parse_sps(r1.stdout)
    except Exception as exc:                    # noqa: BLE001
        record["single_proc_error"] = _err_str(exc)
    try:
        r2 = subprocess.run(
            [_sys.executable, "-m", "mxnet_tpu.tools.launch", "-n",
             "2", _sys.executable, worker],
            env=env_for(4, JAX_NUM_CPU_DEVICES=4),
            capture_output=True, text=True, timeout=600)
        record["two_proc_2x4_steps_per_sec"] = parse_sps(r2.stdout)
    except Exception as exc:                    # noqa: BLE001
        record["two_proc_error"] = _err_str(exc)
    one = record.get("single_proc_8dev_steps_per_sec")
    two = record.get("two_proc_2x4_steps_per_sec")
    if one and two:
        record["two_proc_vs_single_ratio"] = round(two / one, 3)
    record["note"] = (
        "two-proc runs the coordination-service DCN leg (CPU backend "
        "cannot span processes in one XLA program): every step pays "
        "a host gRPC exchange, so the ratio is a latency floor, not "
        "a scaling claim — real pods keep the exchange in-program "
        "over the global mesh; the trajectory is bit-identical "
        "either way (tests/test_multihost.py)")

    # supervised host loss: detection + restart timings from the
    # launcher's events file
    events = os.path.join(tmp, "events.jsonl")
    prefix = os.path.join(tmp, "ck")
    try:
        r3 = subprocess.run(
            [_sys.executable, "-m", "mxnet_tpu.tools.launch", "-n",
             "2", "--supervise", "--resume-prefix", prefix,
             "--events-file", events, _sys.executable, worker],
            env=env_for(4, JAX_NUM_CPU_DEVICES=4,
                        BENCH_FAULT_STEP=10, BENCH_CKPT_PREFIX=prefix,
                        MXNET_HB_TIMEOUT_MS=2000,
                        MXNET_LAUNCH_BACKOFF="0.2",
                        MXNET_LAUNCH_GRACE=3),
            capture_output=True, text=True, timeout=900)
        recs = [json.loads(line) for line in open(events)]
        by_kind = {}
        for rec in recs:
            by_kind.setdefault(rec["kind"], []).append(rec)
        fail = (by_kind.get("worker_failed") or [None])[0]
        relaunch = [r for r in by_kind.get("launch", [])
                    if r["attempt"] > 0]
        restart = {"supervised_exit": r3.returncode,
                   "restarts": len(relaunch)}
        if fail is not None:
            # detect_s includes the doomed attempt's startup; the
            # fault fires at step 10, so detection proper is the tail
            restart["attempt_start_to_detect_s"] = fail["detect_s"]
            restart["failed_rank"] = fail["rank"]
            restart["exit_code"] = fail["code"]
        if fail is not None and relaunch:
            restart["detect_to_relaunch_s"] = round(
                relaunch[0]["t"] - fail["t"], 3)
            restart["resume_epoch"] = relaunch[0].get("resume_epoch")
        record["host_loss"] = restart
    except Exception as exc:                    # noqa: BLE001
        record["host_loss_error"] = _err_str(exc)
    return record


def _bench_amp_case(steps=40, warmup=5, rounds=3, batch=64,
                    in_units=256, hidden=1024, classes=10):
    """bf16 AMP vs plain fp32 through the SAME gluon-Trainer fused
    step (BENCH_r20, training half): a 3-layer MLP trained with the
    multi-precision fused step — bf16 resident weights + fp32 masters
    and in-program loss scaling — against the fp32 baseline. Rounds
    are interleaved so host-load noise hits both modes symmetrically.

    The acceptance surface is NOT a CPU speedup claim (host XLA often
    emulates bf16 matmuls): it is zero ``fused_step_fallbacks``, ONE
    trace for the whole run (loss scale rides the traced scalar
    block), and the resident-weight byte split — bf16 weights are half
    the fp32 footprint, which is the number a TPU capacity plan is
    built on."""
    import numpy as np_
    import mxnet_tpu as mx
    from mxnet_tpu import autograd, fault, gluon, profiler
    from mxnet_tpu.amp import DtypePolicy

    prior = os.environ.get("MXNET_FUSED_STEP")
    os.environ["MXNET_FUSED_STEP"] = "1"
    try:
        rng = np_.random.RandomState(0)
        x = rng.uniform(-1, 1, (batch, in_units)).astype(np_.float32)
        y = rng.randint(0, classes, (batch,)).astype(np_.float32)
        loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()

        def build(policy):
            net = gluon.nn.HybridSequential()
            net.add(gluon.nn.Dense(hidden, activation="relu",
                                   in_units=in_units))
            net.add(gluon.nn.Dense(hidden, activation="relu",
                                   in_units=hidden))
            net.add(gluon.nn.Dense(classes, in_units=hidden))
            net.initialize(mx.init.Xavier())
            if policy is not None:
                policy.apply(net)
            net.hybridize()
            trainer = gluon.Trainer(
                net.collect_params(), "sgd",
                {"learning_rate": 0.05, "momentum": 0.9,
                 "multi_precision": policy is not None})
            xb = mx.nd.array(x)
            if policy is not None:
                xb = xb.astype("bfloat16")
            yb = mx.nd.array(y)

            def step():
                with autograd.record():
                    out = net(xb)
                    loss = loss_fn(out.astype("float32"), yb)
                loss.backward()
                trainer.step(batch)
                return loss
            return net, trainer, step

        fb_before = profiler.counters().get("fused_step_fallbacks", 0)
        built = {}
        for mode, pol in (("fp32", None),
                          ("bf16", DtypePolicy("bfloat16"))):
            net, trainer, step = build(pol)
            for _ in range(warmup):
                loss = step()
            loss.asnumpy()
            built[mode] = (net, trainer, step)

        best = {"fp32": 0.0, "bf16": 0.0}
        for _ in range(rounds):
            for mode in ("fp32", "bf16"):
                _, _, step = built[mode]
                t0 = time.perf_counter()
                for _ in range(steps):
                    loss = step()
                loss.asnumpy()
                dt = time.perf_counter() - t0
                best[mode] = max(best[mode], steps / dt)

        width = {"float64": 8, "float32": 4, "bfloat16": 2,
                 "float16": 2}

        def weight_bytes(net):
            tot = 0
            for p in net.collect_params().values():
                d = p.data()
                tot += int(np_.prod(d.shape)) \
                    * width.get(str(d.dtype), 4)
            return tot

        fused = built["bf16"][1]._fused_updater
        assert fused is not None, "mp fused path did not run"
        b_fp32 = weight_bytes(built["fp32"][0])
        b_bf16 = weight_bytes(built["bf16"][0])
        return {
            "net": "mlp %d-%d-%d-%d" % (in_units, hidden, hidden,
                                        classes),
            "batch": batch,
            "optimizer": "sgd_momentum_mp",
            "fp32_steps_per_sec": round(best["fp32"], 2),
            "bf16_steps_per_sec": round(best["bf16"], 2),
            "bf16_vs_fp32": round(best["bf16"] / best["fp32"], 3),
            "fused_step_fallbacks":
                profiler.counters().get("fused_step_fallbacks", 0)
                - fb_before,
            "bf16_traces": fused._trace_count,
            "bf16_dispatches": fused.dispatch_count,
            "loss_scale_final": float(fault.loss_scale()),
            "resident_weight_bytes_fp32": b_fp32,
            "resident_weight_bytes_bf16": b_bf16,
            "weight_bytes_ratio": round(b_fp32 / b_bf16, 3),
        }
    finally:
        if prior is None:
            os.environ.pop("MXNET_FUSED_STEP", None)
        else:
            os.environ["MXNET_FUSED_STEP"] = prior


def _amp_record():
    """The AMP-training benchmark record (BENCH_r20.json, training
    half): bf16 multi-precision fused step vs fp32 on the same MLP —
    steps/sec, zero-fallback/one-trace oracle, resident weight
    bytes. CPU backend."""
    record = {"bench": "amp_fused_step", "platform": "cpu"}
    try:
        record.update(_bench_amp_case())
    except Exception as exc:                     # noqa: BLE001
        record["errors"] = {"amp": _err_str(exc)}
    return record


def _bench_int8_kv_case(fp32_pages=13, page_size=16, prompt_len=30,
                        max_new=18):
    """Decode stream capacity at a FIXED KV-pool byte budget
    (BENCH_r20, serving half): the byte budget is what a fp32 pool of
    ``fp32_pages`` pages costs; the int8 pool (``MXNET_KV_DTYPE=int8``,
    per-page fp32 scales riding along) buys ~4x the pages for the same
    bytes, so ~4x the concurrent streams. Each mode runs its full
    analytic capacity — every stream live at once (window == capacity,
    max_new > admission ramp) — and must finish with ZERO preemptions,
    ZERO alloc failures, and the fixed-program oracle unchanged from
    warmup (``compile_watch.site_stats``): quantize/dequantize live
    INSIDE the compiled programs, so int8 adds no program-set or
    steady-state-recompile cost."""
    import numpy as np
    from mxnet_tpu import compile_watch
    from mxnet_tpu.serving import DecodeServer, ToyDecoderLM

    compile_watch.enable()
    n_layers, n_heads, head_dim = 2, 4, 16
    model = ToyDecoderLM(vocab=128, n_layers=n_layers, n_heads=n_heads,
                         head_dim=head_dim, max_len=128)
    params = model.init_params(seed=0)

    # pool byte budget, from the array shapes kvcache.py allocates:
    # k + v planes of (L, P, S, H, D), plus (L, P) fp32 scale planes
    # for the quantized pool
    plane = n_layers * page_size * n_heads * head_dim * 2     # k + v
    budget = fp32_pages * plane * 4
    int8_page = plane + n_layers * 2 * 4          # int8 body + scales
    int8_pages = budget // int8_page
    pages_per_stream = -(-(prompt_len + max_new) // page_size)

    def run(dtype, pool_pages):
        prior = os.environ.get("MXNET_KV_DTYPE")
        os.environ["MXNET_KV_DTYPE"] = dtype
        try:
            cap = (pool_pages - 1) // pages_per_stream
            name = "kv_" + dtype
            srv = DecodeServer(model, params, seq_ladder=[32],
                               max_new_tokens=max_new, window=cap,
                               page_size=page_size,
                               pool_pages=pool_pages,
                               max_queue=cap + 4, name=name)
            srv.warmup()
            warm = compile_watch.site_stats("decode:" + name)
            rs = np.random.RandomState(7)
            t0 = time.perf_counter()
            reqs = [srv.submit(rs.randint(1, 128, size=prompt_len),
                               max_new_tokens=max_new)
                    for _ in range(cap)]
            for r in reqs:
                r.result(timeout=600)
            wall = time.perf_counter() - t0
            st = srv.stats()
            steady = compile_watch.site_stats("decode:" + name)
            srv.stop()
            return {
                "pool_pages": pool_pages,
                "pool_bytes": pool_pages
                * (plane * 4 if dtype == "float32" else int8_page),
                "kv_dtype": st["kv"]["dtype"],
                "max_concurrent_streams": cap,
                "completed": st["completed"],
                "preempted": st["preempted"],
                "alloc_failures": st["kv"]["alloc_failures"],
                "kv_peak_pages": st["kv"]["peak_used"],
                "wall_s": round(wall, 3),
                "tokens_per_sec": round(st["tokens_out"] / wall, 2),
                "programs": {site: s["count"] for site, s in
                             sorted((steady or {}).items())},
                "zero_steady_state_recompiles": bool(steady == warm),
            }
        finally:
            if prior is None:
                os.environ.pop("MXNET_KV_DTYPE", None)
            else:
                os.environ["MXNET_KV_DTYPE"] = prior

    out = {"page_size": page_size, "prompt_len": prompt_len,
           "max_new_tokens": max_new,
           "pages_per_stream": pages_per_stream,
           "pool_byte_budget": budget,
           "fp32": run("float32", fp32_pages),
           "int8": run("int8", int8_pages)}
    ratio = (out["int8"]["max_concurrent_streams"]
             / out["fp32"]["max_concurrent_streams"])
    out["stream_capacity_ratio"] = round(ratio, 2)
    clean = all(
        c["completed"] == c["max_concurrent_streams"]
        and c["preempted"] == 0 and c["alloc_failures"] == 0
        and c["zero_steady_state_recompiles"]
        for c in (out["fp32"], out["int8"]))
    out["meets_1p8x_at_same_bytes"] = bool(ratio >= 1.8 and clean)
    compile_watch.disable()
    return out


def _int8_kv_record():
    """The quantized-KV-cache benchmark record (BENCH_r20.json,
    serving half): concurrent decode-stream capacity of a fp32 vs an
    int8 paged KV pool at the SAME byte budget — the int8 pool must
    carry >= 1.8x the streams with zero preemptions and zero
    steady-state recompiles. CPU backend."""
    record = {"bench": "int8_kv_capacity", "platform": "cpu"}
    try:
        record.update(_bench_int8_kv_case())
    except Exception as exc:                     # noqa: BLE001
        record["errors"] = {"int8_kv": _err_str(exc)}
    return record


def _bench_prefix_cache_case(page_size=16, header_pages=12,
                             max_new=16, n_requests=20,
                             shared_frac=0.8, pool_pages=256):
    """Prefix-cache serving benchmark (BENCH_r22): the SAME
    80%-shared-prefix request mix (a fleet-style system-prompt
    header + short per-request suffixes) through one DecodeServer
    with prefix sharing OFF then ON. Sharing must cut median TTFT
    (hit requests skip prefill entirely — the suffix feeds through
    the decode-step program) and raise throughput, with the
    fixed-program oracle holding in both modes (ON adds exactly one
    program: the ``decode:cow`` page copy). The capacity half then
    runs each mode's analytic stream ceiling at the SAME pool byte
    budget — concurrent streams share the header's pages instead of
    each carrying a private copy — and must finish with zero
    preemptions and zero alloc failures."""
    import numpy as np
    from mxnet_tpu import compile_watch
    from mxnet_tpu.serving import DecodeServer, ToyDecoderLM

    compile_watch.enable()
    n_layers, n_heads, head_dim = 2, 4, 16
    model = ToyDecoderLM(vocab=128, n_layers=n_layers,
                         n_heads=n_heads, head_dim=head_dim,
                         max_len=256)
    params = model.init_params(seed=0)
    rs = np.random.RandomState(11)
    ladder_top = header_pages * page_size + 2 * page_size
    header = rs.randint(1, 128, size=header_pages * page_size)
    prompts = []
    for i in range(n_requests):
        if i < n_requests * shared_frac:
            suffix = rs.randint(1, 128, size=int(rs.randint(1, 5)))
            prompts.append(np.concatenate([header, suffix]))
        else:
            prompts.append(rs.randint(
                1, 128, size=int(rs.randint(20, 60))))

    def run(prefix_on):
        name = "px_on" if prefix_on else "px_off"
        srv = DecodeServer(model, params, seq_ladder=[ladder_top],
                           max_new_tokens=max_new, window=8,
                           page_size=page_size, pool_pages=pool_pages,
                           max_queue=n_requests + 4,
                           prefix_cache=prefix_on, name=name)
        srv.warmup()
        warm = compile_watch.site_stats("decode:" + name)
        t0 = time.perf_counter()
        reqs = [srv.submit(p, max_new_tokens=max_new)
                for p in prompts]
        for r in reqs:
            r.result(timeout=600)
        wall = time.perf_counter() - t0
        st = srv.stats()
        steady = compile_watch.site_stats("decode:" + name)
        srv.stop()
        out = {
            "wall_s": round(wall, 3),
            "tokens_per_sec": round(st["tokens_out"] / wall, 2),
            "ttft_ms_p50": st["ttft_ms"]["p50"],
            "ttft_ms_p99": st["ttft_ms"]["p99"],
            "prefill_steps": st["prefill_steps"],
            "prefix_hits": st["prefix"]["hits"],
            "prefix_hit_tokens": st["prefix"]["hit_tokens"],
            "prefix_bytes_saved": st["prefix"]["bytes_saved"],
            "cow_splits": st["prefix"]["cow_splits"],
            "programs": {site: s["count"] for site, s in
                         sorted((steady or {}).items())},
            "zero_steady_state_recompiles": bool(steady == warm),
        }
        return out

    def capacity(prefix_on):
        """Max concurrent streams at the fixed pool budget: every
        stream is header + a 1-page suffix-and-generation run."""
        usable = pool_pages - 1
        per_stream = header_pages + 1
        if prefix_on:
            cap = (usable - header_pages) // 1
        else:
            cap = usable // per_stream
        cap = min(cap, 48)              # keep the CPU run bounded
        name = "cap_on" if prefix_on else "cap_off"
        srv = DecodeServer(model, params, seq_ladder=[ladder_top],
                           max_new_tokens=page_size - 14, window=cap,
                           page_size=page_size, pool_pages=pool_pages,
                           max_queue=cap + 4, prefix_cache=prefix_on,
                           name=name)
        srv.warmup()
        if prefix_on:
            # seed the index once so every measured stream shares
            srv.submit(header, max_new_tokens=1).result(timeout=600)
        reqs = []
        for i in range(cap):
            suffix = np.asarray([1 + (i % 120)], np.int64)
            reqs.append(srv.submit(np.concatenate([header, suffix]),
                                   max_new_tokens=page_size - 14))
        for r in reqs:
            r.result(timeout=600)
        st = srv.stats()
        srv.stop()
        return {
            "max_concurrent_streams": cap,
            "completed": st["completed"],
            "preempted": st["preempted"],
            "alloc_failures": st["kv"]["alloc_failures"],
            "kv_peak_pages": st["kv"]["peak_used"],
        }

    def median_run(prefix_on, repeats=3):
        # CPU wall-clock is noisy (the documented BENCH_r09 band):
        # take the median-TTFT repeat, each on a fresh server/pool
        runs = sorted((run(prefix_on) for _ in range(repeats)),
                      key=lambda r: r["ttft_ms_p50"])
        return runs[len(runs) // 2]

    out = {"page_size": page_size,
           "header_tokens": header_pages * page_size,
           "shared_fraction": shared_frac,
           "n_requests": n_requests,
           "max_new_tokens": max_new,
           "pool_pages": pool_pages,
           "off": median_run(False), "on": median_run(True),
           "capacity_off": capacity(False),
           "capacity_on": capacity(True)}
    out["ttft_p50_speedup"] = round(
        out["off"]["ttft_ms_p50"] / max(out["on"]["ttft_ms_p50"],
                                        1e-9), 2)
    out["stream_capacity_ratio"] = round(
        out["capacity_on"]["max_concurrent_streams"]
        / out["capacity_off"]["max_concurrent_streams"], 2)
    clean = all(
        c["completed"] >= c["max_concurrent_streams"]
        and c["preempted"] == 0 and c["alloc_failures"] == 0
        for c in (out["capacity_off"], out["capacity_on"]))
    out["meets_ttft_and_capacity_win"] = bool(
        out["ttft_p50_speedup"] > 1.0
        and out["stream_capacity_ratio"] > 1.5 and clean
        and out["on"]["zero_steady_state_recompiles"]
        and out["off"]["zero_steady_state_recompiles"])
    compile_watch.disable()
    return out


def _prefix_cache_record():
    """The prefix-cache benchmark record (BENCH_r22.json): an
    80%-shared-prefix serving mix with page sharing off vs on — TTFT
    and tokens/sec deltas, plus the concurrent-stream ceiling at the
    same pool byte budget. CPU backend."""
    record = {"bench": "prefix_cache", "platform": "cpu"}
    try:
        record.update(_bench_prefix_cache_case())
    except Exception as exc:                     # noqa: BLE001
        record["errors"] = {"prefix_cache": _err_str(exc)}
    return record


def _replica_device(i):
    """One replica per device, round robin: a fleet built without
    ``device=`` lands every replica on the first chip."""
    import jax
    return jax.local_devices()[i % jax.local_device_count()]


def _err_str(exc):
    return "%s: %s" % (type(exc).__name__, str(exc)[:400])


def _emit_mode(record):
    """Print a ``--flag`` mode's record; a record that caught a phase's
    failure still prints (the error is the evidence) but the run exits
    non-zero."""
    print(json.dumps(record))
    if any(k == "error" or k == "errors" or k.endswith("_error")
           for k in record):
        sys.exit(1)


def main():
    """The default run: ResNet-50 on one TPU chip. No probe process, no
    CPU branch, no per-phase net: a phase that fails raises and the run
    exits non-zero without a record."""
    from mxnet_tpu import compile_watch, runtime
    runtime.enable_compile_cache()
    import jax
    device = jax.devices()[0]
    if device.platform != "tpu":
        sys.exit("bench.py: the default run measures a TPU; JAX found "
                 "%s (%s)" % (device.platform, device.device_kind))
    # the ONE peak table (compile_watch's); an unknown kind raises
    peak, peak_bw, kind, n_devices = compile_watch.peak_table()
    record = {
        "metric": "resnet50_inference_img_per_sec_per_chip",
        "unit": "img/s",
        "batch": BATCH,
        "dtype": "bfloat16",
        "platform": device.platform,
        "device_kind": kind,
        "device_count": n_devices,
    }

    infer_img_s, infer_mfu, gf_per_img = _bench_inference(
        BATCH, ITERS, peak)
    record["value"] = round(infer_img_s, 2)
    record["vs_baseline"] = round(infer_img_s / BASELINE_INFER, 3)
    record["inference_mfu_pct"] = round(100 * infer_mfu, 1)
    record["flops_per_image_gf"] = round(gf_per_img / 1e9, 2)

    for b in SWEEP:
        s_img, s_mfu, _ = _bench_inference(b, 64, peak)
        record["inference_img_per_sec_batch%d" % b] = round(s_img, 2)
        record["inference_mfu_pct_batch%d" % b] = round(100 * s_mfu, 1)

    train_img_s, train_mfu, train_hw = \
        _bench_training_framework_path(peak, gf_per_img)
    record["training_img_per_sec_per_chip"] = round(train_img_s, 2)
    record["training_vs_baseline"] = round(
        train_img_s / BASELINE_TRAIN, 3)
    record["training_mfu_pct"] = round(100 * train_mfu, 1)
    record["training_hw_util_pct"] = round(100 * train_hw, 1)
    t128_img_s, t128_mfu, t128_hw = _bench_training_framework_path(
        peak, gf_per_img, batch=128, check_parity=False)
    record["training_img_per_sec_batch128"] = round(t128_img_s, 2)
    record["training_mfu_pct_batch128"] = round(100 * t128_mfu, 1)
    record["training_hw_util_pct_batch128"] = round(100 * t128_hw, 1)
    record["training_path"] = (
        "Executor.fwdbwd + aggregated multi_sgd_update op "
        "(trajectory-parity checked vs eager Executor+Updater)")

    fused_rec = _fused_step_record()
    if "errors" in fused_rec:
        raise RuntimeError("fused-step bench failed: %s"
                           % fused_rec["errors"])
    record["fused_step"] = fused_rec["cases"]

    allreduce_gbps = _bench_allreduce_bandwidth()
    bound = peak_bw / 1e9
    record["kvstore_pushpull_gbps"] = round(allreduce_gbps, 1)
    record["kvstore_hbm_bound_gbps"] = bound
    # reduce streams from/to HBM, so the figure must sit below the
    # chip's HBM bandwidth but within 2x of it for a healthy kernel
    record["kvstore_within_2x_of_bound"] = bool(
        allreduce_gbps <= bound and allreduce_gbps >= bound / 2)
    print(json.dumps(record))


if __name__ == "__main__":
    if "--fused-step" in sys.argv:
        # CPU-friendly standalone mode: only the fused-train-step
        # benchmark, one JSON line (the BENCH_r06 artifact)
        _emit_mode(_fused_step_record())
    elif "--telemetry-overhead" in sys.argv:
        # CPU-friendly standalone mode: telemetry-off vs telemetry-on
        # MLP train-step time, one JSON line (the BENCH_r07 artifact)
        _emit_mode(_telemetry_record())
    elif "--input-pipeline" in sys.argv:
        # CPU-friendly standalone mode: eager vs 1-worker prefetch vs
        # pooled+device-prefetch input path on a decode-bound loop,
        # one JSON line (the BENCH_r08 artifact)
        _emit_mode(_input_pipeline_record())
    elif "--compile-watch-overhead" in sys.argv:
        # CPU-friendly standalone mode: compile-watch-off vs -on fused
        # MLP train-step time, one JSON line (the BENCH_r09 artifact)
        _emit_mode(_compile_watch_record())
    elif "--grad-overlap" in sys.argv:
        # CPU-friendly standalone mode on a forced 8-device host mesh:
        # unbucketed post-backward blob vs in-program bucketed
        # reduce-scatter + ZeRO-1 state memory, one JSON line (the
        # BENCH_r11 artifact). Topology must be set before jax loads.
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                flags + " --xla_force_host_platform_device_count=8"
            ).strip()
        _emit_mode(_grad_overlap_record())
    elif "--param-shard" in sys.argv:
        # CPU-friendly standalone mode on a forced 8-device host mesh:
        # replicated vs FSDP-sharded resident parameters through the
        # DistributedTrainer — steps/sec, measured per-device param
        # bytes, capped-allocator budget — one JSON line (the
        # BENCH_r12 artifact). Topology must be set before jax loads.
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                flags + " --xla_force_host_platform_device_count=8"
            ).strip()
        _emit_mode(_param_shard_record())
    elif "--amp" in sys.argv:
        # CPU-friendly standalone mode: bf16 multi-precision fused
        # step vs fp32 on the same MLP — zero-fallback/one-trace
        # oracle + resident weight bytes, one JSON line (the training
        # half of the BENCH_r20 artifact)
        _emit_mode(_amp_record())
    elif "--int8-kv" in sys.argv:
        # CPU-friendly standalone mode: fp32 vs int8 paged-KV-pool
        # decode stream capacity at the SAME byte budget (>= 1.8x
        # streams, zero preemptions, fixed program set), one JSON line
        # (the serving half of the BENCH_r20 artifact)
        _emit_mode(_int8_kv_record())
    elif "--prefix-cache" in sys.argv:
        # CPU-friendly standalone mode: 80%-shared-prefix serving mix
        # with KV page sharing off vs on — TTFT/throughput deltas and
        # the concurrent-stream ceiling at the same pool byte budget,
        # one JSON line (the BENCH_r22 artifact)
        _emit_mode(_prefix_cache_record())
    elif "--decode" in sys.argv:
        # CPU-friendly standalone mode: sequential prefill-then-decode
        # vs continuous batching over the paged-KV DecodeServer —
        # tokens/sec, p99 inter-token latency, fixed-program oracle,
        # one JSON line (the BENCH_r17 artifact)
        _emit_mode(_decode_record())
    elif "--router" in sys.argv:
        # CPU-friendly standalone mode: 4-replica fleet router under
        # skewed two-tenant load with one replica killed mid-run —
        # zero failed streams, detect-to-resume latency, fairness
        # ratio, one JSON line (the BENCH_r19 artifact)
        _emit_mode(_router_record())
    elif "--fleet-obs" in sys.argv:
        # CPU-friendly standalone mode: 2-replica routed load with the
        # fleet observability stack off vs armed (within the noise
        # band), plus one injected replica_lost drill — exactly one
        # flight-recorder bundle reconciling with the router failover
        # counters, one JSON line (the BENCH_r21 artifact)
        _emit_mode(_fleet_obs_record())
    elif "--metering" in sys.argv:
        # CPU-friendly standalone mode: 2-replica skewed two-tenant
        # routed load with the usage meter off vs on (within the
        # noise band), plus one metered replica-kill drill — the
        # per-tenant ledger must reconcile against the router's
        # counters with replay tokens billed exactly once, one JSON
        # line (the BENCH_r23 artifact)
        _emit_mode(_metering_record())
    elif "--serving" in sys.argv:
        # CPU-friendly standalone mode: offered-load sweep over the
        # continuous-batching inference server (arrival rate x bucket
        # ladder -> latency/throughput curve, shed rate at overload,
        # program-cache oracle), one JSON line (the BENCH_r13 artifact)
        _emit_mode(_serving_record())
    elif "--bucketing" in sys.argv:
        # CPU-friendly standalone mode: variable-length LSTM text
        # training bucketed over a 4-rung ladder vs naively compiling
        # one program per distinct length — compile bill + wall clock,
        # one JSON line (the BENCH_r14 artifact)
        _emit_mode(_bucketing_record())
    elif "--packing" in sys.argv:
        # CPU-friendly standalone mode: padded vs FFD-packed training
        # at a skewed ragged length mix — steps/sec, samples/sec,
        # real-token fraction (one half of the BENCH_r16 artifact)
        _emit_mode(_packing_record())
    elif "--multihost" in sys.argv:
        # CPU-friendly standalone mode: 1-proc 8-device vs launched
        # 2-proc 2x4 steps/sec plus supervised detection-to-restart
        # wall time for one injected host loss, one JSON line (the
        # BENCH_r18 artifact). Subprocesses set their own topology.
        _emit_mode(_multihost_record())
    elif "--trace-overhead" in sys.argv:
        # CPU-friendly standalone mode: the live observability stack
        # (tracing + /metrics + watchdog) off vs on for the fused-MLP
        # train loop and a fixed-rate serving run, plus the
        # metrics-agree-with-stats oracle, one JSON line (the
        # BENCH_r15 artifact)
        _emit_mode(_trace_overhead_record())
    elif "--checkpoint-overhead" in sys.argv:
        # CPU-friendly standalone mode: step-time p99 with
        # checkpointing off vs sync vs async on the MLP and convnet
        # cases, one JSON line (the BENCH_r10 artifact)
        _emit_mode(_checkpoint_record())
    elif "--lint" in sys.argv:
        # mxlint wall-time guard: the tree-wide static-analysis run
        # is a tier-1 test, so its cost is a perf surface — this mode
        # records it (cold parse + warm re-run) so a quadratic rule
        # regression shows up as a number, not a slow CI mystery
        import time as _time
        from mxnet_tpu.tools.lint import lint_paths
        t0 = _time.perf_counter()
        cold = lint_paths()
        t_cold = _time.perf_counter() - t0
        t0 = _time.perf_counter()
        warm = lint_paths()
        t_warm = _time.perf_counter() - t0
        print(json.dumps({
            "bench": "lint", "files": cold.files,
            "violations": len(cold.violations),
            "baselined": len(cold.baselined),
            "suppressed": cold.suppressed,
            "cold_s": round(t_cold, 3), "warm_s": round(t_warm, 3),
            "budget_s": 10.0, "within_budget": t_warm < 10.0,
        }))
    else:
        main()
