"""What the training drivers share: the seeded host ring behind the
framework's input pipeline, the closed loop of optimizer steps, the
profiler slice and the comparison with the plain reference. A driver
supplies ``build(ctx, net) -> step`` with

- ``step(x, y, before_update=None) -> loss`` (an NDArray): one complete
  optimizer step; ``before_update`` runs once the parameters exist and
  before they change;
- ``step.placement``: where the pipeline puts a batch;
- ``step.params()``: the parameters as they are now, by name;
- ``step.dispatches()``, ``step.fallbacks()``: running counts.
"""
from __future__ import annotations

import math
import time

import numpy as np

from .. import harness, traffic as traffic_mod
from ..harness import now, span


def build_net(ctx):
    import mxnet_tpu as mx
    from mxnet_tpu.amp import DtypePolicy
    cfg = ctx.config
    mx.random.seed(ctx.seed)
    np.random.seed(ctx.seed)        # the initializers draw from numpy
    net = harness.load_object(cfg["model"]["import"])(
        **cfg["model"]["kwargs"])
    net.initialize(harness.load_object(cfg["init"])())
    policy = ctx.traffic["dtype_policy"]
    DtypePolicy(policy["compute"], rules=policy.get("rules")).apply(net)
    return net


def _ring_source(x, y, batch):
    """An endless ``DataIter`` over the host ring, in the split protocol
    the framework's pipeline fans out to its decode workers. A batch is
    a contiguous slice of the ring, so that what the pipeline pays for
    is the transfer to the device. (``io.NDArrayIter`` copies every
    batch with a fancy index first, which on the chip's host took 0.64 s
    for these 77 MB and held the loop to 400-800 img/s: PERF.md,
    findings of PR 22.)"""
    import mxnet_tpu as mx
    from mxnet_tpu import io

    class RingIter(io.DataIter):
        def __init__(self):
            super().__init__(batch)
            self.cursor = 0
            self.provide_data = [io.DataDesc(
                "data", (batch,) + x.shape[1:], x.dtype)]
            self.provide_label = [io.DataDesc(
                "softmax_label", (batch,), y.dtype)]

        def reset(self):
            self.cursor = 0

        def next_raw(self):
            at = self.cursor
            self.cursor = (at + batch) % len(x)
            return at

        def decode_raw(self, at):
            return io.DataBatch(data=[mx.nd.array(x[at:at + batch])],
                                label=[mx.nd.array(y[at:at + batch])],
                                pad=0)

        def next(self):
            return self.decode_raw(self.next_raw())

    return RingIter()


def _params(step, net, suffix=""):
    """The program's parameters as they are now, by their name without
    the network's prefix; with ``suffix``, those whose name ends in it."""
    return {name[len(net.prefix):]: value
            for name, value in step.params().items()
            if name.endswith(suffix)}


def _reference_step(ctx, step, net, x, y):
    """What the plain float32 reference makes of the first optimizer
    step, on the weights as they are before it: the loss on the batch,
    and the watched parameters (those whose name ends in the
    configuration's ``update_check``) after one step of SGD with
    momentum from a zero state, ``w - lr * (grad + wd * w)``, with
    ``jax.grad`` of the reference's loss. The reference runs on the
    cell's chips: weights whole on each, the batch split as the
    program's."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec
    spec = ctx.config["reference"]
    fn = harness.load_object(spec["import"])
    where = whole = step.placement
    if isinstance(where, NamedSharding):
        whole = NamedSharding(where.mesh, PartitionSpec())
    params = {name: jax.device_put(value.astype(jnp.float32), whole)
              for name, value in _params(step, net).items()}
    watched = {name: value for name, value in params.items()
               if name.endswith(spec["update_check"])}
    rest = {name: value for name, value in params.items()
            if name not in watched}

    def loss_and_grads(watched, rest, a, b):
        return jax.value_and_grad(
            lambda w: fn({**rest, **w}, a, b, **spec["kwargs"]))(watched)

    loss, grads = jax.jit(loss_and_grads)(
        watched, rest, jax.device_put(x, where), jax.device_put(y, where))
    opt = ctx.traffic["optimizer_params"]
    lr, wd = opt["learning_rate"], opt.get("wd", 0.0)
    after = {name: np.asarray(w - lr * (grads[name] + wd * w))
             for name, w in watched.items()}
    return {"loss": float(loss), "after": after,
            "before": {name: np.asarray(w) for name, w in watched.items()}}


def _update_error(ref, served):
    """How far the program's first step moved the watched parameters
    from where the reference's step moves them: the largest, over the
    parameters, of |served - reference| over |reference's move|."""
    errs = {}
    for name, after in ref["after"].items():
        move = np.linalg.norm(after - ref["before"][name])
        errs[name] = float(np.linalg.norm(served[name] - after)
                           / max(move, 1e-30))
    which = max(errs, key=lambda n: math.inf if math.isnan(errs[n])
                else errs[n])            # a NaN is the worst
    return errs[which], which


def run(ctx, build):
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.io.pipeline import AsyncInputPipeline
    cfg, traffic = ctx.config, ctx.traffic
    batch = traffic["batch_per_chip"] * ctx.chips
    net = build_net(ctx)
    ring_x, ring_y = traffic_mod.image_ring(
        ctx.seed, traffic["ring_batches"] * batch, cfg["image"],
        cfg["model"]["kwargs"]["classes"])
    step = build(ctx, net)
    pipe = AsyncInputPipeline(_ring_source(ring_x, ring_y, batch),
                              placement=step.placement)
    losses = []
    state = {"steps": 0, "data_wait_s": 0.0, "ref": None, "syncs": []}

    def one(before_update=None):
        t = now()
        with span("pipeline.next"):
            b = pipe.next()
        state["data_wait_s"] += now() - t
        with span("trainer.step"):
            loss = step(b.data[0], b.label[0], before_update)
        losses.append(loss._data)
        state["steps"] += 1
        if state["steps"] % traffic["sync_every"] == 0:
            sync()

    def sync():
        if losses:
            with span("sync"):
                jax.block_until_ready(losses[-1])
            state["syncs"].append((state["steps"], now()))

    def steps_until(t_end=None, n=None):
        first = state["steps"]
        while (now() < t_end) if n is None \
                else (state["steps"] - first < n):
            one()

    try:
        # the first step also proves the mathematics, against the plain
        # float32 reference on the ring's first batch and the weights as
        # they were before the update: its loss, and where it moved the
        # watched parameters
        def snapshot():
            state["ref"] = _reference_step(ctx, step, net, ring_x[:batch],
                                           ring_y[:batch])
        one(snapshot)
        update_err, update_at = _update_error(state["ref"], {
            name: np.asarray(value, np.float32) for name, value in _params(
                step, net, cfg["reference"]["update_check"]).items()})
        steps_until(n=traffic["warmup_steps"] - 1)
        sync()
        first_loss = float(losses[0])
        del losses[:]
        state.update(steps=0, data_wait_s=0.0, syncs=[])
        d0, f0, c0 = step.dispatches(), step.fallbacks(), ctx.compiles.count
        w0 = now()
        state["syncs"].append((0, w0))
        ctx.raw["setup_s"] = w0 - ctx.t_start
        ctx.raw["w0_unix"] = time.time()
        if ctx.tracing:
            steps_until(w0 + min(traffic["trace_after_s"],
                                 ctx.seconds / 3.0))
            sync()
            # starting and stopping the trace hold this loop
            t = now()
            with harness.profiler_slice(ctx):
                held = now() - t
                steps_until(n=traffic["trace_steps"])
                sync()
                t = now()
            ctx.raw["profiler_held_s"] = held + now() - t
        steps_until(w0 + ctx.seconds)
        sync()
        w1 = now()
        compiled = ctx.compiles.count - c0
        ctx.raw["memory"] = harness.memory_peak(ctx)
    finally:
        pipe.close()
    values = np.asarray(jnp.stack(losses).astype(jnp.float32))
    bad = int((~np.isfinite(values)).sum())
    ref_loss = state["ref"]["loss"]
    # the last pass over the ring against the very first loss
    last_loss = float(values[-traffic["ring_batches"]:].mean())
    ctx.raw.update(
        window_s=w1 - w0, steps=state["steps"],
        images=state["steps"] * batch, data_wait_s=state["data_wait_s"],
        syncs=[(n, t - w0) for n, t in state["syncs"]],
        dispatches=step.dispatches() - d0,
        fallbacks=step.fallbacks() - f0,
        compiles_in_window=compiled,
        traced_steps=traffic["trace_steps"] if ctx.tracing else 0,
        first_loss=first_loss, reference_loss=ref_loss,
        last_loss=last_loss, update_rel_err=update_err,
        update_rel_err_at=update_at, unnamed_gap="unattributed")
    rel = abs(first_loss - ref_loss) / max(abs(ref_loss), 1e-6)
    ctx.raw["first_loss_rel_diff"] = rel
    # what decides ``correct``, each beside its limit; the losses and
    # the leaf behind a number over its limit are in ``raw``
    compared = {
        "first_loss_rel_diff": {"value": rel,
                                "limit": traffic["loss_rel_tol"]},
        "update_rel_err": {"value": update_err,
                           "limit": traffic["update_rel_tol"]},
        "last_over_first_loss": {"value": last_loss / first_loss,
                                 "limit": traffic["loss_fall_ratio"]},
        "dispatches_beside_steps": {
            "value": abs(ctx.raw["dispatches"] - state["steps"]),
            "limit": 0},
        "eager_fallbacks": {"value": ctx.raw["fallbacks"], "limit": 0},
        "compiles_in_window": {"value": ctx.raw["compiles_in_window"],
                               "limit": 0},
        "nonfinite_losses": {"value": bad, "limit": 0}}
    problems = harness.over_limit(compared)
    return {"attempted": state["steps"], "failed": bad,
            "correct": not problems, "problems": problems,
            "compared": compared}
