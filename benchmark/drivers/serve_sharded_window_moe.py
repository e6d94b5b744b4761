"""Driver for serving cells whose model is SHARDED over the chips of the
cell: one mesh axis over ``chips`` devices, every layer shared by them
(experts by their leading dimension, attention, pages and rings by
key/value head, the head by columns), served by ONE ``DecodeServer``
whose step programs run under ``shard_map``. ``serve_window_moe``'s
load, clock, records, sample and check — the same objects, imported —
with a mesh and weights of its own.

Why a driver of its own: ``serve_latent_moe.make_params`` draws every
weight in ONE jitted call onto the default device (24 GB onto device 0
of 16), and no driver builds a mesh. This one makes the mesh from the
cell's ``chips`` (``parallel.mesh.create_mesh``), binds the model to it
(``model.sharded_over``), draws the same seeded values an array at a
time INTO the model's declared shardings (``out_shardings``), and hands
server and reference what it built: the reference computes on the
program's weights as they lie.

``ctx.raw["model"]`` holds ONE chip's sizes — its experts, its heads,
its key/value head, its columns of the head — because every reader of a
kernel's roofline divides device 0's kernel time by them: one chip's
work against one chip's time (a reader that summed four chips' bytes
would read four times the roofline). The model's counters on
``mx:decode.readback`` and in ``stats()["moe"]`` are chip 0's for the
same reason; every chip's own are ``raw.moe_by_chip``.
"""
from __future__ import annotations

import functools
import gc
import time

from .. import harness
from ..harness import now
from .serve_decode import _sleep_until, _stream_record
from .serve_window_moe import FixedShapesLoad, _check


@functools.lru_cache(maxsize=None)
def _drawn(shape, dtype, std, sharding):
    import jax
    import jax.numpy as jnp

    def draw(key):
        return (jax.random.normal(key, shape, jnp.float32)
                * std).astype(dtype)

    return jax.jit(draw, out_shardings=sharding)


def make_params(model, spec, seed):
    """``serve_latent_moe.make_params``' values — a matrix ``(...,
    fan_in, fan_out)`` normal with deviation ``fan_in ** -0.5`` from
    ``fold_in(key, its index among the sorted names)``, a table by
    ``spec["tables"]``, a vector 1 where its name ends in
    ``gain_suffix`` and 0 elsewhere — each array drawn by a jitted call
    of its own INTO the sharding the model declares for it: no array
    ever lies whole on one chip."""
    import jax
    import jax.numpy as jnp
    shapes = jax.eval_shape(model.init_params, 0)
    where = model.param_shardings()
    key = jax.random.PRNGKey(seed)
    out = {}
    for i, name in enumerate(sorted(shapes)):
        shape, dtype = shapes[name].shape, shapes[name].dtype
        if len(shape) == 1:
            fill = 1.0 if name.endswith(spec["gain_suffix"]) else 0.0
            out[name] = jnp.full(shape, fill, dtype, device=where[name])
        else:
            std = spec["tables"].get(name, shape[-2] ** -0.5)
            out[name] = _drawn(shape, jnp.dtype(dtype).name, float(std),
                               where[name])(jax.random.fold_in(key, i))
    return out


def chip_sizes(model, ladder, window):
    """ONE chip's sizes of the sharded ``model``, under the names the
    cost functions read (``window_moe_costs``, ``mellum_costs``)."""
    chip, n = model.local(), model.shards
    held = model.held[1] - model.held[0]
    return {"n_layers": model.n_layers, "d_model": model.d_model,
            "vocab": model.vocab // n, "n_dense_layers": sum(model.dense),
            "n_moe_layers": model.n_moe_layers, "d_ff": model.d_ff,
            "d_expert": model.d_expert, "d_shared": model.d_shared,
            "n_shared": int(bool(model.d_shared)),
            "experts_held": held // n,
            "n_routed_experts": model.n_experts, "top_k": model.top_k,
            "heads": list(chip.heads), "kinds": list(model.kinds),
            "n_kv_heads": chip.n_kv_heads, "head_dim": model.head_dim,
            "ring_window": model.window, "long_rung": ladder[-1],
            "window": window, "gated": bool(model.gated),
            "chips": n, "whole": {
                "vocab": model.vocab, "experts_held": held,
                "heads": list(model.heads),
                "n_kv_heads": model.n_kv_heads}}


def run(ctx):
    import jax
    from mxnet_tpu.parallel.mesh import create_mesh
    cfg = ctx.config
    stamps = ctx.raw.setdefault("setup_stamps", {})
    stamps["driver"] = now() - ctx.t_start
    devices = jax.devices()
    mesh = create_mesh(
        {cfg["mesh"]["axis"]: ctx.chips},
        devices=None if len(devices) == ctx.chips
        else devices[:ctx.chips])
    model = harness.load_object(cfg["model"]["import"])(
        **cfg["model"]["kwargs"]).sharded_over(mesh)
    params = make_params(model, cfg["weights"], ctx.seed)
    jax.block_until_ready(params)
    stamps["weights"] = now() - ctx.t_start
    srv = harness.load_object(cfg["server"]["import"])(
        model, params, name="bench", mesh=mesh, **cfg["server"]["kwargs"])
    stamps["server"] = now() - ctx.t_start
    load = None
    try:
        srv.warmup()
        stamps["warmup"] = now() - ctx.t_start
        lead_in = float(ctx.traffic["lead_in_s"])
        load = FixedShapesLoad(srv, ctx, model.vocab)
        t0 = now()
        load.start(t0, lead_in + ctx.seconds)
        w0, w1 = t0 + lead_in, t0 + lead_in + ctx.seconds
        _sleep_until(w0)
        ctx.raw["setup_s"] = now() - ctx.t_start
        ctx.raw["w0_unix"] = time.time()
        stats0, compiles0 = srv.stats(), ctx.compiles.count
        if ctx.tracing:
            _sleep_until(w0 + min(ctx.traffic["trace_after_s"],
                                  ctx.seconds / 3.0))
            with harness.profiler_slice(ctx):
                _sleep_until(min(now() + ctx.traffic["trace_s"], w1))
        _sleep_until(w1)
        stats1, compiles1 = srv.stats(), ctx.compiles.count
        ctx.raw["memory"] = harness.memory_peak(ctx)
        stuck = load.finish()
    finally:
        if load is not None:
            load.stop.set()
        srv.stop(drain=False)
    streams = load.streams
    ladder = sorted(cfg["server"]["kwargs"]["seq_ladder"])
    counted = ("steps", "moe_slots", "experts_touched",
               "ring_rows_wrapped", "global_pages_live", "ring_bytes")

    def delta(one, nil):
        return {k: one.get(k, 0) - nil.get(k, 0) for k in counted}

    ctx.raw.update(
        window_s=w1 - w0, stats0=stats0, stats1=stats1,
        compiles_in_window=compiles1 - compiles0,
        streams=[_stream_record(r, w0, w1) for r in streams
                 if r.sent is not None],
        model=chip_sizes(model, ladder, stats1["window"]),
        stats_delta={k: stats1[k] - stats0[k] for k in stats1
                     if isinstance(stats1[k], int)
                     and not isinstance(stats1[k], bool)},
        # chip 0's, as the readers that divide chip 0's kernel time want
        moe_delta=delta(stats1["moe"], stats0["moe"]),
        moe_by_chip=[delta(one, nil) for one, nil in zip(
            stats1["moe_by_chip"], stats0["moe_by_chip"])],
        mesh=stats1["mesh"], unnamed_gap="scheduler")
    judged = [r for r in streams if not r.cut]
    failed = [r for r in judged if r.error is not None
              or len(r.tokens) != r.asked]
    # the reference runs once the memory has been read and the server's
    # pool and rings are freed: the weights are the benchmark's own and
    # stay where they lie
    load.srv = srv = None
    for rec in streams:
        rec.req = None
    gc.collect()
    t_check = now()
    check = _check(ctx, cfg, model, params, judged, ladder)
    ctx.raw["check"] = dict(check, seconds=now() - t_check)
    compared = {
        "failed_requests": {"value": len(failed), "limit": 0},
        "stuck_client_threads": {"value": len(stuck), "limit": 0},
        "compiles_in_window": {"value": ctx.raw["compiles_in_window"],
                               "limit": 0},
        **check["compared"]}
    problems = harness.over_limit(compared)
    return {"attempted": len(judged), "failed": len(failed),
            "correct": not problems, "problems": problems,
            "compared": compared}
