"""Driver for serving cells whose model attends through a latent and
routes tokens to experts: the load, the clock and the records of
``serve_decode`` (imported, the same objects), its own weights and its
own check against ``benchmark/reference/latent_moe_lm.py``.

``serve_decode.run`` names ``reference.decoder_lm`` and ``model.d_ff``
and cannot serve another model; one driver whose configuration names
its reference is a ``benchmark`` issue (PERF.md section 7).
"""
from __future__ import annotations

import gc
import importlib
import time

import numpy as np

from .. import harness, traffic as traffic_mod
from ..harness import now
from .serve_decode import Load, _sleep_until, _stream_record


def make_params(model, spec, seed):
    """Every weight in one jitted call, on the device, from the seed, in
    the shapes and types ``model.init_params`` gives them: a matrix
    ``(..., fan_in, fan_out)`` normal with deviation ``fan_in ** -0.5``
    — the SECOND-LAST dimension, so that a stack of experts ``(E, fan_in,
    fan_out)`` is scaled by its fan-in and not by the number of experts —
    a table by the deviation ``spec["tables"]`` names, a vector 1 where
    its name ends in ``gain_suffix`` and 0 elsewhere (the router's
    correction bias)."""
    import jax
    import jax.numpy as jnp
    shapes = jax.eval_shape(model.init_params, 0)

    def make(key):
        out = {}
        for i, name in enumerate(sorted(shapes)):
            shape, dtype = shapes[name].shape, shapes[name].dtype
            if len(shape) == 1:
                fill = 1.0 if name.endswith(spec["gain_suffix"]) else 0.0
                out[name] = jnp.full(shape, fill, dtype)
            else:
                std = spec["tables"].get(name, shape[-2] ** -0.5)
                out[name] = (jax.random.normal(
                    jax.random.fold_in(key, i), shape, jnp.float32)
                    * std).astype(dtype)
        return out

    return jax.jit(make)(jax.random.PRNGKey(seed))


def run(ctx):
    import jax
    cfg = ctx.config
    stamps = ctx.raw.setdefault("setup_stamps", {})
    stamps["driver"] = now() - ctx.t_start
    model = harness.load_object(cfg["model"]["import"])(
        **cfg["model"]["kwargs"])
    params = make_params(model, cfg["weights"], ctx.seed)
    jax.block_until_ready(params)
    stamps["weights"] = now() - ctx.t_start
    srv = harness.load_object(cfg["server"]["import"])(
        model, params, name="bench", **cfg["server"]["kwargs"])
    stamps["server"] = now() - ctx.t_start
    load = None
    try:
        srv.warmup()
        stamps["warmup"] = now() - ctx.t_start
        lead_in = float(ctx.traffic["lead_in_s"])
        load = Load(srv, ctx, model.vocab)
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
    held = model.held
    ctx.raw.update(
        window_s=w1 - w0, stats0=stats0, stats1=stats1,
        compiles_in_window=compiles1 - compiles0,
        streams=[_stream_record(r, w0, w1) for r in streams
                 if r.sent is not None],
        model={"n_layers": model.n_layers, "d_model": model.d_model,
               "vocab": model.vocab, "n_dense_layers": model.n_dense,
               "n_moe_layers": model.n_moe_layers, "d_ff": model.d_ff,
               "d_expert": model.d_expert, "n_shared": model.n_shared,
               "experts_held": held[1] - held[0],
               "n_routed_experts": model.n_experts,
               "top_k": model.top_k, "n_heads": model.n_heads,
               "q_rank": model.q_rank, "kv_rank": model.kv_rank,
               "nope": model.nope, "rope": model.rope,
               "v_dim": model.v_dim, "window": stats1["window"]},
        stats_delta={k: stats1[k] - stats0[k] for k in stats1
                     if isinstance(stats1[k], int)
                     and not isinstance(stats1[k], bool)},
        moe_delta={k: stats1["moe"].get(k, 0) - stats0["moe"].get(k, 0)
                   for k in ("steps", "moe_slots", "experts_touched")},
        unnamed_gap="scheduler")
    judged = [r for r in streams if not r.cut]
    failed = [r for r in judged if r.error is not None
              or len(r.tokens) != r.asked]
    # the reference runs once the memory has been read and the server's
    # pool is freed: the weights are the benchmark's own and stay
    load.srv = srv = None
    for rec in streams:
        rec.req = None
    gc.collect()
    t_check = now()
    check = _check(ctx, cfg, model, params, judged)
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


def _check(ctx, cfg, model, params, judged):
    """The served tokens against the plain reference, once the window
    has closed: the longest finished request and ``check.requests``
    drawn from the seed (more, in the seed's order, until
    ``check.min_tokens`` served tokens are in the sample), each
    teacher-forced through the float32 reference over its prompt and
    ALL its served tokens. Read, in standard deviations of the
    reference's logits: the mean gap by which a served token's logit
    lies below the reference's best (compared against
    ``check.limits``) and the widest gap (read only). With
    ``--control`` the float8 control stands in the program's place: the
    numbers are those of the tokens IT puts first at each position of
    the same sequences (the program's own go to ``raw.check.program``).
    Also read, never compared: ``routing_differs_share``, the share of
    (position, expert layer) pairs of the longest sample's first
    ``check.routing_positions`` positions at which the program's router
    (on its prefill path) and the reference's chose different sets of
    experts."""
    reference = importlib.import_module(cfg["reference"]["import"])
    spec = ctx.traffic["check"]
    kwargs = cfg["model"]["kwargs"]
    done = [r for r in judged if r.error is None and r.tokens
            and len(r.tokens) == r.asked]
    samples, routing = [], None
    if done:
        longest = max(range(len(done)), key=lambda i: (
            len(done[i].prompt) + done[i].asked, -i))
        order = [int(i) for i in traffic_mod.rng(ctx.seed, 3).permutation(
            len(done))]
        picks = [longest] + [i for i in order if i != longest]
        rung = max(cfg["server"]["kwargs"]["seq_ladder"])
        rows = ctx.traffic["output_len"]["max"]
        tokens = 0
        for n, i in enumerate(picks):
            if n > spec["requests"] and tokens >= spec["min_tokens"]:
                break
            rec = done[i]
            routed = [] if n == 0 else None
            samples.append(reference.teacher_forced(
                params, rec.prompt, np.asarray(rec.tokens), rung + rows,
                rows, kwargs, model.held, control=ctx.args.control,
                routed=routed))
            tokens += len(rec.tokens)
            if n == 0:
                routing = _routing_differs(
                    model, params, rec, routed, spec["routing_positions"])
    tokens = sum(s["tokens"] for s in samples)

    def worst(key):
        return max(s[key] for s in samples) if samples else None

    def mean(key):
        return sum(s[key] * s["tokens"] for s in samples) / tokens \
            if samples else None

    def readings(place):
        return {"gap_worst_std": worst(place + "worst"),
                "gap_mean_std": mean(place + "mean")}

    read = readings("control_" if ctx.args.control else "")
    out = {"samples": samples, "tokens": tokens, "readings": read,
           "routing_differs_share": routing,
           "compared": {name: {"value": read[name], "limit": limit}
                        for name, limit in spec["limits"].items()}}
    if ctx.args.control and samples:
        out["program"] = readings("")
    return out


def _routing_differs(model, params, rec, routed, positions):
    """Share of (position, expert layer) pairs over the first
    ``positions`` of prompt + served tokens where the program's chosen
    set of experts is not the reference's."""
    import jax
    seq = np.concatenate([rec.prompt, np.asarray(rec.tokens, np.int32)])
    n = min(positions, len(seq))
    padded = np.zeros((1, positions), np.int32)
    padded[0, :n] = seq[:n]
    mine = np.sort(np.asarray(jax.jit(model.routing)(params, padded)),
                   axis=-1)[:, :n]
    theirs = np.sort(np.stack([np.asarray(r) for r in routed]),
                     axis=-1)[:, :n]
    return float((mine != theirs).any(axis=-1).mean())
