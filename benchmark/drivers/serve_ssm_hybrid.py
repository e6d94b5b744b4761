"""Driver for serving cells whose model keeps a selective scan's state a
row beside its pages (state-space layers with a full-attention layer a
period, a dense MLP, the head tied to the embedding): ``serve_decode``'s
load, clock and records and ``serve_latent_moe``'s matrices — the same
objects — with a draw of its own for what a state-space layer keeps that
is no matrix, a load of its own and a check against
``benchmark/reference/ssm_hybrid_lm.py``.

The weights: ``serve_latent_moe.make_params`` fills every vector with 1
or 0, and a selective scan needs its step DRAWN: at ``b_dt = 0`` and
``A_log = 0`` a step's decay would be ``exp(-softplus(.))``, about 0.5 —
a state that forgets in a few tokens and hides a wrong slot or a lost
state from ``correct``. The configuration's ``weights.mamba`` names
Mamba's published initialisation, which :func:`make_params` draws from
the seed (the model's own ``init_params`` draws the same).

The load: a closed loop whose every client sends ONE fixed sequence of
request shapes whatever the seed (:func:`shapes`: the prompt's length
from ``prompt_len``, the answer's from ``output_len``, each by
``traffic.draw`` from the fixed stream :data:`SHAPES_DRAW`); ``--seed``
draws the token ids and the weights. Two seeds then offer every step the
same lanes and differ by timing alone: the tail of the gap between
tokens is the widest mixed step, and how many of a window's admissions
take it is the draw's (PR 41 and PR 43 were lost to a tail that moved
with the draw; ``serve_window_moe.shapes`` is the same cure).
"""
from __future__ import annotations

import gc
import importlib
import math
import time

import numpy as np

from .. import harness, traffic as traffic_mod
from ..harness import now
from . import serve_latent_moe as base
from .serve_decode import Load, Stream, _sleep_until, _stream_record

SHAPES_DRAW = 52     # the one draw of every client's request shapes (PR 52)


def make_params(model, spec, seed):
    """``serve_latent_moe.make_params``' weights (a matrix normal at
    ``fan_in ** -0.5``, the embedding at ``spec["tables"]``' deviation, a
    gain 1), then what ``spec["mamba"]`` names, on the device, from the
    seed: ``A_log = log(1..N)`` a channel, ``b_dt`` the inverse softplus
    of a step drawn log-uniformly in ``mamba.dt``, ``D_skip =
    mamba.D``, ``W_dt`` uniform in ``+-R ** -0.5`` and ``b_conv`` in
    ``+-K ** -0.5``."""
    import jax
    import jax.numpy as jnp
    params = base.make_params(model, spec, seed)
    how = spec["mamba"]
    drawn = sorted(n for n in params if n.rsplit(".", 1)[-1] in (
        "A_log", "dt_b", "D", "wdt", "conv_b"))

    def make(key):
        out = {}
        for i, name in enumerate(drawn):
            k, leaf = jax.random.fold_in(key, i), name.rsplit(".", 1)[1]
            shape, dtype = params[name].shape, params[name].dtype
            if leaf == "A_log":
                out[name] = jnp.broadcast_to(jnp.log(jnp.arange(
                    1, shape[0] + 1, dtype=jnp.float32))[:, None], shape)
            elif leaf == "dt_b":
                lo, hi = (math.log(v) for v in how["dt"])
                step = jnp.exp(jax.random.uniform(k, shape, jnp.float32,
                                                  lo, hi))
                out[name] = step + jnp.log(-jnp.expm1(-step))
            elif leaf == "D":
                out[name] = jnp.full(shape, how["D"], dtype)
            else:   # W_dt by its rank, b_conv by the kernel's taps
                fan = shape[0] if leaf == "wdt" else model.conv
                out[name] = jax.random.uniform(
                    k, shape, jnp.float32, -fan ** -0.5, fan ** -0.5) \
                    .astype(dtype)
        return out

    params.update(jax.jit(make)(
        jax.random.fold_in(jax.random.PRNGKey(seed), 1 << 20)))
    return params


def shapes(stream, traffic):
    """Endless request shapes of one client, the same for every seed:
    ``(prompt length, answer length)``."""
    gen = traffic_mod.rng(SHAPES_DRAW, 1, stream)
    while True:
        size = traffic_mod.draw(gen, traffic["prompt_len"])
        yield size, traffic_mod.draw(gen, traffic["output_len"])


class FixedShapesLoad(Load):
    """``serve_decode.Load`` whose closed-loop clients take their request
    shapes from :func:`shapes` and their token ids from the seed."""

    def _client(self, stream):
        ids = traffic_mod.rng(self.ctx.seed, 1, stream)
        for size, asked in shapes(stream, self.traffic):
            if self.stop.is_set():
                return
            prompt = ids.integers(0, self.vocab, size=size, dtype=np.int32)
            rec = Stream(now(), prompt, asked)
            if self._send(rec):
                self._consume(rec)


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
    ctx.raw.update(
        window_s=w1 - w0, stats0=stats0, stats1=stats1,
        compiles_in_window=compiles1 - compiles0,
        streams=[_stream_record(r, w0, w1) for r in streams
                 if r.sent is not None],
        model={"n_layers": model.n_layers, "d_model": model.d_model,
               "vocab": model.vocab, "d_ff": model.d_ff,
               "state_layers": model.state_layers,
               "cache_layers": model.cache_layers,
               "d_inner": model.d_inner, "d_state": model.d_state,
               "window": stats1["window"]},
        stats_delta={k: stats1[k] - stats0[k] for k in stats1
                     if isinstance(stats1[k], int)
                     and not isinstance(stats1[k], bool)},
        unnamed_gap="scheduler")
    judged = [r for r in streams if not r.cut]
    failed = [r for r in judged if r.error is not None
              or len(r.tokens) != r.asked]
    # the reference runs once the memory has been read and the server's
    # pool and state are freed: the weights are the benchmark's own and
    # stay
    load.srv = srv = None
    for rec in streams:
        rec.req = None
    gc.collect()
    t_check = now()
    check = _check(ctx, cfg, params, judged)
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


def _check(ctx, cfg, params, judged):
    """The served tokens against the plain reference, once the window
    has closed: the longest finished request and ``check.requests`` in
    the seed's order (more until ``check.min_tokens`` served tokens are
    in the sample), each teacher-forced through the float32 reference
    over its prompt and ALL its served tokens, at ONE compiled length
    (the widest rung and the longest answer). Compared against
    ``check.limits``, in standard deviations of the reference's logits:
    the mean gap by which a served token's logit lies below the
    reference's best. With ``--control`` the compared control stands in
    the program's place (the program's own numbers go to
    ``raw.check.program``, the other control's to
    ``raw.check.state_bf16``)."""
    reference = importlib.import_module(cfg["reference"]["import"])
    spec = ctx.traffic["check"]
    kwargs = cfg["model"]["kwargs"]
    done = [r for r in judged if r.error is None and r.tokens
            and len(r.tokens) == r.asked]
    samples = []
    if done:
        longest = max(range(len(done)), key=lambda i: (
            len(done[i].prompt) + done[i].asked, -i))
        order = [int(i) for i in traffic_mod.rng(ctx.seed, 3).permutation(
            len(done))]
        rung = max(cfg["server"]["kwargs"]["seq_ladder"])
        rows = ctx.traffic["output_len"]["max"]
        tokens = 0
        for n, i in enumerate([longest] + [i for i in order
                                           if i != longest]):
            if n > spec["requests"] and tokens >= spec["min_tokens"]:
                break
            rec = done[i]
            samples.append(reference.teacher_forced(
                params, rec.prompt, np.asarray(rec.tokens), rung + rows,
                rows, kwargs, control=ctx.args.control))
            tokens += len(rec.tokens)
    tokens = sum(s["tokens"] for s in samples)

    def readings(place):
        if not samples:
            return {"gap_worst_std": None, "gap_mean_std": None}
        return {"gap_worst_std": max(s[place + "worst"] for s in samples),
                "gap_mean_std": sum(s[place + "mean"] * s["tokens"]
                                    for s in samples) / tokens}

    read = readings("control_" if ctx.args.control else "")
    out = {"samples": samples, "tokens": tokens, "readings": read,
           "compared": {name: {"value": read[name], "limit": limit}
                        for name, limit in spec["limits"].items()}}
    if ctx.args.control and samples:
        out["program"] = readings("")
        out["state_bf16"] = readings("state_bf16_")
    return out
