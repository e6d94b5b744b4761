"""Driver for serving cells whose queue mixes LONG and SHORT prompts
over a model that keeps a ring of a window's keys a row beside its pages
(sliding-window layers with a full-attention layer a period, over routed
experts): ``serve_latent_moe``'s weights, clock, records and comparison
— the same objects — with a load of its own and a sample of its own.

The load: a closed loop whose every client sends ONE fixed sequence of
request shapes whatever the seed (:func:`shapes`: long or short by the
traffic file's ``long_share``, the prompt's length from ``prompt_len``
or ``short_prompt_len``, the answer's from ``output_len``, each by
``traffic.draw`` from the fixed stream :data:`SHAPES_DRAW`); ``--seed``
draws the token ids and the weights. Two seeds then differ by timing
alone, and a tail that is one step plus one long prefill is the same
tail in every run (PR 42's lesson for the open loop, applied to a closed
one).

The sample that decides ``correct``: the longest finished request, then
requests in the seed's order until the sample holds at least
``check.min_long`` long ones, ``check.min_short`` short ones,
``check.requests`` beside the longest and ``check.min_tokens`` served
tokens — each teacher-forced through the configuration's reference at
the length of its own rung (two compiled shapes, not one at the longest).
"""
from __future__ import annotations

import gc
import importlib
import time

import numpy as np

from .. import harness, traffic as traffic_mod
from ..harness import now
from . import serve_latent_moe as base
from .serve_decode import Load, Stream, _sleep_until, _stream_record

SHAPES_DRAW = 44     # the one draw of every client's request shapes (PR 44)


def shapes(stream, traffic):
    """Endless request shapes of one client, the same for every seed:
    ``(long, prompt length, answer length)``."""
    gen = traffic_mod.rng(SHAPES_DRAW, 1, stream)
    while True:
        long = bool(gen.random() < traffic["long_share"])
        size = traffic_mod.draw(
            gen, traffic["prompt_len" if long else "short_prompt_len"])
        yield long, size, traffic_mod.draw(gen, traffic["output_len"])


class FixedShapesLoad(Load):
    """``serve_decode.Load`` whose closed-loop clients take their request
    shapes from :func:`shapes` and their token ids from the seed."""

    def _client(self, stream):
        ids = traffic_mod.rng(self.ctx.seed, 1, stream)
        for _long, size, asked in shapes(stream, self.traffic):
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
    params = base.make_params(model, cfg["weights"], ctx.seed)
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
    held = model.held
    ladder = sorted(cfg["server"]["kwargs"]["seq_ladder"])
    ctx.raw.update(
        window_s=w1 - w0, stats0=stats0, stats1=stats1,
        compiles_in_window=compiles1 - compiles0,
        streams=[_stream_record(r, w0, w1) for r in streams
                 if r.sent is not None],
        model={"n_layers": model.n_layers, "d_model": model.d_model,
               "vocab": model.vocab, "n_dense_layers": sum(model.dense),
               "n_moe_layers": model.n_moe_layers, "d_ff": model.d_ff,
               "d_expert": model.d_expert, "d_shared": model.d_shared,
               "n_shared": 1, "experts_held": held[1] - held[0],
               "n_routed_experts": model.n_experts, "top_k": model.top_k,
               "heads": list(model.heads), "kinds": list(model.kinds),
               "n_kv_heads": model.n_kv_heads, "head_dim": model.head_dim,
               "ring_window": model.window, "long_rung": ladder[-1],
               "window": stats1["window"]},
        stats_delta={k: stats1[k] - stats0[k] for k in stats1
                     if isinstance(stats1[k], int)
                     and not isinstance(stats1[k], bool)},
        moe_delta={k: stats1["moe"].get(k, 0) - stats0["moe"].get(k, 0)
                   for k in ("steps", "moe_slots", "experts_touched",
                             "ring_rows_wrapped", "global_pages_live",
                             "ring_bytes")},
        unnamed_gap="scheduler")
    judged = [r for r in streams if not r.cut]
    failed = [r for r in judged if r.error is not None
              or len(r.tokens) != r.asked]
    # the reference runs once the memory has been read and the server's
    # pool and rings are freed: the weights are the benchmark's own and
    # stay
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


def _sample(done, seed, spec, long_over):
    """Indices into ``done``: the longest request, then the seed's order
    until the sample has its long ones, its short ones, its requests and
    its tokens (module docstring)."""
    longest = max(range(len(done)), key=lambda i: (
        len(done[i].prompt) + done[i].asked, -i))
    order = [int(i) for i in traffic_mod.rng(seed, 3).permutation(len(done))
             if int(i) != longest]
    picks, want = [longest], {True: spec.get("min_long", 0),
                              False: spec.get("min_short", 0)}

    def have(long):
        return sum((len(done[i].prompt) > long_over) == long for i in picks)

    for long in (True, False):      # first what the sample must hold
        for i in order:
            if have(long) >= want[long]:
                break
            if i not in picks and (len(done[i].prompt) > long_over) == long:
                picks.append(i)
    for i in order:                 # then the seed's order, to the counts
        if len(picks) > spec["requests"] and sum(
                len(done[j].tokens) for j in picks) >= spec["min_tokens"]:
            break
        if i not in picks:
            picks.append(i)
    return picks


def _check(ctx, cfg, model, params, judged, ladder):
    """``serve_latent_moe._check`` over :func:`_sample`'s requests, each
    at its own rung's length."""
    reference = importlib.import_module(cfg["reference"]["import"])
    spec = ctx.traffic["check"]
    kwargs = cfg["model"]["kwargs"]
    done = [r for r in judged if r.error is None and r.tokens
            and len(r.tokens) == r.asked]
    samples, routing = [], None
    rows = ctx.traffic["output_len"]["max"]
    short_rung = ladder[0] if len(ladder) > 1 else 0
    for n, i in enumerate(_sample(done, ctx.seed, spec, short_rung)
                          if done else []):
        rec = done[i]
        rung = min(r for r in ladder if r >= len(rec.prompt))
        routed = [] if n == 0 else None
        samples.append(reference.teacher_forced(
            params, rec.prompt, np.asarray(rec.tokens), rung + rows, rows,
            kwargs, model.held, control=ctx.args.control, routed=routed))
        if n == 0:
            routing = base._routing_differs(
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
           "long_samples": sum(s["prompt_len"] > short_rung
                               for s in samples),
           "routing_differs_share": routing,
           "compared": {name: {"value": read[name], "limit": limit}
                        for name, limit in spec["limits"].items()}}
    if ctx.args.control and samples:
        out["program"] = readings("")
        out["window_minus_one"] = readings("window_minus_one_")
    return out
