"""Driver for serving cells whose model drafts for itself: latent
attention, routed experts, several residual streams, and a next-token
module whose draft a two-position verify step accepts or rolls back. The
load, the clock and the records of ``serve_decode`` (imported, the same
objects), the weights of ``serve_latent_moe`` with the vectors the
configuration names, and its own check against
``benchmark/reference/hyper_latent_moe_lm.py`` — the served tokens under
the reference's main logits AND the server's recorded drafts
(``DecodeRequest.drafts``) under the reference's module logits.

One driver whose configuration names its reference is a ``benchmark``
issue (PERF.md section 7); until then a fourth model is a fourth driver.
"""
from __future__ import annotations

import gc
import importlib
import time

import numpy as np

from .. import harness, traffic as traffic_mod
from ..harness import now
from . import serve_latent_moe
from .serve_decode import Load, _sleep_until, _stream_record


def make_params(model, spec, seed):
    """``serve_latent_moe.make_params`` (matrices by their fan-in, tables
    by the deviation the block names, gains 1, other vectors 0) with the
    vectors the block gives a value: a vector whose name ends in a key
    of ``spec["vectors"]`` is that list (the gates and biases of the
    stream mixing, which decide how far ``H_res`` lies from uniform and
    from the identity)."""
    import jax
    import jax.numpy as jnp
    params = dict(serve_latent_moe.make_params(model, spec, seed))
    for name in params:
        for suffix, value in spec.get("vectors", {}).items():
            if name.endswith(suffix):
                value = jnp.asarray(value, params[name].dtype)
                assert value.shape == params[name].shape, name
                params[name] = jax.device_put(value)
    return params


class SpecLoad(Load):
    """``serve_decode.Load`` that keeps, for a finished stream, the
    draft that was verified against each of its tokens."""

    def __init__(self, srv, ctx, vocab):
        super().__init__(srv, ctx, vocab)
        self.drafts = {}

    def _consume(self, rec):
        super()._consume(rec)
        if rec.req is not None and rec.error is None:
            self.drafts[id(rec)] = np.asarray(rec.req.drafts, np.int64)


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
        load = SpecLoad(srv, ctx, model.vocab)
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

    def delta(group, keys):
        return {k: stats1[group].get(k, 0) - stats0[group].get(k, 0)
                for k in keys}

    ctx.raw.update(
        window_s=w1 - w0, stats0=stats0, stats1=stats1,
        compiles_in_window=compiles1 - compiles0,
        streams=[_stream_record(r, w0, w1) for r in streams
                 if r.sent is not None],
        # ``n_layers``: the main model's blocks; ``n_draft_layers``: the
        # next-token module's; ``n_moe_layers``: the expert layers a
        # step runs, the module's among them (the program's counters
        # sum over them all)
        model={"n_layers": model.n_layers,
               "n_draft_layers": model.cache_layers - model.n_layers,
               "d_model": model.d_model, "vocab": model.vocab,
               "n_dense_layers": model.n_dense,
               "n_moe_layers": model.n_moe_layers, "d_ff": model.d_ff,
               "d_expert": model.d_expert, "n_shared": model.n_shared,
               "experts_held": held[1] - held[0],
               "n_routed_experts": model.n_experts,
               "top_k": model.top_k, "n_heads": model.n_heads,
               "q_rank": model.q_rank, "kv_rank": model.kv_rank,
               "nope": model.nope, "rope": model.rope,
               "v_dim": model.v_dim, "streams": model.hc,
               "verify_positions": model.draft_length + 1,
               "window": stats1["window"]},
        stats_delta={k: stats1[k] - stats0[k] for k in stats1
                     if isinstance(stats1[k], int)
                     and not isinstance(stats1[k], bool)},
        moe_delta=delta("moe", ("steps", "moe_slots", "experts_touched")),
        spec_delta=delta("spec", ("drafts_verified", "drafts_accepted",
                                  "tokens_out", "positions_run")),
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
    check = _check(ctx, cfg, model, params, judged, load.drafts)
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


def _check(ctx, cfg, model, params, judged, drafts):
    """The served tokens and the recorded drafts against the plain
    reference, once the window has closed: the longest finished request
    and ``check.requests`` drawn from the seed (more, in the seed's
    order, until ``check.min_tokens`` served tokens are in the sample),
    each teacher-forced through the float32 reference over its prompt
    and ALL its served tokens. Read, in standard deviations of the
    reference's logits: ``gap_mean_std``, the mean gap by which a served
    token's logit lies below the best of the reference's MAIN logits,
    and ``draft_gap_mean_std``, the same for the server's recorded
    drafts under the reference's MODULE logits at the position that
    made each (both compared against ``check.limits``), and the widest
    of each (read only). With ``--control`` each of the reference's
    controls stands in the program's place, one after the other: the
    numbers are those of the tokens IT puts first at each position of
    the same sequences, under ``<control>.<name>``; those the traffic
    file lists as ``check.controls_compared`` are compared against the
    same limits (the run has to come out not correct by every one of
    them), the others are read (the program's own go to
    ``raw.check.program``). Also read, never compared:
    ``routing_differs_share`` over the longest sample's first
    ``check.routing_positions`` positions, and ``reference_accept``, the
    share of drafted positions at which the reference's own module puts
    the served token first."""
    reference = importlib.import_module(cfg["reference"]["import"])
    spec = ctx.traffic["check"]
    kwargs = cfg["model"]["kwargs"]
    controls = tuple(reference.CONTROLS) if ctx.args.control else ()
    done = [r for r in judged if r.error is None and r.tokens
            and len(r.tokens) == r.asked and id(r) in drafts]
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
                params, rec.prompt, np.asarray(rec.tokens), drafts[id(rec)],
                rung + rows, rows, kwargs, model.held, controls=controls,
                routed=routed))
            tokens += len(rec.tokens)
            if n == 0:
                routing = serve_latent_moe._routing_differs(
                    model, params, rec, routed, spec["routing_positions"])

    def worst(key):
        found = [s[key] for s in samples if key in s]
        return max(found) if found else None

    def mean(key, weight):
        found = [(s[key], s[weight]) for s in samples if key in s]
        total = sum(w for _v, w in found)
        return sum(v * w for v, w in found) / total if total else None

    def readings(place):
        return {"gap_worst_std": worst(place + "worst"),
                "gap_mean_std": mean(place + "mean", "tokens"),
                "draft_gap_worst_std": worst(place + "draft_worst"),
                "draft_gap_mean_std": mean(place + "draft_mean", "drafts")}

    read = readings("")
    out = {"samples": samples,
           "tokens": sum(s["tokens"] for s in samples),
           "drafts": sum(s["drafts"] for s in samples),
           "readings": read, "routing_differs_share": routing,
           "reference_accept": mean("accept", "drafts")}
    if controls:
        out["program"] = read
        out["controls"] = {c: readings(c + "_") for c in controls}
        listed = spec.get("controls_compared", controls)
        out["compared"] = {
            "%s.%s" % (c, name): {"value": out["controls"][c][name],
                                  "limit": limit}
            for c in controls if c in listed
            for name, limit in spec["limits"].items()}
    else:
        out["compared"] = {name: {"value": read[name], "limit": limit}
                           for name, limit in spec["limits"].items()}
    return out
