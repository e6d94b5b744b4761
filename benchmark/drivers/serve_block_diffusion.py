"""Driver for serving cells whose model generates by diffusion over
blocks: the load, the clock and the records of ``serve_decode``
(imported, the same objects), the weights of ``serve_latent_moe``, and
its own check against ``benchmark/reference/block_diffusion_lm.py`` —
every served token in the denoising pass that chose it, which the
server's request keeps (``DecodeRequest.unmask_pass``).

One driver whose configuration names its reference is a ``benchmark``
issue (PERF.md section 7); until then a third model is a third driver.
"""
from __future__ import annotations

import gc
import importlib
import time

import numpy as np

from .. import harness, traffic as traffic_mod
from ..harness import now
from .serve_decode import Load, _sleep_until, _stream_record
from .serve_latent_moe import make_params


class BlockLoad(Load):
    """``serve_decode.Load`` for a block model: prompts draw no mask
    token, and a finished stream keeps the record of its passes."""

    def __init__(self, srv, ctx, model):
        # ids uniform over the vocabulary without the mask id: drawn
        # over one id fewer, those from the mask id on moved up by one
        super().__init__(srv, ctx, model.vocab - 1)
        self.mask_id = model.mask_token_id
        self.block = model.block_length
        self.passes = {}

    def _send(self, rec):
        rec.prompt = (rec.prompt + (rec.prompt >= self.mask_id)).astype(
            np.int32)
        return super()._send(rec)

    def _consume(self, rec):
        super()._consume(rec)
        req = rec.req
        if req is None or rec.error is not None:
            return
        # the pass that unmasked each served token, and the rest of the
        # last block where the answer was cut inside it (the block was
        # denoised whole: the reference needs what stood there)
        used = (len(req.prompt) + len(req.generated)) % self.block
        cut = used and req.blk_x is not None and not any(req.blk_masked)
        self.passes[id(rec)] = (
            np.asarray(req.unmask_pass, int),
            (np.asarray(req.blk_x[used:] if cut else [], np.int32),
             np.asarray(req.blk_when[used:] if cut else [], int)))


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
        load = BlockLoad(srv, ctx, model)
        t0 = now()
        load.start(t0, lead_in + ctx.seconds)
        w0, w1 = t0 + lead_in, t0 + lead_in + ctx.seconds
        _sleep_until(w0)
        ctx.raw["setup_s"] = now() - ctx.t_start
        ctx.raw["w0_unix"] = time.time()
        stats0, compiles0 = srv.stats(), ctx.compiles.count
        if ctx.tracing:
            # late enough to hold admissions (the traffic file's
            # trace_why), and inside a window of any length
            _sleep_until(w0 + min(
                ctx.traffic["trace_after_s"],
                max(ctx.seconds - ctx.traffic["trace_s"], 0)))
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
        model={"n_layers": model.n_layers, "d_model": model.d_model,
               "vocab": model.vocab, "n_moe_layers": model.n_layers,
               "d_expert": model.d_expert,
               "experts_held": held[1] - held[0],
               "n_routed_experts": model.n_experts,
               "top_k": model.top_k, "n_heads": model.n_heads,
               "n_kv_heads": model.n_kv_heads, "head_dim": model.head_dim,
               "block_length": model.block_length,
               "window": stats1["window"]},
        stats_delta={k: stats1[k] - stats0[k] for k in stats1
                     if isinstance(stats1[k], int)
                     and not isinstance(stats1[k], bool)},
        moe_delta=delta("moe", ("steps", "moe_slots", "experts_touched")),
        block_delta=delta("block", (
            "denoise_passes", "commit_passes", "fused_commits",
            "tokens_unmasked", "blocks_committed")),
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
    check = _check(ctx, cfg, model, params, judged, load.passes)
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


def _check(ctx, cfg, model, params, judged, passes):
    """The served tokens against the plain reference, once the window
    has closed: the longest finished request and ``check.requests``
    drawn from the seed (more, in the seed's order, until
    ``check.min_tokens`` served tokens are in the sample). The reference
    runs its clean pass once over prompt + answer and one noisy pass for
    each denoising pass the program made, and reads every served token
    at its row of the pass that CHOSE it (``teacher_forced`` there).
    Read, in standard deviations of the reference's logits: the mean gap
    by which a served token's logit lies below the reference's best
    (compared against ``check.limits``) and the widest gap (read only).
    With ``--control`` the float8 control stands in the program's
    place: the numbers are those of the tokens IT puts first at the same
    rows of the same passes (the program's own go to
    ``raw.check.program``). Also read, never compared:
    ``unmask_differs_share``, the share of (block, pass) pairs in which
    the reference's rule on the reference's confidences unmasks another
    set of positions than the program did, and ``routing_differs_share``
    over the first ``check.routing_positions`` positions of the longest
    sample's clean pass."""
    reference = importlib.import_module(cfg["reference"]["import"])
    spec = ctx.traffic["check"]
    kwargs = cfg["model"]["kwargs"]
    done = [r for r in judged if r.error is None and r.tokens
            and len(r.tokens) == r.asked and id(r) in passes]
    samples, routing = [], None
    if done:
        longest = max(range(len(done)), key=lambda i: (
            len(done[i].prompt) + done[i].asked, -i))
        order = [int(i) for i in traffic_mod.rng(ctx.seed, 3).permutation(
            len(done))]
        picks = [longest] + [i for i in order if i != longest]
        block = model.block_length
        padded = -(-(max(cfg["server"]["kwargs"]["seq_ladder"])
                     + ctx.traffic["output_len"]["max"]) // block) * block
        tokens = 0
        for n, i in enumerate(picks):
            if n > spec["requests"] and tokens >= spec["min_tokens"]:
                break
            rec = done[i]
            when, tail = passes[id(rec)]
            routed = [] if n == 0 else None
            samples.append(reference.teacher_forced(
                params, rec.prompt, np.asarray(rec.tokens, np.int32), when,
                tail, padded, kwargs, control=ctx.args.control,
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
           "unmask_differs_share": mean("unmask_differs"),
           "routing_differs_share": routing,
           "compared": {name: {"value": read[name], "limit": limit}
                        for name, limit in spec["limits"].items()}}
    if ctx.args.control and samples:
        out["program"] = readings("")
    return out


def _routing_differs(model, params, rec, routed, positions):
    """Share of (position, layer) pairs over the first ``positions`` of
    prompt + served tokens where the program's chosen set of experts (on
    its prefill path) is not the reference's (on its clean pass)."""
    import jax
    seq = np.concatenate([rec.prompt, np.asarray(rec.tokens, np.int32)])
    block = model.block_length
    n = min(positions, len(seq)) // block * block   # whole blocks only
    padded = np.zeros((1, positions), np.int32)
    padded[0, :n] = seq[:n]
    mine = np.sort(np.asarray(jax.jit(model.routing)(params, padded)),
                   axis=-1)[:, :n]
    theirs = np.sort(np.stack([np.asarray(r) for r in routed]),
                     axis=-1)[:, :n]
    return float((mine != theirs).any(axis=-1).mean())
