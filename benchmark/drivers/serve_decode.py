"""Driver for cells that serve token streams: builds the configuration's
model and server, offers the traffic file's load from this process, and
times every token on the benchmark's own clock.

The server is any class with ``warmup()``, ``submit(prompt,
max_new_tokens=) -> request`` whose ``tokens(timeout=)`` iterates the
stream and whose ``cancel()`` ends it, ``stats()`` and ``stop()``; the
model any class with ``init_params(seed)``. Both come from the
configuration file by import path.
"""
from __future__ import annotations

import gc
import threading
import time

import numpy as np

from .. import harness, traffic as traffic_mod
from ..harness import now, span

TOKEN_TIMEOUT_S = 120.0


class Stream:
    """One request as its client saw it."""

    __slots__ = ("due", "sent", "asked", "prompt", "times", "tokens",
                 "error", "done", "cut", "req")

    def __init__(self, due, prompt, asked):
        self.due, self.prompt, self.asked = due, prompt, asked
        self.sent = None
        self.times, self.tokens = [], []
        self.error, self.done, self.cut, self.req = None, False, False, None


class Load:
    """The generator: ``closed`` clients or a ``poisson`` schedule, one
    thread per client or per open request, each stamping its tokens."""

    def __init__(self, srv, ctx, vocab):
        self.srv, self.ctx, self.vocab = srv, ctx, vocab
        self.traffic = ctx.traffic
        self.stop = threading.Event()
        self.lock = threading.Lock()
        self.streams, self.threads = [], []

    def _send(self, rec):
        with self.lock:
            self.streams.append(rec)
        try:
            with span("submit"):
                rec.req = self.srv.submit(rec.prompt,
                                          max_new_tokens=rec.asked)
        except Exception as exc:            # shed, closed: a failure
            rec.error, rec.done = repr(exc), True
            rec.sent = now()
            return False
        rec.sent = now()
        return True

    def _consume(self, rec):
        try:
            for tok in rec.req.tokens(timeout=TOKEN_TIMEOUT_S):
                rec.times.append(now())
                rec.tokens.append(tok)
        except Exception as exc:            # timeout, preemption, model
            rec.error = repr(exc)
        rec.done = True

    def _client(self, stream):
        for prompt, asked in traffic_mod.requests(
                self.ctx.seed, stream, self.traffic, self.vocab):
            if self.stop.is_set():
                return
            rec = Stream(now(), prompt, asked)
            if self._send(rec):
                self._consume(rec)

    def _schedule(self, t0, horizon_s):
        for at, prompt, asked in traffic_mod.schedule(
                self.ctx.seed, self.traffic, self.vocab, horizon_s):
            due = t0 + at
            while not self.stop.is_set():
                wait = due - now()
                if wait <= 0:
                    break
                self.stop.wait(min(wait, 0.05))
            if self.stop.is_set():
                return
            rec = Stream(due, prompt, asked)
            if self._send(rec):
                self._spawn(self._consume, rec)

    def _spawn(self, fn, *args):
        t = threading.Thread(target=fn, args=args, daemon=True)
        self.threads.append(t)
        t.start()

    def start(self, t0, horizon_s):
        arrivals = self.traffic["arrivals"]
        if arrivals["kind"] == "closed":
            for i in range(arrivals["clients"]):
                self._spawn(self._client, i)
        elif arrivals["kind"] == "poisson":
            self._spawn(self._schedule, t0, horizon_s)
        else:
            raise ValueError("traffic: unknown arrivals %r"
                             % (arrivals["kind"],))

    def finish(self):
        """Stop offering load; give open streams ``drain_s`` (an
        open-loop cell) or cut them at once (``unfinished_at_end:
        "cut"``, a closed loop, whose clients always have one open);
        then end every stream and wait for every thread."""
        self.stop.set()
        cut = self.traffic.get("unfinished_at_end") == "cut"
        deadline = now() + (0.0 if cut else self.traffic["drain_s"])
        with self.lock:
            open_ = [r for r in self.streams if not r.done]
        for rec in open_:
            while not rec.done and now() < deadline:
                self.stop.wait(0.01)
            if not rec.done:
                rec.cut = cut
                if not cut:
                    rec.error = "unfinished %.0f s after the window" \
                        % self.traffic["drain_s"]
                if rec.req is not None:
                    rec.req.cancel()
        for t in list(self.threads):
            t.join(timeout=TOKEN_TIMEOUT_S)
        return [t for t in self.threads if t.is_alive()]


def _sleep_until(t):
    while True:
        wait = t - now()
        if wait <= 0:
            return
        time.sleep(min(wait, 0.2))


def _stream_record(rec, w0, w1):
    """What the metric readers see of one stream."""
    return {"due": rec.due - w0, "sent": rec.sent - w0,
            "asked": rec.asked, "prompt_len": int(len(rec.prompt)),
            "times": [t - w0 for t in rec.times],
            "error": rec.error, "cut": rec.cut,
            "in_window": w0 <= rec.due < w1}


def make_params(model, spec, seed):
    """Every weight in one jitted call, on the device, from the seed, in
    the shapes and types ``model.init_params`` gives them. The values
    are the benchmark's own, by the configuration's ``weights`` block: a
    matrix ``(fan_in, fan_out)`` is normal with standard deviation
    ``fan_in ** -0.5``, a lookup table has the deviation the block
    names, a vector is 1 where its name ends in ``gain_suffix`` and 0
    elsewhere — activations of order 1, as in a trained model, so that
    rounding does not decide the attention pattern (PERF.md, finding on
    the toy weights)."""
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
                std = spec["tables"].get(name, shape[0] ** -0.5)
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
    ctx.raw.update(
        window_s=w1 - w0, stats0=stats0, stats1=stats1,
        compiles_in_window=compiles1 - compiles0,
        streams=[_stream_record(r, w0, w1) for r in streams
                 if r.sent is not None],
        model={"n_layers": model.n_layers, "d_model": model.d_model,
               "d_ff": model.d_ff, "vocab": model.vocab},
        stats_delta={k: stats1[k] - stats0[k] for k in stats1
                     if isinstance(stats1[k], int)
                     and not isinstance(stats1[k], bool)},
        unnamed_gap="scheduler")
    # every stream that was offered and not cut by the window's end is
    # judged, the lead-in's too; latencies keep to the window
    judged = [r for r in streams if not r.cut]
    failed = [r for r in judged if r.error is not None
              or len(r.tokens) != r.asked]
    # the reference runs once the memory has been read and the server's
    # pools are freed: the weights are the benchmark's own and stay
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
    has closed: a sample of finished requests drawn from the seed, the
    longest of them all in it, some hundreds of served tokens. The
    reference runs once over each prompt with ALL its served tokens
    (teacher-forced) and two numbers are read, both in standard
    deviations of the logits: the widest gap by which a served token's
    logit lies below the reference's best, and the mean gap. Compared
    are those that the traffic file gives a limit (``check.limits``, set
    from chip readings: PERF.md section 2); both go to
    ``raw.check.readings``. With ``--control`` the bfloat16 control
    stands in the program's place: the numbers are those of the tokens
    IT puts first at each position of the same sequences (the program's
    own go to ``raw.check.program``)."""
    from ..reference import decoder_lm
    spec = ctx.traffic["check"]
    done = [r for r in judged if r.error is None and r.tokens
            and len(r.tokens) == r.asked]
    samples = []
    if done:
        longest = max(range(len(done)), key=lambda i: (
            len(done[i].prompt) + done[i].asked, -i))
        picks = traffic_mod.rng(ctx.seed, 3).choice(
            len(done), size=min(spec["requests"], len(done)),
            replace=False)
        ladder = cfg["server"]["kwargs"]["seq_ladder"]
        rows = ctx.traffic["output_len"]["max"]
        for i in dict.fromkeys([longest, *(int(i) for i in picks)]):
            rec = done[i]
            rung = min(r for r in ladder if r >= len(rec.prompt))
            samples.append(decoder_lm.teacher_forced(
                params, rec.prompt, np.asarray(rec.tokens), rung + rows,
                rows, model.n_layers, model.n_heads, model.head_dim,
                control=ctx.args.control))
    tokens = sum(s["tokens"] for s in samples)

    def worst(key):
        return max(s[key] for s in samples) if samples else None

    def mean(key):
        return sum(s[key] * s["tokens"] for s in samples) / tokens \
            if samples else None

    # the control takes the program's place: its numbers are the ones
    # compared, so a run with ``--control`` has to come out not correct
    def readings(place):
        return {"gap_worst_std": worst(place + "worst"),
                "gap_mean_std": mean(place + "mean")}

    read = readings("control_" if ctx.args.control else "")
    out = {"samples": samples, "tokens": tokens, "readings": read,
           "compared": {name: {"value": read[name], "limit": limit}
                        for name, limit in spec["limits"].items()}}
    if ctx.args.control and samples:
        out["program"] = readings("")
    return out
