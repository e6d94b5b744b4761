#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that mxnet_tpu still starts on the chip.

One process drives the two hot paths through the entry points a user
calls, at full width, on synthetic data made from ``--seed``:

    python chip_smoke.py            # one chip: train, kernels, serve
    python chip_smoke.py --chips 4  # four chips: ONLY the cross-chip paths

Default run, in order (any failed check raises, the exit code is then
non-zero and no ``"ok"`` line is printed):

- **device**  ``jax.devices()[0].platform`` must be ``tpu``.
- **train**   ResNet-50 v1 (model zoo, 1000 classes), batch 32 x 3x224x224,
  bf16 AMP policy, hybridized, ``gluon.Trainer`` SGD+momentum through the
  fused step; five steps on one fixed batch.
- **kernels** ``flash_attention`` (causal, with/without segment ids,
  forward and grad), ``flash_decode`` and the paged decode kernel of
  ``kvcache.paged_attention`` (fp32 and int8 cache each) at
  (B,T,H,D)=(4,2048,8,128) against the jnp references, with the Mosaic
  custom call asserted in the lowered program.
- **serve**   ``DecodeServer`` over ``ToyDecoderLM`` (vocab 32000, 4 layers,
  8 heads x 128, d_ff 4096): warmup, 8 concurrent requests, fixed program
  set, tokens compared with a dense float32 forward of the same weights.

``--chips 4`` runs, and nothing else: ``DistributedTrainer`` on a 4-device
``dp`` mesh against the same steps on ``devices[0]`` alone, and a ``Router``
over four ``DecodeServer`` replicas, one per device, against one replica.

Each phase prints one JSON line; the LAST line of a passing run is exactly
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.

Rehearsal without a chip (prints no ``"ok"`` line, proves nothing about the
chip): ``JAX_PLATFORMS=cpu python -c "import chip_smoke;
chip_smoke.rehearse()"`` runs the same phases at a tiny size;
``rehearse(chips=4)`` under ``XLA_FLAGS=--xla_force_host_platform_device_count=4``
the four-chip ones.
"""
from __future__ import annotations

import argparse
import json
import math
import sys
import time

import numpy as np

FULL = dict(
    net="resnet50_v1", classes=1000, image=224, train_batch=32,
    train_steps=5, lr=0.01,
    attn=(4, 2048, 8, 128),
    lm=dict(vocab=32000, n_layers=4, n_heads=8, head_dim=128, d_ff=4096,
            max_len=2048),
    # page size, ladder rungs and the 13-page table (1536 + 32 tokens)
    # are all multiples of the kernels' 128-row blocks
    ladder=(256, 512, 1024, 1536), page_size=128, pool_pages=128, window=8,
    prompt_lens=(100, 1500), n_requests=8, new_tokens=32, check_tokens=8,
    dist_batch=128, dist_steps=3, dist_lr=0.01, dist_rel_tol=0.02,
)
TINY = dict(
    net="resnet18_v1", classes=10, image=32, train_batch=8,
    train_steps=5, lr=0.01,
    attn=(1, 256, 2, 128),
    lm=dict(vocab=64, n_layers=2, n_heads=2, head_dim=16, d_ff=64,
            max_len=128),
    ladder=(32, 64), page_size=16, pool_pages=64, window=4,
    prompt_lens=(5, 60), n_requests=8, new_tokens=8, check_tokens=8,
    # a toy net memorises 16 samples almost at once, and a loss that
    # halves every step amplifies bf16 reduction-order noise
    dist_batch=16, dist_steps=3, dist_lr=1e-4, dist_rel_tol=0.1,
)

# A served token passes when the float32 reference scores it within this
# much of its own best logit (a tie between its top two). That needs the
# server's fp32 matmuls to BE fp32: the serve phase pins
# ``jax_default_matmul_precision`` to "highest", because XLA's TPU default
# multiplies fp32 operands in bf16 passes, which moves these logits
# (std ~6) by up to ~1.7 and flips about one greedy token in seven on
# near-ties (CPU emulation at this width, PERF.md). The phase prints what
# the default costs on this device next to the check.
TOKEN_LOGIT_SLACK = 1e-3


def emit(record):
    print(json.dumps(record), flush=True)


def check(cond, what):
    if not cond:
        raise AssertionError("chip_smoke: " + what)


def device_record():
    import jax
    d = jax.devices()
    return {"platform": d[0].platform, "kind": d[0].device_kind,
            "count": len(d)}


def memory(device):
    stats = device.memory_stats() or {}
    return {k: int(stats[k]) for k in ("bytes_in_use", "peak_bytes_in_use")
            if k in stats}


def on_device(array, device):
    return set(array.devices()) == {device}


class Compiles:
    """compile_watch's compile count and seconds since construction."""

    def __init__(self):
        from mxnet_tpu import compile_watch
        self._watch = compile_watch
        self._base = self._now()

    def _now(self):
        s = self._watch.stats()
        return s["compiles"], s["compile_total_s"]

    def delta(self):
        n, secs = self._now()
        return n - self._base[0], round(secs - self._base[1], 3)


ATTENTION_PATHS = ("flash_attention_pallas", "flash_attention_jnp",
                   "flash_decode_pallas", "flash_decode_jnp",
                   "paged_decode_pallas", "paged_decode_jnp")


def counted_since(before, names):
    """How far each ``profiler.counters()`` entry in ``names`` grew."""
    from mxnet_tpu import profiler
    after = profiler.counters()
    return {n: after.get(n, 0) - before.get(n, 0) for n in names}


def cache_counts():
    from mxnet_tpu import profiler
    c = profiler.counters()
    return {"hits": c.get("jax_cache_hits", 0),
            "misses": c.get("jax_cache_misses", 0)}


# ---------------------------------------------------------------------------
# train
# ---------------------------------------------------------------------------

def _resnet(cfg, seed, rules=None):
    import mxnet_tpu as mx
    from mxnet_tpu import gluon
    from mxnet_tpu.amp import DtypePolicy
    mx.random.seed(seed)
    np.random.seed(seed)       # the initializers draw from numpy's RNG
    net = gluon.model_zoo.vision.get_model(cfg["net"],
                                           classes=cfg["classes"])
    net.initialize(mx.init.Xavier())
    DtypePolicy("bfloat16", rules=rules).apply(net)
    return net


def _batch(cfg, seed, batch):
    import mxnet_tpu as mx
    rs = np.random.RandomState(seed)
    x = rs.standard_normal((batch, 3, cfg["image"], cfg["image"]))
    y = rs.randint(0, cfg["classes"], size=(batch,))
    return (mx.nd.array(x.astype(np.float32)).astype("bfloat16"),
            mx.nd.array(y.astype(np.float32)))


def train_phase(cfg, seed, on_chip):
    import jax
    from mxnet_tpu import autograd, gluon, profiler
    device = jax.devices()[0]
    t0 = time.perf_counter()
    compiles = Compiles()
    before = profiler.counters()
    net = _resnet(cfg, seed)
    net.hybridize()
    trainer = gluon.Trainer(
        net.collect_params(), "sgd",
        {"learning_rate": cfg["lr"], "momentum": 0.9,
         "multi_precision": True})
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    x, y = _batch(cfg, seed, cfg["train_batch"])
    losses, compiles_at = [], []
    for _ in range(cfg["train_steps"]):
        with autograd.record():
            loss = loss_fn(net(x).astype("float32"), y).mean()
        loss.backward()
        trainer.step(1)
        jax.block_until_ready(loss._data)
        losses.append(float(loss.asnumpy()))
        compiles_at.append(compiles.delta()[0])
    params = list(net.collect_params().values())
    misplaced = [p.name for p in params
                 if not on_device(p.data()._data, device)]
    grew = counted_since(before, (
        "fused_step_dispatches", "fused_step_fallbacks",
        "context_accelerator_on_cpu"))
    n_compiles, compile_s = compiles.delta()
    emit({"phase": "train", "net": cfg["net"], "batch": cfg["train_batch"],
          "image": cfg["image"], "dtype": "bfloat16",
          "steps": len(losses), "losses": losses,
          "params": len(params), "params_off_device": len(misplaced),
          "fused_dispatches": grew["fused_step_dispatches"],
          "fused_fallbacks": grew["fused_step_fallbacks"],
          "compiles": n_compiles, "compiles_after_step": compiles_at,
          "compile_s": compile_s, "cache": cache_counts(),
          "wall_s": round(time.perf_counter() - t0, 3),
          "memory": memory(device), "device": device_record()})
    check(all(math.isfinite(v) for v in losses), "train: non-finite loss")
    check(losses[-1] < losses[0], "train: loss did not fall: %s" % losses)
    check(not misplaced, "train: parameters off %s: %s"
          % (device, misplaced[:5]))
    check(grew["fused_step_dispatches"] == len(losses),
          "train: %d fused dispatches for %d steps"
          % (grew["fused_step_dispatches"], len(losses)))
    check(grew["fused_step_fallbacks"] == 0, "train: eager fallback taken")
    check(compiles_at[-1] == compiles_at[1],
          "train: compiled after step 2: %s" % compiles_at)
    if on_chip:
        check(grew["context_accelerator_on_cpu"] == 0,
              "train: an accelerator context resolved to the host")


# ---------------------------------------------------------------------------
# kernels
# ---------------------------------------------------------------------------

def _max_err(got, want, where=None):
    import jax.numpy as jnp
    got = jnp.asarray(got, jnp.float32)
    want = jnp.asarray(want, jnp.float32)
    diff = jnp.abs(got - want)
    if where is not None:
        diff = jnp.where(where, diff, 0.0)
        want = jnp.where(where, want, 0.0)
    return float(jnp.max(diff) / jnp.maximum(1.0, jnp.max(jnp.abs(want))))


# Tolerances by what multiplies: bf16 inputs, and fp32 inputs at JAX's
# default matmul precision (Mosaic, like XLA on the TPU, then multiplies
# fp32 operands in bf16 passes), are held to 2e-2; fp32 inputs with
# ``jax.default_matmul_precision("highest")`` around the call to 2e-3.
KERNEL_CHECKS = {"bfloat16": (("default", 2e-2),),
                 "float32": (("default", 2e-2), ("highest", 2e-3))}


def _precision(name):
    import contextlib
    import jax
    return contextlib.nullcontext() if name == "default" \
        else jax.default_matmul_precision(name)


def kernels_phase(cfg, seed, on_chip):
    """Kernel results against the jnp compositions, which are evaluated
    in float32 at "highest" matmul precision on the same (rounded)
    inputs. Errors are relative to max(1, |reference|_max)."""
    import jax
    import jax.numpy as jnp
    from mxnet_tpu import profiler
    from mxnet_tpu.parallel.flash_attention import (
        _jnp_decode, _jnp_reference, flash_attention, flash_decode)
    from mxnet_tpu.serving import kvcache
    t0 = time.perf_counter()
    before = profiler.counters()
    B, T, H, D = cfg["attn"]
    scale = 1.0 / math.sqrt(D)
    force = not on_chip        # the rehearsal interprets the kernels
    keys = iter(jax.random.split(jax.random.PRNGKey(seed), 24))
    results = {}

    def compare(name, dtype, kernel, reference, args, ref_args, where=None):
        """Forward (and, for attention, grads) of ``kernel`` against
        ``reference`` at every precision ``dtype`` is held to."""
        grad = len(args) == 4            # q, k, v, cotangent
        with jax.default_matmul_precision("highest"):
            want = jax.jit(reference)(*ref_args)
        for precision, tol in KERNEL_CHECKS[dtype]:
            with _precision(precision):
                lowered = jax.jit(kernel).lower(*args)
                check(not on_chip or "tpu_custom_call" in lowered.as_text(),
                      "kernels: %s lowers without a Mosaic call" % name)
                got = lowered.compile()(*args)
            if not grad:
                got, want = (got,), (want,)
            errs = [_max_err(g, w, where if n == 0 else None)
                    for n, (g, w) in enumerate(zip(got, want))]
            results["%s_%s_%s" % (name, dtype, precision)] = {
                "err": errs, "tol": tol}
            check(all(math.isfinite(e) and e <= tol for e in errs),
                  "kernels: %s %s at %s precision: error %s > %g"
                  % (name, dtype, precision, errs, tol))

    # attention: causal, with and without packed segments (three
    # samples and a padded tail, id 0), forward and grads together
    cuts = (int(T * 0.34), int(T * 0.78), int(T * 0.93))
    seg_row = np.zeros((T,), np.int32)
    seg_row[:cuts[0]], seg_row[cuts[0]:cuts[1]] = 1, 2
    seg_row[cuts[1]:cuts[2]] = 3
    seg = jnp.asarray(np.tile(seg_row, (B, 1)))
    real = (seg > 0)[:, :, None, None]

    def with_grads(attend, where):
        def run(q, k, v, cot):
            if where is not None:        # padded rows are garbage
                cot = jnp.where(where, cot, 0.0)
            out, vjp = jax.vjp(attend, q, k, v)
            return (out,) + vjp(cot.astype(out.dtype))
        return run

    for dtype in (jnp.bfloat16, jnp.float32):
        args = [jax.random.normal(next(keys), (B, T, H, D),
                                  jnp.float32).astype(dtype)
                for _ in range(4)]
        ref_args = [a.astype(jnp.float32) for a in args]
        for name, ids, where in (("attention_causal", None, None),
                                 ("attention_segments", seg, real)):
            compare(name, jnp.dtype(dtype).name,
                    with_grads(lambda q, k, v, ids=ids: flash_attention(
                        q, k, v, causal=True, segment_ids=ids,
                        force_pallas=force), where),
                    with_grads(lambda q, k, v, ids=ids: _jnp_reference(
                        q, k, v, scale, True, segment_ids=ids), where),
                    args, ref_args, where)

    # decode: one query row per sequence against a T-long cache
    lengths = jnp.asarray(np.linspace(1, T, B).astype(np.int32))
    q = jax.random.normal(next(keys), (B, 1, H, D), jnp.float32)
    kc, vc = (jax.random.normal(next(keys), (B, T, H, D), jnp.float32)
              for _ in range(2))
    k8, v8 = (jax.random.randint(next(keys), (B, T, H, D), -127, 128,
                                 jnp.int32).astype(jnp.int8)
              for _ in range(2))
    ks, vs = (jax.random.uniform(next(keys), (B, T), jnp.float32,
                                 0.005, 0.02) for _ in range(2))
    compare("decode", "float32",
            lambda q, k, v: flash_decode(q, k, v, lengths,
                                         force_pallas=force),
            lambda q, k, v: _jnp_decode(q, k, v, lengths, scale),
            (q, kc, vc), (q, kc, vc))
    compare("decode_int8", "float32",
            lambda q, k, v: flash_decode(q, k, v, lengths, k_scale=ks,
                                         v_scale=vs, force_pallas=force),
            lambda q, k, v: _jnp_decode(
                q, k.astype(jnp.float32) * ks[:, :, None, None],
                v.astype(jnp.float32) * vs[:, :, None, None],
                lengths, scale),
            (q, k8, v8), (q, k8, v8))

    # the same rows through the paged kernel: the cache cut into the
    # serve phase's pages behind a dump page (layer 1 of a two-layer
    # pool), each row's last live key handed over as the new token
    S = cfg["page_size"]
    M = T // S
    table = jnp.arange(1, B * M + 1, dtype=jnp.int32).reshape(B, M)
    at = lengths - 1
    rows = jnp.arange(B)
    ksp, vsp = (jax.random.uniform(next(keys), (B, M), jnp.float32,
                                   0.005, 0.02) for _ in range(2))

    def pool(cache):
        pages = cache.reshape(B * M, S, H, D)
        pages = jnp.concatenate([jnp.zeros_like(pages[:1]), pages])
        return jnp.stack([jnp.zeros_like(pages), pages])

    def page_scales(scales):
        flat = jnp.concatenate([jnp.ones((1,)), scales.reshape(-1)])
        return jnp.stack([jnp.ones_like(flat), flat])

    def paged(q, k, v, k_scale=None, v_scale=None):
        new = [c[rows, at].astype(jnp.float32) for c in (k, v)]
        if k_scale is not None:
            new = [n * s[rows, at // S][:, None, None]
                   for n, s in zip(new, (k_scale, v_scale))]
            k_scale, v_scale = page_scales(k_scale), page_scales(v_scale)
        return kvcache.paged_attention(
            pool(k), pool(v), table, at, 1, q[:, 0], *new,
            force_pallas=force, k_scale=k_scale, v_scale=v_scale)[:, None]

    compare("decode_paged", "float32", paged,
            lambda q, k, v: _jnp_decode(q, k, v, lengths, scale),
            (q, kc, vc), (q, kc, vc))
    compare("decode_paged_int8", "float32",
            lambda q, k, v: paged(q, k, v, ksp, vsp),
            lambda q, k, v: _jnp_decode(
                q, k.astype(jnp.float32)
                * jnp.repeat(ksp, S, axis=1)[:, :, None, None],
                v.astype(jnp.float32)
                * jnp.repeat(vsp, S, axis=1)[:, :, None, None],
                lengths, scale),
            (q, k8, v8), (q, k8, v8))

    paths = counted_since(before, ATTENTION_PATHS)
    emit({"phase": "kernels", "shape": [B, T, H, D], "errors": results,
          "paths": paths, "mosaic_asserted": bool(on_chip),
          "wall_s": round(time.perf_counter() - t0, 3),
          "device": device_record()})
    check(paths["flash_attention_jnp"] == 0
          and paths["flash_decode_jnp"] == 0
          and paths["paged_decode_jnp"] == 0,
          "kernels: the jnp path was taken: %s" % paths)


# ---------------------------------------------------------------------------
# serve
# ---------------------------------------------------------------------------

def dense_reference(model, params, tokens):
    """Plain float32 forward of ToyDecoderLM's weights over one whole
    sequence — dense masked softmax attention, no cache, no kernels.
    ``tokens (L,)`` -> logits ``(L, vocab)``."""
    import jax
    import jax.numpy as jnp

    def ln(x, g, b):
        mu = jnp.mean(x, -1, keepdims=True)
        var = jnp.mean((x - mu) ** 2, -1, keepdims=True)
        return (x - mu) / jnp.sqrt(var + 1e-5) * g + b

    L = tokens.shape[0]
    H, Dh = model.n_heads, model.head_dim
    with jax.default_matmul_precision("highest"):
        h = params["embed"][tokens] + params["pos"][:L]
        causal = jnp.tril(jnp.ones((L, L), bool))
        for i in range(model.n_layers):
            w = {n: params["l%d.%s" % (i, n)] for n in (
                "att_g", "att_b", "wq", "wk", "wv", "wo", "ffn_g",
                "ffn_b", "w1", "w2")}
            x = ln(h, w["att_g"], w["att_b"])
            q, k, v = ((x @ w[n]).reshape(L, H, Dh)
                       for n in ("wq", "wk", "wv"))
            s = jnp.einsum("qhd,khd->hqk", q, k) / math.sqrt(Dh)
            p = jax.nn.softmax(jnp.where(causal[None], s, -jnp.inf), -1)
            h = h + jnp.einsum("hqk,khd->qhd", p, v).reshape(L, -1) \
                @ w["wo"]
            x = ln(h, w["ffn_g"], w["ffn_b"])
            h = h + jax.nn.relu(x @ w["w1"]) @ w["w2"]
        return ln(h, params["out_g"], params["out_b"]) @ params["wout"]


def _prompts(cfg, seed):
    rs = np.random.RandomState(seed)
    lo, hi = cfg["prompt_lens"]
    lens = np.linspace(lo, hi, cfg["n_requests"]).astype(int)
    return [rs.randint(0, cfg["lm"]["vocab"], size=int(n)).astype(np.int32)
            for n in lens]


def _lm(cfg, seed):
    from mxnet_tpu.serving import ToyDecoderLM
    model = ToyDecoderLM(**cfg["lm"])
    return model, model.init_params(seed=seed)


def _server(cfg, model, params, device, name):
    from mxnet_tpu.serving import DecodeServer
    return DecodeServer(
        model, params, seq_ladder=list(cfg["ladder"]),
        max_new_tokens=cfg["new_tokens"], window=cfg["window"],
        page_size=cfg["page_size"], pool_pages=cfg["pool_pages"],
        name=name, device=device)


def _check_against_reference(cfg, model, params, prompt, served):
    """Teacher-forced: one dense forward over prompt + served tokens;
    position P-1+i must predict served token i. Returns (exact matches,
    largest shortfall, logit std)."""
    import jax
    import jax.numpy as jnp
    n = cfg["check_tokens"]
    seq = np.concatenate([prompt, served[:n - 1]]).astype(np.int32)
    logits = jax.jit(lambda p, t: dense_reference(model, p, t))(
        params, jnp.asarray(seq))
    rows = np.asarray(logits[len(prompt) - 1:len(prompt) - 1 + n])
    picked = rows[np.arange(n), np.asarray(served[:n])]
    shortfall = rows.max(axis=1) - picked
    exact = int((rows.argmax(axis=1) == np.asarray(served[:n])).sum())
    return exact, float(shortfall.max()), float(rows.std())


def _default_precision_error(seed):
    """Relative error of an fp32 matmul at the backend's default
    precision against "highest" — what unpinned fp32 serving gives up."""
    import jax
    import jax.numpy as jnp
    a, b = (jax.random.normal(k, (256, 1024), jnp.float32)
            for k in jax.random.split(jax.random.PRNGKey(seed)))
    lo = jnp.matmul(a, b.T, precision="default")
    hi = jnp.matmul(a, b.T, precision="highest")
    return float(jnp.max(jnp.abs(lo - hi)) / jnp.max(jnp.abs(hi)))


def serve_phase(cfg, seed, on_chip):
    import jax
    device = jax.devices()[0]
    default_err = _default_precision_error(seed)
    precision = jax.config.jax_default_matmul_precision
    jax.config.update("jax_default_matmul_precision", "highest")
    try:
        _serve_phase(cfg, seed, on_chip, device, default_err)
    finally:
        jax.config.update("jax_default_matmul_precision", precision)


def _serve_phase(cfg, seed, on_chip, device, default_err):
    from mxnet_tpu import compile_watch, profiler
    t0 = time.perf_counter()
    compiles = Compiles()
    before = profiler.counters()
    model, params = _lm(cfg, seed)
    srv = _server(cfg, model, params, device, "smoke")
    try:
        n_programs = srv.warmup()
        warm_compiles, warm_s = compiles.delta()
        prompts = _prompts(cfg, seed)
        reqs = [srv.submit(p, max_new_tokens=cfg["new_tokens"])
                for p in prompts]
        served = [np.asarray(r.result(timeout=900)) for r in reqs]
        stats = srv.stats()
        pool_ok = all(on_device(a, device) for a in srv.pool.arrays)
        sites = compile_watch.site_stats("decode:smoke")
    finally:
        srv.stop()
    total_compiles, _ = compiles.delta()
    paths = counted_since(before, ATTENTION_PATHS)
    checked = []
    for i in np.argsort([len(p) for p in prompts])[:2]:
        exact, shortfall, spread = _check_against_reference(
            cfg, model, params, prompts[i], served[i])
        checked.append({"prompt_len": int(len(prompts[i])),
                        "exact": exact, "of": cfg["check_tokens"],
                        "max_shortfall": shortfall, "logit_std": spread})
    emit({"phase": "serve", "lm": cfg["lm"], "ladder": list(cfg["ladder"]),
          "page_size": cfg["page_size"], "pool_pages": cfg["pool_pages"],
          "requests": len(reqs), "completed": stats["completed"],
          "errors": stats["errors"], "tokens_out": stats["tokens_out"],
          "prompt_lens": [int(len(p)) for p in prompts],
          "programs": n_programs, "sites": sorted(sites),
          "warmup_compiles": warm_compiles, "warmup_compile_s": warm_s,
          "compiles_after_warmup": total_compiles - warm_compiles,
          "pool_on_device": pool_ok, "paths": paths,
          "reference": checked, "token_logit_slack": TOKEN_LOGIT_SLACK,
          "matmul_precision": "highest",
          "default_precision_matmul_rel_err": default_err,
          "cache": cache_counts(),
          "wall_s": round(time.perf_counter() - t0, 3),
          "memory": memory(device), "device": device_record()})
    check(stats["completed"] == len(reqs) and stats["errors"] == 0,
          "serve: %d/%d completed, %d errors"
          % (stats["completed"], len(reqs), stats["errors"]))
    check(all(len(s) == cfg["new_tokens"] for s in served),
          "serve: short generations %s" % [len(s) for s in served])
    check(pool_ok, "serve: KV pool is not on %s" % device)
    # the step and the mixed steps that carry a prompt's chunks; where a
    # server keeps the whole-prompt prefill, the step and a rung each
    want_programs = 1 + len(stats["chunk_sizes"] if stats["chunk"]
                            else cfg["ladder"])
    check(n_programs == want_programs and len(sites) == want_programs
          and all(s["count"] == 1 for s in sites.values()),
          "serve: program set %s, expected %d programs compiled once"
          % (sites, want_programs))
    check(total_compiles == warm_compiles,
          "serve: compiled after warmup (%d -> %d)"
          % (warm_compiles, total_compiles))
    check(stats["prefill_programs"] == 0 and stats["chunk_tokens"]
          == sum(len(p) for p in prompts) if stats["chunk"]
          else stats["prefill_programs"] == len(reqs),
          "serve: prompts did not ride the step as the server says: %s"
          % {k: stats[k] for k in ("chunk", "chunk_tokens", "chunk_steps",
                                   "prefill_programs")})
    if on_chip:
        # the step takes the paged decode kernel (a prompt's chunks ride
        # it on lanes of composed attention; a server that keeps the
        # prefill takes flash_attention there); the contiguous
        # flash_decode is not on this path
        check(paths["flash_attention_jnp"] == 0
              and paths["paged_decode_jnp"] == 0
              and (stats["chunk"] or paths["flash_attention_pallas"] > 0)
              and paths["paged_decode_pallas"] > 0,
              "serve: attention did not take the kernels: %s" % paths)
    for c in checked:
        check(c["max_shortfall"] <= TOKEN_LOGIT_SLACK,
              "serve: tokens disagree with the float32 reference: %s" % c)


# ---------------------------------------------------------------------------
# --chips 4
# ---------------------------------------------------------------------------

def _dist_losses(cfg, seed, mesh):
    """``dist_steps`` steps of DistributedTrainer on ``mesh``; returns
    (losses, trainer, the placed data batch)."""
    import jax
    from mxnet_tpu import gluon
    from mxnet_tpu.parallel import DistributedTrainer
    # DistributedTrainer's bucketed update wants ONE parameter dtype
    # and ONE state layout over the whole roster, so the AMP policy's
    # fp32 norm parameters and fp32 masters cannot ride it: here every
    # parameter is bf16 and the update has no master copy.
    net = _resnet(cfg, seed, rules={"gamma": "bfloat16",
                                    "beta": "bfloat16"})
    trainer = DistributedTrainer(
        net, gluon.loss.SoftmaxCrossEntropyLoss(), mesh, optimizer="sgd",
        optimizer_params={"learning_rate": cfg["dist_lr"],
                          "momentum": 0.9})
    x, y = _batch(cfg, seed, cfg["dist_batch"])
    losses = []
    for _ in range(cfg["dist_steps"]):
        loss = trainer.fit_batch(x, y)
        jax.block_until_ready(loss._data)
        losses.append(float(loss.asnumpy()))
    placed = jax.device_put(x._data, trainer._batch_sharding)
    return losses, trainer, placed


def _shard_devices(array):
    return {s.device for s in array.addressable_shards}


def dist_train_phase(cfg, seed, devices, on_chip):
    from mxnet_tpu.parallel import create_mesh
    t0 = time.perf_counter()
    compiles = Compiles()
    mesh = create_mesh({"dp": len(devices)})
    ring = [d for d in mesh.devices.flat]
    losses4, trainer, placed = _dist_losses(cfg, seed, mesh)
    resident = list(trainer._param_vals) + list(trainer._state_vals)
    spread = min(len(_shard_devices(v)) for v in resident)
    data_spread = len(_shard_devices(placed))
    mem = [memory(d) for d in devices]
    n_buckets = len(trainer._plan.buckets)
    del trainer, placed, resident
    losses1, _, _ = _dist_losses(
        cfg, seed, create_mesh({"dp": 1}, devices=[devices[0]]))
    rel = [abs(a - b) / max(abs(b), 1e-6) for a, b in zip(losses4, losses1)]
    n_compiles, compile_s = compiles.delta()
    emit({"phase": "dist_train", "net": cfg["net"],
          "dtype": "bfloat16, every parameter, no fp32 masters",
          "global_batch": cfg["dist_batch"], "mesh": {"dp": len(devices)},
          "ring": [{"id": d.id, "coords": list(getattr(d, "coords", []))}
                   for d in ring],
          "losses_mesh": losses4, "losses_one_device": losses1,
          "rel_diff": rel, "grad_buckets": n_buckets,
          "min_devices_per_param_or_state": spread,
          "data_shard_devices": data_spread,
          "compiles": n_compiles, "compile_s": compile_s,
          "wall_s": round(time.perf_counter() - t0, 3),
          "memory": mem, "device": device_record()})
    check(all(math.isfinite(v) for v in losses4 + losses1),
          "dist_train: non-finite loss")
    check(max(rel) <= cfg["dist_rel_tol"],
          "dist_train: mesh vs one device differ by "
          "%s (losses %s vs %s)" % (rel, losses4, losses1))
    check(data_spread == len(devices) and spread == len(devices),
          "dist_train: batch on %d devices, params/state on >= %d, "
          "wanted %d" % (data_spread, spread, len(devices)))
    if on_chip:            # the CPU client reports no memory stats
        check(all(m.get("bytes_in_use", 0) > (1 << 20) for m in mem),
              "dist_train: a device holds almost nothing: %s" % mem)


def router_phase(cfg, seed, devices, on_chip):
    from mxnet_tpu.serving import Router
    t0 = time.perf_counter()
    compiles = Compiles()
    model, params = _lm(cfg, seed)
    prompts = _prompts(cfg, seed)
    # two rungs: every replica compiles its own copy of each program
    cfg = dict(cfg, ladder=(cfg["ladder"][1], cfg["ladder"][-1]))
    fleet = [_server(cfg, model, params, d, "rep-%d" % i)
             for i, d in enumerate(devices)]
    router = Router(fleet, name="smoke")
    try:
        for srv in fleet:
            srv.warmup()
        placed = [all(on_device(a, d) for a in s.pool.arrays)
                  for s, d in zip(fleet, devices)]
        # the one-replica run: the same requests on replica 0 alone
        alone = [fleet[0].submit(p, max_new_tokens=cfg["new_tokens"])
                 for p in prompts]
        tokens1 = [np.asarray(r.result(timeout=900)) for r in alone]
        done_alone = fleet[0].stats()["completed"]
        reqs = [router.submit(p, max_new_tokens=cfg["new_tokens"])
                for p in prompts]
        tokens4 = [np.asarray(r.result(timeout=900)) for r in reqs]
        rstats = router.stats()
        sstats = [s.stats() for s in fleet]
    finally:
        router.stop()
    routed = [s["completed"] - (done_alone if i == 0 else 0)
              for i, s in enumerate(sstats)]
    same = [bool(np.array_equal(a, b)) for a, b in zip(tokens1, tokens4)]
    n_compiles, compile_s = compiles.delta()
    mem = [memory(d) for d in devices]
    emit({"phase": "router", "replicas": len(fleet),
          "ladder": list(cfg["ladder"]), "requests": len(prompts),
          "completed": rstats["completed"], "failed": rstats["failed"],
          "routed_per_replica": routed,
          "errors_per_replica": [s["errors"] for s in sstats],
          "pool_on_own_device": placed, "tokens_equal_one_replica": same,
          "compiles": n_compiles, "compile_s": compile_s,
          "wall_s": round(time.perf_counter() - t0, 3),
          "memory": mem, "device": device_record()})
    check(rstats["completed"] == len(prompts) and rstats["failed"] == 0,
          "router: %s" % rstats)
    check(all(s["errors"] == 0 for s in sstats), "router: replica errors")
    check(all(placed), "router: a pool is off its device: %s" % placed)
    check(all(routed), "router: idle replicas: %s" % routed)
    check(all(same), "router: tokens differ from one replica: %s" % same)
    if on_chip:
        check(all(m.get("bytes_in_use", 0) > (1 << 20) for m in mem),
              "router: a device holds almost nothing: %s" % mem)


# ---------------------------------------------------------------------------

def run_phases(cfg, seed, chips, on_chip):
    import jax
    from mxnet_tpu import compile_watch
    compile_watch.enable()
    if chips == 1:
        train_phase(cfg, seed, on_chip)
        kernels_phase(cfg, seed, on_chip)
        serve_phase(cfg, seed, on_chip)
    else:
        devices = jax.devices()[:chips]
        dist_train_phase(cfg, seed, devices, on_chip)
        router_phase(cfg, seed, devices, on_chip)


def rehearse(chips=1, seed=0):
    """The phases at a tiny size on whatever devices JAX has (Pallas in
    interpret mode off the TPU). A rehearsal of control flow, never a
    result: it prints no "ok" line."""
    import jax
    check(len(jax.devices()) >= chips,
          "rehearse(chips=%d) needs %d devices" % (chips, chips))
    run_phases(TINY, seed, chips, on_chip=False)
    emit({"rehearsal": True, "device": device_record()})


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the cross-chip phases")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    from mxnet_tpu import runtime
    cache_dir = runtime.enable_compile_cache()
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        sys.exit("chip_smoke: needs a TPU, JAX found %s (%s) — there is "
                 "no CPU mode" % (devices[0].platform,
                                  devices[0].device_kind))
    if len(devices) < args.chips:
        sys.exit("chip_smoke: --chips %d but JAX found %d device(s)"
                 % (args.chips, len(devices)))
    emit({"phase": "device", "device": device_record(),
          "compile_cache": cache_dir, "seed": args.seed})
    run_phases(FULL, args.seed, args.chips, on_chip=True)
    print(json.dumps({"ok": True, "device": device_record()}), flush=True)


if __name__ == "__main__":
    main()
