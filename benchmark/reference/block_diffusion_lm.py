"""Plain float32 reference of a block-diffusion decoder LM with
grouped-query attention and softmax-routed experts (JetLM/SDAR-30B-A3B-
Chat, ``model_type`` ``sdar_moe``): ``jax.numpy`` over ONE whole
sequence, every product at "highest" precision, no cache, no kernels, no
batching, the experts by a plain loop over all of them with a mask, the
router as published. The benchmark's own copy: nothing here imports the
program.

**The block** (pre-norm, RMSNorm eps from the config, no biases):
``h = x + Attn(RMSNorm(x))``, ``y = h + MoE(RMSNorm(h))``, all layers
alike, a final RMSNorm, an untied head. Attention: ``q = x W_q`` (heads
x head_dim), ``k = x W_k``, ``v = x W_v`` (kv heads x head_dim); ``q``
and ``k`` pass an RMSNorm over each head's values and then RoPE over all
of them (half against half, ``rope_theta``); scores ``q.k /
sqrt(head_dim)``; query head ``i`` reads key/value head ``i // (heads /
kv heads)``. Experts: ``p = softmax(x W_g)`` in float32, the ``top_k``
largest, ``w = p / sum(p chosen)``, each expert ``(silu(x W_gate) * (x
W_up)) W_down``.

**Two passes.** :func:`clean_pass` is the forward over final tokens
under the block-causal mask (key ``j`` visible to query ``i`` iff ``j //
B <= i // B``): what a prefill and a commit compute. :func:`noisy_pass`
is the published TRAINING mask's other half: a noisy copy of the
sequence in which block ``b`` sees the clean blocks ``< b`` (their keys
and values from the clean pass) and itself, whole. Given the final
tokens and which positions were still masked, one noisy pass gives the
logits of that denoising pass of EVERY block at once. The two halves of
the published ``[noisy, clean]`` forward are computed one after the
other, so that the scores fit beside the weights.

Departures from the published model, all of them:
- weights are random from a seed;
- the per-head q/k RMSNorm is the block ``sdar_moe`` is initialised from
  (it has no key in ``config.json``);
- RoPE rotates half against half (``rotate_half``), as published;
- the sampler's settings (block length, steps, strategy, threshold, mask
  id) are the family's published inference defaults, not ``config.json``
  keys; a masked position predicts its own token (no shift).

``low=True`` is the CONTROL, not the reference: the same equations with
every matrix and the keys and values rounded to ``float8_e4m3fn``, the
next precision under the bfloat16 the configuration states. The
comparison that decides ``correct`` has to tell it from the reference;
no benchmark run computes it.

``cfg`` is the keyword arguments the served model is built with.
"""
from __future__ import annotations

import functools

import numpy as np


def _f32(w, low):
    import jax.numpy as jnp
    if low:
        w = w.astype(jnp.float8_e4m3fn)
    return w.astype(jnp.float32)


def _rms(x, g, eps):
    import jax
    import jax.numpy as jnp
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g


def _rope(x, theta):
    """``x (L, H, d)`` at positions ``0..L-1``, half against half."""
    import jax.numpy as jnp
    d = x.shape[-1]
    freqs = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None, None] * freqs
    a, b = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([a * jnp.cos(ang) - b * jnp.sin(ang),
                            b * jnp.cos(ang) + a * jnp.sin(ang)], -1)


def _qkv(h, w, *, heads, kv_heads, head_dim, eps, theta, low):
    L = h.shape[0]
    x = _rms(h, w["attn_g"], eps)
    q = (x @ _f32(w["wq"], low)).reshape(L, heads, head_dim)
    k = (x @ _f32(w["wk"], low)).reshape(L, kv_heads, head_dim)
    v = (x @ _f32(w["wv"], low)).reshape(L, kv_heads, head_dim)
    q = _rope(_rms(q, w["q_g"], eps), theta)
    k = _rope(_rms(k, w["k_g"], eps), theta)
    if low:         # the cached keys and values, in the control's precision
        k, v = _f32(k, True), _f32(v, True)
    return q, k, v


def attention(h, w, clean, *, heads, kv_heads, head_dim, eps, theta, block,
              low):
    """One layer's attention over the sequence ``h (L, D)``. With
    ``clean`` None it is the block-causal pass over final tokens and
    returns ``(increment, (k, v))``; with ``clean = (k, v)`` of that
    pass, ``h`` is the noisy copy: a query sees the clean keys of
    earlier blocks and the noisy keys of its own block."""
    import jax
    import jax.numpy as jnp
    with jax.default_matmul_precision("highest"):
        L = h.shape[0]
        g = heads // kv_heads
        q, k, v = _qkv(h, w, heads=heads, kv_heads=kv_heads,
                       head_dim=head_dim, eps=eps, theta=theta, low=low)
        q = q.reshape(L, kv_heads, g, head_dim) * head_dim ** -0.5
        blk = jnp.arange(L) // block
        own = jnp.einsum("qhgd,khd->hgqk", q, k)
        if clean is None:
            s = jnp.where(blk[None, :] <= blk[:, None], own, -jnp.inf)
            p = jax.nn.softmax(s, -1)
            out = jnp.einsum("hgqk,khd->qhgd", p, v)
        else:
            kc, vc = clean
            s = jnp.concatenate([
                jnp.where(blk[None, :] < blk[:, None],
                          jnp.einsum("qhgd,khd->hgqk", q, kc), -jnp.inf),
                jnp.where(blk[None, :] == blk[:, None], own, -jnp.inf)], -1)
            p = jax.nn.softmax(s, -1)
            out = jnp.einsum("hgqk,khd->qhgd", p[..., :L], vc) \
                + jnp.einsum("hgqk,khd->qhgd", p[..., L:], v)
        return out.reshape(L, heads * head_dim) @ _f32(w["wo"], low), (k, v)


def route(x, w, *, top_k, renormalize):
    """The published router: softmax over all experts in float32, the
    ``top_k`` largest (ties to the lower index), renormalised over the
    chosen. Returns ``(ids (L, top_k), weights (L, top_k))``."""
    import jax
    import jax.numpy as jnp
    with jax.default_matmul_precision("highest"):
        p = jax.nn.softmax(x @ w.astype(jnp.float32), -1)
    ids = jnp.argsort(-p, axis=-1, stable=True)[:, :top_k]
    chosen = jnp.take_along_axis(p, ids, axis=1)
    if renormalize:
        chosen = chosen / chosen.sum(-1, keepdims=True)
    return ids, chosen


def experts(h, ffn_g, router_w, w_gate, w_up, w_down, *, top_k,
            renormalize, eps, low):
    """``sum over the chosen experts`` for the residual ``h (L, D)``: a
    plain loop over ALL experts, each computed for every token and
    masked by its routing weight. Returns ``(increment, ids)``."""
    import jax
    import jax.numpy as jnp
    x = _rms(h, ffn_g, eps)
    ids, weights = route(x, router_w, top_k=top_k, renormalize=renormalize)

    def one(e, out):
        with jax.default_matmul_precision("highest"):
            part = (jax.nn.silu(x @ _f32(w_gate[e], low))
                    * (x @ _f32(w_up[e], low))) @ _f32(w_down[e], low)
        mask = jnp.sum(jnp.where(ids == e, weights, 0.0), axis=1)
        return out + mask[:, None] * part

    return jax.lax.fori_loop(0, w_gate.shape[0], one,
                             jnp.zeros_like(h)), ids


@functools.lru_cache(maxsize=None)
def _jitted():
    import jax
    return (jax.jit(attention, static_argnames=(
                "heads", "kv_heads", "head_dim", "eps", "theta", "block",
                "low")),
            jax.jit(experts, static_argnames=(
                "top_k", "renormalize", "eps", "low")))


def _hidden(params, tokens, cfg, clean, low, routed):
    """Final-norm hidden states ``(L, D)`` of one pass and the layers'
    keys and values."""
    attn, moe = _jitted()
    eps = float(cfg.get("rms_norm_eps", 1e-6))
    h = _f32(params["embed"][tokens], low)
    kv = []
    for i in range(cfg["num_hidden_layers"]):
        l = "l%d." % i
        w = {n: params[l + n] for n in ("attn_g", "wq", "wk", "wv", "wo",
                                        "q_g", "k_g")}
        inc, layer_kv = attn(
            h, w, None if clean is None else clean[i],
            heads=cfg["num_attention_heads"],
            kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
            eps=eps, theta=float(cfg["rope_theta"]),
            block=cfg["block_length"], low=low)
        h = h + inc
        kv.append(layer_kv)
        inc, ids = moe(
            h, params[l + "ffn_g"], params[l + "router_w"],
            *(params[l + "experts." + n]
              for n in ("w_gate", "w_up", "w_down")),
            top_k=cfg["num_experts_per_tok"],
            renormalize=bool(cfg.get("norm_topk_prob", True)), eps=eps,
            low=low)
        h = h + inc
        if routed is not None:
            routed.append(ids)
    return _rms(h, params["out_g"], eps), kv


def clean_pass(params, tokens, cfg, low=False, routed=None):
    """Block-causal forward over final ``tokens (L,)``: ``(hidden (L, D),
    [(k, v) a layer])``. ``routed``, a list, is given the router's
    choice of every layer, ``(L, top_k)`` ids each."""
    return _hidden(params, tokens, cfg, None, low, routed)


def noisy_pass(params, noisy, clean_kv, cfg, low=False):
    """The noisy copy ``noisy (L,)`` against the clean pass's keys and
    values: hidden states ``(L, D)`` of one denoising pass of every
    block."""
    return _hidden(params, noisy, cfg, clean_kv, low, None)[0]


@functools.partial(__import__("jax").jit, static_argnames=("low",))
def _read(h, head, tokens, other, low):
    """Of the logits ``h @ head`` of each row: the best, its index, the
    logit of the row's own ``tokens`` and of the row's ``other`` token,
    the log of the sum of exponentials, and the sum and sum of squares
    over the vocabulary."""
    import jax
    import jax.numpy as jnp
    with jax.default_matmul_precision("highest"):
        z = h @ _f32(head, low)
    take = lambda t: jnp.take_along_axis(z, t[:, None], axis=1)[:, 0]  # noqa: E731,E501
    return (z.max(-1), z.argmax(-1), take(tokens), take(other),
            jax.nn.logsumexp(z, axis=-1), z.sum(-1), (z * z).sum(-1))


def forward(params, tokens, cfg, low=False):
    """Logits ``(L, V)`` of the block-causal forward over ``tokens``
    (``cfg["block_length"]``): a prefill or a commit pass, whole."""
    import jax
    import jax.numpy as jnp
    h, _ = clean_pass(params, jnp.asarray(tokens, jnp.int32), cfg, low)
    with jax.default_matmul_precision("highest"):
        return h @ _f32(params["head"], low)


def denoising_logits(params, final, masked, cfg, low=False):
    """Logits ``(L, V)`` of ONE denoising pass of every block: ``final
    (L,)`` the final tokens, ``masked (L,)`` bool the positions still
    masked when the pass ran (they are fed ``cfg["mask_token_id"]``)."""
    import jax
    import jax.numpy as jnp
    final = jnp.asarray(final, jnp.int32)
    _, kv = clean_pass(params, final, cfg, low)
    noisy = jnp.where(jnp.asarray(masked), cfg["mask_token_id"], final)
    h = noisy_pass(params, noisy, kv, cfg, low)
    with jax.default_matmul_precision("highest"):
        return h @ _f32(params["head"], low)


def _picks(conf, masked, least, threshold):
    """The positions one pass unmasks, by the published rule, a block a
    row of ``conf``/``masked (blocks, B)``: the ``least`` most confident
    masked ones (ties to the lower index), or, with a ``threshold``,
    every masked one over it where those are at least as many."""
    c = np.where(masked, conf, -1.0)
    order = np.argsort(-c, axis=1, kind="stable")[:, :least]
    pick = np.zeros_like(masked)
    np.put_along_axis(pick, order, True, axis=1)
    if threshold is not None:
        high = (conf > threshold) & masked
        pick = np.where(high.sum(1, keepdims=True) >= least, high, pick)
    return pick & masked


def teacher_forced(params, prompt, served, when, tail, padded_len, cfg,
                   control=False, routed=None):
    """The served tokens of one request, each in the pass that chose it.

    ``served (n,)`` with ``when (n,)``, the denoising pass of its block
    (0 the first) in which the program unmasked each; ``tail =
    (tokens, passes)`` the rest of the last block where the answer was
    cut inside it (the block was denoised whole). The clean pass runs
    once over prompt + answer, then one noisy pass for each pass index
    ``p``: a position of the answer shows its final token where it was
    unmasked before ``p`` and the mask token otherwise — the input the
    program's pass ``p`` of every block saw. At each served token's row
    of ITS pass, in standard deviations of the reference's logits over
    those rows: ``worst``, the widest gap by which the served token's
    logit lies below the reference's best, and ``mean`` (0 where it is
    the reference's own). ``unmask_differs``: the share of (block, pass)
    pairs in which the reference's rule, on the reference's confidences,
    unmasks another set of positions than the program did.
    ``padded_len`` only fixes the compiled shapes. With ``control`` the
    same numbers for the tokens the float8 control puts first at the
    same rows, under ``control_*``. ``routed`` as in :func:`clean_pass`
    (the reference's own choice on the clean pass)."""
    import jax.numpy as jnp
    B, mask_id = cfg["block_length"], cfg["mask_token_id"]
    P, n = len(prompt), len(served)
    seq = np.concatenate([prompt, served, tail[0]]).astype(np.int32)
    passes = np.concatenate([np.full(P, -1), when, tail[1]]).astype(int)
    used = len(seq)
    assert used % B == 0 and used <= padded_len and padded_len % B == 0
    final = np.full((padded_len,), mask_id, np.int32)
    final[:used] = seq
    # padding behind the answer: later blocks, never unmasked, unseen
    shown = np.full((padded_len,), 1 << 30)
    shown[:used] = passes
    rows = np.arange(P, P + n)
    n_passes = int(passes.max()) + 1

    def run(low, other):
        """Every pass at the control's or the reference's precision; of
        each position, the readings of the pass that unmasked it."""
        _, kv = clean_pass(params, jnp.asarray(final), cfg, low,
                           None if low else routed)
        keys = ("best", "arg", "own", "other", "lse", "sum", "sq")
        got = {k: np.zeros(padded_len) for k in keys}
        conf = np.zeros((n_passes, padded_len))
        for p in range(n_passes):
            noisy = np.where(shown >= p, mask_id, final).astype(np.int32)
            h = noisy_pass(params, jnp.asarray(noisy), kv, cfg, low)
            out = [np.asarray(a) for a in _read(
                h, params["head"], jnp.asarray(final), jnp.asarray(other),
                low)]
            at = shown == p
            for k, a in zip(keys, out):
                got[k][at] = a[at]
            conf[p] = np.exp(out[0] - out[4])
        return got, conf

    # the control's first choice at each row of its pass, then the
    # reference, which also reads the logit IT gives that choice
    theirs = run(True, final)[0]["arg"].astype(np.int32) if control \
        else final
    ref, conf = run(False, theirs)
    count = n * params["head"].shape[1]
    mean_all = ref["sum"][rows].sum() / count
    std = float(np.sqrt(ref["sq"][rows].sum() / count - mean_all ** 2))
    got = (ref["best"][rows] - ref["own"][rows]) / std

    # the reference's own unmasking, pass by pass, block by block
    blocks = slice(P // B * B, used)
    differs = total = 0
    threshold = cfg["confidence_threshold"] \
        if cfg["remasking_strategy"] == "low_confidence_dynamic" else None
    least = -(-B // cfg["denoising_steps"])
    for p in range(n_passes):
        masked = (shown[blocks] >= p).reshape(-1, B)
        mine = (shown[blocks] == p).reshape(-1, B)
        ran = masked.any(1)
        rule = _picks(conf[p][blocks].reshape(-1, B), masked, least,
                      threshold)
        differs += int((rule != mine).any(1)[ran].sum())
        total += int(ran.sum())
    out = {"tokens": int(n), "prompt_len": int(P), "logit_std": std,
           "exact": int((got == 0).sum()), "worst": float(got.max()),
           "mean": float(got.mean()), "passes": n_passes,
           "unmask_differs": differs / max(total, 1)}
    if control:
        gap = (ref["best"][rows] - ref["other"][rows]) / std
        out.update(control_exact=int((gap == 0).sum()),
                   control_worst=float(gap.max()),
                   control_mean=float(gap.mean()))
    return out
