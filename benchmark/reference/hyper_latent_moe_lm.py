"""Plain float32 reference of a latent-attention, routed-expert decoder
LM whose residual path is FOUR streams mixed by manifold-constrained
hyper-connections, with its next-token module (XingChen-AGI/
Xing4.0-29B-A4B, ``model_type`` ``xing4_0``): ``jax.numpy`` over ONE
whole sequence, every product at "highest" precision, attention in the
published NON-absorbed form, no cache, no kernels, no batching, the
experts by a plain loop, the Sinkhorn normalisation by a plain loop. The
benchmark's own copy: nothing here imports the program. The two
sublayers ``F`` — ``Attn o RMSNorm`` and ``FFN o RMSNorm``, the
DeepSeek-V3 block — are the ones ``benchmark/reference/latent_moe_lm.py``
already states (latent attention, sigmoid routing with one shared
expert), used from there.

**The residual path** (mHC, arXiv:2512.24880, after hyper-connections,
arXiv:2409.19606), ``n = hc_mult`` streams of width ``C``, state ``X (n,
C)`` a token, float32. In: the token's embedding in every stream. Every
sublayer ``F`` has ``Phi (n C, 2 n + n n)`` (``[Phi_pre, Phi_post,
Phi_res]`` side by side), biases ``b (2 n + n n)`` and gates ``alpha
(3)``:

    x~     = vec(X) / sqrt(mean(vec(X)^2) + rms_norm_eps)     no gain
    H_pre  = sigmoid(alpha_pre  x~ Phi_pre  + b_pre)          (n)
    H_post = 2 sigmoid(alpha_post x~ Phi_post + b_post)       (n)
    H_res  = SK(clip(alpha_res mat(x~ Phi_res) + B_res, -30, 30))
    SK(M)  : M = exp(M); 20 times  M <- M / (rowsum + hc_eps),
                                   M <- M / (colsum + hc_eps)
    u = H_pre X;   y = F(u);   X' = H_res X + H_post^T y

Out: ``h = sum_i X_i``, the final RMSNorm, the head.

**The next-token module** (DeepSeek-V3 section 2.2, depth 1): position
``i`` reads the main model's ``h_i`` (summed over the streams, BEFORE
the final norm) and the NEXT token, ``h'_i = W_p [RMSNorm(h_i);
RMSNorm(Emb(t_{i+1}))]``, runs one block of the expert-layer kind over
it, wrapped in the same four-stream mixing (in replicated, out summed),
then its own final norm and the main model's head: logits for
``t_{i+2}``. Here it is teacher-forced from THIS reference's hidden
states and the sequence's own tokens.

Departures from the published model, beside those of
``latent_moe_lm.py`` (random weights; ``e_score_correction_bias`` what
``router_b`` holds; ``kv_b_proj`` held as its two halves; RoPE half
against half):
- the published modelling code is not in the catalog: which ``eps`` the
  ``n C``-wide norm takes (``rms_norm_eps``) and where ``hc_eps`` sits
  (Sinkhorn's denominators), rows before columns, replicate-in /
  sum-out, ``h_i`` before the final norm and the module's block inside
  the mixing are the mHC paper's and DeepSeek-V3's forms, listed in the
  configuration file under ``assumed``.

Two CONTROLS, never the reference: ``control="float8"`` rounds every
matrix and the cached latent to ``float8_e4m3fn`` (the next precision
under the bfloat16 the configuration states); ``control="mix_bf16"``
computes ONLY the mixing coefficients (the norm, the product, sigmoid,
Sinkhorn) in bfloat16 and everything else as the reference does. The
comparison that decides ``correct`` has to tell both from the
reference.

``cfg`` is the keyword arguments the served model is built with (the
published config's keys).
"""
from __future__ import annotations

import functools

import numpy as np

from . import latent_moe_lm as base

CONTROLS = ("float8", "mix_bf16")
HEAD_COLUMNS = 16384        # the head is read in blocks of columns


def mixing(X, w, a, b, *, n, iters, hc_eps, clamp, eps, low):
    """The three coefficient sets of one sublayer for the states ``X (L,
    n, C)``: ``H_pre (L, n)``, ``H_post (L, n)``, ``H_res (L, n, n)``.
    ``low``: the whole path in bfloat16 (the second control)."""
    import jax
    import jax.numpy as jnp
    kind = jnp.bfloat16 if low else jnp.float32
    with jax.default_matmul_precision("highest"):
        L = X.shape[0]
        flat = X.reshape(L, -1).astype(kind)
        xt = flat * jax.lax.rsqrt(
            jnp.mean(flat * flat, -1, keepdims=True) + kind(eps))
        z = jnp.dot(xt, w.astype(kind), preferred_element_type=kind)
        a, b = a.astype(kind), b.astype(kind)
        pre = jax.nn.sigmoid(a[0] * z[:, :n] + b[:n])
        post = 2 * jax.nn.sigmoid(a[1] * z[:, n:2 * n] + b[n:2 * n])
        m = a[2] * z[:, 2 * n:].reshape(L, n, n) + b[2 * n:].reshape(n, n)
        m = jnp.exp(jnp.clip(m, kind(clamp[0]), kind(clamp[1])))
        for _ in range(iters):
            m = m / (m.sum(-1, keepdims=True) + kind(hc_eps))
            m = m / (m.sum(-2, keepdims=True) + kind(hc_eps))
        f32 = jnp.float32
        return pre.astype(f32), post.astype(f32), m.astype(f32)


def read(X, pre):
    """``u = H_pre X``."""
    import jax.numpy as jnp
    return jnp.sum(pre[:, :, None] * X, axis=1)


def write(X, y, post, res):
    """``X' = H_res X + H_post^T y``."""
    import jax.numpy as jnp
    return jnp.sum(res[:, :, :, None] * X[:, None, :, :], axis=2) \
        + post[:, :, None] * y[:, None, :]


@functools.lru_cache(maxsize=None)
def _jitted():
    import jax
    return (jax.jit(mixing, static_argnames=(
                "n", "iters", "hc_eps", "clamp", "eps", "low")),
            jax.jit(read), jax.jit(write))


def block(X, params, i, cfg, held, freqs, low, mix_low, routed=None):
    """Block ``i`` over the states ``X (L, n, C)``."""
    mix, rd, wr = _jitted()
    attn, mlp, _ = base._jitted()
    eps = float(cfg.get("rms_norm_eps", 1e-6))
    ys = cfg["rope_scaling"]
    gain = base.mscale(ys["factor"], ys.get("mscale", 1)) \
        / base.mscale(ys["factor"], ys.get("mscale_all_dim", 0))
    n = cfg["hc_mult"]
    l = "l%d." % i

    def coefficients(sub):
        return mix(X, params[l + sub + "hc_w"], params[l + sub + "hc_a"],
                   params[l + sub + "hc_b"], n=n,
                   iters=cfg["hc_sinkhorn_iters"],
                   hc_eps=float(cfg["hc_eps"]),
                   clamp=(float(cfg["mhc_h_res_clamp_min"]),
                          float(cfg["mhc_h_res_clamp_max"])),
                   eps=eps, low=mix_low)

    pre, post, res = coefficients("attn_")
    w = {k: params[l + k] for k in (
        "attn_g", "wq_a", "q_g", "wq_b", "wkv_a", "kv_g", "wk_b", "wv_b",
        "wo")}
    y = attn(rd(X, pre), w, freqs, heads=cfg["num_attention_heads"],
             nope=cfg["qk_nope_head_dim"], rope=cfg["qk_rope_head_dim"],
             v_dim=cfg["v_head_dim"], rank=cfg["kv_lora_rank"], eps=eps,
             scale=base.score_scale(cfg), gain=gain, low=low)
    X = wr(X, y, post, res)
    pre, post, res = coefficients("ffn_")
    x = base._rms(rd(X, pre), params[l + "ffn_g"], eps)
    if i < cfg["first_k_dense_replace"]:
        y = mlp(x, *(params[l + k] for k in ("w_gate", "w_up", "w_down")),
                low=low)
    else:
        y, ids = base.moe_layer(x, params, l, cfg, held, low)
        if routed is not None:
            routed.append(ids)
    return wr(X, y, post, res)


def _streams(e, n):
    import jax.numpy as jnp
    return jnp.broadcast_to(e[:, None, :], (e.shape[0], n, e.shape[1]))


def hidden_states(params, tokens, cfg, held, control=None, routed=None):
    """``tokens (L,)`` -> ``(h, h_next)``, both ``(L, D)`` float32 and
    BEFORE their final norms: the main model's state summed over the
    streams, and the next-token module's, teacher-forced — position
    ``i`` reads ``h[i]`` and ``tokens[i + 1]`` (the last position reads
    a token that is not there and is never asked for). ``routed``, a
    list, is given the router's choice of every expert layer of the
    MAIN model."""
    import jax.numpy as jnp
    low, mix_low = control == "float8", control == "mix_bf16"
    eps = float(cfg.get("rms_norm_eps", 1e-6))
    n, depth = cfg["hc_mult"], cfg["num_hidden_layers"]
    freqs = jnp.asarray(base.inv_freq(cfg))
    X = _streams(base._f32(params["embed"][tokens], low), n)
    for i in range(depth):
        X = block(X, params, i, cfg, held, freqs, low, mix_low, routed)
    h = X.sum(1)
    after = base._f32(params["embed"][jnp.roll(tokens, -1)], low)
    joined = jnp.concatenate([base._rms(h, params["mtp.h_g"], eps),
                              base._rms(after, params["mtp.e_g"], eps)], -1)
    X = _streams(_project(joined, params["mtp.proj"], low), n)
    X = block(X, params, depth, cfg, held, freqs, low, mix_low)
    return h, X.sum(1)


@functools.partial(__import__("jax").jit, static_argnames=("low",))
def _project(x, w, low):
    import jax
    with jax.default_matmul_precision("highest"):
        return x @ base._f32(w, low)


@functools.partial(__import__("jax").jit,
                   static_argnames=("n_rows", "columns", "eps", "low"))
def _head_block(h, g, head, first_row, first_column, n_rows, columns, eps,
                low):
    import jax
    rows = jax.lax.dynamic_slice_in_dim(h, first_row, n_rows, axis=0)
    part = jax.lax.dynamic_slice_in_dim(head, first_column, columns, axis=1)
    with jax.default_matmul_precision("highest"):
        return base._rms(rows, g, eps) @ base._f32(part, low)


def _logits(h, g, head, first_row, n_rows, eps, low):
    """``(n_rows, vocab)`` on the host: the final norm and the head over
    ``n_rows`` positions, the head's columns a block at a time (131,072
    columns of 3,584 are 1.9 GB in float32, beside 11 GB of weights)."""
    vocab = head.shape[1]
    columns = min(HEAD_COLUMNS, vocab)
    parts = []
    for c in range(0, vocab, columns):
        first = min(c, vocab - columns)         # the last block overlaps
        part = np.asarray(_head_block(h, g, head, first_row, first,
                                      n_rows, columns, eps, low))
        parts.append(part[:, c - first:])
    return np.concatenate(parts, axis=1)


def logits_rows(params, tokens, first_row, n_rows, cfg, held, control=None,
                routed=None):
    """``(main, module)`` logits ``(n_rows, vocab)`` of positions
    ``first_row ..`` of the sequence ``tokens``: the main model's
    predict the token AFTER each position, the module's the one after
    that. Tokens after the rows asked for cannot reach them (causal), so
    a sequence may be padded to a fixed length."""
    eps = float(cfg.get("rms_norm_eps", 1e-6))
    low = control == "float8"
    h, h_next = hidden_states(params, tokens, cfg, held, control, routed)
    return tuple(
        _logits(state, params[gain], params["head"], first_row, n_rows,
                eps, low)
        for state, gain in ((h, "out_g"), (h_next, "mtp.out_g")))


def _gaps(rows, tokens):
    """Per position, in deviations of ``rows``: how far the logit of
    ``tokens`` lies under the largest."""
    return (rows.max(axis=1) - rows[np.arange(len(tokens)), tokens]) \
        / float(rows.std())


def teacher_forced(params, prompt, served, drafts, padded_len, n_rows, cfg,
                   held, controls=(), routed=None):
    """One dense forward over prompt + served tokens. Main model:
    position ``P-1+i`` must predict served token ``i``; ``worst`` /
    ``mean`` are the widest and the mean gap by which a served token's
    logit lies below the reference's best, in deviations of the
    reference's logits, over ALL served tokens. Module: served token
    ``i >= 1`` was verified against ``drafts[i]`` (-1: against none),
    which the module made at position ``P+i-2`` from the true tokens
    before it; ``draft_worst`` / ``draft_mean`` are the same two numbers
    for the recorded drafts under the reference's MODULE logits, and
    ``accept`` the share of positions with a draft at which the
    reference's own module puts the served token first (its acceptance).
    For each of ``controls`` (:data:`CONTROLS`) the same four numbers
    for the tokens THAT control puts first at the same positions of the
    same sequence, under ``<control>_...``. ``padded_len`` and
    ``n_rows`` only fix the compiled shapes."""
    import jax.numpy as jnp
    P, n = len(prompt), len(served)
    served, drafts = np.asarray(served), np.asarray(drafts)
    seq = np.zeros((padded_len,), np.int32)
    seq[:P] = prompt
    seq[P:P + n - 1] = served[:n - 1]
    args = (params, jnp.asarray(seq), jnp.int32(P - 1), n_rows, cfg, held)
    main, module = logits_rows(*args, routed=routed)
    main, module = main[:n], module[:n - 1]
    # module row j (position P-1+j) predicts served token j+1
    judged = np.flatnonzero(drafts[1:n] >= 0)

    def read(prefix, tokens, drafted):
        got = _gaps(main, tokens)
        out = {prefix + "exact": int((got == 0).sum()),
               prefix + "worst": float(got.max()),
               prefix + "mean": float(got.mean())}
        if len(judged):
            low = _gaps(module[judged], drafted[judged])
            out.update({prefix + "draft_worst": float(low.max()),
                        prefix + "draft_mean": float(low.mean())})
        return out

    out = {"tokens": int(n), "prompt_len": int(P),
           "drafts": int(len(judged)), "logit_std": float(main.std()),
           "draft_logit_std": float(module.std()) if n > 1 else None,
           **read("", served, drafts[1:n])}
    if len(judged):
        out["accept"] = float(
            (module[judged].argmax(axis=1) == served[1:n][judged]).mean())
    for control in controls:
        c_main, c_module = logits_rows(*args, control=control)
        out.update(read(control + "_", c_main[:n].argmax(axis=1),
                        c_module[:n - 1].argmax(axis=1)))
    return out
