"""Plain float32 reference of a hybrid decoder LM — linear-attention
layers (a gated delta rule with a decay a channel: Kimi Delta Attention,
arXiv:2510.26692) with a latent-attention layer closing every group of
``layer_group_size``, over routed experts (inclusionAI/Ling-3.0-flash,
``bailing_hybrid``): the forward pass over ONE whole sequence in
``jax.numpy``, every product at "highest" precision. The recurrence runs
ONE TOKEN AT A TIME by ``lax.scan`` — no chunks, no cache, no batching,
no kernels; the latent layers attend in the published NON-absorbed form;
the experts are a plain loop over the held ids; the head is computed
16,384 columns at a time. The benchmark's own copy: nothing here imports
the program (the pieces shared with ``latent_moe_lm``, the benchmark's
own too, are imported from there: RMS norm, RoPE's rotation, the gated
MLP, the router, the expert layer).

A linear-attention layer, ``x`` the RMS-normed residual, ``H`` heads of
``d``:

    q~, k~, v~ = x W_qkv                               3 H d
    q, k, v = SiLU(conv(.)): causal, depthwise, kernel 4, no bias
    q <- q / sqrt(|q|^2 + 1e-6) * d^-0.5;  k <- k / sqrt(|k|^2 + 1e-6)
    log alpha = kda_lower_bound * sigmoid(exp(A_log_h) * (x W_f + dt_bias))
    beta = sigmoid(x W_b)
    S_t = (I - beta_t k_t k_t^T) Diag(alpha_t) S_{t-1} + beta_t k_t v_t^T
    o_t = S_t^T q_t;  out = (RMSNorm_d(o_t) * sigmoid(x W_g)_h) W_o

A latent layer: ``latent_moe_lm``'s with ``q = x W_q`` (no query rank),
plain RoPE at ``rope_theta`` (score scale ``(nope + rope) ** -0.5``) and
the head-wise output gate ``sigmoid(x W_g)_h`` before ``W_o``.

It is given the chip's share like the program: ``held = (lo, hi)`` of
the router's ``num_experts`` and the vocabulary's slice (the shapes of
``embed`` / ``head``). Each matrix is cast from bfloat16 to float32 as it
is reached. Departures from the published model are the configuration's
``assumed`` (``benchmark/configs/Ling-3.0-flash.json``): the next-token
module left out, the clamped SwiGLU absent from every layer held, the
gate's lower-bound form, random weights.

``control`` names a CONTROL, not the reference — the same equations at
the next precision down, which the comparison that decides ``correct``
has to tell from the reference; no benchmark run computes one:
``"state_bf16"`` keeps the recurrent state ``S`` in bfloat16 between
tokens (the state is the one thing this model carries that no other
does, and float32 is what the configuration states for it);
``"float8"`` rounds every matrix and the cached latent to
``float8_e4m3fn`` as ``latent_moe_lm``'s control does.

``cfg`` is the keyword arguments the served model is built with (the
published config's keys).
"""
from __future__ import annotations

import functools

import numpy as np

from . import latent_moe_lm as base
from .latent_moe_lm import _f32, _rms

CONTROLS = ("state_bf16", "float8")
# the control whose numbers the driver compares (``control_mean``); the
# other is read beside it
COMPARED = "float8"
HEAD_COLUMNS = 16384


def delta_rule(q, k, v, g, beta, state_dtype="float32"):
    """The recurrence, one token at a time: ``q``, ``k``, ``v``, ``g (L,
    H, d)``, ``beta (L, H)`` -> ``o (L, H, d)``. ``S`` starts at zero and
    is kept in ``state_dtype`` between tokens."""
    import jax
    import jax.numpy as jnp
    L, H, d = q.shape

    def token(S, x):
        q, k, v, g, beta = x
        S = jnp.exp(g)[:, :, None] * S.astype(jnp.float32)
        S = S - beta[:, None, None] * k[:, :, None] * jnp.einsum(
            "hk,hkv->hv", k, S, precision="highest")[:, None, :]
        S = S + beta[:, None, None] * k[:, :, None] * v[:, None, :]
        o = jnp.einsum("hk,hkv->hv", q, S, precision="highest")
        return S.astype(state_dtype), o

    _, o = jax.lax.scan(token, jnp.zeros((H, d, d), state_dtype),
                        (q, k, v, g, beta))
    return o


def linear_attention(h, w, *, heads, d, kernel, g_floor, eps, low,
                     state_dtype):
    """One linear-attention layer over ``h (L, D)``; returns the
    residual's increment."""
    import jax
    import jax.numpy as jnp
    with jax.default_matmul_precision("highest"):
        L = h.shape[0]
        x = _rms(h, w["attn_g"], eps)
        raw = x @ _f32(w["wqkv"], low)                    # (L, 3 H d)
        padded = jnp.pad(raw, ((kernel - 1, 0), (0, 0)))
        y = sum(w["conv_w"][j] * padded[j:j + L] for j in range(kernel))
        q, k, v = jnp.split(jax.nn.silu(y).reshape(L, 3 * heads, d), 3,
                            axis=1)
        q = q * jax.lax.rsqrt(jnp.sum(q * q, -1, keepdims=True) + 1e-6) \
            * d ** -0.5
        k = k * jax.lax.rsqrt(jnp.sum(k * k, -1, keepdims=True) + 1e-6)
        a = (x @ _f32(w["wf"], low) + w["dt_bias"]).reshape(L, heads, d)
        g = g_floor * jax.nn.sigmoid(jnp.exp(w["A_log"])[:, None] * a)
        beta = jax.nn.sigmoid(x @ _f32(w["wb"], low))
        o = delta_rule(q, k, v, g, beta, state_dtype)
        o = _rms(o, w["o_g"], eps) \
            * jax.nn.sigmoid(x @ _f32(w["wg"], low))[:, :, None]
        return o.reshape(L, heads * d) @ _f32(w["wo"], low)


def latent_attention(h, w, freqs, *, heads, nope, rope, v_dim, rank, eps,
                     low):
    """One latent-attention layer, published (non-absorbed) form, no
    query rank, plain RoPE, head-wise output gate."""
    import jax
    import jax.numpy as jnp
    with jax.default_matmul_precision("highest"):
        L = h.shape[0]
        x = _rms(h, w["attn_g"], eps)
        q = (x @ _f32(w["wq"], low)).reshape(L, heads, nope + rope)
        q_nope, q_r = q[..., :nope], base._rope(q[..., nope:], freqs, 1.0)
        ckv = x @ _f32(w["wkv_a"], low)
        c_kv = _rms(ckv[:, :rank], w["kv_g"], eps)
        k_r = base._rope(ckv[:, rank:], freqs, 1.0)
        if low:     # the cached latent, in the control's precision
            c_kv, k_r = _f32(c_kv, True), _f32(k_r, True)
        k_nope = (c_kv @ _f32(w["wk_b"], low)).reshape(L, heads, nope)
        v = (c_kv @ _f32(w["wv_b"], low)).reshape(L, heads, v_dim)
        s = (jnp.einsum("qhd,khd->hqk", q_nope, k_nope)
             + jnp.einsum("qhd,kd->hqk", q_r, k_r)) * (nope + rope) ** -0.5
        causal = jnp.tril(jnp.ones((L, L), bool))
        p = jax.nn.softmax(jnp.where(causal[None], s, -jnp.inf), -1)
        out = jnp.einsum("hqk,khd->qhd", p, v) \
            * jax.nn.sigmoid(x @ _f32(w["wg"], low))[:, :, None]
        return out.reshape(L, heads * v_dim) @ _f32(w["wo"], low)


@functools.lru_cache(maxsize=None)
def _jitted():
    import jax
    return (jax.jit(linear_attention, static_argnames=(
                "heads", "d", "kernel", "g_floor", "eps", "low",
                "state_dtype")),
            jax.jit(latent_attention, static_argnames=(
                "heads", "nope", "rope", "v_dim", "rank", "eps", "low")))


_LINEAR = ("attn_g", "wqkv", "conv_w", "wf", "wb", "wg", "A_log",
           "dt_bias", "o_g", "wo")
_LATENT = ("attn_g", "wq", "wkv_a", "kv_g", "wk_b", "wv_b", "wg", "wo")


def hidden_states(params, tokens, cfg, held, control=None, routed=None):
    """``tokens (L,)`` -> final-norm hidden states ``(L, D)`` float32.
    ``routed``, a list, is given the router's choice of every expert
    layer, ``(L, top_k)`` ids each."""
    import jax.numpy as jnp
    linear, latent = _jitted()
    _, mlp, _ = base._jitted()
    low = control == "float8"
    state_dtype = "bfloat16" if control == "state_bf16" else "float32"
    eps = float(cfg.get("rms_norm_eps", 1e-6))
    rope = cfg["qk_rope_head_dim"]
    freqs = jnp.asarray((1.0 / float(cfg["rope_theta"]) ** (
        np.arange(0, rope, 2, dtype=np.float64) / rope)).astype(np.float32))
    h = _f32(params["embed"][tokens], low)
    for i in range(cfg["num_hidden_layers"]):
        l = "l%d." % i
        if (i + 1) % cfg["layer_group_size"]:
            h = h + linear(
                h, {n: params[l + n] for n in _LINEAR},
                heads=cfg["num_attention_heads"], d=cfg["head_dim"],
                kernel=cfg.get("short_conv_kernel_size", 4),
                g_floor=float(cfg.get("kda_lower_bound", -5.0)), eps=eps,
                low=low, state_dtype=state_dtype)
        else:
            h = h + latent(
                h, {n: params[l + n] for n in _LATENT}, freqs,
                heads=cfg["num_attention_heads"],
                nope=cfg["qk_nope_head_dim"], rope=rope,
                v_dim=cfg["v_head_dim"], rank=cfg["kv_lora_rank"],
                eps=eps, low=low)
        x = _rms(h, params[l + "ffn_g"], eps)
        if i < cfg["first_k_dense_replace"]:
            h = h + mlp(x, *(params[l + n] for n in (
                "w_gate", "w_up", "w_down")), low=low)
        else:
            out, ids = base.moe_layer(x, params, l, cfg, held, low)
            h = h + out
            if routed is not None:
                routed.append(ids)
    return _rms(h, params["out_g"], eps)


def logits_rows(params, tokens, first_row, n_rows, cfg, held, control=None,
                routed=None):
    """Logits ``(n_rows, vocab)`` of positions ``first_row ..`` of the
    sequence ``tokens``, on the host, the head a block of
    :data:`HEAD_COLUMNS` columns at a time. Tokens after the rows asked
    for cannot reach them (causal; a recurrence runs forward), so a
    sequence may be padded to a fixed length."""
    h = hidden_states(params, tokens, cfg, held, control, routed)
    head = params["head"]
    return np.concatenate([
        np.asarray(base._head(h, head[:, c:c + HEAD_COLUMNS], first_row,
                              n_rows, control == "float8"))
        for c in range(0, head.shape[1], HEAD_COLUMNS)], axis=1)


def teacher_forced(params, prompt, served, padded_len, n_rows, cfg, held,
                   control=False, routed=None):
    """One dense forward over prompt + served tokens: position
    ``P-1+i`` must predict served token ``i``. Over ALL the served
    tokens, in units of the standard deviation of the reference's
    logits: ``worst``, the widest gap by which a served token's logit
    lies below the reference's best, and ``mean``, the mean gap (0 where
    the served token is the reference's own). ``padded_len`` and
    ``n_rows`` only fix the compiled shapes. With ``control`` the same
    two numbers for the tokens each of :data:`CONTROLS` puts first at
    each position of the same sequence, under ``<control>_worst`` and
    ``<control>_mean``; ``control_mean`` / ``control_worst``, which the
    driver compares, are :data:`COMPARED`'s. ``routed`` as in
    :func:`hidden_states`."""
    import jax.numpy as jnp
    P, n = len(prompt), len(served)
    seq = np.zeros((padded_len,), np.int32)
    seq[:P] = prompt
    seq[P:P + n - 1] = served[:n - 1]
    args = (params, jnp.asarray(seq), jnp.int32(P - 1), n_rows, cfg, held)
    rows = logits_rows(*args, routed=routed)[:n]
    std = float(rows.std())

    def gaps(tokens):
        return (rows.max(axis=1) - rows[np.arange(n), tokens]) / std

    got = gaps(np.asarray(served))
    out = {"tokens": int(n), "prompt_len": int(P), "logit_std": std,
           "exact": int((got == 0).sum()), "worst": float(got.max()),
           "mean": float(got.mean())}
    if control:
        for name in CONTROLS:
            low = gaps(logits_rows(*args, control=name)[:n].argmax(axis=1))
            out.update({name + "_exact": int((low == 0).sum()),
                        name + "_worst": float(low.max()),
                        name + "_mean": float(low.mean())})
        out.update(control=COMPARED,
                   control_exact=out[COMPARED + "_exact"],
                   control_worst=out[COMPARED + "_worst"],
                   control_mean=out[COMPARED + "_mean"])
    return out
