"""Plain float32 reference of a hybrid decoder LM — state-space layers (a
selective scan, Mamba-1, arXiv:2312.00752) with a full-attention layer
every ``attn_layer_period``-th, over a dense gated MLP
(ai21labs/AI21-Jamba2-3B, ``jamba`` with one expert): the forward pass
over ONE whole sequence in ``jax.numpy``, every product at "highest"
precision. The recurrence runs ONE TOKEN AT A TIME by ``lax.scan`` — no
chunks, no cache, no batching, no kernels; attention is the full causal
softmax over the sequence; the head is the embedding's own matrix,
computed 16,384 columns at a time. The benchmark's own copy: nothing
here imports the program.

A state-space layer, ``x`` the RMS-normed residual (``E = mamba_expand
hidden_size``, ``N = mamba_d_state``, ``R = mamba_dt_rank``):

    [u~, z] = x W_in;  u = SiLU(conv_K(u~) + b_conv)   causal, depthwise
    [d~, B, C] = u W_x;  d~, B, C <- RMSNorm(d~), RMSNorm(B), RMSNorm(C)
    delta = softplus(d~ W_dt + b_dt);  A = -exp(A_log)
    h_t = exp(delta_t (x) A) . h_{t-1} + (delta_t u_t) (x) B_t
    y_t = h_t C_t + D_skip . u_t;  out = (y . SiLU(z)) W_out

An attention layer: ``q``, ``k``, ``v = x W_q, x W_k, x W_v``, no
position encoding, causal softmax at ``head_dim ** -0.5``, query head
``j`` reading key/value head ``j // (heads / kv heads)``, ``W_o``. Every
layer then ``h += (SiLU(x W_gate) . x W_up) W_down`` of its own RMS norm;
a final RMS norm; logits ``= h E^T``.

Each matrix is cast from its stored dtype (bfloat16 in a benchmark run)
to float32 as it is reached, a layer at a time: 12 GB of float32
matrices never stand whole beside the program's. ``A_log`` is held ``(N,
E)``, the published array transposed (the weights are seeded).
Departures from the published model are the configuration's ``assumed``
(``benchmark/configs/AI21-Jamba2-3B.json``): random weights, the
embedding at deviation 0.02, ``h`` in float32 between tokens.

``control`` names a CONTROL, not the reference — the same equations at
the next precision down, which the comparison that decides ``correct``
has to tell from the reference; no benchmark run computes one:
``"state_bf16"`` keeps the recurrent state ``h`` in bfloat16 between
tokens (the state is the one thing this model carries that no other
does, and float32 is what the configuration states for it);
``"float8"`` rounds every matrix, the inputs of every product, the
cached keys and values and ``u~`` to ``float8_e4m3fn`` (the nearest
precision under the bfloat16 the configuration states for them);
``"bf16"`` does the same at bfloat16 (what a CPU test at float32 has to
tell from the reference).

``cfg`` is the keyword arguments the served model is built with (the
published config's keys).
"""
from __future__ import annotations

import functools

import numpy as np

CONTROLS = ("state_bf16", "float8")
# the control whose numbers the driver compares (``control_mean``); the
# other is read beside it
COMPARED = "float8"
HEAD_COLUMNS = 16384
_LOW = {"float8": "float8_e4m3fn", "bf16": "bfloat16"}


def _f32(a, low=None):
    """``a`` in float32; through ``low`` (a dtype's name) first where
    given."""
    import jax.numpy as jnp
    if low:
        a = a.astype(low)
    return a.astype(jnp.float32)


def _mm(x, w, low=None):
    return _f32(x, low) @ _f32(w, low)


def _rms(x, g, eps):
    import jax
    import jax.numpy as jnp
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g


def selective_scan(u, delta, b, c, a, state_dtype="float32"):
    """The recurrence, one token at a time: ``u``, ``delta (L, E)``,
    ``b``, ``c (L, N)``, ``a (N, E)`` -> ``y (L, E)`` without the skip.
    ``h`` starts at zero and is kept in ``state_dtype`` between tokens."""
    import jax
    import jax.numpy as jnp

    def token(h, x):
        u, delta, b, c = x
        h = jnp.exp(delta[None, :] * a) * h.astype(jnp.float32) \
            + b[:, None] * (delta * u)[None, :]
        return h.astype(state_dtype), jnp.sum(h * c[:, None], axis=0)

    _, y = jax.lax.scan(token, jnp.zeros(a.shape, state_dtype),
                        (u, delta, b, c))
    return y


def ssm_layer(h, w, *, kernel, n_state, dt_rank, eps, low, state_dtype):
    """One state-space layer over ``h (L, D)``; returns the residual's
    increment."""
    import jax
    import jax.numpy as jnp
    with jax.default_matmul_precision("highest"):
        L = h.shape[0]
        N, R = n_state, dt_rank
        x = _rms(h, w["mix_g"], eps)
        uz = _mm(x, w["win"], low)
        E = uz.shape[1] // 2
        # what a row's state holds of the rows before, in the control's
        # precision
        raw = _f32(uz[:, :E], low)
        z = uz[:, E:]
        padded = jnp.pad(raw, ((kernel - 1, 0), (0, 0)))
        u = jax.nn.silu(sum(w["conv_w"][j] * padded[j:j + L]
                            for j in range(kernel)) + w["conv_b"])
        dbc = _mm(u, w["wx"], low)
        d = _rms(dbc[:, :R], w["dt_g"], eps)
        b = _rms(dbc[:, R:R + N], w["b_g"], eps)
        c = _rms(dbc[:, R + N:], w["c_g"], eps)
        delta = jax.nn.softplus(_mm(d, w["wdt"], low) + w["dt_b"])
        y = selective_scan(u, delta, b, c, -jnp.exp(w["A_log"]),
                           state_dtype)
        return _mm((y + w["D"] * u) * jax.nn.silu(z), w["wout"], low)


def attention_layer(h, w, *, heads, kv_heads, head_dim, eps, low):
    """One full-attention layer, no position encoding."""
    import jax
    import jax.numpy as jnp
    with jax.default_matmul_precision("highest"):
        L = h.shape[0]
        x = _rms(h, w["mix_g"], eps)
        q = _mm(x, w["wq"], low).reshape(L, kv_heads, heads // kv_heads,
                                         head_dim)
        # the cached keys and values, in the control's precision
        k = _f32(_mm(x, w["wk"], low), low).reshape(L, kv_heads, head_dim)
        v = _f32(_mm(x, w["wv"], low), low).reshape(L, kv_heads, head_dim)
        s = jnp.einsum("qhgd,khd->hgqk", q, k) * head_dim ** -0.5
        causal = jnp.tril(jnp.ones((L, L), bool))
        p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), -1)
        out = jnp.einsum("hgqk,khd->qhgd", p, v)
        return _mm(out.reshape(L, heads * head_dim), w["wo"], low)


def mlp(h, g, w_gate, w_up, w_down, *, eps, low):
    import jax
    with jax.default_matmul_precision("highest"):
        x = _rms(h, g, eps)
        return _mm(jax.nn.silu(_mm(x, w_gate, low)) * _mm(x, w_up, low),
                   w_down, low)


def _head(h, columns, first_row, n_rows, low):
    import jax
    with jax.default_matmul_precision("highest"):
        rows = jax.lax.dynamic_slice_in_dim(h, first_row, n_rows, axis=0)
        return _f32(rows, low) @ _f32(columns, low).T


@functools.lru_cache(maxsize=None)
def _jitted():
    import jax
    return (jax.jit(ssm_layer, static_argnames=(
                "kernel", "n_state", "dt_rank", "eps", "low",
                "state_dtype")),
            jax.jit(attention_layer, static_argnames=(
                "heads", "kv_heads", "head_dim", "eps", "low")),
            jax.jit(mlp, static_argnames=("eps", "low")),
            jax.jit(_head, static_argnames=("n_rows", "low")))


_SSM = ("mix_g", "win", "conv_w", "conv_b", "wx", "dt_g", "b_g", "c_g",
        "wdt", "dt_b", "A_log", "D", "wout")
_ATTENTION = ("mix_g", "wq", "wk", "wv", "wo")


def _widths(cfg):
    heads = cfg["num_attention_heads"]
    return heads, cfg["num_key_value_heads"], \
        cfg.get("head_dim") or cfg["hidden_size"] // heads


def hidden_states(params, tokens, cfg, control=None):
    """``tokens (L,)`` -> final-norm hidden states ``(L, D)`` float32."""
    ssm, attention, ffn, _ = _jitted()
    low = _LOW.get(control)
    state_dtype = "bfloat16" if control == "state_bf16" else "float32"
    eps = float(cfg.get("rms_norm_eps", 1e-6))
    heads, kv_heads, head_dim = _widths(cfg)
    h = _f32(params["embed"][tokens], low)
    for i in range(cfg["num_hidden_layers"]):
        l = "l%d." % i
        if i % cfg["attn_layer_period"] == cfg["attn_layer_offset"]:
            h = h + attention(
                h, {n: params[l + n] for n in _ATTENTION}, heads=heads,
                kv_heads=kv_heads, head_dim=head_dim, eps=eps, low=low)
        else:
            h = h + ssm(
                h, {n: params[l + n] for n in _SSM},
                kernel=cfg["mamba_d_conv"], n_state=cfg["mamba_d_state"],
                dt_rank=cfg["mamba_dt_rank"], eps=eps, low=low,
                state_dtype=state_dtype)
        h = h + ffn(h, params[l + "ffn_g"], params[l + "w_gate"],
                    params[l + "w_up"], params[l + "w_down"], eps=eps,
                    low=low)
    return _rms(h, params["out_g"], eps)


def logits_rows(params, tokens, first_row, n_rows, cfg, control=None):
    """Logits ``(n_rows, vocab)`` of positions ``first_row ..`` of the
    sequence ``tokens``, on the host, the head — the embedding's own
    matrix — a block of :data:`HEAD_COLUMNS` columns at a time. Tokens
    after the rows asked for cannot reach them (causal; a recurrence runs
    forward), so a sequence may be padded to a fixed length."""
    head = _jitted()[3]
    h = hidden_states(params, tokens, cfg, control)
    embed = params["embed"]
    return np.concatenate([
        np.asarray(head(h, embed[c:c + HEAD_COLUMNS], first_row, n_rows,
                        _LOW.get(control)))
        for c in range(0, embed.shape[0], HEAD_COLUMNS)], axis=1)


def teacher_forced(params, prompt, served, padded_len, n_rows, cfg,
                   control=False):
    """One dense forward over prompt + served tokens: position ``P-1+i``
    must predict served token ``i``. Over ALL the served tokens, in units
    of the standard deviation of the reference's logits: ``worst``, the
    widest gap by which a served token's logit lies below the
    reference's best, and ``mean``, the mean gap (0 where the served
    token is the reference's own). ``padded_len`` and ``n_rows`` only fix
    the compiled shapes. With ``control`` the same two numbers for the
    tokens each of :data:`CONTROLS` puts first at each position of the
    same sequence, under ``<control>_worst`` and ``<control>_mean``;
    ``control_mean`` / ``control_worst``, which the driver compares, are
    :data:`COMPARED`'s."""
    import jax.numpy as jnp
    P, n = len(prompt), len(served)
    seq = np.zeros((padded_len,), np.int32)
    seq[:P] = prompt
    seq[P:P + n - 1] = served[:n - 1]
    args = (params, jnp.asarray(seq), jnp.int32(P - 1), n_rows, cfg)
    rows = logits_rows(*args)[:n]
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
