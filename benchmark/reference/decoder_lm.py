"""Plain float32 reference of the pre-LN decoder-only LM (the OPT block:
learned positions, full multi-head attention, ReLU feed-forward of two
matrices, final LayerNorm, eps 1e-5) — dense masked softmax attention
over one whole sequence, no cache, no kernels, no batching, every matrix
product at "highest" precision. Copied from ``chip_smoke.dense_reference``
(PR 21) so that the program cannot move the yardstick.

Departures from facebook/opt-6.7b, the same as the served model's (listed
in ``configs/opt-6.7b.json``): no biases on the linear layers, an output
head that is not tied to the embedding, no +2 offset on the positions.

Parameters are the flat ``{name: array}`` dict ``ToyDecoderLM`` uses.

``low=True`` is the CONTROL, not the reference: the same equations with
every weight and every activation in bfloat16 at the default precision,
the nearest precision below the float32 the configuration states. The
comparison that decides ``correct`` has to tell it from the reference
(``benchmark/tests/test_correct.py``); no benchmark run computes it.
"""
from __future__ import annotations

import math

import numpy as np


def hidden_states(params, tokens, n_layers, n_heads, head_dim, low=False):
    """``tokens (L,)`` -> final-LayerNorm hidden states ``(L, d_model)``."""
    import jax
    import jax.numpy as jnp
    if low:
        params = {k: v.astype(jnp.bfloat16) for k, v in params.items()}

    def ln(x, g, b):
        mu = jnp.mean(x, -1, keepdims=True)
        var = jnp.mean((x - mu) ** 2, -1, keepdims=True)
        return (x - mu) / jnp.sqrt(var + 1e-5) * g + b

    L = tokens.shape[0]
    with jax.default_matmul_precision("default" if low else "highest"):
        h = params["embed"][tokens] + params["pos"][:L]
        causal = jnp.tril(jnp.ones((L, L), bool))
        for i in range(n_layers):
            w = {n: params["l%d.%s" % (i, n)] for n in (
                "att_g", "att_b", "wq", "wk", "wv", "wo", "ffn_g",
                "ffn_b", "w1", "w2")}
            x = ln(h, w["att_g"], w["att_b"])
            q, k, v = ((x @ w[n]).reshape(L, n_heads, head_dim)
                       for n in ("wq", "wk", "wv"))
            s = jnp.einsum("qhd,khd->hqk", q, k) / math.sqrt(head_dim)
            p = jax.nn.softmax(jnp.where(causal[None], s, -jnp.inf), -1)
            h = h + jnp.einsum("hqk,khd->qhd", p, v).reshape(L, -1) \
                @ w["wo"]
            x = ln(h, w["ffn_g"], w["ffn_b"])
            h = h + jax.nn.relu(x @ w["w1"]) @ w["w2"]
        return ln(h, params["out_g"], params["out_b"])


def logits_rows(params, tokens, first_row, n_rows, n_layers, n_heads,
                head_dim, low=False):
    """Logits ``(n_rows, vocab)`` of positions ``first_row ..`` of the
    sequence ``tokens``. Tokens after the rows asked for cannot reach
    them (causal), so a sequence may be padded to a fixed length."""
    import jax
    h = hidden_states(params, tokens, n_layers, n_heads, head_dim, low)
    rows = jax.lax.dynamic_slice_in_dim(h, first_row, n_rows, axis=0)
    if low:
        return rows @ params["wout"].astype(rows.dtype)
    with jax.default_matmul_precision("highest"):
        return rows @ params["wout"]


def teacher_forced(params, prompt, served, padded_len, n_rows, n_layers,
                   n_heads, head_dim, control=False):
    """One dense forward over prompt + served tokens: position
    ``P-1+i`` must predict served token ``i``. Over ALL the served
    tokens, in units of the standard deviation of the reference's
    logits: ``worst``, the widest gap by which a served token's logit
    lies below the reference's best, and ``mean``, the mean gap (0 where
    the served token is the reference's own). ``padded_len`` and
    ``n_rows`` only fix the compiled shapes (a causal model's rows do
    not see what follows them). With ``control`` the same two numbers
    for the tokens the bfloat16 control puts first at each position of
    the same sequence, under ``control_worst`` and ``control_mean``."""
    import jax
    import jax.numpy as jnp
    P, n = len(prompt), len(served)
    seq = np.zeros((padded_len,), np.int32)
    seq[:P] = prompt
    seq[P:P + n - 1] = served[:n - 1]
    fn = jax.jit(logits_rows, static_argnums=(3, 4, 5, 6, 7))
    args = (params, jnp.asarray(seq), jnp.int32(P - 1), n_rows, n_layers,
            n_heads, head_dim)
    rows = np.asarray(fn(*args))[:n]
    std = float(rows.std())

    def gaps(tokens):
        return (rows.max(axis=1) - rows[np.arange(n), tokens]) / std

    got = gaps(np.asarray(served))
    out = {"tokens": int(n), "prompt_len": int(P), "logit_std": std,
           "exact": int((got == 0).sum()), "worst": float(got.max()),
           "mean": float(got.mean())}
    if control:
        low = gaps(np.asarray(fn(*args, True))[:n].argmax(axis=1))
        out.update(control_exact=int((low == 0).sum()),
                   control_worst=float(low.max()),
                   control_mean=float(low.mean()))
    return out
