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
"""
from __future__ import annotations

import math

import numpy as np


def hidden_states(params, tokens, n_layers, n_heads, head_dim):
    """``tokens (L,)`` -> final-LayerNorm hidden states ``(L, d_model)``."""
    import jax
    import jax.numpy as jnp

    def ln(x, g, b):
        mu = jnp.mean(x, -1, keepdims=True)
        var = jnp.mean((x - mu) ** 2, -1, keepdims=True)
        return (x - mu) / jnp.sqrt(var + 1e-5) * g + b

    L = tokens.shape[0]
    with jax.default_matmul_precision("highest"):
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
                head_dim):
    """Logits ``(n_rows, vocab)`` of positions ``first_row ..`` of the
    sequence ``tokens``. Tokens after the rows asked for cannot reach
    them (causal), so a sequence may be padded to a fixed length."""
    import jax
    h = hidden_states(params, tokens, n_layers, n_heads, head_dim)
    rows = jax.lax.dynamic_slice_in_dim(h, first_row, n_rows, axis=0)
    with jax.default_matmul_precision("highest"):
        return rows @ params["wout"]


def teacher_forced_shortfall(params, prompt, served, n_check, padded_len,
                             n_layers, n_heads, head_dim):
    """One dense forward over prompt + served tokens: position
    ``P-1+i`` must predict served token ``i``. Returns, over the first
    ``n_check`` served tokens: how many are the reference's own argmax,
    the largest shortfall (reference's best logit minus the logit of the
    served token) and the standard deviation of the logits, which gives
    the shortfall its scale."""
    import jax
    import jax.numpy as jnp
    P = len(prompt)
    seq = np.zeros((padded_len,), np.int32)
    seq[:P] = prompt
    seq[P:P + n_check - 1] = served[:n_check - 1]
    fn = jax.jit(logits_rows, static_argnums=(3, 4, 5, 6))
    rows = np.asarray(fn(params, jnp.asarray(seq), jnp.int32(P - 1),
                         n_check, n_layers, n_heads, head_dim))
    got = np.asarray(served[:n_check])
    shortfall = rows.max(axis=1) - rows[np.arange(n_check), got]
    return {"exact": int((rows.argmax(axis=1) == got).sum()),
            "of": int(n_check), "max_shortfall": float(shortfall.max()),
            "logit_std": float(rows.std()), "prompt_len": int(P)}
