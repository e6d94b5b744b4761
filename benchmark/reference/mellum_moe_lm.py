"""Plain float32 reference of a decoder LM whose attention layers are
sliding-window with a full-attention layer every fourth, ONE count of
grouped-query heads, and softmax-routed experts in every layer — no
output gate, no shared expert, no dense layer
(JetBrains/Mellum2-12B-A2.5B-Instruct, ``model_type`` ``mellum``): the
forward pass over ONE whole sequence in ``jax.numpy``, every product at
"highest" precision. No kernel, no cache, no ring, no batching and no
mesh: the sliding window is a MASK over the whole sequence's scores;
every expert is computed for every token and masked by its routing
weight; the scores are computed :data:`QUERY_BLOCK` queries at a time
against the keys those queries can see, and the head
:data:`HEAD_COLUMNS` columns at a time, so that 5k tokens fit beside
the weights. The benchmark's own copy: nothing here imports the program
(the rotary tables and the rotation are ``window_moe_lm``'s, the cast,
RMS norm, the gated MLP and the head's rows ``latent_moe_lm``'s, the
softmax router ``block_diffusion_lm``'s: the benchmark's own, imported).

Layer ``i``, ``x`` the RMS-normed residual (pre-norm, eps 1e-6, no
biases):

    q = x W_q (H d),  k = x W_k,  v = x W_v (Hkv d);  no q/k norm
    RoPE by rope_parameters[layer_types[i]] over the WHOLE head:
      yarn    — YaRN's frequencies, cos and sin times attention_factor
      default — plain RoPE at rope_theta
    scores q . k / sqrt(d); query head j reads key head j // (H / Hkv);
    causal; in a sliding layer key t is visible to query s iff
    s - sliding_window < t <= s
    h = x_in + o W_o
    y = h + sum over the num_experts_per_tok experts of softmax(h^ W_r),
        their weights renormalised over the chosen, of
        W_down,e (silu(h^ W_gate,e) * h^ W_up,e)

It is handed the program's weights as they lie — over a mesh, sharded:
``jax.numpy`` follows the arrays — and ``held = (lo, hi)`` of the
router's ``num_experts`` (all of them where nothing is cut). Each matrix
is cast from bfloat16 to float32 as it is reached. Departures from the
published model are the configuration's ``assumed``
(``benchmark/configs/Mellum2-12B-A2.5B-Instruct.json``).

``control`` names a CONTROL, not the reference (``window_moe_lm``'s two:
``"float8"`` rounds every matrix and the cached keys and values to
``float8_e4m3fn``; ``"window_minus_one"`` sees ``sliding_window - 1``
keys).

``cfg`` is the keyword arguments the served model is built with (the
published config's keys).
"""
from __future__ import annotations

import functools

import numpy as np

from .block_diffusion_lm import route
from .latent_moe_lm import _f32, _head, _rms, gated_mlp
from .window_moe_lm import (COMPARED, CONTROLS, FULL, HEAD_COLUMNS,
                            QUERY_BLOCK, SLIDING, _rope, rope_table)


def attention(h, w, freqs, *, heads, kv_heads, head_dim, rot, gain,
              visible, eps, low):
    """One layer's attention over ``h (L, D)``; ``visible`` is how many
    keys a query sees with its own (None: all before it). Returns the
    residual's increment."""
    import jax
    import jax.numpy as jnp
    with jax.default_matmul_precision("highest"):
        L = h.shape[0]
        g = heads // kv_heads
        x = _rms(h, w["attn_g"], eps)
        q = _rope((x @ _f32(w["wq"], low)).reshape(L, heads, head_dim),
                  freqs, rot, gain)
        k = _rope((x @ _f32(w["wk"], low)).reshape(L, kv_heads, head_dim),
                  freqs, rot, gain)
        v = (x @ _f32(w["wv"], low)).reshape(L, kv_heads, head_dim)
        if low:     # the cached keys and values, in the control's precision
            k, v = _f32(k, True), _f32(v, True)
        q = q.reshape(L, kv_heads, g, head_dim) * head_dim ** -0.5
        at = jnp.arange(L)
        outs = []
        for s0 in range(0, L, QUERY_BLOCK):
            s1 = min(s0 + QUERY_BLOCK, L)
            s = jnp.einsum("qhgd,khd->hgqk", q[s0:s1], k[:s1])
            seen = at[None, :s1] <= at[s0:s1, None]
            if visible is not None:
                seen = jnp.logical_and(
                    seen, at[None, :s1] > at[s0:s1, None] - visible)
            p = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), -1)
            outs.append(jnp.einsum("hgqk,khd->qhgd", p, v[:s1]))
        out = jnp.concatenate(outs).reshape(L, heads * head_dim)
        return out @ _f32(w["wo"], low)


def _expert(x, w_gate, w_up, w_down, e, mask, low):
    """Expert ``e`` of the stacks for EVERY token of ``x (L, D)``, times
    its routing weight ``mask (L,)`` (0 where it was not chosen)."""
    return mask[:, None] * gated_mlp(x, w_gate[e], w_up[e], w_down[e], low)


@functools.lru_cache(maxsize=None)
def _jitted():
    import jax
    return (jax.jit(attention, static_argnames=(
                "heads", "kv_heads", "head_dim", "rot", "gain", "visible",
                "eps", "low")),
            jax.jit(_expert, static_argnames=("low",)),
            jax.jit(route, static_argnames=("top_k", "renormalize")))


def moe_layer(x, params, prefix, cfg, held, low=False):
    """``sum over the chosen experts that are HELD`` for the normed ``x
    (L, D)``: a plain loop over the held ids, each expert computed for
    every token and masked by its routing weight. Returns ``(out,
    ids)``."""
    import jax.numpy as jnp
    _, expert, router = _jitted()
    ids, weights = router(x, params[prefix + "router_w"],
                          top_k=cfg["num_experts_per_tok"],
                          renormalize=bool(cfg.get("norm_topk_prob", True)))
    stacks = [params[prefix + "experts." + n]
              for n in ("w_gate", "w_up", "w_down")]
    out = jnp.zeros_like(x)
    for e in range(held[0], held[1]):
        mask = jnp.sum(jnp.where(ids == e, weights, 0.0), axis=1)
        out = out + expert(x, *stacks, jnp.int32(e - held[0]), mask,
                           low=low)
    return out, ids


def hidden_states(params, tokens, cfg, held, control=None, routed=None):
    """``tokens (L,)`` -> final-norm hidden states ``(L, D)`` float32.
    ``routed``, a list, is given the router's choice of every layer,
    ``(L, top_k)`` ids each."""
    import jax.numpy as jnp
    attn, _, _ = _jitted()
    low = control == "float8"
    window = cfg["sliding_window"] - (control == "window_minus_one")
    eps = float(cfg.get("rms_norm_eps", 1e-6))
    tables = {kind: rope_table(cfg["rope_parameters"][kind],
                               cfg["head_dim"]) for kind in (FULL, SLIDING)}
    h = _f32(params["embed"][tokens], low)
    for i in range(cfg["num_hidden_layers"]):
        l = "l%d." % i
        kind = cfg["layer_types"][i]
        freqs, rot, gain = tables[kind]
        h = h + attn(
            h, {n: params[l + n] for n in (
                "attn_g", "wq", "wk", "wv", "wo")},
            jnp.asarray(freqs), heads=cfg["num_attention_heads"],
            kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
            rot=rot, gain=gain,
            visible=window if kind == SLIDING else None, eps=eps, low=low)
        out, ids = moe_layer(_rms(h, params[l + "ffn_g"], eps), params, l,
                             cfg, held, low)
        h = h + out
        if routed is not None:
            routed.append(ids)
    return _rms(h, params["out_g"], eps)


def logits_rows(params, tokens, first_row, n_rows, cfg, held, control=None,
                routed=None):
    """Logits ``(n_rows, vocab)`` of positions ``first_row ..`` of the
    sequence ``tokens``, on the host, the head a block of
    :data:`HEAD_COLUMNS` columns at a time. Tokens after the rows asked
    for cannot reach them (causal), so a sequence may be padded to a
    fixed length."""
    h = hidden_states(params, tokens, cfg, held, control, routed)
    head = params["head"]
    return np.concatenate([
        np.asarray(_head(h, head[:, c:c + HEAD_COLUMNS], first_row, n_rows,
                         control == "float8"))
        for c in range(0, head.shape[1], HEAD_COLUMNS)], axis=1)


def teacher_forced(params, prompt, served, padded_len, n_rows, cfg, held,
                   control=False, routed=None):
    """``window_moe_lm.teacher_forced`` over this module's forward: one
    dense forward over prompt + served tokens, position ``P-1+i`` must
    predict served token ``i``; ``worst`` and ``mean`` gap in deviations
    of the reference's logits, and with ``control`` the same for each of
    :data:`CONTROLS`, ``control_mean`` / ``control_worst``
    :data:`COMPARED`'s."""
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
