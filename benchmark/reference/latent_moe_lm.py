"""Plain float32 reference of a latent-attention, routed-expert decoder
LM (the language model of rednote-hilab/dots.vlm1.inst, the
DeepSeek-V3 block): the forward pass over ONE whole sequence in
``jax.numpy``, every product at "highest" precision, attention in the
published NON-absorbed form (keys and values expanded from the latent
for every head), no cache, no kernels, no batching, the experts by a
plain loop over the held ids with a mask, the router as published. The
benchmark's own copy: nothing here imports the program.

It is given the chip's share like the program: ``held = (lo, hi)`` of
the router's ``n_routed_experts`` and the vocabulary's slice (the
shapes of ``embed``/``head``). What the absent experts would have added
is left out here as there. Each matrix is cast from bfloat16 to float32
as it is reached, one small jitted piece at a time, so that the
reference fits beside the 11 GB of weights.

Departures from the published model:
- no image tower and no next-token (MTP) module: the language model of
  ``config.json`` alone, text in;
- ``e_score_correction_bias`` is whatever ``router_b`` holds, zero in
  the benchmark (the checkpoint's values are not in the config);
- weights are random from a seed;
- ``kv_b_proj`` is held as its two per-head halves, ``wk_b`` (keys'
  no-position part) and ``wv_b`` (values): the same numbers, split;
- RoPE rotates half against half (``rotate_half``); the checkpoint
  first de-interleaves each pair, a fixed permutation of the rotary
  columns of ``q_b_proj``/``kv_a_proj`` that random weights cannot tell.

``low=True`` is the CONTROL, not the reference: the same equations with
every matrix and the cached latent ``[c_kv, k_r]`` rounded to
``float8_e4m3fn``, the next precision under the bfloat16 the
configuration states. The comparison that decides ``correct`` has to
tell it from the reference; no benchmark run computes it.

``cfg`` is the keyword arguments the served model is built with (the
published config's keys).
"""
from __future__ import annotations

import functools
import math

import numpy as np


def mscale(factor, m):
    return 0.1 * m * math.log(factor) + 1.0 if factor > 1 else 1.0


def inv_freq(cfg):
    """YaRN's rotary frequencies (``DeepseekV3YarnRotaryEmbedding``)."""
    ys, dim = cfg["rope_scaling"], cfg["qk_rope_head_dim"]
    base, orig = float(cfg["rope_theta"]), \
        ys["original_max_position_embeddings"]

    def corr(turns):
        return dim * math.log(orig / (turns * 2 * math.pi)) \
            / (2 * math.log(base))

    low = max(math.floor(corr(ys["beta_fast"])), 0)
    high = min(math.ceil(corr(ys["beta_slow"])), dim - 1)
    extra = 1.0 / base ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    inter = extra / ys["factor"]
    ramp = np.clip((np.arange(dim // 2) - low) / max(high - low, 0.001),
                   0, 1)
    mask = 1.0 - ramp
    return (inter * (1 - mask) + extra * mask).astype(np.float32)


def score_scale(cfg):
    ys = cfg["rope_scaling"]
    return (cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]) ** -0.5 \
        * mscale(ys["factor"], ys.get("mscale_all_dim", 0)) ** 2


def _f32(w, low):
    import jax.numpy as jnp
    if low:
        w = w.astype(jnp.float8_e4m3fn)
    return w.astype(jnp.float32)


def _rms(x, g, eps):
    import jax
    import jax.numpy as jnp
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g


def _rope(x, freqs, gain):
    """``x (L, ..., rope)`` at positions ``0..L-1``."""
    import jax.numpy as jnp
    ang = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * freqs
    ang = ang.reshape((x.shape[0],) + (1,) * (x.ndim - 2) + (-1,))
    cos, sin = jnp.cos(ang) * gain, jnp.sin(ang) * gain
    a, b = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


def attention(h, w, freqs, *, heads, nope, rope, v_dim, rank, eps, scale,
              gain, low):
    """One layer's attention, published form. ``h (L, D)``; returns the
    residual's increment."""
    import jax
    import jax.numpy as jnp
    with jax.default_matmul_precision("highest"):
        L = h.shape[0]
        x = _rms(h, w["attn_g"], eps)
        cq = _rms(x @ _f32(w["wq_a"], low), w["q_g"], eps)
        q = (cq @ _f32(w["wq_b"], low)).reshape(L, heads, nope + rope)
        q_nope, q_r = q[..., :nope], _rope(q[..., nope:], freqs, gain)
        ckv = x @ _f32(w["wkv_a"], low)
        c_kv = _rms(ckv[:, :rank], w["kv_g"], eps)
        k_r = _rope(ckv[:, rank:], freqs, gain)
        if low:     # the cached latent, in the control's precision
            c_kv, k_r = _f32(c_kv, True), _f32(k_r, True)
        k_nope = (c_kv @ _f32(w["wk_b"], low)).reshape(L, heads, nope)
        v = (c_kv @ _f32(w["wv_b"], low)).reshape(L, heads, v_dim)
        s = (jnp.einsum("qhd,khd->hqk", q_nope, k_nope)
             + jnp.einsum("qhd,kd->hqk", q_r, k_r)) * scale
        causal = jnp.tril(jnp.ones((L, L), bool))
        p = jax.nn.softmax(jnp.where(causal[None], s, -jnp.inf), -1)
        out = jnp.einsum("hqk,khd->qhd", p, v).reshape(L, heads * v_dim)
        return out @ _f32(w["wo"], low)


def gated_mlp(x, w_gate, w_up, w_down, low):
    import jax
    with jax.default_matmul_precision("highest"):
        return (jax.nn.silu(x @ _f32(w_gate, low))
                * (x @ _f32(w_up, low))) @ _f32(w_down, low)


def route(x, w, b, *, n_group, topk_group, top_k, scaling):
    """The published router (``noaux_tc``, sigmoid scores): the choice
    on ``s + b``, groups by the sum of their two best, the losing
    groups' scores put to 0, ties to the lower index; the weights from
    ``s`` alone, normalised, times the scaling factor. Returns ``(ids
    (L, top_k), weights (L, top_k))``."""
    import jax
    import jax.numpy as jnp
    with jax.default_matmul_precision("highest"):
        s = jax.nn.sigmoid(x @ w.astype(jnp.float32))
    L, E = s.shape
    choice = s + b
    groups = choice.reshape(L, n_group, E // n_group)
    best2 = -jnp.sort(-groups, axis=-1)[..., :2]
    order = jnp.argsort(-best2.sum(-1), axis=-1, stable=True)
    kept = jnp.zeros((L, n_group), bool).at[
        jnp.arange(L)[:, None], order[:, :topk_group]].set(True)
    masked = jnp.where(kept[:, :, None], groups, 0.0).reshape(L, E)
    ids = jnp.argsort(-masked, axis=-1, stable=True)[:, :top_k]
    w_sel = jnp.take_along_axis(s, ids, axis=1)
    return ids, w_sel / (w_sel.sum(-1, keepdims=True) + 1e-20) * scaling


@functools.lru_cache(maxsize=None)
def _jitted():
    import jax
    return (jax.jit(attention, static_argnames=(
                "heads", "nope", "rope", "v_dim", "rank", "eps", "scale",
                "gain", "low")),
            jax.jit(gated_mlp, static_argnames=("low",)),
            jax.jit(route, static_argnames=("n_group", "topk_group",
                                            "top_k", "scaling")))


def moe_layer(x, params, prefix, cfg, held, low=False):
    """``shared(x) + sum over the chosen experts that are HELD`` for the
    normed ``x (L, D)``: a plain loop over the held ids, each expert
    computed for every token and masked by its routing weight. Returns
    ``(out, ids)``."""
    import jax.numpy as jnp
    _, mlp, router = _jitted()
    ids, weights = router(
        x, params[prefix + "router_w"], params[prefix + "router_b"],
        n_group=cfg["n_group"], topk_group=cfg["topk_group"],
        top_k=cfg["num_experts_per_tok"],
        scaling=float(cfg["routed_scaling_factor"]))
    out = mlp(x, *(params[prefix + "shared." + n]
                   for n in ("w_gate", "w_up", "w_down")), low=low)
    for e in range(held[0], held[1]):
        mask = jnp.sum(jnp.where(ids == e, weights, 0.0), axis=1)
        part = mlp(x, *(params[prefix + "experts." + n][e - held[0]]
                        for n in ("w_gate", "w_up", "w_down")), low=low)
        out = out + mask[:, None] * part
    return out, ids


def hidden_states(params, tokens, cfg, held, low=False, routed=None):
    """``tokens (L,)`` -> final-norm hidden states ``(L, D)`` float32.
    ``routed``, a list, is given the router's choice of every expert
    layer, ``(L, top_k)`` ids each."""
    import jax.numpy as jnp
    attn, mlp, _ = _jitted()
    eps = float(cfg.get("rms_norm_eps", 1e-6))
    ys = cfg["rope_scaling"]
    gain = mscale(ys["factor"], ys.get("mscale", 1)) \
        / mscale(ys["factor"], ys.get("mscale_all_dim", 0))
    freqs = jnp.asarray(inv_freq(cfg))
    h = _f32(params["embed"][tokens], low)
    for i in range(cfg["num_hidden_layers"]):
        l = "l%d." % i
        w = {n: params[l + n] for n in (
            "attn_g", "wq_a", "q_g", "wq_b", "wkv_a", "kv_g", "wk_b",
            "wv_b", "wo")}
        h = h + attn(
            h, w, freqs, heads=cfg["num_attention_heads"],
            nope=cfg["qk_nope_head_dim"], rope=cfg["qk_rope_head_dim"],
            v_dim=cfg["v_head_dim"], rank=cfg["kv_lora_rank"], eps=eps,
            scale=score_scale(cfg), gain=gain, low=low)
        x = _rms(h, params[l + "ffn_g"], eps)
        if i < cfg["first_k_dense_replace"]:
            h = h + mlp(x, *(params[l + n] for n in (
                "w_gate", "w_up", "w_down")), low=low)
        else:
            out, ids = moe_layer(x, params, l, cfg, held, low)
            h = h + out
            if routed is not None:
                routed.append(ids)
    return _rms(h, params["out_g"], eps)


@functools.partial(__import__("jax").jit, static_argnames=("n_rows", "low"))
def _head(h, head, first_row, n_rows, low):
    import jax
    rows = jax.lax.dynamic_slice_in_dim(h, first_row, n_rows, axis=0)
    with jax.default_matmul_precision("highest"):
        return rows @ _f32(head, low)


def logits_rows(params, tokens, first_row, n_rows, cfg, held, low=False,
                routed=None):
    """Logits ``(n_rows, vocab)`` of positions ``first_row ..`` of the
    sequence ``tokens``. Tokens after the rows asked for cannot reach
    them (causal), so a sequence may be padded to a fixed length."""
    h = hidden_states(params, tokens, cfg, held, low, routed)
    return _head(h, params["head"], first_row, n_rows, low)


def teacher_forced(params, prompt, served, padded_len, n_rows, cfg, held,
                   control=False, routed=None):
    """One dense forward over prompt + served tokens: position
    ``P-1+i`` must predict served token ``i``. Over ALL the served
    tokens, in units of the standard deviation of the reference's
    logits: ``worst``, the widest gap by which a served token's logit
    lies below the reference's best, and ``mean``, the mean gap (0 where
    the served token is the reference's own). ``padded_len`` and
    ``n_rows`` only fix the compiled shapes. With ``control`` the same
    two numbers for the tokens the float8 control puts first at each
    position of the same sequence, under ``control_worst`` and
    ``control_mean``. ``routed`` as in :func:`hidden_states` (the
    reference's own choice)."""
    import jax.numpy as jnp
    P, n = len(prompt), len(served)
    seq = np.zeros((padded_len,), np.int32)
    seq[:P] = prompt
    seq[P:P + n - 1] = served[:n - 1]
    args = (params, jnp.asarray(seq), jnp.int32(P - 1), n_rows, cfg, held)
    rows = np.asarray(logits_rows(*args, routed=routed))[:n]
    std = float(rows.std())

    def gaps(tokens):
        return (rows.max(axis=1) - rows[np.arange(n), tokens]) / std

    got = gaps(np.asarray(served))
    out = {"tokens": int(n), "prompt_len": int(P), "logit_std": std,
           "exact": int((got == 0).sum()), "worst": float(got.max()),
           "mean": float(got.mean())}
    if control:
        low = gaps(np.asarray(logits_rows(*args, low=True))[:n]
                   .argmax(axis=1))
        out.update(control_exact=int((low == 0).sum()),
                   control_worst=float(low.max()),
                   control_mean=float(low.mean()))
    return out
