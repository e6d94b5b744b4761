"""Plain float32 reference of a decoder LM whose attention layers are
sliding-window with a full-attention layer every few, grouped-query
heads whose count differs by the kind of layer, a per-head output gate,
and softmax-routed experts beside a shared one (poolside/Laguna-S-2.1,
``model_type`` ``laguna``): the forward pass over ONE whole sequence in
``jax.numpy``, every product at "highest" precision. No kernel, no
cache, no ring, no batching: the sliding window is a MASK over the whole
sequence's scores; the experts are a plain loop over the held ids; the
scores are computed :data:`QUERY_BLOCK` queries at a time against the
keys those queries can see, and the head :data:`HEAD_COLUMNS` columns at
a time, so that 9k tokens fit beside the weights. The benchmark's own
copy: nothing here imports the program (the pieces shared with the other
references, the benchmark's own too, are imported from them: the cast,
RMS norm, the gated MLP and the head's rows from ``latent_moe_lm``, the
softmax router from ``block_diffusion_lm``).

Layer ``i``, ``x`` the RMS-normed residual (pre-norm, no biases):

    H_i = num_attention_heads_per_layer[i];  Hkv key/value heads of d
    q = x W_q (H_i d),  k = x W_k,  v = x W_v (Hkv d);  no q/k norm
    RoPE by rope_parameters[layer_types[i]]:
      yarn    — YaRN's frequencies over the FIRST partial_rotary_factor *
                d values of a head (half against half), the rest passed
                through; cos and sin times attention_factor
      default — plain RoPE at rope_theta over all of them
    scores q . k / sqrt(d); query head j reads key head j // (H_i / Hkv);
    causal; in a sliding layer key t is visible to query s iff
    s - sliding_window < t <= s
    o_j <- sigmoid(x W_g)_j o_j;  out = o W_o
    FFN: a layer of mlp_only_layers a gated SiLU MLP; every other
    shared(x) + scaling * sum over the num_experts_per_tok experts of
    softmax(x W_r) that are HELD, their weights renormalised over the
    chosen

It is given the chip's share like the program: ``held = (lo, hi)`` of
the router's ``num_experts`` and the vocabulary's slice (the shapes of
``embed`` / ``head``). Each matrix is cast from bfloat16 to float32 as
it is reached. Departures from the published model are the
configuration's ``assumed`` (``benchmark/configs/Laguna-S-2.1.json``).

``control`` names a CONTROL, not the reference — which the comparison
that decides ``correct`` has to tell from the reference, or is read
beside it; no benchmark run computes one: ``"float8"`` rounds every
matrix and the cached keys and values to ``float8_e4m3fn``, the next
precision under the bfloat16 the configuration states;
``"window_minus_one"`` is the reference with ``sliding_window - 1`` keys
visible (the off-by-one a ring written or masked one slot wrong would
give).

``cfg`` is the keyword arguments the served model is built with (the
published config's keys).
"""
from __future__ import annotations

import functools
import math

import numpy as np

from .block_diffusion_lm import route
from .latent_moe_lm import _f32, _head, _rms, gated_mlp

CONTROLS = ("float8", "window_minus_one")
# the control whose numbers the driver compares (``control_mean``); the
# other is read beside it
COMPARED = "float8"
HEAD_COLUMNS = 16384
QUERY_BLOCK = 512
FULL, SLIDING = "full_attention", "sliding_attention"


def rope_table(rp, head_dim):
    """``(frequencies (rot / 2,), rot, gain)`` of one entry of
    ``rope_parameters``: YaRN's frequencies (dimensions that turn more
    than ``beta_fast`` times over the original context keep theirs,
    those that turn fewer than ``beta_slow`` times are slowed by
    ``factor``, a linear ramp between) or the plain ones."""
    rot = int(round(head_dim * float(rp.get("partial_rotary_factor", 1))))
    theta = float(rp["rope_theta"])
    plain = 1.0 / theta ** (np.arange(0, rot, 2, dtype=np.float64) / rot)
    if rp.get("rope_type", "default") == "default":
        return plain.astype(np.float32), rot, 1.0
    orig = rp["original_max_position_embeddings"]

    def corr(turns):
        return rot * math.log(orig / (turns * 2 * math.pi)) \
            / (2 * math.log(theta))

    low = max(math.floor(corr(rp["beta_fast"])), 0)
    high = min(math.ceil(corr(rp["beta_slow"])), rot - 1)
    ramp = np.clip((np.arange(rot // 2) - low) / max(high - low, 0.001),
                   0, 1)
    freqs = plain / rp["factor"] * ramp + plain * (1.0 - ramp)
    return freqs.astype(np.float32), rot, float(rp["attention_factor"])


def _rope(x, freqs, rot, gain):
    """``x (L, H, d)`` at positions ``0..L-1``."""
    import jax.numpy as jnp
    ang = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None, None] * freqs
    cos, sin = jnp.cos(ang) * gain, jnp.sin(ang) * gain
    a, b = jnp.split(x[..., :rot], 2, axis=-1)
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin,
                            x[..., rot:]], -1)


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
        out = jnp.concatenate(outs).reshape(L, heads, head_dim) \
            * jax.nn.sigmoid(x @ _f32(w["wg"], low))[:, :, None]
        return out.reshape(L, heads * head_dim) @ _f32(w["wo"], low)


@functools.lru_cache(maxsize=None)
def _jitted():
    import jax
    return (jax.jit(attention, static_argnames=(
                "heads", "kv_heads", "head_dim", "rot", "gain", "visible",
                "eps", "low")),
            jax.jit(gated_mlp, static_argnames=("low",)),
            jax.jit(route, static_argnames=("top_k", "renormalize")))


def moe_layer(x, params, prefix, cfg, held, low=False):
    """``shared(x) + scaling * sum over the chosen experts that are
    HELD`` for the normed ``x (L, D)``: a plain loop over the held ids,
    each expert computed for every token and masked by its routing
    weight. Returns ``(out, ids)``."""
    import jax.numpy as jnp
    _, mlp, router = _jitted()
    ids, weights = router(x, params[prefix + "router_w"],
                          top_k=cfg["num_experts_per_tok"],
                          renormalize=bool(cfg.get("norm_topk_prob", True)))
    weights = weights * float(cfg.get("moe_routed_scaling_factor", 1.0))
    out = mlp(x, *(params[prefix + "shared." + n]
                   for n in ("w_gate", "w_up", "w_down")), low=low)
    for e in range(held[0], held[1]):
        mask = jnp.sum(jnp.where(ids == e, weights, 0.0), axis=1)
        part = mlp(x, *(params[prefix + "experts." + n][e - held[0]]
                        for n in ("w_gate", "w_up", "w_down")), low=low)
        out = out + mask[:, None] * part
    return out, ids


def hidden_states(params, tokens, cfg, held, control=None, routed=None):
    """``tokens (L,)`` -> final-norm hidden states ``(L, D)`` float32.
    ``routed``, a list, is given the router's choice of every expert
    layer, ``(L, top_k)`` ids each."""
    import jax.numpy as jnp
    attn, mlp, _ = _jitted()
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
                "attn_g", "wq", "wk", "wv", "wg", "wo")},
            jnp.asarray(freqs),
            heads=cfg["num_attention_heads_per_layer"][i],
            kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
            rot=rot, gain=gain,
            visible=window if kind == SLIDING else None, eps=eps, low=low)
        x = _rms(h, params[l + "ffn_g"], eps)
        if i in cfg.get("mlp_only_layers", ()):
            h = h + mlp(x, *(params[l + n] for n in (
                "w_gate", "w_up", "w_down")), low=low)
        else:
            out, ids = moe_layer(x, params, l, cfg, held, low)
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
