"""A decoder LM with latent attention and routed experts, for
:class:`~mxnet_tpu.serving.DecodeServer` — the second model of the
decode-model contract (``serving.decode``'s docstring), named by what
it computes.

**Every layer attends through a latent** (multi-head latent attention).
With ``x`` the RMS-normed residual:

    c_q  = RMSNorm(x W_qa)                       q_lora_rank
    q    = c_q W_qb            heads x (qk_nope_head_dim + qk_rope_head_dim)
    [c_kv, k_r] = x W_kva      kv_lora_rank + qk_rope_head_dim
    c_kv = RMSNorm(c_kv);  k_r = RoPE(k_r), ONE rotary key for all heads
    [k_nope, v] = c_kv W_kvb   heads x (qk_nope_head_dim + v_head_dim)
    scores = (q_nope . k_nope + RoPE(q_r) . k_r) * s;  out = softmax . v -> W_o

**What is cached is ``[c_kv, k_r]``**: one row of ``kv_lora_rank +
qk_rope_head_dim`` values a token a layer, the same for every head
(576 values against the 32,768 of per-head K and V at 128 heads), so
the model declares ONE cache array and the server's pool follows; the
row is stored 640 wide, padded with zeros to whole lane tiles.
Prefill computes the published form above (its attention on the flash
kernel: queries and keys zero-padded from 192 to 256 columns, values
from 128, which changes no score and adds zero columns that are cut
off — the kernel takes head sizes that fill whole lane tiles, and at
one prompt of a few hundred tokens the padding is a few tens of
microseconds beside the 11 GB of weights a prefill reads). Decode uses
the ABSORBED form, which never expands the cache: ``q_lat = q_nope
W_UK`` per head, ``score = q_lat . c_kv + q_r . k_r``, ``o_lat =
softmax . c_kv``, ``o = o_lat W_UV`` (``W_UK``/``W_UV`` the two halves of
``W_kvb``, kept as two matrices ``wk_b``/``wv_b`` so that the step
slices neither) — through the server's ``attend``
(``kvcache.paged_latent_attention``).

RoPE is YaRN (:func:`yarn_inv_freq`, :func:`yarn_mscale`); the score
scale ``s = (nope + rope) ** -0.5 * mscale(factor, mscale_all_dim) **
2``. The rotation is the half-split one (``rotate_half``); the
published checkpoint interleaves the pairs first, a fixed permutation
of the rotary columns of ``W_qb``/``W_kva`` that random weights cannot
tell.

**Feed-forward.** The first ``first_k_dense_replace`` layers: a gated
MLP (SiLU) of ``intermediate_size``. Every later layer: ``shared(x) +
sum_i w_i expert_i(x)``, the router ``parallel.moe.
route_grouped_sigmoid`` over ALL ``n_routed_experts`` (float32,
"highest"), the experts ``parallel.moe.expert_ffn``, dropless, told
which experts this chip HOLDS: ``ep=(rank, size)`` names its shard of
an expert axis (``sharding_rules.held_experts``); what the absent
experts would have added is left out, and that partial result goes on.
``ep=(0, 1)`` holds them all.

Precision: bf16 matrices (the router's matrix and bias float32), bf16
pool; float32 accumulation, residual stream, norms, softmax and
router. Parameters are a FLAT ``{name: array}`` dict.
"""
from __future__ import annotations

import math

__all__ = ["LatentMoEDecoderLM", "yarn_inv_freq", "yarn_mscale"]


def yarn_mscale(factor, mscale):
    """YaRN's attention temperature: ``0.1 * mscale * ln(factor) + 1``
    above a factor of 1."""
    return 0.1 * mscale * math.log(factor) + 1.0 if factor > 1 else 1.0


def yarn_inv_freq(dim, theta, factor, original, beta_fast, beta_slow):
    """The ``dim // 2`` rotary frequencies of YaRN: dimensions that turn
    more than ``beta_fast`` times over the ``original`` context keep
    their frequency, those that turn fewer than ``beta_slow`` times are
    slowed by ``factor`` (interpolated), a linear ramp between."""
    import numpy as np

    def correction_dim(turns):
        return dim * math.log(original / (turns * 2 * math.pi)) \
            / (2 * math.log(theta))

    low = max(math.floor(correction_dim(beta_fast)), 0)
    high = min(math.ceil(correction_dim(beta_slow)), dim - 1)
    base = theta ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    ramp = np.clip((np.arange(dim // 2, dtype=np.float64) - low)
                   / max(high - low, 1e-3), 0.0, 1.0)
    keep = 1.0 - ramp                     # 1: extrapolate, 0: interpolate
    return (keep / base + (1.0 - keep) / (factor * base)) \
        .astype(np.float32)


class LatentMoEDecoderLM:
    """The decode-model contract for a latent-attention, routed-expert
    decoder (module docstring). Keyword arguments are the keys of the
    published ``config.json``; ``ep=(rank, size)`` is the chip's share
    of the expert axis, ``use_pallas`` forces the Pallas kernels
    (interpreted off the TPU) as in ``ToyDecoderLM``."""

    step_counters = ("moe", ("moe_slots", "experts_touched", "max_load"))

    def __init__(self, *, vocab_size, hidden_size, num_hidden_layers,
                 num_attention_heads, q_lora_rank, kv_lora_rank,
                 qk_nope_head_dim, qk_rope_head_dim, v_head_dim,
                 intermediate_size, moe_intermediate_size,
                 n_routed_experts, n_shared_experts, num_experts_per_tok,
                 n_group, topk_group, routed_scaling_factor,
                 first_k_dense_replace, rope_theta, rope_scaling,
                 rms_norm_eps=1e-6, max_position_embeddings=4096,
                 ep=(0, 1), use_pallas=False):
        from ..parallel.sharding_rules import held_experts
        self.vocab = int(vocab_size)
        self.d_model = int(hidden_size)
        self.n_layers = int(num_hidden_layers)
        self.n_heads = int(num_attention_heads)
        self.q_rank, self.kv_rank = int(q_lora_rank), int(kv_lora_rank)
        self.nope, self.rope = int(qk_nope_head_dim), int(qk_rope_head_dim)
        self.v_dim = int(v_head_dim)
        self.d_ff = int(intermediate_size)
        self.d_expert = int(moe_intermediate_size)
        self.n_experts = int(n_routed_experts)
        self.n_shared = int(n_shared_experts)
        self.top_k = int(num_experts_per_tok)
        self.n_group, self.topk_group = int(n_group), int(topk_group)
        self.route_scale = float(routed_scaling_factor)
        self.n_dense = int(first_k_dense_replace)
        self.eps = float(rms_norm_eps)
        self.max_len = int(max_position_embeddings)
        self.use_pallas = bool(use_pallas)
        self.held = held_experts(self.n_experts, ep[1], ep[0])
        ys = dict(rope_scaling)
        self.inv_freq = yarn_inv_freq(
            self.rope, float(rope_theta), float(ys["factor"]),
            int(ys["original_max_position_embeddings"]),
            float(ys["beta_fast"]), float(ys["beta_slow"]))
        # cos and sin carry mscale(factor, mscale) / mscale(factor,
        # mscale_all_dim) (1 where the two are equal); the scores
        # mscale(factor, mscale_all_dim) squared
        self.rope_gain = yarn_mscale(ys["factor"], ys.get("mscale", 1)) \
            / yarn_mscale(ys["factor"], ys.get("mscale_all_dim", 0))
        self.scale = (self.nope + self.rope) ** -0.5 \
            * yarn_mscale(ys["factor"], ys.get("mscale_all_dim", 0)) ** 2
        self.latent = self.kv_rank + self.rope
        # what the server's pool holds: one row a token, for all heads,
        # padded with zeros to whole 128-lane tiles (576 -> 640: a
        # row-major array 576 wide takes 640 columns of HBM anyway, and
        # XLA's default layout for it is not row-major; see
        # flash_attention._mla_decode_kernel)
        self.row_width = -(-self.latent // 128) * 128
        self.cache_arrays = (("kv", (self.row_width,), "bfloat16"),)

    @property
    def n_moe_layers(self):
        return self.n_layers - self.n_dense

    # -- parameters ------------------------------------------------------
    def init_params(self, seed=0):
        """bf16 matrices at ``fan_in ** -0.5`` (the embedding at 1), the
        router's matrix and bias float32, norm gains 1."""
        import jax
        import jax.numpy as jnp
        keys = iter(jax.random.split(jax.random.PRNGKey(seed),
                                     16 * self.n_layers + 8))

        def w(*shape, dtype=jnp.bfloat16, std=None):
            std = shape[-2] ** -0.5 if std is None else std
            return (jax.random.normal(next(keys), shape, jnp.float32)
                    * std).astype(dtype)

        D, H, E = self.d_model, self.n_heads, self.held[1] - self.held[0]
        ones = lambda n: jnp.ones((n,), jnp.float32)    # noqa: E731
        p = {"embed": w(self.vocab, D, std=1.0), "out_g": ones(D),
             "head": w(D, self.vocab)}
        for i in range(self.n_layers):
            l = "l%d." % i
            p.update({
                l + "attn_g": ones(D),
                l + "wq_a": w(D, self.q_rank),
                l + "q_g": ones(self.q_rank),
                l + "wq_b": w(self.q_rank, H * (self.nope + self.rope)),
                l + "wkv_a": w(D, self.latent),
                l + "kv_g": ones(self.kv_rank),
                l + "wk_b": w(self.kv_rank, H * self.nope),
                l + "wv_b": w(self.kv_rank, H * self.v_dim),
                l + "wo": w(H * self.v_dim, D),
                l + "ffn_g": ones(D)})
            if i < self.n_dense:
                p.update({l + "w_gate": w(D, self.d_ff),
                          l + "w_up": w(D, self.d_ff),
                          l + "w_down": w(self.d_ff, D)})
                continue
            F, Fs = self.d_expert, self.d_expert * self.n_shared
            p.update({
                l + "router_w": w(D, self.n_experts, dtype=jnp.float32),
                l + "router_b": jnp.zeros((self.n_experts,), jnp.float32),
                l + "shared.w_gate": w(D, Fs), l + "shared.w_up": w(D, Fs),
                l + "shared.w_down": w(Fs, D),
                l + "experts.w_gate": w(E, D, F),
                l + "experts.w_up": w(E, D, F),
                l + "experts.w_down": w(E, F, D)})
        return p

    # -- pieces ----------------------------------------------------------
    def _rms(self, x, g):
        import jax
        import jax.numpy as jnp
        x = x.astype(jnp.float32)
        return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True)
                                 + self.eps) * g

    @staticmethod
    def _mm(x, w):
        """bf16 operands, float32 accumulation."""
        import jax.numpy as jnp
        return jnp.dot(x.astype(w.dtype), w,
                       preferred_element_type=jnp.float32)

    def _rotate(self, x, positions):
        """``x (..., T, [H,] rope)`` at ``positions (..., T)``: the
        half-split rotation, float32."""
        import jax.numpy as jnp
        ang = positions[..., None].astype(jnp.float32) \
            * jnp.asarray(self.inv_freq)                   # (..., T, r/2)
        if x.ndim == ang.ndim + 1:
            ang = ang[..., None, :]                        # over heads
        cos = jnp.cos(ang) * self.rope_gain
        sin = jnp.sin(ang) * self.rope_gain
        a, b = jnp.split(x.astype(jnp.float32), 2, axis=-1)
        return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)

    def _gated(self, x, p, prefix):
        import jax
        g = self._mm(x, p[prefix + "w_gate"])
        u = self._mm(x, p[prefix + "w_up"])
        return self._mm(jax.nn.silu(g) * u, p[prefix + "w_down"])

    def _ffn(self, i, x, p, routed=None):
        """``x (T, D)`` float32 -> ``(out (T, D), load (E_held,) or
        None)``; ``routed``, a list, is given the router's choice."""
        from ..parallel import moe
        l = "l%d." % i
        if i < self.n_dense:
            return self._gated(x, p, l), None
        topi, topw = moe.route_grouped_sigmoid(
            x, p[l + "router_w"], p[l + "router_b"], n_group=self.n_group,
            topk_group=self.topk_group, top_k=self.top_k,
            scaling=self.route_scale)
        if routed is not None:
            routed.append(topi)
        out = moe.expert_ffn(
            x, {n: p[l + "experts." + n]
                for n in ("w_gate", "w_up", "w_down")},
            topi, topw, self.held, force_pallas=self.use_pallas)
        return self._gated(x, p, l + "shared.") + out, \
            moe.expert_load(topi, self.held)

    def _latent(self, i, x, p, positions):
        """Queries and the cached row of one layer: ``q_nope (..., H,
        nope)``, ``q_r (..., H, rope)`` rotated, ``row (..., row_width)``
        = ``[RMSNorm(c_kv), RoPE(k_r), zeros]`` float32."""
        import jax.numpy as jnp
        l = "l%d." % i
        H = self.n_heads
        cq = self._rms(self._mm(x, p[l + "wq_a"]), p[l + "q_g"])
        q = self._mm(cq, p[l + "wq_b"]).reshape(
            x.shape[:-1] + (H, self.nope + self.rope))
        q_nope, q_r = q[..., :self.nope], q[..., self.nope:]
        ckv = self._mm(x, p[l + "wkv_a"])
        row = jnp.concatenate(
            [self._rms(ckv[..., :self.kv_rank], p[l + "kv_g"]),
             self._rotate(ckv[..., self.kv_rank:], positions),
             jnp.zeros(x.shape[:-1] + (self.row_width - self.latent,),
                       jnp.float32)], -1)
        return q_nope, self._rotate(q_r, positions), row

    # -- the contract ----------------------------------------------------
    def prefill(self, params, tokens):
        return self._forward(params, tokens)

    def routing(self, params, tokens):
        """The router's choice at every expert layer over whole
        sequences ``tokens (B, L)``, on the prefill path: ``(expert
        layers, B * L, top_k)`` int32 — for a comparison with a
        reference's choice, not for serving."""
        import jax.numpy as jnp
        routed = []
        self._forward(params, tokens, routed)
        return jnp.stack(routed)

    def _forward(self, params, tokens, routed=None):
        import jax.numpy as jnp
        from ..parallel.flash_attention import flash_attention
        p = params
        B, L = tokens.shape
        H, R = self.n_heads, self.kv_rank
        pos = jnp.broadcast_to(jnp.arange(L, dtype=jnp.int32), (B, L))
        h = p["embed"][tokens].astype(jnp.float32)
        # the flash kernel takes head sizes of whole lane tiles: zero
        # columns change no score and give zero outputs
        wide = -(-max(self.nope + self.rope, self.v_dim) // 128) * 128

        def pad(a):
            return jnp.pad(a.astype(jnp.bfloat16), (
                (0, 0), (0, 0), (0, 0), (0, wide - a.shape[-1])))

        rows = []
        for i in range(self.n_layers):
            l = "l%d." % i
            x = self._rms(h, p[l + "attn_g"])
            q_nope, q_r, row = self._latent(i, x, p, pos)
            row = row.astype(jnp.bfloat16)       # as the pool holds it
            c_kv, k_r = row[..., :R], row[..., R:self.latent]
            k_nope = self._mm(c_kv, p[l + "wk_b"]).reshape(B, L, H,
                                                           self.nope)
            v = self._mm(c_kv, p[l + "wv_b"]).reshape(B, L, H, self.v_dim)
            q = jnp.concatenate([q_nope, q_r], -1)
            k = jnp.concatenate(
                [k_nope, jnp.broadcast_to(k_r[:, :, None].astype(
                    jnp.float32), (B, L, H, self.rope))], -1)
            a = flash_attention(pad(q), pad(k), pad(v), causal=True,
                                scale=self.scale,
                                force_pallas=self.use_pallas)
            a = a[..., :self.v_dim].reshape(B, L, H * self.v_dim)
            h = h + self._mm(a, p[l + "wo"])
            x = self._rms(h, p[l + "ffn_g"])
            out, _ = self._ffn(i, x.reshape(B * L, -1), p, routed)
            h = h + out.reshape(B, L, -1)
            rows.append(row)
        logits = self._mm(self._rms(h, p["out_g"]), p["head"])
        return logits, jnp.stack(rows)

    def decode(self, params, tokens, positions, attend):
        import jax.numpy as jnp
        p = params
        B = tokens.shape[0]
        H, R = self.n_heads, self.kv_rank
        h = p["embed"][tokens].astype(jnp.float32)
        rows, loads = [], []
        for i in range(self.n_layers):
            l = "l%d." % i
            x = self._rms(h, p[l + "attn_g"])
            q_nope, q_r, row = self._latent(i, x, p, positions)
            # absorbed: the query goes through the key up-projection,
            # the cache is never expanded
            wk = p[l + "wk_b"].reshape(R, H, self.nope)
            q_lat = jnp.einsum("bhn,rhn->bhr", q_nope.astype(wk.dtype),
                               wk, preferred_element_type=jnp.float32)
            q_row = jnp.pad(jnp.concatenate([q_lat, q_r], -1), (
                (0, 0), (0, 0), (0, self.row_width - self.latent)))
            o_lat = attend(i, q_row, row,
                           rank=R, scale=self.scale,
                           force_pallas=self.use_pallas)
            wv = p[l + "wv_b"].reshape(R, H, self.v_dim)
            a = jnp.einsum("bhr,rhv->bhv", o_lat.astype(wv.dtype), wv,
                           preferred_element_type=jnp.float32)
            h = h + self._mm(a.reshape(B, H * self.v_dim), p[l + "wo"])
            x = self._rms(h, p[l + "ffn_g"])
            out, load = self._ffn(i, x, p)
            h = h + out
            rows.append(row)
            if load is not None:
                loads.append(load)
        logits = self._mm(self._rms(h, p["out_g"]), p["head"])
        if loads:
            load = jnp.stack(loads)                       # (layers, E)
            counters = jnp.stack([load.sum(), (load > 0).sum(),
                                  load.max()])
        else:
            counters = jnp.zeros((3,), jnp.int32)
        return logits, jnp.stack(rows), counters
