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
2``. With ``rope_scaling`` null it is plain RoPE at ``rope_theta`` and
``s = (nope + rope) ** -0.5``; with ``q_lora_rank`` null the query has
no rank, ``q = x W_q``; with ``attention_gate`` the heads' outputs pass a
head-wise sigmoid gate before ``W_o``, ``(softmax . v) * sigmoid(x
W_g)_h`` (all three: ``serving.hybrid_linear_moe``'s latent layers). The
rotation is the half-split one (``rotate_half``); the
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

**The residual path** is ``h = h + F(norm(h))`` at ``hc_mult`` 1. At
``hc_mult = n`` > 1 it is ``n`` STREAMS mixed by manifold-constrained
hyper-connections (mHC, arXiv:2512.24880, after hyper-connections,
arXiv:2409.19606): the state is ``X (n, C)`` a token, float32; in, the
token's embedding in every stream; out, their sum. Every sublayer ``F``
(``Attn o RMSNorm`` or ``FFN o RMSNorm``, the two above) has a mixing
matrix ``hc_w (n C, 2 n + n n)`` (``[Phi_pre, Phi_post, Phi_res]``),
biases ``hc_b`` and gates ``hc_a``:

    x~     = vec(X) / sqrt(mean(vec(X)^2) + rms_norm_eps)     no gain
    H_pre  = sigmoid(a_pre x~ Phi_pre + b_pre)                 (n)
    H_post = 2 sigmoid(a_post x~ Phi_post + b_post)            (n)
    H_res  = SK(clip(a_res mat(x~ Phi_res) + B_res, -30, 30))  (n, n)
    u = H_pre X;   y = F(u);   X' = H_res X + H_post^T y

``SK`` exponentiates and then, ``hc_sinkhorn_iters`` times, divides by
the row sums and by the column sums (each ``+ hc_eps``): ``H_res`` is
doubly stochastic, so the mix keeps what the streams sum to. The
coefficient path is float32 with its product at "highest", as the
router is, under ``jax.named_scope("mx_mhc")``, written in ``jnp``
inside the step; the mixes are multiply-adds, never an MXU product.

**The next-token module** (``num_nextn_predict_layers`` 1; DeepSeek-V3
section 2.2, depth 1): position ``i`` reads the main model's ``h_i``
(the streams' sum BEFORE the final norm) and the NEXT token, ``h'_i =
W_p [RMSNorm(h_i); RMSNorm(Emb(t_{i+1}))]``, runs one block of the
expert-layer kind over it (block ``n_layers``, inside the same stream
mixing, its latent in a cache layer of its own: ``cache_layers =
n_layers + 1``), then its own final norm and the main model's head:
logits for ``t_{i+2}``. With it the model declares ``draft_length`` 1
and is served in the SPECULATIVE form of the contract (``verify`` /
``draft`` over two positions a row, ``prefill_draft`` /
``draft_prefill`` over a prompt; ``serving.decode``'s docstring).

Precision: bf16 matrices (the router's matrix and bias and the mixing
matrices float32), bf16 pool; float32 accumulation, residual streams,
mixing coefficients, norms, softmax and router. Parameters are a FLAT
``{name: array}`` dict.
"""
from __future__ import annotations

import math

__all__ = ["LatentMoEDecoderLM", "yarn_inv_freq", "yarn_mscale",
           "HC_GATES", "HC_RES_BIAS"]


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


# how ``init_params`` draws the mixing of the residual streams: the
# gates ``(alpha_pre, alpha_post, alpha_res)`` and the diagonal of
# ``B_res``. ``exp(2)`` on the diagonal against ``exp(0.5 z)`` off it
# leaves ``H_res`` leaning to the identity and moved by the token: the
# largest entry of a row is 0.6-0.7 on average, neither the uniform 0.25
# nor the identity's 1.
HC_GATES = (1.0, 1.0, 0.5)
HC_RES_BIAS = 2.0


class LatentMoEDecoderLM:
    """The decode-model contract for a latent-attention, routed-expert
    decoder (module docstring). Keyword arguments are the keys of the
    published ``config.json``; ``ep=(rank, size)`` is the chip's share
    of the expert axis, ``use_pallas`` forces the Pallas kernels
    (interpreted off the TPU) as in ``ToyDecoderLM``. ``hc_mult`` > 1
    carries that many residual streams (hyper-connections, module
    docstring); ``num_nextn_predict_layers`` 1 adds the next-token
    module and makes this the SPECULATIVE form of the contract
    (``draft_length`` 1: ``verify`` / ``draft`` in ``decode``'s place)."""

    step_counters = ("moe", ("moe_slots", "experts_touched", "max_load"))
    # ``decode`` takes ``head`` and ``live``: a prompt may ride the
    # step in chunks (``DecodeServer``)
    chunk_lanes = True

    def __init__(self, *, vocab_size, hidden_size, num_hidden_layers,
                 num_attention_heads, q_lora_rank, kv_lora_rank,
                 qk_nope_head_dim, qk_rope_head_dim, v_head_dim,
                 intermediate_size, moe_intermediate_size,
                 n_routed_experts, n_shared_experts, num_experts_per_tok,
                 n_group, topk_group, routed_scaling_factor,
                 first_k_dense_replace, rope_theta, rope_scaling,
                 rms_norm_eps=1e-6, max_position_embeddings=4096,
                 hc_mult=1, hc_sinkhorn_iters=20, hc_eps=1e-6,
                 mhc_h_res_clamp_min=-30.0, mhc_h_res_clamp_max=30.0,
                 num_nextn_predict_layers=0, attention_gate=False,
                 cache_dtype="bfloat16", ep=(0, 1), use_pallas=False):
        from ..base import MXNetError
        from ..parallel.sharding_rules import held_experts
        self.vocab = int(vocab_size)
        self.d_model = int(hidden_size)
        self.n_layers = int(num_hidden_layers)
        self.n_heads = int(num_attention_heads)
        # no query rank (null in the published config): q = x W_q
        self.q_rank, self.kv_rank = int(q_lora_rank or 0), int(kv_lora_rank)
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
        self.attn_gate = bool(attention_gate)
        self.cache_dtype = str(cache_dtype)
        self.held = held_experts(self.n_experts, ep[1], ep[0])
        self.hc = int(hc_mult)
        self.hc_iters, self.hc_eps = int(hc_sinkhorn_iters), float(hc_eps)
        self.hc_clamp = (float(mhc_h_res_clamp_min),
                         float(mhc_h_res_clamp_max))
        self.n_nextn = int(num_nextn_predict_layers)
        if self.n_nextn not in (0, 1):
            raise MXNetError(
                "LatentMoEDecoderLM: num_nextn_predict_layers %d — the "
                "next-token module is served at depth 1 (one draft a "
                "step); a chain of modules needs a draft longer than "
                "one token" % self.n_nextn)
        if self.n_nextn:
            # the speculative form of the decode-model contract
            self.draft_length = 1
        # the module's block keeps its latent in a cache layer of its
        # own, behind the main model's
        self.cache_layers = self.n_layers + self.n_nextn
        # no scaling (null in the published config) is plain RoPE: YaRN
        # at factor 1 keeps every frequency and every gain is 1
        ys = dict(rope_scaling or {"factor": 1,
                                   "original_max_position_embeddings": 1,
                                   "beta_fast": 1, "beta_slow": 1})
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
        self.cache_arrays = (("kv", (self.row_width,), self.cache_dtype),)

    @property
    def n_moe_layers(self):
        """Expert layers a step runs: the main model's and the
        next-token module's block."""
        return self.n_layers - self.n_dense + self.n_nextn

    # -- parameters ------------------------------------------------------
    def init_params(self, seed=0):
        """bf16 matrices at ``fan_in ** -0.5`` (the embedding at 1), the
        router's matrix and bias float32, norm gains 1. Under
        ``hc_mult`` > 1 every sublayer's mixing matrix ``hc_w (n C, 2 n
        + n n)`` float32 at ``fan_in ** -0.5`` (``[Phi_pre, Phi_post,
        Phi_res]`` side by side), its gates ``hc_a = (1, 1, 0.5)`` and
        its biases ``hc_b = [0.., 0.., 2 I]``: ``H_res`` leans to the
        identity and is moved by the token (:data:`HC_GATES`)."""
        import jax
        import jax.numpy as jnp
        base = jax.random.PRNGKey(seed)
        keys = iter(jax.random.split(base, 16 * self.n_layers + 8))

        def w(*shape, dtype=jnp.bfloat16, std=None):
            std = shape[-2] ** -0.5 if std is None else std
            return (jax.random.normal(next(keys), shape, jnp.float32)
                    * std).astype(dtype)

        D = self.d_model
        ones = lambda n: jnp.ones((n,), jnp.float32)    # noqa: E731
        p = {"embed": w(self.vocab, D, std=1.0), "out_g": ones(D),
             "head": w(D, self.vocab)}
        for i in range(self.n_layers):
            p.update(self._layer_params(i, w))
        if self.hc == 1 and not self.n_nextn:
            return p
        # what the two extensions add draws from a key stream of its
        # own: the parameters above are the same numbers with and
        # without them
        keys = iter(jax.random.split(jax.random.fold_in(base, 1),
                                     4 * self.cache_layers + 24))
        if self.n_nextn:
            p.update(self._layer_params(self.n_layers, w))
            p.update({"mtp.h_g": ones(D), "mtp.e_g": ones(D),
                      "mtp.proj": w(2 * D, D), "mtp.out_g": ones(D)})
        if self.hc > 1:
            n = self.hc
            bias = jnp.concatenate([
                jnp.zeros((2 * n,), jnp.float32),
                HC_RES_BIAS * jnp.eye(n, dtype=jnp.float32).reshape(-1)])
            for i in range(self.cache_layers):
                for sub in ("attn_", "ffn_"):
                    l = "l%d.%s" % (i, sub)
                    p.update({
                        l + "hc_w": w(n * D, 2 * n + n * n,
                                      dtype=jnp.float32),
                        l + "hc_a": jnp.asarray(HC_GATES, jnp.float32),
                        l + "hc_b": bias})
        return p

    def _layer_params(self, i, w):
        return {**self._attn_params(i, w), **self._ffn_params(i, w)}

    def _attn_params(self, i, w):
        import jax.numpy as jnp
        D, H = self.d_model, self.n_heads
        ones = lambda n: jnp.ones((n,), jnp.float32)    # noqa: E731
        l = "l%d." % i
        p = {l + "attn_g": ones(D)}
        if self.q_rank:
            p.update({
                l + "wq_a": w(D, self.q_rank),
                l + "q_g": ones(self.q_rank),
                l + "wq_b": w(self.q_rank, H * (self.nope + self.rope))})
        else:
            p[l + "wq"] = w(D, H * (self.nope + self.rope))
        p.update({
            l + "wkv_a": w(D, self.latent),
            l + "kv_g": ones(self.kv_rank),
            l + "wk_b": w(self.kv_rank, H * self.nope),
            l + "wv_b": w(self.kv_rank, H * self.v_dim),
            l + "wo": w(H * self.v_dim, D)})
        if self.attn_gate:
            p[l + "wg"] = w(D, H)
        return p

    def _ffn_params(self, i, w):
        import jax.numpy as jnp
        D, E = self.d_model, self.held[1] - self.held[0]
        l = "l%d." % i
        p = {l + "ffn_g": jnp.ones((D,), jnp.float32)}
        if i < self.n_dense:
            p.update({l + "w_gate": w(D, self.d_ff),
                      l + "w_up": w(D, self.d_ff),
                      l + "w_down": w(self.d_ff, D)})
            return p
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

    def _ffn(self, i, x, p, routed=None, live=None):
        """``x (T, D)`` float32 -> ``(out (T, D), load (E_held,) or
        None)``; ``routed``, a list, is given the router's choice. A
        token that is not ``live (T,)`` chooses no expert: its choice is
        put past the last expert, which nobody holds."""
        import jax.numpy as jnp
        from ..parallel import moe
        l = "l%d." % i
        if i < self.n_dense:
            return self._gated(x, p, l), None
        topi, topw = moe.route_grouped_sigmoid(
            x, p[l + "router_w"], p[l + "router_b"], n_group=self.n_group,
            topk_group=self.topk_group, top_k=self.top_k,
            scaling=self.route_scale)
        if live is not None:
            topi = jnp.where(live[:, None], topi, self.n_experts)
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
        if self.q_rank:
            cq = self._rms(self._mm(x, p[l + "wq_a"]), p[l + "q_g"])
            q = self._mm(cq, p[l + "wq_b"])
        else:
            q = self._mm(x, p[l + "wq"])
        q = q.reshape(x.shape[:-1] + (H, self.nope + self.rope))
        q_nope, q_r = q[..., :self.nope], q[..., self.nope:]
        ckv = self._mm(x, p[l + "wkv_a"])
        row = jnp.concatenate(
            [self._rms(ckv[..., :self.kv_rank], p[l + "kv_g"]),
             self._rotate(ckv[..., self.kv_rank:], positions),
             jnp.zeros(x.shape[:-1] + (self.row_width - self.latent,),
                       jnp.float32)], -1)
        return q_nope, self._rotate(q_r, positions), row

    def _gate_heads(self, a, x, p, l):
        """``a (..., H, v)`` through the head-wise output gate
        ``sigmoid(x W_g)_h`` where the model has one."""
        import jax
        if not self.attn_gate:
            return a
        return a * jax.nn.sigmoid(self._mm(x, p[l + "wg"]))[..., None]

    # -- the residual streams --------------------------------------------
    # With ``hc_mult`` 1 these are the plain residual path, ``h = h +
    # F(norm(h))``, operation for operation.
    def _streams(self, e):
        """In: the token's embedding in every stream, ``(..., n, C)``."""
        import jax.numpy as jnp
        if self.hc == 1:
            return e
        return jnp.broadcast_to(e[..., None, :],
                                e.shape[:-1] + (self.hc, e.shape[-1]))

    def _merge(self, X):
        """Out: the sum of the streams."""
        return X if self.hc == 1 else X.sum(-2)

    def _read(self, name, X, p):
        """What sublayer ``name`` reads of the state ``X (..., n, C)``:
        ``u = H_pre X``, and the coefficients of its write-back. The
        coefficient path — the norm over all ``n C`` values (no gain),
        the product at "highest", sigmoid, Sinkhorn — is float32, as the
        router is; the mixes are multiply-adds on the VPU, never a
        product the MXU would round to bfloat16."""
        import jax
        import jax.numpy as jnp
        if self.hc == 1:
            return X, None
        n = self.hc
        with jax.named_scope("mx_mhc"):
            flat = X.reshape(X.shape[:-2] + (n * X.shape[-1],))
            xt = flat * jax.lax.rsqrt(
                jnp.mean(flat * flat, -1, keepdims=True) + self.eps)
            z = jnp.dot(xt, p[name + "hc_w"], precision="highest")
            a, b = p[name + "hc_a"], p[name + "hc_b"]
            pre = jax.nn.sigmoid(a[0] * z[..., :n] + b[:n])
            post = 2.0 * jax.nn.sigmoid(a[1] * z[..., n:2 * n]
                                        + b[n:2 * n])
            res = a[2] * z[..., 2 * n:].reshape(z.shape[:-1] + (n, n)) \
                + b[2 * n:].reshape(n, n)
            res = jnp.exp(jnp.clip(res, *self.hc_clamp))
            for _ in range(self.hc_iters):      # rows, then columns
                res = res / (res.sum(-1, keepdims=True) + self.hc_eps)
                res = res / (res.sum(-2, keepdims=True) + self.hc_eps)
            u = (pre[..., None] * X).sum(-2)
        return u, (post, res)

    def _write(self, X, y, mix):
        """``X' = H_res X + H_post^T y``."""
        import jax
        if mix is None:
            return X + y
        post, res = mix
        with jax.named_scope("mx_mhc"):
            return (res[..., None] * X[..., None, :, :]).sum(-2) \
                + post[..., None] * y[..., None, :]

    def _block(self, i, X, p, attention, routed=None, live=None):
        """Block ``i`` over the state: ``attention(i, u) -> (increment,
        cached row)`` is the path's own (prefill or cached decode).
        Returns ``(X, row, expert load or None)``."""
        l = "l%d." % i
        u, mix = self._read(l + "attn_", X, p)
        out, row = attention(i, u)
        X = self._write(X, out, mix)
        u, mix = self._read(l + "ffn_", X, p)
        x = self._rms(u, p[l + "ffn_g"])
        out, load = self._ffn(i, x.reshape(-1, x.shape[-1]), p, routed,
                              live)
        return self._write(X, out.reshape(x.shape), mix), row, load

    def _draft_in(self, p, hidden, tokens):
        """The next-token module's input: ``W_p [RMSNorm(h_i);
        RMSNorm(Emb(t_{i+1}))]``, ``h_i`` the main model's state summed
        over the streams BEFORE its final norm."""
        import jax.numpy as jnp
        e = p["embed"][tokens].astype(jnp.float32)
        return self._mm(jnp.concatenate(
            [self._rms(hidden, p["mtp.h_g"]),
             self._rms(e, p["mtp.e_g"])], -1), p["mtp.proj"])

    @staticmethod
    def _counters(loads):
        import jax.numpy as jnp
        if not loads:
            return jnp.zeros((3,), jnp.int32)
        load = jnp.stack(loads)                           # (layers, E)
        return jnp.stack([load.sum(), (load > 0).sum(), load.max()])

    # -- the contract ----------------------------------------------------
    def prefill(self, params, tokens):
        logits, _h, rows = self._forward(params, tokens)
        return logits, rows

    def routing(self, params, tokens):
        """The router's choice at every expert layer of the main model
        over whole sequences ``tokens (B, L)``, on the prefill path:
        ``(expert layers, B * L, top_k)`` int32 — for a comparison with
        a reference's choice, not for serving."""
        import jax.numpy as jnp
        routed = []
        self._forward(params, tokens, routed)
        return jnp.stack(routed)

    def _flash(self, p, pos):
        """``attention(i, u)`` of the prefill path: the published form
        over a whole sequence on the flash kernel."""
        import jax.numpy as jnp
        from ..parallel.flash_attention import flash_attention
        B, L = pos.shape
        H, R = self.n_heads, self.kv_rank
        # the flash kernel takes head sizes of whole lane tiles: zero
        # columns change no score and give zero outputs
        wide = -(-max(self.nope + self.rope, self.v_dim) // 128) * 128

        def pad(a):
            return jnp.pad(a.astype(self.cache_dtype), (
                (0, 0), (0, 0), (0, 0), (0, wide - a.shape[-1])))

        def attention(i, u):
            l = "l%d." % i
            x = self._rms(u, p[l + "attn_g"])
            q_nope, q_r, row = self._latent(i, x, p, pos)
            row = row.astype(self.cache_dtype)   # as the pool holds it
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
            a = self._gate_heads(a[..., :self.v_dim], x, p, l)
            a = a.reshape(B, L, H * self.v_dim)
            return self._mm(a, p[l + "wo"]), row

        return attention

    def _forward(self, params, tokens, routed=None):
        """``(logits, h, rows)`` over whole sequences: ``h (B, L, D)``
        is the state summed over the streams before the final norm."""
        import jax.numpy as jnp
        p = params
        B, L = tokens.shape
        pos = jnp.broadcast_to(jnp.arange(L, dtype=jnp.int32), (B, L))
        X = self._streams(p["embed"][tokens].astype(jnp.float32))
        attention = self._flash(p, pos)
        rows = []
        for i in range(self.n_layers):
            X, row, _ = self._block(i, X, p, attention, routed)
            rows.append(row)
        h = self._merge(X)
        logits = self._mm(self._rms(h, p["out_g"]), p["head"])
        return logits, h, jnp.stack(rows)

    def _absorbed(self, p, positions, attend):
        """``attention(i, u)`` of the cached path, the ABSORBED form:
        the query goes through the key up-projection, the cache is
        never expanded. ``u (..., D)`` at ``positions (...)``."""
        import jax.numpy as jnp
        H, R = self.n_heads, self.kv_rank

        def attention(i, u):
            l = "l%d." % i
            x = self._rms(u, p[l + "attn_g"])
            q_nope, q_r, row = self._latent(i, x, p, positions)
            wk = p[l + "wk_b"].reshape(R, H, self.nope)
            q_lat = jnp.einsum("...hn,rhn->...hr", q_nope.astype(wk.dtype),
                               wk, preferred_element_type=jnp.float32)
            q_row = jnp.pad(
                jnp.concatenate([q_lat, q_r], -1),
                ((0, 0),) * (q_lat.ndim - 1)
                + ((0, self.row_width - self.latent),))
            o_lat = attend(i, q_row, row,
                           rank=R, scale=self.scale,
                           force_pallas=self.use_pallas)
            wv = p[l + "wv_b"].reshape(R, H, self.v_dim)
            a = jnp.einsum("...hr,rhv->...hv", o_lat.astype(wv.dtype), wv,
                           preferred_element_type=jnp.float32)
            a = self._gate_heads(a, x, p, l)
            return self._mm(a.reshape(u.shape[:-1] + (H * self.v_dim,)),
                            p[l + "wo"]), row

        return attention

    def _cached(self, p, e, layers, positions, attend, live=None):
        """Blocks ``layers`` over the embeddings ``e (..., D)`` through
        the cache: ``(h summed over the streams, the layers' rows, the
        expert layers' loads)``."""
        X = self._streams(e)
        attention = self._absorbed(p, positions, attend)
        rows, loads = [], []
        for i in layers:
            X, row, load = self._block(i, X, p, attention, live=live)
            rows.append(row)
            if load is not None:
                loads.append(load)
        return self._merge(X), rows, loads

    def decode(self, params, tokens, positions, attend, head=None,
               live=None):
        """The one-token form; a mixed step (``DecodeServer``) hands it
        more lanes than rows: ``head``, the lanes whose logits are
        wanted (the others do not pay the head), and ``live (T,)``, the
        lanes that are real (a dead one chooses no expert)."""
        import jax.numpy as jnp
        p = params
        h, rows, loads = self._cached(
            p, p["embed"][tokens].astype(jnp.float32),
            range(self.n_layers), positions, attend, live)
        if head is not None:
            h = h[head]
        logits = self._mm(self._rms(h, p["out_g"]), p["head"])
        counters = self._counters(loads)
        return logits, jnp.stack(rows), counters

    # -- the speculative form (``num_nextn_predict_layers`` 1) ------------
    def prefill_draft(self, params, tokens):
        """:meth:`prefill` that also hands out what the next-token
        module reads: ``(logits, h (B, L, D), rows)``."""
        return self._forward(params, tokens)

    def draft_prefill(self, params, hidden, tokens):
        """The next-token module over a whole sequence: position ``i``
        reads the main model's ``hidden[:, i]`` and the NEXT token
        ``tokens[:, i]`` (= t_{i+1}) and predicts t_{i+2}. ``(logits (B,
        L, V), rows (1, B, L, W))`` — the module's own cache layer."""
        import jax.numpy as jnp
        p = params
        B, L = tokens.shape
        pos = jnp.broadcast_to(jnp.arange(L, dtype=jnp.int32), (B, L))
        X = self._streams(self._draft_in(p, hidden, tokens))
        X, row, _ = self._block(self.n_layers, X, p, self._flash(p, pos))
        logits = self._mm(self._rms(self._merge(X), p["mtp.out_g"]),
                          p["head"])
        return logits, row[None]

    def _span(self, tokens, positions):
        import jax.numpy as jnp
        return positions[:, None] + jnp.arange(tokens.shape[1],
                                               dtype=jnp.int32)[None]

    def verify(self, params, tokens, positions, attend):
        """The main model over ``tokens (B, Q)`` at positions
        ``positions[b] ..``, causal, through the cache: ``(logits (B, Q,
        V), h (B, Q, D), rows (n_layers, B, Q, W), counters)``.
        ``attend`` is the layout's causal block form."""
        import jax.numpy as jnp
        p = params
        h, rows, loads = self._cached(
            p, p["embed"][tokens].astype(jnp.float32),
            range(self.n_layers), self._span(tokens, positions), attend)
        logits = self._mm(self._rms(h, p["out_g"]), p["head"])
        return logits, h, jnp.stack(rows), self._counters(loads)

    def draft(self, params, hidden, tokens, positions, attend):
        """The next-token module over ``Q`` positions through its cache
        layer (``n_layers``): position ``j`` reads ``hidden[:, j]`` and
        the token AFTER it, ``tokens[:, j]``. ``(logits (B, Q, V), rows
        (1, B, Q, W), counters)``."""
        import jax.numpy as jnp
        p = params
        h, rows, loads = self._cached(
            p, self._draft_in(p, hidden, tokens), (self.n_layers,),
            self._span(tokens, positions), attend)
        logits = self._mm(self._rms(h, p["mtp.out_g"]), p["head"])
        return logits, jnp.stack(rows), self._counters(loads)
