"""A decoder LM whose layers are mostly LINEAR attention — a gated delta
rule with a decay a channel, whose state is a fixed array a row — with
a latent-attention layer every ``layer_group_size``-th, over routed
experts: the published ``bailing_hybrid`` block, for
:class:`~mxnet_tpu.serving.DecodeServer`, named by what it computes. It
is the first model of the STATE form of the decode-model contract
(``serving.decode``'s docstring): beside the pages of its latent layers
it declares ``state_arrays``, which the server holds for each row of its
window.

``x`` is the RMS-normed residual, ``H`` heads of ``d = head_dim``.

**A linear-attention layer** (KDA, Kimi Delta Attention,
arXiv:2510.26692; layer ``i`` with ``(i + 1) % layer_group_size != 0``):

    q~, k~, v~ = x W_q, x W_k, x W_v                   each H d (one matrix, ``wqkv``)
    q, k, v    = SiLU(conv(q~)), SiLU(conv(k~)), SiLU(conv(v~))
                 causal, depthwise, ``short_conv_kernel_size`` 4, no bias
    q <- q / |q| * d^-0.5;  k <- k / |k|               a head (eps 1e-6 under the root)
    a = x W_f + dt_bias                                H d, a channel
    log alpha = kda_lower_bound * sigmoid(exp(A_log_h) * a)      in (-5, 0)
    beta = sigmoid(x W_b)                              a head
    S_t = (I - beta_t k_t k_t^T) Diag(alpha_t) S_{t-1} + beta_t k_t v_t^T
    o_t = S_t^T q_t                                    S (d, d) a head, float32
    out = (RMSNorm_d(o_t) * sigmoid(x W_g)_h) W_o      head-wise gate

No position encoding. What a row carries from token to token is ``S``
(``H d d`` float32) and the last ``kernel - 1`` rows of ``[q~, k~, v~]``
— ``state_arrays = (("s", (H, d, d), "float32"), ("conv", (3 * 3 H d,),
dtype))``, whatever the context. Decode: :func:`parallel.delta_rule.
kda_step` on the row's slot, in place; prefill: :func:`parallel.
delta_rule.kda_chunk`. Positions at or past a prompt's true length
leave both untouched (``beta = 0``, ``log alpha = 0``; the convolution's
rows are taken at the true end), and so does a dead row of the window.
``[q~, k~, v~]`` is rounded to the parameters' dtype before the
convolution, in prefill and decode alike, because that is what the
state holds of the three rows before.

**A prompt rides the step in chunks** (``chunk_lanes = True``: what
``DecodeServer`` observes to build the mixed step programs and no
prefill program): behind the ``B`` rows of a step, ``C`` lanes that are
consecutive positions ``start .. start + C - 1`` of ONE request's
prompt, the first ``n`` of them live. The lanes share the rows' matrices
(one stream of weights for the rows and the prompt) and a linear layer
does for them what the prefill does for a whole prompt, but FROM THE
ROW'S STATE: the convolution's first ``kernel - 1`` inputs are the row's
``conv`` rows and the rule starts from the row's ``S``
(``kda_chunk(..., state=)``) — zeros for both where ``start`` is 0,
whatever the slot's last tenant left — and the state after lane ``n -
1``, with the rows before position ``start + n``, is written back into
the request's row, which is no live row of that step. A latent layer's
lanes go through the layout's split ``attend``; a lane that is not live
chooses no expert; only the rows and the chunk's last live lane reach
the head.

**A latent-attention layer** (``(i + 1) % layer_group_size == 0``):
``serving.latent_moe``'s, with no query rank (``q = x W_q``), plain RoPE
at ``rope_theta`` on the rotary columns (``rope_scaling`` null: score
scale ``(nope + rope) ** -0.5``) and the head-wise output gate
``(softmax . v * sigmoid(x W_g)_h) W_o``. Its row ``[c_kv, k_r]`` lies in
cache layer ``(i + 1) // layer_group_size - 1``: ``cache_layers`` is the
number of such layers, NOT ``n_layers``; a linear layer's state lies in
state layer ``i - (i + 1) // layer_group_size``.

**Feed-forward**: ``serving.latent_moe``'s — the first
``first_k_dense_replace`` layers a gated SiLU MLP, every later one
``shared(x) + sum_i w_i expert_i(x)`` through ``route_grouped_sigmoid``
and ``expert_ffn`` told which experts this chip holds (``ep``).

**What the published keys do not settle** (``assumed``; the
configuration's file says each again, with its reason):

- the gate is the LOWER-BOUND form above (``kda_safe_gate``,
  ``kda_lower_bound``); the paper's original is ``-exp(A_log)
  softplus(a)``. ``kda_safe_gate`` false is refused.
- ``no_kda_lora``: ``W_f`` is one full matrix (no low-rank pair) and the
  output gates ``W_g`` are HEAD-WISE (``gated_attention_proj_
  granularity_type``), one value a head, in both kinds of layer.
- ``use_qk_norm``: the L2 norm above in a linear layer; in a latent
  layer the RMSNorm on ``c_kv`` alone (there is no query rank to norm).
- ``group_norm_size`` 1: the output norm is one RMSNorm a head over its
  ``d`` values, one gain vector of ``d`` shared by the heads.
- ``rope_interleave``: a fixed permutation of the rotary columns that
  random weights cannot tell (``serving.latent_moe``).
- ``expert_swiglu_limit_list`` / ``share_expert_swiglu_limit_list``: 0
  (no clamp) for every layer held; a NON-ZERO entry of a held layer is
  refused with a typed error, not guessed.
- ``A_log`` and ``dt_bias`` (:data:`A_LOG_RANGE`, :data:`DT_BIAS_RANGE`):
  drawn uniformly so that a step's ``alpha`` has its median over
  channels near 0.98 and reaches from about 0.5 to 0.999 — a state that
  forgot in two tokens would hide a wrong slot or a lost state.
- the next-token module (``num_nextn_predict_layers`` 1) is LEFT OUT and
  a non-zero value refused: speculation over a recurrent state needs the
  state after each drafted position kept until the verdict.

Precision: matrices in ``dtype`` (bfloat16), the router float32; float32
accumulation, residual, norms, gates, convolution and ``S``. Parameters
are a FLAT ``{name: array}`` dict.
"""
from __future__ import annotations

from .latent_moe import LatentMoEDecoderLM

__all__ = ["HybridLinearMoEDecoderLM", "A_LOG_RANGE", "DT_BIAS_RANGE"]

# how ``init_params`` (and the benchmark's weights) draw the gate's two
# vectors, uniformly: with ``a = x W_f`` of unit deviation the gate's
# argument ``exp(A_log) (a + dt_bias)`` has its median near -5.4, so
# ``alpha = exp(-5 sigmoid(.))`` has its median near 0.98 and reaches
# from about 0.5 (a channel that forgets in a few tokens) to 0.999
A_LOG_RANGE = (0.0, 0.5)
DT_BIAS_RANGE = (-6.0, -2.5)

# the keys whose published value is the only one written here
_PUBLISHED = {"kda_safe_gate": True, "no_kda_lora": True,
              "linear_silu": True, "use_qk_norm": True,
              "gated_attention_proj_granularity_type": "head_wise",
              "group_norm_size": 1}


class HybridLinearMoEDecoderLM(LatentMoEDecoderLM):
    """The decode-model contract, STATE form, for the hybrid block of the
    module docstring. Keyword arguments are the keys of the published
    ``config.json``; ``ep=(rank, size)`` the chip's share of the expert
    axis, ``use_pallas`` forces the Pallas kernels (interpreted off the
    TPU), ``dtype`` the matrices', the pool's and the convolution
    rows' (``"float32"`` for a test that compares logits)."""

    # ``decode`` takes ``head``, ``live`` and ``chunk``: a chunk of a
    # delta rule is the same recurrence from the row's state
    # (``kda_chunk(..., state=)``), so a prompt may ride the step in chunks
    chunk_lanes = True

    def __init__(self, *, vocab_size, hidden_size, num_hidden_layers,
                 num_attention_heads, head_dim, layer_group_size,
                 kv_lora_rank, qk_nope_head_dim, qk_rope_head_dim,
                 v_head_dim, intermediate_size, moe_intermediate_size,
                 num_experts, num_shared_experts, num_experts_per_tok,
                 n_group, topk_group, routed_scaling_factor,
                 first_k_dense_replace, rope_theta, q_lora_rank=None,
                 rope_scaling=None, short_conv_kernel_size=4,
                 kda_lower_bound=-5.0, rms_norm_eps=1e-6,
                 max_position_embeddings=4096, num_nextn_predict_layers=0,
                 expert_swiglu_limit_list=(),
                 share_expert_swiglu_limit_list=(), dtype="bfloat16",
                 ep=(0, 1), use_pallas=False, **published):
        from ..base import MXNetError
        for key, value in published.items():
            if key not in _PUBLISHED:
                raise TypeError("HybridLinearMoEDecoderLM: unexpected "
                                "keyword %r" % key)
            if value != _PUBLISHED[key]:
                raise MXNetError(
                    "HybridLinearMoEDecoderLM: %s = %r — only the "
                    "published %r is written (the other form's equations "
                    "are not settled by the config: serving."
                    "hybrid_linear_moe's docstring)"
                    % (key, value, _PUBLISHED[key]))
        self.group = int(layer_group_size)
        n = int(num_hidden_layers)
        if n // self.group < 1 or self.group < 2:
            raise MXNetError(
                "HybridLinearMoEDecoderLM: %d layers in groups of %d hold "
                "no whole group — a latent layer closes every group of "
                "layer_group_size, and the server's pool needs one"
                % (n, self.group))
        if int(num_nextn_predict_layers):
            raise MXNetError(
                "HybridLinearMoEDecoderLM: num_nextn_predict_layers %d "
                "with linear-attention layers — a drafted position that "
                "is rejected has already moved the recurrent state, and "
                "the state after each drafted position is not kept until "
                "the verdict; serve the model without its next-token "
                "module (0)" % int(num_nextn_predict_layers))
        for name, limits in (
                ("expert_swiglu_limit_list", expert_swiglu_limit_list),
                ("share_expert_swiglu_limit_list",
                 share_expert_swiglu_limit_list)):
            clamped = [i for i, v in enumerate(limits or ()) if v and i < n]
            if clamped:
                raise MXNetError(
                    "HybridLinearMoEDecoderLM: %s is %s at layer %d — "
                    "the clamped SwiGLU's form is not settled by the "
                    "config and is not guessed; only layers whose limit "
                    "is 0 are held" % (name, limits[clamped[0]],
                                       clamped[0]))
        super().__init__(
            vocab_size=vocab_size, hidden_size=hidden_size,
            num_hidden_layers=n, num_attention_heads=num_attention_heads,
            q_lora_rank=q_lora_rank, kv_lora_rank=kv_lora_rank,
            qk_nope_head_dim=qk_nope_head_dim,
            qk_rope_head_dim=qk_rope_head_dim, v_head_dim=v_head_dim,
            intermediate_size=intermediate_size,
            moe_intermediate_size=moe_intermediate_size,
            n_routed_experts=num_experts,
            n_shared_experts=num_shared_experts,
            num_experts_per_tok=num_experts_per_tok, n_group=n_group,
            topk_group=topk_group,
            routed_scaling_factor=routed_scaling_factor,
            first_k_dense_replace=first_k_dense_replace,
            rope_theta=rope_theta, rope_scaling=rope_scaling,
            rms_norm_eps=rms_norm_eps,
            max_position_embeddings=max_position_embeddings,
            attention_gate=True, cache_dtype=dtype, ep=ep,
            use_pallas=use_pallas)
        self.dtype = str(dtype)
        self.head_dim = int(head_dim)
        self.conv = int(short_conv_kernel_size)
        self.g_floor = float(kda_lower_bound)
        self.cache_layers = n // self.group
        self.state_layers = n - self.cache_layers
        # the chunk of the prefill's chunkwise form: as long as the
        # running decay's reciprocal stays finite (delta_rule.kda_chunk)
        self.chunk = min(64, 1 << (int(80.0 / abs(self.g_floor))
                                   .bit_length() - 1))
        H, d = self.n_heads, self.head_dim
        self.qkv = 3 * H * d
        self.state_arrays = (
            ("s", (H, d, d), "float32"),
            ("conv", ((self.conv - 1) * self.qkv,), self.dtype))

    # -- which layer is which ----------------------------------------------
    def latent_layer(self, i):
        """The cache layer of layer ``i``, or None for a linear one."""
        return (i + 1) // self.group - 1 if (i + 1) % self.group == 0 \
            else None

    def state_layer(self, i):
        return i - (i + 1) // self.group

    # -- parameters --------------------------------------------------------
    def _attn_params(self, i, w):
        import jax.numpy as jnp
        if self.latent_layer(i) is not None:
            return super()._attn_params(i, w)
        D, H, d = self.d_model, self.n_heads, self.head_dim
        l = "l%d." % i
        return {
            l + "attn_g": jnp.ones((D,), jnp.float32),
            l + "wqkv": w(D, self.qkv),
            l + "conv_w": w(self.conv, self.qkv, dtype=jnp.float32),
            l + "wf": w(D, H * d), l + "wb": w(D, H), l + "wg": w(D, H),
            l + "A_log": jnp.zeros((H,), jnp.float32),
            l + "dt_bias": jnp.zeros((H * d,), jnp.float32),
            l + "o_g": jnp.ones((d,), jnp.float32),
            l + "wo": w(H * d, D)}

    def init_params(self, seed=0):
        """:meth:`LatentMoEDecoderLM.init_params`' draw, the gate's
        ``A_log`` and ``dt_bias`` uniform in :data:`A_LOG_RANGE` and
        :data:`DT_BIAS_RANGE` from a key stream of their own, and every
        matrix in ``dtype``."""
        import jax
        import jax.numpy as jnp
        p = super().init_params(seed)
        keys = iter(jax.random.split(
            jax.random.fold_in(jax.random.PRNGKey(seed), 2),
            2 * self.n_layers))
        for name in sorted(p):
            for suffix, (lo, hi) in ((".A_log", A_LOG_RANGE),
                                     (".dt_bias", DT_BIAS_RANGE)):
                if name.endswith(suffix):
                    p[name] = jax.random.uniform(
                        next(keys), p[name].shape, jnp.float32, lo, hi)
        if self.dtype != "bfloat16":
            p = {n: a.astype(self.dtype) if a.dtype == jnp.bfloat16 else a
                 for n, a in p.items()}
        return p

    # -- a linear-attention layer ------------------------------------------
    def _gates(self, i, x, p, live):
        """``(log alpha (..., H, d), beta (..., H))`` of layer ``i``;
        where not ``live (...)``: 0 and 0, which leave the state as it
        was."""
        import jax
        import jax.numpy as jnp
        l = "l%d." % i
        H, d = self.n_heads, self.head_dim
        a = (self._mm(x, p[l + "wf"]) + p[l + "dt_bias"]).reshape(
            x.shape[:-1] + (H, d))
        g = self.g_floor * jax.nn.sigmoid(
            jnp.exp(p[l + "A_log"])[:, None] * a)
        beta = jax.nn.sigmoid(self._mm(x, p[l + "wb"]))
        return jnp.where(live[..., None, None], g, 0.0), \
            jnp.where(live[..., None], beta, 0.0)

    def _qkv(self, y):
        """The convolution's output ``y (..., 3 H d)`` as the rule's
        ``q``, ``k``, ``v (..., H, d)``: SiLU, then the L2 norms."""
        import jax
        import jax.numpy as jnp
        H, d = self.n_heads, self.head_dim
        q, k, v = jnp.split(jax.nn.silu(y).reshape(
            y.shape[:-1] + (3 * H, d)), 3, axis=-2)

        def unit(a):
            return a * jax.lax.rsqrt(jnp.sum(a * a, -1, keepdims=True)
                                     + 1e-6)

        return unit(q) * d ** -0.5, unit(k), v

    def _out(self, i, o, x, p):
        """``(RMSNorm_d(o) * sigmoid(x W_g)_h) W_o``."""
        l = "l%d." % i
        o = self._gate_heads(self._rms(o, p[l + "o_g"]), x, p, l)
        return self._mm(o.reshape(o.shape[:-2] + (-1,)), p[l + "wo"])

    def _linear_prefill(self, i, u, p, lengths):
        """Layer ``i`` over whole sequences ``u (B, L, D)`` of true
        lengths ``lengths (B,)``: ``(increment, S (B, H, d, d), conv
        rows (B, (K - 1) 3 H d))`` — both as they stand after position
        ``lengths - 1``."""
        import jax
        import jax.numpy as jnp
        from ..parallel.delta_rule import kda_chunk
        l = "l%d." % i
        B, L = u.shape[:2]
        K = self.conv
        x = self._rms(u, p[l + "attn_g"])
        raw = self._mm(x, p[l + "wqkv"]).astype(self.dtype)
        padded = jnp.pad(raw, ((0, 0), (K - 1, 0), (0, 0)))
        wide = padded.astype(jnp.float32)
        y = sum(p[l + "conv_w"][j] * wide[:, j:j + L] for j in range(K))
        # the rows before position ``lengths``: zeros where the prompt
        # is shorter than the kernel
        rows = jax.vmap(lambda a, n: jax.lax.dynamic_slice_in_dim(
            a, n, K - 1, axis=0))(padded, lengths)
        live = jnp.arange(L)[None] < lengths[:, None]
        g, beta = self._gates(i, x, p, live)
        # the chunkwise form takes whole chunks: a rung that is none
        # (a test's) is padded with positions that change nothing
        q, k, v = self._qkv(y)
        pad = -L % self.chunk
        if pad:
            q, k, v, g, beta = (jnp.pad(a, ((0, 0), (0, pad))
                                        + ((0, 0),) * (a.ndim - 2))
                                for a in (q, k, v, g, beta))
        o, S = kda_chunk(q, k, v, g, beta, chunk=self.chunk,
                         g_floor=self.g_floor)
        return self._out(i, o[:, :L], x, p), S, rows.reshape(B, -1)

    def _linear_step(self, i, u, p, state, arrays, live=None, chunk=None):
        """Layer ``i`` over one token a row ``u (B, D)`` on the rows'
        slots: ``(increment, (s, conv))``, the state arrays updated.

        On a MIXED step ``u`` is ``(B + C, D)``: behind the rows, the
        ``C`` lanes of ONE request's chunk, ``chunk = (its row of the
        state arrays, the first lane's position, the live lanes)`` and
        ``live (B + C,)``. The lanes share the rows' matrices — one
        stream of ``wqkv``, ``wf``, ``wb``, ``wg``, ``wo`` — and do what
        :meth:`_linear_prefill` does for a whole prompt, FROM THE ROW'S
        STATE (:meth:`_chunk_conv`, :meth:`_chunk_rule`). The request is
        no live row of the step: the rows' kernel passes its row through,
        and the chunk's lanes alone write it."""
        import jax.numpy as jnp
        from ..parallel.delta_rule import kda_step
        l = "l%d." % i
        s_all, conv_all = arrays
        j, K = self.state_layer(i), self.conv
        B = state.slots.shape[0]
        x = self._rms(u, p[l + "attn_g"])
        raw = self._mm(x, p[l + "wqkv"]).astype(self.dtype)
        before = conv_all[j, state.slots]                 # (B, (K-1) C)
        window = jnp.concatenate(
            [before.reshape(B, K - 1, self.qkv), raw[:B, None]], axis=1)
        y = (p[l + "conv_w"] * window.astype(jnp.float32)).sum(1)
        after = jnp.where(state.live[:, None],
                          window[:, 1:].reshape(B, -1), before)
        # the step's rows are ALL the window's, so the rows go back by a
        # gather through the inverse permutation and one whole-plane
        # write: a scatter would widen a 16-bit array to float32, whole
        plane = after[state.inverse]
        if chunk is not None:
            # the request's row rides in the same write
            tail, rows = self._chunk_conv(raw[B:], p[l + "conv_w"],
                                          conv_all[j, chunk[0]], chunk)
            y = jnp.concatenate([y, tail])
            plane = jnp.where((jnp.arange(B) == chunk[0])[:, None],
                              rows[None], plane)
        conv_all = conv_all.at[j].set(plane)
        g, beta = self._gates(i, x, p, state.live if live is None else live)
        q, k, v = self._qkv(y)
        o, s_all = kda_step(s_all, j, state.slots, q[:B], k[:B], v[:B],
                            g[:B], beta[:B], force_pallas=self.use_pallas)
        if chunk is not None:
            tail, s_all = self._chunk_rule(
                j, (q[B:], k[B:], v[B:], g[B:], beta[B:]), s_all, chunk)
            o = jnp.concatenate([o, tail])
        return self._out(i, o, x, p), (s_all, conv_all)

    def _chunk_conv(self, raw, conv_w, held, chunk):
        """The convolution over a chunk's lanes ``raw (C, 3 H d)``, its
        first ``K - 1`` inputs the row's ``conv`` rows ``held`` — zeros
        where the chunk starts the prompt: a slot's last tenant never
        leaks, whatever it left. Returns ``(y (C, 3 H d), the rows before
        position start + n)``: part of them the carried ones where ``n <
        K - 1``, ``held`` as it was where ``n`` is 0 (a warm-up)."""
        import jax
        import jax.numpy as jnp
        _row, start, n = chunk
        K, C = self.conv, raw.shape[0]
        carried = jnp.where(start > 0, held, jnp.zeros_like(held))
        padded = jnp.concatenate([carried.reshape(K - 1, self.qkv), raw])
        wide = padded.astype(jnp.float32)
        y = sum(conv_w[t] * wide[t:t + C] for t in range(K))
        rows = jax.lax.dynamic_slice_in_dim(padded, n, K - 1, axis=0)
        return y, jnp.where(n > 0, rows.reshape(-1), held)

    def _chunk_rule(self, j, qkvgb, s_all, chunk):
        """The delta rule over a chunk's lanes from the row's ``S``
        (zeros where the chunk starts the prompt; a ``where`` on the row
        that was read — a ``cond`` would compile both branches over the
        state): ``(o (C, H, d), s_all)``, the state after the last live
        lane written into the request's row of state layer ``j``, in
        place, and only that row. ``g`` and ``beta`` are 0 on the lanes
        that are not live, so the state stops at the last that is."""
        import jax.numpy as jnp
        from ..parallel.delta_rule import kda_chunk
        row, start, n = chunk
        C = qkvgb[0].shape[0]
        held = s_all[j, row]
        # the chunkwise form takes whole chunks: lanes that change nothing
        pad = -C % self.chunk
        o, S = kda_chunk(
            *(jnp.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1))[None]
              for a in qkvgb),
            state=jnp.where(start > 0, held, 0.0)[None], chunk=self.chunk,
            g_floor=self.g_floor)
        return o[0, :C], s_all.at[j, row].set(jnp.where(n > 0, S[0], held))

    # -- the contract (STATE form) -----------------------------------------
    def prefill(self, params, tokens, lengths):
        logits, rows, states = self._forward(params, tokens, lengths)
        return (logits, rows, *states)

    def routing(self, params, tokens):
        import jax.numpy as jnp
        routed = []
        self._forward(params, tokens,
                      jnp.full((tokens.shape[0],), tokens.shape[1],
                               jnp.int32), routed)
        return jnp.stack(routed)

    def _forward(self, params, tokens, lengths, routed=None):
        """``(logits, rows (cache_layers, B, L, W), (S (state_layers, B,
        H, d, d), conv (state_layers, B, (K - 1) 3 H d)))``."""
        import jax.numpy as jnp
        p = params
        B, L = tokens.shape
        pos = jnp.broadcast_to(jnp.arange(L, dtype=jnp.int32), (B, L))
        flash = self._flash(p, pos)
        states = []

        def attention(i, u):
            if self.latent_layer(i) is not None:
                return flash(i, u)
            out, S, conv = self._linear_prefill(i, u, p, lengths)
            states.append((S, conv))
            return out, None

        X = p["embed"][tokens].astype(jnp.float32)
        rows = []
        for i in range(self.n_layers):
            X, row, _ = self._block(i, X, p, attention, routed)
            if row is not None:
                rows.append(row)
        logits = self._mm(self._rms(X, p["out_g"]), p["head"])
        return logits, jnp.stack(rows), \
            tuple(jnp.stack(a) for a in zip(*states))

    def decode(self, params, tokens, positions, attend, state, head=None,
               live=None, chunk=None):
        """One token a row: ``state`` is the step's
        :class:`~mxnet_tpu.serving.kvcache.RowState` (``.arrays``: ``s``
        and ``conv``, whole; ``.slots``; ``.live``). Returns ``(logits,
        rows (cache_layers, B, W), s, conv, counters)``.

        A MIXED step hands more lanes than rows: behind the ``B`` rows of
        ``state``, ``C`` lanes that are consecutive positions of ONE
        request's prompt, ``chunk = (its row of the state arrays, the
        first lane's position, the live lanes)``. Everything is lane-wise
        but the two kinds of attention: a latent layer's ``attend`` is
        the layout's split one (``attend_chunk``), a linear layer runs
        the rows through ``kda_step`` and the chunk through ``kda_chunk``
        from the request's row of ``s`` and ``conv``, which the chunk's
        lanes write (:meth:`_linear_step`). ``live (B + C,)``: a lane
        that is not live chooses no expert and moves no state; ``head (B
        + 1,)``: the lanes that reach the head, ``logits`` theirs alone;
        the latent rows come back for every lane, ``(cache_layers, B + C,
        W)``."""
        import jax.numpy as jnp
        p = params
        absorbed = self._absorbed(
            p, positions,
            lambda i, *a, **kw: attend(self.latent_layer(i), *a, **kw))
        arrays = tuple(state.arrays)

        def attention(i, u):
            nonlocal arrays
            if self.latent_layer(i) is not None:
                return absorbed(i, u)
            out, arrays = self._linear_step(i, u, p, state, arrays, live,
                                            chunk)
            return out, None

        X = p["embed"][tokens].astype(jnp.float32)
        rows, loads = [], []
        for i in range(self.n_layers):
            X, row, load = self._block(i, X, p, attention, live=live)
            if row is not None:
                rows.append(row)
            if load is not None:
                loads.append(load)
        if head is not None:
            # a chunk's lanes do not pay the head
            X = X[head]
        logits = self._mm(self._rms(X, p["out_g"]), p["head"])
        return (logits, jnp.stack(rows), *arrays, self._counters(loads))
