"""A decoder LM whose attention layers are mostly SLIDING-WINDOW — a
layer keeps the last ``sliding_window`` keys and values of a row, the
same bytes whatever the context — with a full-attention layer every few,
grouped-query heads whose COUNT differs by the kind of layer, a per-head
output gate, and softmax-routed experts beside a shared one: the
published ``laguna`` block, for :class:`~mxnet_tpu.serving.DecodeServer`,
named by what it computes. It is the second model of the STATE form of
the decode-model contract (``serving.decode``'s docstring): beside the
pages of its full-attention layers it declares ``state_arrays``, a RING
of keys and one of values a row and sliding layer.

**The block** (pre-norm, RMSNorm, no biases): ``h = x + Attn_i(RMSNorm(
x))``, ``y = h + FFN_i(RMSNorm(h))``; a final RMSNorm, an untied head.

- *Attention of layer i.* ``H_i = num_attention_heads_per_layer[i]``
  query heads over ``num_key_value_heads`` key/value heads of
  ``head_dim``: ``q = x W_q`` (``H_i`` x ``head_dim``), ``k = x W_k``,
  ``v = x W_v``; no norm over ``q`` and ``k``. RoPE by the layer's type,
  from ``rope_parameters[layer_types[i]]``: ``rope_type`` ``yarn`` —
  YaRN's frequencies over the FIRST ``partial_rotary_factor * head_dim``
  values of a head (half against half inside them), the rest passed
  through, cos and sin times ``attention_factor`` — or ``default``, plain
  RoPE at ``rope_theta``. Scores ``q . k / sqrt(head_dim)``; query head
  ``j`` reads key/value head ``j // (H_i / kv heads)``; causal, and in a
  ``sliding_attention`` layer key ``t`` is visible to query ``s`` iff ``s
  - sliding_window < t <= s`` (``sliding_window`` keys with its own).
  The gate: ``g = sigmoid(x W_g)``, one value a head, from the layer's
  normed input; ``o_j <- g_j o_j`` before ``W_o``.
- *Feed-forward.* A layer of ``mlp_only_layers`` is a gated SiLU MLP of
  ``intermediate_size``. Every other: the router in float32 at
  "highest", ``p = softmax(x W_r)`` over all ``num_experts``, the
  ``num_experts_per_tok`` largest, renormalised over the chosen
  (``norm_topk_prob``), times ``moe_routed_scaling_factor``, applied to
  the experts' outputs (``parallel.moe.route_softmax_topk``,
  ``expert_ffn`` told which experts this chip holds: ``ep=(rank,
  size)``); plus one shared expert of ``shared_expert_intermediate_
  size``, added ungated to every token.

**What a row carries.** A full-attention layer caches per-head K and V
in the server's pages — cache layer = the number of full layers before
it; ``cache_layers`` counts those layers only. A sliding layer keeps
``state_arrays = (("ring_k", (W, kv heads * head_dim), dtype),
("ring_v", ...))``: key ``t`` lies in slot ``t % W`` of its row's ring,
already rotated, a token's heads side by side. A prefill writes a row's
rings WHOLE (a prompt shorter than ``W`` leaves slots that the row's
position masks, never their content); a decode step
(:func:`parallel.flash_attention.ring_decode`) attends the slots the
position says are valid and puts its own key into slot ``p % W``, in
place; a row that is not live leaves its ring as it was.

**A prompt rides the step in chunks** (``chunk_lanes = True``: what
``DecodeServer`` observes; the server then builds no prefill program and
:meth:`prefill` is the oracle of the tests). ``decode`` is handed, behind
the step's rows, ``C`` lanes that are consecutive positions ``start ..``
of ONE request's prompt. Lane-wise everywhere but in attention: a
full-attention layer's ``attend`` is the layout's split one (the rows
their paged kernel, the chunk a walk of the request's pages under a
running softmax); a sliding layer's chunk
(:func:`parallel.flash_attention.ring_chunk`) sees, lane ``j`` at ``p =
start + j``, keys ``p - W < t <= p`` of the request's ring as it stands
and the chunk's own rows ``<= j`` — the banded grouped forward the
prefill uses, its queries offset behind the ring's ``W`` keys laid out in
position order, a slot whose position would be negative masked by
position — and then puts the last ``min(n, W)`` live lanes into slots
``(start + j) % W``, one row written in place. The chunk's request is no
live row of the step, so nothing else writes its ring; the step counters
``ring_rows_wrapped``, ``global_pages_live`` and ``ring_bytes`` count the
rows that DECODE (what ``mx_ring_decode`` and the paged kernel read), the
routed experts' three every live lane.

**What the published keys do not settle** (``assumed``; the
configuration's file says each again, with its reason): no norm over
``q`` and ``k`` (no key names one); ``attention_factor`` multiplies cos
and sin; the window counts the query's own key; the gate reads the
layer's normed input and is applied before ``W_o``; ``softmax`` scores;
the shared expert is ungated. A key whose published value is the only
one written here is REFUSED at any other value with a typed error
(:data:`_PUBLISHED`), never ignored.

Step counters (``step_counters``, on ``mx:decode.readback`` and in
``stats()["moe"]``): the routed experts' three, and ``ring_rows_wrapped``
(live rows at or past position ``W``), ``global_pages_live`` (table
pages the live rows' keys occupy) and ``ring_bytes`` (what the rings'
visible keys and values weigh, all sliding layers).

Precision: matrices in ``dtype`` (bfloat16), the router float32, pages
and rings in ``cache_dtype``; float32 accumulation, residual, norms,
RoPE, softmax and gates. Parameters are a FLAT ``{name: array}`` dict.

Ten lines that serve it (run by ``tests/test_window_moe.py``)::

    from mxnet_tpu.serving import DecodeServer, WindowMoEDecoderLM
    from mxnet_tpu.serving.window_moe import tiny_config
    model = WindowMoEDecoderLM(**tiny_config(), dtype="float32")
    params = model.init_params(seed=0)
    srv = DecodeServer(model, params, seq_ladder=[16, 64],
                       max_new_tokens=24, page_size=8, window=4,
                       pool_pages=64, prefix_cache=False)
    req = srv.submit([5, 9, 2, 7] * 10, max_new_tokens=24)
    print(list(req.tokens(timeout=60)))        # 24 token ids
    srv.stop()
"""
from __future__ import annotations

import math

__all__ = ["WindowMoEDecoderLM", "tiny_config"]

# the keys whose published value is the only one written here
_PUBLISHED = {"model_type": "laguna", "attention_bias": False,
              "tie_word_embeddings": False, "gating": "per-head",
              "decoder_sparse_step": 1,
              "moe_apply_router_weight_on_input": False,
              "moe_router_logit_softcapping": 0}
_FULL, _SLIDING = "full_attention", "sliding_attention"
_ROPE_KEYS = {
    "yarn": {"rope_type", "rope_theta", "factor",
             "original_max_position_embeddings", "beta_slow", "beta_fast",
             "attention_factor", "partial_rotary_factor"},
    "default": {"rope_type", "rope_theta", "partial_rotary_factor"}}


def tiny_config():
    """The published keys at a size a CPU test runs: five layers in the
    published pattern (one leading dense full-attention layer, then a
    period of three sliding layers and a full one), a window of 8, 6 and
    4 query heads over 2 key/value heads, 8 experts."""
    return dict(
        vocab_size=96, hidden_size=32, intermediate_size=64,
        num_hidden_layers=5, num_attention_heads=4, num_key_value_heads=2,
        head_dim=16, max_position_embeddings=4096, rms_norm_eps=1e-6,
        num_experts=8, num_experts_per_tok=3, moe_intermediate_size=16,
        shared_expert_intermediate_size=16, norm_topk_prob=True,
        mlp_only_layers=[0], sliding_window=8,
        rope_parameters={
            _FULL: {"rope_theta": 500000, "rope_type": "yarn",
                    "factor": 128, "original_max_position_embeddings": 64,
                    "beta_slow": 1, "beta_fast": 32,
                    "attention_factor": 1.4852030263919618,
                    "partial_rotary_factor": 0.5},
            _SLIDING: {"rope_type": "default", "rope_theta": 10000,
                       "partial_rotary_factor": 1}},
        layer_types=[_FULL] + [_SLIDING] * 3 + [_FULL],
        mlp_layer_types=["dense"] + ["sparse"] * 4,
        gating_types=["per_head"] * 5, moe_routed_scaling_factor=2.5,
        num_attention_heads_per_layer=[4, 6, 6, 6, 4])


class WindowMoEDecoderLM:
    """The decode-model contract, STATE form, for the block of the module
    docstring. Keyword arguments are the keys of the published
    ``config.json`` (the per-layer lists may be longer than
    ``num_hidden_layers``: the first that many are the layers held);
    ``ep=(rank, size)`` the chip's share of the expert axis,
    ``use_pallas`` forces the Pallas kernels (interpreted off the TPU),
    ``dtype`` the matrices' and ``cache_dtype`` the pages' and the rings'
    (``dtype`` where not given; ``"float32"`` for a test that compares
    logits)."""

    # ``decode`` takes ``head``, ``live`` and ``chunk``: a ring takes a
    # chunk of a prompt (512 more keys into slots ``t % W``, attended under
    # the band), so a prompt may ride the step in chunks
    chunk_lanes = True
    step_counters = ("moe", ("moe_slots", "experts_touched", "max_load",
                             "ring_rows_wrapped", "global_pages_live",
                             "ring_bytes"))

    def __init__(self, *, vocab_size, hidden_size, intermediate_size,
                 num_hidden_layers, num_attention_heads,
                 num_key_value_heads, head_dim, num_experts,
                 num_experts_per_tok, moe_intermediate_size,
                 shared_expert_intermediate_size, sliding_window,
                 rope_parameters, layer_types,
                 num_attention_heads_per_layer, mlp_only_layers=(),
                 mlp_layer_types=None, gating_types=None,
                 norm_topk_prob=True, moe_routed_scaling_factor=1.0,
                 rms_norm_eps=1e-6, max_position_embeddings=4096,
                 dtype="bfloat16", cache_dtype=None, ep=(0, 1),
                 use_pallas=False, **published):
        from ..base import MXNetError
        from ..parallel.sharding_rules import held_experts
        me = type(self).__name__
        for key, value in published.items():
            if key not in _PUBLISHED:
                raise TypeError("%s: unexpected keyword %r" % (me, key))
            if value != _PUBLISHED[key]:
                raise MXNetError(
                    "%s: %s = %r — only the published %r is written (the "
                    "other form's equations are not settled by the "
                    "config: serving.window_moe's docstring)"
                    % (me, key, value, _PUBLISHED[key]))
        n = self.n_layers = int(num_hidden_layers)
        self.vocab = int(vocab_size)
        self.d_model = int(hidden_size)
        self.d_ff = int(intermediate_size)
        self.n_kv_heads = int(num_key_value_heads)
        self.head_dim = int(head_dim)
        self.d_expert = int(moe_intermediate_size)
        self.d_shared = int(shared_expert_intermediate_size)
        self.n_experts = int(num_experts)
        self.top_k = int(num_experts_per_tok)
        self.renormalize = bool(norm_topk_prob)
        self.route_scale = float(moe_routed_scaling_factor)
        self.window = int(sliding_window)
        self.eps = float(rms_norm_eps)
        self.max_len = int(max_position_embeddings)
        self.use_pallas = bool(use_pallas)
        self.dtype = str(dtype)
        self.cache_dtype = str(cache_dtype or dtype)
        self.held = held_experts(self.n_experts, ep[1], ep[0])
        self.scale = 1.0 / math.sqrt(self.head_dim)
        for name, per_layer in (
                ("layer_types", layer_types),
                ("num_attention_heads_per_layer",
                 num_attention_heads_per_layer)):
            if len(per_layer) < n:
                raise MXNetError("%s: %s names %d layers, the model has %d"
                                 % (me, name, len(per_layer), n))
        self.kinds = tuple(str(t) for t in layer_types[:n])
        self.heads = tuple(int(h) for h in
                           num_attention_heads_per_layer[:n])
        self.dense = tuple(i in set(mlp_only_layers) for i in range(n))
        for i, (kind, heads) in enumerate(zip(self.kinds, self.heads)):
            if kind not in (_FULL, _SLIDING):
                raise MXNetError(
                    "%s: layer_types[%d] = %r is neither %r nor %r"
                    % (me, i, kind, _FULL, _SLIDING))
            if heads % self.n_kv_heads:
                raise MXNetError(
                    "%s: layer %d's %d query heads do not divide over %d "
                    "key/value heads" % (me, i, heads, self.n_kv_heads))
            if kind == _FULL and heads != int(num_attention_heads):
                raise MXNetError(
                    "%s: num_attention_heads is %d and a full-attention "
                    "layer (%d) has %d" % (me, int(num_attention_heads),
                                           i, heads))
        for i, said in enumerate((mlp_layer_types or ())[:n]):
            if said != ("dense" if self.dense[i] else "sparse"):
                raise MXNetError(
                    "%s: mlp_layer_types[%d] = %r disagrees with "
                    "mlp_only_layers %s" % (me, i, said,
                                            list(mlp_only_layers)))
        for i, said in enumerate((gating_types or ())[:n]):
            if said != "per_head":
                raise MXNetError(
                    "%s: gating_types[%d] = %r — only the per-head output "
                    "gate is written" % (me, i, said))
        self.cache_layers = self.kinds.count(_FULL)
        self.state_layers = self.kinds.count(_SLIDING)
        if not (self.cache_layers and self.state_layers):
            raise MXNetError(
                "%s: %d full-attention and %d sliding layers — the state "
                "form of the server needs a layer of each kind (pages for "
                "the first, a ring a row for the second)"
                % (me, self.cache_layers, self.state_layers))
        self.n_moe_layers = n - sum(self.dense)
        # (frequencies, rotated width, the gain on cos and sin) a kind
        self.rope = {kind: self._rope_table(kind, rope_parameters)
                     for kind in (_FULL, _SLIDING)}
        width = self.n_kv_heads * self.head_dim
        self.cache_arrays = (
            ("k", (self.n_kv_heads, self.head_dim), self.cache_dtype),
            ("v", (self.n_kv_heads, self.head_dim), self.cache_dtype))
        self.state_arrays = (
            ("ring_k", (self.window, width), self.cache_dtype),
            ("ring_v", (self.window, width), self.cache_dtype))

    def _rope_table(self, kind, rope_parameters):
        import numpy as np
        from ..base import MXNetError
        from .latent_moe import yarn_inv_freq
        me = type(self).__name__
        if kind not in rope_parameters:
            raise MXNetError("%s: rope_parameters names no %r"
                             % (me, kind))
        rp = dict(rope_parameters[kind])
        how = rp.get("rope_type", "default")
        if how not in _ROPE_KEYS:
            raise MXNetError(
                "%s: rope_parameters[%r].rope_type %r — 'yarn' and "
                "'default' are written" % (me, kind, how))
        unknown = sorted(set(rp) - _ROPE_KEYS[how])
        if unknown:
            raise MXNetError(
                "%s: rope_parameters[%r] has %s, which rope_type %r does "
                "not read — refused, not ignored" % (me, kind, unknown, how))
        rot = int(round(self.head_dim * float(
            rp.get("partial_rotary_factor", 1))))
        if rot % 2 or not 0 < rot <= self.head_dim:
            raise MXNetError(
                "%s: partial_rotary_factor %s rotates %d of a head's %d "
                "values" % (me, rp.get("partial_rotary_factor"), rot,
                            self.head_dim))
        theta = float(rp["rope_theta"])
        if how == "yarn":
            freqs = yarn_inv_freq(
                rot, theta, float(rp["factor"]),
                int(rp["original_max_position_embeddings"]),
                float(rp["beta_fast"]), float(rp["beta_slow"]))
            gain = float(rp.get("attention_factor", 1.0))
        else:
            freqs = (theta ** (-np.arange(0, rot, 2, dtype=np.float64)
                               / rot)).astype(np.float32)
            gain = 1.0
        return freqs, rot, gain

    # -- which layer is which ----------------------------------------------
    def cache_layer(self, i):
        """The cache layer of layer ``i``, or None for a sliding one."""
        return self.kinds[:i].count(_FULL) if self.kinds[i] == _FULL \
            else None

    def state_layer(self, i):
        return self.kinds[:i].count(_SLIDING)

    # -- parameters --------------------------------------------------------
    def init_params(self, seed=0):
        """Matrices in ``dtype`` at ``fan_in ** -0.5`` (the embedding at
        1), the router's matrix float32, norm gains 1."""
        import jax
        import jax.numpy as jnp
        keys = iter(jax.random.split(jax.random.PRNGKey(seed),
                                     16 * self.n_layers + 4))
        dt = jnp.dtype(self.dtype)

        def w(*shape, dtype=dt, std=None):
            std = shape[-2] ** -0.5 if std is None else std
            return (jax.random.normal(next(keys), shape, jnp.float32)
                    * std).astype(dtype)

        D, Dh, Hkv = self.d_model, self.head_dim, self.n_kv_heads
        E = self.held[1] - self.held[0]
        ones = lambda n: jnp.ones((n,), jnp.float32)    # noqa: E731
        p = {"embed": w(self.vocab, D, std=1.0), "out_g": ones(D),
             "head": w(D, self.vocab)}
        for i, H in enumerate(self.heads):
            l = "l%d." % i
            p.update({
                l + "attn_g": ones(D), l + "wq": w(D, H * Dh),
                l + "wk": w(D, Hkv * Dh), l + "wv": w(D, Hkv * Dh),
                l + "wg": w(D, H), l + "wo": w(H * Dh, D),
                l + "ffn_g": ones(D)})
            if self.dense[i]:
                p.update({l + "w_gate": w(D, self.d_ff),
                          l + "w_up": w(D, self.d_ff),
                          l + "w_down": w(self.d_ff, D)})
                continue
            F, Fs = self.d_expert, self.d_shared
            p.update({
                l + "router_w": w(D, self.n_experts, dtype=jnp.float32),
                l + "experts.w_gate": w(E, D, F),
                l + "experts.w_up": w(E, D, F),
                l + "experts.w_down": w(E, F, D),
                l + "shared.w_gate": w(D, Fs), l + "shared.w_up": w(D, Fs),
                l + "shared.w_down": w(Fs, D)})
        return p

    # -- pieces ------------------------------------------------------------
    def _rms(self, x, g):
        import jax
        import jax.numpy as jnp
        x = x.astype(jnp.float32)
        return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True)
                                 + self.eps) * g

    @staticmethod
    def _mm(x, w):
        """Operands in the matrix's dtype, float32 accumulation."""
        import jax.numpy as jnp
        return jnp.dot(x.astype(w.dtype), w,
                       preferred_element_type=jnp.float32)

    def _rotate(self, kind, x, positions):
        """``x (..., H, head_dim)`` at ``positions (...)`` by the layer
        kind's table: the first ``rot`` values of a head rotated half
        against half, cos and sin times the gain, the rest passed
        through. Float32."""
        import jax.numpy as jnp
        freqs, rot, gain = self.rope[kind]
        ang = positions[..., None, None].astype(jnp.float32) \
            * jnp.asarray(freqs)                          # (..., 1, rot/2)
        cos, sin = jnp.cos(ang) * gain, jnp.sin(ang) * gain
        x = x.astype(jnp.float32)
        a, b = jnp.split(x[..., :rot], 2, axis=-1)
        return jnp.concatenate(
            [a * cos - b * sin, b * cos + a * sin, x[..., rot:]], -1)

    def _qkv(self, i, x, p, positions):
        """Rotated queries and keys, values and the gate of layer ``i``:
        ``x (..., D)`` at ``positions (...)`` -> ``q (..., H_i, Dh)``,
        ``k``/``v (..., Hkv, Dh)`` — the keys and values in the cache's
        dtype, as the pages and the rings hold them — ``g (..., H_i)``."""
        import jax
        import jax.numpy as jnp
        l, kind = "l%d." % i, self.kinds[i]
        lead, Dh = x.shape[:-1], self.head_dim
        q = self._mm(x, p[l + "wq"]).reshape(lead + (self.heads[i], Dh))
        k = self._mm(x, p[l + "wk"]).reshape(lead + (self.n_kv_heads, Dh))
        v = self._mm(x, p[l + "wv"]).reshape(lead + (self.n_kv_heads, Dh))
        cache = jnp.dtype(self.cache_dtype)
        return self._rotate(kind, q, positions), \
            self._rotate(kind, k, positions).astype(cache), \
            v.astype(cache), jax.nn.sigmoid(self._mm(x, p[l + "wg"]))

    def _gated(self, x, p, prefix):
        import jax
        return self._mm(jax.nn.silu(self._mm(x, p[prefix + "w_gate"]))
                        * self._mm(x, p[prefix + "w_up"]),
                        p[prefix + "w_down"])

    def _ffn(self, i, x, p, routed=None, live=None):
        """``x (T, D)`` float32 -> ``(out (T, D), load (E_held,) or
        None)``; ``routed``, a list, is given the router's choice. A
        token that is not ``live (T,)`` chooses no expert: its choice is
        put past the last expert, which nobody holds."""
        import jax.numpy as jnp
        from ..parallel import moe
        l = "l%d." % i
        if self.dense[i]:
            return self._gated(x, p, l), None
        topi, topw = moe.route_softmax_topk(
            x, p[l + "router_w"], top_k=self.top_k,
            renormalize=self.renormalize)
        if routed is not None:
            routed.append(topi)
        if live is not None:
            topi = jnp.where(live[:, None], topi, self.n_experts)
        out = moe.expert_ffn(
            x, {n: p[l + "experts." + n]
                for n in ("w_gate", "w_up", "w_down")},
            topi, topw * self.route_scale, self.held,
            force_pallas=self.use_pallas)
        return self._gated(x, p, l + "shared.") + out, \
            moe.expert_load(topi, self.held)

    def _ring_of(self, seq, lengths):
        """A prompt's keys (or values) ``seq (B, L, Hkv, Dh)`` as the
        rings they leave, ``(B, W, Hkv * Dh)``: slot ``s`` holds the
        LAST position ``t < lengths`` with ``t % W == s`` (whatever lies
        in a slot no position has reached is masked by the row's
        position, never read)."""
        import jax.numpy as jnp
        B, L = seq.shape[:2]
        W = self.window
        slot = jnp.arange(W, dtype=jnp.int32)[None, :]
        last = slot + W * ((lengths[:, None] - 1 - slot) // W)
        flat = seq.reshape(B, L, -1)
        return jnp.take_along_axis(
            flat, jnp.clip(last, 0, L - 1)[:, :, None], axis=1)

    # -- the contract (STATE form) -----------------------------------------
    def prefill(self, params, tokens, lengths):
        """``tokens (B, L)`` of true lengths ``lengths (B,)`` ->
        ``(logits (B, L, V), k, v (cache_layers, B, L, Hkv, Dh), ring_k,
        ring_v (state_layers, B, W, Hkv * Dh))``, the rings as they stand
        after position ``lengths - 1``. A position at or past its true
        length costs no expert (what it computes is nobody's)."""
        return self._forward(params, tokens, lengths)

    def routing(self, params, tokens):
        """The router's choice at every expert layer over whole sequences
        ``tokens (B, L)``, on the prefill path: ``(expert layers, B * L,
        top_k)`` int32 — for a comparison with a reference's choice."""
        import jax.numpy as jnp
        routed = []
        self._forward(params, tokens,
                      jnp.full((tokens.shape[0],), tokens.shape[1],
                               jnp.int32), routed)
        return jnp.stack(routed)

    def _forward(self, params, tokens, lengths, routed=None):
        import jax.numpy as jnp
        from ..parallel.flash_attention import flash_attention
        p = params
        B, L = tokens.shape
        pos = jnp.broadcast_to(jnp.arange(L, dtype=jnp.int32), (B, L))
        # a rung's padding chooses no expert: every padded position holds
        # the same token, so all of them would pile onto ONE choice of
        # experts — thousands of slots on this chip or none, by the
        # seed's weights and the prompt's length
        live = (pos < lengths[:, None]).reshape(B * L)
        h = p["embed"][tokens].astype(jnp.float32)
        ks, vs, rings = [], [], []
        for i in range(self.n_layers):
            l = "l%d." % i
            x = self._rms(h, p[l + "attn_g"])
            q, k, v, g = self._qkv(i, x, p, pos)
            sliding = self.kinds[i] == _SLIDING
            a = flash_attention(
                q, k, v, causal=True, scale=self.scale,
                window=self.window if sliding else None,
                force_pallas=self.use_pallas)
            a = (a.astype(jnp.float32) * g[..., None]).reshape(B, L, -1)
            h = h + self._mm(a, p[l + "wo"])
            if sliding:
                rings.append((self._ring_of(k, lengths),
                              self._ring_of(v, lengths)))
            else:
                ks.append(k)
                vs.append(v)
            x = self._rms(h, p[l + "ffn_g"])
            out, _ = self._ffn(i, x.reshape(B * L, -1), p, routed, live)
            h = h + out.reshape(B, L, -1)
        logits = self._mm(self._rms(h, p["out_g"]), p["head"])
        return (logits, jnp.stack(ks), jnp.stack(vs),
                *(jnp.stack(a) for a in zip(*rings)))

    def decode(self, params, tokens, positions, attend, state, head=None,
               live=None, chunk=None):
        """One token a row: ``attend(cache layer, q (B, H, Dh), k_new,
        v_new (B, Hkv, Dh), scale=, force_pallas=)`` attends a
        full-attention layer's pages; ``state`` is the step's
        :class:`~mxnet_tpu.serving.kvcache.RowState` (``.arrays``: the
        rings, whole; ``.slots``; ``.live``). Returns ``(logits, k, v
        (cache_layers, B, Hkv, Dh), ring_k, ring_v, counters)``.

        A MIXED step hands more lanes than rows: behind the ``B`` rows of
        ``state``, ``C`` lanes that are consecutive positions of ONE
        request's prompt, ``chunk = (its row of the rings, the first
        lane's position, the live lanes)``. Everything is lane-wise but
        attention: a full layer's ``attend`` is the layout's split one
        (``attend_chunk``), a sliding layer runs the rows through
        :func:`~mxnet_tpu.parallel.flash_attention.ring_decode` and the
        chunk through :func:`~mxnet_tpu.parallel.flash_attention.
        ring_chunk`, which writes the request's ring (the request is no
        live row of the step). ``live (B + C,)``: a lane that is not live
        chooses no expert; ``head (B + 1,)``: the lanes that reach the
        head, ``logits`` theirs alone; the keys and values come back for
        every lane, ``(cache_layers, B + C, Hkv, Dh)``, and the counters
        count the ``B`` rows only (what ``mx_ring_decode`` and the paged
        kernel read)."""
        import jax.numpy as jnp
        from ..parallel.flash_attention import ring_chunk, ring_decode
        p = params
        B = state.slots.shape[0]
        row_pos = positions[:B]
        live = state.live if live is None else live
        ring_k, ring_v = state.arrays
        h = p["embed"][tokens].astype(jnp.float32)
        ks, vs, loads = [], [], []
        for i in range(self.n_layers):
            l = "l%d." % i
            x = self._rms(h, p[l + "attn_g"])
            q, k, v, g = self._qkv(i, x, p, positions)
            if self.kinds[i] == _SLIDING:
                a, ring_k, ring_v = ring_decode(
                    q[:B], k[:B], v[:B], ring_k, ring_v,
                    self.state_layer(i), state.slots, row_pos, state.live,
                    scale=self.scale, force_pallas=self.use_pallas)
                if chunk is not None:
                    tail, ring_k, ring_v = ring_chunk(
                        q[B:], k[B:], v[B:], ring_k, ring_v,
                        self.state_layer(i), *chunk, scale=self.scale,
                        force_pallas=self.use_pallas)
                    a = jnp.concatenate([a, tail])
            else:
                a = attend(self.cache_layer(i), q, k, v, scale=self.scale,
                           force_pallas=self.use_pallas)
                ks.append(k)
                vs.append(v)
            a = (a.astype(jnp.float32) * g[..., None]).reshape(
                tokens.shape[0], -1)
            h = h + self._mm(a, p[l + "wo"])
            out, load = self._ffn(i, self._rms(h, p[l + "ffn_g"]), p,
                                  live=live)
            h = h + out
            if load is not None:
                loads.append(load)
        if head is not None:
            # a chunk's lanes do not pay the head
            h = h[head]
        logits = self._mm(self._rms(h, p["out_g"]), p["head"])
        load = jnp.stack(loads)                           # (layers, E)
        # a live row's keys: those the step's position has reached
        seen = jnp.where(state.live, row_pos + 1, 0)
        token = 2 * self.n_kv_heads * self.head_dim \
            * jnp.dtype(self.cache_dtype).itemsize
        counters = jnp.stack([
            load.sum(), (load > 0).sum(), load.max(),
            jnp.sum(jnp.logical_and(state.live, row_pos >= self.window)),
            jnp.sum(-(-seen // state.page_size)),
            jnp.sum(jnp.minimum(seen, self.window))
            * (self.state_layers * token)])
        return (logits, jnp.stack(ks), jnp.stack(vs), ring_k, ring_v,
                counters)
