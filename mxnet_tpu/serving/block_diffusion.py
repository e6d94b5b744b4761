"""A decoder LM that generates by diffusion over blocks, with
grouped-query attention and softmax-routed experts, for
:class:`~mxnet_tpu.serving.DecodeServer` — the third model of the
decode-model contract and the first of its BLOCK form
(``serving.decode``'s docstring), named by what it computes.

**The block** (pre-norm, RMSNorm, no biases, all layers alike):
``h = x + Attn(RMSNorm(x))``, ``y = h + MoE(RMSNorm(h))``; a final
RMSNorm and an untied head.

- *Attention, grouped-query.* ``q = x W_q`` (``num_attention_heads`` x
  ``head_dim``), ``k = x W_k``, ``v = x W_v`` (``num_key_value_heads`` x
  ``head_dim``); ``q`` and ``k`` pass an RMSNorm over each head's
  ``head_dim`` values (one gain a head dimension) and then RoPE over all
  of them (half against half, ``rope_theta``, no scaling); scores ``q.k
  / sqrt(head_dim)``; query head ``i`` reads key/value head ``i //
  (heads / kv heads)``; ``W_o`` back to the residual.
- *The mask is block-causal in every pass*: with block length ``B`` key
  ``j`` is visible to query ``i`` iff ``j // B <= i // B`` — a block
  sees itself whole, both ways, and every earlier block.
- *Routed experts, no shared one.* The router in float32 at "highest":
  ``p = softmax(x W_g)`` over all experts, the ``top_k`` largest,
  renormalised over the chosen (``parallel.moe.route_softmax_topk``);
  each expert a gated MLP (SiLU), dropless (``parallel.moe.expert_ffn``,
  told which experts this chip holds: ``ep=(rank, size)``).

**Generation by diffusion over blocks.** A sequence is ``[prompt,
answer]`` cut into blocks of ``block_length`` from position 0. A block
starts with its unknown positions at ``mask_token_id``. A *denoising
pass* runs the block's positions against everything committed before
the block plus the block's own rows as they stand; at every still-masked
position ``x0 = argmax logits`` (no shift: a position predicts its own
token) and ``conf = softmax(logits)[x0]``; :meth:`unmask` then unmasks
positions by ``remasking_strategy``: ``low_confidence_static`` the
``ceil(B / denoising_steps)`` most confident masked ones,
``low_confidence_dynamic`` every masked one with ``conf >
confidence_threshold`` and, if those are fewer, the static rule's
instead. An unmasked token is never masked again. Once nothing is
masked the block is *committed*: a pass runs its final tokens and their
keys and values are cached. The block that follows is known whole before
that pass runs (all MASK, at known positions) and under the mask above
its first denoising pass needs exactly what the commit writes, so the
server runs the two as ONE pass over ``2 x block_length`` positions a
row (:meth:`decode_block` over two blocks, the second dead for a row
that is still denoising): a block's commit rides with the next block's
first denoising pass (``DecodeServer``'s docstring).

Prefill attention is the plain masked softmax in ``jax.numpy``: a
256-token rung's scores are 8 MB, and the flash kernel would need a new
mask and a 32-over-4 head mapping in code two other models share, for
well under a tenth of a millisecond of a prefill that reads every
expert.

Precision: bf16 matrices (the router's float32), bf16 pool; float32
accumulation, residual stream, norms, softmax, router and confidence.
Parameters are a FLAT ``{name: array}`` dict.
"""
from __future__ import annotations

import math

__all__ = ["BlockDiffusionMoEDecoderLM"]

_STRATEGIES = ("low_confidence_static", "low_confidence_dynamic")


class BlockDiffusionMoEDecoderLM:
    """The block form of the decode-model contract (module docstring).
    Keyword arguments are the keys of the published ``config.json``
    plus the sampler's published inference settings (``block_length``,
    ``denoising_steps``, ``remasking_strategy``,
    ``confidence_threshold``, ``mask_token_id``); ``ep=(rank, size)`` is
    the chip's share of the expert axis, ``use_pallas`` forces the
    Pallas kernels (interpreted off the TPU)."""

    step_counters = ("moe", ("moe_slots", "experts_touched", "max_load"))

    def __init__(self, *, vocab_size, hidden_size, num_hidden_layers,
                 num_attention_heads, num_key_value_heads, head_dim,
                 moe_intermediate_size, num_experts, num_experts_per_tok,
                 rope_theta, block_length, mask_token_id,
                 denoising_steps=None,
                 remasking_strategy="low_confidence_dynamic",
                 confidence_threshold=0.9, norm_topk_prob=True,
                 rms_norm_eps=1e-6, max_position_embeddings=32768,
                 ep=(0, 1), use_pallas=False):
        import numpy as np
        from ..base import MXNetError
        from ..parallel.sharding_rules import held_experts
        self.vocab = int(vocab_size)
        self.d_model = int(hidden_size)
        self.n_layers = int(num_hidden_layers)
        self.n_heads = int(num_attention_heads)
        self.n_kv_heads = int(num_key_value_heads)
        self.head_dim = int(head_dim)
        self.d_expert = int(moe_intermediate_size)
        self.n_experts = int(num_experts)
        self.top_k = int(num_experts_per_tok)
        self.renormalize = bool(norm_topk_prob)
        self.eps = float(rms_norm_eps)
        self.max_len = int(max_position_embeddings)
        self.use_pallas = bool(use_pallas)
        self.held = held_experts(self.n_experts, ep[1], ep[0])
        self.block_length = int(block_length)
        self.mask_token_id = int(mask_token_id)
        self.denoising_steps = int(denoising_steps or block_length)
        self.remasking_strategy = str(remasking_strategy)
        self.confidence_threshold = float(confidence_threshold)
        if self.n_heads % self.n_kv_heads:
            raise MXNetError(
                "BlockDiffusionMoEDecoderLM: %d query heads do not divide "
                "over %d key/value heads" % (self.n_heads, self.n_kv_heads))
        if self.remasking_strategy not in _STRATEGIES:
            raise MXNetError(
                "BlockDiffusionMoEDecoderLM: remasking_strategy %r is "
                "none of %s" % (remasking_strategy, _STRATEGIES))
        if not 0 <= self.mask_token_id < self.vocab:
            raise MXNetError(
                "BlockDiffusionMoEDecoderLM: mask_token_id %d is no row "
                "of a vocabulary of %d" % (self.mask_token_id, self.vocab))
        # positions a pass unmasks at the least
        self.unmask_least = -(-self.block_length // self.denoising_steps)
        self.scale = 1.0 / math.sqrt(self.head_dim)
        self.inv_freq = (float(rope_theta) ** (
            -np.arange(0, self.head_dim, 2, dtype=np.float64)
            / self.head_dim)).astype(np.float32)
        # what the server's pool holds: per-head K and V of the FEW
        # key/value heads (the pool's layout packs them into one row
        # where they would not fill a tile: kvcache._PackedHeadKV)
        self.cache_arrays = (
            ("k", (self.n_kv_heads, self.head_dim), "bfloat16"),
            ("v", (self.n_kv_heads, self.head_dim), "bfloat16"))

    # -- parameters ------------------------------------------------------
    def init_params(self, seed=0):
        """bf16 matrices at ``fan_in ** -0.5`` (the embedding at 1), the
        router's matrix float32, norm gains 1."""
        import jax
        import jax.numpy as jnp
        keys = iter(jax.random.split(jax.random.PRNGKey(seed),
                                     8 * self.n_layers + 4))

        def w(*shape, dtype=jnp.bfloat16, std=None):
            std = shape[-2] ** -0.5 if std is None else std
            return (jax.random.normal(next(keys), shape, jnp.float32)
                    * std).astype(dtype)

        D, Dh = self.d_model, self.head_dim
        Hq, Hkv = self.n_heads, self.n_kv_heads
        E, F = self.held[1] - self.held[0], self.d_expert
        ones = lambda n: jnp.ones((n,), jnp.float32)    # noqa: E731
        p = {"embed": w(self.vocab, D, std=1.0), "out_g": ones(D),
             "head": w(D, self.vocab)}
        for i in range(self.n_layers):
            l = "l%d." % i
            p.update({
                l + "attn_g": ones(D),
                l + "wq": w(D, Hq * Dh), l + "wk": w(D, Hkv * Dh),
                l + "wv": w(D, Hkv * Dh), l + "wo": w(Hq * Dh, D),
                l + "q_g": ones(Dh), l + "k_g": ones(Dh),
                l + "ffn_g": ones(D),
                l + "router_w": w(D, self.n_experts, dtype=jnp.float32),
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
        """``x (..., T, H, head_dim)`` at ``positions (..., T)``: the
        half-split rotation over all of a head, float32."""
        import jax.numpy as jnp
        ang = positions[..., None, None].astype(jnp.float32) \
            * jnp.asarray(self.inv_freq)               # (..., T, 1, d/2)
        cos, sin = jnp.cos(ang), jnp.sin(ang)
        a, b = jnp.split(x.astype(jnp.float32), 2, axis=-1)
        return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)

    def _qkv(self, i, x, p, positions):
        """Normed, rotated queries and keys, and values, of one layer:
        ``x (..., T, D)`` -> ``q (..., T, Hq, Dh)``, ``k``/``v (..., T,
        Hkv, Dh)`` float32."""
        l = "l%d." % i
        lead = x.shape[:-1]
        q = self._mm(x, p[l + "wq"]).reshape(
            lead + (self.n_heads, self.head_dim))
        k = self._mm(x, p[l + "wk"]).reshape(
            lead + (self.n_kv_heads, self.head_dim))
        v = self._mm(x, p[l + "wv"]).reshape(
            lead + (self.n_kv_heads, self.head_dim))
        q = self._rotate(self._rms(q, p[l + "q_g"]), positions)
        k = self._rotate(self._rms(k, p[l + "k_g"]), positions)
        return q, k, v

    def _ffn(self, i, x, p, routed=None, live=None):
        """``x (T, D)`` float32 -> ``(out (T, D), load (E_held,))``;
        ``routed``, a list, is given the router's choice. A position
        that is not ``live (T,)`` chooses no expert: its choice is set
        outside every chip's held range, which ``expert_ffn`` drops and
        ``expert_load`` does not count, and its output is zero."""
        import jax.numpy as jnp
        from ..parallel import moe
        l = "l%d." % i
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
            topi, topw, self.held, force_pallas=self.use_pallas)
        return out, moe.expert_load(topi, self.held)

    def _block_causal(self, q, k, v):
        """Plain masked softmax over one whole sequence: ``q (B, L, Hq,
        Dh)``, ``k``/``v (B, L, Hkv, Dh)`` as the pool will hold them;
        key ``j`` visible to query ``i`` iff ``j // block <= i //
        block``. Float32 scores and softmax."""
        import jax
        import jax.numpy as jnp
        B, L, Hq, Dh = q.shape
        Hkv, f32 = self.n_kv_heads, jnp.float32
        qg = (q * self.scale).astype(k.dtype).astype(f32).reshape(
            B, L, Hkv, Hq // Hkv, Dh)
        s = jnp.einsum("bqhgd,bkhd->bhgqk", qg, k.astype(f32))
        blk = jnp.arange(L) // self.block_length
        s = jnp.where((blk[None, :] <= blk[:, None])[None, None, None],
                      s, -1e30)
        pr = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("bhgqk,bkhd->bqhgd", pr,
                          v.astype(f32)).reshape(B, L, Hq * Dh)

    @staticmethod
    def _counters(loads):
        import jax.numpy as jnp
        load = jnp.stack(loads)                           # (layers, E)
        return jnp.stack([load.sum(), (load > 0).sum(), load.max()])

    # -- the contract ----------------------------------------------------
    def prefill(self, params, tokens):
        """``tokens (B, L)`` under the block-causal mask -> ``(logits
        (B, L, V), k, v (n_layers, B, L, Hkv, Dh))``. The server commits
        the whole blocks of the prompt and reads no logit (a block model's
        prefill emits no token; XLA drops the head)."""
        return self._forward(params, tokens)

    def routing(self, params, tokens):
        """The router's choice at every layer over whole sequences
        ``tokens (B, L)``, on the prefill path: ``(layers, B * L,
        top_k)`` int32 — for a comparison with a reference's choice."""
        import jax.numpy as jnp
        routed = []
        self._forward(params, tokens, routed)
        return jnp.stack(routed)

    def _forward(self, params, tokens, routed=None):
        import jax.numpy as jnp
        p = params
        B, L = tokens.shape
        pos = jnp.broadcast_to(jnp.arange(L, dtype=jnp.int32), (B, L))
        h = p["embed"][tokens].astype(jnp.float32)
        ks, vs = [], []
        for i in range(self.n_layers):
            l = "l%d." % i
            q, k, v = self._qkv(i, self._rms(h, p[l + "attn_g"]), p, pos)
            k, v = k.astype(jnp.bfloat16), v.astype(jnp.bfloat16)
            h = h + self._mm(self._block_causal(q, k, v), p[l + "wo"])
            x = self._rms(h, p[l + "ffn_g"])
            out, _ = self._ffn(i, x.reshape(B * L, -1), p, routed)
            h = h + out.reshape(B, L, -1)
            ks.append(k)
            vs.append(v)
        logits = self._mm(self._rms(h, p["out_g"]), p["head"])
        return logits, jnp.stack(ks), jnp.stack(vs)

    def decode_block(self, params, tokens, positions, attend, live=None,
                     head=None):
        """One pass over ``Q`` consecutive positions a row, a whole number
        of blocks: ``tokens (B, Q)`` at positions ``positions[b] ..
        positions[b] + Q - 1``; ``attend(layer, q (B, Q, Hq, Dh), k_new,
        v_new (B, Q, Hkv, Dh), scale=, force_pallas=)`` attends what each
        position may see (the server's step: ``kvcache._BlockStep``).
        ``live (B, Q)`` bool: a position that is not live costs no expert
        (nothing reads what it computes). ``head (B,)`` int32: only that
        block of the row's ``Q // block_length`` reaches the final norm
        and the head. Returns ``(logits (B, Q, V) float32 — ``(B,
        block_length, V)`` under ``head`` —, k_new, v_new (n_layers, B,
        Q, Hkv, Dh), counters)``."""
        import jax.numpy as jnp
        p = params
        B, Q = tokens.shape
        pos = positions[:, None] + jnp.arange(Q, dtype=jnp.int32)[None]
        if live is not None:
            live = live.reshape(B * Q)
        h = p["embed"][tokens].astype(jnp.float32)
        ks, vs, loads = [], [], []
        for i in range(self.n_layers):
            l = "l%d." % i
            q, k, v = self._qkv(i, self._rms(h, p[l + "attn_g"]), p, pos)
            a = attend(i, q, k, v, scale=self.scale,
                       force_pallas=self.use_pallas)
            h = h + self._mm(a.reshape(B, Q, -1), p[l + "wo"])
            x = self._rms(h, p[l + "ffn_g"])
            out, load = self._ffn(i, x.reshape(B * Q, -1), p, live=live)
            h = h + out.reshape(B, Q, -1)
            ks.append(k)
            vs.append(v)
            loads.append(load)
        if head is not None:
            blocks = h.reshape(B, Q // self.block_length,
                               self.block_length, -1)
            h = jnp.take_along_axis(
                blocks, head[:, None, None, None], axis=1)[:, 0]
        logits = self._mm(self._rms(h, p["out_g"]), p["head"])
        return logits, jnp.stack(ks), jnp.stack(vs), self._counters(loads)

    def unmask(self, logits, tokens, masked):
        """The unmasking rule of one denoising pass, by this model's
        settings: ``logits (R, Q, V)`` float32, ``tokens (R, Q)``,
        ``masked (R, Q)`` bool -> ``(tokens, masked)`` after the pass. At
        a masked position ``x0 = argmax``, ``conf = softmax[x0]``;
        ``low_confidence_static`` unmasks the ``ceil(Q /
        denoising_steps)`` most confident masked positions (ties to the
        lower index), ``low_confidence_dynamic`` every masked position
        over the threshold and, where those are fewer, the static
        rule's. A row with nothing masked is returned as it came."""
        import jax
        import jax.numpy as jnp
        logits = logits.astype(jnp.float32)
        top = jnp.max(logits, axis=-1)
        x0 = jnp.argmax(logits, axis=-1).astype(tokens.dtype)
        conf = 1.0 / jnp.sum(jnp.exp(logits - top[..., None]), axis=-1)
        k = min(self.unmask_least, tokens.shape[-1])
        _, best = jax.lax.top_k(jnp.where(masked, conf, -1.0), k)
        pick = jnp.zeros(masked.shape, bool).at[
            jnp.arange(masked.shape[0])[:, None], best].set(True)
        if self.remasking_strategy == "low_confidence_dynamic":
            high = conf > self.confidence_threshold
            enough = jnp.sum(jnp.logical_and(high, masked), axis=-1,
                             keepdims=True) >= k
            pick = jnp.where(enough, high, pick)
        pick = jnp.logical_and(pick, masked)
        return jnp.where(pick, x0, tokens), \
            jnp.logical_and(masked, jnp.logical_not(pick))
