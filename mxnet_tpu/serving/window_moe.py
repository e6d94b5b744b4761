"""A decoder LM whose attention layers are mostly SLIDING-WINDOW — a
layer keeps the last ``sliding_window`` keys and values of a row, the
same bytes whatever the context — with a full-attention layer every few,
grouped-query heads, and softmax-routed experts: TWO published blocks in
one class, by the published ``model_type``, for
:class:`~mxnet_tpu.serving.DecodeServer`, named by what they compute.

- ``laguna`` (poolside/Laguna-S-2.1): a head COUNT that differs by the
  kind of layer, a per-head output gate, a shared expert beside the
  routed ones, leading dense layers, a scale on the routed weights.
- ``mellum`` (JetBrains/Mellum2-12B-A2.5B-Instruct): ONE head count, no
  gate, no shared expert, no dense layer, no scale; the whole head
  rotated (``partial_rotary_factor`` absent = 1). A key of the other
  block is refused at any value.

It is the second model of the STATE form of the decode-model contract
(``serving.decode``'s docstring): beside the pages of its full-attention
layers it declares ``state_arrays``, a RING of keys and one of values a
row and sliding layer.

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
  The gate (``laguna``): ``g = sigmoid(x W_g)``, one value a head,
  from the layer's normed input; ``o_j <- g_j o_j`` before ``W_o``.
- *Feed-forward.* A layer of ``mlp_only_layers`` is a gated SiLU MLP of
  ``intermediate_size``. Every other: the router in float32 at
  "highest", ``p = softmax(x W_r)`` over all ``num_experts``, the
  ``num_experts_per_tok`` largest, renormalised over the chosen
  (``norm_topk_prob``), times ``moe_routed_scaling_factor``, applied to
  the experts' outputs (``parallel.moe.route_softmax_topk``,
  ``expert_ffn`` told which experts this chip holds: ``ep=(rank,
  size)``); plus one shared expert of ``shared_expert_intermediate_
  size``, added ungated to every token (``laguna``; ``mellum``: every
  layer sparse, ``y = h + sum_{e in top8(softmax(h^ W_r))} w_e W_down,e
  (silu(h^ W_gate,e) * h^ W_up,e)``, ``w`` renormalised over the chosen).

**The sharded form** (``model.sharded_over(mesh)``; ``DecodeServer(model,
params, mesh=mesh)`` asks for it): every layer shared by the ``n`` chips
of ONE mesh axis, no pipeline stage left out.

- *Attention* by key/value head: chip ``r`` holds key/value heads ``r *
  Hkv / n ..`` and their query heads (``W_q``, ``W_k``, ``W_v``, the gate
  by columns, ``W_o`` by rows: :meth:`WindowMoEDecoderLM.param_specs`),
  its pages and rings hold those heads alone (``state_head_dims``;
  ``serving.kvcache.shard_specs``) — a page id names the same page on
  every chip; the ``W_o`` products are summed (``psum``, float32).
- *Experts* by their leading dimension: chip ``r`` holds a contiguous
  block of the held experts, the router is computed WHOLE on every chip
  from the same bits, ``expert_ffn`` computes the chip's experts' part
  for ALL lanes (``held``'s ``lo`` from ``jax.lax.axis_index``), the
  parts are summed (``psum``, float32); what every chip computes alike
  (a shared expert, a dense layer) is added once, behind the sum.
- *The head* by columns: a chip's logits are its columns, the step's
  greedy token the arg-max over the chips' (max, index) pairs; the
  embedding, the router, norm gains, the residual (float32), RoPE
  tables, page tables, positions and tokens are whole on every chip.

Two all-reduces a layer (:meth:`~WindowMoEDecoderLM.exchange_bytes`).
``model.local()`` is ONE chip's view — its head counts, its declaration
of pages and rings — and what the server's programs trace under
``shard_map`` (:meth:`~WindowMoEDecoderLM.on_mesh`); ``init_params``
draws every array INTO its sharding, the same bits as on one chip;
``prefill`` and ``routing`` run over the mesh too. The step counters are
then over the experts, pages and rings a chip HOLDS.

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

Ten lines that serve the sharded form on one host's four chips (run by
``tests/test_mellum_moe.py`` on 4 host devices):

.. code-block:: python

    from mxnet_tpu.parallel.mesh import create_mesh
    from mxnet_tpu.serving import DecodeServer, WindowMoEDecoderLM
    from mxnet_tpu.serving.window_moe import tiny_config
    mesh = create_mesh({"tp": 4})
    model = WindowMoEDecoderLM(**tiny_config("mellum"),
                               dtype="float32").sharded_over(mesh)
    params = model.init_params(seed=0)         # born sharded
    srv = DecodeServer(model, params, mesh=mesh, seq_ladder=[16, 64],
                       max_new_tokens=24, page_size=8, window=4,
                       pool_pages=64, prefix_cache=False)
    print(list(srv.submit([5, 9, 2, 7] * 10).tokens(timeout=60)))
    srv.stop()

Ten lines that serve it on one chip (run by ``tests/test_window_moe.py``)::

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

import functools
import math

__all__ = ["WindowMoEDecoderLM", "tiny_config"]

# the keys whose published value is the only one written here, by the
# published ``model_type``
_PUBLISHED = {
    "laguna": {"attention_bias": False, "tie_word_embeddings": False,
               "gating": "per-head", "decoder_sparse_step": 1,
               "moe_apply_router_weight_on_input": False,
               "moe_router_logit_softcapping": 0},
    "mellum": {"attention_bias": False, "tie_word_embeddings": False,
               "hidden_act": "silu", "max_window_layers": 0,
               "use_sliding_window": True}}
# what only the ``laguna`` block has: a per-head output gate, one head
# count a layer, a shared expert, leading dense layers, a scale on the
# routed experts' weights. ``mellum`` names none of them and is refused
# any: (keyword, the value that says "absent")
_LAGUNA_ONLY = (("shared_expert_intermediate_size", None),
                ("num_attention_heads_per_layer", None),
                ("gating_types", None), ("mlp_only_layers", ()),
                ("moe_routed_scaling_factor", 1.0))
_FULL, _SLIDING = "full_attention", "sliding_attention"
_ROPE_KEYS = {
    "yarn": {"rope_type", "rope_theta", "factor",
             "original_max_position_embeddings", "beta_slow", "beta_fast",
             "attention_factor", "partial_rotary_factor"},
    "default": {"rope_type", "rope_theta", "partial_rotary_factor"}}


def tiny_config(model_type="laguna"):
    """The published keys at a size a CPU test runs. ``laguna``: five
    layers in the published pattern (one leading dense full-attention
    layer, then a period of three sliding layers and a full one), a
    window of 8, 6 and 4 query heads over 2 key/value heads, 8 experts.
    ``mellum``: one period of four (three sliding layers, then a full
    one), 8 query heads over 4 key/value heads — a group a chip on a mesh
    of 4 — 8 experts, no gate, no shared expert, no dense layer."""
    if model_type == "mellum":
        return dict(
            model_type="mellum", vocab_size=96, hidden_size=32,
            intermediate_size=64, num_hidden_layers=4,
            num_attention_heads=8, num_key_value_heads=4, head_dim=16,
            max_position_embeddings=4096, rms_norm_eps=1e-6,
            num_experts=8, num_experts_per_tok=3, moe_intermediate_size=16,
            norm_topk_prob=True, sliding_window=8,
            rope_parameters={
                _FULL: {"rope_type": "yarn", "rope_theta": 500000,
                        "factor": 16, "original_max_position_embeddings": 64,
                        "beta_fast": 32, "beta_slow": 1,
                        "attention_factor": 1.2772588722239782},
                _SLIDING: {"rope_type": "default", "rope_theta": 500000}},
            layer_types=[_SLIDING] * 3 + [_FULL],
            mlp_layer_types=["sparse"] * 4)
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


def _drawn(shape, dtype, std, sharding):
    """``key -> normal(shape) * std`` in ``dtype``; with a ``sharding``
    the normal draw is born sharded (one program a shape and sharding)
    and scaled and cast where it lies, an operation at a time as without
    one: the same bits whatever the mesh."""
    import jax
    import jax.numpy as jnp
    normal = _sharded_normal(shape, sharding) if sharding is not None \
        else functools.partial(jax.random.normal, shape=shape,
                               dtype=jnp.float32)

    def draw(key):
        return (normal(key) * std).astype(dtype)

    return draw


@functools.lru_cache(maxsize=None)
def _sharded_normal(shape, sharding):
    import jax
    import jax.numpy as jnp
    from .. import compile_watch

    def normal(key):
        return jax.random.normal(key, shape, jnp.float32)

    # polymorphic by design: one program a parameter shape
    return compile_watch.jit(normal, "window_moe:init_params", storm=False,
                             out_shardings=sharding)


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
                 sliding_window, rope_parameters, layer_types,
                 shared_expert_intermediate_size=None,
                 num_attention_heads_per_layer=None, mlp_only_layers=(),
                 mlp_layer_types=None, gating_types=None,
                 norm_topk_prob=True, moe_routed_scaling_factor=1.0,
                 rms_norm_eps=1e-6, max_position_embeddings=4096,
                 model_type="laguna", dtype="bfloat16", cache_dtype=None,
                 ep=(0, 1), use_pallas=False, **published):
        from ..base import MXNetError
        from ..parallel.sharding_rules import held_experts
        me = type(self).__name__
        if model_type not in _PUBLISHED:
            raise MXNetError(
                "%s: model_type %r — the published blocks written here "
                "are %s" % (me, model_type, sorted(_PUBLISHED)))
        self.model_type = str(model_type)
        for key, value in published.items():
            if key not in _PUBLISHED[model_type]:
                raise TypeError("%s: unexpected keyword %r" % (me, key))
            if value != _PUBLISHED[model_type][key]:
                raise MXNetError(
                    "%s: %s = %r — only the published %r is written (the "
                    "other form's equations are not settled by the "
                    "config: serving.window_moe's docstring)"
                    % (me, key, value, _PUBLISHED[model_type][key]))
        said = dict(
            shared_expert_intermediate_size=shared_expert_intermediate_size,
            num_attention_heads_per_layer=num_attention_heads_per_layer,
            gating_types=gating_types,
            mlp_only_layers=tuple(mlp_only_layers),
            moe_routed_scaling_factor=float(moe_routed_scaling_factor))
        # the output gate is the ``laguna`` block's alone
        self.gated = model_type == "laguna"
        if self.gated:
            for key in ("shared_expert_intermediate_size",
                        "num_attention_heads_per_layer"):
                if said[key] is None:
                    raise MXNetError("%s: model_type 'laguna' needs %s"
                                     % (me, key))
        for key, absent in () if self.gated else _LAGUNA_ONLY:
            if said[key] != absent:
                raise MXNetError(
                    "%s: %s = %r — the published %r block has no such key "
                    "(one head count, no output gate, no shared expert, "
                    "no dense layer, no scale on the routed weights)"
                    % (me, key, said[key], model_type))
        if not self.gated:
            shared_expert_intermediate_size = 0
            num_attention_heads_per_layer = \
                [int(num_attention_heads)] * int(num_hidden_layers)
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
        # no mesh: one chip runs the whole of what it holds
        self.mesh = self.axis = self.on_chip = None
        self.shards = 1
        self._declare()

    def _declare(self):
        """What a row carries, from the key/value heads held (all of
        them; under a mesh one chip's)."""
        width = self.n_kv_heads * self.head_dim
        self.cache_arrays = (
            ("k", (self.n_kv_heads, self.head_dim), self.cache_dtype),
            ("v", (self.n_kv_heads, self.head_dim), self.cache_dtype))
        self.state_arrays = (
            ("ring_k", (self.window, width), self.cache_dtype),
            ("ring_v", (self.window, width), self.cache_dtype))

    # of a ring's row shape ``(W, heads side by side)``, the dimension a
    # mesh splits by key/value head (``serving.kvcache.shard_axes``)
    state_head_dims = (1, 1)

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

    # -- over a mesh -------------------------------------------------------
    def sharded_over(self, mesh, axis=None):
        """This model bound to ONE axis of ``mesh`` (its only one where
        ``axis`` is not given): every layer shared by the axis's chips —
        key/value heads, their query heads and the held experts in
        contiguous blocks, the head by columns (:meth:`param_specs`) —
        and :meth:`local`, one chip's view, what ``DecodeServer`` runs
        under ``shard_map``. The model itself stays the whole one: its
        declaration, ``init_params`` (drawn into the shardings),
        ``prefill`` and ``routing`` (under ``shard_map``)."""
        import copy
        from ..base import MXNetError
        me = type(self).__name__
        if axis is None:
            if len(mesh.axis_names) != 1:
                raise MXNetError(
                    "%s: mesh axes %s — say which ONE shares a layer"
                    % (me, mesh.axis_names))
            axis = mesh.axis_names[0]
        if (self.mesh, self.axis) == (mesh, axis):
            return self
        if self.mesh is not None or self.on_chip:
            raise MXNetError("%s: already bound to a mesh" % me)
        n = int(mesh.shape[axis])
        for what, count in (("key/value heads", self.n_kv_heads),
                            ("held experts", self.held[1] - self.held[0]),
                            ("vocabulary rows", self.vocab)):
            if count % n:
                raise MXNetError(
                    "%s: %d %s do not divide over the %d chips of mesh "
                    "axis %r" % (me, count, what, n, axis))
        bound = copy.copy(self)
        bound.mesh, bound.axis, bound.shards = mesh, axis, n
        return bound

    def local(self):
        """One chip's view of a model bound to a mesh: the key/value
        heads, query heads, pages and rings of ONE chip, the experts it
        holds by its index on the axis; its products through ``W_o`` and
        through its experts are summed over the axis (``psum``), its
        logits are its columns of the head. To be run under
        :meth:`on_mesh`."""
        import copy
        chip = copy.copy(self)
        chip.mesh, chip.on_chip = None, True
        chip.n_kv_heads = self.n_kv_heads // self.shards
        chip.heads = tuple(h // self.shards for h in self.heads)
        chip._declare()
        return chip

    def param_specs(self):
        """``{name: PartitionSpec}``, the model's declaration of where
        its parameters lie on the mesh axis: ``W_q``, ``W_k``, ``W_v``,
        the gate and the head by columns, ``W_o`` by rows, an expert
        stack by its leading dimension; the embedding, the router, norm
        gains and whatever every chip computes alike (a shared expert, a
        dense layer) whole on every chip."""
        from jax.sharding import PartitionSpec as P
        ax, out = self.axis, {}
        for name in self._param_shapes():
            leaf = name.rsplit(".", 1)[-1]
            if ".experts." in name:
                out[name] = P(ax, None, None)
            elif name == "head" or leaf in ("wq", "wk", "wv", "wg"):
                out[name] = P(None, ax)
            elif leaf == "wo":
                out[name] = P(ax, None)
            else:
                out[name] = P()
        return out

    def param_shardings(self):
        from jax.sharding import NamedSharding
        return {name: NamedSharding(self.mesh, spec)
                for name, spec in self.param_specs().items()}

    def on_mesh(self, fn, in_specs, out_specs):
        """``fn(params, *args)`` under ``shard_map`` over the model's
        mesh: the parameters by :meth:`param_specs`, the rest as said."""
        import jax
        return jax.shard_map(
            fn, mesh=self.mesh, in_specs=(self.param_specs(), *in_specs),
            out_specs=out_specs, check_vma=False)

    def exchange_bytes(self, lanes):
        """What ONE chip hands the all-reduces of a step of ``lanes``
        lanes: a float32 ``(lanes, hidden)`` array behind every
        attention layer and every expert layer."""
        return (self.n_layers + self.n_moe_layers) * int(lanes) \
            * self.d_model * 4

    # -- parameters --------------------------------------------------------
    def _param_shapes(self):
        """``{name: (shape, dtype, deviation or None for a gain)}`` in the
        order the keys are drawn."""
        import jax.numpy as jnp
        dt = jnp.dtype(self.dtype)
        D, Dh, Hkv = self.d_model, self.head_dim, self.n_kv_heads
        E = self.held[1] - self.held[0]
        out = {}

        def w(name, *shape, dtype=dt, std=None):
            out[name] = (shape, dtype,
                         shape[-2] ** -0.5 if std is None else std)

        def ones(name, n):
            out[name] = ((n,), jnp.dtype(jnp.float32), None)

        w("embed", self.vocab, D, std=1.0)
        ones("out_g", D)
        w("head", D, self.vocab)
        for i, H in enumerate(self.heads):
            l = "l%d." % i
            ones(l + "attn_g", D)
            w(l + "wq", D, H * Dh)
            w(l + "wk", D, Hkv * Dh)
            w(l + "wv", D, Hkv * Dh)
            if self.gated:
                w(l + "wg", D, H)
            w(l + "wo", H * Dh, D)
            ones(l + "ffn_g", D)
            if self.dense[i]:
                w(l + "w_gate", D, self.d_ff)
                w(l + "w_up", D, self.d_ff)
                w(l + "w_down", self.d_ff, D)
                continue
            F, Fs = self.d_expert, self.d_shared
            w(l + "router_w", D, self.n_experts, dtype=jnp.float32)
            w(l + "experts.w_gate", E, D, F)
            w(l + "experts.w_up", E, D, F)
            w(l + "experts.w_down", E, F, D)
            if Fs:
                w(l + "shared.w_gate", D, Fs)
                w(l + "shared.w_up", D, Fs)
                w(l + "shared.w_down", Fs, D)
        return out

    def init_params(self, seed=0):
        """Matrices in ``dtype`` at ``fan_in ** -0.5`` (the embedding at
        1), the router's matrix float32, norm gains 1. Over a mesh every
        array is drawn INTO its sharding (:meth:`param_specs`: a layer's
        experts never lie whole on one chip), the same values as on
        one."""
        import jax
        import jax.numpy as jnp
        keys = iter(jax.random.split(jax.random.PRNGKey(seed),
                                     16 * self.n_layers + 4))
        where = self.param_shardings() if self.mesh is not None else {}

        def draw(name, shape, dtype, std):
            if std is None:
                return jnp.ones(shape, jnp.float32, device=where.get(name))
            return _drawn(shape, jnp.dtype(dtype).name, std,
                          where.get(name))(next(keys))

        return {name: draw(name, *spec)
                for name, spec in self._param_shapes().items()}

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
        g = jax.nn.sigmoid(self._mm(x, p[l + "wg"])) if self.gated \
            else None
        return self._rotate(kind, q, positions), \
            self._rotate(kind, k, positions).astype(cache), \
            v.astype(cache), g

    def _out(self, i, a, g, p):
        """The attention layer's increment of the residual: the heads'
        outputs ``a (..., H_i, Dh)``, gated where the block has a gate,
        through ``W_o`` — under a mesh this chip's heads through its
        rows of ``W_o``, summed over the chips."""
        import jax.numpy as jnp
        a = a.astype(jnp.float32)
        if g is not None:
            a = a * g[..., None]
        return self._sum(self._mm(a.reshape(a.shape[:-2] + (-1,)),
                                  p["l%d.wo" % i]))

    def _sum(self, x):
        """``x``, or under a mesh the sum of the chips' parts (float32):
        every chip is handed the same bits."""
        import jax
        return x if self.on_chip is None else jax.lax.psum(x, self.axis)

    def _held(self):
        """``(lo, hi)`` of the experts this chip holds: ``held`` as
        built, or under a mesh this chip's share of them, ``lo`` from
        the chip's index on the axis (traced: one program for all)."""
        import jax
        if self.on_chip is None:
            return self.held
        n = (self.held[1] - self.held[0]) // self.shards
        lo = self.held[0] + jax.lax.axis_index(self.axis) * n
        return lo, lo + n

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
        held = self._held()
        experts = {n: p[l + "experts." + n]
                   for n in ("w_gate", "w_up", "w_down")}
        # under a mesh: this chip's experts' part for ALL lanes, the
        # parts summed; what every chip computes alike (the shared
        # expert) is added once, behind the sum
        out = self._sum(moe.expert_ffn(
            x, experts, topi, topw * self.route_scale, held,
            force_pallas=self.use_pallas))
        if self.d_shared:
            out = self._gated(x, p, l + "shared.") + out
        return out, moe.expert_load(topi, held,
                                    experts["w_gate"].shape[0])

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
        length costs no expert (what it computes is nobody's). Over a
        mesh the same, every result sharded as the server's are: the
        logits by columns, keys, values and rings by key/value head."""
        if self.mesh is None:
            return self._forward(params, tokens, lengths)
        from jax.sharding import PartitionSpec as P
        ax = self.axis
        heads, lanes = P(None, None, None, ax, None), P(None, None, None, ax)
        return self.on_mesh(
            self.local()._forward, (P(), P()),
            (P(None, None, ax), heads, heads, lanes, lanes))(
                params, tokens, lengths)

    def routing(self, params, tokens):
        """The router's choice at every expert layer over whole sequences
        ``tokens (B, L)``, on the prefill path: ``(expert layers, B * L,
        top_k)`` int32 — for a comparison with a reference's choice."""
        import jax.numpy as jnp

        def choices(model, params, tokens):
            routed = []
            model._forward(params, tokens,
                           jnp.full((tokens.shape[0],), tokens.shape[1],
                                    jnp.int32), routed)
            return jnp.stack(routed)

        if self.mesh is None:
            return choices(self, params, tokens)
        from jax.sharding import PartitionSpec as P
        # every chip routes alike: chip 0's choice is everyone's
        return self.on_mesh(functools.partial(choices, self.local()),
                            (P(),), P())(params, tokens)

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
            h = h + self._out(i, a, g, p)
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
            h = h + self._out(i, a, g, p)
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
