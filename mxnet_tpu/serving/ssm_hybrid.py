"""A decoder LM whose layers are mostly STATE-SPACE — a selective scan
(Mamba-1, arXiv:2312.00752) whose state is a fixed array a row — with a
full-attention layer every ``attn_layer_period``-th, over a dense gated
MLP: the published ``jamba`` block with one expert
(ai21labs/AI21-Jamba2-3B), for :class:`~mxnet_tpu.serving.DecodeServer`,
named by what it computes. It is the third model of the STATE form of
the decode-model contract (``serving.decode``'s docstring): beside the
pages of its attention layers it declares ``state_arrays``, which the
server holds for each row of its window.

``x`` is the RMS-normed residual, ``D = hidden_size``, ``E = mamba_expand
D``, ``N = mamba_d_state``, ``R = mamba_dt_rank``, ``K = mamba_d_conv``.

**A state-space layer** (layer ``i`` with ``i % attn_layer_period !=
attn_layer_offset``):

    [u~, z] = x W_in                          D -> 2E, no bias
    u       = SiLU(conv_K(u~) + b_conv)       causal, depthwise, bias
    [d~, B, C] = u W_x                        E -> R + 2N, no bias
    d~, B, C <- RMSNorm_R(d~), RMSNorm_N(B), RMSNorm_N(C)     gains, eps
    delta   = softplus(d~ W_dt + b_dt)        R -> E, bias; a channel
    A       = -exp(A_log)                     (N, E)
    h_t     = exp(delta_t (x) A) . h_{t-1} + (delta_t u_t) (x) B_t    float32
    y_t     = h_t C_t + D_skip . u_t          E
    out     = (y . SiLU(z)) W_out             E -> D, no bias

What a row carries from token to token is ``h`` (``(N, E)`` float32,
channel-minor: ``parallel.selective_scan`` says why) and the last ``K -
1`` rows of ``u~`` — ``state_arrays = (("h", (N, E), "float32"),
("conv", ((K - 1) E,), dtype))``, whatever the context. Decode:
:func:`parallel.selective_scan.ssm_conv_step` then
:func:`~parallel.selective_scan.ssm_step` over the layer's plane of each,
in place; a prompt: :func:`parallel.selective_scan.ssm_chunk`.
**:meth:`~SSMHybridDecoderLM.decode` runs in SLOT order**: a step's rows
are a permutation of all the window's (``RowState.slots``), so the rows'
lanes are brought into the order their state lies in ONCE, at the
embedding (the token ids are permuted, not the residual), every
lane-wise layer and every state-space mixer runs there — both kernels
walk the arrays in aligned blocks, nothing gathered or scattered a layer
— and the lanes go back to the step's order only where a row's identity
is the server's: around an attention layer's ``attend`` (its page tables
are by row), for the head, and in the keys and values returned. A
chunk's lanes, behind the rows, never move. Positions at or past
a prompt's true length leave both untouched (``delta = 0``; the
convolution's rows are taken at the true end), and so does a dead row
of the window. ``u~`` is rounded to the parameters' dtype before the
convolution, in prefill, chunk and decode alike, because that is what
the state holds of the rows before. ``A_log`` is held ``(N, E)`` (the
published array transposed: the weights are seeded, and the kernels want
the channels in the lanes).

**An attention layer** (``i % attn_layer_period == attn_layer_offset``):
``q = x W_q`` (``num_attention_heads`` x ``head_dim``), ``k, v = x W_k,
x W_v`` (``num_key_value_heads`` x ``head_dim``), no bias, NO position
encoding of any kind, causal softmax at scale ``head_dim ** -0.5``,
``out = concat(heads) W_o``. Its keys and values lie in cache layer
``(i - offset) // period`` of the server's pages — ``cache_layers``
counts those layers, NOT ``n_layers``; a state-space layer's state lies
in state layer ``i -`` the attention layers before it.

**Every layer**: ``h += mixer(RMSNorm(h))``; ``h += MLP(RMSNorm(h))``
with ``MLP(x) = (SiLU(x W_gate) . x W_up) W_down``. Final RMSNorm; logits
``= h E^T`` with the embedding's own matrix (``tie_word_embeddings``):
ONE array in the parameters, read by the gather and by the head.

**A prompt rides the step in chunks** (``chunk_lanes = True``: what
``DecodeServer`` observes to build the mixed step programs and no
prefill program; :meth:`prefill` is the oracle of the tests): behind the
``B`` rows of a step, ``C`` lanes that are consecutive positions ``start
.. start + C - 1`` of ONE request's prompt, the first ``n`` of them live.
The lanes share the rows' matrices and a state-space layer does for them
what the prefill does for a whole prompt, but FROM THE ROW'S STATE: the
convolution's first ``K - 1`` inputs are the row's ``conv`` rows and the
scan starts from the row's ``h`` — zeros for both where ``start`` is 0,
whatever the slot's last tenant left — and the state after lane ``n -
1``, with the rows before position ``start + n``, is written back into
the request's row, which is no live row of that step. An attention
layer's lanes go through the layout's split ``attend``; only the rows
and the chunk's last live lane reach the head.

**What the published keys do not settle** (``assumed``; the
configuration's file says each again, with its reason): the layer order
(the ``jamba`` model type's convention above); ``head_dim = hidden_size
/ num_attention_heads`` where none is given; ``num_experts`` 1 makes
``expert_layer_period`` / ``offset`` moot — a softmax over one logit is
1, every feed-forward is the dense MLP and there is no router; ``h``
kept in float32 between tokens; ``A_log``, ``b_dt``, ``D_skip`` and
``W_dt`` drawn by Mamba's published initialisation
(:meth:`SSMHybridDecoderLM.init_params`), the embedding at the model
type's ``initializer_range`` 0.02 — tied to the head, an embedding of
unit deviation would put a token's own logit ten deviations above the
rest and every served token would be its predecessor.
``num_logits_to_keep``, ``use_mamba_kernels``, ``expert_layer_period`` /
``offset`` and ``max_position_embeddings`` are read as they stand; none
changes a served token. Refused with a typed error, never guessed:
``num_experts`` or ``num_experts_per_tok`` above 1, a ``sliding_window``,
a ``mamba_d_conv`` under 2 (no row for the state to hold), and any other
value of a key in :data:`_PUBLISHED`.

Precision: matrices, pages and convolution rows in ``dtype`` (bfloat16);
float32 accumulation, residual, norms, ``delta``, ``exp``, the
recurrence and softmax. Parameters are a FLAT ``{name: array}`` dict.

Ten lines that serve it on one chip (run by ``tests/test_ssm_hybrid.py``)::

    from mxnet_tpu.serving import DecodeServer, SSMHybridDecoderLM
    from mxnet_tpu.serving.ssm_hybrid import tiny_config
    model = SSMHybridDecoderLM(**tiny_config(), dtype="float32")
    params = model.init_params(seed=0)
    srv = DecodeServer(model, params, seq_ladder=[16, 32],
                       max_new_tokens=24, page_size=8, window=4,
                       pool_pages=64, prefix_cache=False)
    req = srv.submit([5, 9, 2, 7] * 5, max_new_tokens=24)
    print(list(req.tokens(timeout=60)))        # 24 token ids
    srv.stop()
"""
from __future__ import annotations

import math

__all__ = ["SSMHybridDecoderLM", "DT_RANGE", "EMBED_STD", "tiny_config"]

# Mamba's published initialisation of the step: ``b_dt`` is the inverse
# softplus of a step drawn log-uniformly in this range, so that with
# ``A = -(1..N)`` a step's decay ``exp(delta A)`` spans about 0.2 (state
# 16 of a fast channel) to 0.999 (state 1 of a slow one)
DT_RANGE = (1e-3, 1e-1)
# the ``jamba`` model type's ``initializer_range``
EMBED_STD = 0.02

# the keys whose published value is the only one written here
_PUBLISHED = {"model_type": "jamba", "hidden_act": "silu",
              "mamba_proj_bias": False, "mamba_conv_bias": True,
              "tie_word_embeddings": True}
# read as they stand: none changes a served token
_IGNORED = ("num_logits_to_keep", "use_mamba_kernels",
            "expert_layer_period", "expert_layer_offset")


def tiny_config():
    """The published keys at a size a CPU test runs: five layers in a
    period of four whose second attends (layer 1; layers 0, 2, 3 and 4
    are state-space), 4 query heads over 1 key/value head, 8 states a
    channel, a step rank of 4."""
    return dict(
        model_type="jamba", vocab_size=96, hidden_size=32,
        intermediate_size=64, num_hidden_layers=5, num_attention_heads=4,
        num_key_value_heads=1, attn_layer_period=4, attn_layer_offset=1,
        mamba_d_state=8, mamba_d_conv=4, mamba_expand=2, mamba_dt_rank=4,
        mamba_conv_bias=True, mamba_proj_bias=False, num_experts=1,
        num_experts_per_tok=1, rms_norm_eps=1e-6, sliding_window=None,
        tie_word_embeddings=True, max_position_embeddings=4096)


class SSMHybridDecoderLM:
    """The decode-model contract, STATE form, for the block of the module
    docstring. Keyword arguments are the keys of the published
    ``config.json``; ``head_dim`` where the config gives none is
    ``hidden_size / num_attention_heads``; ``use_pallas`` forces the
    Pallas kernels (interpreted off the TPU), ``dtype`` the matrices',
    the pages' and the convolution rows' (``"float32"`` for a test that
    compares logits)."""

    # ``decode`` takes ``head``, ``live`` and ``chunk``: a chunk of a
    # selective scan is the same recurrence from the row's state
    # (``ssm_chunk(..., state=)``), so a prompt may ride the step in chunks
    chunk_lanes = True

    def __init__(self, *, vocab_size, hidden_size, intermediate_size,
                 num_hidden_layers, num_attention_heads,
                 num_key_value_heads, attn_layer_period, attn_layer_offset,
                 mamba_d_state, mamba_d_conv, mamba_expand, mamba_dt_rank,
                 head_dim=None, num_experts=1, num_experts_per_tok=1,
                 sliding_window=None, rms_norm_eps=1e-6,
                 max_position_embeddings=4096, dtype="bfloat16",
                 use_pallas=False, **published):
        from ..base import MXNetError
        me = type(self).__name__
        for key, value in published.items():
            if key in _IGNORED:
                continue
            if key not in _PUBLISHED:
                raise TypeError("%s: unexpected keyword %r" % (me, key))
            if value != _PUBLISHED[key]:
                raise MXNetError(
                    "%s: %s = %r — only the published %r is written (the "
                    "other form's equations are not settled by the "
                    "config: serving.ssm_hybrid's docstring)"
                    % (me, key, value, _PUBLISHED[key]))
        if int(num_experts) > 1 or int(num_experts_per_tok) > 1:
            raise MXNetError(
                "%s: num_experts %d, num_experts_per_tok %d — only the "
                "one-expert block is written: its router is a softmax "
                "over one logit and every feed-forward the dense MLP; "
                "routed experts are serving.window_moe's"
                % (me, int(num_experts), int(num_experts_per_tok)))
        if sliding_window is not None:
            raise MXNetError(
                "%s: sliding_window %r — the attention layers here "
                "attend the whole context from pages; a window's ring a "
                "row is serving.window_moe's" % (me, sliding_window))
        if int(mamba_d_conv) < 2:
            raise MXNetError(
                "%s: mamba_d_conv %d — the state holds the kernel's last "
                "mamba_d_conv - 1 inputs a row, and a kernel of one holds "
                "none" % (me, int(mamba_d_conv)))
        n = self.n_layers = int(num_hidden_layers)
        self.vocab = int(vocab_size)
        self.d_model = int(hidden_size)
        self.d_ff = int(intermediate_size)
        self.n_heads = int(num_attention_heads)
        self.n_kv_heads = int(num_key_value_heads)
        if head_dim is None:
            if self.d_model % self.n_heads:
                raise MXNetError(
                    "%s: no head_dim, and hidden_size %d does not divide "
                    "over %d heads" % (me, self.d_model, self.n_heads))
            head_dim = self.d_model // self.n_heads
        self.head_dim = int(head_dim)
        if self.n_heads % self.n_kv_heads:
            raise MXNetError(
                "%s: %d query heads do not divide over %d key/value heads"
                % (me, self.n_heads, self.n_kv_heads))
        self.period, self.offset = int(attn_layer_period), \
            int(attn_layer_offset)
        self.d_inner = int(mamba_expand) * self.d_model
        self.d_state = int(mamba_d_state)
        self.dt_rank = int(mamba_dt_rank)
        self.conv = int(mamba_d_conv)
        self.eps = float(rms_norm_eps)
        self.max_len = int(max_position_embeddings)
        self.use_pallas = bool(use_pallas)
        self.dtype = str(dtype)
        self.scale = 1.0 / math.sqrt(self.head_dim)
        self.kinds = tuple(i % self.period == self.offset for i in range(n))
        self.cache_layers = sum(self.kinds)
        self.state_layers = n - self.cache_layers
        if not (self.cache_layers and self.state_layers):
            raise MXNetError(
                "%s: %d attention and %d state-space layers — the state "
                "form of the server needs a layer of each kind (pages for "
                "the first, fixed state a row for the second)"
                % (me, self.cache_layers, self.state_layers))
        self.cache_arrays = (
            ("k", (self.n_kv_heads, self.head_dim), self.dtype),
            ("v", (self.n_kv_heads, self.head_dim), self.dtype))
        self.state_arrays = (
            ("h", (self.d_state, self.d_inner), "float32"),
            ("conv", ((self.conv - 1) * self.d_inner,), self.dtype))

    # -- which layer is which ----------------------------------------------
    def cache_layer(self, i):
        """The cache layer of layer ``i``, or None for a state-space
        one."""
        return sum(self.kinds[:i]) if self.kinds[i] else None

    def state_layer(self, i):
        return i - sum(self.kinds[:i])

    # -- parameters --------------------------------------------------------
    def _param_shapes(self):
        """``{name: (shape, dtype, how)}`` in the order the keys are
        drawn; ``how`` is a matrix's deviation or the name of a draw of
        :meth:`init_params`."""
        import jax.numpy as jnp
        dt, f32 = jnp.dtype(self.dtype), jnp.dtype(jnp.float32)
        D, E, N, R, F = (self.d_model, self.d_inner, self.d_state,
                         self.dt_rank, self.d_ff)
        Dh = self.head_dim
        out = {}

        def w(name, *shape, dtype=dt, std=None):
            out[name] = (shape, dtype,
                         shape[-2] ** -0.5 if std is None else std)

        def vec(name, *shape, how="one"):
            out[name] = (shape, f32, how)

        w("embed", self.vocab, D, std=EMBED_STD)
        vec("out_g", D)
        for i, attends in enumerate(self.kinds):
            l = "l%d." % i
            vec(l + "mix_g", D)
            if attends:
                w(l + "wq", D, self.n_heads * Dh)
                w(l + "wk", D, self.n_kv_heads * Dh)
                w(l + "wv", D, self.n_kv_heads * Dh)
                w(l + "wo", self.n_heads * Dh, D)
            else:
                w(l + "win", D, 2 * E)
                w(l + "conv_w", self.conv, E, dtype=f32)
                vec(l + "conv_b", E, how="uniform_fan_in")
                w(l + "wx", E, R + 2 * N)
                vec(l + "dt_g", R)
                vec(l + "b_g", N)
                vec(l + "c_g", N)
                out[l + "wdt"] = ((R, E), dt, "uniform_fan_in")
                vec(l + "dt_b", E, how="step")
                vec(l + "A_log", N, E, how="log_states")
                vec(l + "D", E)
                w(l + "wout", E, D)
            vec(l + "ffn_g", D)
            w(l + "w_gate", D, F)
            w(l + "w_up", D, F)
            w(l + "w_down", F, D)
        return out

    def init_params(self, seed=0):
        """Matrices in ``dtype``, normal at ``fan_in ** -0.5`` (the
        embedding at :data:`EMBED_STD`); the rest by Mamba's published
        initialisation: a gain (``*_g``) and ``D_skip`` 1; ``A_log =
        log(1..N)`` a channel; ``b_dt`` the inverse softplus of a step
        drawn log-uniformly in :data:`DT_RANGE`; ``W_dt`` uniform in
        ``+-R ** -0.5`` and ``b_conv`` in ``+-K ** -0.5``."""
        import jax
        import jax.numpy as jnp
        shapes = self._param_shapes()
        keys = iter(jax.random.split(jax.random.PRNGKey(seed), len(shapes)))
        out = {}
        for name, (shape, dtype, how) in shapes.items():
            k = next(keys)
            if how == "one":
                a = jnp.ones(shape, jnp.float32)
            elif how == "log_states":
                a = jnp.broadcast_to(jnp.log(jnp.arange(
                    1, shape[0] + 1, dtype=jnp.float32))[:, None], shape)
            elif how == "step":
                lo, hi = (math.log(v) for v in DT_RANGE)
                step = jnp.exp(jax.random.uniform(k, shape, jnp.float32,
                                                  lo, hi))
                a = step + jnp.log(-jnp.expm1(-step))
            elif how == "uniform_fan_in":
                # W_dt by its rank, b_conv by the kernel's taps
                fan = shape[0] if len(shape) == 2 else self.conv
                a = jax.random.uniform(k, shape, jnp.float32,
                                       -fan ** -0.5, fan ** -0.5)
            else:
                a = jax.random.normal(k, shape, jnp.float32) * how
            out[name] = a.astype(dtype)
        return out

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

    def _mlp(self, i, h, p):
        import jax
        l = "l%d." % i
        x = self._rms(h, p[l + "ffn_g"])
        return self._mm(jax.nn.silu(self._mm(x, p[l + "w_gate"]))
                        * self._mm(x, p[l + "w_up"]), p[l + "w_down"])

    def _logits(self, h, p):
        """``RMSNorm(h) E^T``: the head is the embedding's own matrix."""
        import jax
        import jax.numpy as jnp
        x = self._rms(h, p["out_g"]).astype(p["embed"].dtype)
        return jax.lax.dot_general(
            x, p["embed"], (((x.ndim - 1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)

    def _qkv(self, i, x, p):
        """``q (..., H, Dh)`` float32 and ``k``, ``v (..., Hkv, Dh)`` in
        the pages' dtype: no position encoding."""
        l = "l%d." % i
        lead, Dh = x.shape[:-1], self.head_dim
        q = self._mm(x, p[l + "wq"]).reshape(lead + (self.n_heads, Dh))
        k = self._mm(x, p[l + "wk"]).reshape(lead + (self.n_kv_heads, Dh))
        v = self._mm(x, p[l + "wv"]).reshape(lead + (self.n_kv_heads, Dh))
        return q, k.astype(self.dtype), v.astype(self.dtype)

    def _attn_out(self, i, a, p):
        import jax.numpy as jnp
        a = a.astype(jnp.float32)
        return self._mm(a.reshape(a.shape[:-2] + (-1,)), p["l%d.wo" % i])

    def _split_in(self, i, x, p):
        """``(u~ in the parameters' dtype, z float32)`` of ``x``."""
        uz = self._mm(x, p["l%d.win" % i])
        E = self.d_inner
        return uz[..., :E].astype(self.dtype), uz[..., E:]

    def _scan_inputs(self, i, y, p, live):
        """The convolution's output ``y (..., E)`` as the scan's ``(u,
        delta, B, C)``; ``delta`` 0 where not ``live (...)``, which
        leaves the state as it was."""
        import jax
        import jax.numpy as jnp
        l = "l%d." % i
        N, R = self.d_state, self.dt_rank
        u = jax.nn.silu(y + p[l + "conv_b"])
        dbc = self._mm(u, p[l + "wx"])
        d = self._rms(dbc[..., :R], p[l + "dt_g"])
        b = self._rms(dbc[..., R:R + N], p[l + "b_g"])
        c = self._rms(dbc[..., R + N:], p[l + "c_g"])
        delta = jax.nn.softplus(self._mm(d, p[l + "wdt"]) + p[l + "dt_b"])
        return u, jnp.where(live[..., None], delta, 0.0), b, c

    def _mix_out(self, i, y, u, z, p):
        """``((y + D_skip . u) . SiLU(z)) W_out``."""
        import jax
        l = "l%d." % i
        return self._mm((y + p[l + "D"] * u) * jax.nn.silu(z),
                        p[l + "wout"])

    def _ssm_prefill(self, i, h, p, lengths):
        """Layer ``i`` over whole sequences ``h (B, L, D)`` of true
        lengths ``lengths (B,)``: ``(increment, h (B, N, E), conv rows
        (B, (K - 1) E))`` — both as they stand after position ``lengths -
        1``. The oracle's form: one token at a time, no kernel."""
        import jax
        import jax.numpy as jnp
        from ..parallel.selective_scan import _jnp_chunk
        l = "l%d." % i
        B, L = h.shape[:2]
        K = self.conv
        x = self._rms(h, p[l + "mix_g"])
        raw, z = self._split_in(i, x, p)
        padded = jnp.pad(raw, ((0, 0), (K - 1, 0), (0, 0)))
        wide = padded.astype(jnp.float32)
        with jax.named_scope("mx_ssm_conv"):
            y = sum(p[l + "conv_w"][j] * wide[:, j:j + L] for j in range(K))
        # the rows before position ``lengths``: zeros where the prompt
        # is shorter than the kernel
        rows = jax.vmap(lambda a, n: jax.lax.dynamic_slice_in_dim(
            a, n, K - 1, axis=0))(padded, lengths)
        live = jnp.arange(L)[None] < lengths[:, None]
        u, delta, b, c = self._scan_inputs(i, y, p, live)
        a = -jnp.exp(p[l + "A_log"])
        zeros = jnp.zeros((B,) + a.shape, jnp.float32)
        y, S = jax.vmap(_jnp_chunk, in_axes=(0, 0, 0, 0, None, 0))(
            u, delta, b, c, a, zeros)
        return self._mix_out(i, y, u, z, p), S, rows.reshape(B, -1)

    def _ssm_step(self, i, h, p, arrays, live, chunk=None):
        """Layer ``i`` over one token a row, in SLOT order: lane ``s`` of
        ``h (B, D)`` and of ``live (B,)`` is the row whose state lies in
        row ``s`` of the state arrays, so both kernels walk the arrays
        themselves in aligned blocks. Returns ``(increment, (h, conv))``,
        the state arrays updated in place.

        On a MIXED step ``h`` is ``(B + C, D)``: behind the rows, the
        ``C`` lanes of ONE request's chunk, ``chunk = (its row of the
        state arrays, the first lane's position, the live lanes)`` and
        ``live (B + C,)``. The lanes share the rows' matrices — one
        stream of ``win``, ``wx``, ``wdt``, ``wout`` — and do what
        :meth:`_ssm_prefill` does for a whole prompt, FROM THE ROW'S
        STATE (:meth:`_chunk_conv`, :meth:`_chunk_scan`). The request is
        no live row of the step: the rows' kernels pass its row through,
        and the chunk's lanes alone write it, behind them — ONE row by a
        scalar index each, a slice's update and no scatter (which would
        widen a 16-bit array to float32, whole)."""
        import jax
        import jax.numpy as jnp
        from ..parallel.selective_scan import ssm_conv_step, ssm_step
        l = "l%d." % i
        h_all, conv_all = arrays
        j = self.state_layer(i)
        B = h_all.shape[1]
        x = self._rms(h, p[l + "mix_g"])
        raw, z = self._split_in(i, x, p)
        with jax.named_scope("mx_ssm_conv"):
            y, conv_all = ssm_conv_step(conv_all, j, raw[:B], live[:B],
                                        p[l + "conv_w"],
                                        force_pallas=self.use_pallas)
            if chunk is not None:
                tail, rows = self._chunk_conv(raw[B:], p[l + "conv_w"],
                                              conv_all[j, chunk[0]], chunk)
                y = jnp.concatenate([y, tail])
                conv_all = conv_all.at[j, chunk[0]].set(rows)
        u, delta, b, c = self._scan_inputs(i, y, p, live)
        a = -jnp.exp(p[l + "A_log"])
        y, h_all = ssm_step(h_all, j, u[:B], delta[:B], b[:B], c[:B], a,
                            force_pallas=self.use_pallas)
        if chunk is not None:
            tail, h_all = self._chunk_scan(
                j, (u[B:], delta[B:], b[B:], c[B:], a), h_all, chunk)
            y = jnp.concatenate([y, tail])
        return self._mix_out(i, y, u, z, p), (h_all, conv_all)

    def _chunk_conv(self, raw, conv_w, held, chunk):
        """The convolution over a chunk's lanes ``raw (C, E)``, its first
        ``K - 1`` inputs the row's ``conv`` rows ``held`` — zeros where
        the chunk starts the prompt: a slot's last tenant never leaks,
        whatever it left. Returns ``(y (C, E), the rows before position
        start + n)``: part of them the carried ones where ``n < K - 1``,
        ``held`` as it was where ``n`` is 0 (a warm-up)."""
        import jax
        import jax.numpy as jnp
        _row, start, n = chunk
        K, C = self.conv, raw.shape[0]
        carried = jnp.where(start > 0, held, jnp.zeros_like(held))
        padded = jnp.concatenate([carried.reshape(K - 1, -1), raw])
        wide = padded.astype(jnp.float32)
        y = sum(conv_w[t] * wide[t:t + C] for t in range(K))
        rows = jax.lax.dynamic_slice_in_dim(padded, n, K - 1, axis=0)
        return y, jnp.where(n > 0, rows.reshape(-1), held)

    def _chunk_scan(self, j, inputs, h_all, chunk):
        """The scan over a chunk's lanes from the row's ``h`` (zeros
        where the chunk starts the prompt; a ``where`` on the row that
        was read — a ``cond`` would compile both branches over the
        state): ``(y (C, E), h_all)``, the state after the last live lane
        written into the request's row of state layer ``j``, in place,
        and only that row. ``delta`` is 0 on the lanes that are not
        live, so the state stops at the last that is."""
        import jax.numpy as jnp
        from ..parallel.selective_scan import ssm_chunk
        row, start, n = chunk
        held = h_all[j, row]
        y, S = ssm_chunk(*inputs, state=jnp.where(start > 0, held, 0.0),
                         force_pallas=self.use_pallas)
        return y, h_all.at[j, row].set(jnp.where(n > 0, S, held))

    # -- the contract (STATE form) -----------------------------------------
    def prefill(self, params, tokens, lengths):
        """``tokens (B, L)`` of true lengths ``lengths (B,)`` ->
        ``(logits (B, L, V), k, v (cache_layers, B, L, Hkv, Dh), h
        (state_layers, B, N, E), conv (state_layers, B, (K - 1) E))``,
        the state as it stands after position ``lengths - 1``."""
        import jax.numpy as jnp
        from ..parallel.flash_attention import flash_attention
        p = params
        h = p["embed"][tokens].astype(jnp.float32)
        ks, vs, states = [], [], []
        for i, attends in enumerate(self.kinds):
            if attends:
                q, k, v = self._qkv(i, self._rms(h, p["l%d.mix_g" % i]), p)
                a = flash_attention(q, k, v, causal=True, scale=self.scale,
                                    force_pallas=self.use_pallas)
                h = h + self._attn_out(i, a, p)
                ks.append(k)
                vs.append(v)
            else:
                out, S, conv = self._ssm_prefill(i, h, p, lengths)
                h = h + out
                states.append((S, conv))
            h = h + self._mlp(i, h, p)
        return (self._logits(h, p), jnp.stack(ks), jnp.stack(vs),
                *(jnp.stack(a) for a in zip(*states)))

    def decode(self, params, tokens, positions, attend, state, head=None,
               live=None, chunk=None):
        """One token a row: ``attend(cache layer, q (B, H, Dh), k_new,
        v_new (B, Hkv, Dh), scale=, force_pallas=)`` attends an attention
        layer's pages; ``state`` is the step's
        :class:`~mxnet_tpu.serving.kvcache.RowState` (``.arrays``: ``h``
        and ``conv``, whole; ``.slots``; ``.live``). Returns ``(logits, k,
        v (cache_layers, B, Hkv, Dh), h, conv)``.

        A MIXED step hands more lanes than rows: behind the ``B`` rows of
        ``state``, ``C`` lanes that are consecutive positions of ONE
        request's prompt, ``chunk = (its row of the state arrays, the
        first lane's position, the live lanes)``. Everything is lane-wise
        but the two mixers: an attention layer's ``attend`` is the
        layout's split one (``attend_chunk``), a state-space layer runs
        the rows through ``ssm_conv_step`` and ``ssm_step`` and the chunk
        through ``ssm_chunk`` from the request's row of ``h`` and
        ``conv``, which the chunk's lanes write (:meth:`_ssm_step`). The
        rows' lanes run in SLOT order between the embedding and the head
        (the module docstring says where they go back); what is handed
        in and what is returned is by row. ``live (B + C,)``: a lane that
        is not live moves no state; ``head (B + 1,)``: the lanes that
        reach the head, ``logits`` theirs alone; the keys and values come
        back for every lane, ``(cache_layers, B + C, Hkv, Dh)``.
        ``positions`` is the server's alone (its ``attend`` is bound to
        it): nothing here encodes a position."""
        import jax.numpy as jnp
        del positions
        p = params
        arrays = tuple(state.arrays)
        # the rows' lanes in slot order (the chunk's, behind them, stay):
        # lane ``s`` is the row of the step whose state is row ``s`` of
        # the arrays, ``to_slot[s]`` of the lanes as they were handed
        behind = jnp.arange(state.slots.shape[0], len(tokens),
                            dtype=jnp.int32)
        to_slot = jnp.concatenate([state.inverse, behind])
        to_row = jnp.concatenate([state.slots, behind])
        live = (state.live if live is None else live)[to_slot]
        h = p["embed"][jnp.asarray(tokens)[to_slot]].astype(jnp.float32)
        ks, vs = [], []
        for i, attends in enumerate(self.kinds):
            if attends:
                # the server's ``attend`` knows a row by its place in the
                # step: there and back, ``(B + C, D)`` each way
                x = self._rms(h, p["l%d.mix_g" % i])[to_row]
                q, k, v = self._qkv(i, x, p)
                a = attend(self.cache_layer(i), q, k, v, scale=self.scale,
                           force_pallas=self.use_pallas)
                h = h + self._attn_out(i, a, p)[to_slot]
                ks.append(k)
                vs.append(v)
            else:
                out, arrays = self._ssm_step(i, h, p, arrays, live, chunk)
                h = h + out
            h = h + self._mlp(i, h, p)
        # a chunk's lanes do not pay the head
        h = h[to_row if head is None else to_row[head]]
        return (self._logits(h, p), jnp.stack(ks), jnp.stack(vs), *arrays)
