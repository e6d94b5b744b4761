"""The gated delta rule with a decay a channel (Kimi Delta Attention,
arXiv:2510.26692) — the recurrence of a linear-attention layer, in the
two forms a server needs. A head keeps a matrix ``S (d_k, d_v)``,
float32; with ``alpha_t = exp(g_t)`` in ``(0, 1]`` a key channel,
``beta_t`` in ``[0, 1]`` a head, ``q_t``, ``k_t`` (unit length) and
``v_t``:

    S_t = (I - beta_t k_t k_t^T) Diag(alpha_t) S_{t-1} + beta_t k_t v_t^T
    o_t = S_t^T q_t

Written with the pseudo-value ``u_t = beta_t (v_t - (alpha_t * k_t)^T
S_{t-1})`` it is ``S_t = Diag(alpha_t) S_{t-1} + k_t u_t^T``: one decay,
one rank-1 correction. ``beta_t = 0`` and ``g_t = 0`` leave ``S``
exactly as it was — how a dead row of a decode window and the padding
of a prompt's rung pass through.

- :func:`kda_step` — ONE token a row, the decode step. The state of
  every row of the window and of every linear-attention layer lies in
  one array ``(layers, rows, H, d_k, d_v)`` that the step program
  carries like a page pool; row ``b`` of the step works on the state in
  ``slots[b]``. On the TPU, where ``d`` fills whole lanes, the Pallas
  kernel ``mx_kda_step.b<rows>.h<H>.d<d>`` reads one row's heads (a
  block of ``heads_per_step``), decays, corrects, reads out and writes
  back IN PLACE (the array is aliased to the result; the slot rides
  scalar prefetch, so nothing is gathered or scattered); all arithmetic
  float32 on the VPU — the products are a vector against a matrix, and
  the MXU would round them to bfloat16. Memory-bound by construction:
  ``2 * H * d * d * 4`` bytes a row a layer. What the kernel needs along
  the key channel — ``alpha``, ``k``, ``beta k``, ``q`` as COLUMNS — is
  handed to it transposed, heads in the lanes (``(rows, d, 4 H)``: a few
  hundred KB that XLA transposes), so that a column is a lane slice
  broadcast over the lanes and the kernel transposes nothing. Elsewhere
  (the CPU, and the kernel's test reference) :func:`_jnp_step`, the
  same signature.
- :func:`kda_chunk` — a whole sequence, the prefill, in chunkwise form
  under ``jax.named_scope("mx_kda_chunk")``, ``jnp``. With ``G`` the
  running sum of ``g`` inside a chunk, ``A[t, j] = sum_c k_t[c] k_j[c]
  exp(G_t[c] - G_j[c])`` for ``j < t`` and ``B`` the same with ``q_t``
  for ``j <= t``: ``(I + Diag(beta) A) U = Diag(beta) (V - (K * e^G)
  S_0)`` (unit lower triangular), ``O = (Q * e^G) S_0 + B U``, ``S_C =
  e^{G_C} * S_0 + (K * e^{G_C - G})^T U``. The solve and the two
  matrices are computed for all chunks at once; only the state walks
  the chunks. **The trap**: ``A`` and ``B`` are formed as products of
  ``K * e^G`` with ``K * e^-G``, and ``e^-G`` overflows float32 once the
  running decay passes ``e^-88`` (and ``K * e^G`` is flushed to zero
  well before): ``G`` is taken from the chunk's middle, which halves
  its reach, and a chunk is at most ``80 / |lower bound of g|``
  positions (16 at ``-5``: factors between ``e^-40`` and ``e^40``),
  which the function checks against the ``g_floor`` it is told.
  Products at "highest": the state is float32 and stays float32.
"""
from __future__ import annotations

import functools

import jax

__all__ = ["kda_step", "kda_chunk"]

# the largest running decay a chunk may reach, end to end: taken from the
# chunk's middle its two halves are e^40 and e^-40, far from float32's
# e^88.7 and from the e^-87 under which a product is flushed to zero
_MAX_LOG_DECAY = 80.0


def _jnp_step(state, layer, slots, q, k, v, g, beta):
    """:func:`kda_step`, plainly: the rows' states gathered, one
    recurrence step, scattered back."""
    import jax.numpy as jnp
    S = state[layer, slots]                               # (B, H, d, d)
    S = jnp.exp(g)[..., None] * S
    u = beta[..., None] * (v - jnp.einsum("bhk,bhkv->bhv", k, S,
                                          precision="highest"))
    S = S + k[..., None] * u[..., None, :]
    o = jnp.einsum("bhk,bhkv->bhv", q, S, precision="highest")
    return o, state.at[layer, slots].set(S)


def _kda_step_kernel(slot_ref, layer_ref, cols_ref, v_ref, s_ref, o_ref,
                     out_ref, *, heads):
    """One row's ``heads`` heads: ``cols_ref (d, 4 heads)`` holds, a
    head a lane, ``alpha``, ``k``, ``beta k`` and ``q`` as columns."""
    import jax.numpy as jnp
    del slot_ref, layer_ref
    cols = cols_ref[...]
    for h in range(heads):
        alpha, kc, bk, qc = (cols[:, i * heads + h:i * heads + h + 1]
                             for i in range(4))
        s = alpha * s_ref[h]                              # (d_k, d_v)
        u = v_ref[h:h + 1, :] - jnp.sum(kc * s, axis=0, keepdims=True)
        s = s + bk * u
        o_ref[h:h + 1, :] = jnp.sum(qc * s, axis=0, keepdims=True)
        out_ref[h] = s


def _heads_per_step(n_heads, d):
    """Heads a grid step: the most whose state block is 1 MiB (in and
    out, each double-buffered, 4 MiB of the 16 the chip's compiler
    allows a kernel by default), a multiple of 8 or all of them."""
    per = max(1, (1 << 20) // (d * d * 4))
    if per >= n_heads:
        return n_heads
    per -= per % 8
    return per if per and n_heads % per == 0 else n_heads


def _pallas_step(state, slots, layer, cols, v, *, heads, interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    B, H, d = v.shape
    block = pl.BlockSpec((None, None, heads, d, d),
                         lambda b, j, sl, la: (la[0], sl[b], j, 0, 0))
    rows = pl.BlockSpec((None, heads, d), lambda b, j, sl, la: (b, j, 0))
    return pl.pallas_call(
        functools.partial(_kda_step_kernel, heads=heads),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(B, H // heads),
            in_specs=[pl.BlockSpec((None, None, d, 4 * heads),
                                   lambda b, j, sl, la: (b, j, 0, 0)),
                      rows, block],
            out_specs=[rows, block]),
        out_shape=[jax.ShapeDtypeStruct(v.shape, v.dtype),
                   jax.ShapeDtypeStruct(state.shape, state.dtype)],
        input_output_aliases={4: 1},
        interpret=interpret,
        name="mx_kda_step.b%d.h%d.d%d" % (B, H, d),
    )(slots, layer, cols, v, state)


def kda_step(state, layer, slots, q, k, v, g, beta, *, force_pallas=False):
    """One token a row: ``state (layers, rows, H, d, d)`` float32, row
    ``b`` of the step on ``state[layer, slots[b]]`` (``slots`` distinct);
    ``q``, ``k``, ``v``, ``g (B, H, d)`` and ``beta (B, H)`` float32.
    Returns ``(o (B, H, d), state)``, the state updated in place where
    the kernel runs. Counted as ``kda_step_pallas`` / ``kda_step_jnp``."""
    import jax.numpy as jnp
    from .flash_attention import _dispatch, _traced_once
    B, H, d = q.shape
    heads = _heads_per_step(H, d)
    slots = jnp.asarray(slots, jnp.int32)

    def composed(state, slots, q, k, v, g, beta):
        return _jnp_step(state, layer, slots, q, k, v, g, beta)

    def kernel(interpret, state, slots, q, k, v, g, beta):
        # (4, B, J, heads, d) -> (B, J, d, 4 heads): a column a lane
        cols = jnp.stack([jnp.exp(g), k, beta[..., None] * k, q]) \
            .reshape(4, B, H // heads, heads, d)
        cols = cols.transpose(1, 2, 4, 0, 3).reshape(
            B, H // heads, d, 4 * heads)
        return tuple(_traced_once(_pallas_step, "heads", "interpret")(
            state, slots, jnp.full((1,), layer, jnp.int32), cols, v,
            heads=heads, interpret=interpret))

    return _dispatch("kda_step", d, (d,), force_pallas, kernel, composed,
                     state, slots, q, k, v, g, beta)


def kda_chunk(q, k, v, g, beta, state=None, *, chunk=16, g_floor=-5.0):
    """A whole sequence in chunkwise form: ``q``, ``k``, ``v``, ``g (B,
    L, H, d)``, ``beta (B, L, H)`` float32, ``state (B, H, d, d)`` or
    None for zeros; ``L`` a multiple of ``chunk``. Returns ``(o (B, L, H,
    d), state after position L - 1)``. A position with ``beta = 0`` and
    ``g = 0`` leaves the state as it was (its own ``o`` reads it)."""
    import jax.numpy as jnp
    from ..base import MXNetError
    if chunk * abs(g_floor) > _MAX_LOG_DECAY:
        raise MXNetError(
            "kda_chunk: a chunk of %d positions at g down to %g reaches a "
            "running decay of e^%g, whose reciprocal overflows float32 "
            "(taken from the chunk's middle, half of it each way) — a "
            "chunk is at most %d positions there"
            % (chunk, g_floor, chunk * g_floor,
               int(_MAX_LOG_DECAY / abs(g_floor))))
    B, L, H, d = q.shape
    if L % chunk:
        raise MXNetError("kda_chunk: sequence length %d is no multiple of "
                         "the chunk %d" % (L, chunk))
    N, C = L // chunk, chunk
    hi = functools.partial(jnp.einsum, precision="highest")
    with jax.named_scope("mx_kda_chunk"):
        def chunks(a):              # (B, L, H, ...) -> (N, B, H, C, ...)
            a = a.reshape((B, N, C, H) + a.shape[3:])
            return jnp.moveaxis(jnp.moveaxis(a, 3, 2), 1, 0)

        q, k, v, g = (chunks(a.astype(jnp.float32)) for a in (q, k, v, g))
        beta = chunks(beta.astype(jnp.float32))           # (N, B, H, C)
        G = jnp.cumsum(g, axis=-2)
        k_dec, q_dec = k * jnp.exp(G), q * jnp.exp(G)
        # A and B hold exp(G_t - G_j): both factors are taken from the
        # chunk's MIDDLE, so that neither leaves float32 (at most e^40
        # and e^-40 where the whole chunk decays by e^-80)
        Gc = G - G[..., C // 2 - 1:C // 2, :]
        k_inc = k * jnp.exp(-Gc)
        t = jnp.arange(C)
        lower, strict = t[:, None] >= t[None, :], t[:, None] > t[None, :]
        A = jnp.where(strict, hi("nbhtc,nbhjc->nbhtj", k * jnp.exp(Gc),
                                 k_inc), 0.0)
        Bm = jnp.where(lower, hi("nbhtc,nbhjc->nbhtj", q * jnp.exp(Gc),
                                 k_inc), 0.0)
        # (I + Diag(beta) A) [U_v, W] = Diag(beta) [V, K e^G]
        rhs = beta[..., None] * jnp.concatenate([v, k_dec], axis=-1)
        sol = jax.scipy.linalg.solve_triangular(
            jnp.eye(C, dtype=jnp.float32) + beta[..., None] * A, rhs,
            lower=True, unit_diagonal=True)
        u_v, w = sol[..., :d], sol[..., d:]
        # what of a chunk's keys is left at its end: K * e^{G_C - G} <= 1
        k_end = k * jnp.exp(G[..., -1:, :] - G)
        decay = jnp.exp(G[..., -1, :])                    # (N, B, H, d)

        def walk(S, x):
            q_dec, Bm, u_v, w, k_end, decay = x
            u = u_v - hi("bhtk,bhkv->bhtv", w, S)
            o = hi("bhtk,bhkv->bhtv", q_dec, S) \
                + hi("bhtj,bhjv->bhtv", Bm, u)
            S = decay[..., None] * S + hi("bhtk,bhtv->bhkv", k_end, u)
            return S, o

        if state is None:
            state = jnp.zeros((B, H, d, d), jnp.float32)
        state, o = jax.lax.scan(walk, state,
                                (q_dec, Bm, u_v, w, k_end, decay))
        o = jnp.moveaxis(jnp.moveaxis(o, 0, 1), 2, 3)     # (B, N, C, H, d)
        return o.reshape(B, L, H, d), state
