"""The selective scan of a state-space layer (Mamba-1, arXiv:2312.00752)
— a DIAGONAL recurrence a channel — in the two forms a server needs. A
channel ``e`` of ``E`` keeps ``N`` states, float32; with ``delta_t > 0``
a channel, ``A < 0`` a (state, channel), ``B_t`` and ``C_t`` a state
(shared by the channels) and the layer's input ``u_t`` a channel:

    h_t[n, e] = exp(delta_t[e] A[n, e]) h_{t-1}[n, e] + delta_t[e] u_t[e] B_t[n]
    y_t[e]    = sum_n h_t[n, e] C_t[n]

No matrix product anywhere in the rule: the decay couples state and
channel inside the exponent, so there is no chunkwise matrix form (what
``parallel.delta_rule.kda_chunk`` does for a delta rule has no like
here) — the work is ``E N`` exponentials and a few multiply-adds a
token, the VPU's and the EUP's, nothing for the MXU. ``delta_t = 0``
leaves ``h`` exactly as it was (``exp(0) = 1``, nothing added): how a
dead row of a decode window and the lanes behind a chunk's last live one
pass through. The skip ``D u_t`` and the gate are the layer's, outside.

**The state lies channel-minor**: ``(N, E)`` a row, so that ``E`` (5,120
in the served model) fills whole 128-lane tiles and ``N`` (16: an eighth
of a lane tile, two sublane tiles) lies along the sublanes; ``A`` is kept
the same way. ``B_t`` and ``C_t`` reach a kernel as COLUMNS ``(N, 1)``
that broadcast over the lanes, ``delta_t`` and ``delta_t u_t`` as rows
that broadcast over the sublanes, and ``y_t`` is a sum over sublanes:
nothing is transposed inside a kernel.

- :func:`ssm_step` — ONE token a row, the decode step. The state of
  every row of the window and of every state-space layer lies in one
  array ``(layers, rows, N, E)`` that the step program carries like a
  page pool; row ``b`` of the step works on ``state[layer, slots[b]]``.
  On the TPU the Pallas kernel ``mx_ssm_step.b<rows>.e<E>.n<N>`` reads
  one row's ``(N, E)``, decays, adds, reads out and writes back IN PLACE
  (the array is aliased to the result; the slot rides scalar prefetch,
  so nothing is gathered or scattered). Memory-bound by construction:
  ``2 N E 4`` bytes a row a layer against ``N E`` exponentials.
  Elsewhere (the CPU, and the kernel's test reference)
  :func:`_jnp_step`, the same signature.
- :func:`ssm_chunk` — ``C`` consecutive positions of ONE request from
  the row's state: a prompt's chunk on a mixed step's lanes, or (from
  zeros, under ``vmap``) a whole prompt. SEQUENTIAL in time, the state
  resident in VMEM: the Pallas kernel ``mx_ssm_chunk.c<C>.e<E>.n<N>``
  gives each grid step a block of channels (the recurrence is
  independent a channel) and walks the positions with that block's
  ``(N, channels)`` of state in registers; ``B`` and ``C`` come
  transposed in groups of eight positions, ``(C / 8, N, 16)``, so that a
  position's column is a static lane slice. Why not an associative scan
  over ``(a, b)`` pairs: the pairs are ``(C, N, E)`` float32 — 168 MB a
  layer at 512 positions — and a log-depth scan reads and writes them
  several times from HBM (some 30 GB over the served model's 26 layers
  against a step that moves 8 GB), where the sequential walk reads
  ``delta`` and ``u`` once and keeps the state on the chip. Elsewhere
  :func:`_jnp_chunk`, ``lax.scan`` a token at a time under
  ``jax.named_scope("mx_ssm_chunk")``.
"""
from __future__ import annotations

import functools

import jax

__all__ = ["ssm_step", "ssm_chunk"]

# positions a group: a group's B and C columns are one (N, 2 * _GROUP)
# tile, and a group's outputs one (8, channels) store
_GROUP = 8
# channels a grid step of the chunk kernel: (16, 512) float32 of state is
# 8 vregs, and beside it the decay, the increment and the read-out
_CHUNK_CHANNELS = 512
# positions a grid step of the chunk kernel (the state is carried from
# one to the next in the resident output block)
_CHUNK_POSITIONS = 256


def _jnp_step(state, layer, slots, u, delta, b, c, a):
    """:func:`ssm_step`, plainly: the rows' states gathered, one
    recurrence step, scattered back."""
    import jax.numpy as jnp
    h = state[layer, slots]                               # (B, N, E)
    h = jnp.exp(delta[:, None, :] * a) * h \
        + b[:, :, None] * (delta * u)[:, None, :]
    y = jnp.sum(h * c[:, :, None], axis=1)
    return y, state.at[layer, slots].set(h)


def _ssm_step_kernel(slot_ref, layer_ref, vec_ref, bc_ref, a_ref, s_ref,
                     y_ref, out_ref):
    """One row: ``vec_ref (2, E)`` holds ``delta`` and ``delta u`` as
    rows, ``bc_ref (N, 2)`` holds ``B`` and ``C`` as columns."""
    import jax.numpy as jnp
    del slot_ref, layer_ref
    vec, bc = vec_ref[...], bc_ref[...]
    h = jnp.exp(vec[0:1, :] * a_ref[...]) * s_ref[...] \
        + bc[:, 0:1] * vec[1:2, :]
    y_ref[...] = jnp.sum(h * bc[:, 1:2], axis=0, keepdims=True)
    out_ref[...] = h


def _pallas_step(state, slots, layer, vec, bc, a, *, interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    B, _two, E = vec.shape
    N = a.shape[0]
    block = pl.BlockSpec((None, None, N, E),
                         lambda b, sl, la: (la[0], sl[b], 0, 0))
    return pl.pallas_call(
        _ssm_step_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(B,),
            in_specs=[pl.BlockSpec((None, 2, E), lambda b, sl, la: (b, 0, 0)),
                      pl.BlockSpec((None, N, 2), lambda b, sl, la: (b, 0, 0)),
                      pl.BlockSpec((N, E), lambda b, sl, la: (0, 0)),
                      block],
            out_specs=[pl.BlockSpec((None, 1, E),
                                    lambda b, sl, la: (b, 0, 0)),
                       block]),
        out_shape=[jax.ShapeDtypeStruct((B, 1, E), state.dtype),
                   jax.ShapeDtypeStruct(state.shape, state.dtype)],
        input_output_aliases={5: 1},
        interpret=interpret,
        name="mx_ssm_step.b%d.e%d.n%d" % (B, E, N),
    )(slots, layer, vec, bc, a, state)


def ssm_step(state, layer, slots, u, delta, b, c, a, *, force_pallas=False):
    """One token a row: ``state (layers, rows, N, E)`` float32, row ``i``
    of the step on ``state[layer, slots[i]]`` (``slots`` distinct); ``u``,
    ``delta (B, E)``, ``b``, ``c (B, N)`` and ``a (N, E)`` float32.
    Returns ``(y (B, E), state)``, the state updated in place where the
    kernel runs. A row with ``delta = 0`` leaves its slot as it was (its
    ``y`` reads it). Counted as ``ssm_step_pallas`` / ``ssm_step_jnp``."""
    import jax.numpy as jnp
    from .flash_attention import _dispatch, _traced_once
    slots = jnp.asarray(slots, jnp.int32)

    def composed(state, slots, u, delta, b, c, a):
        return _jnp_step(state, layer, slots, u, delta, b, c, a)

    def kernel(interpret, state, slots, u, delta, b, c, a):
        y, state = _traced_once(_pallas_step, "interpret")(
            state, slots, jnp.full((1,), layer, jnp.int32),
            jnp.stack([delta, delta * u], axis=1),
            jnp.stack([b, c], axis=2), a, interpret=interpret)
        return y[:, 0], state

    return _dispatch("ssm_step", u.shape[-1], (), force_pallas, kernel,
                     composed, state, slots, u, delta, b, c, a)


def _jnp_chunk(u, delta, b, c, a, state):
    """:func:`ssm_chunk`, plainly: one token at a time."""
    import jax.numpy as jnp

    def token(h, x):
        u, delta, b, c = x
        h = jnp.exp(delta[None, :] * a) * h \
            + b[:, None] * (delta * u)[None, :]
        return h, jnp.sum(h * c[:, None], axis=0)

    with jax.named_scope("mx_ssm_chunk"):
        state, y = jax.lax.scan(token, state, (u, delta, b, c))
    return y, state


def _ssm_chunk_kernel(d_ref, du_ref, bc_ref, a_ref, h0_ref, y_ref, h_ref,
                      *, groups):
    """A block of channels over a block of positions: ``d_ref``, ``du_ref
    (positions, channels)`` hold ``delta`` and ``delta u``, ``bc_ref
    (groups, N, 2 * _GROUP)`` the columns of ``B`` then of ``C``, a group
    of positions a tile; ``h_ref``, the state's output block, stays
    resident over the position blocks and carries the state."""
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    G = _GROUP

    @pl.when(pl.program_id(1) == 0)
    def _start():
        h_ref[...] = h0_ref[...]

    a = a_ref[...]
    at = jax.lax.broadcasted_iota(jnp.int32, (G, a.shape[1]), 0)

    def group(i, h):
        bc = bc_ref[i]                                    # (N, 2 G)
        t0 = pl.multiple_of(i * G, G)
        d, du = d_ref[pl.ds(t0, G), :], du_ref[pl.ds(t0, G), :]
        y = jnp.zeros_like(d)
        for j in range(G):
            h = jnp.exp(d[j:j + 1, :] * a) * h \
                + bc[:, j:j + 1] * du[j:j + 1, :]
            row = jnp.sum(h * bc[:, G + j:G + j + 1], axis=0, keepdims=True)
            y = jnp.where(at == j, row, y)
        y_ref[pl.ds(t0, G), :] = y
        return h

    h_ref[...] = jax.lax.fori_loop(0, groups, group, h_ref[...])


def _pallas_chunk(d, du, bc, a, h0, *, interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    C, E = d.shape
    N = a.shape[0]
    te = _CHUNK_CHANNELS if E % _CHUNK_CHANNELS == 0 else E
    tc = _CHUNK_POSITIONS if C % _CHUNK_POSITIONS == 0 else C
    lanes = pl.BlockSpec((tc, te), lambda i, j: (j, i))
    held = pl.BlockSpec((N, te), lambda i, j: (0, i))
    return pl.pallas_call(
        functools.partial(_ssm_chunk_kernel, groups=tc // _GROUP),
        grid=(E // te, C // tc),
        in_specs=[lanes, lanes,
                  pl.BlockSpec((tc // _GROUP, N, 2 * _GROUP),
                               lambda i, j: (j, 0, 0)),
                  held, held],
        out_specs=[lanes, held],
        out_shape=[jax.ShapeDtypeStruct((C, E), d.dtype),
                   jax.ShapeDtypeStruct((N, E), d.dtype)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="mx_ssm_chunk.c%d.e%d.n%d" % (C, E, N),
    )(d, du, bc, a, h0)


def ssm_chunk(u, delta, b, c, a, state=None, n=None, *, force_pallas=False):
    """``C`` consecutive positions of one request: ``u``, ``delta (C,
    E)``, ``b``, ``c (C, N)``, ``a (N, E)`` float32, ``state (N, E)`` or
    None for zeros, ``n`` the live positions (all where None). Returns
    ``(y (C, E), the state after position n - 1)``: a position at or past
    ``n`` leaves the state as it was (its own ``y`` reads it). The
    kernel takes ``C`` in whole groups of eight positions. Counted as
    ``ssm_chunk_pallas`` / ``ssm_chunk_jnp``."""
    import jax.numpy as jnp
    from .flash_attention import _dispatch, _traced_once
    C, E = u.shape
    N = a.shape[0]
    if n is not None:
        delta = jnp.where(jnp.arange(C)[:, None] < n, delta, 0.0)
    if state is None:
        state = jnp.zeros((N, E), jnp.float32)

    def kernel(interpret, u, delta, b, c, a, state):
        def grouped(x):             # (C, N) -> (C / G, N, G)
            return x.reshape(C // _GROUP, _GROUP, N).transpose(0, 2, 1)

        return tuple(_traced_once(_pallas_chunk, "interpret")(
            delta, delta * u,
            jnp.concatenate([grouped(b), grouped(c)], axis=2), a, state,
            interpret=interpret))

    # the chooser's predicate is "multiples of 128": whole groups of
    # positions, said in its terms
    return _dispatch("ssm_chunk", E, (C * (128 // _GROUP),), force_pallas,
                     kernel, _jnp_chunk, u, delta, b, c, a, state)
