"""The selective scan of a state-space layer (Mamba-1, arXiv:2312.00752)
— a DIAGONAL recurrence a channel — in the forms a server needs: the
recurrence's one-token step, the short convolution's in front of it, and
a chunk of positions. A channel ``e`` of ``E`` keeps ``N`` states,
float32; with ``delta_t > 0`` a channel, ``A < 0`` a (state, channel),
``B_t`` and ``C_t`` a state (shared by the channels) and the layer's
input ``u_t`` a channel:

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
that broadcast over the lanes, ``delta_t`` and ``u_t`` as rows that
broadcast over the sublanes, and ``y_t`` is a sum over sublanes: nothing
is transposed inside a kernel.

- :func:`ssm_step` — ONE token a row, the decode step. The state of
  every row of the window and of every state-space layer lies in one
  array ``(layers, rows, N, E)`` that the step program carries like a
  page pool, and the step's lanes reach the kernel IN THAT ORDER: lane
  ``s`` works on ``state[layer, s]``. A step's rows are a permutation of
  ALL the window's (``kvcache.RowState``), so slot order IS the whole
  array: the model permutes its lanes once a step and the Pallas kernel
  ``mx_ssm_step.b<rows>.e<E>.n<N>`` walks the layer's plane in blocks of
  8 consecutive slots — decays, adds, reads out and writes back IN PLACE
  (the array is aliased to the result; the layer rides scalar prefetch).
  Why blocks by slot and not a row a grid step through a prefetched slot:
  one row of a ``(rows, E)`` float32 array is not a tile of its own, so
  a one-row kernel had ``delta`` and ``delta u`` STACKED to ``(rows, 2,
  E)`` for it — a relayout copy whose result is padded 2 -> 8 sublanes in
  HBM, a layer — and 3,328 grid steps a step of the served model; eight
  rows ARE a tile, so ``delta`` and ``u`` arrive as ``(8, E)`` blocks of
  the arrays XLA leaves (their product moves inside), ``B`` and ``C`` as
  columns a block, ``(rows / 8, N, 16)``, and the grid is 416 steps.
  Memory-bound by construction: ``2 N E 4`` bytes a row a layer against
  ``N E`` exponentials. Elsewhere (the CPU, and the kernel's test
  reference) :func:`_jnp_step`, the same signature.
- :func:`ssm_conv_step` — the causal depthwise convolution in front of
  the recurrence, ONE token a row: a row keeps its last ``K - 1`` inputs,
  oldest first, in ``conv (layers, rows, (K - 1) E)`` in the parameters'
  dtype, beside ``h``. In slot order for the same reason: the Pallas
  kernel ``mx_ssm_conv.b<rows>.e<E>.k<K>`` walks the layer's plane in
  blocks of 16 slots (whole ``(16, 128)`` tiles of a 16-bit array),
  sums the taps in float32 and writes the rows back shifted by one where
  the slot is live, as they were where not — each row read once and
  written once, in place, where gathering the rows by slot, shifting and
  writing the plane whole (a scatter would widen a 16-bit array to
  float32) moved four times the bytes. Elsewhere
  :func:`_jnp_conv_step`.
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

__all__ = ["ssm_step", "ssm_conv_step", "ssm_chunk"]

# positions a group: a group's B and C columns are one (N, 2 * _GROUP)
# tile, and a group's outputs one (8, channels) store
_GROUP = 8
# channels a grid step of the chunk kernel: (16, 512) float32 of state is
# 8 vregs, and beside it the decay, the increment and the read-out
_CHUNK_CHANNELS = 512
# positions a grid step of the chunk kernel (the state is carried from
# one to the next in the resident output block)
_CHUNK_POSITIONS = 256
# slots a grid step of the step kernel: a block's ``delta``, ``u`` and
# ``y`` are whole (8, 128) float32 tiles of the arrays XLA leaves
_STEP_ROWS = 8
# slots a grid step of the convolution's kernel: a 16-bit array packs 16
# rows a tile
_CONV_ROWS = 16
# the step kernel's 8 rows of state, in and out, each double-buffered,
# are 10.5 MB at the served sizes: over the 16 MiB a kernel gets by
# default with its vectors and its temporaries, well under the chip's 128
_STEP_VMEM = 48 << 20


def _jnp_step(state, layer, u, delta, b, c, a):
    """:func:`ssm_step`, plainly: one recurrence step over the layer's
    rows as they lie."""
    import jax.numpy as jnp
    h = jnp.exp(delta[:, None, :] * a) * state[layer] \
        + b[:, :, None] * (delta * u)[:, None, :]
    y = jnp.sum(h * c[:, :, None], axis=1)
    return y, state.at[layer].set(h)


def _ssm_step_kernel(layer_ref, d_ref, u_ref, bc_ref, a_ref, s_ref, y_ref,
                     out_ref, *, rows):
    """``rows`` consecutive slots: ``d_ref``, ``u_ref (rows, E)`` hold
    ``delta`` and ``u`` as XLA left them, ``bc_ref (N, 2 rows)`` the
    slots' ``B`` then their ``C`` as columns, ``s_ref (rows, N, E)``
    their states."""
    import jax.numpy as jnp
    del layer_ref
    a, d, bc = a_ref[...], d_ref[...], bc_ref[...]
    du = d * u_ref[...]
    for r in range(rows):
        h = jnp.exp(d[r:r + 1, :] * a) * s_ref[r] \
            + bc[:, r:r + 1] * du[r:r + 1, :]
        y_ref[r:r + 1, :] = jnp.sum(h * bc[:, rows + r:rows + r + 1],
                                    axis=0, keepdims=True)
        out_ref[r] = h


def _rows_a_block(B, rows):
    """``rows`` where the window is whole blocks of them, else ONE block
    of the whole window (a test's few rows, interpreted: on the chip the
    chooser takes the composition for such a window)."""
    return rows if B % rows == 0 else B


def _pallas_step(state, layer, u, delta, bc, a, *, interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    B, E = u.shape
    N = a.shape[0]
    R = bc.shape[2] // 2
    lanes = pl.BlockSpec((R, E), lambda i, la: (i, 0))
    block = pl.BlockSpec((None, R, N, E), lambda i, la: (la[0], i, 0, 0))
    return pl.pallas_call(
        functools.partial(_ssm_step_kernel, rows=R),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(B // R,),
            in_specs=[lanes, lanes,
                      pl.BlockSpec((None, N, 2 * R), lambda i, la: (i, 0, 0)),
                      pl.BlockSpec((N, E), lambda i, la: (0, 0)),
                      block],
            out_specs=[lanes, block]),
        out_shape=[jax.ShapeDtypeStruct((B, E), state.dtype),
                   jax.ShapeDtypeStruct(state.shape, state.dtype)],
        input_output_aliases={5: 1},
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=_STEP_VMEM),
        interpret=interpret,
        name="mx_ssm_step.b%d.e%d.n%d" % (B, E, N),
    )(layer, delta, u, bc, a, state)


def _columns(x, rows):
    """``(B, N) -> (B / rows, N, rows)``: a block's rows (a group's
    positions) as columns, one a lane."""
    B, N = x.shape
    return x.reshape(B // rows, rows, N).transpose(0, 2, 1)


def ssm_step(state, layer, u, delta, b, c, a, *, force_pallas=False):
    """One token a row, in SLOT order: ``state (layers, rows, N, E)``
    float32, row ``i`` of ``u``, ``delta (rows, E)``, ``b``, ``c (rows,
    N)`` works on ``state[layer, i]``; ``a (N, E)``; all float32. Returns
    ``(y (rows, E), state)``, the state updated in place where the kernel
    runs. A row with ``delta = 0`` leaves its slot as it was (its ``y``
    reads it): how a step says which rows it moves. Counted as
    ``ssm_step_pallas`` / ``ssm_step_jnp``."""
    import jax.numpy as jnp
    from .flash_attention import _dispatch, _traced_once
    B, E = u.shape
    R = _rows_a_block(B, _STEP_ROWS)

    def composed(state, u, delta, b, c, a):
        return _jnp_step(state, layer, u, delta, b, c, a)

    def kernel(interpret, state, u, delta, b, c, a):
        return tuple(_traced_once(_pallas_step, "interpret")(
            state, jnp.full((1,), layer, jnp.int32), u, delta,
            jnp.concatenate([_columns(b, R), _columns(c, R)], axis=2), a,
            interpret=interpret))

    # the chooser's predicate is "multiples of 128": whole blocks of
    # rows, said in its terms
    return _dispatch("ssm_step", E, (B * (128 // _STEP_ROWS),),
                     force_pallas, kernel, composed, state, u, delta, b, c,
                     a)


def _jnp_conv_step(conv, layer, raw, live, w):
    """:func:`ssm_conv_step`, plainly: the layer's rows with the new
    input behind them, the taps' sum, the rows shifted by one where
    ``live``."""
    import jax.numpy as jnp
    B, E = raw.shape
    K = w.shape[0]
    before = conv[layer]                                  # (B, (K-1) E)
    window = jnp.concatenate([before.reshape(B, K - 1, E), raw[:, None]],
                             axis=1)
    y = (w * window.astype(jnp.float32)).sum(1)
    after = jnp.where(live[:, None], window[:, 1:].reshape(B, -1), before)
    return y, conv.at[layer].set(after)


def _ssm_conv_kernel(layer_ref, live_ref, raw_ref, w_ref, rows_ref, y_ref,
                     out_ref, *, taps):
    """A block of consecutive slots: ``rows_ref (rows, (K - 1) E)`` their
    held inputs, oldest first, ``raw_ref (rows, E)`` the step's,
    ``live_ref (rows, 1)`` not 0 where the slot moves. The select is
    made on the float32 values the sum needs anyway: a 16-bit value goes
    there and back exactly."""
    import jax.numpy as jnp
    del layer_ref
    E = raw_ref.shape[1]
    w = w_ref[...]
    window = [rows_ref[:, t * E:(t + 1) * E].astype(jnp.float32)
              for t in range(taps - 1)]
    window.append(raw_ref[...].astype(jnp.float32))
    y = w[0:1, :] * window[0]
    for t in range(1, taps):
        y = y + w[t:t + 1, :] * window[t]
    y_ref[...] = y
    live = live_ref[...] != 0
    for t in range(taps - 1):
        out_ref[:, t * E:(t + 1) * E] = jnp.where(
            live, window[t + 1], window[t]).astype(out_ref.dtype)


def _pallas_conv(conv, layer, live, raw, w, *, interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    B, E = raw.shape
    K = w.shape[0]
    R = _rows_a_block(B, _CONV_ROWS)
    block = pl.BlockSpec((None, R, (K - 1) * E),
                         lambda i, la: (la[0], i, 0))
    return pl.pallas_call(
        functools.partial(_ssm_conv_kernel, taps=K),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(B // R,),
            in_specs=[pl.BlockSpec((R, 1), lambda i, la: (i, 0)),
                      pl.BlockSpec((R, E), lambda i, la: (i, 0)),
                      pl.BlockSpec((K, E), lambda i, la: (0, 0)),
                      block],
            out_specs=[pl.BlockSpec((R, E), lambda i, la: (i, 0)), block]),
        out_shape=[jax.ShapeDtypeStruct((B, E), w.dtype),
                   jax.ShapeDtypeStruct(conv.shape, conv.dtype)],
        input_output_aliases={4: 1},
        interpret=interpret,
        name="mx_ssm_conv.b%d.e%d.k%d" % (B, E, K),
    )(layer, live, raw, w, conv)


def ssm_conv_step(conv, layer, raw, live, w, *, force_pallas=False):
    """The short causal convolution's one-token step, in SLOT order:
    ``conv (layers, rows, (K - 1) E)`` holds each row's last ``K - 1``
    inputs, oldest first, in the dtype of ``raw (rows, E)``, the step's
    input; ``w (K, E)`` float32 the taps; ``live (rows,)`` bool. Returns
    ``(y (rows, E) float32, conv)``: ``y = sum_t w[t] . window[t]`` over
    the held inputs and the new one, summed in float32 oldest tap first,
    and the layer's rows shifted by one where ``live``, as they were
    where not — in place where the kernel runs. Counted as
    ``ssm_conv_pallas`` / ``ssm_conv_jnp``."""
    import jax.numpy as jnp
    from .flash_attention import _dispatch, _traced_once
    B, E = raw.shape

    def composed(conv, raw, live, w):
        return _jnp_conv_step(conv, layer, raw, live, w)

    def kernel(interpret, conv, raw, live, w):
        return tuple(_traced_once(_pallas_conv, "interpret")(
            conv, jnp.full((1,), layer, jnp.int32),
            live.astype(jnp.int32)[:, None], raw, w, interpret=interpret))

    return _dispatch("ssm_conv", E, (B * (128 // _CONV_ROWS),),
                     force_pallas, kernel, composed, conv, raw, live, w)


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
        return tuple(_traced_once(_pallas_chunk, "interpret")(
            delta, delta * u,
            jnp.concatenate([_columns(b, _GROUP), _columns(c, _GROUP)],
                            axis=2), a, state, interpret=interpret))

    # the chooser's predicate is "multiples of 128": whole groups of
    # positions, said in its terms
    return _dispatch("ssm_chunk", E, (C * (128 // _GROUP),), force_pallas,
                     kernel, _jnp_chunk, u, delta, b, c, a, state)
