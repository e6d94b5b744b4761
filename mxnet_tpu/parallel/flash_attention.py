"""Fused (flash) attention as Pallas TPU kernels — forward AND backward.

The hot op of the long-context path (SURVEY §5.7): K/V stream through
VMEM one block per grid step with the numerically-stable running
max/sum accumulation, so neither the (Tq, Tk) score matrix nor the
full K/V sequence is ever VMEM-resident — the role cuDNN fused
attention plays for the reference's GPU builds, written against the
MXU/VMEM model from the Pallas guide. The TPU grid executes
sequentially, so accumulators live in VMEM scratch across the
innermost grid axis (the canonical TPU flash pattern).

Differentiation (``jax.custom_vjp``) also rides Pallas: the forward
kernel additionally emits the per-row logsumexp, and two backward
kernels recompute the probability blocks from (q, k, lse) to
accumulate dk/dv (k outer, q inner) and dq (q outer, k inner) — O(T)
memory end to end, which is what makes long-context *training* fit
(a dense recompute would materialize the (Tq, Tk) score matrix).

Sequence lengths that do not tile by the block size are zero-padded to
the 128-lane multiple and masked inside the kernels (k positions
beyond the true length score -inf; padded q rows are sliced off) — no
silent dense fallback.

``flash_attention``/``flash_decode`` take the kernels on the TPU for
shapes the chip tiles (:func:`tiles_on_chip`: head dim and blocks in
multiples of 128) and the jnp composition otherwise — one predicate,
:func:`_choose_path`, counted in ``profiler.counters()``; which device
a program lands on is left to JAX (:func:`_dispatch`). Tests pin
kernel forward AND backward against the jnp reference on CPU via
Pallas interpret mode (``force_pallas=True``);
``tests/test_chip_compile.py`` compiles every ``pallas_call`` here for
a described v5e, and ``chip_smoke.py`` runs them on the chip.

The serving decode step does not use ``flash_decode``'s contiguous
cache: :func:`_pallas_paged_decode` (behind
``serving.kvcache.paged_attention``, which holds its plain reference)
reads the paged KV pool's pages where they lie, steered by the page
table in SMEM, so the step copies no cache; its grid has a step a table
column. The MXU paged kernels — :func:`_pallas_latent_decode` and
:func:`_pallas_latent_verify` (one latent row a token, shared by every
head) and :func:`_pallas_block_decode` (a block of query positions a
row over fewer key/value than query heads: one MXU product a key/value
head and live page) — have one grid step a ROW: the pools stay in HBM
and the kernel walks the row's live pages itself
(:func:`_walk_pages`), so a table's dead columns cost nothing and a
table 10 wide and one 80 wide cost what their live pages cost.

Per-row planes (logsumexp, rowsum(dO*O), segment ids, int8 scales)
are rank-3 — ``(BH, T, 1)`` columns on the q side, ``(BH, 1, T)`` rows
on the k side — because the TPU lowering refuses a rank-2 ``(None,
block)`` block; decode's per-row lengths ride scalar prefetch (SMEM).
"""
from __future__ import annotations

import functools
import math

import jax

__all__ = ["flash_attention", "flash_decode", "ring_decode"]

_NEG = -1e30


def _jnp_reference(q, k, v, scale, causal, segment_ids=None):
    import jax.numpy as jnp
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    if causal:
        Tq, Tk = q.shape[1], k.shape[1]
        mask = jnp.tril(jnp.ones((Tq, Tk), dtype=bool))
        s = jnp.where(mask[None, None], s, _NEG)
    if segment_ids is not None:
        # packed rows (bucketing.packing): a position only attends
        # inside its OWN segment — a blocked score is _NEG, its
        # softmax weight a true IEEE zero, so the packed result at a
        # sample's positions is bit-identical to attending that sample
        # alone. Padding (id 0) attends to nothing and must be masked
        # (or ignored) downstream.
        seg = jnp.asarray(segment_ids)
        allowed = jnp.logical_and(seg[:, :, None] == seg[:, None, :],
                                  seg[:, :, None] > 0)
        s = jnp.where(allowed[:, None], s, _NEG)
    p = jnp.asarray(
        jnp.exp(s - jnp.max(s, axis=-1, keepdims=True)), q.dtype)
    p = p / jnp.maximum(jnp.sum(p, axis=-1, keepdims=True), 1e-30)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v)


def _dot(a, b, contract=(1, 0)):
    """Contract dim ``contract[0]`` of ``a`` with dim ``contract[1]`` of
    ``b`` into an fp32 accumulator: ``(1, 0)`` is ``a @ b``, ``(1, 1)``
    ``a @ b.T`` and ``(0, 0)`` ``a.T @ b`` — the MXU takes either
    operand transposed natively, so no block is ever relaid out."""
    import jax.numpy as jnp
    return jax.lax.dot_general(
        a, b, (((contract[0],), (contract[1],)), ((), ())),
        preferred_element_type=jnp.float32)


_NT, _TN = (1, 1), (0, 0)


def _mask_scores(s, qi, kb, block_q, block_k, causal, kv_len,
                 qseg=None, kseg=None):
    """-inf the scores of padded k positions (and the causal triangle,
    and — for packed batches — every cross-segment pair)."""
    import jax.numpy as jnp
    # 2-D iotas: Mosaic has no 1-D iota
    k_pos = kb * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (1, block_k), 1)
    live = k_pos < kv_len
    if causal:
        q_pos = qi * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, 1), 0)
        live = jnp.logical_and(live, q_pos >= k_pos)
    if qseg is not None:
        # qseg is a (bq, 1) column, kseg a (1, bk) row: they broadcast
        # to the score tile with no relayout
        live = jnp.logical_and(
            live, jnp.logical_and(qseg == kseg, qseg > 0))
    return jnp.where(live, s, _NEG)


def _fwd_kernel(q_ref, k_ref, v_ref, *refs, scale, causal, block_q,
                block_k, n_kb, kv_len, has_seg):
    """Grid = (batch*heads, q_blocks, k_blocks), k innermost: scratch
    accumulators carry across the sequential k steps. With ``has_seg``
    two extra int32 refs stream each block's q/k segment ids (packed
    batches) and cross-segment scores mask to -inf in
    ``_mask_scores``."""
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    if has_seg:
        qseg_ref, kseg_ref, o_ref, lse_ref, acc_ref, m_ref, l_ref = refs
    else:
        o_ref, lse_ref, acc_ref, m_ref, l_ref = refs
        qseg_ref = kseg_ref = None
    qi = pl.program_id(1)
    kb = pl.program_id(2)

    @pl.when(kb == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
        l_ref[...] = jnp.zeros_like(l_ref)

    # causal: K blocks fully above the diagonal contribute nothing
    live = True
    if causal:
        live = kb * block_k <= (qi + 1) * block_q - 1

    @pl.when(live)
    def _step():
        q = q_ref[...].astype(jnp.float32) * scale    # (bq, d)
        k = k_ref[...].astype(jnp.float32)            # (bk, d)
        v = v_ref[...].astype(jnp.float32)
        s = _dot(q, k, _NT)                           # (bq, bk)
        s = _mask_scores(s, qi, kb, block_q, block_k, causal, kv_len,
                         qseg_ref[...] if has_seg else None,
                         kseg_ref[...] if has_seg else None)
        m_prev = m_ref[...]                           # (bq, 1)
        l_prev = l_ref[...]
        m_new = jnp.maximum(m_prev,
                            jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        m_ref[...] = m_new
        l_ref[...] = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + _dot(p, v)

    @pl.when(kb == n_kb - 1)
    def _finish():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[...] = (acc_ref[...] / l).astype(o_ref.dtype)
        lse_ref[...] = m_ref[...] + jnp.log(l)


def _bwd_dkdv_kernel(q_ref, do_ref, lse_ref, dcap_ref, k_ref, v_ref,
                     *refs, scale, causal, block_q, block_k, n_qb,
                     kv_len, has_seg):
    """Grid = (batch*heads, k_blocks, q_blocks), q innermost: dk/dv
    accumulate in VMEM scratch while q/do/lse/D stream through."""
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    if has_seg:
        qseg_ref, kseg_ref, dk_ref, dv_ref, dk_acc, dv_acc = refs
    else:
        dk_ref, dv_ref, dk_acc, dv_acc = refs
        qseg_ref = kseg_ref = None
    kb = pl.program_id(1)
    qi = pl.program_id(2)

    @pl.when(qi == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    live = True
    if causal:
        # q blocks fully above this k block see none of it
        live = (qi + 1) * block_q - 1 >= kb * block_k

    @pl.when(live)
    def _step():
        q = q_ref[...].astype(jnp.float32)            # (bq, d)
        do = do_ref[...].astype(jnp.float32)          # (bq, d)
        lse = lse_ref[...]                            # (bq, 1)
        dcap = dcap_ref[...]                          # (bq, 1) rowsum(do*o)
        k = k_ref[...].astype(jnp.float32)            # (bk, d)
        v = v_ref[...].astype(jnp.float32)
        s = _dot(q, k, _NT) * scale
        s = _mask_scores(s, qi, kb, block_q, block_k, causal, kv_len,
                         qseg_ref[...] if has_seg else None,
                         kseg_ref[...] if has_seg else None)
        p = jnp.exp(s - lse)                          # (bq, bk)
        dv_acc[...] += _dot(p, do, _TN)
        dp = _dot(do, v, _NT)                         # (bq, bk)
        ds = p * (dp - dcap) * scale
        dk_acc[...] += _dot(ds, q, _TN)

    @pl.when(qi == n_qb - 1)
    def _finish():
        dk_ref[...] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[...] = dv_acc[...].astype(dv_ref.dtype)


def _bwd_dq_kernel(q_ref, do_ref, lse_ref, dcap_ref, k_ref, v_ref,
                   *refs, scale, causal, block_q, block_k, n_kb,
                   kv_len, has_seg):
    """Grid = (batch*heads, q_blocks, k_blocks), k innermost: dq
    accumulates in VMEM scratch while k/v stream through."""
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    if has_seg:
        qseg_ref, kseg_ref, dq_ref, dq_acc = refs
    else:
        dq_ref, dq_acc = refs
        qseg_ref = kseg_ref = None
    qi = pl.program_id(1)
    kb = pl.program_id(2)

    @pl.when(kb == 0)
    def _init():
        dq_acc[...] = jnp.zeros_like(dq_acc)

    live = True
    if causal:
        live = kb * block_k <= (qi + 1) * block_q - 1

    @pl.when(live)
    def _step():
        q = q_ref[...].astype(jnp.float32)
        do = do_ref[...].astype(jnp.float32)
        lse = lse_ref[...]
        dcap = dcap_ref[...]
        k = k_ref[...].astype(jnp.float32)
        v = v_ref[...].astype(jnp.float32)
        s = _dot(q, k, _NT) * scale
        s = _mask_scores(s, qi, kb, block_q, block_k, causal, kv_len,
                         qseg_ref[...] if has_seg else None,
                         kseg_ref[...] if has_seg else None)
        p = jnp.exp(s - lse)
        dp = _dot(do, v, _NT)
        ds = p * (dp - dcap) * scale
        dq_acc[...] += _dot(ds, k)

    @pl.when(kb == n_kb - 1)
    def _finish():
        dq_ref[...] = dq_acc[...].astype(dq_ref.dtype)


_LANES = 128


def _on_tpu():
    return jax.devices()[0].platform == "tpu"


def tiles_on_chip(head_dim, *blocks):
    """The shape predicate of the kernel path: the head dim fills whole
    128-lane vregs and every sequence block is a multiple of the
    128-row MXU tile. Narrower shapes (head_dim 8, a 64-key cache
    bucket) would pad each tile up to 16x in VMEM — or, for the int8
    scale rows, are refused by the lowering outright — so they take
    the jnp composition, which XLA tiles itself."""
    return head_dim % _LANES == 0 and all(b % _LANES == 0 for b in blocks)


def _choose_path(op, head_dim, blocks, force_pallas):
    """The ONE place a kernel-or-jnp decision is made, from the
    platform and the shapes alone, never from a caught compile error.
    True means the kernel; the choice is counted at trace time in
    ``profiler.counters()`` as ``<op>_pallas`` / ``<op>_jnp``.
    ``force_pallas`` takes the kernel whatever the shape (the CPU
    tests, interpreted; on the TPU a shape the chip cannot tile then
    fails to compile, loudly)."""
    from .. import profiler
    use_kernel = force_pallas or (_on_tpu()
                                  and tiles_on_chip(head_dim, *blocks))
    profiler.increment_counter(
        "%s_%s" % (op, "pallas" if use_kernel else "jnp"))
    return use_kernel


def _dispatch(op, head_dim, blocks, force_pallas, kernel, composed,
              *args):
    """``kernel(interpret, *args)`` or ``composed(*args)``, as
    :func:`_choose_path` says. In a process without a TPU the kernel is
    interpreted (the CPU tests). In a process with one, the Mosaic
    kernel runs wherever the program lands on the TPU, and where it
    lands on the host CPU device (``mx.cpu()`` arrays in a TPU process)
    the composition does — or the interpreter under ``force_pallas``:
    JAX resolves ``platform_dependent`` at lowering, from the device
    the computation really runs on, so nothing on the chip is ever
    interpreted and nothing on the host ever meets a Mosaic call."""
    if not _choose_path(op, head_dim, blocks, force_pallas):
        return composed(*args)
    if not _on_tpu():
        return kernel(True, *args)
    return jax.lax.platform_dependent(
        *args, tpu=functools.partial(kernel, False),
        default=functools.partial(kernel, True) if force_pallas
        else composed)


def _pick_block(t_padded, pref):
    return pref if t_padded % pref == 0 else _LANES


def _blocks(Tq, Tk, block_q, block_k):
    """(tq_pad, tk_pad, bq, bk): lengths padded to the lane multiple
    and the block that tiles each."""
    tq_pad = -(-Tq // _LANES) * _LANES
    tk_pad = -(-Tk // _LANES) * _LANES
    return (tq_pad, tk_pad, _pick_block(tq_pad, block_q),
            _pick_block(tk_pad, block_k))


def _pad_seq(x, t_padded):
    import jax.numpy as jnp
    pad = t_padded - x.shape[1]
    if pad == 0:
        return x
    return jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))


def _flatten(x):
    import jax.numpy as jnp
    B, T, H, D = x.shape
    return jnp.moveaxis(x, 2, 1).reshape(B * H, T, D)


def _unflatten(x, B, H):
    import jax.numpy as jnp
    BH, T, D = x.shape
    return jnp.moveaxis(x.reshape(B, H, T, D), 1, 2)


def _col_spec(block, index_map):
    """Block of a per-row plane kept as a ``(BH, T, 1)`` column: the
    kernel sees ``(block, 1)``, which broadcasts along the lanes of a
    ``(block_q, block_k)`` score tile. The TPU lowering tiles the last
    two dims of a block by (8, 128) unless they span the array, which
    a rank-2 ``(None, block)`` plane cannot satisfy."""
    from jax.experimental import pallas as pl
    return pl.BlockSpec((None, block, 1), index_map)


def _row_spec(block, index_map):
    """Block of a per-key plane kept as a ``(BH, 1, T)`` row: the
    kernel sees ``(1, block)``, broadcasting along sublanes."""
    from jax.experimental import pallas as pl
    return pl.BlockSpec((None, 1, block), index_map)


def _kernel_name(kernel, q, k):
    """``mx_<kernel>.bh<B*H>.q<Tq>.k<Tk>.d<D>.<cache dtype>``: the name
    a ``pallas_call`` carries into the program. XLA names the Mosaic
    custom call after it (``%mx_flash_fwd.bh32.q512.k512.d128.bfloat16.1
    = ... custom-call``; under differentiation with ``jvp_`` or
    ``transpose_jvp_`` in front) and a profile names an operation's
    event by that line, so a reader of the trace finds the kernel and
    the call's static shapes (padded lengths) in the event's name."""
    import jax.numpy as jnp
    return "mx_%s.bh%d.q%d.k%d.d%d.%s" % (
        kernel, q.shape[0], q.shape[1], k.shape[1], q.shape[2],
        jnp.dtype(k.dtype).name)


def _pallas_forward(q, k, v, seg, scale, causal, block_q, block_k,
                    kv_len, interpret):
    """Padded/flattened forward; returns (out, lse) at PADDED length,
    ``lse`` a ``(BH, T, 1)`` column. ``seg`` is the (BH, T) int32
    segment-id plane of a packed batch (or None) — streamed blockwise
    next to q and k, as a column for the q side and a row for the k
    side."""
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    BH, Tq, D = q.shape
    Tk = k.shape[1]
    n_kb = Tk // block_k

    scratch = [pltpu.VMEM((block_q, D), jnp.float32),
               pltpu.VMEM((block_q, 1), jnp.float32),
               pltpu.VMEM((block_q, 1), jnp.float32)]

    in_specs = [
        pl.BlockSpec((None, block_q, D), lambda b, i, j: (b, i, 0)),
        pl.BlockSpec((None, block_k, D), lambda b, i, j: (b, j, 0)),
        pl.BlockSpec((None, block_k, D), lambda b, i, j: (b, j, 0)),
    ]
    inputs = [q, k, v]
    if seg is not None:
        in_specs += [_col_spec(block_q, lambda b, i, j: (b, i, 0)),
                     _row_spec(block_k, lambda b, i, j: (b, 0, j))]
        inputs += [seg[:, :, None], seg[:, None, :]]
    out, lse = pl.pallas_call(
        functools.partial(_fwd_kernel, scale=scale, causal=causal,
                          block_q=block_q, block_k=block_k, n_kb=n_kb,
                          kv_len=kv_len, has_seg=seg is not None),
        grid=(BH, Tq // block_q, n_kb),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((None, block_q, D), lambda b, i, j: (b, i, 0)),
            _col_spec(block_q, lambda b, i, j: (b, i, 0)),
        ],
        out_shape=[jax.ShapeDtypeStruct((BH, Tq, D), q.dtype),
                   jax.ShapeDtypeStruct((BH, Tq, 1), jnp.float32)],
        scratch_shapes=scratch,
        interpret=interpret,
        name=_kernel_name("flash_fwd", q, k),
    )(*inputs)
    return out, lse


def _pallas_backward(q, k, v, do, o, lse, seg, scale, causal, block_q,
                     block_k, kv_len, interpret):
    """Padded/flattened backward; q/k/v/do/o at padded lengths."""
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    BH, Tq, D = q.shape
    Tk = k.shape[1]
    n_qb = Tq // block_q
    n_kb = Tk // block_k
    has_seg = seg is not None
    # D_i = rowsum(dO * O): one cheap fused pass in XLA
    dcap = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32),
                   axis=-1, keepdims=True)

    in_specs = [
        pl.BlockSpec((None, block_q, D), lambda b, j, i: (b, i, 0)),
        pl.BlockSpec((None, block_q, D), lambda b, j, i: (b, i, 0)),
        _col_spec(block_q, lambda b, j, i: (b, i, 0)),
        _col_spec(block_q, lambda b, j, i: (b, i, 0)),
        pl.BlockSpec((None, block_k, D), lambda b, j, i: (b, j, 0)),
        pl.BlockSpec((None, block_k, D), lambda b, j, i: (b, j, 0)),
    ]
    inputs = [q, do, lse, dcap, k, v]
    if has_seg:
        in_specs += [_col_spec(block_q, lambda b, j, i: (b, i, 0)),
                     _row_spec(block_k, lambda b, j, i: (b, 0, j))]
        inputs += [seg[:, :, None], seg[:, None, :]]
    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkdv_kernel, scale=scale, causal=causal,
                          block_q=block_q, block_k=block_k, n_qb=n_qb,
                          kv_len=kv_len, has_seg=has_seg),
        grid=(BH, n_kb, n_qb),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((None, block_k, D), lambda b, j, i: (b, j, 0)),
            pl.BlockSpec((None, block_k, D), lambda b, j, i: (b, j, 0)),
        ],
        out_shape=[jax.ShapeDtypeStruct((BH, Tk, D), k.dtype),
                   jax.ShapeDtypeStruct((BH, Tk, D), v.dtype)],
        scratch_shapes=[pltpu.VMEM((block_k, D), jnp.float32),
                        pltpu.VMEM((block_k, D), jnp.float32)],
        interpret=interpret,
        name=_kernel_name("flash_bwd_dkdv", q, k),
    )(*inputs)

    in_specs = [
        pl.BlockSpec((None, block_q, D), lambda b, i, j: (b, i, 0)),
        pl.BlockSpec((None, block_q, D), lambda b, i, j: (b, i, 0)),
        _col_spec(block_q, lambda b, i, j: (b, i, 0)),
        _col_spec(block_q, lambda b, i, j: (b, i, 0)),
        pl.BlockSpec((None, block_k, D), lambda b, i, j: (b, j, 0)),
        pl.BlockSpec((None, block_k, D), lambda b, i, j: (b, j, 0)),
    ]
    inputs = [q, do, lse, dcap, k, v]
    if has_seg:
        in_specs += [_col_spec(block_q, lambda b, i, j: (b, i, 0)),
                     _row_spec(block_k, lambda b, i, j: (b, 0, j))]
        inputs += [seg[:, :, None], seg[:, None, :]]
    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, scale=scale, causal=causal,
                          block_q=block_q, block_k=block_k, n_kb=n_kb,
                          kv_len=kv_len, has_seg=has_seg),
        grid=(BH, n_qb, n_kb),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((None, block_q, D),
                               lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, Tq, D), q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, D), jnp.float32)],
        interpret=interpret,
        name=_kernel_name("flash_bwd_dq", q, k),
    )(*inputs)
    return dq, dk, dv


def _seg_flat(seg, H, t_pad):
    """(B, T) int32 segment ids -> the kernels' (B*H, T_pad) plane:
    padded tail positions get id 0 (attend to/attended by nothing),
    rows repeat per head to match the flattened batch*heads axis."""
    import jax.numpy as jnp
    seg = jnp.asarray(seg, jnp.int32)
    pad = t_pad - seg.shape[1]
    if pad:
        seg = jnp.pad(seg, ((0, 0), (0, pad)))
    return jnp.repeat(seg, H, axis=0)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8))
def _flash(q, k, v, seg, scale, causal, block_q, block_k, interpret):
    out, _ = _flash_fwd(q, k, v, seg, scale, causal, block_q, block_k,
                        interpret)
    return out


def _flash_fwd(q, k, v, seg, scale, causal, block_q, block_k,
               interpret):
    import jax.numpy as jnp
    B, Tq, H, D = q.shape
    Tk = k.shape[1]
    tq_pad, tk_pad, bq, bk = _blocks(Tq, Tk, block_q, block_k)
    qf = _flatten(_pad_seq(q, tq_pad))
    kf = _flatten(_pad_seq(k, tk_pad))
    vf = _flatten(_pad_seq(v, tk_pad))
    # self-attention: q and k index the same positions, one plane
    # serves both sides (tq_pad == tk_pad by construction)
    segf = None if seg is None else _seg_flat(seg, H, tq_pad)
    outf, lse = _pallas_forward(qf, kf, vf, segf, scale, causal, bq,
                                bk, Tk, interpret)
    out = _unflatten(outf, B, H)[:, :Tq]
    return out, (q, k, v, seg, outf, lse)


def _flash_fwd_rule(q, k, v, seg, scale, causal, block_q, block_k,
                    interpret):
    out, res = _flash_fwd(q, k, v, seg, scale, causal, block_q,
                          block_k, interpret)
    return out, res


def _flash_bwd(scale, causal, block_q, block_k, interpret, res, g):
    import jax.numpy as jnp
    q, k, v, seg, outf, lse = res
    B, Tq, H, D = q.shape
    Tk = k.shape[1]
    tq_pad, tk_pad, bq, bk = _blocks(Tq, Tk, block_q, block_k)
    qf = _flatten(_pad_seq(q, tq_pad))
    kf = _flatten(_pad_seq(k, tk_pad))
    vf = _flatten(_pad_seq(v, tk_pad))
    dof = _flatten(_pad_seq(g, tq_pad))
    segf = None if seg is None else _seg_flat(seg, H, tq_pad)
    dqf, dkf, dvf = _pallas_backward(qf, kf, vf, dof, outf, lse, segf,
                                     scale, causal, bq, bk, Tk,
                                     interpret)
    dq = _unflatten(dqf, B, H)[:, :Tq]
    dk = _unflatten(dkf, B, H)[:, :Tk]
    dv = _unflatten(dvf, B, H)[:, :Tk]
    return dq, dk, dv, None


_flash.defvjp(_flash_fwd_rule, _flash_bwd)


# ---------------------------------------------------------------------------
# query-length-1 cached-KV decode path (autoregressive serving)
# ---------------------------------------------------------------------------

def _jnp_decode(q, k, v, lengths, scale):
    """The decode reference: same formula as :func:`_jnp_reference`
    with the causal triangle replaced by a per-row valid-key count —
    position ``i`` of row ``b`` is live iff ``i < lengths[b]``. A
    blocked key's softmax weight is an exact IEEE zero (``exp`` of
    ``_NEG - max`` underflows), so a row's result depends only on its
    own live keys, never on the gathered cache's garbage tail."""
    import jax.numpy as jnp
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    T = k.shape[1]
    live = jax.lax.iota(jnp.int32, T)[None, :] \
        < jnp.asarray(lengths, jnp.int32)[:, None]       # (B, T)
    s = jnp.where(live[:, None, None, :], s, _NEG)
    p = jnp.asarray(
        jnp.exp(s - jnp.max(s, axis=-1, keepdims=True)), q.dtype)
    p = p / jnp.maximum(jnp.sum(p, axis=-1, keepdims=True), 1e-30)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v)


def _decode_accumulate(q, k, v, ks, vs, len_ref, o_ref, acc_ref, m_ref,
                       l_ref, scale, block_k, n_kb):
    """The shared streaming-softmax step for one (q-row, k-block)
    program instance — ``q``/``k``/``v`` are the block's fp32 values.
    ``ks``/``vs`` are the int8 cache's ``(1, bk)`` per-position scale
    rows (or None): ``q·(k*s) == (q·k)*s`` and ``p @ (v*s) == (p*s) @
    v``, so they fold into the score and probability rows instead of
    dequantizing the ``(bk, D)`` blocks. Running max/sum accumulators
    live in VMEM scratch; the accumulation order matches the forward
    kernel's for a single q row at the same ``block_k``."""
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    kb = pl.program_id(1)

    @pl.when(kb == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
        l_ref[...] = jnp.zeros_like(l_ref)

    s = _dot(q * scale, k, _NT)                       # (1, bk)
    if ks is not None:
        s = s * ks
    k_pos = kb * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (1, block_k), 1)
    s = jnp.where(k_pos < len_ref[pl.program_id(0)], s, _NEG)
    m_prev = m_ref[...]                               # (1, 1)
    l_prev = l_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new)
    m_ref[...] = m_new
    l_ref[...] = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha \
        + _dot(p if vs is None else p * vs, v)

    @pl.when(kb == n_kb - 1)
    def _finish():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[...] = (acc_ref[...] / l).astype(o_ref.dtype)


def _decode_kernel(len_ref, q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref,
                   l_ref, *, scale, block_k, n_kb):
    """Grid = (batch*heads, k_blocks), k innermost: one query row per
    program instance (see :func:`_decode_accumulate`). ``len_ref`` is
    the scalar-prefetched ``(BH,)`` valid-key count in SMEM."""
    import jax.numpy as jnp
    _decode_accumulate(q_ref[...].astype(jnp.float32),
                       k_ref[...].astype(jnp.float32),
                       v_ref[...].astype(jnp.float32), None, None,
                       len_ref, o_ref, acc_ref, m_ref, l_ref,
                       scale, block_k, n_kb)


def _decode_kernel_q8(len_ref, q_ref, k_ref, v_ref, ks_ref, vs_ref,
                      o_ref, acc_ref, m_ref, l_ref, *, scale, block_k,
                      n_kb):
    """The int8-cache decode kernel: K/V blocks arrive quantized, so
    HBM traffic for the cache is a quarter of the fp32 kernel's; the
    per-position scales apply inside the block stream
    (:func:`_decode_accumulate`)."""
    import jax.numpy as jnp
    _decode_accumulate(q_ref[...].astype(jnp.float32),
                       k_ref[...].astype(jnp.float32),
                       v_ref[...].astype(jnp.float32),
                       ks_ref[...], vs_ref[...],
                       len_ref, o_ref, acc_ref, m_ref, l_ref,
                       scale, block_k, n_kb)


def _pallas_decode(q, k, v, lengths, scale, block_k, interpret,
                   k_scale=None, v_scale=None):
    """``q`` (BH, 1, D), ``k``/``v`` (BH, T, D), ``lengths`` (BH,)
    int32 (scalar-prefetched into SMEM), scales (BH, T) fp32."""
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    BH, _, D = q.shape
    Tk = k.shape[1]
    n_kb = Tk // block_k
    quant = k_scale is not None
    scale_spec = _row_spec(block_k, lambda b, j, lens: (b, 0, j))
    kern = functools.partial(
        _decode_kernel_q8 if quant else _decode_kernel,
        scale=scale, block_k=block_k, n_kb=n_kb)
    out = pl.pallas_call(
        kern,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(BH, n_kb),
            in_specs=[
                pl.BlockSpec((None, 1, D), lambda b, j, lens: (b, 0, 0)),
                pl.BlockSpec((None, block_k, D),
                             lambda b, j, lens: (b, j, 0)),
                pl.BlockSpec((None, block_k, D),
                             lambda b, j, lens: (b, j, 0)),
            ] + ([scale_spec, scale_spec] if quant else []),
            out_specs=pl.BlockSpec((None, 1, D),
                                   lambda b, j, lens: (b, 0, 0)),
            scratch_shapes=[pltpu.VMEM((1, D), jnp.float32),
                            pltpu.VMEM((1, 1), jnp.float32),
                            pltpu.VMEM((1, 1), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((BH, 1, D), q.dtype),
        interpret=interpret,
        name=_kernel_name("flash_decode_q8" if quant else "flash_decode",
                          q, k),
    )(*((lengths, q, k, v)
        + ((k_scale[:, None, :], v_scale[:, None, :]) if quant
           else ())))
    return out


def _paged_decode_kernel(tbl_ref, len_ref, q_ref, kn_ref, vn_ref, k_ref,
                         v_ref, *refs, scale, page_size, n_pages, chunk,
                         quant):
    """Grid = (rows, head blocks, table columns), columns innermost: one
    program instance attends ``hb`` heads of one row to ONE page, read
    where it lies in the pool — ``k_ref``/``v_ref`` are the page's
    ``(S, hb, D)`` block, token-major as the pool stores it. A query of
    length 1 is a matrix-vector product a head, so the VPU does it in
    that layout with no relayout and in full fp32: ``sum_d k*q`` along
    the lanes gives ``(tokens, hb, 1)`` score columns, the softmax
    reduces over the leading token axis, and ``sum_t p*v`` accumulates
    ``(hb, D)`` — :func:`_decode_accumulate`'s streaming softmax,
    ``chunk`` tokens a time so the carried max/sum/accumulator stay in
    registers.

    ``len_ref[b]`` counts the row's keys IN THE POOL; the new token's
    own key/value (``kn_ref``/``vn_ref``, not in the pool yet) open the
    accumulation as the first key. Columns at or past ``ceil(len /
    S)`` are dead: the caller's table re-names the last live page there,
    so the pipeline fetches nothing, and ``pl.when`` skips the arithmetic.
    An int8 pool brings its pages' scales as ``(rows, columns)`` SMEM
    scalars: ``q·(k*s) == (q·k)*s`` and ``p@(v*s) == (p@v)*s``, applied
    to the chunk's scores and partial sum."""
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    if quant:
        ks_ref, vs_ref, o_ref, acc_ref, m_ref, l_ref = refs
    else:
        o_ref, acc_ref, m_ref, l_ref = refs
    b = pl.program_id(0)
    j = pl.program_id(2)
    length = len_ref[b]
    q = q_ref[...].astype(jnp.float32) * scale            # (hb, D)

    @pl.when(j == 0)
    def _init():
        kn = kn_ref[...].astype(jnp.float32)
        m_ref[...] = jnp.sum(q * kn, axis=-1, keepdims=True)
        l_ref[...] = jnp.ones_like(l_ref)
        acc_ref[...] = vn_ref[...].astype(jnp.float32)

    @pl.when(j * page_size < length)
    def _step():
        def body(c, carry):
            m_prev, l_prev, acc = carry
            rows = pl.ds(c * chunk, chunk)
            k = k_ref[rows].astype(jnp.float32)           # (chunk, hb, D)
            v = v_ref[rows].astype(jnp.float32)
            s = jnp.sum(k * q[None], axis=-1, keepdims=True)
            if quant:
                s = s * ks_ref[b, j]
            pos = j * page_size + c * chunk \
                + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
            s = jnp.where(pos < length, s, _NEG)          # (chunk, hb, 1)
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=0))
            alpha = jnp.exp(m_prev - m_new)
            p = jnp.exp(s - m_new[None])
            pv = jnp.sum(p * v, axis=0)                   # (hb, D)
            if quant:
                pv = pv * vs_ref[b, j]
            return (m_new, l_prev * alpha + jnp.sum(p, axis=0),
                    acc * alpha + pv)

        m_ref[...], l_ref[...], acc_ref[...] = jax.lax.fori_loop(
            0, page_size // chunk, body,
            (m_ref[...], l_ref[...], acc_ref[...]))

    @pl.when(j == n_pages - 1)
    def _finish():
        # the new token is always live, so l >= its weight > 0
        o_ref[...] = (acc_ref[...] / l_ref[...]).astype(o_ref.dtype)


_PAGE_BLOCK_BYTES = 2 << 20
_PAGE_CHUNK = 16     # tokens of a page the kernel folds in at a time


def _heads_per_block(n_heads, page_size, head_dim, dtype):
    """Heads a program instance of the paged kernel takes: the most
    that keep a page's ``(S, hb, D)`` block inside 2 MB (K and V, each
    double-buffered, then fill half of the 16 MB a kernel may use of
    VMEM) — all of them, or a divisor of ``H`` that fills the dtype's
    sublane tile (8 rows of 32 bits); all of them too when none fits."""
    import jax.numpy as jnp
    itemsize = jnp.dtype(dtype).itemsize
    tile = 8 * 4 // itemsize
    for hb in range(n_heads, 0, -1):
        if n_heads % hb == 0 and (hb == n_heads or hb % tile == 0) \
                and page_size * hb * head_dim * itemsize \
                <= _PAGE_BLOCK_BYTES:
            return hb
    return n_heads


def _pallas_paged_decode(q, k_new, v_new, k_pages, v_pages, layer,
                         page_table, lengths, scale, interpret,
                         k_scale=None, v_scale=None):
    """``q``/``k_new``/``v_new`` (B, H, D); the WHOLE pools ``(L, P, S,
    H, D)`` — ``layer`` (static) is picked in the index map, so no
    operand is a slice of the pool that XLA would have to copy;
    ``page_table`` (B, M) and ``lengths`` (B,) int32 — the keys each
    row has IN the pool — ride scalar prefetch (SMEM) and steer the
    page fetches; ``k_scale``/``v_scale`` (B, M) fp32 are an int8
    pool's page scales as the table names them."""
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, H, D = q.shape
    S = k_pages.shape[2]
    M = page_table.shape[1]
    hb = _heads_per_block(H, S, D, k_pages.dtype)
    quant = k_scale is not None

    # a dead column names the row's last live page again: the block
    # index does not change, so the pipeline fetches nothing for it
    last = jnp.maximum((lengths + S - 1) // S, 1) - 1
    columns = jnp.minimum(jax.lax.iota(jnp.int32, M)[None], last[:, None])
    page_table = jnp.take_along_axis(page_table, columns, axis=1)

    row = pl.BlockSpec((None, hb, D), lambda b, h, j, tbl, lens: (b, h, 0))
    page = pl.BlockSpec(
        (None, None, S, hb, D),
        lambda b, h, j, tbl, lens: (layer, tbl[b * M + j], 0, h, 0))
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    return pl.pallas_call(
        functools.partial(_paged_decode_kernel, scale=scale, page_size=S,
                          n_pages=M, chunk=math.gcd(S, _PAGE_CHUNK),
                          quant=quant),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B, H // hb, M),
            in_specs=[row, row, row, page, page]
            + ([smem, smem] if quant else []),
            out_specs=row,
            scratch_shapes=[pltpu.VMEM((hb, D), jnp.float32),
                            pltpu.VMEM((hb, 1), jnp.float32),
                            pltpu.VMEM((hb, 1), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((B, H, D), q.dtype),
        interpret=interpret,
        # the contiguous kernel's name at the table's full width: a
        # reader of the profile finds both under mx_flash_decode
        name="mx_flash_decode.bh%d.q1.k%d.d%d.%s.paged" % (
            B * H, M * S, D, jnp.dtype(k_pages.dtype).name),
    )(*((page_table.reshape(-1), lengths, q, k_new, v_new, k_pages,
         v_pages) + ((k_scale, v_scale) if quant else ())))


# ---------------------------------------------------------------------------
# latent (MLA) decode over a one-array paged cache
# ---------------------------------------------------------------------------

def _jnp_latent_decode(q, kv, lengths, rank):
    """The absorbed-form reference: ``q (B, H, W)`` already scaled, ``kv
    (B, T, W)`` one latent row a token, shared by every head — its first
    ``rank`` columns are the compressed K/V, the rest the shared rotary
    key. Scores over all ``W`` columns, float32 softmax over the row's
    ``lengths`` live tokens, the weighted sum over the first ``rank``
    columns only: ``(B, H, rank)`` float32. A ``q (B, Q, H, W)`` is the
    CAUSAL form over ``Q`` consecutive positions a row
    (:func:`_jnp_latent_verify`): ``lengths`` then counts query 0's live
    tokens."""
    import jax.numpy as jnp
    if q.ndim == 4:
        return _jnp_latent_verify(q, kv, lengths, rank)
    qf, kf = q.astype(jnp.float32), kv.astype(jnp.float32)
    s = jnp.einsum("bhw,btw->bht", qf, kf)
    live = jax.lax.iota(jnp.int32, kv.shape[1])[None, :] \
        < jnp.asarray(lengths, jnp.int32)[:, None]
    s = jnp.where(live[:, None, :], s, _NEG)
    p = jnp.exp(s - jnp.max(s, axis=-1, keepdims=True))
    p = p / jnp.sum(p, axis=-1, keepdims=True)
    return jnp.einsum("bht,btc->bhc", p, kf[..., :rank])


_WALK_DEPTH = 6      # page buffers a pool: two being folded, four in flight


def _lanes(x, n):
    """``x (rows, 128)``, every lane of a row the same value, as ``(rows,
    n)``: how the softmax state of the paged MXU kernels (running max,
    running sum) meets a score tile or an accumulator ``n`` lanes wide.
    Kept ``(rows, 1)``, a column fills one lane of as many vregs and
    every use is a lane broadcast through the XLU (48 permutes a folded
    page in the one-query latent kernel)."""
    from jax.experimental.pallas import tpu as pltpu
    if n <= x.shape[1]:
        return x[:, :n]
    return pltpu.repeat(x, n // x.shape[1], axis=1)


def _walk_scratch(*pages):
    """The scratch :func:`_walk_pages` needs for pools whose pages are
    ``pages`` (``ShapeDtypeStruct``s): a ring of ``_WALK_DEPTH`` page
    buffers a pool, a DMA semaphore a buffer, the head's four counters."""
    import jax.numpy as jnp
    from jax.experimental.pallas import tpu as pltpu
    return [pltpu.VMEM((_WALK_DEPTH,) + page.shape, page.dtype)
            for page in pages] \
        + [pltpu.SemaphoreType.DMA((len(pages), _WALK_DEPTH)),
           pltpu.SMEM((4,), jnp.int32)]


def _walk_pages(tbl_ref, len_ref, layer, pool_refs, buf_refs, sem_ref,
                ring_ref, fold):
    """The page walk of the MXU paged kernels, written once. Grid =
    (rows,): program instance ``row`` folds its OWN live pages — the
    first ``ceil(len_ref[row] / S)`` columns of its row of the flat
    table ``tbl_ref`` — in column order, and nothing past them: a dead
    column is never named, copied or branched over, and a row with
    nothing in the pool waits for nothing.

    ``pool_refs`` are the whole ``(L, P, S, ...)`` pools, left in HBM,
    of which ``layer`` (a scalar, traced or not) is read; ``buf_refs``
    one ``(depth, S, ...)`` VMEM ring a pool, ``sem_ref`` a ``(pools,
    depth)`` array of DMA semaphores, ``ring_ref`` four SMEM counters
    (:func:`_walk_scratch` declares all three). To the copies the live
    pages of the whole call, row after row, are ONE sequence: the head
    (``ring_ref``, SMEM: its row, its column, pages started, pages
    folded) runs ``depth - 2`` pages in front of the fold and over the
    rows' ends, so a row's first pages arrive under the row before's
    folds. Every copy started is waited for by the row that owns the
    page.

    ``fold([(j, page_refs), ...])`` is the kernel's arithmetic on table
    columns ``j``, in the order given. It is handed TWO pages a loop
    body (and a last odd one alone): a page's fold is one dependent
    chain — score product, row max, exponentials, value product,
    rescale — and with two in one body the scheduler runs the second
    page's score product under the first one's softmax."""
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    row, rows = pl.program_id(0), len_ref.shape[0]
    n_pages = tbl_ref.shape[0] // rows
    depth, page_size = buf_refs[0].shape[:2]

    def n_live(r):
        return (len_ref[r] + page_size - 1) // page_size

    def copies(r, j, slot):
        page = tbl_ref[r * n_pages + j]
        return [pltpu.make_async_copy(pool.at[layer, page], buf.at[slot],
                                      sem_ref.at[i, slot])
                for i, (pool, buf) in enumerate(zip(pool_refs, buf_refs))]

    def next_live(r):
        """The first row at or after ``r`` with a page in the pool."""
        return jax.lax.while_loop(
            lambda r: jnp.logical_and(
                r < rows, n_live(jnp.minimum(r, rows - 1)) == 0),
            lambda r: r + 1, r)

    def start_head():
        r, c, started = ring_ref[0], ring_ref[1], ring_ref[2]

        @pl.when(r < rows)
        def _start():
            for copy in copies(r, c, jax.lax.rem(started, depth)):
                copy.start()
            last = c + 1 >= n_live(r)
            ring_ref[0] = jnp.where(last, next_live(r + 1), r)
            ring_ref[1] = jnp.where(last, 0, c + 1)
            ring_ref[2] = started + 1

    @pl.when(row == 0)
    def _prime():
        ring_ref[0] = next_live(0)
        ring_ref[1] = 0
        ring_ref[2] = 0
        ring_ref[3] = 0
        for _ in range(depth - 2):
            start_head()

    def step(j, count):
        """Fold this row's pages ``j .. j + count - 1`` (``count``
        static): the head moves on as many, so ``depth - 2 + count``
        pages at most are in their buffers or on their way."""
        for _ in range(count):
            start_head()
        folded = ring_ref[3]
        pages = []
        for k in range(count):
            slot = jax.lax.rem(folded + k, depth)
            for copy in copies(row, j + k, slot):
                copy.wait()
            pages.append((j + k, [buf.at[slot] for buf in buf_refs]))
        fold(pages)
        ring_ref[3] = folded + count

    n = n_live(row)

    def pair(i, carry):
        step(2 * i, 2)
        return carry

    jax.lax.fori_loop(0, n // 2, pair, 0)

    @pl.when(n % 2 == 1)
    def _odd():
        step(n - 1, 1)


def _mla_walk(tbl_ref, len_ref, layer_ref, q_ref, pool_ref, o_ref, acc_ref,
              m_ref, l_ref, buf_ref, sem_ref, ring_ref, page_size, rank):
    """What both latent kernels do once the step's own rows have opened
    the accumulation: walk the row's live pages (:func:`_walk_pages`),
    folding each page's live tokens into the running softmax — every
    query row of ``q_ref`` against the page in one product — and write
    the normalised sum out."""
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    b = pl.program_id(0)
    length = len_ref[b]

    def fold(pages):
        scores = []
        for j, (page_ref,) in pages:
            s = _dot(q_ref[...], page_ref[...], _NT)          # (rows, S)
            pos = j * page_size + jax.lax.broadcasted_iota(
                jnp.int32, (1, page_size), 1)
            scores.append(jnp.where(pos < length, s, _NEG))
        for (_, (page_ref,)), s in zip(pages, scores):
            lat = page_ref[:, :rank]                          # (S, rank)
            m_prev = m_ref[...]                               # (rows, 128)
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            p = jnp.exp(s - _lanes(m_new, page_size))
            m_ref[...] = m_new
            l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1,
                                                      keepdims=True)
            acc_ref[...] = acc_ref[...] * _lanes(alpha, rank) \
                + _dot(p.astype(lat.dtype), lat)

    _walk_pages(tbl_ref, len_ref, layer_ref[0], [pool_ref], [buf_ref],
                sem_ref, ring_ref, fold)
    # the step's own first row is always live, so l >= its weight > 0
    o_ref[...] = (acc_ref[...] / _lanes(l_ref[...], rank)).astype(
        o_ref.dtype)


def _mla_decode_kernel(tbl_ref, len_ref, layer_ref, q_ref, new_ref, pool_ref,
                       o_ref, acc_ref, m_ref, l_ref, buf_ref, sem_ref,
                       ring_ref, *, page_size, rank):
    """Grid = (rows,): one program instance attends ALL heads of one row
    to the row's live pages of the latent pool, which it walks itself
    (:func:`_walk_pages`) — a page is an ``(S, W)`` block, one row a
    token, the same for every head. That is what makes this an MXU
    kernel where the per-head paged kernel is VPU arithmetic: the scores
    of a page are one ``(H, W) x (W, S)`` product and the weighted sum
    one ``(H, S) x (S, rank)``, bf16 operands, float32 accumulation,
    float32 running softmax.

    **How the columns are laid.** A token's row is ``W`` = 640 columns
    for the 576 = 512 + 64 = 4.5 lane tiles it holds: the compressed
    K/V first, four whole 128-lane tiles, which the value product reads
    with no relayout; the 64 rotary columns in the first half of the
    fifth tile; the second half zero. A tiled row-major array whose
    rows are 576 wide takes 640 columns of HBM all the same, and XLA,
    to avoid that padding, lays a ``(..., 128, 576)`` array out with the
    TOKEN axis minor and copies the whole pool to row-major in front of
    every kernel call and back (three 0.68 GB copies a step in the
    sandbox compile for a v5e). Declared 640 wide, the pool is
    row-major by default, the score is ONE product over all five tiles
    (the query's padding columns are zero) and nothing is copied.

    ``len_ref[b]`` counts the row's tokens IN THE POOL; the step's own
    latent (``new_ref``, not in the pool yet) opens the accumulation as
    the first key, as in the per-head paged kernel. The walk is
    ``ceil(len / S)`` pages long whatever the table's width: a table
    column past it is never looked at. The running max and sum
    (``m_ref``, ``l_ref``) are ``(H, 128)``, every lane of a row the
    same value (:func:`_lanes`)."""
    import jax.numpy as jnp

    new = new_ref[...].astype(jnp.float32)                    # (1, W)
    m_ref[...] = jnp.broadcast_to(
        jnp.sum(q_ref[...].astype(jnp.float32) * new, axis=-1,
                keepdims=True), m_ref.shape)
    l_ref[...] = jnp.ones_like(l_ref)
    acc_ref[...] = jnp.broadcast_to(new[:, :rank], acc_ref.shape)

    _mla_walk(tbl_ref, len_ref, layer_ref, q_ref, pool_ref, o_ref, acc_ref,
              m_ref, l_ref, buf_ref, sem_ref, ring_ref, page_size, rank)


@functools.cache
def _traced_once(call, *static):
    """``call`` as a jitted function of its own (through
    ``compile_watch.jit``, as every jit of the package): a program that
    calls it once a layer with the layer an OPERAND traces and lowers
    the kernel inside once. Tracing a kernel's body is a few hundred
    small operations of Python; with the layer index baked into it,
    every layer's kernel was another one, and the walk's larger bodies
    cost 6 s of a seven-layer model's warm-up (my chip run, PR 34)."""
    from .. import compile_watch
    return compile_watch.jit(call, "kernel:" + call.__name__.lstrip("_"),
                             storm=False, static_argnames=static)


def _pallas_latent(q, kv_new, kv_pages, layer, page_table, lengths, *, rank,
                   n_new, interpret):
    """The ``pallas_call`` of both latent kernels: ``q (B, n_new * H, W)``
    and ``kv_new (B, n_new, W)`` a block a row; the WHOLE pool ``(L, P,
    S, W)`` stays in HBM, where the kernel's own copies read it (no
    operand is a slice of it that XLA would have to copy);
    ``page_table (B, M)``, ``lengths (B,)`` and ``layer (1,)`` ride
    scalar prefetch (the layer an operand: :func:`_traced_once`).
    Returns ``(B, n_new * H, rank)`` float32."""
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, rows, W = q.shape
    S = kv_pages.shape[2]
    M = page_table.shape[1]
    static = dict(page_size=S, rank=rank)
    if n_new == 1:
        kernel = functools.partial(_mla_decode_kernel, **static)
    else:
        kernel = functools.partial(_mla_verify_kernel, n_new=n_new,
                                   n_heads=rows // n_new, **static)

    def row(shape):
        return pl.BlockSpec((None,) + shape, lambda b, *prefetch: (b, 0, 0))

    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(B,),
            in_specs=[row((rows, W)), row((n_new, W)),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=row((rows, rank)),
            scratch_shapes=[pltpu.VMEM((rows, rank), jnp.float32),
                            pltpu.VMEM((rows, _LANES), jnp.float32),
                            pltpu.VMEM((rows, _LANES), jnp.float32)]
            + _walk_scratch(jax.ShapeDtypeStruct((S, W), kv_pages.dtype))),
        out_shape=jax.ShapeDtypeStruct((B, rows, rank), jnp.float32),
        # the walk's copies run over the rows' ends: rows in order
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        # rows x query positions x heads, the query positions a row, the
        # table's full width in keys, the latent's width: what
        # kernel_costs.shapes reads from the event
        name="mx_mla_decode.bh%d.q%d.k%d.d%d.%s.r%d.paged" % (
            B * rows, n_new, M * S, W, jnp.dtype(kv_pages.dtype).name, rank),
    )(page_table.reshape(-1), lengths, layer, q, kv_new, kv_pages)


def _pallas_latent_decode(q, kv_new, kv_pages, layer, page_table, lengths,
                          rank, interpret):
    """``q (B, H, W)`` scaled, in the pool's dtype; ``kv_new (B, 1, W)``;
    the WHOLE pool ``(L, P, S, W)`` and the ``layer`` to read;
    ``page_table (B, M)`` and ``lengths (B,)``. Returns ``(B, H, rank)``
    float32."""
    import jax.numpy as jnp
    return _traced_once(_pallas_latent, "rank", "n_new", "interpret")(
        q, kv_new, kv_pages, jnp.full((1,), layer, jnp.int32), page_table,
        lengths, rank=rank, n_new=1, interpret=interpret)


def _latent_write_kernel(pg_ref, slot_ref, new_ref, page_ref, out_ref):
    """One program instance puts one row's new latent of one layer into
    its page: the page comes in whole, leaves whole, and differs in the
    one row ``slot_ref[b]`` (a select over the block, so no store at a
    dynamic offset into packed 16-bit rows)."""
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    del pg_ref
    rows = jax.lax.broadcasted_iota(jnp.int32, page_ref.shape, 0)
    out_ref[...] = jnp.where(rows == slot_ref[pl.program_id(0)],
                             new_ref[...], page_ref[...])


def _pallas_latent_write(pages, page_idx, slot, new, interpret):
    """The step's new latent rows ``new (L, B, W)`` into the pool
    ``(L, P, S, W)``, in place (the pool is aliased to the result): row
    ``b`` lands in page ``page_idx[b]`` at ``slot[b]``, in every layer.
    XLA's own row writes would do, but for a 4-D pool its layout
    assignment moves the LAYER axis next to the lanes for them and
    copies the whole pool there and back every step."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    L, P, S, W = pages.shape
    B = new.shape[1]
    page = pl.BlockSpec((None, None, S, W),
                        lambda b, l, pg, sl: (l, pg[b], 0, 0))
    return pl.pallas_call(
        _latent_write_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(B, L),
            in_specs=[pl.BlockSpec((None, None, 1, W),
                                   lambda b, l, pg, sl: (l, b, 0, 0)),
                      page],
            out_specs=page),
        out_shape=jax.ShapeDtypeStruct(pages.shape, pages.dtype),
        input_output_aliases={3: 0},
        interpret=interpret,
        name="mx_latent_write.b%d.l%d.s%d.d%d.%s" % (
            B, L, S, W, pages.dtype.name),
    )(page_idx, slot, new.reshape(L, B, 1, W), pages)


def _jnp_latent_verify(q, kv, lengths, rank):
    """:func:`_jnp_latent_decode` for ``Q`` consecutive query positions
    a row, causal among themselves — the verify step of self-speculation
    and the oracle of ``mx_mla_decode...q<Q>``. ``q (B, Q, H, W)`` scaled;
    ``kv (B, T, W)`` the row's cache with the step's ``Q`` new rows
    already in place at their positions; query ``j`` sees the first
    ``lengths[b] + j`` tokens (its own row the last of them). Returns
    ``(B, Q, H, rank)`` float32."""
    import jax.numpy as jnp
    qf, kf = q.astype(jnp.float32), kv.astype(jnp.float32)
    s = jnp.einsum("bqhw,btw->bqht", qf, kf)
    seen = jnp.asarray(lengths, jnp.int32)[:, None] \
        + jax.lax.iota(jnp.int32, q.shape[1])[None, :]           # (B, Q)
    live = jax.lax.iota(jnp.int32, kv.shape[1])[None, None, :] \
        < seen[:, :, None]
    s = jnp.where(live[:, :, None, :], s, _NEG)
    p = jnp.exp(s - jnp.max(s, axis=-1, keepdims=True))
    p = p / jnp.sum(p, axis=-1, keepdims=True)
    return jnp.einsum("bqht,btc->bqhc", p, kf[..., :rank])


def _mla_verify_kernel(tbl_ref, len_ref, layer_ref, q_ref, new_ref, pool_ref,
                       o_ref, acc_ref, m_ref, l_ref, buf_ref, sem_ref,
                       ring_ref, *, page_size, rank, n_new, n_heads):
    """:func:`_mla_decode_kernel` for ``n_new`` consecutive query
    positions a row: the ``n_new * n_heads`` query vectors of one row
    (position-major: row ``j * n_heads + h``) are the rows of ONE
    ``(Q*H, W) x (W, S)`` product against each live page — 64 rows at 2
    positions of 32 heads, where one position fills a quarter of the
    MXU's rows. ``len_ref[b]`` counts the row's tokens IN THE POOL, all
    visible to every query; the step's own ``n_new`` latents
    (``new_ref``, not in the pool yet) open the accumulation on the VPU,
    folded in TRIANGULARLY: new row ``k`` is visible to query ``j`` iff
    ``k <= j`` (row 0 to both, row 1 to the second only). Then the same
    walk over the row's live pages (:func:`_mla_walk`)."""
    import jax.numpy as jnp
    f32 = jnp.float32

    q = q_ref[...].astype(f32)                                # (Q*H, W)
    new = new_ref[...].astype(f32)                            # (Q, W)
    query = jax.lax.broadcasted_iota(
        jnp.int32, (n_new * n_heads, 1), 0) // n_heads
    s = [jnp.where(query >= k,
                   jnp.sum(q * new[k:k + 1], axis=-1, keepdims=True),
                   _NEG) for k in range(n_new)]
    m = functools.reduce(jnp.maximum, s)         # row 0 is always seen
    p = [jnp.exp(sk - m) for sk in s]
    m_ref[...] = jnp.broadcast_to(m, m_ref.shape)
    l_ref[...] = jnp.broadcast_to(functools.reduce(jnp.add, p), l_ref.shape)
    acc_ref[...] = functools.reduce(
        jnp.add, [pk * new[k:k + 1, :rank] for k, pk in enumerate(p)])

    _mla_walk(tbl_ref, len_ref, layer_ref, q_ref, pool_ref, o_ref, acc_ref,
              m_ref, l_ref, buf_ref, sem_ref, ring_ref, page_size, rank)


def _pallas_latent_verify(q, kv_new, kv_pages, layer, page_table, lengths,
                          rank, interpret):
    """``q (B, Q, H, W)`` scaled, in the pool's dtype; ``kv_new (B, Q,
    W)``; the WHOLE pool ``(L, P, S, W)`` and the ``layer`` to read;
    ``page_table (B, M)`` and ``lengths (B,)`` (the tokens in the pool,
    the same for every query of a row). Returns ``(B, Q, H, rank)``
    float32."""
    import jax.numpy as jnp
    B, Q, H, W = q.shape
    out = _traced_once(_pallas_latent, "rank", "n_new", "interpret")(
        q.reshape(B, Q * H, W), kv_new, kv_pages,
        jnp.full((1,), layer, jnp.int32), page_table, lengths, rank=rank,
        n_new=Q, interpret=interpret)
    return out.reshape(B, Q, H, rank)


def _latent_write_rows_kernel(pg_ref, base_ref, new_ref, page_ref, out_ref,
                              *, n_new):
    """One program instance puts one row's ``n_new`` consecutive new
    latents of one layer into ONE of the pages they touch: the page
    comes in whole and leaves whole. ``base_ref[b * n_new + k]`` is the
    slot new row 0 WOULD have in the page that holds new row ``k`` (-1
    in the second page of a pair that straddles a boundary), so the
    same selects serve both pages; where all rows share a page the
    instances of one row name the same block, compute the same page,
    and it is fetched and written back once."""
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    del pg_ref
    base = base_ref[pl.program_id(0) * n_new + pl.program_id(2)]
    rows = jax.lax.broadcasted_iota(jnp.int32, page_ref.shape, 0)
    out = page_ref[...]
    for i in range(n_new):
        out = jnp.where(rows == base + i, new_ref[i:i + 1, :], out)
    out_ref[...] = out


def _pallas_latent_write_rows(pages, page_idx, base, new, interpret):
    """``mx_latent_write`` for ``Q`` consecutive rows a row that may
    STRADDLE a page boundary: ``new (L, B, Q, W)`` into the pool ``(L, P,
    S, W)``, in place. ``page_idx (B, Q)`` names the page of each new
    row and ``base (B, Q)`` the slot new row 0 would have there
    (:func:`_latent_write_rows_kernel`)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    L, P, S, W = pages.shape
    B, Q = new.shape[1], new.shape[2]
    page = pl.BlockSpec((None, None, S, W),
                        lambda b, l, k, pg, bs: (l, pg[b * Q + k], 0, 0))
    return pl.pallas_call(
        functools.partial(_latent_write_rows_kernel, n_new=Q),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(B, L, Q),
            in_specs=[pl.BlockSpec((None, None, Q, W),
                                   lambda b, l, k, pg, bs: (l, b, 0, 0)),
                      page],
            out_specs=page),
        out_shape=jax.ShapeDtypeStruct(pages.shape, pages.dtype),
        input_output_aliases={3: 0},
        interpret=interpret,
        name="mx_latent_write.b%d.q%d.l%d.s%d.d%d.%s" % (
            B, Q, L, S, W, pages.dtype.name),
    )(page_idx.reshape(-1), base.reshape(-1), new, pages)


# ---------------------------------------------------------------------------
# block decode: a few query positions a row, grouped-query heads, over a
# paged cache whose token row packs every key/value head
# ---------------------------------------------------------------------------

def _jnp_block_decode(q, kc, vc, k_new, v_new, lengths):
    """The block-decode reference. ``q (B, Q, Hq, D)`` already scaled;
    ``kc``/``vc (B, T, Hkv, D)`` the row's cache (position == index),
    of which the first ``lengths[b]`` keys are live; ``k_new``/``v_new
    (B, Q, Hkv, D)`` the block's own keys and values, NOT in the cache,
    every one visible to every query of the block. Query head ``i``
    reads key/value head ``i // (Hq // Hkv)``. Float32 softmax over the
    live keys and the block's own: ``(B, Q, Hq, D)`` float32."""
    import jax.numpy as jnp
    B, Q, Hq, D = q.shape
    Hkv = kc.shape[2]
    f32 = jnp.float32
    qg = q.astype(f32).reshape(B, Q, Hkv, Hq // Hkv, D)
    s_old = jnp.einsum("bqhgd,bthd->bhgqt", qg, kc.astype(f32))
    live = jax.lax.iota(jnp.int32, kc.shape[1])[None, :] \
        < jnp.asarray(lengths, jnp.int32)[:, None]
    s_old = jnp.where(live[:, None, None, None, :], s_old, _NEG)
    s_new = jnp.einsum("bqhgd,bkhd->bhgqk", qg, k_new.astype(f32))
    s = jnp.concatenate([s_old, s_new], -1)
    p = jnp.exp(s - jnp.max(s, axis=-1, keepdims=True))
    p = p / jnp.sum(p, axis=-1, keepdims=True)
    T = kc.shape[1]
    out = jnp.einsum("bhgqt,bthd->bqhgd", p[..., :T], vc.astype(f32)) \
        + jnp.einsum("bhgqk,bkhd->bqhgd", p[..., T:], v_new.astype(f32))
    return out.reshape(B, Q, Hq, D)


def _block_decode_kernel(tbl_ref, len_ref, layer_ref, q_ref, kn_ref, vn_ref,
                         k_pool, v_pool, o_ref, acc_ref, m_ref, l_ref, k_buf,
                         v_buf, sem_ref, ring_ref, *, page_size, n_kv,
                         head_dim, n_new):
    """Grid = (rows,): one program instance attends ALL query positions
    and heads of one row to the row's live pages, which it walks itself
    (:func:`_walk_pages`: a K and a V page a step, read where they lie).
    A token's row in the pool packs its ``n_kv`` key (or value) heads
    side by side, ``n_kv * head_dim`` lanes, so key/value head ``h`` of
    the page is the lane-aligned ``(S, head_dim)`` slice ``[:, h *
    head_dim:(h + 1) * head_dim]``, and the ``Q * (Hq // Hkv)`` query
    vectors that read it — every position of the block, every query
    head of the group — are the rows of ONE ``(R, head_dim) x
    (head_dim, S)`` product on the MXU, the weighted sum one ``(R, S) x
    (S, head_dim)``: operands in the pool's dtype, float32
    accumulation, float32 running softmax. (The per-head paged kernel
    is VPU arithmetic for one query vector a head.)

    ``len_ref[b]`` counts the row's keys IN THE POOL, all visible to
    every query of the block; the block's own ``n_new`` keys and values
    (``kn_ref``/``vn_ref``, not in the pool) open the accumulation, on
    the VPU, each visible to every query. The walk is ``ceil(len / S)``
    pages long whatever the table's width: a table column past it is
    never looked at. The running max and sum are ``(Hkv, R, 128)``,
    every lane of a row the same value (:func:`_lanes`)."""
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    b = pl.program_id(0)
    length = len_ref[b]
    f32 = jnp.float32

    for h in range(n_kv):
        q = q_ref[h].astype(f32)                              # (R, D)
        kn = kn_ref[h].astype(f32)                            # (n_new, D)
        vn = vn_ref[h].astype(f32)
        s = [jnp.sum(q * kn[i:i + 1], axis=-1, keepdims=True)
             for i in range(n_new)]                           # (R, 1) each
        m = functools.reduce(jnp.maximum, s)
        p = [jnp.exp(si - m) for si in s]
        m_ref[h] = jnp.broadcast_to(m, m_ref.shape[1:])
        l_ref[h] = jnp.broadcast_to(functools.reduce(jnp.add, p),
                                    l_ref.shape[1:])
        acc_ref[h] = functools.reduce(
            jnp.add, [pi * vn[i:i + 1] for i, pi in enumerate(p)])

    def fold(pages):
        # every score product first, then the softmax chains in (page,
        # head) order: each product is free to run under another's chain
        todo = []
        for j, (k_ref, v_ref) in pages:
            pos = j * page_size + jax.lax.broadcasted_iota(
                jnp.int32, (1, page_size), 1)
            for h in range(n_kv):
                lanes = slice(h * head_dim, (h + 1) * head_dim)
                s = _dot(q_ref[h], k_ref[:, lanes], _NT)      # (R, S)
                todo.append((h, v_ref, lanes,
                             jnp.where(pos < length, s, _NEG)))
        for h, v_ref, lanes, s in todo:
            v = v_ref[:, lanes]                               # (S, D)
            m_prev = m_ref[h]                                 # (R, 128)
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            p = jnp.exp(s - _lanes(m_new, page_size))
            m_ref[h] = m_new
            l_ref[h] = l_ref[h] * alpha + jnp.sum(p, axis=-1,
                                                  keepdims=True)
            acc_ref[h] = acc_ref[h] * _lanes(alpha, head_dim) \
                + _dot(p.astype(v.dtype), v)

    _walk_pages(tbl_ref, len_ref, layer_ref[0], [k_pool, v_pool],
                [k_buf, v_buf], sem_ref, ring_ref, fold)
    # the block's own keys are always live, so l > 0
    for h in range(n_kv):
        o_ref[h] = (acc_ref[h] / _lanes(l_ref[h], head_dim)).astype(
            o_ref.dtype)


def _pallas_block(q, k_new, v_new, k_pages, v_pages, layer, page_table,
                  lengths, *, interpret):
    """:func:`_pallas_block_decode` with the ``layer (1,)`` an operand
    (:func:`_traced_once`)."""
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, Hkv, R, D = q.shape
    Q = k_new.shape[2]
    S = k_pages.shape[2]
    M = page_table.shape[1]

    row = pl.BlockSpec((None, Hkv, R, D), lambda b, *prefetch: (b, 0, 0, 0))
    new = pl.BlockSpec((None, Hkv, Q, D), lambda b, *prefetch: (b, 0, 0, 0))
    pool = pl.BlockSpec(memory_space=pl.ANY)
    return pl.pallas_call(
        functools.partial(_block_decode_kernel, page_size=S, n_kv=Hkv,
                          head_dim=D, n_new=Q),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(B,),
            in_specs=[row, new, new, pool, pool],
            out_specs=row,
            scratch_shapes=[pltpu.VMEM((Hkv, R, D), jnp.float32),
                            pltpu.VMEM((Hkv, R, _LANES), jnp.float32),
                            pltpu.VMEM((Hkv, R, _LANES), jnp.float32)]
            + _walk_scratch(
                jax.ShapeDtypeStruct((S, Hkv * D), k_pages.dtype),
                jax.ShapeDtypeStruct((S, Hkv * D), v_pages.dtype))),
        out_shape=jax.ShapeDtypeStruct((B, Hkv, R, D), jnp.float32),
        # the walk's copies run over the rows' ends: rows in order
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        # rows x query heads, the block's query positions, the table's
        # full width in keys, the head size, then the key/value heads:
        # what kernel_costs.shapes and a reader of the cost file take
        # from the event's name
        name="mx_block_decode.bh%d.q%d.k%d.d%d.%s.kv%d.paged" % (
            B * Hkv * R // Q, Q, M * S, D,
            jnp.dtype(k_pages.dtype).name, Hkv),
    )(page_table.reshape(-1), lengths, layer, q, k_new, v_new, k_pages,
      v_pages)


def _pallas_block_decode(q, k_new, v_new, k_pages, v_pages, layer,
                         page_table, lengths, interpret):
    """``q (B, Hkv, R, D)`` scaled, in the pool's dtype, ``R`` = query
    positions x query heads a group; ``k_new``/``v_new (B, Hkv, Q, D)``;
    the WHOLE pools ``(L, P, S, Hkv * D)``, left in HBM for the kernel's
    own copies, and the ``layer`` to read; ``page_table (B, M)`` and
    ``lengths (B,)``. Returns ``(B, Hkv, R, D)`` float32."""
    import jax.numpy as jnp
    return _traced_once(_pallas_block, "interpret")(
        q, k_new, v_new, k_pages, v_pages, jnp.full((1,), layer, jnp.int32),
        page_table, lengths, interpret=interpret)


def _block_write_kernel(pg_ref, slot_ref, layer_ref, new_ref, page_ref,
                        out_ref, *, n_new):
    """One program instance puts one row's ``n_new`` new token rows of
    one layer into their page: the page comes in whole, leaves whole,
    and differs in rows ``slot .. slot + n_new - 1`` (selects over the
    block, so no store at a dynamic offset into packed 16-bit rows)."""
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    del pg_ref, layer_ref
    slot = slot_ref[pl.program_id(0)]
    rows = jax.lax.broadcasted_iota(jnp.int32, page_ref.shape, 0)
    out = page_ref[...]
    for i in range(n_new):
        out = jnp.where(rows == slot + i, new_ref[i:i + 1, :], out)
    out_ref[...] = out


def _pallas_block_rows(pages, page_idx, slot, layer, new, *, interpret):
    """:func:`_pallas_block_write` with the first ``layer (1,)`` an
    operand (:func:`_traced_once`)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    S, W = pages.shape[2:]
    n, B, Q = new.shape[:3]
    page = pl.BlockSpec((None, None, S, W),
                        lambda b, l, pg, sl, first: (first[0] + l, pg[b],
                                                     0, 0))
    return pl.pallas_call(
        functools.partial(_block_write_kernel, n_new=Q),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(B, n),
            in_specs=[pl.BlockSpec((None, None, Q, W),
                                   lambda b, l, pg, sl, first: (l, b, 0, 0)),
                      page],
            out_specs=page),
        out_shape=jax.ShapeDtypeStruct(pages.shape, pages.dtype),
        input_output_aliases={4: 0},
        interpret=interpret,
        name="mx_block_write.b%d.q%d.l%d.s%d.d%d.%s" % (
            B, Q, n, S, W, pages.dtype.name),
    )(page_idx, slot, layer, new, pages)


def _pallas_block_write(pages, page_idx, slot, new, layer, interpret):
    """A block's new token rows ``new (n, B, Q, W)`` into the pool ``(L,
    P, S, W)``, in place (the pool is aliased to the result): row ``b``'s
    ``Q`` rows land in page ``page_idx[b]`` from ``slot[b]`` on, in the
    ``n`` layers from ``layer`` on (all of them after a step's last
    layer, or one from inside it) — ``mx_latent_write`` for more than
    one row a row, and for the same reason (XLA's own row writes into a
    4-D pool copy it whole)."""
    import jax.numpy as jnp
    return _traced_once(_pallas_block_rows, "interpret")(
        pages, page_idx, slot, jnp.full((1,), layer, jnp.int32), new,
        interpret=interpret)


# ---------------------------------------------------------------------------
# grouped-query forward, causal, optionally BANDED (a sliding window):
# serving's prefill for fewer key/value than query heads
# ---------------------------------------------------------------------------

def _jnp_grouped(q, k, v, scale, window=None, q_offset=0, k_first=None):
    """The reference of :func:`_pallas_grouped_forward`: causal attention
    of ``q (B, T, Hq, D)`` over ``k``/``v (B, T, Hkv, D)``, query head
    ``i`` reading key/value head ``i // (Hq // Hkv)``; with ``window``
    key ``t`` is visible to query ``s`` iff ``s - window < t <= s``.
    With ``q_offset`` the queries are the LAST of a longer run of keys:
    query ``j`` stands at key ``q_offset + j`` (``k``/``v (B, Tk, Hkv,
    D)``, ``Tk >= q_offset + T``), and with ``k_first`` (a traced scalar)
    no key in front of that one is visible (:func:`ring_chunk`).
    Float32 scores and softmax, the weights rounded to ``v``'s dtype as
    the kernel rounds them; returns ``(B, T, Hq, D)`` float32."""
    import jax.numpy as jnp
    B, T, Hq, D = q.shape
    Hkv, f32 = k.shape[2], jnp.float32
    qg = (q * scale).astype(k.dtype).astype(f32).reshape(
        B, T, Hkv, Hq // Hkv, D)
    s = jnp.einsum("bqhgd,bkhd->bhgqk", qg, k.astype(f32))
    # the key a query stands at, and every key
    at = (q_offset + jax.lax.iota(jnp.int32, T))[:, None]
    keys = jax.lax.iota(jnp.int32, k.shape[1])[None, :]
    seen = keys <= at
    if window is not None:
        seen = jnp.logical_and(seen, keys > at - window)
    if k_first is not None:
        seen = jnp.logical_and(seen, keys >= k_first)
    s = jnp.where(seen[None, None, None], s, _NEG)
    p = jnp.exp(s - jnp.max(s, axis=-1, keepdims=True))
    l = jnp.sum(p, axis=-1, keepdims=True)
    out = jnp.einsum("bhgqk,bkhd->bqhgd", p.astype(v.dtype).astype(f32),
                     v.astype(f32))
    return out.reshape(B, T, Hq, D) \
        / jnp.transpose(l[..., 0], (0, 3, 1, 2)).reshape(B, T, Hq, 1)


def _band(i, block_q, block_k, window, q_offset=0, n_kb=None):
    """The first and the last key block a query block ``i`` (traced, or a
    Python int) may see, its queries standing ``q_offset`` keys in (of
    ``n_kb`` key blocks: the padding of an offset block of queries
    reaches past the keys')."""
    import jax.numpy as jnp
    most, least = (max, min) if isinstance(i, int) \
        else (jnp.maximum, jnp.minimum)
    lo = q_offset + i * block_q
    first = 0 if window is None else most(lo - window + 1, 0) // block_k
    last = (lo + block_q - 1) // block_k
    return first, last if n_kb is None else least(last, n_kb - 1)


def _grouped_fwd_kernel(*refs, block_q, block_k, n_steps, n_kb, kv_len,
                        window, q_offset, k_first):
    """Grid = (batch * query heads, q blocks, band steps), the band
    innermost: step ``j`` of query block ``i`` folds key block ``first(i)
    + j`` — under a window only the blocks the band touches are ever
    named (two of 512 for a window of 512 over blocks of 512), the
    blocks behind it are skipped, not masked. Operands as they come (the
    cache's dtype, the queries scaled and rounded to it), float32 scores,
    running softmax and accumulation. With ``k_first`` the first
    reference is a prefetched scalar, the first key that may be seen at
    all: whatever lies in front of it is masked, never read for what it
    holds."""
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    first_ref = None
    if k_first:
        first_ref, *refs = refs
    q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref = refs
    qi, j = pl.program_id(1), pl.program_id(2)
    first, last = _band(qi, block_q, block_k, window, q_offset, n_kb)
    kb = first + j

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
        l_ref[...] = jnp.zeros_like(l_ref)

    @pl.when(kb <= last)
    def _step():
        s = _dot(q_ref[...], k_ref[...], _NT)            # (bq, bk)
        k_pos = kb * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (1, block_k), 1)
        q_pos = q_offset + qi * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, 1), 0)
        seen = jnp.logical_and(k_pos < kv_len, q_pos >= k_pos)
        if window is not None:
            seen = jnp.logical_and(seen, k_pos > q_pos - window)
        if first_ref is not None:
            seen = jnp.logical_and(seen, k_pos >= first_ref[0])
        s = jnp.where(seen, s, _NEG)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        m_ref[...] = m_new
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1,
                                                  keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha \
            + _dot(p.astype(v_ref.dtype), v_ref[...])

    @pl.when(j == n_steps - 1)
    def _finish():
        o_ref[...] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)) \
            .astype(o_ref.dtype)


def _pallas_grouped_forward(q, k, v, *, n_q_heads, window, block_q, block_k,
                            kv_len, interpret, q_offset=0, k_first=None):
    """``q (B * Hq, Tq, D)`` scaled, in the keys' dtype; ``k``/``v (B *
    Hkv, Tk, D)``, both lengths padded to their blocks. Query ``j``
    stands at key ``q_offset + j`` (0 and ``Tq == Tk``: a prompt over
    itself); ``k_first (1,)`` int32, where given, is the first key that
    may be seen. Returns ``(B * Hq, Tq, D)`` float32. Named
    ``mx_grouped_fwd``, the key/value heads behind the shapes and, under
    a window, ``.w<window>`` behind those (and ``.o<q_offset>``)."""
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    BH, Tq, D = q.shape
    Tk = k.shape[1]
    Hq = n_q_heads
    Hkv = k.shape[0] // (BH // Hq)
    G = Hq // Hkv
    n_qb, n_kb = Tq // block_q, Tk // block_k

    def band(i):
        return _band(i, block_q, block_k, window, q_offset, n_kb)

    # the widest band of any query block: the grid's innermost extent
    n_steps = max(last - first + 1
                  for first, last in map(band, range(n_qb)))

    def kv_map(b, i, j, *_prefetched):
        first, last = band(i)
        # a step past the band names the band's last block again: no copy
        return ((b // Hq) * Hkv + (b % Hq) // G,
                jnp.minimum(first + j, last), 0)

    q_spec = pl.BlockSpec((None, block_q, D),
                          lambda b, i, j, *_prefetched: (b, i, 0))
    kv_spec = pl.BlockSpec((None, block_k, D), kv_map)
    name = "mx_grouped_fwd.bh%d.q%d.k%d.d%d.%s.kv%d" % (
        BH, Tq, Tk, D, jnp.dtype(k.dtype).name, BH // Hq * Hkv)
    if window is not None:
        name += ".w%d" % window
    if q_offset:
        name += ".o%d" % q_offset
    scratch = [pltpu.VMEM((block_q, D), jnp.float32),
               pltpu.VMEM((block_q, 1), jnp.float32),
               pltpu.VMEM((block_q, 1), jnp.float32)]
    prefetched = () if k_first is None else (k_first,)
    return pl.pallas_call(
        functools.partial(_grouped_fwd_kernel, block_q=block_q,
                          block_k=block_k, n_steps=n_steps, n_kb=n_kb,
                          kv_len=kv_len, window=window, q_offset=q_offset,
                          k_first=bool(prefetched)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(prefetched),
            grid=(BH, n_qb, n_steps),
            in_specs=[q_spec, kv_spec, kv_spec],
            out_specs=q_spec,
            scratch_shapes=scratch),
        out_shape=jax.ShapeDtypeStruct((BH, Tq, D), jnp.float32),
        interpret=interpret,
        name=name,
    )(*prefetched, q, k, v)


def _grouped_forward(q, k, v, scale, window, block_q, block_k, interpret):
    """:func:`flash_attention`'s kernel path for grouped-query heads or a
    window: forward only (serving's prefill; no backward kernels)."""
    B, T, Hq, D = q.shape
    t_pad, _, bq, bk = _blocks(T, T, block_q, block_k)
    qf = _flatten(_pad_seq((q * scale).astype(k.dtype), t_pad))
    out = _pallas_grouped_forward(
        qf, _flatten(_pad_seq(k, t_pad)), _flatten(_pad_seq(v, t_pad)),
        n_q_heads=Hq, window=window, block_q=bq, block_k=bk, kv_len=T,
        interpret=interpret)
    return _unflatten(out, B, Hq)[:, :T]


# ---------------------------------------------------------------------------
# ring decode: one query position a row over a RING of the row's last W
# keys and values, grouped-query heads
# ---------------------------------------------------------------------------

def _jnp_ring_decode(q, ring_k, ring_v, k_new, v_new, positions):
    """The ring-decode reference. ``q (B, Hq, D)`` scaled; ``ring_k``/
    ``ring_v (B, W, Hkv, D)`` the rows' rings as they stand BEFORE the
    step — key ``t`` lies in slot ``t % W`` — ``k_new``/``v_new (B, Hkv,
    D)`` the step's own, not in the ring; ``positions (B,)``. A query at
    position ``p`` sees its own key and, of the ring, the slots ``s < p``
    but for slot ``p % W``, which holds key ``p - W`` (the one the step
    overwrites): ``min(p, W - 1)`` keys, by the row's position alone.
    Float32 softmax: ``(B, Hq, D)`` float32."""
    import jax.numpy as jnp
    B, Hq, D = q.shape
    W, Hkv = ring_k.shape[1:3]
    f32 = jnp.float32
    qg = q.astype(f32).reshape(B, Hkv, Hq // Hkv, D)
    pos = jnp.asarray(positions, jnp.int32)[:, None]
    slot = jax.lax.iota(jnp.int32, W)[None, :]
    seen = jnp.logical_and(slot < pos, slot != pos % W)
    s = jnp.einsum("bhgd,bwhd->bhgw", qg, ring_k.astype(f32))
    s = jnp.where(seen[:, None, None, :], s, _NEG)
    own = jnp.einsum("bhgd,bhd->bhg", qg, k_new.astype(f32))[..., None]
    m = jnp.maximum(jnp.max(s, axis=-1, keepdims=True), own)
    p, p_own = jnp.exp(s - m), jnp.exp(own - m)
    out = jnp.einsum("bhgw,bwhd->bhgd",
                     p.astype(ring_v.dtype).astype(f32), ring_v.astype(f32)) \
        + p_own * v_new.astype(f32)[:, :, None, :]
    return (out / (jnp.sum(p, axis=-1, keepdims=True) + p_own)) \
        .reshape(B, Hq, D)


_RING_TILE = 16      # ring slots a write carries: a sublane tile of 16 bits


def _ring_decode_kernel(slot_ref, pos_ref, live_ref, layer_ref, q_ref,
                        kn_ref, vn_ref, k_ref, v_ref, o_ref, ko_ref, vo_ref,
                        *, window, n_kv, head_dim, tile):
    """Grid = (rows,): one program instance attends ALL query heads of
    one row to the row's ring — ``(W, Hkv * D)``, a token's key (or
    value) heads side by side, one block, the same bytes whatever the
    context — and puts the step's own key and value into slot ``p % W``.
    Key/value head ``h`` is the lane-aligned slice ``[:, h * D:(h + 1) *
    D]`` and the query heads of its group the rows of ONE ``(G, D) x (D,
    W)`` product on the MXU; the weighted sum one ``(G, W) x (W, D)``.
    What is visible follows from the row's position alone (:func:`_jnp_
    ring_decode`); the own key opens the softmax on the VPU.

    The rings are aliased to the results and only the ``tile`` slots
    around ``p % W`` are written back (a select over the tile, so no
    store at a dynamic offset into packed 16-bit rows); a row that is not
    live writes its tile back as it was."""
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    del slot_ref, layer_ref
    b = pl.program_id(0)
    p = pos_ref[b]
    at = jax.lax.rem(p, window)
    slots = jax.lax.broadcasted_iota(jnp.int32, (1, window), 1)
    seen = jnp.logical_and(slots < p, slots != at)
    f32 = jnp.float32
    for h in range(n_kv):
        lanes = slice(h * head_dim, (h + 1) * head_dim)
        q = q_ref[h]                                          # (G, D)
        kn, vn = kn_ref[:, lanes].astype(f32), vn_ref[:, lanes].astype(f32)
        s = jnp.where(seen, _dot(q, k_ref[:, lanes], _NT), _NEG)  # (G, W)
        own = jnp.sum(q.astype(f32) * kn, axis=-1, keepdims=True)
        m = jnp.maximum(jnp.max(s, axis=-1, keepdims=True), own)
        e, e_own = jnp.exp(s - m), jnp.exp(own - m)
        v = v_ref[:, lanes]
        acc = _dot(e.astype(v.dtype), v) + e_own * vn
        o_ref[h] = (acc / (jnp.sum(e, axis=-1, keepdims=True) + e_own)) \
            .astype(o_ref.dtype)
    base = pl.multiple_of((at // tile) * tile, tile)
    rows = base + jax.lax.broadcasted_iota(jnp.int32, (tile, 1), 0)
    put = jnp.logical_and(rows == at, live_ref[b] > 0)
    ko_ref[...] = jnp.where(put, kn_ref[...], k_ref[pl.ds(base, tile), :])
    vo_ref[...] = jnp.where(put, vn_ref[...], v_ref[pl.ds(base, tile), :])


def _pallas_ring(q, k_new, v_new, ring_k, ring_v, layer, slots, positions,
                 live, *, interpret):
    """:func:`_pallas_ring_decode` with the ``layer (1,)`` an operand
    (:func:`_traced_once`)."""
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, Hkv, G, D = q.shape
    W, width = ring_k.shape[2:]
    tile = min(_RING_TILE, W)

    def row(shape):
        return pl.BlockSpec((None,) + shape,
                            lambda b, *prefetch: (b,) + (0,) * len(shape))

    ring = pl.BlockSpec(
        (None, None, W, width),
        lambda b, slot, pos, live, layer: (layer[0], slot[b], 0, 0))
    put = pl.BlockSpec(
        (None, None, tile, width),
        lambda b, slot, pos, live, layer: (
            layer[0], slot[b], jax.lax.rem(pos[b], W) // tile, 0))
    return pl.pallas_call(
        functools.partial(_ring_decode_kernel, window=W, n_kv=Hkv,
                          head_dim=D, tile=tile),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(B,),
            in_specs=[row((Hkv, G, D)), row((1, width)), row((1, width)),
                      ring, ring],
            out_specs=[row((Hkv, G, D)), put, put]),
        out_shape=[jax.ShapeDtypeStruct((B, Hkv, G, D), jnp.float32),
                   jax.ShapeDtypeStruct(ring_k.shape, ring_k.dtype),
                   jax.ShapeDtypeStruct(ring_v.shape, ring_v.dtype)],
        input_output_aliases={7: 1, 8: 2},
        interpret=interpret,
        # rows x query heads, one query position, the ring's slots, the
        # head size, then the key/value heads
        name="mx_ring_decode.bh%d.q1.k%d.d%d.%s.kv%d" % (
            B * Hkv * G, W, D, jnp.dtype(ring_k.dtype).name, Hkv),
    )(slots, positions, live, layer, q, k_new, v_new, ring_k, ring_v)


def _pallas_ring_decode(q, k_new, v_new, ring_k, ring_v, layer, slots,
                        positions, live, interpret):
    """``q (B, Hkv, G, D)`` scaled, in the rings' dtype; ``k_new``/
    ``v_new (B, 1, Hkv * D)``; the WHOLE rings ``(layers, rows, W, Hkv *
    D)`` and the ``layer`` to read; ``slots``, ``positions``, ``live
    (B,)`` int32. Returns ``(out (B, Hkv, G, D) float32, ring_k,
    ring_v)``, the rings updated in place."""
    import jax.numpy as jnp
    return _traced_once(_pallas_ring, "interpret")(
        q, k_new, v_new, ring_k, ring_v, jnp.full((1,), layer, jnp.int32),
        slots, positions, live, interpret=interpret)


def ring_decode(q, k_new, v_new, ring_k, ring_v, layer, slots, positions,
                live, scale=None, force_pallas=False):
    """One decode step of SLIDING-WINDOW attention over rings: every row
    keeps its last ``W`` keys and values in a ring of its own — ``ring_k``
    / ``ring_v (layers, rows, W, Hkv * D)``, key ``t`` in slot ``t % W``,
    a token's heads side by side — the same bytes whatever the context.

    - ``q (B, Hq, D)``, ``k_new``/``v_new (B, Hkv, D)``: the step's one
      position a row (query head ``i`` reads key/value head ``i // (Hq //
      Hkv)``);
    - ``slots (B,)``: the ring each row of the step works on, all
      different; ``positions (B,)``; ``live (B,)``: a row that is not
      live leaves its ring as it was (its output is nobody's).

    A query at position ``p`` sees keys ``p - W < t <= p``: its own and
    ``min(p, W - 1)`` of the ring — what is valid follows from the
    position, never from the ring's content. The step's own key and value
    then take slot ``p % W``. Returns ``(out (B, Hq, D) float32, ring_k,
    ring_v)``. On the TPU, for a head size and a window of whole 128s,
    the Pallas kernel ``mx_ring_decode`` (one grid step a row, the rings
    updated in place); the jnp composition elsewhere. Counted as
    ``ring_decode_pallas`` / ``ring_decode_jnp``."""
    import jax.numpy as jnp
    B, Hq, D = q.shape
    Hkv = k_new.shape[1]
    W = ring_k.shape[2]
    scale = scale if scale is not None else 1.0 / math.sqrt(D)
    dtype = ring_k.dtype
    q = (q * scale).astype(dtype)
    k_new = k_new.astype(dtype).reshape(B, 1, Hkv * D)
    v_new = v_new.astype(dtype).reshape(B, 1, Hkv * D)
    slots = jnp.asarray(slots, jnp.int32)
    pos = jnp.asarray(positions, jnp.int32)
    live = jnp.asarray(live)

    def composed(q, k_new, v_new, ring_k, ring_v, slots, pos, live):
        out = _jnp_ring_decode(
            q, ring_k[layer, slots].reshape(B, W, Hkv, D),
            ring_v[layer, slots].reshape(B, W, Hkv, D),
            k_new.reshape(B, Hkv, D), v_new.reshape(B, Hkv, D), pos)
        at = pos % W
        put = live[:, None]
        ring_k = ring_k.at[layer, slots, at].set(
            jnp.where(put, k_new[:, 0], ring_k[layer, slots, at]))
        ring_v = ring_v.at[layer, slots, at].set(
            jnp.where(put, v_new[:, 0], ring_v[layer, slots, at]))
        return out, ring_k, ring_v

    def kernel(interpret, q, k_new, v_new, ring_k, ring_v, slots, pos, live):
        out, ring_k, ring_v = _pallas_ring_decode(
            q.reshape(B, Hkv, Hq // Hkv, D), k_new, v_new, ring_k, ring_v,
            layer, slots, pos, live.astype(jnp.int32), interpret)
        return out.reshape(B, Hq, D), ring_k, ring_v

    return _dispatch("ring_decode", D, (W,), force_pallas, kernel, composed,
                     q, k_new, v_new, ring_k, ring_v, slots, pos, live)


def ring_chunk(q, k_new, v_new, ring_k, ring_v, layer, slot, start, n_live,
               scale=None, force_pallas=False):
    """A CHUNK of one row's prompt under sliding-window attention over
    the row's ring: ``C`` consecutive positions ``start .. start + C - 1``
    of ONE row, the first ``n_live`` of them live — what a mixed decode
    step's chunk lanes run in a sliding layer (:func:`ring_decode` is the
    step's one position a row).

    - ``q (C, Hq, D)``, ``k_new``/``v_new (C, Hkv, D)``: the chunk's own,
      not in the ring; ``ring_k`` / ``ring_v (layers, rows, W, Hkv * D)``
      WHOLE, ``slot`` the row of them the request holds; ``start``,
      ``n_live`` traced scalars.

    Lane ``j`` at position ``p = start + j`` sees keys ``p - W < t <= p``:
    of the ring AS IT STANDS BEFORE the chunk (slot ``s`` holds the last
    ``t < start`` with ``t % W == s``; a slot whose ``t`` would be
    negative is masked by position — a slot's last tenant's keys are
    never read for what they hold) and the chunk's own rows ``<= j``. The
    keys are laid out ``[the ring in position order ; the chunk's own]``,
    ``W + C`` of them, key ``i`` at position ``start - W + i``, the
    queries ``W`` keys in: the banded grouped forward with an offset
    (``mx_grouped_fwd...w<W>.o<W>``, the blocks behind the band never
    named) on the TPU for a head size of whole 128s, the ``jnp``
    composition elsewhere; counted as ``ring_chunk_pallas`` /
    ``ring_chunk_jnp``. Then the ring takes the LAST ``min(n_live, W)``
    live lanes, lane ``j`` into slot ``(start + j) % W``, as one row
    written in place (no lane: the row as it was). Holds for any ``C``
    against ``W``. Returns ``(out (C, Hq, D) float32, ring_k, ring_v)``;
    a lane at or past ``n_live`` computes finite garbage nobody reads."""
    import jax.numpy as jnp
    C, Hq, D = q.shape
    Hkv = k_new.shape[1]
    W, width = ring_k.shape[2:]
    scale = scale if scale is not None else 1.0 / math.sqrt(D)
    start = jnp.asarray(start, jnp.int32)
    n_live = jnp.asarray(n_live, jnp.int32)

    def laid_out(ring, new):
        row = jax.lax.dynamic_slice(
            ring, (layer, slot, 0, 0), (1, 1, W, width))[0, 0]
        return jnp.concatenate([jnp.roll(row, -(start % W), axis=0),
                                new.astype(ring.dtype).reshape(C, width)])

    keys, values = laid_out(ring_k, k_new), laid_out(ring_v, v_new)
    # the key at position 0: nothing in front of it was ever this row's
    k_first = jnp.maximum(W - start, 0)

    def heads(a):
        return a.reshape(1, W + C, Hkv, D)

    def composed(q, keys, values, k_first):
        return _jnp_grouped(q[None], heads(keys), heads(values), scale,
                            window=W, q_offset=W, k_first=k_first)[0]

    tq_pad, tk_pad, bq, bk = _blocks(C, W + C, 512, 512)

    def kernel(interpret, q, keys, values, k_first):
        out = _pallas_grouped_forward(
            _flatten(_pad_seq((q * scale).astype(keys.dtype)[None],
                              tq_pad)),
            _flatten(_pad_seq(heads(keys), tk_pad)),
            _flatten(_pad_seq(heads(values), tk_pad)), n_q_heads=Hq,
            window=W, block_q=bq, block_k=bk, kv_len=W + C,
            interpret=interpret, q_offset=W,
            k_first=jnp.reshape(k_first, (1,)))
        return _unflatten(out, 1, Hq)[0, :C]

    out = _dispatch("ring_chunk", D, (bq, bk), force_pallas, kernel,
                    composed, q, keys, values, k_first)

    def put(ring, laid):
        # positions start + n_live - W .. start + n_live - 1, back into
        # their slots
        row = jnp.roll(jax.lax.dynamic_slice_in_dim(laid, n_live, W),
                       (start + n_live) % W, axis=0)
        return jax.lax.dynamic_update_slice(ring, row[None, None],
                                            (layer, slot, 0, 0))

    return out, put(ring_k, keys), put(ring_v, values)


def flash_decode(q, k, v, lengths, scale=None, block_k=128,
                 force_pallas=False, k_scale=None, v_scale=None):
    """One autoregressive decode step of attention: a single cached-KV
    query per sequence.

    - ``q``: ``(B, 1, H, D)`` — the new token's query;
    - ``k``/``v``: ``(B, T, H, D)`` — the KV cache gathered to a fixed
      bucket length ``T`` (``serving.kvcache`` page gather), including
      the new token's own key/value already written at its position;
    - ``lengths``: ``(B,)`` int32 — per-row valid key count (the new
      token's position + 1); positions at or beyond a row's length are
      masked to exact-zero weight, so the cache's garbage tail (unused
      page slots, the dump page) never leaks into the result.

    Runs the Pallas kernel on the TPU when ``head_dim`` and the key
    block tile by 128 (:func:`tiles_on_chip`), or under
    ``force_pallas`` (interpret mode off the TPU); the jnp composition
    otherwise. The key block is ``block_k`` when it divides ``T``,
    else ``gcd(T, block_k)`` — so a pool whose bucket lengths are
    multiples of 128 stays on the kernel and a 64-key bucket takes
    jnp. The kernel accumulates in the prefill kernel's order, so a
    decode step agrees with the same row of a full causal forward to
    the last few ulps (``tests/test_decode.py``).

    **Quantized caches**: with int8 ``k``/``v`` plus ``k_scale``/
    ``v_scale`` — ``(B, T)`` fp32 per-position dequantization scales
    (a paged pool's per-page scales repeated over each page's slots;
    ``serving.kvcache``'s int8 mode) — the kernel path applies the
    scales INSIDE the block stream, so the cache crosses HBM→VMEM at
    a quarter of the fp32 bytes; the jnp path dequantizes up front.
    Both scales must be given together."""
    import jax.numpy as jnp
    scale = scale if scale is not None else 1.0 / math.sqrt(q.shape[-1])
    if q.shape[1] != 1:
        raise ValueError(
            "flash_decode: expected a single query position, got "
            "q length %d" % q.shape[1])
    quant = k_scale is not None or v_scale is not None
    if quant and (k_scale is None or v_scale is None):
        raise ValueError(
            "flash_decode: quantized caches need BOTH k_scale and "
            "v_scale (B, T)")
    B, _, H, D = q.shape
    Tk = k.shape[1]
    bk = block_k if Tk % block_k == 0 else math.gcd(Tk, block_k)

    def composed(q, k, v, lengths, k_scale, v_scale):
        if quant:
            k = k.astype(jnp.float32) \
                * jnp.asarray(k_scale, jnp.float32)[:, :, None, None]
            v = v.astype(jnp.float32) \
                * jnp.asarray(v_scale, jnp.float32)[:, :, None, None]
        return _jnp_decode(q, k, v, lengths, scale).astype(q.dtype)

    def kernel(interpret, q, k, v, lengths, k_scale, v_scale):
        lens = jnp.repeat(jnp.asarray(lengths, jnp.int32), H)
        ksf = vsf = None
        if quant:
            # per-(row, position) planes repeat per head, matching the
            # kernels' flattened batch*heads axis (the _seg_flat layout)
            ksf = jnp.repeat(jnp.asarray(k_scale, jnp.float32), H,
                             axis=0)
            vsf = jnp.repeat(jnp.asarray(v_scale, jnp.float32), H,
                             axis=0)
        out = _pallas_decode(_flatten(q), _flatten(k), _flatten(v),
                             lens, scale, bk, interpret,
                             k_scale=ksf, v_scale=vsf)
        return _unflatten(out, B, H)

    return _dispatch("flash_decode", D, (bk,), force_pallas, kernel,
                     composed, q, k, v, jnp.asarray(lengths), k_scale,
                     v_scale)


def flash_attention(q, k, v, causal=False, scale=None, block_q=512,
                    block_k=512, force_pallas=False, segment_ids=None,
                    window=None):
    """Attention over (B, T, H, D) tensors.

    The Pallas kernels (forward and backward) run on the TPU when
    ``head_dim`` is a multiple of 128 (:func:`tiles_on_chip`) — or
    under ``force_pallas``, in interpret mode off the TPU — for ANY
    sequence length: non-tiling lengths are zero-padded to the
    128-lane multiple and masked in-kernel. The jnp composition runs
    elsewhere; same math, differentiable everywhere.

    ``segment_ids`` (``(B, T)`` int32, 1-based per sample, 0 = pad —
    ``bucketing.packing``'s plane) turns on segment-blocked attention
    for PACKED batches: a position attends only within its own
    segment, cross-segment softmax weights are exact IEEE zeros (in
    the kernels AND the jnp composition), and padding attends to
    nothing — its rows produce garbage a masked loss must (and does)
    ignore.

    **Grouped-query heads and a sliding window** (serving's prefill;
    causal self-attention, forward only): ``k``/``v`` may carry FEWER
    heads than ``q`` — query head ``i`` reads key/value head ``i // (Hq
    // Hkv)`` — and with ``window`` key ``t`` is visible to query ``s``
    iff ``s - window < t <= s``. Either takes the grouped kernel
    (``mx_grouped_fwd``; under a window ``...w<window>``, and the grid
    names only the key blocks the band touches): operands in the keys'
    dtype, the result float32.
    """
    scale = scale if scale is not None else 1.0 / math.sqrt(q.shape[-1])
    if window is not None or k.shape[2] != q.shape[2]:
        if not causal or segment_ids is not None \
                or q.shape[1] != k.shape[1] or q.shape[2] % k.shape[2]:
            raise ValueError(
                "flash_attention: grouped-query heads (%d over %d) or a "
                "window are written for causal self-attention without "
                "segments" % (q.shape[2], k.shape[2]))
        bq, bk = _blocks(q.shape[1], k.shape[1], block_q, block_k)[2:]
        return _dispatch(
            "flash_grouped", q.shape[-1], (bq, bk), force_pallas,
            lambda interpret, q, k, v: _grouped_forward(
                q, k, v, scale, window, block_q, block_k, interpret),
            lambda q, k, v: _jnp_grouped(q, k, v, scale, window), q, k, v)
    if segment_ids is not None and q.shape[1] != k.shape[1]:
        raise ValueError(
            "flash_attention: segment_ids requires self-attention "
            "(q and k sequence lengths %d vs %d differ)"
            % (q.shape[1], k.shape[1]))
    _, _, bq, bk = _blocks(q.shape[1], k.shape[1], block_q, block_k)

    def composed(q, k, v, seg):
        return _jnp_reference(q, k, v, scale, causal, segment_ids=seg)

    def kernel(interpret, q, k, v, seg):
        return _flash(q, k, v, seg, scale, causal, block_q, block_k,
                      interpret)

    return _dispatch("flash_attention", q.shape[-1], (bq, bk),
                     force_pallas, kernel, composed, q, k, v, segment_ids)
