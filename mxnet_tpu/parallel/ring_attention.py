"""Sequence/context parallelism: ring attention + Ulysses.

First-class capability extension mandated by SURVEY §5.7 (the reference
predates it; its only sequence tools are bucketing and fused RNNs).

- :func:`ring_attention` — blockwise attention with flash-style stable
  accumulation; K/V shards rotate around the ``sp`` mesh axis via
  ``ppermute`` so each device streams all keys past its local queries.
  Memory per device is O(T/sp · T/sp) per step instead of O(T²);
  communication rides the ICI ring (sp-1 hops of the local K/V shard).
- :func:`ulysses_attention` — all-to-all head-scatter/seq-gather: each
  device gathers the FULL sequence for a subset of heads, runs dense
  attention locally, and scatters back. One all_to_all each way.

Both operate on globally-sharded arrays (B, T, H, D) with T split over
the ``sp`` axis, composed via shard_map so XLA overlaps the collectives
with the blockwise matmuls.
"""
from __future__ import annotations

import functools
import math

__all__ = ["ring_attention", "ulysses_attention", "local_attention"]


def _block_attn(q, k, v, scale, mask=None):
    """One attention block: returns (out_unnormalized, row_max, row_sum).

    q: (B, Tq, H, D), k/v: (B, Tk, H, D) → scores (B, H, Tq, Tk).
    """
    import jax.numpy as jnp
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    if mask is not None:
        s = jnp.where(mask, s, -1e30)
    m = jnp.max(s, axis=-1)                        # (B,H,Tq)
    p = jnp.exp(s - m[..., None])
    if mask is not None:
        # rows with no valid keys: exp(-1e30 - (-1e30)) = 1 junk; zero them
        any_valid = jnp.any(mask, axis=-1)
        p = jnp.where(any_valid[..., None], p, 0.0)
        m = jnp.where(any_valid, m, -1e30)
    l = jnp.sum(p, axis=-1)                        # (B,H,Tq)
    o = jnp.einsum("bhqk,bkhd->bqhd", p, v)        # (B,Tq,H,D)
    return o, m, l


def _merge_blocks(o1, m1, l1, o2, m2, l2):
    """Combine two softmax partial results with stable rescaling."""
    import jax.numpy as jnp
    m = jnp.maximum(m1, m2)
    a1 = jnp.exp(m1 - m)
    a2 = jnp.exp(m2 - m)
    l = l1 * a1 + l2 * a2
    # o are (B,T,H,D); alphas are (B,H,T) → transpose to (B,T,H)
    a1t = jnp.swapaxes(a1, 1, 2)[..., None]
    a2t = jnp.swapaxes(a2, 1, 2)[..., None]
    o = o1 * a1t + o2 * a2t
    return o, m, l


def local_attention(q, k, v, causal=False, scale=None):
    """Attention for unsharded inputs (B, T, H, D): delegates to
    flash_attention, which picks the Pallas kernel on TPU and the jnp
    composition elsewhere (one shared implementation of the math)."""
    from .flash_attention import flash_attention
    return flash_attention(q, k, v, causal=causal, scale=scale)


def ring_attention(q, k, v, mesh=None, axis="sp", causal=False, scale=None):
    """Ring attention over sequence-sharded q/k/v (B, T, H, D).

    If ``mesh`` is None the inputs are assumed unsharded and plain
    attention runs (single-chip fallback).
    """
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    shard_map = jax.shard_map

    if mesh is None or axis not in getattr(mesh, "axis_names", ()):
        return local_attention(q, k, v, causal=causal, scale=scale)

    sp = mesh.shape[axis]
    scale_ = scale if scale is not None else 1.0 / math.sqrt(q.shape[-1])

    def kernel(ql, kl, vl):
        # ql/kl/vl: local shards (B, T/sp, H, D)
        my = jax.lax.axis_index(axis)
        Tl = ql.shape[1]
        q_pos = my * Tl + jnp.arange(Tl)

        def mask_for(block_idx):
            if not causal:
                return None
            k_pos = block_idx * Tl + jnp.arange(Tl)
            return (q_pos[:, None] >= k_pos[None, :])[None, None]

        # step 0: local block
        o, m, l = _block_attn(ql, kl, vl, scale_, mask_for(my))
        perm = [(i, (i + 1) % sp) for i in range(sp)]

        def body(step, carry):
            o, m, l, kc, vc = carry
            kc = jax.lax.ppermute(kc, axis, perm)
            vc = jax.lax.ppermute(vc, axis, perm)
            src = (my - step) % sp  # owner of the K/V block we now hold
            ob, mb, lb = _block_attn(ql, kc, vc, scale_, mask_for(src))
            o, m, l = _merge_blocks(o, m, l, ob, mb, lb)
            return (o, m, l, kc, vc)

        o, m, l, _, _ = jax.lax.fori_loop(
            1, sp, body, (o, m, l, kl, vl))
        lt = jnp.swapaxes(l, 1, 2)[..., None]
        return o / jnp.maximum(lt, 1e-30)

    spec = P(None, axis, None, None)
    return shard_map(kernel, mesh=mesh, in_specs=(spec, spec, spec),
                     out_specs=spec)(q, k, v)


def ulysses_attention(q, k, v, mesh=None, axis="sp", causal=False,
                      scale=None):
    """Ulysses (DeepSpeed) sequence parallelism: all_to_all so each
    device holds ALL timesteps for H/sp heads, local dense attention,
    all_to_all back. Requires H % sp == 0."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    shard_map = jax.shard_map

    if mesh is None or axis not in getattr(mesh, "axis_names", ()):
        return local_attention(q, k, v, causal=causal, scale=scale)

    sp = mesh.shape[axis]
    H = q.shape[2]
    assert H % sp == 0, \
        "ulysses_attention: num heads %d must divide sp=%d" % (H, sp)
    scale_ = scale if scale is not None else 1.0 / math.sqrt(q.shape[-1])

    def kernel(ql, kl, vl):
        # local (B, T/sp, H, D) → (B, T, H/sp, D): scatter heads,
        # gather sequence
        def a2a(x):
            return jax.lax.all_to_all(x, axis, split_axis=2, concat_axis=1,
                                      tiled=True)

        qh, kh, vh = a2a(ql), a2a(kl), a2a(vl)
        out = local_attention(qh, kh, vh, causal=causal, scale=scale_)
        # back: (B, T, H/sp, D) → (B, T/sp, H, D)
        return jax.lax.all_to_all(out, axis, split_axis=1, concat_axis=2,
                                  tiled=True)

    spec = P(None, axis, None, None)
    return shard_map(kernel, mesh=mesh, in_specs=(spec, spec, spec),
                     out_specs=spec)(q, k, v)
