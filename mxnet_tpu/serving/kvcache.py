"""Paged KV-cache pool for stateful autoregressive decode.

The vLLM insight adapted to this tree's fixed-program contract: the
server owns one device-resident pool of **fixed-size pages** per K and
V — shape ``(n_layers, n_pages, page_size, n_heads, head_dim)`` — and
each in-flight request holds a *page table*, a short list of page ids
covering its token positions in order. Every compiled program then
sees only fixed shapes:

- **attend** (:func:`paged_attention`) — one layer's decode attention
  reads the pool THROUGH the ``(batch, max_pages)`` page table: on the
  TPU, for pages the chip tiles, the paged Pallas kernel of
  ``parallel.flash_attention`` streams each row's live pages where
  they lie (``ceil(len / page_size)`` of them; the table's tail is
  neither fetched nor computed) and folds the step's new token in as
  one more key, so the decode step holds no copy of the cache.
- **gather** (:func:`gather_pages`) — the plain reference path, which
  the kernel is tested against and which every shape the chip cannot
  tile (and a CPU process) still takes: indexing the pool with the
  page table yields a ``(batch, max_pages * page_size, ...)``
  contiguous view per request, where a token's cache index IS its
  absolute position. Unallocated table tail entries point at the
  reserved **dump page 0**, whose garbage is masked to exact-zero
  attention weight by the per-row lengths.
- **scatter** (:func:`scatter_token` / :func:`scatter_prefill`) — new
  K/V rows write back through the same table, functionally, into the
  donated pool, so the whole decode step stays one compiled program:
  attend → write the token's rows, no host round-trip per token.

**Layouts.** What a page holds is the model's declaration
(``DecodeServer``'s contract), and the kind of cache is ONE layout
object that :func:`cache_layout` picks from that declaration and the
pool's dtype: by default two arrays, per-head K and V, ``(n_layers,
n_pages, page_size, n_heads, head_dim)`` each; under an int8 dtype the
same two as int8 pages, with their per-page scales carried beside them;
a latent-attention model declares ONE array ``(n_layers, n_pages,
page_size, W)`` whose row is the compressed K/V and the shared rotary
key (:func:`paged_latent_attention`, :func:`write_prefill_pages`,
:func:`write_token_rows` — and, for a step that runs a few CONSECUTIVE
positions a row, causal among themselves, :func:`paged_latent_causal_
attention` and :func:`write_latent_rows`: a speculative step's verify
and draft passes); per-head K and V whose FEW heads would not
fill a 16-bit dtype's sublane tile (grouped-query attention: 4
key/value heads under 32 query heads) are packed into one row of ``H *
D`` lanes a token, ``(n_layers, n_pages, page_size, H * D)``
(:class:`_PackedHeadKV`, :func:`paged_block_attention`,
:func:`write_block_rows` — which also serve a step that runs BLOCKS of
query positions a row, over either per-head form: a row's block and the
block after it in one pass, the first committed from inside each layer
where it is final, :class:`_BlockStep`). Every float layout also runs a
CHUNK of one row's prompt beside a step's decode rows
(``DecodeServer``'s mixed step; ``chunks``, ``attend_chunk``,
:func:`paged_chunk_attention`, :func:`paged_latent_chunk_attention`,
:func:`write_chunk_rows`: ``C`` consecutive positions of ONE row, lane
``j`` over the row's pages before the chunk and the chunk's own rows
``<= j``, written in place across page boundaries). The layout alone
knows which arrays a
program carries, what ``attend`` a step hands its model and how a
prefill's sequences and a step's new rows reach their pages; the
server's three programs are written over it, once. The page accounting
below — alloc, free, refcounts, copy-on-write, preemption, the prefix
index — never looks inside a page and is the same for every kind.

**Fixed state a row, beside the pages.** A model whose layers are not
all attention — a linear-attention layer keeps a matrix a head and the
last rows of a short convolution, the same bytes whatever the context —
declares ``state_arrays = ((name, shape a row, dtype), ...)`` and
``state_layers`` (``DecodeServer``'s contract, the STATE form). The pool
then carries, BEHIND its page arrays in ``.arrays`` and donated through
the same programs, one array a declared name, ``(state_layers, rows,
*shape)``, ``rows`` the server's decode window: a request holds ONE row
of it (its slot, :meth:`KVCachePool.take_row`) from its prefill, which
writes the row whole, to its end. Nothing of it is paged, shared or
copied on write. The layout object answers for both kinds
(:class:`_RowStateBeside` around the pages' own layout:
``layout_for`` hands a program the same object the pool holds): it
splits a program's ``pools`` into pages and state, hands a step the
:class:`RowState` its model works on and writes a prefill's state into
its row. ``stats()["state"]`` counts its bytes, its rows, the rows held
and the prefills' writes.

Page *accounting* is host-side and lives here too: an allocate/free
free-list under a lock, with peak/eviction counters for the ``decode``
telemetry record and the ``/metrics`` gauges. Page reclaim visits the
``kv_evict`` fault site once per page (``MXNET_FAULT_PLAN``), making
"a dead request's pages provably come back" a deterministic test, and
a planned ``raise`` there is counted and survived — a reclaim fault
must never leak the page it was reclaiming.

Sizing: ``MXNET_KV_PAGE_SIZE`` tokens per page and
``MXNET_KV_POOL_PAGES`` pages; the decode server derives its
page-table width from the bucketing ladder's top prompt rung plus the
generation budget, so the program set is fixed no matter the request
mix.

**Quantized storage** (``MXNET_KV_DTYPE=int8``, or ``dtype=`` on the
pool): K/V pages store int8 with one fp32 scale per ``(layer, page)``
(``k_scale``/``v_scale``, shape ``(L, P)``, the last two of the pool's
``.arrays``). The quantized ops are
the same traced, functional shapes as the fp32 ones, so the decode
server's program set stays fixed:

- :func:`paged_attention` hands the kernel each page's scale as one
  scalar, applied to the page's scores and weighted values in VMEM;
  :func:`gather_pages_q8` (the reference path) dequantizes on gather —
  the per-page scale broadcasts across its page's token slots;
- :func:`scatter_token_q8` grows a page's scale monotonically as
  tokens land (``max(old, |new|/127)``) and REQUANTIZES the page body
  under the grown scale in-program — except on a page's FIRST slot,
  where the scale is set fresh (a reallocated page's stale scale and
  garbage from its prior tenant must not leak in);
- :func:`scatter_prefill_q8` sets each covered page's scale from its
  own token chunk (padding rows beyond ``n_valid`` are zeroed first so
  prefill garbage never inflates a scale).

Scale semantics make correctness independent of page history: a slot's
dequantized value is always ``q * scale_at_last_write``, and positions
at/after a row's ``lengths`` are masked by the attention anyway. bf16
storage (``MXNET_KV_DTYPE=bfloat16``) needs no scales — it is a plain
dtype choice on the pool arrays.

**Prefix sharing** (``MXNET_KV_PREFIX_CACHE=1`` or ``prefix_cache=``
on the decode server): pages are REFERENCE-COUNTED, and the pool
carries a :class:`PrefixIndex` — a content-hashed radix over
page-aligned token runs. A finished prefill registers its full pages
under SHA-1 digests of the whole token prefix up to each page boundary
(namespaced by share group + weight generation, so two models or two
weight generations can never alias); a later prompt that walks the
same chain enters decode with its page table pointing at the SHARED
pages and computes only the un-cached suffix. The first write into a
still-shared page triggers copy-on-write (the decode server's
``:cow`` program — a q8 page's per-page scales are carried arrays like
the pages, and copy with it). Index
entries hold one reference each, so cached prefixes survive their
requests; under pool pressure ``alloc`` evicts COLD entries — pages
nobody holds beyond the index itself — through the counted
``kv_evict`` reclaim path. Refcounted pages are never victims.

**Multi-model pools**: :meth:`KVCachePool.attach` registers several
decode servers (several models / weight generations) on ONE pool with
per-model page quotas (``MXNET_KV_MODEL_QUOTA`` default) and a pool
priority; ``alloc(owner=)`` enforces the quota, and
:meth:`request_preempt` asks lower-pool-priority co-tenants to give
pages back via their scheduled preemption callbacks. ``step_lock``
serializes the servers' compiled steps on the shared device arrays.
"""
from __future__ import annotations

import functools
import hashlib
import math
import threading
from collections import OrderedDict

from .. import envs, fault
from ..base import MXNetError

__all__ = ["KVCachePool", "PrefixIndex", "gather_pages",
           "paged_attention", "paged_latent_attention",
           "paged_block_attention", "write_block_rows",
           "paged_latent_causal_attention", "write_latent_rows",
           "paged_chunk_attention", "paged_latent_chunk_attention",
           "write_chunk_rows", "cache_layout",
           "declared_arrays", "declared_state", "layout_for", "RowState",
           "scatter_token", "scatter_prefill", "write_prefill_pages",
           "write_token_rows",
           "pages_for",
           "gather_pages_q8", "scatter_token_q8",
           "scatter_prefill_q8"]

_INT8_MAX = 127.0
_EPS = 1e-8          # scale floor: an all-zero chunk still divides


def pages_for(n_tokens, page_size):
    """Pages needed to back ``n_tokens`` positions."""
    return -(-int(n_tokens) // int(page_size))


# ---------------------------------------------------------------------------
# traced pool ops (pure; called inside the server's compiled programs)
# ---------------------------------------------------------------------------

def gather_pages(pages, page_table):
    """``pages (L, P, S, ...)`` indexed by ``page_table (B, M)`` →
    contiguous per-request caches ``(L, B, M*S, ...)``: cache index ==
    absolute token position. Table entries of 0 bring in the dump
    page — finite garbage the attention mask zeroes exactly."""
    g = pages[:, page_table]                   # (L, B, M, S, ...)
    shape = g.shape
    return g.reshape(shape[0], shape[1], shape[2] * shape[3],
                     *shape[4:])


def paged_attention(k_pages, v_pages, page_table, positions, layer, q,
                    k_new, v_new, *, scale=None, force_pallas=False,
                    k_scale=None, v_scale=None):
    """One layer's decode attention over the pool — what the server's
    step hands a model as ``attend(layer, q, k_new, v_new)`` (the
    leading five arguments bound). ``q``/``k_new``/``v_new`` ``(B, H,
    D)`` are the step's new token; its K/V is NOT in the pool yet
    (:func:`scatter_token` writes it at the step's end) and is attended
    at ``positions (B,)`` with the row's ``positions`` earlier keys.
    Returns ``(B, H, D)``.

    On the TPU, for pages the chip tiles (``head_dim`` and ``page_size``
    multiples of 128), the Pallas kernel of ``parallel.flash_attention``
    reads each row's live pages where they lie: nothing pool-sized or
    cache-sized is copied, whatever the table's width. Every other
    shape, and a CPU process without ``force_pallas``, takes the plain
    reference the kernel is tested against: :func:`gather_pages` the
    layer to a contiguous cache, insert the new token, masked softmax
    (``_jnp_decode``). The choice is ``flash_attention._choose_path``'s,
    counted as ``paged_decode_pallas`` / ``paged_decode_jnp``. An int8
    pool passes its ``(L, P)`` page scales; the new token is attended
    unquantized on both paths, a float pool's rounded to the pool's
    dtype as the pool will hold it."""
    import jax.numpy as jnp
    from ..parallel.flash_attention import (_dispatch, _jnp_decode,
                                            _pallas_paged_decode)
    scale = scale if scale is not None else 1.0 / math.sqrt(q.shape[-1])
    quant = k_scale is not None
    if q.shape[1] != k_new.shape[1] and not quant:
        # fewer key/value than query heads over a per-head float pool (a
        # packed pool attends through the block form already): a block
        # of one position
        return paged_block_attention(
            k_pages, v_pages, page_table, positions, layer, q, k_new,
            v_new, scale=scale, force_pallas=force_pallas)
    pos = jnp.asarray(positions, jnp.int32)
    table = jnp.asarray(page_table, jnp.int32)
    if not quant:
        k_new = k_new.astype(k_pages.dtype)
        v_new = v_new.astype(v_pages.dtype)

    def composed(q, k_new, v_new, k_pages, v_pages, table, pos, ks, vs):
        one = slice(layer, layer + 1)
        if quant:
            kc = gather_pages_q8(k_pages[one], ks[one], table)[0]
            vc = gather_pages_q8(v_pages[one], vs[one], table)[0]
        else:
            kc = gather_pages(k_pages[one], table)[0]
            vc = gather_pages(v_pages[one], table)[0]
        rows = jnp.arange(q.shape[0])
        # cache index == absolute position: the new token joins the
        # gathered copy at its own before attending
        kc = kc.at[rows, pos].set(k_new)
        vc = vc.at[rows, pos].set(v_new)
        return _jnp_decode(q[:, None], kc, vc, pos + 1,
                           scale)[:, 0].astype(q.dtype)

    def kernel(interpret, q, k_new, v_new, k_pages, v_pages, table, pos,
               ks, vs):
        if quant:
            ks, vs = ks[layer][table], vs[layer][table]     # (B, M)
        return _pallas_paged_decode(
            q, k_new, v_new, k_pages, v_pages, layer, table, pos, scale,
            interpret, k_scale=ks, v_scale=vs)

    return _dispatch("paged_decode", q.shape[-1], (k_pages.shape[2],),
                     force_pallas, kernel, composed, q, k_new, v_new,
                     k_pages, v_pages, table, pos, k_scale, v_scale)


def scatter_token(pages, page_table, positions, new):
    """Write one decode step's new K (or V) rows into the pool:
    ``new (L, B, H, D)`` (``(L, B, W)`` for a latent pool) lands at each
    row's absolute ``positions (B,)`` through its ``page_table (B, M)``
    row. Inactive batch rows
    must carry an all-zero table row — their write lands in the dump
    page. Functional: returns the updated pool."""
    import jax
    import jax.numpy as jnp
    S = pages.shape[2]
    pos = jnp.asarray(positions, jnp.int32)
    pidx = jnp.take_along_axis(
        jnp.asarray(page_table, jnp.int32), (pos // S)[:, None],
        axis=1)[:, 0]                          # (B,)
    slot = pos % S
    new = new.astype(pages.dtype)

    def write_row(b, pages):
        row = jax.lax.dynamic_slice_in_dim(new, b, 1, axis=1)
        return jax.lax.dynamic_update_slice(
            pages, row[:, :, None],
            (0, pidx[b], slot[b]) + (0,) * (pages.ndim - 3))

    # one in-place row write a batch row, not a scatter: XLA's TPU
    # scatter widens a 16-bit pool to float32 and back, whole
    return jax.lax.fori_loop(0, new.shape[1], write_row, pages)


def scatter_prefill(pages, page_table_row, seq, n_valid):
    """Write one request's prefill K (or V) sequence into the pool:
    ``seq (L, Lr, H, D)`` at positions ``0..Lr-1`` through
    ``page_table_row (M,)``. Positions at or beyond ``n_valid`` (the
    true prompt length — the rest of the rung is padding whose K/V is
    garbage) are routed to the dump page instead. Functional."""
    import jax
    import jax.numpy as jnp
    S = pages.shape[2]
    Lr = seq.shape[1]
    pos = jax.lax.iota(jnp.int32, Lr)
    pidx = jnp.asarray(page_table_row, jnp.int32)[pos // S]
    pidx = jnp.where(pos < n_valid, pidx, 0)
    return pages.at[:, pidx, pos % S].set(seq)


def write_prefill_pages(pages, page_table_row, seq, n_valid):
    """:func:`scatter_prefill` as in-place page writes, for a float pool
    of any width (the latent pool's prefill): ``seq (L, Lr, ...)``, a
    whole number of pages (rungs are page-aligned), goes in page by
    page with ``dynamic_update_slice``, as :func:`scatter_token` writes
    rows — XLA's TPU scatter widens a 16-bit pool to float32 and back,
    whole. A page that starts at or past ``n_valid`` is all padding and
    goes to the dump page. The page that holds position ``n_valid``
    takes the rung's padding rows after it too: finite garbage at
    positions no query attends before a decode step has overwritten
    them, and never part of a shared prefix (the index takes full pages
    of true tokens only)."""
    import jax
    import jax.numpy as jnp
    S = pages.shape[2]
    n = seq.shape[1] // S
    table = jnp.asarray(page_table_row, jnp.int32)
    seq = seq.astype(pages.dtype)
    tail = (0,) * (pages.ndim - 3)
    for c in range(n):
        pidx = jnp.where(c * S < n_valid, table[c], 0)
        chunk = jax.lax.slice_in_dim(seq, c * S, (c + 1) * S, axis=1)
        pages = jax.lax.dynamic_update_slice(
            pages, chunk[:, None], (0, pidx, 0) + tail)
    return pages


def write_token_rows(pages, page_table, positions, new,
                     force_pallas=False):
    """:func:`scatter_token` for a latent pool ``(L, P, S, W)``: ``new
    (L, B, W)`` at each row's ``positions`` through its table row. On
    the TPU, where the page tiles, a Pallas kernel rewrites each row's
    page in place (``mx_latent_write``); elsewhere
    :func:`scatter_token`'s row writes, the kernel's test reference.
    Counted as ``latent_write_pallas`` / ``latent_write_jnp``."""
    import jax.numpy as jnp
    from ..parallel.flash_attention import _dispatch, _pallas_latent_write
    S = pages.shape[2]
    pos = jnp.asarray(positions, jnp.int32)
    table = jnp.asarray(page_table, jnp.int32)
    new = new.astype(pages.dtype)

    def composed(pages, table, pos, new):
        return scatter_token(pages, table, pos, new)

    def kernel(interpret, pages, table, pos, new):
        pidx = jnp.take_along_axis(table, (pos // S)[:, None],
                                   axis=1)[:, 0]
        return _pallas_latent_write(pages, pidx, pos % S, new, interpret)

    return _dispatch("latent_write", pages.shape[-1], (S,), force_pallas,
                     kernel, composed, pages, table, pos, new)


def paged_latent_attention(kv_pages, page_table, positions, layer, q,
                           kv_new, *, rank, scale, force_pallas=False):
    """:func:`paged_attention`'s sibling for a latent pool — one array
    ``(L, P, S, W)``, a token's row the compressed K/V (``rank``
    columns) and the shared rotary key (the other ``W - rank``), the
    same for every head. ``q (B, H, W)`` is the absorbed query (its
    first ``rank`` columns the no-position part carried through the
    key up-projection, the rest the rotary part), ``kv_new (B, W)`` the
    step's own latent, NOT in the pool yet and attended at
    ``positions`` with the row's ``positions`` earlier tokens. Returns
    the softmax-weighted sum of the first ``rank`` columns, ``(B, H,
    rank)`` float32 — the caller carries it through the value
    up-projection.

    Operands in the pool's dtype, accumulation and softmax in float32.
    On the TPU, where ``rank`` and the page size are multiples of 128,
    the Pallas kernel ``mx_mla_decode`` reads each row's live pages
    where they lie — one grid step a row, the kernel walking that row's
    ``ceil(positions / S)`` pages itself, so the table's width costs
    nothing; elsewhere :func:`gather_pages` + jnp, the kernel's test
    reference. Counted as ``mla_decode_pallas`` / ``mla_decode_jnp``."""
    import jax.numpy as jnp
    from ..parallel.flash_attention import (_dispatch, _jnp_latent_decode,
                                            _pallas_latent_decode)
    pos = jnp.asarray(positions, jnp.int32)
    table = jnp.asarray(page_table, jnp.int32)
    q = (q * scale).astype(kv_pages.dtype)
    kv_new = kv_new.astype(kv_pages.dtype)

    def composed(q, kv_new, kv_pages, table, pos):
        kc = gather_pages(kv_pages[layer:layer + 1], table)[0]
        kc = kc.at[jnp.arange(q.shape[0]), pos].set(kv_new)
        return _jnp_latent_decode(q, kc, pos + 1, rank)

    def kernel(interpret, q, kv_new, kv_pages, table, pos):
        return _pallas_latent_decode(q, kv_new[:, None], kv_pages, layer,
                                     table, pos, rank, interpret)

    return _dispatch("mla_decode", rank, (kv_pages.shape[2],),
                     force_pallas, kernel, composed, q, kv_new, kv_pages,
                     table, pos)


def paged_latent_causal_attention(kv_pages, page_table, positions, layer,
                                   q, kv_new, *, rank, scale,
                                   force_pallas=False):
    """:func:`paged_latent_attention` for a step that runs ``Q``
    CONSECUTIVE query positions a row, causal among themselves — the
    block form of the latent layout, what a speculative step's verify
    and draft passes are handed as ``attend``. ``q (B, Q, H, W)`` the
    absorbed queries, ``kv_new (B, Q, W)`` the step's own latents, NOT
    in the pool yet; ``positions (B,)`` the first query's position = the
    row's tokens in the pool, all visible to every query; new row ``k``
    is visible to query ``j`` iff ``k <= j`` (a block model's block is
    all-see-all: :func:`paged_block_attention`). Returns ``(B, Q, H,
    rank)`` float32.

    On the TPU, where ``rank`` and the page size are multiples of 128,
    the Pallas kernel ``mx_mla_decode...q<Q>`` reads each row's live
    pages where they lie (one grid step a row, the same page walk as the
    one-query kernel), all ``Q * H`` query vectors of a row in one
    product a page; elsewhere :func:`gather_pages` + jnp, the kernel's
    test reference. Counted as ``mla_verify_pallas`` /
    ``mla_verify_jnp``."""
    import jax.numpy as jnp
    from ..parallel.flash_attention import (_dispatch, _jnp_latent_decode,
                                            _pallas_latent_verify)
    pos = jnp.asarray(positions, jnp.int32)
    table = jnp.asarray(page_table, jnp.int32)
    q = (q * scale).astype(kv_pages.dtype)
    kv_new = kv_new.astype(kv_pages.dtype)

    def composed(q, kv_new, kv_pages, table, pos):
        kc = gather_pages(kv_pages[layer:layer + 1], table)[0]
        rows = jnp.arange(q.shape[0])
        for k in range(q.shape[1]):
            kc = kc.at[rows, pos + k].set(kv_new[:, k])
        return _jnp_latent_decode(q, kc, pos + 1, rank)

    def kernel(interpret, q, kv_new, kv_pages, table, pos):
        return _pallas_latent_verify(q, kv_new, kv_pages, layer, table,
                                     pos, rank, interpret)

    return _dispatch("mla_verify", rank, (kv_pages.shape[2],),
                     force_pallas, kernel, composed, q, kv_new, kv_pages,
                     table, pos)


def write_latent_rows(pages, page_table, positions, new,
                      force_pallas=False):
    """:func:`write_token_rows` for ``Q`` consecutive rows a row: ``new
    (L, B, Q, W)`` lands at positions ``positions[b] .. positions[b] + Q
    - 1`` through the row's table row. The rows may STRADDLE a page
    boundary (a speculative step starts wherever the last one was
    accepted to; :func:`write_block_rows`' block lies inside one page).
    Nothing is rolled back: a rejected position's row is simply
    overwritten by the next step. On the TPU, where the page tiles, the
    Pallas kernel ``mx_latent_write...q<Q>`` rewrites the one or two
    pages in place; elsewhere :func:`scatter_token`'s row writes, one
    pass a new row. Counted as ``latent_write2_pallas`` /
    ``latent_write2_jnp``."""
    import jax.numpy as jnp
    from ..parallel.flash_attention import (_dispatch,
                                            _pallas_latent_write_rows)
    S = pages.shape[2]
    Q = new.shape[2]
    pos = jnp.asarray(positions, jnp.int32)
    table = jnp.asarray(page_table, jnp.int32)
    new = new.astype(pages.dtype)

    def composed(pages, table, pos, new):
        for k in range(Q):
            pages = scatter_token(pages, table, pos + k, new[:, :, k])
        return pages

    def kernel(interpret, pages, table, pos, new):
        each = pos[:, None] + jnp.arange(Q, dtype=jnp.int32)[None]  # (B, Q)
        pidx = jnp.take_along_axis(table, each // S, axis=1)
        # the slot new row 0 would have in the page of new row k
        base = pos[:, None] - (each // S) * S
        return _pallas_latent_write_rows(pages, pidx, base, new, interpret)

    return _dispatch("latent_write2", pages.shape[-1], (S,), force_pallas,
                     kernel, composed, pages, table, pos, new)


def paged_block_attention(k_pages, v_pages, page_table, positions, layer,
                          q, k_new, v_new, *, scale=None,
                          force_pallas=False):
    """:func:`paged_attention` for a step that runs a BLOCK of query
    positions a row over grouped-query heads — what a block model's step
    is handed as ``attend(layer, q, k_new, v_new)``. ``q (B, Q, Hq, D)``;
    ``k_new``/``v_new (B, Q, Hkv, D)``, the block's own keys and values,
    NOT in the pool (a block is written when it is final) and every one
    visible to every query of the block; ``positions (B,)`` the block's
    first position = the row's keys in the pool, all visible. Query head
    ``i`` reads key/value head ``i // (Hq // Hkv)``. A ``q (B, Hq, D)``
    with ``k_new``/``v_new (B, Hkv, D)`` is a block of one. Returns
    ``q``'s shape in float32.

    The pools are per-head ``(L, P, S, Hkv, D)`` or packed ``(L, P, S,
    Hkv * D)`` (:class:`_PackedHeadKV`). Operands in the pool's dtype,
    softmax and accumulation in float32. On the TPU a packed pool whose
    head size and pages are multiples of 128 takes the Pallas kernel
    ``mx_block_decode`` (one grid step a row, which walks its own live
    pages: one MXU product a key/value head and live page); everything
    else :func:`gather_pages` + jnp, the kernel's test
    reference. Counted as ``block_decode_pallas`` / ``block_decode_jnp``."""
    import jax.numpy as jnp
    from ..parallel.flash_attention import (_dispatch, _jnp_block_decode,
                                            _pallas_block_decode)
    single = q.ndim == 3
    if single:
        q, k_new, v_new = q[:, None], k_new[:, None], v_new[:, None]
    B, Q, Hq, D = q.shape
    Hkv = k_new.shape[2]
    scale = scale if scale is not None else 1.0 / math.sqrt(D)
    pos = jnp.asarray(positions, jnp.int32)
    table = jnp.asarray(page_table, jnp.int32)
    q = (q * scale).astype(k_pages.dtype)
    k_new = k_new.astype(k_pages.dtype)
    v_new = v_new.astype(v_pages.dtype)
    packed = k_pages.ndim == 4

    def composed(q, k_new, v_new, k_pages, v_pages, table, pos):
        one = slice(layer, layer + 1)
        kc = gather_pages(k_pages[one], table)[0]
        vc = gather_pages(v_pages[one], table)[0]
        shape = kc.shape[:2] + (Hkv, D)
        return _jnp_block_decode(q, kc.reshape(shape), vc.reshape(shape),
                                 k_new, v_new, pos)

    def kernel(interpret, q, k_new, v_new, k_pages, v_pages, table, pos):
        G = Hq // Hkv
        # (B, Q, Hkv, G, D) -> (B, Hkv, Q * G, D): the rows of one product
        qr = jnp.transpose(q.reshape(B, Q, Hkv, G, D),
                           (0, 2, 1, 3, 4)).reshape(B, Hkv, Q * G, D)
        out = _pallas_block_decode(
            qr, jnp.swapaxes(k_new, 1, 2), jnp.swapaxes(v_new, 1, 2),
            k_pages, v_pages, layer, table, pos, interpret)
        return jnp.transpose(out.reshape(B, Hkv, Q, G, D),
                             (0, 2, 1, 3, 4)).reshape(B, Q, Hq, D)

    # the kernel reads a packed pool only: hand the chooser a head size
    # it refuses for the per-head form
    out = _dispatch("block_decode", D if packed else 1,
                    (k_pages.shape[2],), force_pallas and packed, kernel,
                    composed, q, k_new, v_new, k_pages, v_pages, table, pos)
    return out[:, 0] if single else out


def write_block_rows(pages, page_table, positions, new, commit,
                     force_pallas=False, layer=0):
    """A block step's new rows into the pool: ``new (n, B, Q, ...)``, row
    ``b``'s ``Q`` token rows in the ``n`` layers from ``layer`` on (all
    of them, written after a step's last layer; or one, written from
    inside it), land at positions ``positions[b] .. positions[b] + Q -
    1`` through its table row where ``commit[b]``, and in the dump page
    where not (a block still being denoised writes nothing: its rows are
    not final). A block lies inside one page (the page size is a
    multiple of the block length and blocks start at multiples of it).
    In-place row writes as :func:`scatter_token`'s; on the TPU a packed
    or latent pool ``(L, P, S, W)`` whose page tiles takes the Pallas
    kernel ``mx_block_write`` (XLA's own row writes into a 4-D pool copy
    it whole). Counted as ``block_write_pallas`` / ``block_write_jnp``."""
    import jax
    import jax.numpy as jnp
    from ..parallel.flash_attention import _dispatch, _pallas_block_write
    S = pages.shape[2]
    pos = jnp.asarray(positions, jnp.int32)
    pidx = jnp.take_along_axis(
        jnp.asarray(page_table, jnp.int32), (pos // S)[:, None],
        axis=1)[:, 0]
    pidx = jnp.where(commit, pidx, 0)
    slot = pos % S
    new = new.astype(pages.dtype).reshape(
        new.shape[:3] + pages.shape[3:])

    def composed(pages, pidx, slot, new):
        def write_row(b, pages):
            rows = jax.lax.dynamic_slice_in_dim(new, b, 1, axis=1)
            return jax.lax.dynamic_update_slice(
                pages, rows,
                (layer, pidx[b], slot[b]) + (0,) * (pages.ndim - 3))
        return jax.lax.fori_loop(0, new.shape[1], write_row, pages)

    def kernel(interpret, pages, pidx, slot, new):
        return _pallas_block_write(pages, pidx, slot, new, layer, interpret)

    flat = pages.ndim == 4
    return _dispatch("block_write", pages.shape[-1] if flat else 1, (S,),
                     force_pallas and flat, kernel, composed, pages, pidx,
                     slot, new)


# ---------------------------------------------------------------------------
# a chunk of ONE row's prompt beside a step's decode rows
# ---------------------------------------------------------------------------
# ``C`` consecutive positions ``start .. start + C - 1`` of one request
# run as ``C`` more lanes of a decode step (``DecodeServer``'s mixed
# step): lane ``j`` sees the row's keys in the pool before ``start`` and
# the chunk's own new rows ``<= j``. Composed ``jnp`` on every platform:
# the row's pages are walked a block of keys at a time, as many blocks
# as hold live keys (a trip count from ``start``, so a prompt's first
# chunk reads nothing of the pool), under a running max and sum.

_CHUNK_KEYS = 512      # pool keys a block of the walk holds, about


def _chunk_softmax(table_row, start, page_size, lanes, scores, mix, own,
                   gathered):
    """The softmax of a chunk's ``lanes`` queries, un-normalised sum and
    normaliser folded block by block: first over the chunk's ``own =
    (keys, values)``, causal among themselves, then over the row's pages
    — ``gathered(pages (n,)) -> (keys, values)`` of ``n`` pages at a
    time, ``ceil(start / (n * S))`` times, keys at or past ``start``
    masked. ``scores(keys) -> (..., lanes, T)`` float32, ``mix(p,
    values)`` the weighted sum. A masked score is ``_NEG``: its weight is
    an exact zero, because the running max is never below a lane's score
    with its own new row. Returns ``mix``'s shape, normalised."""
    import jax
    import jax.numpy as jnp
    from ..parallel.flash_attention import _NEG
    S = int(page_size)
    n = max(1, _CHUNK_KEYS // S)
    table = jnp.asarray(table_row, jnp.int32)
    table = jnp.pad(table, (0, -table.shape[0] % n))
    at = jnp.arange(n * S, dtype=jnp.int32)
    lane = jnp.arange(lanes, dtype=jnp.int32)
    s = jnp.where(lane[:, None] >= lane[None, :], scores(own[0]), _NEG)
    m = jnp.max(s, axis=-1)
    p = jnp.exp(s - m[..., None])

    def block(j, carry):
        m, l, acc = carry
        keys, values = gathered(
            jax.lax.dynamic_slice_in_dim(table, j * n, n))
        s = jnp.where(j * (n * S) + at < start, scores(keys), _NEG)
        m2 = jnp.maximum(m, jnp.max(s, axis=-1))
        a = jnp.exp(m - m2)
        p = jnp.exp(s - m2[..., None])
        return m2, l * a + jnp.sum(p, axis=-1), \
            acc * a[..., None] + mix(p, values)

    _m, l, acc = jax.lax.fori_loop(
        0, (start + n * S - 1) // (n * S), block,
        (m, jnp.sum(p, axis=-1), mix(p, own[1])))
    return acc / l[..., None]


def _block_pages(pool, layer, pages, start):
    """``pool[layer, pages]`` for one block of the walk, ``(n, S, ...)``.
    A float32 pool's keys are multiplied in bfloat16 passes, and XLA
    moves that rounding up through the gather and out of the walk: it
    rounds the WHOLE pool once a step (2.7 GB of temporaries and 8 GB of
    traffic at the benchmark's sizes, sandbox compile). Adding a zero the
    compiler cannot know (``start`` is never negative) keeps the rounding
    behind the gather, on the block's pages alone."""
    return pool[layer, pages] + (start < 0).astype(pool.dtype)


def paged_chunk_attention(k_pages, v_pages, table_row, start, layer, q,
                          k_new, v_new, *, scale=None):
    """One layer's attention of a chunk over per-head K and V. ``q (C,
    Hq, D)``, ``k_new``/``v_new (C, Hkv, D)``: ``C`` consecutive
    positions of ONE row from ``start`` on, NOT in the pool yet
    (:func:`write_chunk_rows` writes them at the step's end); lane ``j``
    attends the row's ``start`` earlier keys through ``table_row (M,)``
    and new rows ``0 .. j``. Query head ``i`` reads key/value head ``i
    // (Hq // Hkv)``. The pools are per-head ``(L, P, S, Hkv, D)`` or
    packed ``(L, P, S, Hkv * D)``. Scores and softmax in float32, the new
    rows rounded to the pool's dtype as the pool will hold them. Returns
    ``(C, Hq, D)`` float32. Lanes past the chunk's live ones compute
    finite garbage nobody reads."""
    import jax.numpy as jnp
    C, Hq, D = q.shape
    Hkv = k_new.shape[1]
    f32 = jnp.float32
    scale = scale if scale is not None else 1.0 / math.sqrt(D)
    qg = (q * scale).reshape(C, Hkv, Hq // Hkv, D)

    def scores(keys):
        return jnp.einsum("chgd,thd->hgct", qg, keys,
                          preferred_element_type=f32)

    def mix(p, values):
        return jnp.einsum("hgct,thd->hgcd", p, values,
                          preferred_element_type=f32)

    def gathered(pages):
        return tuple(_block_pages(pool, layer, pages, start)
                     .reshape(-1, Hkv, D) for pool in (k_pages, v_pages))

    out = _chunk_softmax(
        table_row, start, k_pages.shape[2], C, scores, mix,
        (k_new.astype(k_pages.dtype), v_new.astype(v_pages.dtype)),
        gathered)                                      # (Hkv, G, C, D)
    return jnp.transpose(out, (2, 0, 1, 3)).reshape(C, Hq, D)


def paged_latent_chunk_attention(kv_pages, table_row, start, layer, q,
                                 kv_new, *, rank, scale):
    """:func:`paged_chunk_attention` for a latent pool ``(L, P, S, W)``:
    ``q (C, H, W)`` the absorbed queries, ``kv_new (C, W)`` the chunk's
    own latents. Operands in the pool's dtype, scores and softmax in
    float32; returns the weighted sum of the first ``rank`` columns,
    ``(C, H, rank)`` float32."""
    import jax.numpy as jnp
    f32 = jnp.float32
    q = (q * scale).astype(kv_pages.dtype)
    kv_new = kv_new.astype(kv_pages.dtype)

    def scores(rows):
        return jnp.einsum("chw,tw->hct", q, rows,
                          preferred_element_type=f32)

    def mix(p, rows):
        return jnp.einsum("hct,tr->hcr", p, rows[:, :rank],
                          preferred_element_type=f32)

    def gathered(pages):
        rows = _block_pages(kv_pages, layer, pages, start)
        return (rows.reshape(-1, rows.shape[-1]),) * 2

    out = _chunk_softmax(table_row, start, kv_pages.shape[2], q.shape[0],
                         scores, mix, (kv_new, kv_new), gathered)
    return jnp.swapaxes(out, 0, 1)                     # (C, H, rank)


def write_chunk_rows(pages, table_row, start, n_live, new):
    """A chunk's new rows into the pool, in place: ``new (L, C, ...)``,
    the ``C`` rows of ONE row at positions ``start .. start + C - 1``, of
    which the first ``n_live`` are written through ``table_row (M,)`` —
    at any offset, across page boundaries. Page by page, as
    :func:`write_prefill_pages` writes a prompt's (XLA's TPU scatter
    widens a 16-bit pool to float32 and back, whole): each page the
    chunk can touch is read, its rows in ``[start, start + n_live)``
    replaced, and written back with ``dynamic_update_slice``, a layer at
    a time; a page that holds none of them is the dump page's,
    unchanged. Works on any float pool ``(L, P, S, ...)``, per-head,
    packed or latent."""
    import jax
    import jax.numpy as jnp
    S = pages.shape[2]
    L, C = new.shape[:2]
    trailing = pages.shape[3:]
    table = jnp.asarray(table_row, jnp.int32)
    new = new.astype(pages.dtype).reshape((L, C) + trailing)
    touched = (C + S - 2) // S + 1          # pages C rows reach at most
    off = start % S
    tail = (0,) * len(trailing)
    # the chunk as it lies on ``touched`` whole pages, lane 0 at ``off``
    wide = jax.lax.dynamic_update_slice(
        jnp.zeros((L, touched * S) + trailing, pages.dtype), new,
        (0, off) + tail)
    rows = jnp.arange(S, dtype=jnp.int32)
    keep_shape = (1, 1, S) + (1,) * len(trailing)

    def write_page(i, pages):
        # one layer's page at a time: a block that is contiguous in the
        # pool as it lies (a block over all layers makes XLA re-lay the
        # whole pool out, page-major, and copy it back: 2.7 GB of
        # temporaries at the benchmark's sizes, sandbox compile)
        l, c = i // touched, i % touched
        lane = c * S + rows - off
        live = jnp.logical_and(lane >= 0, lane < n_live)
        at = jnp.minimum(start // S + c, table.shape[0] - 1)
        pidx = jnp.where(jnp.any(live), table[at], 0)
        old = jax.lax.dynamic_slice(
            pages, (l, pidx, 0) + tail, (1, 1, S) + trailing)
        page = jax.lax.dynamic_slice(
            wide, (l, c * S) + tail, (1, S) + trailing)
        return jax.lax.dynamic_update_slice(
            pages, jnp.where(live.reshape(keep_shape), page[:, None], old),
            (l, pidx, 0) + tail)

    return jax.lax.fori_loop(0, L * touched, write_page, pages)


# ---------------------------------------------------------------------------
# quantized (int8 + per-page fp32 scale) variants — same traced shapes
# ---------------------------------------------------------------------------

def gather_pages_q8(pages, scales, page_table):
    """:func:`gather_pages` for an int8 pool: ``pages (L, P, S, ...)``
    int8 + ``scales (L, P)`` fp32, indexed by ``page_table (B, M)`` →
    DEQUANTIZED fp32 caches ``(L, B, M*S, ...)`` — each page's scale
    broadcasts over its token slots, so the gathered cache feeds the
    unchanged decode-model contract."""
    import jax.numpy as jnp
    g = pages[:, page_table]                   # (L, B, M, S, ...)
    s = scales[:, page_table]                  # (L, B, M)
    extra = (1,) * (g.ndim - s.ndim)
    out = g.astype(jnp.float32) * s.reshape(s.shape + extra)
    shape = out.shape
    return out.reshape(shape[0], shape[1], shape[2] * shape[3],
                       *shape[4:])


def scatter_token_q8(pages, scales, page_table, positions, new):
    """:func:`scatter_token` for an int8 pool: quantize the step's new
    fp32 rows ``new (L, B, H, D)`` into their pages and grow each
    touched page's scale monotonically — ``max(old, amax/127)`` — with
    the page body requantized in-program under the grown scale, so
    earlier tokens keep dequantizing to (within one rounding step of)
    their stored values. A write landing on a page's FIRST slot
    instead sets the scale fresh and zeroes the body: pages are filled
    in position order, so slot 0 means a newly (re)allocated page
    whose stale scale/content belong to a prior tenant. Returns the
    updated ``(pages, scales)``."""
    import jax.numpy as jnp
    S = pages.shape[2]
    B = new.shape[1]
    pos = jnp.asarray(positions, jnp.int32)
    pidx = jnp.take_along_axis(
        jnp.asarray(page_table, jnp.int32), (pos // S)[:, None],
        axis=1)[:, 0]                          # (B,)
    slot = pos % S
    amax = jnp.max(jnp.abs(new), axis=(2, 3))  # (L, B)
    need = jnp.maximum(amax, _EPS) / _INT8_MAX
    old = scales[:, pidx]                      # (L, B)
    first = (slot == 0)[None, :]
    new_scale = jnp.where(first, need, jnp.maximum(old, need))
    ratio = jnp.where(first, 0.0, old / new_scale)
    body = pages[:, pidx].astype(jnp.float32) \
        * ratio[:, :, None, None, None]        # (L, B, S, H, D)
    body = body.at[:, jnp.arange(B), slot].set(
        new / new_scale[:, :, None, None])
    body = jnp.clip(jnp.round(body), -_INT8_MAX, _INT8_MAX) \
        .astype(pages.dtype)
    return (pages.at[:, pidx].set(body),
            scales.at[:, pidx].set(new_scale))


def scatter_prefill_q8(pages, scales, page_table_row, seq, n_valid):
    """:func:`scatter_prefill` for an int8 pool: one request's prefill
    K (or V) rows ``seq (L, Lr, H, D)`` quantize page-chunk-wise —
    each covered page's scale comes from its own ``page_size``-token
    chunk's amax (rows at/after ``n_valid`` are zeroed first, so rung
    padding garbage neither lands in a page nor inflates a scale).
    Scales are SET, not grown: prefill is always a page's first
    tenant. Returns the updated ``(pages, scales)``."""
    import jax
    import jax.numpy as jnp
    S = pages.shape[2]
    L, Lr = seq.shape[0], seq.shape[1]
    pos = jax.lax.iota(jnp.int32, Lr)
    valid = pos < n_valid
    seq = jnp.where(valid[None, :, None, None], seq, 0.0)
    table = jnp.asarray(page_table_row, jnp.int32)
    pidx = jnp.where(valid, table[pos // S], 0)
    Lp = -(-Lr // S) * S
    seq_p = seq if Lp == Lr else jnp.pad(
        seq, ((0, 0), (0, Lp - Lr)) + ((0, 0),) * (seq.ndim - 2))
    chunks = seq_p.reshape(L, Lp // S, S, *seq.shape[2:])
    red = tuple(range(2, chunks.ndim))
    pscale = jnp.maximum(jnp.max(jnp.abs(chunks), axis=red), _EPS) \
        / _INT8_MAX                            # (L, n_chunks)
    rscale = jnp.repeat(pscale, S, axis=1)[:, :Lr]
    q = jnp.clip(jnp.round(seq / rscale[:, :, None, None]),
                 -_INT8_MAX, _INT8_MAX).astype(pages.dtype)
    pages = pages.at[:, pidx, pos % S].set(q)
    cpos = jax.lax.iota(jnp.int32, Lp // S) * S
    cpidx = jnp.where(cpos < n_valid, table[cpos // S], 0)
    return pages, scales.at[:, cpidx].set(pscale)


# ---------------------------------------------------------------------------
# the layouts: one object a kind of cache
# ---------------------------------------------------------------------------

class _PerHeadKV:
    """Per-head K and V in a float dtype, two arrays ``(L, P, S, H, D)``
    — and the base of the other kinds. A layout alone knows how its
    kind is stored, attended, written and copied: which arrays a
    program carries (:meth:`arrays`), what ``attend`` a decode step
    hands its model, how a prefill's sequences reach their pages and
    how a step's new rows reach theirs. ``specs`` is the model's
    declaration, ``((name, trailing shape), ...)``; :func:`cache_layout`
    picks the class."""

    def __init__(self, specs, dtype):
        self.specs = specs
        self.dtype = dtype

    # the axis of every carried array along which a mesh splits the
    # key/value heads — side by side in a packed row or on a dimension
    # of their own, the heads lie on axis 3 — or None: a kind no mesh
    # shards yet (a latent row has no heads; int8 pages' scales)
    head_axis = 3

    def arrays(self, n_layers, n_pages, page_size):
        """``(name, shape, dtype)`` of every array a program carries, in
        the order it carries (and returns) them. A page copy
        (``DecodeServer._cow_fn``) is ``a.at[:, dst].set(a[:, src])``
        over each: axis 1 of every carried array is the page."""
        lead = (n_layers, n_pages, page_size)
        return tuple((name, lead + trailing, self.dtype)
                     for name, trailing in self.specs)

    def token_bytes(self, n_layers):
        """Bytes one token occupies across all layers and arrays."""
        return n_layers * self.dtype.itemsize * sum(
            math.prod(trailing) for _name, trailing in self.specs)

    def attend(self, pools, page_tables, positions):
        """The ``attend`` a decode step hands its model."""
        return functools.partial(paged_attention, *pools, page_tables,
                                 positions)

    def write_prefill(self, pools, page_table_row, seqs, n_valid):
        """One request's prefill sequences ``(L, B=1, Lr, ...)`` into
        their pages; returns the carried arrays, updated."""
        return tuple(scatter_prefill(pages, page_table_row, seq[:, 0],
                                     n_valid)
                     for pages, seq in zip(pools, seqs))

    def write_tokens(self, pools, page_tables, positions, new,
                     force_pallas=False):
        """One decode step's new rows ``(L, B, ...)`` an array into their
        pages (``new`` may carry more behind them: the model's step
        counters); returns the carried arrays, updated."""
        return tuple(scatter_token(pages, page_tables, positions, rows)
                     for pages, rows in zip(pools, new))

    # a block model's step (``DecodeServer``'s contract for a model with
    # ``block_length``): a few query positions a row, grouped-query heads,
    # every position of the block visible to every other. The CAUSAL
    # block of a speculative step exists for the latent layout only
    blocks = True
    causal_blocks = False

    def attend_block(self, pools, page_tables, positions):
        """The ``attend`` of a pass over ONE block a row: ``positions``
        committed keys and the block's own."""
        return functools.partial(paged_block_attention, *pools,
                                 page_tables, positions)

    def write_block(self, pools, page_tables, positions, new, commit,
                    force_pallas=False, layer=0):
        """A pass's new rows ``(n, B, Q, ...)`` an array, of the ``n``
        layers from ``layer`` on, into their page where ``commit``, into
        the dump page where not."""
        return tuple(write_block_rows(pages, page_tables, positions, rows,
                                      commit, force_pallas, layer)
                     for pages, rows in zip(pools, new))

    def block_step(self, pools, page_tables, positions, commit, fresh,
                   force_pallas=False):
        """What a block step hands its model as ``attend``
        (:class:`_BlockStep`)."""
        return _BlockStep(self, pools, page_tables, positions, commit,
                          fresh, force_pallas)

    # a chunk of ONE row's prompt beside a step's decode rows
    # (``DecodeServer``'s mixed step): ``C`` consecutive positions from
    # ``start`` on as ``C`` more lanes behind the step's ``B``. Every
    # float layout has it; int8 pages (a chunk's rows would requantize
    # theirs) have not; beside fixed state a row (``_RowStateBeside``) the
    # pages have it and the model says whether its state can take one
    chunks = True
    _chunk_attention = staticmethod(paged_chunk_attention)

    def attend_chunk(self, pools, page_tables, positions, table_row,
                     start):
        """The ``attend`` of a mixed step, called once a layer with the
        model's own arguments, every array ``(B + C, ...)``: the first
        ``B`` lanes are the decode rows', through :meth:`attend` as in
        any step (they keep the paged kernels they have); the lanes
        behind them one row's chunk, lane ``j`` over that row's keys
        before ``start`` and the chunk's own new rows ``<= j``."""
        import jax.numpy as jnp
        B = len(positions)
        rows = self.attend(pools, page_tables, positions)
        chunk = functools.partial(self._chunk_attention, *pools, table_row,
                                  start)

        def attend(layer, *arrays, force_pallas=False, **how):
            head = rows(layer, *(a[:B] for a in arrays),
                        force_pallas=force_pallas, **how)
            tail = chunk(layer, *(a[B:] for a in arrays), **how)
            return jnp.concatenate([head, tail.astype(head.dtype)])

        return attend

    def write_chunk(self, pools, table_row, start, n_live, new):
        """A chunk's new rows ``(L, C, ...)`` an array into their pages,
        the first ``n_live`` of them, from position ``start`` on; they
        may cross page boundaries."""
        return tuple(write_chunk_rows(pages, table_row, start, n_live,
                                      rows)
                     for pages, rows in zip(pools, new))


class _BlockStep:
    """The ``attend`` of a block step, which runs TWO blocks a row — the
    row's block at ``positions[b]`` and the block after it — and the
    pools as its layers leave them (``.pools``). Called once a layer with
    ``q (B, 2Q, Hq, D)``, ``k_new``/``v_new (B, 2Q, Hkv, D)``:

    - where ``commit[b]`` the first block's keys and values are FINAL and
      are written into the layer's pages first (the dump page for every
      other row), so that
    - where ``fresh[b]`` the second block's queries read them there: they
      attend ``positions[b] + Q`` keys in the pool — everything committed
      before, and the block just committed, whole — and their own ``Q``;
      where not, the second block is dead and attends none (length 0, and
      its own keys, which nothing reads);
    - the first block's queries attend ``positions[b]`` keys in the pool
      and their own ``Q``, committing or not.

    Both blocks go through :func:`paged_block_attention` together, as
    ``2B`` rows of ``Q`` queries: one kernel call a layer."""

    def __init__(self, layout, pools, page_tables, positions, commit,
                 fresh, force_pallas):
        import jax.numpy as jnp
        self.layout, self.pools = layout, tuple(pools)
        self.page_tables = jnp.asarray(page_tables, jnp.int32)
        self.positions = jnp.asarray(positions, jnp.int32)
        self.commit, self.fresh = commit, fresh
        self.force_pallas = force_pallas
        # both blocks of a row walk the row's table
        self.both_tables = jnp.concatenate([self.page_tables] * 2)

    def __call__(self, layer, q, k_new, v_new, *, scale=None,
                 force_pallas=False):
        import jax.numpy as jnp
        B, Q = q.shape[0], q.shape[1] // 2

        def rows(a):                # (B, 2Q, ...) -> (2B, Q, ...)
            return jnp.concatenate([a[:, :Q], a[:, Q:]], axis=0)

        self.pools = self.layout.write_block(
            self.pools, self.page_tables, self.positions,
            [k_new[None, :, :Q], v_new[None, :, :Q]], self.commit,
            self.force_pallas, layer)
        pos = self.positions
        out = paged_block_attention(
            *self.pools, self.both_tables,
            jnp.concatenate([pos, jnp.where(self.fresh, pos + Q, 0)]),
            layer, rows(q), rows(k_new), rows(v_new), scale=scale,
            force_pallas=force_pallas)
        return jnp.concatenate([out[:B], out[B:]], axis=1)


class _PackedHeadKV(_PerHeadKV):
    """Per-head K and V whose heads do not fill the dtype's sublane tile
    (4 heads of bfloat16 where a tile holds 16 rows): a token's heads are
    packed side by side into ONE row of ``H * D`` lanes, two arrays ``(L,
    P, S, H * D)``. Stored ``(..., S, 4, 128)`` a tiled array pads the 4
    to 16 and takes four times its bytes in HBM (or is laid out
    token-minor and copied whole around every kernel call, as a 576-wide
    latent row was). The model's declaration stays ``(H, D)``; only this
    object knows the row is packed. Every step, of one position or of a
    block, attends through :func:`paged_block_attention` and writes in
    place."""

    def arrays(self, n_layers, n_pages, page_size):
        lead = (n_layers, n_pages, page_size)
        return tuple((name, lead + (math.prod(trailing),), self.dtype)
                     for name, trailing in self.specs)

    attend = _PerHeadKV.attend_block

    def write_prefill(self, pools, page_table_row, seqs, n_valid):
        return tuple(
            write_prefill_pages(
                pages, page_table_row,
                seq[:, 0].reshape(seq.shape[0], seq.shape[2], -1), n_valid)
            for pages, seq in zip(pools, seqs))

    def write_tokens(self, pools, page_tables, positions, new,
                     force_pallas=False):
        import jax.numpy as jnp
        every = jnp.ones(jnp.shape(positions), bool)
        return self.write_block(
            pools, page_tables, positions,
            [rows[:, :, None] for rows in new[:len(pools)]], every,
            force_pallas)


class _Latent(_PerHeadKV):
    """One float array ``(L, P, S, W)``: a token's row is the compressed
    K/V and the shared rotary key. Written in place (page by page, row
    by row) so a 16-bit pool is never widened by a scatter."""

    # its block form is CAUSAL inside the block (consecutive positions:
    # a speculative step's verify and draft passes); the all-see-all
    # block of a diffusion model is not written for it
    blocks = False
    causal_blocks = True
    head_axis = None

    def attend(self, pools, page_tables, positions):
        return functools.partial(paged_latent_attention, *pools,
                                 page_tables, positions)

    _chunk_attention = staticmethod(paged_latent_chunk_attention)

    def attend_causal(self, pools, page_tables, positions):
        """The ``attend`` a speculative step hands its model."""
        return functools.partial(paged_latent_causal_attention, *pools,
                                 page_tables, positions)

    def write_causal(self, pools, page_tables, positions, new,
                     force_pallas=False):
        """A speculative step's new rows ``(L, B, Q, W)`` into their
        pages, from ``positions`` on; they may straddle a boundary."""
        return tuple(write_latent_rows(pages, page_tables, positions, rows,
                                       force_pallas)
                     for pages, rows in zip(pools, new))

    def write_prefill(self, pools, page_table_row, seqs, n_valid):
        return tuple(write_prefill_pages(pages, page_table_row, seq[:, 0],
                                         n_valid)
                     for pages, seq in zip(pools, seqs))

    def write_tokens(self, pools, page_tables, positions, new,
                     force_pallas=False):
        return tuple(write_token_rows(pages, page_tables, positions, rows,
                                      force_pallas)
                     for pages, rows in zip(pools, new))


class _PerHeadKVInt8(_PerHeadKV):
    """Per-head K and V as int8 pages with one fp32 scale a ``(layer,
    page)``: a program carries ``k, v, k_scale, v_scale``. The scales
    are part of a page's content (axis 1 is the page, so the page copy
    carries them); the model's contract stays float ``q``/``k_new``/
    ``v_new`` — attention applies the scales page by page, the writes
    quantize."""

    blocks = False      # a block's rows would requantize their page
    chunks = False      # and so would a chunk's
    head_axis = None    # a page's scale is over all its heads

    def arrays(self, n_layers, n_pages, page_size):
        pages = super().arrays(n_layers, n_pages, page_size)
        return pages + tuple(
            (name + "_scale", (n_layers, n_pages), "float32")
            for name, _shape, _dtype in pages)

    def attend(self, pools, page_tables, positions):
        k_pages, v_pages, k_scale, v_scale = pools
        return functools.partial(paged_attention, k_pages, v_pages,
                                 page_tables, positions, k_scale=k_scale,
                                 v_scale=v_scale)

    def write_prefill(self, pools, page_table_row, seqs, n_valid):
        (k, k_scale), (v, v_scale) = (
            scatter_prefill_q8(pages, scales, page_table_row, seq[:, 0],
                               n_valid)
            for pages, scales, seq in zip(pools[:2], pools[2:], seqs))
        return k, v, k_scale, v_scale

    def write_tokens(self, pools, page_tables, positions, new,
                     force_pallas=False):
        (k, k_scale), (v, v_scale) = (
            scatter_token_q8(pages, scales, page_tables, positions, rows)
            for pages, scales, rows in zip(pools[:2], pools[2:], new))
        return k, v, k_scale, v_scale


def declared_arrays(model):
    """What ``model`` declares its cache to hold, as ``(specs, dtype)``:
    ``specs`` the ``((name, trailing shape), ...)`` of
    ``model.cache_arrays`` (per-head K and V by ``n_heads``/``head_dim``
    for a model that predates the declaration), ``dtype`` the one the
    declaration asks for, or None."""
    cache = getattr(model, "cache_arrays", None)
    if cache is None:
        if not (hasattr(model, "n_heads") and hasattr(model, "head_dim")):
            raise MXNetError(
                "DecodeServer: model declares no cache_arrays = "
                "((name, trailing shape[, dtype]), ...) and has no "
                "n_heads/head_dim to mean per-head K and V by (see "
                "serving.decode.ToyDecoderLM)")
        cache = (("k", (model.n_heads, model.head_dim)),
                 ("v", (model.n_heads, model.head_dim)))
    specs = tuple((str(c[0]), tuple(int(d) for d in c[1])) for c in cache)
    dtypes = {c[2] for c in cache if len(c) > 2}
    if len(dtypes) > 1:
        raise MXNetError(
            "DecodeServer: the arrays of one pool share a dtype, the "
            "model declares %s" % sorted(dtypes))
    return specs, (dtypes.pop() if dtypes else None)


def declared_state(model):
    """What ``model`` declares as fixed state a row: ``(specs, layers)``,
    ``specs`` the ``((name, shape a row, dtype), ...)`` of
    ``model.state_arrays`` and ``layers`` its ``state_layers``; ``((),
    0)`` for a model that keeps none."""
    state = getattr(model, "state_arrays", None)
    if not state:
        return (), 0
    return tuple((str(n), tuple(int(d) for d in shape), str(dtype))
                 for n, shape, dtype in state), int(model.state_layers)


class RowState:
    """What a decode step hands its model of the fixed state a row:
    ``arrays``, one a declared name, WHOLE — ``(state_layers, rows,
    *shape)``, every row of the window — ``slots (B,)``, the row of them
    each row of the step works on (a permutation of all of them: a dead
    row of the step takes a row nobody else has), its ``inverse``, and
    ``live (B,)``: a row that is not live leaves its state as it was.
    The model returns the arrays, updated, among its results.
    ``page_size`` is the pool's, for a model that counts the pages its
    rows' keys occupy."""

    __slots__ = ("arrays", "slots", "inverse", "live", "page_size")

    def __init__(self, arrays, slots, live, page_size=None):
        import jax.numpy as jnp
        self.arrays = tuple(arrays)
        self.page_size = page_size
        self.slots = jnp.asarray(slots, jnp.int32)
        self.live = jnp.asarray(live, bool)
        n = self.slots.shape[0]
        self.inverse = jnp.zeros((n,), jnp.int32).at[self.slots].set(
            jnp.arange(n, dtype=jnp.int32))


class _RowStateBeside:
    """A paged layout (``pages``: the latent one, or per-head K and V,
    packed or not) with fixed state a row beside it: a program's
    ``pools`` are the pages' arrays and then one array a declared state,
    ``(state_layers, rows, *shape)`` — a linear-attention layer's matrix
    a head and its convolution rows (``serving.hybrid_linear_moe``), or a
    sliding-window layer's RING of its last keys and one of its values
    (``serving.window_moe``): whatever is the same bytes whatever the
    context. Attending and the pages' writes are the inner layout's, over
    the arrays that are its own and the model's ``cache_layers`` only;
    the state is handed to a step whole (:class:`RowState`) and written
    by a prefill into ONE row, whole — so what of a row is valid follows
    from the row's position, never from what a slot's last tenant left.
    No block form: a pass over several positions a row would have to keep
    the state after each. A CHUNK of a prompt on a mixed step's lanes is
    the pages' own to attend and write (``chunks`` is the inner layout's);
    what the state does with a chunk is the model's, which says whether it
    can by declaring ``chunk_lanes``: a ring takes one (more keys into
    slots ``t % W``, attended under the band, valid by position as ever)
    and so does a recurrence (the same rule over the chunk's positions
    from the row's state — zeros where the chunk starts the prompt — and
    the row written back at the last live lane); a model that does not
    declare it keeps the whole-prompt prefill."""

    blocks = False
    causal_blocks = False
    chunks = property(lambda self: self.pages.chunks)
    head_axis = property(lambda self: self.pages.head_axis)

    def __init__(self, pages, state, layers):
        self.pages, self.state, self.state_layers = pages, state, layers
        self.specs, self.dtype = pages.specs, pages.dtype

    def arrays(self, n_layers, n_pages, page_size):
        return self.pages.arrays(n_layers, n_pages, page_size)

    def token_bytes(self, n_layers):
        return self.pages.token_bytes(n_layers)

    def state_arrays(self, rows):
        """``(name, shape, dtype)`` of the state arrays for a window of
        ``rows``, in the order a program carries them behind the pages."""
        return tuple((name, (self.state_layers, rows) + shape, dtype)
                     for name, shape, dtype in self.state)

    def split(self, pools):
        """``pools`` as ``(the pages' arrays, the state arrays)``."""
        n = len(pools) - len(self.state)
        return tuple(pools[:n]), tuple(pools[n:])

    def attend(self, pools, page_tables, positions):
        return self.pages.attend(self.split(pools)[0], page_tables,
                                 positions)

    def write_prefill(self, pools, page_table_row, seqs, n_valid):
        return self.pages.write_prefill(self.split(pools)[0],
                                        page_table_row, seqs, n_valid)

    def write_tokens(self, pools, page_tables, positions, new,
                     force_pallas=False):
        return self.pages.write_tokens(self.split(pools)[0], page_tables,
                                       positions, new, force_pallas)

    def attend_chunk(self, pools, page_tables, positions, table_row,
                     start):
        return self.pages.attend_chunk(self.split(pools)[0], page_tables,
                                       positions, table_row, start)

    def write_chunk(self, pools, table_row, start, n_live, new):
        return self.pages.write_chunk(self.split(pools)[0], table_row,
                                      start, n_live, new)

    def row_state(self, pools, slots, live):
        """The :class:`RowState` of a step over ``pools``."""
        return RowState(self.split(pools)[1], slots, live,
                        page_size=pools[0].shape[2])

    def write_state(self, pools, slot, new, valid):
        """A prefill's state ``new`` (one a declared array, ``(state_
        layers, 1, *shape)``) into row ``slot``, whole, where ``valid``
        (a warm-up's prefill writes nothing); returns the state arrays."""
        import jax.numpy as jnp
        return tuple(
            a.at[:, slot].set(jnp.where(valid, n[:, 0].astype(a.dtype),
                                        a[:, slot]))
            for a, n in zip(self.split(pools)[1], new))


@functools.lru_cache(maxsize=None)
def _with_row_state(pages, state, layers):
    return _RowStateBeside(pages, state, layers)


@functools.lru_cache(maxsize=None)
def cache_layout(specs, dtype):
    """THE choice of cache kind, from the two things that can be
    observed: the declaration ``specs`` (``((name, trailing shape),
    ...)``) and the pool's ``dtype``. Two arrays of per-head vectors
    ``(H, D)`` are K and V — int8 pages with per-page scales under an
    int8 dtype; one array of rows ``(W,)`` is a latent pool. The pool
    asks once, when it is built; a program being traced asks again with
    the same two observations (:func:`layout_for`) and is handed the
    same object."""
    ranks = tuple(len(trailing) for _name, trailing in specs)
    int8 = dtype == "int8"
    if ranks == (2, 2):
        if int8:
            return _PerHeadKVInt8(specs, dtype)
        heads, width = specs[0][1]
        # heads that would not fill a sublane tile of a 16-bit dtype (16
        # rows) under a head size of whole lanes: pack them into one row
        packs = dtype.itemsize == 2 and heads % 16 and width % 128 == 0 \
            and specs[0][1] == specs[1][1]
        return (_PackedHeadKV if packs else _PerHeadKV)(specs, dtype)
    if int8:
        raise MXNetError(
            "KVCachePool: int8 pages with per-page scales exist for "
            "the per-head K/V layout only, not for %s" % (specs,))
    if ranks == (1,):
        return _Latent(specs, dtype)
    raise MXNetError(
        "KVCachePool: no cache layout for %s — a model caches per-head "
        "K and V, two arrays of (n_heads, head_dim), or one latent "
        "array of (row_width,), in pages; what a layer keeps at a fixed "
        "size a row (a recurrent state, a ring of a window's keys) is "
        "declared beside them as state_arrays, not as a page shape"
        % (specs,))


def layout_for(model, pools):
    """The layout of the ``pools`` a program of ``model``'s was handed —
    the pool's own (:func:`cache_layout` is cached): the pages' layout,
    and around it :class:`_RowStateBeside` for a model that declares
    fixed state a row."""
    pages = cache_layout(declared_arrays(model)[0], pools[0].dtype)
    state, layers = declared_state(model)
    return _with_row_state(pages, state, layers) if state else pages


def shard_specs(model, layout, axis):
    """One ``PartitionSpec`` a carried array of ``layout`` — the pages'
    arrays, then the model's state arrays — for a mesh ``axis`` that
    splits the key/value heads: a page array along the layout's
    ``head_axis``, a state array ``(state_layers, rows, *shape)`` along
    the dimension of its row shape the model names (``state_head_dims``).
    A page id then names the same page on every chip, each holding its
    heads' part; the host's page tables do not know. A kind no mesh
    shards is refused with a typed error."""
    from jax.sharding import PartitionSpec as P
    if layout.head_axis is None:
        raise MXNetError(
            "KVCachePool: no mesh splits %s pages yet — per-head K and V "
            "in a float dtype are split by key/value head"
            % type(getattr(layout, "pages", layout)).__name__)

    def along(at):
        return P(*([None] * at + [axis]))

    n_state = len(getattr(layout, "state", ()))
    dims = tuple(getattr(model, "state_head_dims", ()))
    if len(dims) != n_state:
        raise MXNetError(
            "KVCachePool: the model declares %d state arrays and names "
            "the head dimension of %d (state_head_dims)"
            % (n_state, len(dims)))
    return (along(layout.head_axis),) * len(layout.specs) \
        + tuple(along(2 + d) for d in dims)


# ---------------------------------------------------------------------------
# the prefix index
# ---------------------------------------------------------------------------

class PrefixIndex:
    """Content-addressed index over page-aligned token runs — the
    sharing map of the prefix cache.

    Keys are SHA-1 digests of the FULL token prefix up to each page
    boundary, computed incrementally and seeded with a namespace
    (share group + weight generation): a page's K/V content depends on
    every token before it AND on the weights that computed it, so the
    key covers exactly that. Values are page ids. Each entry holds ONE
    pool reference — an indexed page survives the request that filled
    it (that is the cache) until cold-prefix eviction reclaims it.
    Entries are LRU-ordered (refreshed on hit and on insert); eviction
    only ever takes entries whose page has no holder beyond the index
    itself. All mutation happens under the owning pool's lock."""

    def __init__(self, page_size):
        self.page_size = int(page_size)
        self._entries = OrderedDict()    # digest -> (page, namespace)
        self.hits = 0          # lookups that matched >= 1 page
        self.misses = 0        # lookups that matched nothing
        self.hit_tokens = 0    # prompt tokens served from the index
        self.inserted = 0      # entries ever registered
        self.evicted = 0       # entries dropped (cold or released)

    def __len__(self):
        return len(self._entries)

    def digests(self, namespace, tokens):
        """One digest per FULL page of ``tokens``, each covering the
        whole prefix up to its page boundary (chain-hashed: page i's
        digest extends page i-1's)."""
        import numpy as np
        arr = np.ascontiguousarray(np.asarray(tokens, np.int32))
        h = hashlib.sha1(repr(namespace).encode())
        S = self.page_size
        out = []
        for i in range(len(arr) // S):
            h.update(arr[i * S:(i + 1) * S].tobytes())
            out.append(h.hexdigest())
        return out

    def _walk_locked(self, digests):
        """The pages of the longest consecutive hit run (no refresh,
        no refcounts — the pool wraps this)."""
        pages = []
        for d in digests:
            ent = self._entries.get(d)
            if ent is None:
                break
            pages.append(ent[0])
        return pages


# ---------------------------------------------------------------------------
# the pool
# ---------------------------------------------------------------------------

class KVCachePool:
    """One model's paged KV storage + host-side page accounting.

    The device arrays (``.arrays``: every one a program carries, in the
    layout's order, an int8 pool's scales among them) are owned by the
    decode server's scheduler thread: compiled steps take them as inputs
    and the scheduler re-points them at the returned (functionally
    updated) arrays. Page ids are allocated lowest-first — allocation
    order is deterministic, so tests can predict table contents. Page
    0 is reserved as the dump page and never allocated."""

    def __init__(self, n_layers, n_heads=None, head_dim=None, *,
                 arrays=None, page_size=None, n_pages=None, dtype=None,
                 device=None, state=(), state_layers=0, state_rows=0,
                 shardings=None):
        import jax.numpy as jnp
        self.page_size = int(page_size) if page_size is not None \
            else envs.get_int("MXNET_KV_PAGE_SIZE")
        self.n_pages = int(n_pages) if n_pages is not None \
            else envs.get_int("MXNET_KV_POOL_PAGES")
        if self.page_size < 1:
            raise MXNetError("KVCachePool: page_size must be >= 1, "
                             "got %d" % self.page_size)
        if self.n_pages < 2:
            raise MXNetError(
                "KVCachePool: need at least 2 pages (page 0 is the "
                "reserved dump page), got %d" % self.n_pages)
        # the model's declaration, ``(name, trailing shape)`` an array
        # (per-head K and V by default), and the dtype pick the layout:
        # the one object that knows this kind of cache
        if arrays is None:
            arrays = (("k", (int(n_heads), int(head_dim))),
                      ("v", (int(n_heads), int(head_dim))))
        self.array_specs = tuple(
            (str(name), tuple(int(d) for d in trailing))
            for name, trailing in arrays)
        if dtype is None:
            name = envs.get_str("MXNET_KV_DTYPE") or "float32"
            try:
                dtype = jnp.dtype(name)
            except TypeError:
                raise MXNetError(
                    "KVCachePool: unknown MXNET_KV_DTYPE %r (one of "
                    "float32 | bfloat16 | int8)" % name)
        self.dtype = jnp.dtype(dtype)
        self.layout = cache_layout(self.array_specs, self.dtype)
        carried = self.layout.arrays(int(n_layers), self.n_pages,
                                     self.page_size)
        # fixed state a row (``declared_state``), one array a name
        # behind the pages' own: ``state_rows`` rows, a server's window
        self.state_specs = tuple(state)
        self.state_rows = int(state_rows) if state else 0
        self.state_bytes, self.state_bytes_by_array = 0, {}
        if state:
            self.layout = _with_row_state(self.layout, self.state_specs,
                                          int(state_layers))
            held = self.layout.state_arrays(self.state_rows)
            self.state_bytes_by_array = {
                name: math.prod(shape) * jnp.dtype(dt).itemsize
                for name, shape, dt in held}
            self.state_bytes = sum(self.state_bytes_by_array.values())
            carried += held
        # every array a program carries, in the layout's order (an int8
        # pool's scales among them). Allocated ON the target device: a
        # replica's pool must never be staged through the first chip's
        # memory on its way there
        # Over a mesh (``shardings(layout)`` gives one ``NamedSharding`` a
        # carried array: ``shard_specs``) every array is born sharded by
        # key/value head, and what the pool says of bytes is ONE chip's
        self.names = tuple(name for name, _shape, _dtype in carried)
        self.shards = 1
        if shardings is not None:
            where = list(shardings(self.layout))
            whole = carried[0][1]
            self.shards = math.prod(whole) // math.prod(
                where[0].shard_shape(whole))
            self.state_bytes //= self.shards
            self.state_bytes_by_array = {
                name: n // self.shards
                for name, n in self.state_bytes_by_array.items()}
        else:
            where = [device] * len(carried)
        self.arrays = [jnp.zeros(shape, dt, device=at)
                       for (_name, shape, dt), at in zip(carried, where)]
        self.n_layers = int(n_layers)
        self._lock = threading.Lock()
        # serializes co-tenant servers' compiled steps on the shared
        # functional arrays — two schedulers must never fork the arrays
        self.step_lock = threading.Lock()
        self._free = list(range(self.n_pages - 1, 0, -1))  # pop() -> 1
        self._rows_free = list(range(self.state_rows - 1, -1, -1))
        self._state_writes = 0
        self._used_peak = 0
        self._evicted = 0
        self._alloc_failures = 0
        self._pages_alloced = 0   # cumulative page grants (metering's
        self._pages_freed = 0     # page-flow conservation inputs)
        self._refs = {}          # page -> refcount (absent == free)
        self._page_owner = {}    # page -> client name (quota credit)
        self._clients = {}       # name -> {quota, priority, preempt, used}
        self._cow_splits = 0
        self._quota_denials = 0
        self.prefix = PrefixIndex(self.page_size)
        self.token_bytes = self.layout.token_bytes(self.n_layers) \
            // self.shards

    # the per-head kinds' pages by name, for tests and tools; the
    # programs take ``.arrays`` whole
    k = property(lambda self: self.arrays[0])
    v = property(lambda self: self.arrays[1])

    @property
    def usable_pages(self):
        """Allocatable pages (the pool minus the dump page)."""
        return self.n_pages - 1

    def pages_for(self, n_tokens):
        return pages_for(n_tokens, self.page_size)

    def alloc(self, n, owner=None):
        """``n`` page ids (lowest-free-first), or None when the pool
        cannot satisfy the request — the caller decides between
        waiting, shedding, and preempting a lower-priority holder.

        With ``owner=`` (an :meth:`attach` name) the pages count
        against that model's quota; a quota denial fails WITHOUT
        evicting anyone else's cache. A plain shortfall first evicts
        COLD prefix-index entries — pages nobody holds beyond the
        index — through the counted ``kv_evict`` path, then retries."""
        n = int(n)
        while True:
            with self._lock:
                client = self._clients.get(owner)
                if client is not None and client["quota"] is not None \
                        and client["used"] + n > client["quota"]:
                    self._quota_denials += 1
                    self._alloc_failures += 1
                    return None
                if n <= len(self._free):
                    pages = [self._free.pop() for _ in range(n)]
                    for p in pages:
                        self._refs[p] = 1
                        if owner is not None:
                            self._page_owner[p] = owner
                    if client is not None:
                        client["used"] += n
                    self._pages_alloced += n
                    used = self.usable_pages - len(self._free)
                    if used > self._used_peak:
                        self._used_peak = used
                    return pages
                cold = self._pop_cold_prefixes_locked(
                    n - len(self._free))
                if not cold:
                    self._alloc_failures += 1
                    return None
            self.free(cold)   # counted kv_evict, outside the lock

    def free(self, pages):
        """Drop one reference per page. A still-shared page (refcount
        > 1) just decrements; the LAST holder's drop visits the
        ``kv_evict`` fault site — a planned ``raise`` there is counted
        and the page is reclaimed anyway, a reclaim fault must never
        leak memory. Returns the number of pages actually reclaimed
        (refcount drops don't count)."""
        reclaimed = 0
        for p in pages:
            p = int(p)
            with self._lock:
                refs = self._refs.get(p, 1)
                if refs > 1:
                    self._refs[p] = refs - 1
                    continue
                self._refs.pop(p, None)
                owner = self._page_owner.pop(p, None)
                client = self._clients.get(owner)
                if client is not None and client["used"] > 0:
                    client["used"] -= 1
            try:
                fault.inject("kv_evict")
            except fault.InjectedFault:
                pass          # counted in fault.stats(); never a leak
            with self._lock:
                self._free.append(p)
                self._evicted += 1
                self._pages_freed += 1
                reclaimed += 1
        return reclaimed

    def retain(self, pages):
        """Add one reference to each page (prefix-share / index)."""
        with self._lock:
            for p in pages:
                p = int(p)
                self._refs[p] = self._refs.get(p, 1) + 1

    def ref(self, page):
        """Current refcount of ``page`` (0 if free)."""
        with self._lock:
            return self._refs.get(int(page), 0)

    def cow_release(self, page):
        """Drop the writer's reference from a shared page after a
        copy-on-write split (the other holders keep it)."""
        with self._lock:
            p = int(page)
            refs = self._refs.get(p, 1)
            if refs > 1:
                self._refs[p] = refs - 1
            self._cow_splits += 1

    # -- multi-model attachment ---------------------------------------

    def attach(self, name, *, quota=None, priority=0, preempt=None):
        """Register a decode server (a model / weight generation) as a
        pool tenant. Returns the — uniquified — owner name to pass to
        ``alloc(owner=)``. ``quota`` caps the tenant's concurrently
        held pages (default ``MXNET_KV_MODEL_QUOTA``; 0 = unlimited);
        ``preempt`` is a callback :meth:`request_preempt` may invoke
        from a HIGHER-priority tenant's thread — it must only schedule
        work (set a flag), never touch pages directly."""
        if quota is None:
            q = envs.get_int("MXNET_KV_MODEL_QUOTA")
            quota = q if q > 0 else None
        with self._lock:
            base = str(name)
            uniq = base
            i = 1
            while uniq in self._clients:
                i += 1
                uniq = "%s-%d" % (base, i)
            self._clients[uniq] = {
                "quota": int(quota) if quota is not None else None,
                "priority": int(priority),
                "preempt": preempt,
                "used": 0,
            }
            return uniq

    def detach(self, name):
        with self._lock:
            self._clients.pop(name, None)

    def request_preempt(self, owner):
        """Ask LOWER-pool-priority co-tenants to give pages back:
        invokes their preemption callbacks (lowest priority first,
        outside the pool lock) until one accepts. Returns True if any
        tenant accepted — the pages come back asynchronously, so the
        caller retries its alloc on a later tick."""
        with self._lock:
            me = self._clients.get(owner)
            my_pri = me["priority"] if me is not None else 0
            victims = sorted(
                ((c["priority"], n, c["preempt"])
                 for n, c in self._clients.items()
                 if n != owner and c["preempt"] is not None
                 and c["priority"] < my_pri and c["used"] > 0),
                key=lambda t: t[0])
        for _pri, _name, cb in victims:
            try:
                if cb():
                    return True
            except Exception:
                continue
        return False

    # -- prefix cache --------------------------------------------------

    def prefix_lookup(self, namespace, tokens):
        """Longest page-aligned cached run of ``tokens`` under
        ``namespace``: returns ``(pages, n_tokens)`` with one
        reference RETAINED per returned page (the caller's ``free``
        drops them). Visits the ``kv_share`` fault site once per
        would-be hit; a planned raise there is a deterministic
        hash-collision-style MISS — the request pays a full private
        prefill, never a wrong token."""
        digests = self.prefix.digests(namespace, tokens)
        if not digests:
            return [], 0
        with self._lock:
            if not self.prefix._walk_locked(digests):
                self.prefix.misses += 1
                return [], 0
        try:
            fault.inject("kv_share")
        except fault.InjectedFault:
            with self._lock:
                self.prefix.misses += 1
            return [], 0
        with self._lock:
            pages = self.prefix._walk_locked(digests)
            if not pages:          # raced away between the two walks
                self.prefix.misses += 1
                return [], 0
            for i, p in enumerate(pages):
                self._refs[p] = self._refs.get(p, 1) + 1
                self.prefix._entries.move_to_end(digests[i])
            n_tok = len(pages) * self.page_size
            self.prefix.hits += 1
            self.prefix.hit_tokens += n_tok
            return list(pages), n_tok

    def prefix_insert(self, namespace, tokens, pages):
        """Register ``pages`` (backing ``tokens`` from position 0)
        under their prefix digests. First writer wins — an existing
        entry is just refreshed. Each NEW entry retains its page, so
        the cached prefix survives the request that filled it."""
        digests = self.prefix.digests(namespace, tokens)
        with self._lock:
            for i, d in enumerate(digests):
                if i >= len(pages):
                    break
                if d in self.prefix._entries:
                    self.prefix._entries.move_to_end(d)
                    continue
                p = int(pages[i])
                if p not in self._refs:
                    continue      # page already reclaimed elsewhere
                self._refs[p] = self._refs[p] + 1
                self.prefix._entries[d] = (p, namespace)
                self.prefix.inserted += 1

    def prefix_release(self, namespace):
        """Drop every index entry of ``namespace`` (weight swap /
        model teardown) and free the index's references."""
        with self._lock:
            drop = [(d, ent[0])
                    for d, ent in self.prefix._entries.items()
                    if ent[1] == namespace]
            for d, _p in drop:
                del self.prefix._entries[d]
                self.prefix.evicted += 1
        self.free([p for _d, p in drop])

    def _pop_cold_prefixes_locked(self, n):
        """Up to ``n`` COLD index pages (refcount 1 — nobody beyond
        the index holds them), oldest-LRU first. Removes their entries
        and returns the pages for the caller to ``free`` OUTSIDE the
        lock. Refcounted (in-use shared) pages are never victims."""
        out = []
        for d in list(self.prefix._entries):
            if len(out) >= n:
                break
            page, _ns = self.prefix._entries[d]
            if self._refs.get(page, 0) != 1:
                continue
            del self.prefix._entries[d]
            self.prefix.evicted += 1
            out.append(page)
        return out

    def take_row(self):
        """A free row of the state arrays (lowest first), or None: the
        slot a request holds from its prefill to its end."""
        with self._lock:
            return self._rows_free.pop() if self._rows_free else None

    def release_row(self, row):
        """Give a row back. Its content stays where it is: the next
        tenant's prefill writes the row whole."""
        with self._lock:
            self._rows_free.append(int(row))
            self._rows_free.sort(reverse=True)

    def note_state_write(self):
        with self._lock:
            self._state_writes += 1

    def stats(self):
        with self._lock:
            free = len(self._free)
            out = {
                "page_size": self.page_size,
                "pages": self.usable_pages,
                "dtype": str(self.dtype),
                "arrays": {n: list(t) for n, t in self.array_specs},
                "token_bytes": self.token_bytes,
                "free": free,
                "used": self.usable_pages - free,
                "peak_used": self._used_peak,
                "evicted": self._evicted,
                "alloc_failures": self._alloc_failures,
                "pages_alloced": self._pages_alloced,
                "pages_freed": self._pages_freed,
                "shared_pages": sum(
                    1 for r in self._refs.values() if r > 1),
                "cow_splits": self._cow_splits,
                "quota_denials": self._quota_denials,
            }
            if self.shards > 1:
                # ``token_bytes`` and the state's ``bytes`` are a chip's
                out["shards"] = self.shards
            if self.state_specs:
                out["state"] = {
                    "bytes": self.state_bytes,
                    "bytes_by_array": dict(self.state_bytes_by_array),
                    "rows": self.state_rows,
                    "rows_live": self.state_rows - len(self._rows_free),
                    "writes": self._state_writes,
                    "arrays": {n: list(shape)
                               for n, shape, _dt in self.state_specs}}
            if self._clients:
                out["owners"] = {
                    n: {"used": c["used"], "quota": c["quota"],
                        "priority": c["priority"]}
                    for n, c in self._clients.items()}
            return out

    def prefix_stats(self):
        """The prefix cache's own counters (the ``prefix_cache``
        telemetry record body)."""
        with self._lock:
            px = self.prefix
            total = px.hits + px.misses
            return {
                "entries": len(px._entries),
                "hits": px.hits,
                "misses": px.misses,
                "hit_rate": px.hits / total if total else 0.0,
                "hit_tokens": px.hit_tokens,
                "bytes_saved": px.hit_tokens * self.token_bytes,
                "inserted": px.inserted,
                "evicted": px.evicted,
                "shared_pages": sum(
                    1 for r in self._refs.values() if r > 1),
                "cow_splits": self._cow_splits,
            }
