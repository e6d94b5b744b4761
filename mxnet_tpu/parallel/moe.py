"""Mixture-of-Experts over the ``ep`` mesh axis: two expert layers.

**Which is which.**

- :func:`moe_ffn` / :func:`topk_route` — Switch/GShard-style
  static-capacity dispatch (softmax gate, top-k, capacity factor,
  load-balance loss). Routing is two dense einsums over a one-hot
  (token, expert, slot) tensor, so the layer is batched matmuls with
  static shapes; a token that overflows an expert's capacity is DROPPED
  for that expert. Kept for training-style tests
  (``tests/test_pipeline_moe.py``); experts live on the ``ep`` axis via
  the ``(E, D, F)`` leading-dim sharding, XLA inserts the all-to-all.
- :func:`route_grouped_sigmoid` + :func:`expert_ffn` — the DROPLESS
  expert layer the serving path uses (``serving.latent_moe``): a
  sigmoid router with group-limited top-k choice (scores for choice
  carry a correction bias, weights do not), and an expert layer that is
  TOLD WHICH EXPERTS IT HOLDS (``held=(lo, hi)`` of the router's full
  width), routes over all of them, and computes its own experts' part
  of the result: token slots sorted by expert into a grouped matmul
  that reads only the experts some token chose. On one chip it runs
  without its exchange — what the absent experts would have added is
  simply not in the result, and nothing stands in for the absent chips.
  ``sharding_rules.held_experts`` gives ``held`` from the ``ep`` axis.

The grouped matmul is the Pallas kernel ``mx_grouped_matmul.…`` on the
TPU where the shapes tile (:func:`_gmm_tiles`), ``jax.lax.ragged_dot``
elsewhere (the CPU, and the kernel's test reference); the choice is
``flash_attention._choose_path``'s, counted at trace time as
``grouped_matmul_pallas`` / ``grouped_matmul_jnp``.

**The padded slot layout follows the slots it is handed.** A decode
step hands :func:`expert_ffn` 2-10 slots an expert, a prompt hundreds,
and the layout's two dimensions are read from those static shapes and
from nothing else (no argument, no model's name):

- *tile height* (:func:`_gmm_rows`): 16 rows (one bf16 sublane tile)
  for a step, where a taller tile would be padding, up to 128 (the
  MXU's own height) at prefill widths, where a 16-row tile feeds the
  MXU an eighth of its rows. The height is in the kernel's name
  (``.r128``) and counted at trace time as ``grouped_matmul_rows_<m>``.
- *slot order*: slots are numbered choice-major (``j * T + t``), so the
  rows gathered back are ``(k, T, D)`` as they lie, tokens and width on
  the tiled dims, and the select of unheld slots, the weight and the sum
  over the ``k`` choices are ONE pass over them. (Token-major, ``k`` sat
  on the sublanes of an (8, 128) tiling: a relaid copy of the whole
  array before the sum.)

Whatever the height, ``R`` rows are laid out: the dropless worst case,
every slot held and every expert's last tile all but empty.

**The group-limited router chooses by reductions, not by sorts.** On
this chip a ``jax.lax.top_k`` over a short minor axis is a full
``sort``: the three of :func:`route_grouped_sigmoid` (each group's 2
best of 64, the 4 best of 8 group scores, the 8 best of 512) were
``sort f32[1088,8,64]`` 0.69 ms and ``sort f32[1088,512]`` 0.14 ms a
layer over a mixed step's 1,088 lanes, 5 ms of a 41.7 ms step
(``PERF.md`` section 5), to pick 8 numbers of 512. Taking ``k <= 8`` of
a row needs ``k`` maxima, so a group's two best are a maximum, the first
lane that holds it masked, and a second maximum; the groups that stay
are the ones fewer than ``topk_group`` others beat (one compare and
count); and the ``top_k`` experts are ``top_k`` passes of arg-max, each
masking the lane it took. The same float32 scores, order and ties as
``jax.lax.top_k`` gives them, so no served token changes
(``tests/test_latent_moe_serving.py`` keeps the three ``top_k`` as the
oracle). :func:`route_softmax_topk` keeps its one full-width ``top_k``
(no record shows it as a cost), and :func:`expert_ffn`'s sort ORDERS
the slots: another job.
"""
from __future__ import annotations

import math

__all__ = ["topk_route", "moe_ffn", "load_balance_loss",
           "route_grouped_sigmoid", "expert_ffn", "expert_load"]


def topk_route(gate_logits, k, capacity):
    """Route each token to its top-k experts under a per-expert capacity.

    gate_logits: (S, E) router scores for S tokens.
    Returns (dispatch, combine, aux):
      dispatch: (S, E, C) one-hot — token s occupies slot c of expert e
      combine:  (S, E, C) — dispatch weighted by renormalised gate prob
      aux: load-balance auxiliary loss (scalar)
    Tokens that overflow an expert's capacity are dropped for that
    expert (their combine weight is 0 — the residual connection carries
    them), exactly the Switch capacity semantics.
    """
    import jax
    import jax.numpy as jnp

    S, E = gate_logits.shape
    probs = jax.nn.softmax(gate_logits, axis=-1)            # (S, E)
    topv, topi = jax.lax.top_k(probs, k)                    # (S, k)
    topv = topv / jnp.clip(topv.sum(-1, keepdims=True), 1e-9)

    # one-hot expert choice per (token, rank): (S, k, E)
    choice = jax.nn.one_hot(topi, E, dtype=gate_logits.dtype)
    # position of each (token, rank) within its expert's queue: number
    # of earlier claims on the same expert. Flatten ranks in priority
    # order (all rank-0 claims before rank-1) so top-1 picks never lose
    # their slot to another token's top-2 pick.
    flat = choice.transpose(1, 0, 2).reshape(k * S, E)      # (k*S, E)
    pos_flat = jnp.cumsum(flat, axis=0) - flat              # claims before
    pos = pos_flat.reshape(k, S, E).transpose(1, 0, 2)      # (S, k, E)
    within = pos * choice                                    # claimed slot
    keep = (pos < capacity) * choice                         # (S, k, E)
    slot = jax.nn.one_hot(jnp.sum(within, -1).astype(jnp.int32),
                          capacity, dtype=gate_logits.dtype)  # (S, k, C)
    # (S, k, E) x (S, k, C) -> (S, E, C)
    dispatch = jnp.einsum("ske,skc->sec", keep, slot)
    combine = jnp.einsum("ske,skc->sec", keep * topv[..., None], slot)

    aux = load_balance_loss(probs, choice[:, 0, :])
    return dispatch, combine, aux


def load_balance_loss(probs, top1_choice):
    """Switch aux loss: E * dot(mean gate prob, mean top-1 assignment)."""
    import jax.numpy as jnp
    E = probs.shape[-1]
    density = top1_choice.mean(0)          # fraction routed per expert
    density_proxy = probs.mean(0)          # mean router prob per expert
    return E * jnp.sum(density * density_proxy)


def moe_ffn(x, gate_w, w1, w2, *, k=2, capacity_factor=1.25, mesh=None,
            ep_axis="ep"):
    """Top-k routed expert FFN.

    x: (B, T, D) tokens; gate_w: (D, E); w1: (E, D, F); w2: (E, F, D)
    with w1/w2 sharded P(ep_axis, ...). Returns (out (B,T,D), aux_loss).
    """
    import jax
    import jax.numpy as jnp

    B, T, D = x.shape
    E = gate_w.shape[-1]
    S = B * T
    capacity = max(1, int(math.ceil(k * S / E * capacity_factor)))

    tokens = x.reshape(S, D)
    dispatch, combine, aux = topk_route(tokens @ gate_w, k, capacity)

    # gather tokens into per-expert buffers: (E, C, D) — a dense einsum,
    # and the point where XLA inserts the dp<->ep all-to-all.
    expert_in = jnp.einsum("sec,sd->ecd", dispatch, tokens)
    if mesh is not None and ep_axis in mesh.axis_names:
        from jax.sharding import NamedSharding, PartitionSpec as P
        expert_in = jax.lax.with_sharding_constraint(
            expert_in, NamedSharding(mesh, P(ep_axis, None, None)))
    h = jnp.einsum("ecd,edf->ecf", expert_in, w1)
    h = jax.nn.gelu(h)
    expert_out = jnp.einsum("ecf,efd->ecd", h, w2)
    out = jnp.einsum("sec,ecd->sd", combine, expert_out)
    return out.reshape(B, T, D), aux


# ---------------------------------------------------------------------------
# the dropless expert layer (serving)
# ---------------------------------------------------------------------------

def route_grouped_sigmoid(x, w_gate, bias, *, n_group, topk_group, top_k,
                          scaling=1.0):
    """Sigmoid router with group-limited choice, in float32 at
    "highest" (a tie decided by a bfloat16 pass would send a token to
    another expert than the reference's).

    ``x (T, D)``; ``w_gate (D, E)``; ``bias (E,)`` — the score
    correction, used for the CHOICE only. ``s = sigmoid(x @ w_gate)``;
    the choice is made on ``s + bias``: the ``E`` experts form
    ``n_group`` groups, a group's score is the sum of its two best, the
    ``topk_group`` best groups stay (the others' scores are put to 0,
    as published), and the ``top_k`` best experts of what is left are
    chosen. The weights are the chosen experts' ``s`` (without the
    bias) over their sum (+1e-20), times ``scaling``. Returns ``(topi
    (T, top_k) int32, topw (T, top_k) float32)``, ``topi`` in descending
    score, ties to the lower index, as ``jax.lax.top_k`` gives them
    (the order is :func:`expert_ffn`'s slot numbering and the order of
    the weights' float32 sum).

    The choice is made by reductions, never by a sort (a ``top_k`` over
    a short minor axis is a full sort on this chip: 0.69 ms a layer at
    1,088 lanes; the module's header). A group's two best: its maximum,
    the FIRST lane that holds it masked, the maximum of the rest. The
    groups that stay: those that fewer than ``topk_group`` groups beat,
    a group of equal score and lower index beating it. The ``top_k``
    best: ``top_k`` passes of arg-max (the first of equal values), each
    putting the lane it took to ``-inf`` — free as a mark, the scores
    being a sigmoid plus a finite bias, or 0. Where ``n_group ==
    topk_group`` every group stays and the group stage is not traced."""
    import jax
    import jax.numpy as jnp
    T = x.shape[0]
    E = w_gate.shape[-1]
    # the scope goes into every operation's op_name (an HLO dump and a
    # profiler's op view show it; a profile's raw event names do not)
    with jax.named_scope("mx_moe_route"):
        with jax.default_matmul_precision("highest"):
            s = jax.nn.sigmoid(jnp.dot(x.astype(jnp.float32),
                                       w_gate.astype(jnp.float32)))
        masked = s + bias.astype(jnp.float32)
        if n_group != topk_group:
            grouped = masked.reshape(T, n_group, E // n_group)
            first = jnp.argmax(grouped, axis=-1, keepdims=True)
            second = jnp.max(
                jnp.where(jnp.arange(E // n_group) == first, -jnp.inf,
                          grouped), axis=-1)
            group_score = jnp.max(grouped, axis=-1) + second     # (T, G)
            mine = group_score[:, :, None]
            other = group_score[:, None, :]
            g = jnp.arange(n_group)
            beats = (other > mine) | ((other == mine)
                                      & (g[None, :] < g[:, None]))
            stays = jnp.sum(beats, axis=-1) < topk_group         # (T, G)
            masked = jnp.where(stays[:, :, None], grouped,
                               0.0).reshape(T, E)
        lane = jnp.arange(E)
        topi = []
        for _ in range(top_k):
            best = jnp.argmax(masked, axis=-1, keepdims=True)
            topi.append(best)
            masked = jnp.where(lane == best, -jnp.inf, masked)
        topi = jnp.concatenate(topi, axis=1)
        topw = jnp.take_along_axis(s, topi, axis=1)
        topw = topw / (topw.sum(-1, keepdims=True) + 1e-20) * scaling
        return topi.astype(jnp.int32), topw


def route_softmax_topk(x, w_gate, *, top_k, renormalize=True):
    """Softmax router with top-k choice, in float32 at "highest" (as
    :func:`route_grouped_sigmoid`, and for the same reason). ``x (T,
    D)``; ``w_gate (D, E)``. ``p = softmax(x @ w_gate)`` over all ``E``
    experts; the ``top_k`` largest are chosen; with ``renormalize`` the
    weights are the chosen experts' ``p`` over their sum, otherwise
    their ``p`` as it is. Returns ``(topi (T, top_k) int32, topw (T,
    top_k) float32)``; ties go to the lower index (``jax.lax.top_k``)."""
    import jax
    import jax.numpy as jnp
    with jax.named_scope("mx_moe_route"):
        with jax.default_matmul_precision("highest"):
            logits = jnp.dot(x.astype(jnp.float32),
                             w_gate.astype(jnp.float32))
        p = jax.nn.softmax(logits, axis=-1)
        topw, topi = jax.lax.top_k(p, top_k)
        if renormalize:
            topw = topw / topw.sum(-1, keepdims=True)
        return topi.astype(jnp.int32), topw


def expert_load(topi, held, count=None):
    """Tokens each HELD expert was sent: ``(hi - lo,)`` int32 from the
    router's choice ``topi (T, k)`` — the step's own count, which the
    serving model hands on as its counters. ``count``: how many are held
    (static), for a ``held`` whose ``lo`` is traced (:func:`expert_ffn`)."""
    import jax.numpy as jnp
    lo, hi = held
    count = hi - lo if count is None else count
    local = topi.reshape(-1) - lo
    return jnp.sum(local[:, None] == jnp.arange(count)[None, :],
                   axis=0).astype(jnp.int32)


_GMM_ROWS = (16, 128)  # a tile's least rows (a bf16 sublane tile) and most
_GMM_VMEM = 64 << 20   # of the chip's 128 MiB; the default scoped limit is 16


def _gmm_rows(n_slots, E):
    """Rows a tile of token slots holds, from the static shapes alone:
    the largest power of two not above HALF of ``n_slots // E`` (the
    most slots a held expert can average), within ``_GMM_ROWS``. Half,
    because an expert's last tile is half empty on average: at a height
    of the average itself the padding would double the rows computed.
    A decode step (at most 32 slots an expert in every served
    configuration) keeps the 16 rows it was drawn for; a prompt of
    thousands of tokens fills the MXU's 128."""
    m, most = _GMM_ROWS
    while m < most and 4 * m <= n_slots // E:
        m *= 2
    return m


def _gmm_block_n(K, N, itemsize, n_weights):
    """Columns of the weight block: the widest multiple of 128 dividing
    ``N`` whose ``(K, bn)`` blocks, double-buffered, keep to 40 MB (all
    of ``N`` where it is no multiple of 128: the forced kernel of a
    small test)."""
    best = None if N % 128 == 0 else N
    for bn in range(128, N + 1, 128):
        if N % bn == 0 and 2 * n_weights * K * bn * itemsize <= 40 << 20:
            best = bn
    return best


def _gmm_tiles(K, N, itemsize=2, n_weights=1):
    """The kernel's shape predicate: both matrix dims whole lanes and a
    weight block that fits."""
    return K % 128 == 0 and N % 128 == 0 \
        and _gmm_block_n(K, N, itemsize, n_weights) is not None


def _gmm_kernel(tile_expert_ref, n_live_ref, x_ref, *refs, gated):
    """Grid = (column blocks, row tiles), tiles innermost. One program
    instance multiplies one tile of token slots, all of one expert, by
    that expert's ``(K, bn)`` weight block: bf16 operands on the MXU,
    float32 accumulation. The tile's height is the row block's, which
    :func:`_gmm_rows` chose from the slots the layer was handed; a row's
    result does not depend on it (each row is its own product over
    ``K``). Consecutive tiles of one expert name the same weight block,
    so it is fetched once a column block; tiles at or past ``n_live``
    name the last live tile's blocks again (nothing is fetched) and
    compute nothing. ``gated``: two
    weights, ``silu(x @ w0) * (x @ w1)``."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    del tile_expert_ref
    o_ref = refs[-1]

    @pl.when(pl.program_id(1) < n_live_ref[0])
    def _():
        x = x_ref[...]
        a = jnp.dot(x, refs[0][...], preferred_element_type=jnp.float32)
        if gated:
            b = jnp.dot(x, refs[1][...],
                        preferred_element_type=jnp.float32)
            a = jax.nn.silu(a) * b
        o_ref[...] = a.astype(o_ref.dtype)


def _pallas_grouped_matmul(x, weights, tile_expert, n_live, out_dtype,
                           interpret):
    """``x (R, K)`` rows in as many tiles as ``tile_expert`` has
    entries, tile ``i`` all of expert ``tile_expert[i]``; ``weights``:
    one ``(E, K, N)`` stack, or two for the gated form. Rows of tiles
    at or past ``n_live`` are left unwritten."""
    import functools
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    R, K = x.shape
    E, _, N = weights[0].shape
    bn = _gmm_block_n(K, N, weights[0].dtype.itemsize, len(weights))
    tiles = tile_expert.shape[0]
    m = R // tiles

    def live(i, n_live):
        return jnp.minimum(i, jnp.maximum(n_live[0], 1) - 1)

    rows = pl.BlockSpec((m, K),
                        lambda j, i, te, nl: (live(i, nl), 0))
    wblk = pl.BlockSpec((None, K, bn),
                        lambda j, i, te, nl: (te[live(i, nl)], 0, j))
    out = pl.BlockSpec((m, bn),
                       lambda j, i, te, nl: (live(i, nl), j))
    return pl.pallas_call(
        functools.partial(_gmm_kernel, gated=len(weights) == 2),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(N // bn, tiles),
            in_specs=[rows] + [wblk] * len(weights), out_specs=out),
        out_shape=jax.ShapeDtypeStruct((R, N), out_dtype),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=_GMM_VMEM),
        interpret=interpret,
        name="mx_grouped_matmul.e%d.m%d.k%d.n%d.%s.r%d%s" % (
            E, R, K, N, jnp.dtype(weights[0].dtype).name, m,
            ".gated" if len(weights) == 2 else ""),
    )(tile_expert, n_live, x, *weights)


def expert_ffn(x, weights, topi, topw, held, force_pallas=False):
    """The held experts' part of a routed gated-MLP layer, dropless.

    ``x (T, D)``; ``weights``: ``{"w_gate": (E_held, D, F), "w_up":
    (E_held, D, F), "w_down": (E_held, F, D)}``, the stacks of experts
    ``held = (lo, hi)`` of the router's width; ``topi``/``topw`` ``(T,
    k)`` from :func:`route_grouped_sigmoid` over ALL experts. Returns
    ``(T, D)`` float32: ``sum_j topw[t, j] * expert_{topi[t, j]}(x[t])``
    over the chosen experts that are held here; a token none of whose
    experts is held gets zeros. Every (token, held expert) pair is
    computed, whatever the load (no capacity). HOW MANY are held is the
    stacks' leading dimension, static; WHICH may be traced: under
    ``shard_map`` every chip runs one program, and ``lo`` is
    ``jax.lax.axis_index(axis) * E_held`` there.

    The slots are sorted by expert and each expert's group padded to
    whole tiles of ``m`` rows, so that a tile belongs to one expert: the
    grouped matmul then reads an expert's matrices once and never those
    of an expert no token chose. The same layout feeds
    ``jax.lax.ragged_dot`` on the plain path.

    The layout takes its dimensions from the static shapes it is handed
    (``n_slots = T * k`` and the ``E`` held experts), not from a decode
    step's: ``m`` is :func:`_gmm_rows`' (16 for a step's few slots an
    expert, up to 128 for a prompt's hundreds; counted at trace time as
    ``grouped_matmul_rows_<m>``), and ``R`` stays the dropless worst
    case at that height. Slots are numbered choice-major (``j * T +
    t``): the rows gathered back are then ``(k, T, D)`` as they lie, and
    select, weight and the float32 sum over the ``k`` choices are one
    pass over them (a reduction over the leading axis). A token's row of
    the result depends neither on the height nor on what else is in the
    batch."""
    import jax
    import jax.numpy as jnp
    from .. import profiler
    from .flash_attention import _dispatch
    lo = held[0]
    w_gate, w_up, w_down = (weights[n] for n in ("w_gate", "w_up",
                                                 "w_down"))
    E = w_gate.shape[0]
    T, D = x.shape
    k = topi.shape[1]
    n_slots = T * k
    F = w_gate.shape[-1]
    m = _gmm_rows(n_slots, E)
    profiler.increment_counter("grouped_matmul_rows_%d" % m)
    # the most rows the padded layout can need: every slot held, and
    # every group's last tile all but empty
    R = (-(-n_slots // m) + E) * m

    local = topi.T.reshape(-1) - lo                     # slot j * T + t
    is_held = jnp.logical_and(local >= 0, local < E)
    key = jnp.where(is_held, local, E)                  # unheld last
    sizes = expert_load(topi, held, E)
    padded = -(-sizes // m) * m
    start = jnp.cumsum(padded) - padded                 # (E,) row starts
    order = jnp.argsort(key, stable=True)
    rank = jnp.zeros((n_slots,), jnp.int32).at[order].set(
        jnp.arange(n_slots, dtype=jnp.int32))
    first = jnp.cumsum(sizes) - sizes                   # sorted starts
    safe = jnp.minimum(key, E - 1)
    # a held slot's row in the padded layout; an unheld one's is R (dropped)
    row = jnp.where(is_held, start[safe] + rank - first[safe], R)
    src = jnp.zeros((R,), jnp.int32).at[row].set(
        jnp.arange(n_slots, dtype=jnp.int32) % T, mode="drop")
    xs = x.astype(w_gate.dtype)[src]                    # (R, D)
    tile_ends = jnp.cumsum(padded) // m
    n_live = tile_ends[-1:].astype(jnp.int32)           # (1,)
    tile_expert = jnp.minimum(
        jnp.searchsorted(tile_ends, jnp.arange(R // m), side="right"),
        E - 1).astype(jnp.int32)

    def composed(xs, w_gate, w_up, w_down, tile_expert, n_live):
        groups = padded.astype(jnp.int32)
        g = jax.lax.ragged_dot(xs, w_gate, groups,
                               preferred_element_type=jnp.float32)
        u = jax.lax.ragged_dot(xs, w_up, groups,
                               preferred_element_type=jnp.float32)
        h = (jax.nn.silu(g) * u).astype(w_down.dtype)
        return jax.lax.ragged_dot(h, w_down, groups,
                                  preferred_element_type=jnp.float32)

    def kernel(interpret, xs, w_gate, w_up, w_down, tile_expert, n_live):
        h = _pallas_grouped_matmul(xs, (w_gate, w_up), tile_expert,
                                   n_live, w_down.dtype, interpret)
        return _pallas_grouped_matmul(h, (w_down,), tile_expert, n_live,
                                      jnp.float32, interpret)

    tiles = _gmm_tiles(D, F, w_gate.dtype.itemsize, 2) \
        and _gmm_tiles(F, D, w_down.dtype.itemsize, 1)
    # the chooser's shape test is head_dim % 128 and blocks % 128: hand
    # it the verdict of this kernel's own predicate
    ys = _dispatch("grouped_matmul", 128 if tiles else 1, (), force_pallas,
                   kernel, composed, xs, w_gate, w_up, w_down, tile_expert,
                   n_live)
    # back to (choice, token): rows of dead tiles are unwritten, so an
    # unheld slot is masked by a select, never by a product; select,
    # weight and sum fuse into one pass over the gathered rows
    got = ys[jnp.minimum(row, R - 1)].reshape(k, T, D)
    is_held = is_held.reshape(k, T, 1)
    w = topw.T.astype(jnp.float32)[:, :, None]
    return jnp.sum(jnp.where(is_held, got, 0.0) * w, axis=0)
