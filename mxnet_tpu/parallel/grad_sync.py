"""Overlapped gradient sync: bucketed gradient exchange + ZeRO-1 sharded
optimizer update (ROADMAP item 4, the optimizer-state half of item 1).

The reference framework overlapped communication with backprop by
engine priority (SURVEY §7 hard-part 2): late-layer gradients were
pushed to the kvstore while early layers were still differentiating.
This module is the TPU-native form of that trick combined with the
bucketing of PyTorch DDP (Li et al., VLDB 2020) and the
optimizer-state sharding of ZeRO (Rajbhandari et al., SC 2020):

- **Buckets** — the flat gradient roster is partitioned into
  size-capped, dtype-uniform buckets (``MXNET_GRAD_BUCKET_MB``) in
  *backward order* (late-layer grads first), so each bucket's exchange
  is ready as soon as its layers finish differentiating.
- **Rows a chip** — a bucket's flat buffer is ``(N, row_len)`` for an
  axis of N chips, constrained to ``P(axis, None)``: every member's
  flat segment is zero-padded to ``N`` equal shares and seen as ``(N,
  cols)``, and the members lie side by side along axis 1
  (:class:`_Bucket`: the large ones first, on whole tiles of 1,024
  elements; the small ones behind them at their exact share, so the
  padding does not grow with their number). Row ``k`` is chip ``k``'s
  share OF EVERY MEMBER,
  so each member's piece sits in a chip's row at an offset fixed when
  the step is traced. (A 1-D roster-order buffer cut ``[k*T/N,
  (k+1)*T/N)`` crosses members at offsets only the partition id
  knows: XLA compiles that to a select out of ALL members for every
  output vector — 26 ms of a 169 ms ResNet-50 step on four v5e chips,
  and an all-gather for every member's slice; PERF.md, PR 32.)
- **What the step compiles to** (read from ``compiled.as_text()`` for
  a 2x2 of v5e chips, ResNet-50, 193 parameters in one bucket): the
  pending cross-device sum of the gradients is exchanged whole, by
  five combined ``all-reduce``s of several tensors each as the
  backward pass yields them (every gradient is pinned replicated in
  its own shape) — no ``reduce-scatter``, on the chip's pipeline or
  the CPU's; each member's row is then a LOCAL
  ``dynamic-slice`` out of the summed gradient (or the replicated
  weight) at partition-id x cols, written into the chip's row by a
  ``dynamic-update-slice`` at a constant offset; the lr / wd vectors
  are one replicated row (a gather of one value a tile, broadcast,
  and of one a column over the small members);
  and the updated buffer comes back by ONE ``all-gather`` a bucket
  (one more for each state slot where the state is resident
  replicated), each parameter a static slice of its columns.
  ``tests/test_grad_sync.py`` holds the gathers to a multiple of the
  buckets and the step to no many-member concatenate cut at run time.
- **ZeRO-1 sharded update** — the optimizer update
  (``Optimizer.fused_step_fn``; every supported rule is elementwise
  and index-independent, so it does not care in what order elements
  lie) runs on each device's row with a per-element lr/wd row built
  in-program, against optimizer state that lives *permanently
  sharded* in the same row layout (1/N per device — the memory win).
- **Bit-exactness** — the sharded composition is float-identical to
  the per-parameter path: the collective sums the same N per-device
  contributions per element, the update rule applies the same scalar
  ops per element (vector lr/wd entries equal the per-parameter
  scalars), and padding is zeros under rules that keep zeros fixed.
  ``tests/test_grad_sync.py`` pins rtol=0 trajectory identity per
  optimizer.

``MXNET_GRAD_OVERLAP=1`` turns the mode on for
``parallel.data_parallel`` (``DistributedTrainer`` /
``make_data_parallel_step``), the gluon ``Trainer``'s fused update on
a dp mesh, and the eager kvstore gradient exchange
(:func:`bucketed_kvstore_sync`, used by ``model._update_params`` and
``gluon.Trainer.allreduce_grads`` — there the buckets are real
host-timed ``grad_sync`` comm spans). Default off: every existing
path is byte-identical with the gate closed (``DistributedTrainer``
then runs the same machinery over ONE bucket with replicated state).

Sharded optimizer state round-trips through ``checkpoint.py``'s
per-shard manifest format. What a checkpoint holds is NOT the row
layout, which depends on N, but each bucket slot as one 1-D vector in
roster order sharded over the axis (re-laid on the device at a save:
:meth:`ShardedOptState.checkpoint_roster`), and
:meth:`ShardedOptState.load_host_flats` lays it out by rows for the
*current* axis size: a run saved on N devices resumes on M, and a
checkpoint written before the row layout loads as it is.
"""
from __future__ import annotations


import numpy as _np

from .. import envs
from ..base import MXNetError

__all__ = ["overlap_enabled", "bucket_cap_bytes", "GradSyncPlan",
           "make_bucketed_apply", "ShardedOptState",
           "bucketed_kvstore_sync", "account_in_program_sync"]


def overlap_enabled():
    """The ``MXNET_GRAD_OVERLAP`` gate — default OFF; ``1``/``true``/
    ``on`` enable (re-read per build so tests and benchmarks can
    toggle it)."""
    return envs.get_bool("MXNET_GRAD_OVERLAP")


def bucket_cap_bytes():
    """Bucket size cap from ``MXNET_GRAD_BUCKET_MB`` (default 4 MiB —
    large enough to amortize collective launch latency, small enough
    that several buckets exist to overlap; see README for tuning)."""
    mb = envs.get_float("MXNET_GRAD_BUCKET_MB")
    return max(1, int(mb * (1 << 20)))


TILE = 1024   # elements: a whole number of the chip's vector registers,
              # bf16 and f32 alike


class _Bucket:
    """One bucket of the flat gradient roster: member parameter
    indices in exchange order and the TWO layouts of its flat buffer.

    - On the device the buffer is ``(axis_size, row_len)``: member
      ``i``'s flat segment is zero-padded to ``axis_size * cols[i]``,
      seen as ``(axis_size, cols[i])``, and the members' views lie
      side by side along axis 1 at ``col_offsets``. Row ``k`` is chip
      ``k``'s share OF EVERY MEMBER, so where each member's piece lies
      in a chip's row is known when the program is traced
      (:meth:`pack`, :meth:`unpack`, :meth:`spread`). A member whose
      share of a chip is a tile or more comes first, its columns
      rounded up to whole tiles, so each starts on a vector register
      (``aligned_len`` columns in all). The smaller members (every
      BatchNorm vector, every bias) follow at their exact share
      ``ceil(size / axis_size)``, one against the other, and the row
      is closed to a whole tile once.
    - What leaves the device (checkpoints, the Updater interchange)
      stays the 1-D roster-order vector: member ``i`` at ``offsets[i]``
      of ``total`` elements, which does not depend on the axis size.

    The padding (``padded_size - total``) is under ``axis_size``
    elements a small member, under ``axis_size * TILE`` a large one
    (less than the member itself) and once more for the row: it grows
    with the axis, never with the axis times the number of small
    members. ``nbytes`` is the logical payload (``total``), what the
    bucket cap and the traffic ledger count."""
    __slots__ = ("indices", "sizes", "offsets", "total", "rows", "cols",
                 "col_offsets", "aligned_len", "row_len", "padded_size",
                 "dtype", "nbytes", "_order")

    def __init__(self, indices, sizes, axis_size, dtype):
        self.indices = tuple(indices)
        self.sizes = tuple(sizes)
        self.rows = int(axis_size)
        offs, off = [], 0
        for s in sizes:
            offs.append(off)
            off += s
        self.offsets = tuple(offs)
        self.total = off
        share = [-(-s // self.rows) for s in sizes]
        cols = [-(-c // TILE) * TILE if c >= TILE else c for c in share]
        # the large members in exchange order, then the small
        large = [m for m, c in enumerate(share) if c >= TILE]
        self._order = tuple(large + [m for m, c in enumerate(share)
                                     if c < TILE])
        self.aligned_len = sum(cols[m] for m in large)
        coffs, coff = [0] * len(sizes), 0
        for m in self._order:
            coffs[m] = coff
            coff += cols[m]
        self.cols = tuple(cols)
        self.col_offsets = tuple(coffs)
        self.row_len = -(-coff // TILE) * TILE
        self.padded_size = self.rows * self.row_len
        self.dtype = str(dtype)
        self.nbytes = self.total * _np.dtype(dtype).itemsize

    def pack(self, segs, xp):
        """Members' flat segments (exchange order) -> the ``(rows,
        row_len)`` buffer; ``xp`` is ``numpy`` at the host boundary
        and ``jax.numpy`` inside a traced step (where the caller pins
        every segment replicated first: see ``make_bucketed_apply``).
        """
        views = []
        for m in self._order:
            seg, c = segs[m], self.cols[m]
            pad = self.rows * c - self.sizes[m]
            if pad:
                seg = xp.concatenate([seg, xp.zeros((pad,), seg.dtype)])
            views.append(seg.reshape(self.rows, c))
        close = self.row_len - sum(self.cols)
        if close:
            views.append(xp.zeros((self.rows, close), views[0].dtype))
        return xp.concatenate(views, axis=1)

    def unpack(self, buf):
        """The ``(rows, row_len)`` buffer -> members' flat segments,
        each a static slice of columns cut to its size."""
        return [buf[:, off:off + c].reshape(-1)[:size]
                for off, c, size in zip(self.col_offsets, self.cols,
                                        self.sizes)]

    def spread(self, values):
        """One traced scalar a member -> the ``(row_len,)`` vector that
        holds it over the member's columns: alike in every row, so one
        row serves all chips. Over the aligned members it is one small
        gather (a value a tile) broadcast over the tile; over the small
        ones behind them a gather of a value a column — nothing of
        ``row_len`` elements is assembled piece by piece. (The columns
        that close the row hold zeros under any lr / wd.)"""
        import jax.numpy as jnp
        member_of_col = _np.zeros((self.row_len,), _np.int32)
        for m, (off, c) in enumerate(zip(self.col_offsets, self.cols)):
            member_of_col[off:off + c] = m
        vals = jnp.stack(values)
        parts = []
        if self.aligned_len:
            parts.append(jnp.repeat(
                vals[member_of_col[:self.aligned_len:TILE]], TILE))
        if self.aligned_len < self.row_len:
            parts.append(vals[member_of_col[self.aligned_len:]])
        return jnp.concatenate(parts)

    def roster_order(self, buf):
        """The ``(rows, row_len)`` buffer on the device -> the 1-D
        roster-order vector a checkpoint holds, zero-padded to divide
        the axis (traced: the save's re-layout program)."""
        import jax.numpy as jnp
        segs = self.unpack(buf)
        segs.append(jnp.zeros((-self.total % self.rows,), buf.dtype))
        return jnp.concatenate(segs)

    def from_roster_order(self, flat):
        """A checkpoint's 1-D roster-order host vector (any tail
        padding) -> the ``(rows, row_len)`` host buffer."""
        return self.pack([flat[off:off + size] for off, size in
                          zip(self.offsets, self.sizes)], _np)


class GradSyncPlan:
    """The bucket partition of one parameter roster.

    Buckets are built traversing the roster in REVERSE order — the
    backward pass produces late-layer gradients first, so bucket 0
    (the last layers) can start reducing while early layers are still
    differentiating. A bucket closes when adding the next parameter
    would exceed the byte cap (every bucket holds at least one
    parameter) or when the dtype changes (flat concatenation is
    dtype-uniform)."""

    def __init__(self, shapes, dtypes, axis_size, cap_bytes=None):
        cap = bucket_cap_bytes() if cap_bytes is None else int(cap_bytes)
        self.axis_size = int(axis_size)
        self.n_params = len(shapes)
        sizes = [int(_np.prod(s)) if len(s) else 1 for s in shapes]
        buckets = []
        cur, cur_sizes, cur_bytes, cur_dt = [], [], 0, None
        for i in reversed(range(len(shapes))):
            dt = str(dtypes[i])
            nb = sizes[i] * _np.dtype(dt).itemsize
            if cur and (dt != cur_dt or cur_bytes + nb > cap):
                buckets.append(_Bucket(cur, cur_sizes, self.axis_size,
                                       cur_dt))
                cur, cur_sizes, cur_bytes = [], [], 0
            cur.append(i)
            cur_sizes.append(sizes[i])
            cur_bytes += nb
            cur_dt = dt
        if cur:
            buckets.append(_Bucket(cur, cur_sizes, self.axis_size,
                                   cur_dt))
        self.buckets = buckets

    def signature(self):
        """Hashable identity for compile-cache keys."""
        return tuple((b.indices, b.total, b.padded_size, b.dtype)
                     for b in self.buckets)

    def layout_key(self):
        """Topology-INDEPENDENT partition identity: which params land
        in which bucket at which flat offset. Excludes padded_size —
        padding legitimately differs across axis sizes, and elastic
        resume re-pads — so a save on N devices matches a restore on M
        iff the member layout agrees."""
        return tuple((b.indices, b.sizes, b.dtype)
                     for b in self.buckets)

    def total_bytes(self):
        return sum(b.nbytes for b in self.buckets)

    def describe(self):
        return {"buckets": len(self.buckets),
                "axis_size": self.axis_size,
                "bytes": self.total_bytes(),
                "params": self.n_params}


# ---------------------------------------------------------------------------
# the traced composition
# ---------------------------------------------------------------------------

MONOLITH_CAP = 1 << 62   # one-blob plan: the unbucketed baseline


def make_bucketed_apply(step_fns, n_slots, plan, mesh, axis="dp",
                        guard=False, inject=False, shard_state=True):
    """The bucketed, sharded form of ``fused_step.make_apply`` — same
    call contract ``apply(grads, weights, states, scalars, poisons) ->
    (new_weights, new_states, finite_mask)`` over raw jax arrays,
    except ``states`` is the bucket row layout: ``n_slots`` sharded
    ``(axis_size, row_len)`` buffers per bucket, ordered
    ``[b0s0..b0s{k-1}, b1s0, ...]``.

    Per bucket: splice poison / read the finite guard per parameter,
    lay the flat gradients out by rows a chip (:meth:`_Bucket.pack`:
    zero-padded members side by side) and constrain the buffer to
    ``P(axis, None)`` — pushed through the concatenate this lands on
    every member as its own row, a contiguous local copy — lay the
    replicated weights out the same way (free), run the bucket's
    update rule once over the whole row with one in-program lr/wd row,
    and constrain the updated buffer back to replicated — ONE
    all-gather of *updated params only*, out of which each parameter
    is a static slice of columns. Every gradient is pinned replicated
    in its own shape, as every weight is, so its cross-device sum is
    an all-reduce of the whole gradient where the backward pass yields
    it: on a 2x2 of v5e chips (and on the CPU) several tensors
    combined in one, and no reduce-scatter. Requires every member's
    ``fused_step_fn`` to be index-independent, true of all compiled
    optimizers (the closures capture only optimizer-level
    hyperparameters).

    ``shard_state=False`` is the unbucketed baseline's state layout:
    states arrive replicated, each chip takes its row for the
    (identical) sharded update, and the new states are all-gathered
    back to replicated — full per-device state memory, the profile
    ZeRO-1 removes. The update arithmetic itself ALWAYS runs on the
    sharded rows in both layouts: XLA's codegen for replicated
    elementwise math contracts FMAs that its partitioned codegen does
    not (measured ~1 ULP per step on CPU), so computing shard-wise in
    every mode is what makes bucketed-vs-monolithic trajectories
    bit-identical."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    shard = NamedSharding(mesh, P(axis, None))
    rep = NamedSharding(mesh, P())
    wsc = jax.lax.with_sharding_constraint
    n = len(step_fns)
    buckets = plan.buckets

    def apply(grads, weights, states, scalars, poisons):
        # Pin every weight replicated BEFORE the bucket machinery
        # touches it. Weights feed the forward matmuls AND the update:
        # without the pin, each bucket's row constraint
        # back-propagates through concatenate onto the weight nodes
        # and re-partitions the forward/backward — monolithic vs
        # bucketed plans then produce ~1-ULP-different gradients
        # (measured on an 8-device CPU mesh) and trajectory identity
        # dies. The pin stops the propagation at this edge. Every
        # gradient is pinned the same way (below): the row constraint
        # otherwise travels back through a member's zero padding into
        # the backward pass, cuts a 64-element gradient's computation
        # by the axis and puts it together again with a gather and a
        # permute of its own (79 all-gathers in the ResNet-50 step).
        # Pinned in its OWN shape, before it is flattened: pinned
        # behind the reshape, the all-reduces move behind it too and
        # the combiner makes ONE of all 25.5 M elements, which cannot
        # start before the backward pass has ended.
        weights = [wsc(w, rep) for w in weights]
        rescale = scalars[2 * n]
        new_ws = [None] * n
        new_sts = [None] * len(states)
        oks = [None] * n
        si = 0
        for bucket in buckets:
            dt = jnp.dtype(bucket.dtype)
            segs_g = []
            for i in bucket.indices:
                g = wsc(grads[i], rep).reshape(-1)
                if inject:
                    g = jnp.where(jnp.isfinite(poisons[i]), g,
                                  jnp.full_like(g, poisons[i]
                                                .astype(g.dtype)))
                if guard:
                    oks[i] = jnp.isfinite(g).all()
                segs_g.append(g)
            # the constraint lands on every member as ITS row k on
            # chip k: a contiguous copy out of a replicated value (the
            # weights) or out of the summed gradient, laid into the
            # chip's row at an offset fixed at trace time
            gflat = wsc(bucket.pack(segs_g, jnp), shard)
            wflat = wsc(bucket.pack([weights[i].reshape(-1)
                                     for i in bucket.indices], jnp),
                        shard)
            # lr / wd are alike in every row: one replicated row
            lr_v = bucket.spread([scalars[i].astype(dt)
                                  for i in bucket.indices])
            wd_v = bucket.spread([scalars[n + i].astype(dt)
                                  for i in bucket.indices])
            st = tuple(states[si + k] for k in range(n_slots))
            if not shard_state:
                # replicated-resident baseline state: take the local
                # row for the shard-wise update (free), gather after
                st = tuple(wsc(s, shard) for s in st)
            fn = step_fns[bucket.indices[0]]
            nw, nst = fn(gflat, wflat, st, lr_v, wd_v,
                         rescale.astype(dt))
            # Pin the update OUTPUTS to the row layout before any
            # replicated re-constraint: with replicated-resident
            # baseline state the partitioner would otherwise satisfy
            # the rep output constraint by gathering the INPUTS and
            # running the elementwise update replicated — whose XLA
            # codegen contracts FMAs the partitioned codegen does not
            # (~1 ULP/step, every stateful optimizer). The pins force
            # the arithmetic shard-wise in BOTH state layouts; the
            # gathers happen strictly after.
            nw = wsc(nw, shard)
            nst = tuple(wsc(s, shard) for s in nst)
            if guard:
                ok_v = bucket.spread([oks[i] for i in bucket.indices])
                nw = jnp.where(ok_v, nw, wflat)
                nst = tuple(jnp.where(ok_v, s_new, s_old)
                            for s_new, s_old in zip(nst, st))
            out_spec = shard if shard_state else rep
            for k in range(n_slots):
                new_sts[si + k] = wsc(nst[k], out_spec)
            si += n_slots
            # ONE all-gather of the bucket's updated parameters; each
            # member is then a static slice of columns
            for i, seg in zip(bucket.indices,
                              bucket.unpack(wsc(nw, rep))):
                new_ws[i] = seg.reshape(weights[i].shape)
        mask = jnp.stack(oks) if guard else jnp.ones((n,), jnp.bool_)
        return tuple(new_ws), tuple(new_sts), mask
    return apply


# ---------------------------------------------------------------------------
# sharded optimizer state (ZeRO-1)
# ---------------------------------------------------------------------------

class ShardedOptState:
    """Flat, bucket-aligned, axis-sharded optimizer state.

    Each bucket contributes ``n_slots`` ``(axis_size, row_len)``
    arrays in the bucket's row layout (:class:`_Bucket`) placed with
    ``NamedSharding(mesh, P(axis, None))`` — every device holds its
    row, 1/N of every state buffer, the ZeRO-1 memory layout
    (``sharded=False`` keeps them replicated: the unbucketed
    baseline's full-per-device memory profile). What leaves the device
    — a checkpoint's ``opt:bucketBB.slotS``, the per-parameter
    interchange — is in roster order and knows nothing of the axis
    size. Slot count and dtypes
    are probed from the optimizer's own eager
    ``create_state_multi_precision`` (so RMSProp's fp32 accumulators
    stay fp32); initial values are zeros, matching every compiled
    optimizer's zero-init eager states."""

    def __init__(self, plan, mesh, axis="dp", sharded=True):
        self.plan = plan
        self.mesh = mesh
        self.axis = axis
        self.sharded = bool(sharded)
        self.n_slots = None
        self._slot_dtypes = None
        self._flats = None        # list over buckets of tuple(arrays)
        self._to_roster = None    # every slot's rows -> roster order (jit)

    # -- layout probing ---------------------------------------------------
    def probe(self, optimizer, indices, weights_nd):
        """Slot count/dtypes from one representative parameter per
        bucket (the layout must be uniform across the roster — true
        whenever one optimizer drives it). Returns False when any
        bucket's layout disagrees (→ caller falls back)."""
        from ..fused_step import _flat_state_handles
        n_slots, dtypes = None, None
        for bucket in self.plan.buckets:
            i = bucket.indices[0]
            st = optimizer.create_state_multi_precision(
                indices[i], weights_nd[i])
            flat = _flat_state_handles(st)
            if flat is None:
                return False
            if n_slots is None:
                n_slots = len(flat)
                dtypes = [str(h.dtype) for h in flat]
            elif len(flat) != n_slots or \
                    [str(h.dtype) for h in flat] != dtypes:
                return False
        self.n_slots = n_slots
        self._slot_dtypes = dtypes
        return True

    def _sharding(self):
        from jax.sharding import NamedSharding, PartitionSpec as P
        return NamedSharding(
            self.mesh, P(self.axis, None) if self.sharded else P())

    def _place(self, host_rows):
        import jax
        import jax.numpy as jnp
        return jax.device_put(jnp.asarray(host_rows), self._sharding())

    # -- state roster ------------------------------------------------------
    def ensure(self):
        """The flat state tuple for a dispatch, creating sharded zeros
        on first use. Call :meth:`probe` first."""
        import jax
        import jax.numpy as jnp
        assert self.n_slots is not None, "probe() before ensure()"
        if self._flats is None:
            sh = self._sharding()
            flats = []
            for bucket in self.plan.buckets:
                flats.append(tuple(
                    jax.device_put(
                        jnp.zeros((bucket.rows, bucket.row_len),
                                  jnp.dtype(dt)), sh)
                    for dt in self._slot_dtypes))
            self._flats = flats
        return tuple(a for b in self._flats for a in b)

    def store(self, new_flat_tuple):
        """Write back a dispatch's output states (same flat order)."""
        k, out = self.n_slots, []
        flats = list(new_flat_tuple)
        for b in range(len(self.plan.buckets)):
            out.append(tuple(flats[b * k:(b + 1) * k]))
        self._flats = out

    def state_bytes_per_device(self):
        """Per-device resident state bytes — the ZeRO denominator the
        memory-watermark assertions check (~1/axis_size of the
        replicated layout; the full size when ``sharded=False``)."""
        if self.n_slots is None:
            return 0
        per_dev = 0
        for bucket in self.plan.buckets:
            n = bucket.padded_size // self.plan.axis_size \
                if self.sharded else bucket.padded_size
            for dt in self._slot_dtypes:
                per_dev += n * _np.dtype(dt).itemsize
        return per_dev

    # -- interchange with the per-parameter layout ------------------------
    def export_per_param(self, shapes):
        """Assemble the sharded flats on the host and split them back
        to per-parameter flat numpy arrays: ``{index: [slot arrays]}``
        — the bridge to ``Updater``-style pickles and eager resume."""
        out = {}
        if self._flats is None:
            return out
        for bucket, slots in zip(self.plan.buckets, self._flats):
            per_slot = [bucket.unpack(_np.asarray(s)) for s in slots]
            for pos, i in enumerate(bucket.indices):
                out[i] = [segs[pos].reshape(shapes[i])
                          for segs in per_slot]
        return out

    def seed_per_param(self, per_param):
        """Populate the sharded flats from per-parameter state arrays
        (``{index: [slot numpy arrays]}``) — the resume/interchange
        path. Missing indices keep zeros."""
        assert self.n_slots is not None, "probe() before seeding"
        flats = []
        for bucket in self.plan.buckets:
            slots = []
            for k in range(self.n_slots):
                dt = _np.dtype(self._slot_dtypes[k])
                segs = [_np.zeros((size,), dt) if per_param.get(i) is None
                        else _np.asarray(per_param[i][k], dt).reshape(-1)
                        for i, size in zip(bucket.indices, bucket.sizes)]
                slots.append(self._place(bucket.pack(segs, _np)))
            flats.append(tuple(slots))
        self._flats = flats

    # -- checkpoint round trip --------------------------------------------
    def checkpoint_roster(self):
        """``{'opt:bucketBB.slotS': sharded array}`` — handed to
        ``checkpoint.snapshot_params(extra=...)``; the manifest's piece
        format records each shard's mesh position. Each array is the
        bucket slot in ROSTER ORDER, 1-D, re-laid on the device from
        the row layout (one program for all of them, compiled at the
        first save and run at a save, not in a step) and sharded over the axis as a checkpoint has held
        it since before the row layout: a save on N chips restores on
        M, and on a build that predates this layout. An ``opt:layout``
        fingerprint of the (topology-independent) bucket partition
        rides along so a restore under a different
        ``MXNET_GRAD_BUCKET_MB`` refuses instead of silently slicing
        another bucket's moments into the wrong parameters."""
        out = {}
        if self._flats is None:
            return out
        if self._to_roster is None:
            from jax.sharding import NamedSharding, PartitionSpec as P
            from .. import compile_watch
            buckets, k = self.plan.buckets, self.n_slots

            def to_roster(*rows):
                return tuple(buckets[j // k].roster_order(r)
                             for j, r in enumerate(rows))
            # ONE program for every bucket and slot, kept: the first
            # save compiles once, a later one not at all
            self._to_roster = compile_watch.jit(
                to_roster, "grad_sync:roster_order",
                statics=(self.plan.signature(), k, self.sharded),
                out_shardings=NamedSharding(
                    self.mesh, P(self.axis) if self.sharded else P()))
        flat = self._to_roster(*(a for b in self._flats for a in b))
        for j, arr in enumerate(flat):
            out["opt:bucket%02d.slot%d" % divmod(j, self.n_slots)] = arr
        out["opt:layout"] = self._layout_fingerprint()
        return out

    def _layout_fingerprint(self):
        import hashlib
        digest = hashlib.sha256(
            repr(self.plan.layout_key()).encode()).digest()
        return _np.frombuffer(digest, _np.uint8).copy()

    def load_host_flats(self, flat_dict):
        """Restore from a checkpoint's ``opt:bucketBB.slotS`` host
        arrays (any save-time topology, roster order): strip the
        save-time padding, lay the members out by rows for the CURRENT
        axis size, and shard onto the current mesh — the
        elastic-resume leg for optimizer state."""
        assert self.n_slots is not None, "probe() before restore"
        saved_layout = flat_dict.get("opt:layout")
        if saved_layout is not None and not _np.array_equal(
                _np.asarray(saved_layout).reshape(-1),
                self._layout_fingerprint()):
            raise MXNetError(
                "sharded optimizer state: the checkpoint's bucket "
                "partition differs from the current plan (different "
                "MXNET_GRAD_BUCKET_MB / roster?) — refusing to slice "
                "state into the wrong parameters")
        flats = []
        for b, bucket in enumerate(self.plan.buckets):
            slots = []
            for k in range(self.n_slots):
                key = "opt:bucket%02d.slot%d" % (b, k)
                if key not in flat_dict:
                    raise MXNetError(
                        "sharded optimizer state: checkpoint is "
                        "missing %s" % key)
                host = _np.asarray(flat_dict[key]).reshape(-1)
                if host.size < bucket.total:
                    raise MXNetError(
                        "sharded optimizer state: %s holds %d elements"
                        " but the roster needs %d (bucket layout "
                        "changed?)" % (key, host.size, bucket.total))
                host = host.astype(_np.dtype(self._slot_dtypes[k]),
                                   copy=False)
                slots.append(self._place(
                    bucket.from_roster_order(host)))
            flats.append(tuple(slots))
        self._flats = flats


# ---------------------------------------------------------------------------
# telemetry accounting
# ---------------------------------------------------------------------------

def account_in_program_sync(plan, mesh=None, axis="dp"):
    """Ledger one compiled-step dispatch's bucket traffic: per-bucket
    ``grad_sync`` comm records (the bucket's bytes once for the
    gradients' exchange and once for the updated-param all-gather;
    latency 0 — the exchange is scheduled INSIDE the
    program, overlapped with backward, so there is no host-observable
    span) plus run counters. With ``mesh`` given, the same bytes are
    additionally split per link — intra-host ``ici`` vs cross-host
    ``dcn`` (``mesh.link_split``) — under the ``grad_sync`` key of the
    per-link table. The eager kvstore leg
    (:func:`bucketed_kvstore_sync`) records real host-timed spans
    under the same kind."""
    from .. import telemetry, tracing
    if tracing._tracer is not None:
        # in-program buckets have no host-observable span (that is
        # the point of the overlap) — they render as instant events
        # on their own trace track, one per bucket per step
        tid = tracing.track("grad_sync")
        ctx = tracing.context() or {}
        for b, bucket in enumerate(plan.buckets):
            tracing.instant("bucket%02d" % b, "comm", tid=tid,
                            args=dict(ctx, bytes=2 * bucket.nbytes,
                                      in_program=True))
    if not telemetry.enabled():
        return
    total = 0
    for b, bucket in enumerate(plan.buckets):
        # the exchange moves the bucket in, the gather the same out;
        # account the logical payload once per direction
        telemetry.comm("grad_sync", "bucket%02d" % b,
                       nbytes=2 * bucket.nbytes, seconds=0.0)
        total += 2 * bucket.nbytes
    if mesh is not None:
        from .mesh import link_split
        try:
            ici, dcn = link_split(mesh, axis, total)
        except ValueError:
            ici = dcn = None
        if ici is not None:
            telemetry.comm_links("grad_sync", ici, dcn)
    telemetry.note("grad_sync_steps")


# ---------------------------------------------------------------------------
# eager kvstore leg (multi-process / kvstore-backed entry points)
# ---------------------------------------------------------------------------

def _dense(nd_arr):
    return nd_arr is not None and \
        getattr(nd_arr, "stype", "default") == "default"


def bucketed_kvstore_sync(kvstore, items, cap_bytes=None):
    """Exchange gradients through the kvstore in size-capped concat
    buckets instead of one push/pull per key — the eager
    (cross-process) form of the overlap recipe. ``items`` is an
    ordered ``[(key_index, grad_nd)]`` roster; each bucket is
    concatenated flat, pushed/pulled under one ``__grad_bucket`` key,
    and split back into the original grad buffers in place. Exact:
    concatenation and the kvstore's element-wise sum commute.

    Returns True when the bucketed path ran; False (nothing touched)
    when any gradient is sparse or the roster is empty — the caller
    keeps its per-key loop."""
    import jax.numpy as jnp
    from .. import telemetry, tracing
    from ..ndarray import NDArray

    if not items or not all(_dense(g) for _, g in items):
        return False
    if getattr(kvstore, "_compression", None) is not None:
        # 2-bit quantization blocks and error-feedback residuals are
        # keyed per parameter; a concat bucket would shift block
        # boundaries and residual state — numerics must never depend
        # on the overlap gate, so compressed stores keep per-key
        return False
    # the plan is a pure function of the roster signature — cache it
    # on the store so the per-step hot path skips the O(n_params)
    # rebuild (the roster never changes across a training run)
    cap = bucket_cap_bytes() if cap_bytes is None else int(cap_bytes)
    sig = (tuple((tuple(g.shape), str(g.dtype)) for _, g in items),
           cap)
    cached = getattr(kvstore, "_grad_bucket_plan", None)
    if cached is not None and cached[0] == sig:
        plan = cached[1]
    else:
        plan = GradSyncPlan([g.shape for _, g in items],
                            [g.dtype for _, g in items],
                            axis_size=1, cap_bytes=cap)
        kvstore._grad_bucket_plan = (sig, plan)
    inited = getattr(kvstore, "_grad_bucket_keys", None)
    if inited is None:
        inited = kvstore._grad_bucket_keys = set()
    for b, bucket in enumerate(plan.buckets):
        key = "__grad_bucket%02d" % b
        flat = jnp.concatenate(
            [items[i][1]._data.reshape(-1) for i in bucket.indices])
        flat_nd = NDArray(flat)
        if key not in inited:
            kvstore.init(key, NDArray(jnp.zeros_like(flat)))
            inited.add(key)
        t_tr = tracing.now() if tracing._tracer is not None else None
        with telemetry.comm_span("grad_sync", "bucket%02d" % b,
                                 nbytes=2 * flat.nbytes):
            # 2x: bucket bytes once per direction (push + pull),
            # matching the in-program exchange + gather accounting
            kvstore.push(key, flat_nd, priority=-b)
            kvstore.pull(key, flat_nd, priority=-b)
        if t_tr is not None:
            # the eager leg IS host-observable: a real duration event
            # on the same grad_sync track the in-program instants use
            tracing.add("bucket%02d" % b, "comm", t_tr,
                        tracing.now() - t_tr,
                        tid=tracing.track("grad_sync"),
                        args={"bytes": 2 * int(flat.nbytes),
                              "in_program": False})
        for i, off, size in zip(bucket.indices, bucket.offsets,
                                bucket.sizes):
            g = items[i][1]
            g._set_data(flat_nd._data[off:off + size].reshape(g.shape))
    from .. import profiler
    profiler.increment_counter("grad_sync_kvstore_buckets",
                               len(plan.buckets))
    return True
