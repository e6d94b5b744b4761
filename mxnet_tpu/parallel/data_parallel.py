"""Data/tensor-parallel training steps over a mesh.

The reference's DataParallelExecutorGroup (one executor per GPU + kvstore
reduce, SURVEY §2.2 row 1) becomes ONE pjit'd train step: the batch is
sharded over ``dp``, parameters are replicated (or sharded over ``tp``),
and XLA inserts the gradient psum where the sharding demands it — the
allreduce overlaps backprop exactly as the reference's engine-priority
trick tried to achieve (SURVEY §7 hard-part 2), but scheduled by the
compiler.

With ``MXNET_GRAD_OVERLAP=1`` (or ``grad_overlap=True``) the step goes
further (``parallel.grad_sync``): gradients are partitioned into
backward-ordered size-capped buckets, each bucket's flat buffer is laid
out by rows a chip, the optimizer update runs on each device's row
against ZeRO-1 sharded optimizer state in the same layout (1/N
per-device state memory), and only the updated parameters all-gather
back, once a bucket — all inside the same compiled step, bit-exact
against the unbucketed path. (The gradients' sum itself compiles to
all-reduces of whole tensors in both modes, not to a reduce-scatter:
``grad_sync``'s docstring has what was read from the compiled step.)
"""
from __future__ import annotations

from typing import Any, Callable, Dict, Optional

from ..base import MXNetError

__all__ = ["make_data_parallel_step", "shard_params", "DistributedTrainer",
           "sharded_input_pipeline", "apply_param_sharding"]


def sharded_input_pipeline(source, mesh, prefetch_depth=2,
                           num_workers=None):
    """An async input pipeline (io/pipeline.py) whose batches arrive
    already sharded for a data-parallel step on ``mesh``: batch-dim
    arrays split over ``dp``, the rest replicated — the exact placement
    :class:`DistributedTrainer`/``make_data_parallel_step`` consume, so
    their own ``device_put`` degenerates to a no-op and the per-device
    H2D scatter overlaps the previous step's compute."""
    from ..io.pipeline import make_sharded_pipeline
    return make_sharded_pipeline(source, mesh,
                                 prefetch_depth=prefetch_depth,
                                 num_workers=num_workers)


def _put_unless_placed(value, sharding):
    """device_put unless the array already carries the wanted sharding
    (the input pipeline's prefetch stage commits batches ahead of
    time — re-putting would serialize the transfer we just hid)."""
    import jax
    if getattr(value, "sharding", None) == sharding:
        return value
    return jax.device_put(value, sharding)


def _axis_size(mesh, axis):
    return dict(zip(mesh.axis_names, mesh.devices.shape))[axis]


def shard_params(params: Dict[str, Any], mesh, rules=None, pad=False):
    """Place a name→array dict on the mesh. ``rules`` is either the
    legacy substring → PartitionSpec mapping or a
    :class:`~mxnet_tpu.parallel.sharding_rules.ShardingRules` (the
    FSDP rules layer: user overrides over name heuristics); default
    replicates everything. NDArray values are unwrapped/rewrapped, so
    a checkpoint roster restored by
    ``mxnet_tpu.checkpoint.restore_params`` re-places directly against
    the current mesh regardless of the topology it was saved on.

    A sharded dim that does not divide its axis size is never dropped
    silently: with ``pad=True`` the array is zero-padded up to the
    next multiple and stored sharded (the ``collectives.py``
    reduce-scatter pad-and-slice convention — callers like
    ``DistributedTrainer`` slice the logical view back inside the
    compiled step), otherwise it stays replicated — either way a
    one-time telemetry note names the parameter."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P
    from ..ndarray import NDArray
    from .sharding_rules import ShardingRules
    out = {}
    if isinstance(rules, ShardingRules):
        resolver = rules
    else:
        # legacy substring table: express it as pure overrides with a
        # replicated default, so both forms share one feasibility path
        table = dict(rules or {})
        table.setdefault("", P())         # catch-all → replicated
        resolver = ShardingRules(mesh, overrides=table)
    for name, arr in params.items():
        val = arr._data if isinstance(arr, NDArray) else arr
        plan = resolver.plan(name, getattr(val, "shape", ()))
        if plan.padded and not pad:
            # do not hand a padded array to a caller expecting the
            # logical shape — fall back to replicated, but never
            # silently: the note names the parameter
            from .. import telemetry
            telemetry.note("param_shard_fallback:%s" % name)
            placed = _put_unless_placed(val, NamedSharding(mesh, P()))
        elif plan.padded:
            resolver.note_padded(name)
            placed = jax.device_put(plan.pad(val), plan.sharding(mesh))
        else:
            placed = _put_unless_placed(val, plan.sharding(mesh))
        if isinstance(arr, NDArray):
            out[name] = NDArray(placed, ctx=arr._ctx)
        else:
            out[name] = placed
    return out


def apply_param_sharding(params, mesh, rules=None):
    """Re-place a gluon ``ParameterDict`` (or ``{name: Parameter}``)
    in place per the FSDP rules layer: each Parameter's array moves to
    its rule-resolved ``NamedSharding`` on ``mesh``. Gluon handles
    must keep their logical shapes, so a param whose sharded dim does
    not divide the axis stays replicated (with a one-time telemetry
    note) — the padded-storage form is :class:`DistributedTrainer`'s.
    Returns the ``{name: ParamShardPlan}`` table for inspection."""
    from jax.sharding import PartitionSpec as P
    from .sharding_rules import ParamShardPlan, ShardingRules
    if not isinstance(rules, ShardingRules):
        rules = ShardingRules(mesh, overrides=rules)
    items = list(params.items())
    roster = {name: p.data() for name, p in items}
    placed = shard_params(roster, mesh, rules=rules, pad=False)
    plans = {}
    for name, p in items:
        p._data._set_data(placed[name]._data)
        pl = rules.plan(name, p.data().shape)
        if pl.padded:
            # pad=False left this one replicated — the table must say
            # what actually happened, not what the rules asked for
            pl = ParamShardPlan(name, P(), pl.shape, pl.shape)
        plans[name] = pl
    return plans


def make_data_parallel_step(loss_fn: Callable, mesh, optimizer_update=None,
                            donate=True, grad_overlap=None,
                            bucket_mb=None, param_shard=None,
                            param_rules=None):
    """Compile ``(params, batch) -> (loss, new_params)`` with batch
    sharded over dp and grads reduced implicitly.

    loss_fn(params: dict, batch: dict) -> scalar loss (pure JAX).
    optimizer_update(p, g) -> new_p elementwise (default SGD lr=0.01).

    ``param_shard`` (None → the ``MXNET_PARAM_SHARD`` gate) keeps the
    parameters FSDP-sharded at rest: place them beforehand with
    ``shard_params(params, mesh, rules)``, and the compiled step
    gathers each sharded param at entry (the partitioner's
    just-in-time all-gather), runs the identical computation, and
    constrains the updated params back to their rule specs —
    ``param_rules`` is the same override table / ``ShardingRules``
    object. Only divisible dims shard through this dict-tree API (the
    padded-storage form is :class:`DistributedTrainer`'s).

    ``grad_overlap`` (None → the ``MXNET_GRAD_OVERLAP`` gate) switches
    the gradient exchange + update to the bucketed form: each
    backward-ordered bucket of the flat gradient roster is laid out by
    rows a chip and constrained to ``P('dp', None)``,
    ``optimizer_update`` runs elementwise on each chip's row, and the
    updated params all-gather back, once a bucket. Losses/gradients are identical
    between modes (weights are pinned replicated before bucketing, so
    the forward/backward never re-partitions); the updated params may
    differ ~1 ULP because the gate-closed path keeps its original
    replicated ``tree_map`` update, whose XLA codegen contracts FMAs
    the shard-wise update does not. ``DistributedTrainer`` runs BOTH
    modes through the same shard-wise machinery and is the bit-exact
    (rtol=0) oracle ``tests/test_grad_sync.py`` pins.
    """
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    from . import grad_sync

    if optimizer_update is None:
        def optimizer_update(p, g):
            return p - 0.01 * g

    overlap = grad_sync.overlap_enabled() if grad_overlap is None \
        else bool(grad_overlap)

    if not overlap:
        def step(params, batch):
            loss, grads = jax.value_and_grad(loss_fn)(params, batch)
            new_params = jax.tree_util.tree_map(optimizer_update,
                                                params, grads)
            return loss, new_params
    else:
        cap = int(bucket_mb * (1 << 20)) if bucket_mb else None
        shard = NamedSharding(mesh, P("dp", None))
        rep = NamedSharding(mesh, P())
        wsc = jax.lax.with_sharding_constraint

        def step(params, batch):
            loss, grads = jax.value_and_grad(loss_fn)(params, batch)
            leaves_g, treedef = jax.tree_util.tree_flatten(grads)
            # pin weights replicated BEFORE bucketing (see
            # grad_sync.make_bucketed_apply): without the pin each
            # bucket's row constraint back-propagates through
            # concatenate onto the weight nodes and re-partitions the
            # forward/backward
            leaves_p = [wsc(l, rep)
                        for l in jax.tree_util.tree_leaves(params)]
            plan = grad_sync.GradSyncPlan(
                [l.shape for l in leaves_p],
                [l.dtype for l in leaves_p],
                axis_size=_axis_size(mesh, "dp"), cap_bytes=cap)
            new_leaves = [None] * len(leaves_p)
            for bucket in plan.buckets:
                # the bucket's row layout: row k is chip k's share of
                # every member (grad_sync._Bucket)
                gflat = wsc(bucket.pack(
                    [wsc(leaves_g[i], rep).reshape(-1)
                     for i in bucket.indices], jnp), shard)
                pflat = wsc(bucket.pack(
                    [leaves_p[i].reshape(-1) for i in bucket.indices],
                    jnp), shard)
                # update pinned shard-wise first, gathered after — the
                # all-gather moves updated params only
                new_flat = wsc(wsc(optimizer_update(pflat, gflat),
                                   shard), rep)
                for i, seg in zip(bucket.indices,
                                  bucket.unpack(new_flat)):
                    new_leaves[i] = seg.reshape(leaves_p[i].shape)
            new_params = jax.tree_util.tree_unflatten(treedef,
                                                      new_leaves)
            return loss, new_params

    from .sharding_rules import ShardingRules, param_shard_enabled
    shard_on = param_shard_enabled() if param_shard is None \
        else bool(param_shard)
    if shard_on:
        rules = param_rules if isinstance(param_rules, ShardingRules) \
            else ShardingRules(mesh, overrides=param_rules)
        rep_s = NamedSharding(mesh, P())
        wsc_s = jax.lax.with_sharding_constraint
        base_step = step

        def step(params, batch):
            full = {n: wsc_s(v, rep_s)
                    if rules.plan(n, v.shape).sharded else v
                    for n, v in params.items()}
            loss, new_params = base_step(full, batch)
            new_params = {
                n: wsc_s(v, rules.plan(n, v.shape).sharding(mesh))
                if rules.plan(n, v.shape).sharded else v
                for n, v in new_params.items()}
            return loss, new_params

    batch_sharding = NamedSharding(mesh, P("dp"))
    jit_kwargs = {}
    if donate:
        jit_kwargs["donate_argnums"] = (0,)
    # staged for compile telemetry/storm detection
    from .. import compile_watch
    return (compile_watch.jit(
        step, "data_parallel:step",
        statics=("overlap" if overlap else "plain",
                 "shard" if shard_on else "rep"),
        **jit_kwargs), batch_sharding)


class DistributedTrainer:
    """Gluon-style trainer whose step is one compiled mesh program.

    Usage: build a HybridBlock, call trainer.fit_batch(data, label).
    Parameters live as mesh-sharded jax arrays inside the compiled
    step, placed ONCE at build and kept device-resident across steps
    (the Gluon Parameter handles are refreshed lazily — call
    :meth:`sync_gluon_params` to read trained values back through
    ``net.collect_params()``).

    The update routes through the shared ``Optimizer.fused_step_fn``
    roster — any registered optimizer with a compiled update path
    (SGD/momentum, Adam, AdaGrad, RMSProp) works; unknown names and
    optimizers without a fused path raise at construction/build.

    With ``grad_overlap=True`` (or ``MXNET_GRAD_OVERLAP=1``) the step
    compiles the bucketed reduce-scatter + ZeRO-1 sharded-update
    composition from ``parallel.grad_sync``: optimizer state lives
    permanently dp-sharded (1/N per device) and round-trips through
    ``checkpoint.py``'s per-shard manifest format
    (:meth:`save_checkpoint` / :meth:`load_checkpoint`, elastic across
    mesh sizes). Trajectories are bit-exact vs ``grad_overlap=False``.
    """

    def __init__(self, net, loss_block, mesh, optimizer="sgd",
                 learning_rate=0.01, optimizer_params=None,
                 param_rules=None, grad_overlap=None, bucket_mb=None,
                 param_shard=None, multihost=None):
        from .. import optimizer as opt_mod
        self._net = net
        self._loss = loss_block
        self._mesh = mesh
        if isinstance(optimizer, opt_mod.Optimizer):
            self._opt = optimizer
        else:
            kwargs = dict(optimizer_params or {})
            kwargs.setdefault("learning_rate", learning_rate)
            self._opt = opt_mod.create(optimizer, **kwargs)
        self._overlap = grad_overlap
        self._bucket_mb = bucket_mb
        self._param_rules = param_rules
        self._param_shard = param_shard
        self._multihost = multihost   # None = auto (see _build)
        self._mesh_global = None      # the full cross-process mesh
        self._mh = False              # resolved multihost mode
        self._mh_grad_fn = None       # stacked per-device grad program
        self._mh_apply_fn = None      # post-exchange update program
        self._shard_rules = None      # resolved ShardingRules (fsdp on)
        self._param_plans = None      # per-roster ParamShardPlan list
        self._mem_bd = None           # cached telemetry byte split
        self._step_fn = None
        self._batch_sharding = None
        self._roster = None
        self._aux_roster = None
        self._param_vals = None       # device-resident, placed once
        self._aux_vals = None
        self._state_vals = None
        self._plan = None
        self._sync_state = None
        self._poisons_zero = None
        self._pending_restore = None
        self._gluon_dirty = False
        self.dispatch_count = 0

    # -- properties -------------------------------------------------------
    @property
    def optimizer(self):
        return self._opt

    @property
    def overlap(self):
        """True when the built step uses the bucketed reduce-scatter
        + sharded-state path (None before the first fit_batch)."""
        return None if self._step_fn is None \
            else self._sync_state.sharded

    @property
    def param_shard(self):
        """True when the built step keeps the parameters FSDP-sharded
        at rest (None before the first fit_batch)."""
        return None if self._step_fn is None \
            else self._param_plans is not None

    def state_bytes_per_device(self):
        """Resident optimizer-state bytes per device: the sharded 1/N
        figure in overlap mode, the full replicated size otherwise."""
        return 0 if self._sync_state is None \
            else self._sync_state.state_bytes_per_device()

    def param_bytes_per_device(self):
        """Resident parameter bytes per device: with FSDP on, each
        sharded param counts its padded shard; replicated params (and
        the whole roster with the gate closed) count their full
        size — the 1/N claim ``tests/test_param_shard.py`` holds it
        to."""
        if self._param_vals is None:
            return 0
        total = 0
        for v in list(self._param_vals) + list(self._aux_vals or []):
            shards = getattr(v, "addressable_shards", None)
            if shards:
                total += int(shards[0].data.nbytes)
            else:
                total += int(getattr(v, "nbytes", 0))
        return total

    # -- build ------------------------------------------------------------
    def _build(self, data, label):
        import jax
        import numpy as _np
        from jax.sharding import NamedSharding, PartitionSpec as P
        from ..cached_op import build_graph_callable
        from ..ndarray import NDArray
        from .. import symbol as sym_mod
        from . import grad_sync

        net, loss_blk = self._net, self._loss
        # trace net(data) -> loss(out, label) into one symbol graph
        data_sym = sym_mod.var("data")
        label_sym = sym_mod.var("label")
        out_sym = net(data_sym)
        loss_sym = loss_blk(out_sym, label_sym)
        fn, arg_names, aux_names, n_rng, n_out = \
            build_graph_callable(loss_sym)
        params = {p.name: p for p in net.collect_params().values()}
        self._graph = (fn, arg_names, aux_names)
        self._params = params
        # -- multihost resolution (the cross-host DCN leg) ----------------
        # When the job is a jax.distributed group whose backend cannot
        # run ONE program across processes (jaxlib's CPU backend), the
        # step splits into a local stacked-gradient program, a
        # coordination-service exchange (multihost.cross_host_sum:
        # rank-major left fold == the flat global mesh's reduction
        # grouping, bit for bit), and a local update program. Backends
        # with cross-process SPMD keep the single fused program over
        # the global mesh.
        from . import multihost as mh_mod
        world, me = 1, 0
        try:
            world = int(jax.process_count())
            me = int(jax.process_index())
        except Exception:
            pass
        mh = self._multihost
        if mh is None:
            mh = world > 1 and not mh_mod.supports_global_spmd()
        self._mh = bool(mh)
        # the authoritative world size for the exchange fold: the
        # process count, NOT a mesh-size ratio — a trainer handed a
        # local-only mesh in a multi-process job must still divide the
        # loss by every rank's rows
        self._mh_world = world if self._mh else 1
        mesh = self._mesh
        if self._mh:
            self._mesh_global = mesh
            local = [d for d in mesh.devices.flat
                     if getattr(d, "process_index", 0) == me]
            if local and len(local) != int(mesh.devices.size):
                from .mesh import create_mesh
                local.sort(key=lambda d: d.id)
                mesh = create_mesh({"dp": len(local)}, devices=local)
                self._mesh = mesh
        roster = [n for n in arg_names if n in params]
        aux_roster = [n for n in aux_names if n in params]
        self._roster, self._aux_roster = roster, aux_roster
        indices = list(range(len(roster)))
        if not self._opt.idx2name:
            self._opt.idx2name = dict(enumerate(roster))

        weights_nd = [params[n].data() for n in roster]
        step_fns = [self._opt.fused_step_fn(i, w)
                    for i, w in zip(indices, weights_nd)]
        if any(f is None for f in step_fns):
            raise MXNetError(
                "DistributedTrainer: optimizer %s has no compiled "
                "(fused_step_fn) update path for this roster — use "
                "SGD/momentum, Adam, AdaGrad or RMSProp"
                % type(self._opt).__name__)

        rep = NamedSharding(mesh, P())
        # FSDP gate: resolve the sharding-rules layer once per build.
        # param_rules is either a ShardingRules, a {substring: spec}
        # override table, or None (pure name heuristics).
        from .sharding_rules import ShardingRules, param_shard_enabled
        shard_on = param_shard_enabled() if self._param_shard is None \
            else bool(self._param_shard)
        if shard_on and self._mh:
            # FSDP at-rest needs the one-program entry gather; the
            # multihost host-exchange leg feeds full params into two
            # programs — fall back replicated, never silently
            import logging
            from .. import telemetry
            logging.getLogger(__name__).warning(
                "DistributedTrainer: FSDP param sharding is not "
                "available on the multihost host-exchange leg — "
                "params stay replicated (per-host FSDP needs the "
                "global-SPMD backend path)")
            telemetry.note("param_shard_multihost_fallback")
            shard_on = False
        plans = None
        if shard_on:
            rules = self._param_rules
            if not isinstance(rules, ShardingRules):
                rules = ShardingRules(mesh, overrides=rules)
            plans = [rules.plan(n, w.shape)
                     for n, w in zip(roster, weights_nd)]
            self._shard_rules = rules
        self._param_plans = plans
        self._mem_bd = None
        # satellite: parameters placed ONCE at build; steps feed the
        # device-resident values, never re-device_put per step. The
        # .copy() breaks any aliasing with the Gluon handles (a
        # same-device device_put can alias its input): fit_batch
        # DONATES these buffers, and a donated alias would leave the
        # Parameter reading a deleted buffer. With FSDP on, sharded
        # params are placed as their (padded) 1/N-per-device storage;
        # the .copy() is just as load-bearing there — a device_put to
        # the sharding the value ALREADY carries (a roster pre-placed
        # via apply_param_sharding) aliases its buffers.
        if plans is None:
            self._param_vals = [
                _put_unless_placed(params[n].data()._data, rep).copy()
                for n in roster]
        else:
            self._param_vals = []
            for n, pl in zip(roster, plans):
                v = params[n].data()._data
                if pl.sharded:
                    if pl.padded:
                        rules.note_padded(n)
                    self._param_vals.append(
                        jax.device_put(pl.pad(v),
                                       pl.sharding(mesh)).copy())
                else:
                    self._param_vals.append(
                        _put_unless_placed(v, rep).copy())
        self._aux_vals = [
            _put_unless_placed(params[n].data()._data, rep).copy()
            for n in aux_roster]

        # Both modes run the SAME sharded-update machinery; they differ
        # only in the bucket partition (size-capped backward-order
        # buckets vs ONE monolithic blob — the "one blob after
        # backward" baseline ROADMAP item 4 names) and in where the
        # optimizer state lives (dp-sharded 1/N vs replicated). That
        # symmetry is what makes the two trajectories bit-identical:
        # XLA contracts FMAs in replicated elementwise code but not in
        # partitioned code, so a replicated-update baseline would
        # drift ~1 ULP/step.
        overlap = grad_sync.overlap_enabled() if self._overlap is None \
            else bool(self._overlap)
        cap = int(self._bucket_mb * (1 << 20)) if self._bucket_mb \
            else None
        plan = grad_sync.GradSyncPlan(
            [w.shape for w in weights_nd],
            [w.dtype for w in weights_nd],
            axis_size=_axis_size(mesh, "dp"),
            cap_bytes=cap if overlap else grad_sync.MONOLITH_CAP)
        sync_state = grad_sync.ShardedOptState(plan, mesh, "dp",
                                               sharded=overlap)
        if not sync_state.probe(self._opt, indices, weights_nd):
            raise MXNetError(
                "DistributedTrainer: optimizer %s state layout "
                "has no sharded path" % type(self._opt).__name__)
        self._state_vals = list(sync_state.ensure())
        self._plan, self._sync_state = plan, sync_state
        apply_fn = grad_sync.make_bucketed_apply(
            step_fns, sync_state.n_slots, plan, mesh, "dp",
            guard=False, inject=False, shard_state=overlap)

        self._poisons_zero = _np.zeros((len(roster),), _np.float32)
        n_aux = len(aux_roster)
        aux_pos = {n: k for k, n in enumerate(aux_roster)}
        roster_pos = {n: k for k, n in enumerate(roster)}

        wsc = jax.lax.with_sharding_constraint

        def step(param_vals, state_vals, aux_vals, data_v, label_v,
                 rng, scalars, poisons):
            if plans is not None:
                # FSDP: gather each sharded resident param to its
                # full logical value at program entry — the SPMD
                # partitioner lowers the constraint to a just-in-time
                # all-gather ahead of the forward — and slice off the
                # pad rows. Everything downstream (forward, backward,
                # bucketed reduce-scatter, shard-local update) is the
                # IDENTICAL traced computation as the replicated
                # mode, which is what makes FSDP-on vs off bit-exact.
                param_vals = tuple(
                    plan.logical(wsc(v, rep)) if plan.sharded else v
                    for plan, v in zip(plans, param_vals))

            def loss_of(pv):
                vals = []
                for n in arg_names:
                    if n == "data":
                        vals.append(data_v)
                    elif n == "label":
                        vals.append(label_v)
                    else:
                        vals.append(pv[roster_pos[n]])
                vals.extend(aux_vals[aux_pos[n]] for n in aux_names)
                outs = fn({"__train__": True}, *vals, rng=rng)
                loss = outs[0].mean()
                new_aux = tuple(outs[n_out:n_out + n_aux])
                return loss, new_aux

            (loss, new_aux), grads = jax.value_and_grad(
                loss_of, has_aux=True)(param_vals)
            new_ws, new_sts, _ = apply_fn(grads, param_vals,
                                          state_vals, scalars, poisons)
            if plans is not None:
                # updated params go back to their sharded residency:
                # re-pad (exact zeros) and constrain to the plan's
                # spec — a LOCAL slice of the already-gathered updated
                # value, not a second collective; the next step's
                # entry gather is the only re-assembly.
                new_ws = tuple(
                    wsc(plan.pad(w), plan.sharding(mesh))
                    if plan.sharded else w
                    for plan, w in zip(plans, new_ws))
            return loss, new_ws, new_sts, new_aux

        # distinct program names: a replicated↔sharded flip must show
        # up as a NEW program in the compile log, not as a recompile
        # (or storm) of one site
        from .. import compile_watch
        site = "fused_step:fsdp" if plans is not None \
            else "fused_step:dist"
        shard_sig = tuple((p.name, str(p.spec), p.padded_shape)
                          for p in plans) if plans is not None else None
        n_states = len(self._state_vals)

        def describe(param_vals, state_vals, aux_vals, data_v, label_v,
                     rng, scalars, poisons):
            from ..compile_watch import describe_arrays
            d = describe_arrays(list(roster), param_vals)
            d.update(describe_arrays(
                ["state%d" % i for i in range(n_states)], state_vals))
            d.update(describe_arrays(
                ["aux:%s" % n for n in aux_roster], aux_vals))
            d.update(describe_arrays(
                ["data", "label", "scalars", "poisons"],
                [data_v, label_v, scalars, poisons]))
            return d

        if not self._mh:
            self._step_fn = compile_watch.jit(
                step, site, describe=describe,
                counter="fused_step_compile_ms",
                statics=(plan.signature(), shard_sig,
                         self._opt.fused_static_key()),
                donate_argnums=(0, 1, 2))
        else:
            self._build_multihost(fn, arg_names, aux_names, roster,
                                  aux_roster, roster_pos, aux_pos,
                                  n_out, n_aux, apply_fn, plan, mesh)
        self._batch_sharding = NamedSharding(mesh, P("dp"))
        if self._pending_restore is not None:
            self._apply_restore(self._pending_restore)
            self._pending_restore = None

    def _build_multihost(self, fn, arg_names, aux_names, roster,
                         aux_roster, roster_pos, aux_pos, n_out, n_aux,
                         apply_fn, plan, mesh):
        """Compile the two programs of the host-exchange leg.

        ``mh_grad`` shard_maps the forward/backward over the LOCAL
        mesh and returns per-device STACKED (unreduced) losses, grads
        and aux updates — each device's row is exactly the local
        contribution the flat global mesh's in-program psum would
        fold, so the host-side rank-major left fold
        (``multihost.cross_host_sum``) reproduces the single-process
        reduction bit for bit. ``mh_apply`` feeds the folded global
        gradient through the SAME bucketed update machinery the fused
        path uses (a replicated input under a dp constraint is a pure
        reshard — no double count), so optimizer math stays partitioned
        and bit-identical to the one-program path."""
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P
        from .collectives import _shard_map
        from .. import compile_watch

        n_states = len(self._state_vals)

        def per_device(param_vals, aux_vals, data_s, label_s, rng,
                       n_rows):
            # loss contribution = local_sum / GLOBAL row count (the
            # traced n_rows scalar): each device's value and gradient
            # rows are then exactly the leaves the flat global mesh's
            # in-program psum would fold — a per-shard mean would
            # scale the folded gradient by the device count
            def loss_of(pv):
                vals = []
                for n in arg_names:
                    if n == "data":
                        vals.append(data_s)
                    elif n == "label":
                        vals.append(label_s)
                    else:
                        vals.append(pv[roster_pos[n]])
                vals.extend(aux_vals[aux_pos[n]] for n in aux_names)
                outs = fn({"__train__": True}, *vals, rng=rng)
                loss = outs[0].sum() / n_rows
                new_aux = tuple(outs[n_out:n_out + n_aux])
                return loss, new_aux

            (loss, new_aux), grads = jax.value_and_grad(
                loss_of, has_aux=True)(param_vals)
            return (loss[None],
                    tuple(g[None] for g in grads),
                    tuple(a[None] for a in new_aux))

        grad_stacked = _shard_map()(
            per_device, mesh=mesh,
            in_specs=(P(), P(), P("dp"), P("dp"), P(), P()),
            out_specs=(P("dp"), P("dp"), P("dp")))

        def describe_grad(param_vals, aux_vals, data_v, label_v, rng,
                          n_rows):
            from ..compile_watch import describe_arrays
            d = describe_arrays(list(roster), param_vals)
            d.update(describe_arrays(
                ["aux:%s" % n for n in aux_roster], aux_vals))
            d.update(describe_arrays(["data", "label", "n_rows"],
                                     [data_v, label_v, n_rows]))
            return d

        self._mh_grad_fn = compile_watch.jit(
            grad_stacked, "fused_step:mh_grad",
            describe=describe_grad,
            counter="fused_step_compile_ms",
            statics=(plan.signature(), self._opt.fused_static_key()))

        def mh_apply(g_tot, param_vals, state_vals, scalars, poisons):
            new_ws, new_sts, _ = apply_fn(g_tot, param_vals,
                                          state_vals, scalars,
                                          poisons)
            return new_ws, new_sts

        def describe_apply(g_tot, param_vals, state_vals, scalars,
                           poisons):
            from ..compile_watch import describe_arrays
            d = describe_arrays(["g:%s" % n for n in roster], g_tot)
            d.update(describe_arrays(list(roster), param_vals))
            d.update(describe_arrays(
                ["state%d" % i for i in range(n_states)], state_vals))
            d.update(describe_arrays(["scalars", "poisons"],
                                     [scalars, poisons]))
            return d

        self._mh_apply_fn = compile_watch.jit(
            mh_apply, "fused_step:mh_apply",
            describe=describe_apply,
            counter="fused_step_compile_ms",
            statics=(plan.signature(), self._opt.fused_static_key()),
            donate_argnums=(1, 2))
        # the built marker every property/entry point checks
        self._step_fn = self._mh_apply_fn

    # -- the step ---------------------------------------------------------
    def fit_batch(self, data, label):
        """One training step — forward, backward, gradient exchange
        and optimizer update in a single compiled dispatch (or, on the
        multihost host-exchange leg, a local gradient program + the
        cross-host fold + a local update program); returns the (host)
        loss value lazily. In a multi-process job each process feeds
        its OWN rank's slice of the global batch."""
        from .. import random as _random
        from .. import telemetry, tracing
        from ..fused_step import pack_step_scalars
        from ..ndarray import NDArray
        from . import grad_sync, multihost
        # the proc_exit fault site + host-loss check: the injectable
        # "this host dies at exactly step N", and the typed surfacing
        # of a peer loss the heartbeat monitor detected
        multihost.step_boundary()
        if self._step_fn is None:
            # ensure params are materialized
            _ = self._net(data)
            self._build(data, label)
        with tracing.span("trainer.step"):
            data_v = _put_unless_placed(data._data,
                                        self._batch_sharding)
            label_v = _put_unless_placed(label._data,
                                         self._batch_sharding)
            scalars = pack_step_scalars(
                self._opt, list(range(len(self._roster))))
            if self._mh:
                loss, new_ws, new_sts, new_aux = self._mh_step(
                    data_v, label_v, scalars)
            else:
                with tracing.span("step.compute", phase="compute"):
                    loss, new_ws, new_sts, new_aux = self._step_fn(
                        tuple(self._param_vals),
                        tuple(self._state_vals),
                        tuple(self._aux_vals), data_v, label_v,
                        _random.new_key(), scalars,
                        self._poisons_zero)
        self._param_vals = list(new_ws)
        self._state_vals = list(new_sts)
        self._aux_vals = list(new_aux)
        self._sync_state.store(new_sts)
        if telemetry.enabled():
            # computed once per build (lazily, so the sharded opt
            # state has materialized) — the split never changes
            # between rebuilds
            if self._mem_bd is None:
                self._mem_bd = self._memory_breakdown()
            telemetry.memory_breakdown(**self._mem_bd)
        if self._sync_state.sharded:
            # only the overlap mode ledgers grad_sync records — the
            # gate-closed baseline's telemetry must look like it
            # always did (and the diagnose table is the overlap-on
            # oracle); the mesh adds the per-link (ici/dcn) split
            grad_sync.account_in_program_sync(self._plan,
                                              mesh=self._mesh)
        self._gluon_dirty = True
        self.dispatch_count += 1
        return NDArray(loss)

    def _mh_step(self, data_v, label_v, scalars):
        """One multihost step: local stacked-gradient program →
        cross-host coordination-service fold (rank-major left fold ==
        the flat mesh's reduction grouping, bit for bit) → local
        bucketed update program. Loss is the global mean (the stacked
        per-device means ride the same exchange)."""
        import numpy as _np
        import jax.numpy as jnp
        from .. import random as _random
        from .. import telemetry, tracing
        from . import multihost
        from .mesh import link_split
        world = max(int(getattr(self, "_mh_world", 1)), 1)
        # every process feeds its rank's equal slice of the global
        # batch, so global rows = local rows x world — the traced
        # divisor that makes each device's gradient rows the flat
        # mesh's exact psum leaves
        n_rows = _np.float32(int(data_v.shape[0]) * world)
        with tracing.span("step.compute", phase="compute"):
            losses, grads, new_aux = self._mh_grad_fn(
                tuple(self._param_vals), tuple(self._aux_vals),
                data_v, label_v, _random.new_key(), n_rows)
        with tracing.span("step.sync", phase="sync") as sync:
            stacks = [_np.asarray(losses)] + [_np.asarray(g)
                                              for g in grads]
            folded = multihost.cross_host_sum("grad", stacks)
        # per-device rows are local_sum/global_rows, so the fold IS
        # the global mean
        loss = folded[0]
        g_tot = folded[1:]
        if telemetry.enabled():
            payload = sum(int(s.nbytes) for s in stacks[1:])
            # the exchange itself: every peer's payload crossed the
            # host boundary once (pure dcn); the local stacked fold is
            # host arithmetic, not a link
            telemetry.comm("grad_sync", "dcn_exchange",
                           nbytes=payload * (world - 1),
                           seconds=sync.t1 - sync.t0)
            audit = self._mesh_global
            if audit is not None:
                try:
                    ici, dcn = link_split(audit, "dp", 2 * payload)
                    telemetry.comm_links("grad_sync", ici, dcn)
                except ValueError:
                    pass
        with tracing.span("step.optimizer", phase="optimizer"):
            new_ws, new_sts = self._mh_apply_fn(
                tuple(jnp.asarray(g) for g in g_tot),
                tuple(self._param_vals), tuple(self._state_vals),
                scalars, self._poisons_zero)
        # aux (batchnorm stats) follow the local leader device — the
        # host-exchange leg does not cross-sync them (documented; the
        # global-SPMD path keeps them in-program)
        aux_vals = tuple(jnp.asarray(_np.asarray(a)[0])
                         for a in new_aux)
        return jnp.asarray(loss), new_ws, new_sts, aux_vals

    def _memory_breakdown(self):
        """Per-device resident bytes split by kind — the telemetry
        memory table's ``params_sharded`` / ``params_replicated`` /
        ``opt_state`` columns."""
        sharded = replicated = 0
        plans = self._param_plans
        for pos, v in enumerate(self._param_vals or []):
            shards = getattr(v, "addressable_shards", None)
            b = int(shards[0].data.nbytes) if shards \
                else int(getattr(v, "nbytes", 0))
            if plans is not None and plans[pos].sharded:
                sharded += b
            else:
                replicated += b
        for v in self._aux_vals or []:
            shards = getattr(v, "addressable_shards", None)
            replicated += int(shards[0].data.nbytes) if shards \
                else int(getattr(v, "nbytes", 0))
        return {"params_sharded": sharded,
                "params_replicated": replicated,
                "opt_state": self.state_bytes_per_device()}

    def sync_gluon_params(self):
        """Refresh the Gluon Parameter handles from the
        device-resident roster (lazy — fit_batch marks them stale
        instead of writing back every step). FSDP-padded params are
        sliced back to their logical shape on the host first."""
        if not self._gluon_dirty:
            return
        import numpy as _np
        # copies, not aliases: the next fit_batch donates the roster
        # arrays, which would delete the Parameter's buffer under it
        for pos, (n, v) in enumerate(zip(self._roster,
                                         self._param_vals)):
            pl = self._param_plans[pos] if self._param_plans else None
            if pl is not None and pl.padded:
                host = pl.logical(_np.asarray(v))
                self._params[n]._data._set_data(_jnp_asarray(host))
            else:
                self._params[n]._data._set_data(v.copy())
        for n, v in zip(self._aux_roster, self._aux_vals):
            self._params[n]._data._set_data(v.copy())
        self._gluon_dirty = False

    # -- checkpointing ----------------------------------------------------
    def _checkpoint_roster(self):
        import numpy as _np
        # sharded params ride the manifest as per-mesh-position pieces
        # (the format already expresses the layout); PADDED storage is
        # the one exception — the manifest must stay logical-shaped so
        # any topology (and any gate state) can restore it, so those
        # few params are sliced to their logical value on the host
        arg = {}
        for pos, n in enumerate(self._roster):
            v = self._param_vals[pos]
            pl = self._param_plans[pos] if self._param_plans else None
            if pl is not None and pl.padded:
                v = pl.logical(_np.asarray(v)).copy()
            arg[n] = v
        aux = dict(zip(self._aux_roster, self._aux_vals))
        extra = self._sync_state.checkpoint_roster()
        # the host-side update counters ride along: Adam's bias
        # correction is t-dependent, so a resume without them would
        # restart the schedule at t=0 and diverge from the
        # uninterrupted trajectory
        opt = self._opt
        extra["opt:update_counts"] = _np.array(
            [opt._index_update_count.get(i, opt.begin_num_update)
             for i in range(len(self._roster))], _np.int64)
        return arg, aux, extra

    def save_checkpoint(self, prefix, epoch, manager=None):
        """One durable sharded checkpoint — params, aux, and the
        optimizer state (flat dp-sharded arrays in overlap mode, whose
        pieces land per mesh position in the manifest's shard files) —
        through ``checkpoint.py``'s atomic manifest writer. Pass a
        ``CheckpointManager`` to save asynchronously."""
        from .. import checkpoint as ckpt
        assert self._step_fn is not None, \
            "fit_batch at least once before checkpointing"
        arg, aux, extra = self._checkpoint_roster()
        if manager is not None:
            manager.save(epoch, arg, aux, extra=extra)
            return
        ckpt.save_arrays(prefix, epoch,
                         ckpt.snapshot_params(arg, aux, extra=extra))

    def load_checkpoint(self, prefix, epoch, validate=True):
        """Elastic resume from a manifest checkpoint: params/aux are
        re-placed replicated on the CURRENT mesh and the sharded
        optimizer state is re-padded for the current dp size —
        a run saved on N devices resumes on M. Before the first
        fit_batch the payload is staged and applied at build."""
        from .. import checkpoint as ckpt
        flat = ckpt.load_arrays(prefix, epoch, validate=validate)
        if self._step_fn is None:
            self._pending_restore = flat
        else:
            self._apply_restore(flat)

    def _apply_restore(self, flat):
        import numpy as _np
        from jax.sharding import NamedSharding, PartitionSpec as P
        rep = NamedSharding(self._mesh, P())

        def host(v):
            return v.asnumpy() if hasattr(v, "asnumpy") \
                else _np.asarray(v)

        # restore the sharded optimizer state FIRST: load_host_flats
        # raises on a bucket-layout mismatch (e.g. a different
        # MXNET_GRAD_BUCKET_MB than the save used) and commits its
        # flats only on success, so a failed restore leaves the
        # trainer fully untouched rather than half-restored (params
        # overwritten, state zeroed, counters advanced)
        counts = flat.pop("opt:update_counts", None)
        opt_flat = {k: host(v) for k, v in flat.items()
                    if k.startswith("opt:")}
        if opt_flat:
            self._sync_state.load_host_flats(opt_flat)
            self._state_vals = list(self._sync_state.ensure())
        for pos, n in enumerate(self._roster):
            key = "arg:%s" % n
            if key in flat:
                val = _jnp_asarray(host(flat[key]))
                pl = self._param_plans[pos] if self._param_plans \
                    else None
                if pl is not None and pl.sharded:
                    # elastic: the manifest holds the logical value —
                    # re-pad for the CURRENT mesh's plan and place it
                    # sharded, whatever topology saved it
                    import jax
                    self._param_vals[pos] = jax.device_put(
                        pl.pad(val), pl.sharding(self._mesh))
                else:
                    self._param_vals[pos] = _put_unless_placed(val,
                                                               rep)
        for pos, n in enumerate(self._aux_roster):
            key = "aux:%s" % n
            if key in flat:
                self._aux_vals[pos] = _put_unless_placed(
                    _jnp_asarray(host(flat[key])), rep)
        if counts is not None:
            opt = self._opt
            for i, c in enumerate(
                    host(counts).astype(_np.int64).tolist()):
                if c > opt.begin_num_update:
                    opt._index_update_count[i] = int(c)
                    opt.num_update = max(opt.num_update, int(c))
        self._gluon_dirty = True


def _jnp_asarray(v):
    import jax.numpy as jnp
    return jnp.asarray(v)
