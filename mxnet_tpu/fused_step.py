"""Fused train-step executor: one donated XLA dispatch per step.

The executor already lowers forward+vjp to a single compiled program
(executor.py), but the optimizer update ran host-side as a per-parameter
eager loop — every step paid 1 fused dispatch plus ~2·P tiny XLA
launches, P host→device round-trips, and P non-donated weight buffers.
This module closes that gap the way MXNet's fused/multi-tensor
optimizer kernels (src/operator/optimizer_op.cc) and
``update_on_kvstore`` did on GPU: the whole step — forward, backward,
and the update rule for *every* parameter and optimizer state — is one
``jax.jit`` program with ``donate_argnums`` on weights and optimizer
state, so XLA reuses the parameter buffers in place.

Two entry points share one core:

- :class:`FusedStepExecutor` (Module path): composes the executor's raw
  fwd+vjp plan with each parameter's :meth:`Optimizer.fused_step_fn`.
  ``Module.backward`` defers, ``Module.update`` runs the whole step as
  ONE dispatch.
- :class:`FusedUpdater` (gluon Trainer path): backward already ran under
  autograd, so only the all-parameter update fuses — still one dispatch
  instead of ~2·P.

Per-step scalars (LR schedule value, wd, rescale/loss-scale, Adam's
bias-corrected lr) enter as *traced inputs* packed into two f32 vectors,
so schedule ticks and dynamic loss-scale changes never retrigger a
compile. The compile cache is keyed on (shapes, dtypes, train-mode,
guard state, optimizer statics); hit/miss counts are exported through
``profiler.counters()``.

Fault tolerance stays inside the compiled step: planned ``grad``-site
faults are spliced in as per-parameter poison scalars
(``fault.grad_poison``), and the non-finite guard's skip is a
``jnp.where`` that keeps the old weight/state — host accounting
(skipped_steps, scale backoff) reads the program's finite mask
(``fault.fused_step_guard``).

Fallback matrix (→ eager loop, counted in
``profiler.counters()['fused_step_fallbacks']``): ``MXNET_FUSED_STEP=0``,
sparse (row_sparse) gradients, kvstore-hosted or dist updates,
optimizers without a ``fused_step_fn``,
monitors/``inputs_need_grad``/``grad_req='add'`` on the Module path,
and multi-device (mesh) binds. Multi-precision low-dtype weights are
NOT a fallback: SGD/Adam/AdaGrad/RMSProp ship mp step fns (f32 master
math inside the donated program, ``scalar_dtype``-marked so traced
scalars stay f32), with in-program dynamic loss scaling on the Module
path fused into the non-finite guard's scale-backoff policy.

Donation caveat: after a fused step the OLD parameter buffers are
donated to XLA. NDArray handles tracked by the executor/trainer are
re-pointed at the new buffers, but any alias made of the raw buffer
beforehand (``detach()``, a stashed ``._data``) is stale and raises on
use. Copies (``.copy()``, ``asnumpy()``) are unaffected. Batch inputs
are NOT donated — they ride in the non-donated ``others`` block — so
the async input pipeline's device-prefetched batches
(``io/pipeline.py``), each a fresh ``device_put`` result, hand off
into the traced inputs safely.
"""
from __future__ import annotations


import numpy as _np

from .base import MXNetError

__all__ = ["fused_step_enabled", "FusedStepExecutor", "FusedUpdater",
           "pack_step_scalars", "make_apply"]


def fused_step_enabled():
    """The MXNET_FUSED_STEP gate — default ON; ``0``/``false``/``off``
    disable (re-read each step so benchmarks can toggle it)."""
    from . import envs
    return envs.get_bool("MXNET_FUSED_STEP")


def _count(name, delta=1):
    from . import profiler
    profiler.increment_counter(name, delta)


def _flat_state_handles(state):
    """Flatten one parameter's optimizer state into a list of NDArray
    handles (state layouts are None, one NDArray, or a tuple of them).
    Returns None when a leaf is not an NDArray — that layout has no
    compiled path and the caller falls back to the eager loop."""
    from .ndarray import NDArray
    if state is None:
        return []
    if isinstance(state, NDArray):
        return [state]
    if isinstance(state, (tuple, list)):
        out = []
        for s in state:
            sub = _flat_state_handles(s)
            if sub is None:
                return None
            out.extend(sub)
        return out
    return None


def _sig(arrays):
    return tuple((tuple(a.shape), str(a.dtype)) for a in arrays)


def pack_step_scalars(optimizer, indices):
    """The per-step scalar block as ONE host f32 vector
    ``[lr_0..lr_n-1, wd_0..wd_n-1, rescale, loss_scale]`` — handed to
    the compiled call as a plain numpy array so pjit's own argument
    path does the single transfer. LR schedules, per-param
    multipliers, rescale changes AND dynamic loss-scale ticks
    (slot ``2n+1``, read by the in-program AMP loss scaling) land per
    step WITHOUT recompiling. Advances the optimizer's update counters
    exactly like the eager ``_step_inputs``. Shared by the fused
    executors here and ``parallel.data_parallel.DistributedTrainer``
    (which, like the bucketed apply, reads only slots ``..2n``)."""
    from . import fault
    n = len(indices)
    block = _np.empty((2 * n + 2,), _np.float32)
    for k, i in enumerate(indices):
        lr, wd = optimizer.fused_step_scalars(i)
        block[k] = lr
        block[n + k] = wd
    block[2 * n] = optimizer.rescale_grad
    block[2 * n + 1] = fault.loss_scale()
    return block


def make_apply(step_fns, state_counts, guard, inject, unscale=False):
    """The traceable all-parameter update shared by every fused path:
    splice in poison, test finiteness, run each param's step fn, and
    (under the guard) keep the old weight/state via jnp.where for
    non-finite grads — the compiled-step equivalent of
    filter_gradient's skip. ``parallel.grad_sync.make_bucketed_apply``
    is the drop-in bucketed/sharded form of this contract.

    ``unscale=True`` (the Module path's in-program AMP loss scaling):
    gradients arrive multiplied by the dynamic loss scale (scalar slot
    ``2n+1``), so the effective rescale is ``rescale / loss_scale`` —
    the finiteness test still sees the SCALED gradient, which is the
    overflow signal the scale-backoff policy keys on."""
    import jax.numpy as jnp
    n = len(step_fns)

    def apply(grads, weights, states, scalars, poisons):
        # scalars = [lr_0..lr_n-1, wd_0..wd_n-1, rescale, loss_scale]
        rescale = scalars[2 * n]
        if unscale:
            rescale = rescale / scalars[2 * n + 1]
        new_ws, new_sts, oks = [], [], []
        si = 0
        for i, fn in enumerate(step_fns):
            g, w = grads[i], weights[i]
            st = tuple(states[si:si + state_counts[i]])
            si += state_counts[i]
            if inject:
                g = jnp.where(jnp.isfinite(poisons[i]), g,
                              jnp.full_like(g, poisons[i]
                                            .astype(g.dtype)))
            if guard:
                ok = jnp.isfinite(g).all()
            # cast the traced scalars to the grad dtype: the eager
            # ops see python floats, which JAX weak-types (f64 →
            # weak f32 → operand dtype) — an uncast strong-f32
            # scalar would PROMOTE low-precision weights to f32.
            # Multi-precision step fns declare scalar_dtype=f32
            # instead: their master math is f32 and a bf16-cast lr
            # would break bit-identity with the eager mp ops.
            sdt = getattr(fn, "scalar_dtype", None) or g.dtype
            nw, nst = fn(g, w, st, scalars[i].astype(sdt),
                         scalars[n + i].astype(sdt),
                         rescale.astype(sdt))
            if guard:
                nw = jnp.where(ok, nw, w)
                nst = tuple(jnp.where(ok, new_s, old_s)
                            for new_s, old_s in zip(nst, st))
                oks.append(ok)
            new_ws.append(nw)
            new_sts.extend(nst)
        mask = jnp.stack(oks) if oks else \
            jnp.ones((n,), jnp.bool_)
        return tuple(new_ws), tuple(new_sts), mask
    return apply


class _FusedCore:
    """Shared machinery of both fused paths: per-parameter step-fn
    roster, state flattening against the SHARED Updater (so optimizer
    state checkpoints stay interchangeable with the eager path),
    per-step scalar packing, the traced update composition with the
    in-program fault guard, and host-side guard accounting."""

    def __init__(self, optimizer, updater):
        self._opt = optimizer
        self._updater = updater
        self._cache = {}
        self._zeros = None       # cached all-clear poison vector
        self._trace_count = 0    # distinct program traces (test hook)
        self.dispatch_count = 0  # compiled-step executions

    # -- rosters ----------------------------------------------------------
    def step_fns(self, indices, weights_nd):
        """One pure update fn per parameter, or None when any parameter
        has no compiled path (→ eager fallback)."""
        fns = []
        for i, w in zip(indices, weights_nd):
            fn = self._opt.fused_step_fn(i, w)
            if fn is None:
                return None
            fns.append(fn)
        return fns

    def _states_for(self, indices, weights_nd):
        """Per-index optimizer states from the shared Updater (created
        on first use exactly like the eager path), flattened to NDArray
        handles plus a per-param count. (None, None) when a layout is
        not fusable."""
        handles, counts = [], []
        for i, w in zip(indices, weights_nd):
            if i not in self._updater.states:
                self._updater.states[i] = \
                    self._opt.create_state_multi_precision(i, w)
                self._updater.states_synced[i] = True
            flat = _flat_state_handles(self._updater.states[i])
            if flat is None:
                return None, None
            handles.extend(flat)
            counts.append(len(flat))
        return handles, tuple(counts)

    # -- per-step traced scalars -----------------------------------------
    def _scalars(self, indices):
        """See :func:`pack_step_scalars` (an explicit jnp.asarray per
        scalar group cost ~1ms/step host-side, hence the single numpy
        block)."""
        return pack_step_scalars(self._opt, indices)

    def _poisons(self, indices):
        """Planned grad-site faults for this step as a poison vector
        (nan/inf fire inside the program; raise/hang fire here, host-
        side, exactly like the eager updater). None when the plan has
        no grad site."""
        from . import fault
        p = fault.plan()
        if p is None or not p.has_site("grad"):
            return None
        return _np.asarray([fault.grad_poison() for _ in indices],
                           _np.float32)

    def _zero_poisons(self, n):
        """Cached all-clear poison vector (the common, no-plan case) —
        the traced program ignores it, but it must exist as an input."""
        z = self._zeros
        if z is None or z.shape[0] != n:
            z = _np.zeros((n,), _np.float32)
            self._zeros = z
        return z

    def _guard_active(self):
        from . import fault
        return fault.guard_policy() is not None

    def _loss_scaling_active(self, fns):
        """In-program dynamic loss scaling (Module path): on exactly
        when the scale-backoff guard owns a live scale AND the roster
        is multi-precision (scalar_dtype-marked step fns). Full-f32
        rosters keep their ogs untouched so existing trajectories stay
        bit-identical."""
        from . import fault
        return fault.guard_policy() == "scale_backoff" and \
            any(getattr(fn, "scalar_dtype", None) is not None
                for fn in fns)

    # -- traced composition ----------------------------------------------
    def _make_apply(self, step_fns, state_counts, guard, inject,
                    unscale=False):
        """See :func:`make_apply` (module-level so the data-parallel
        trainer composes the identical update without an executor)."""
        return make_apply(step_fns, state_counts, guard, inject,
                          unscale=unscale)

    # -- host-side guard accounting --------------------------------------
    def _post_step(self, indices, mask, guard):
        """When the guard is on, read the program's finite mask (the
        only host sync the fused step performs, and only in guarded
        runs): roll back update counts for skipped params (the eager
        path never advanced them) and run the per-step bookkeeping."""
        from . import metering
        # every fused dispatch is one metered training step — the
        # run-level cost account (device-seconds, flops/step via the
        # compile watch, fault-reconciled goodput) integrates here
        metering.training_step()
        if not guard:
            return
        from . import fault
        finite = _np.asarray(mask)
        for i, ok in zip(indices, finite):
            if not ok:
                self._opt.fused_rollback_count(i)
        fault.fused_step_guard(bool(finite.all()))


class FusedStepExecutor(_FusedCore):
    """Module-path fused step: the bound executor's fwd+vjp plan and
    every parameter's update rule in ONE jitted program with weights
    and optimizer state donated. ``Module.update`` drives it."""

    def __init__(self, executor, optimizer, updater, param_names):
        super().__init__(optimizer, updater)
        self._ex = executor
        self._param_names = list(param_names)
        gpos = list(executor._grad_positions)
        names = [executor.arg_names[p] for p in gpos]
        # the fused roster is the grad-carrying subset of the params —
        # frozen params (fixed_param_names -> grad_req 'null') simply
        # ride along as non-donated constants, exactly as the eager
        # loop skips their None grads. Optimizer indices stay the full-
        # roster positions so states/lr-mult tables match the eager
        # Updater's keying.
        pos = {n: i for i, n in enumerate(self._param_names)}
        if any(n not in pos for n in names):
            raise MXNetError(
                "fused step: grad-carrying args %s are not all "
                "parameters %s" % (names, self._param_names))
        self._gpos = gpos
        in_g = set(gpos)
        self._other_pos = [i for i in range(len(executor.arg_names))
                           if i not in in_g]
        self._indices = [pos[n] for n in names]

    def step(self):
        """Run one train step — forward + backward + every optimizer
        update — as a single compiled dispatch; write outputs, aux,
        new weights, and new optimizer states back into the executor
        and shared-updater handles."""
        ex = self._ex
        weights_nd = [ex.arg_arrays[p] for p in self._gpos]
        fns = self.step_fns(self._indices, weights_nd)
        if fns is None:
            raise MXNetError("fused step: optimizer has no compiled "
                             "update path")
        handles, counts = self._states_for(self._indices, weights_nd)
        if handles is None:
            raise MXNetError("fused step: optimizer state layout has "
                             "no compiled path")
        weights = tuple(w._data for w in weights_nd)
        states = tuple(h._data for h in handles)
        others = tuple(ex.arg_arrays[p]._data for p in self._other_pos)
        aux = tuple(a._data for a in ex.aux_arrays)
        rngs = ex._rngs()
        poisons = self._poisons(self._indices)
        guard = self._guard_active()
        inject = poisons is not None
        scale_loss = self._loss_scaling_active(fns)
        scalars = self._scalars(self._indices)
        fn = self._compiled(weights, states, others, aux, counts, fns,
                            guard, inject, scale_loss)
        if poisons is None:
            poisons = self._zero_poisons(len(fns))
        from . import tracing
        # this is THE "optimizer" span of a fused-mode Module step —
        # module.update()'s fused branch opens none of its own
        with tracing.span("fused_step.dispatch", phase="optimizer"):
            outs, new_aux, new_ws, new_sts, mask = fn(
                weights, states, others, aux, rngs, scalars, poisons)
        self.dispatch_count += 1
        _count("fused_step_dispatches")
        ex._store_outputs(outs)
        ex._store_aux(new_aux)
        for p, w in zip(self._gpos, new_ws):
            ex.arg_arrays[p]._set_data(w)
        for h, s in zip(handles, new_sts):
            h._set_data(s)
        self._post_step(self._indices, mask, guard)
        return ex.outputs

    def _compiled(self, weights, states, others, aux, counts, fns,
                  guard, inject, scale_loss=False):
        key = (_sig(weights), _sig(states), _sig(others), _sig(aux),
               counts, guard, inject, scale_loss,
               self._opt.fused_static_key())
        cached = self._cache.get(key)
        if cached is not None:
            _count("fused_step_cache_hits")
            return cached
        _count("fused_step_cache_misses")
        import jax.numpy as jnp
        fwdbwd, gpos, out_structs = self._ex.fused_plan()
        apply_fn = self._make_apply(fns, counts, guard, inject,
                                    unscale=scale_loss)
        n_args = len(self._ex.arg_names)
        other_pos = list(self._other_pos)
        ostructs = [(tuple(s.shape), s.dtype) for s in out_structs]
        n_params = len(fns)

        def program(weights, states, others, aux_vals, rng_keys,
                    scalars, poisons):
            self._trace_count += 1
            full = [None] * n_args
            for p, w in zip(gpos, weights):
                full[p] = w
            for p, o in zip(other_pos, others):
                full[p] = o
            ogs = tuple(jnp.ones(s, d) for s, d in ostructs)
            if scale_loss:
                # in-program dynamic loss scaling: the backward seeds
                # carry the traced loss scale (slot 2n+1), so low-
                # precision grads overflow-signal at the scale the
                # backoff policy manages; make_apply(unscale=True)
                # divides it back out of the master update
                ls = scalars[2 * n_params + 1]
                ogs = tuple(o * ls.astype(d) for o, (_, d)
                            in zip(ogs, ostructs))
            outs, new_aux, grads = fwdbwd(tuple(full), aux_vals,
                                          rng_keys, ogs)
            new_ws, new_sts, mask = apply_fn(grads, weights, states,
                                             scalars, poisons)
            return outs, new_aux, new_ws, new_sts, mask

        arg_names = self._ex.arg_names
        aux_names = self._ex.aux_names

        def describe(weights, states, others, aux_vals, rng_keys,
                     scalars, poisons):
            from .compile_watch import describe_arrays
            d = describe_arrays([arg_names[p] for p in gpos], weights)
            d.update(describe_arrays(
                ["state%d" % i for i in range(len(states))], states))
            d.update(describe_arrays(
                [arg_names[p] for p in other_pos], others))
            d.update(describe_arrays(
                ["aux:%s" % n for n in aux_names], aux_vals))
            d.update(describe_arrays(
                ["scalars", "poisons"], [scalars, poisons]))
            return d

        from . import compile_watch
        from .engine import compiler_options
        site = "fused_step:module"
        statics = (counts, guard, inject, scale_loss,
                   self._opt.fused_static_key())
        bucket = getattr(self._ex, "_cw_bucket", None)
        if bucket is not None:
            # one bucket of a shape ladder: the fused program IS this
            # bucket's compiled step — stage it under the bucket's own
            # site so site_stats("bucketing") counts the ladder and a
            # bucket switch is never storm-flagged as churn
            from .bucketing.ladder import bucket_site
            site = bucket_site(bucket)
            statics = statics + ("fused", bucket)
        fn = compile_watch.jit(
            program, site, describe=describe,
            counter="fused_step_compile_ms",
            statics=statics,
            donate_argnums=(0, 1),
            compiler_options=compiler_options(self._ex._ctx))
        self._cache[key] = fn
        return fn


class FusedUpdater(_FusedCore):
    """Gluon-Trainer-path fused update: autograd already produced the
    gradients, so the fused program is the all-parameter optimizer
    update — one donated dispatch instead of ~2·P eager launches.

    In-program sync mode (``MXNET_GRAD_OVERLAP=1`` + ``sync_mesh``):
    the update lowers through ``parallel.grad_sync`` — gradients are
    bucketed, laid out by rows a chip and constrained to the dp axis,
    the update runs on each device's row against ZeRO-1 sharded
    optimizer state in the same layout, and only the updated params
    all-gather back, once a bucket. Donation and the in-program fault guard
    are intact; every ineligibility (sparse grads, non-mesh weights,
    unfusable optimizer/state layout) falls back to the plain fused
    or eager path exactly as before."""

    def __init__(self, optimizer, updater, sync_mesh=None,
                 sync_axis="dp"):
        super().__init__(optimizer, updater)
        self._sync_mesh = sync_mesh
        self._sync_axis = sync_axis
        self._sync_plan = None
        self._sync_state = None
        self._sync_sig = None
        self._sync_failed_sig = None  # negative probe cache
        self._sync_weights = None    # last roster, for state export

    # -- sync-mode helpers ------------------------------------------------
    def _sync_eligible(self, weights_nd, grads_nd):
        """The in-program sync mode this roster's placement supports:
        ``"sync"`` when every weight and grad lives replicated on the
        sync mesh (the PR 7 bucketed path), ``"fsdp"`` when weights
        are FSDP-sharded on it (``MXNET_PARAM_SHARD=1`` and the rules
        layer placed them — the program gathers at entry and returns
        the updated params to their sharded residency), False when
        anything lives off-mesh (→ plain fused path)."""
        if self._sync_mesh is None:
            return False
        any_sharded = False
        for arr in list(weights_nd) + list(grads_nd):
            sh = getattr(arr._data, "sharding", None)
            if sh is None or getattr(sh, "mesh", None) is None:
                return False
            if sh.mesh != self._sync_mesh:
                return False
            if not arr._data.is_fully_replicated:
                any_sharded = True
        if not any_sharded:
            return "sync"
        # sharded residency is itself the opt-in: only shard_params /
        # apply_param_sharding / the rules layer ever place weights
        # non-replicated, so route them through the fsdp program (the
        # only update that returns them to their shards) regardless
        # of the env gate's current state
        return "fsdp"

    def _sync_setup(self, indices, weights_nd):
        """(Re)build the bucket plan + sharded state when the roster
        changes; seed state from any per-param Updater states (the
        load_states interchange), consuming them so the replicated
        copies do not defeat the 1/N layout. None → no sync path."""
        from .parallel import grad_sync
        sig = tuple((tuple(w.shape), str(w.dtype), i)
                    for i, w in zip(indices, weights_nd))
        if sig == self._sync_sig and self._sync_state is not None:
            self._sync_weights = list(weights_nd)
            return self._sync_plan, self._sync_state
        if sig == self._sync_failed_sig:
            # this roster already failed the layout probe — don't pay
            # the plan rebuild + eager state allocations every step
            return None
        if self._sync_state is not None:
            # roster changed: the live moments are in the OLD sharded
            # flats — materialize them back first so the re-seed below
            # picks them up instead of silently restarting from zeros
            self.export_states_to_updater()
        plan = grad_sync.GradSyncPlan(
            [w.shape for w in weights_nd],
            [w.dtype for w in weights_nd],
            axis_size=int(self._sync_mesh.devices.size))
        state = grad_sync.ShardedOptState(plan, self._sync_mesh,
                                          self._sync_axis)
        if not state.probe(self._opt, indices, weights_nd):
            self._sync_failed_sig = sig
            return None
        seed = {}
        for pos, i in enumerate(indices):
            st = self._updater.states.pop(i, None)
            self._updater.states_synced.pop(i, None)
            flat = _flat_state_handles(st)
            if flat:
                seed[pos] = [_np.asarray(h._data) for h in flat]
        if seed:
            # seed_per_param builds the full flats itself — ensure()
            # first would allocate sharded zeros only to discard them
            state.seed_per_param(seed)
        else:
            state.ensure()
        self._sync_plan, self._sync_state = plan, state
        self._sync_sig = sig
        self._sync_weights = list(weights_nd)
        return plan, state

    def invalidate_sync(self):
        """Force the next update to rebuild + re-seed the sharded
        state (Trainer.load_states just replaced the Updater's)."""
        self._sync_sig = None
        self._sync_state = None
        self._sync_failed_sig = None

    def export_states_to_updater(self):
        """Materialize the flat-sharded state back into the shared
        Updater's per-param layout (``Trainer.save_states`` pickles
        that), keeping .states files interchangeable with every
        non-sync run."""
        if self._sync_state is None or self._sync_weights is None:
            return
        import jax.numpy as jnp
        indices = [i for (_, _, i) in self._sync_sig] \
            if self._sync_sig else []
        shapes = {pos: tuple(w.shape)
                  for pos, w in enumerate(self._sync_weights)}
        per_param = self._sync_state.export_per_param(shapes)
        for pos, i in enumerate(indices):
            template = self._opt.create_state_multi_precision(
                i, self._sync_weights[pos])
            flat = _flat_state_handles(template)
            vals = per_param.get(pos)
            if flat is None or vals is None:
                continue
            for h, v in zip(flat, vals):
                h._set_data(jnp.asarray(v))
            self._updater.states[i] = template
            self._updater.states_synced[i] = True

    def _update_sync(self, items, indices, weights_nd, fns,
                     mode="sync"):
        """The bucketed reduce-scatter + sharded-update dispatch
        (``mode="fsdp"``: weights arrive FSDP-sharded and return to
        that residency). Returns True when it ran; None → caller takes
        the plain fused path."""
        from .parallel import grad_sync
        built = self._sync_setup(indices, weights_nd)
        if built is None:
            return None
        plan, sync_state = built
        states = sync_state.ensure()
        weights = tuple(w._data for w in weights_nd)
        grads = tuple(g._data for _, _, g in items)
        poisons = self._poisons(indices)
        guard = self._guard_active()
        inject = poisons is not None
        scalars = self._scalars(indices)
        fn = self._compiled_sync(grads, weights, states, plan, fns,
                                 guard, inject, tuple(indices),
                                 mode=mode)
        if poisons is None:
            poisons = self._zero_poisons(len(fns))
        from . import telemetry, tracing
        with tracing.span("fused_step.dispatch", phase="optimizer"):
            new_ws, new_sts, mask = fn(grads, weights, states, scalars,
                                       poisons)
        self.dispatch_count += 1
        _count("fused_step_dispatches")
        _count("fused_step_sync_dispatches")
        grad_sync.account_in_program_sync(plan, mesh=self._sync_mesh,
                                          axis=self._sync_axis)
        for w_nd, w in zip(weights_nd, new_ws):
            w_nd._set_data(w)
        sync_state.store(new_sts)
        self._sync_weights = list(weights_nd)
        if telemetry.enabled():
            # the split is fixed for a given roster+mode — walk the
            # shards once, not every step
            bd_key = (tuple(indices), mode)
            if getattr(self, "_mem_bd_key", None) != bd_key:
                sharded = replicated = 0
                by_dtype = {}
                for w_nd in weights_nd:
                    v = w_nd._data
                    shards = getattr(v, "addressable_shards", None)
                    b = int(shards[0].data.nbytes) if shards \
                        else int(getattr(v, "nbytes", 0))
                    if v.is_fully_replicated:
                        replicated += b
                    else:
                        sharded += b
                    dt = str(getattr(v, "dtype", "?"))
                    by_dtype[dt] = by_dtype.get(dt, 0) + b
                self._mem_bd_key = bd_key
                self._mem_bd = {
                    "params_sharded": sharded,
                    "params_replicated": replicated,
                    "opt_state": sync_state.state_bytes_per_device()}
                if len(by_dtype) > 1:
                    # mixed precision: the per-dtype split is what a
                    # capacity planner actually reasons about (bf16
                    # weights vs the fp32 masters hiding in opt_state)
                    for dt, b in sorted(by_dtype.items()):
                        self._mem_bd["params_" + dt] = b
            telemetry.memory_breakdown(**self._mem_bd)
        self._post_step(indices, mask, guard)
        return True

    def _compiled_sync(self, grads, weights, states, plan, fns, guard,
                       inject, idx_key, mode="sync"):
        shard_key = tuple(str(getattr(a, "sharding", None))
                          for a in tuple(weights) + tuple(grads)) \
            if mode == "fsdp" else None
        key = ("sync", mode, _sig(grads), _sig(weights), _sig(states),
               plan.signature(), guard, inject, idx_key, shard_key,
               self._opt.fused_static_key())
        cached = self._cache.get(key)
        if cached is not None:
            _count("fused_step_cache_hits")
            return cached
        _count("fused_step_cache_misses")
        from .parallel import grad_sync
        apply_fn = grad_sync.make_bucketed_apply(
            fns, self._sync_state.n_slots, plan, self._sync_mesh,
            self._sync_axis, guard, inject)

        if mode == "fsdp":
            # FSDP: weights (and possibly grads) arrive sharded per
            # the rules layer. Gather both to replicated at program
            # entry — the partitioner's just-in-time all-gather, exact
            # — run the IDENTICAL bucketed composition, and constrain
            # the updated params back to each input's own sharding (a
            # local slice of the gathered update, not a second
            # collective), so the 1/N residency survives the step.
            import jax
            from jax.sharding import NamedSharding, PartitionSpec as P
            wsc = jax.lax.with_sharding_constraint
            rep = NamedSharding(self._sync_mesh, P())
            out_shardings = tuple(a.sharding for a in weights)
            inner = apply_fn

            def apply_fn(grads, weights, states, scalars, poisons):
                grads = tuple(wsc(g, rep) for g in grads)
                weights = tuple(wsc(w, rep) for w in weights)
                new_ws, new_sts, mask = inner(grads, weights, states,
                                              scalars, poisons)
                new_ws = tuple(wsc(w, sh) for w, sh
                               in zip(new_ws, out_shardings))
                return new_ws, new_sts, mask

        def program(grads, weights, states, scalars, poisons):
            self._trace_count += 1
            return apply_fn(grads, weights, states, scalars, poisons)

        def describe(grads, weights, states, scalars, poisons):
            from .compile_watch import describe_arrays
            d = describe_arrays(
                ["grad:param%d" % i for i in idx_key], grads)
            d.update(describe_arrays(
                ["param%d" % i for i in idx_key], weights))
            d.update(describe_arrays(
                ["state%d" % i for i in range(len(states))], states))
            d.update(describe_arrays(
                ["scalars", "poisons"], [scalars, poisons]))
            return d

        from . import compile_watch
        from .engine import compiler_options
        # a replicated↔sharded flip is a NEW program (fused_step:fsdp),
        # never a recompile-storm cause against trainer_sync
        site = "fused_step:fsdp" if mode == "fsdp" \
            else "fused_step:trainer_sync"
        fn = compile_watch.jit(
            program, site, describe=describe,
            counter="fused_step_compile_ms",
            statics=(plan.signature(), guard, inject, idx_key,
                     shard_key, self._opt.fused_static_key()),
            donate_argnums=(1, 2),
            compiler_options=compiler_options())
        self._cache[key] = fn
        return fn

    def update(self, items):
        """``items``: ordered ``[(index, weight_nd, grad_nd)]`` for the
        parameters being updated this step. Returns True when the fused
        program ran; False (nothing modified) → caller falls back to
        the eager per-parameter loop."""
        indices = [i for i, _, _ in items]
        weights_nd = [w for _, w, _ in items]
        fns = self.step_fns(indices, weights_nd)
        if fns is None:
            _count("fused_step_fallbacks")
            return False
        # multi-precision rosters (scalar_dtype-marked fns) carry
        # mixed-dtype [.., master] state layouts the flat-sharded
        # bucket planner does not model — run them through the plain
        # fused program (still ONE donated dispatch, no fallback)
        mp_roster = any(getattr(fn, "scalar_dtype", None) is not None
                        for fn in fns)
        mode = self._sync_eligible(weights_nd,
                                   [g for _, _, g in items]) \
            if self._sync_mesh is not None and not mp_roster else False
        if mode:
            ran = self._update_sync(items, indices, weights_nd, fns,
                                    mode)
            if ran is not None:
                return ran
        if self._sync_state is not None:
            # leaving the sync path (roster/placement ineligible this
            # step): the live moments are in the sharded flats, not the
            # Updater — put them back so the plain/eager update
            # continues the same trajectory, and force a re-seed if
            # sync mode resumes later
            self.export_states_to_updater()
            self.invalidate_sync()
        handles, counts = self._states_for(indices, weights_nd)
        if handles is None:
            _count("fused_step_fallbacks")
            return False
        weights = tuple(w._data for w in weights_nd)
        grads = tuple(g._data for _, _, g in items)
        states = tuple(h._data for h in handles)
        poisons = self._poisons(indices)
        guard = self._guard_active()
        inject = poisons is not None
        scalars = self._scalars(indices)
        fn = self._compiled(grads, weights, states, counts, fns, guard,
                            inject, tuple(indices))
        if poisons is None:
            poisons = self._zero_poisons(len(fns))
        from . import tracing
        with tracing.span("fused_step.dispatch", phase="optimizer"):
            new_ws, new_sts, mask = fn(grads, weights, states, scalars,
                                       poisons)
        self.dispatch_count += 1
        _count("fused_step_dispatches")
        for w_nd, w in zip(weights_nd, new_ws):
            w_nd._set_data(w)
        for h, s in zip(handles, new_sts):
            h._set_data(s)
        self._post_step(indices, mask, guard)
        return True

    def _compiled(self, grads, weights, states, counts, fns, guard,
                  inject, idx_key):
        key = (_sig(grads), _sig(weights), _sig(states), counts, guard,
               inject, idx_key, self._opt.fused_static_key())
        cached = self._cache.get(key)
        if cached is not None:
            _count("fused_step_cache_hits")
            return cached
        _count("fused_step_cache_misses")
        apply_fn = self._make_apply(fns, counts, guard, inject)

        def program(grads, weights, states, scalars, poisons):
            self._trace_count += 1
            return apply_fn(grads, weights, states, scalars, poisons)

        def describe(grads, weights, states, scalars, poisons):
            from .compile_watch import describe_arrays
            d = describe_arrays(
                ["grad:param%d" % i for i in idx_key], grads)
            d.update(describe_arrays(
                ["param%d" % i for i in idx_key], weights))
            d.update(describe_arrays(
                ["state%d" % i for i in range(len(states))], states))
            d.update(describe_arrays(
                ["scalars", "poisons"], [scalars, poisons]))
            return d

        from . import compile_watch
        from .engine import compiler_options
        fn = compile_watch.jit(
            program, "fused_step:trainer", describe=describe,
            counter="fused_step_compile_ms",
            statics=(counts, guard, inject, idx_key,
                     self._opt.fused_static_key()),
            donate_argnums=(1, 2),
            compiler_options=compiler_options())
        self._cache[key] = fn
        return fn
