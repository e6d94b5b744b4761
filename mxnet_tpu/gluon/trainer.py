"""Gluon Trainer (API parity: python/mxnet/gluon/trainer.py).

TPU-native: every Parameter is ONE (mesh-shardable) array, so the
single-process multi-device reduce the reference performs across GPU
copies is unnecessary by construction — ``allreduce_grads`` only
becomes a real collective when a dist/tpu kvstore spans processes.
Own structure: the parameter roster is validated once into an indexed
list; kvstore resolution lives in a single ``_resolve_kvstore`` step;
the update loop separates its skip conditions from the sparse-grad
fast path.

Fault tolerance: every update funnels through the shared ``Updater``,
so the non-finite gradient guard and planned ``grad`` faults
(``mxnet_tpu.fault``) apply here exactly as in Module; dist pushes in
``allreduce_grads`` inherit the kvstore's retry/timeout guarding, and
``step`` unscales by the dynamic loss scale under the scale_backoff
policy.
"""
from __future__ import annotations

from ..base import MXNetError
from .. import optimizer as opt
from .parameter import Parameter, ParameterDict

__all__ = ["Trainer"]


def _as_param_list(params):
    """Normalize the constructor's params argument to an ordered list
    of Parameters, rejecting anything else loudly."""
    if isinstance(params, (dict, ParameterDict)):
        params = list(params.values())
    if not isinstance(params, (list, tuple)):
        raise ValueError(
            "First argument must be a list or dict of Parameters, "
            "got %s." % (type(params)))
    for p in params:
        if not isinstance(p, Parameter):
            raise ValueError(
                "First argument must be a list or dict of Parameters, "
                "got list of %s." % (type(p)))
    return list(params)


class Trainer:
    """Applies an Optimizer to a set of Parameters after backward
    (reference: trainer.py:27)."""

    def __init__(self, params, optimizer, optimizer_params=None,
                 kvstore='device', compression_params=None,
                 update_on_kvstore=None):
        self._params = _as_param_list(params)
        self._param2idx = {p.name: i
                           for i, p in enumerate(self._params)}
        self._compression_params = compression_params
        opts = dict(optimizer_params or {})
        self._scale = float(opts.get('rescale_grad', 1.0))
        self._contexts = self._shared_contexts()
        self._fused_updater = None
        self._setup_optimizer(optimizer, opts)
        self._kvstore_params = {'kvstore': kvstore,
                                'update_on_kvstore': update_on_kvstore}
        self._reset_kvstore()

    # -- wiring -----------------------------------------------------------
    def _shared_contexts(self):
        for p in self._params:
            try:
                return p.list_ctx()
            except Exception:
                continue
        return []

    def _setup_optimizer(self, optimizer, opts):
        roster = dict(enumerate(self._params))
        if isinstance(optimizer, opt.Optimizer):
            if opts:
                raise AssertionError(
                    "optimizer_params must be None if optimizer is an "
                    "instance of Optimizer instead of str")
            self._optimizer = optimizer
            optimizer.param_dict = roster
        else:
            self._optimizer = opt.create(optimizer, param_dict=roster,
                                         **opts)
        self._updaters = [opt.get_updater(self._optimizer)]

    def _reset_kvstore(self):
        self._kv_initialized = False
        self._kvstore = None
        self._update_on_kvstore = None
        self._params_to_init = list(self._params)

    def _resolve_kvstore(self):
        """Pick the kvstore backend (reference: trainer.py:169). A
        plain local/device name resolves to NO kvstore — one logical
        sharded array needs no cross-copy reduce; dist/tpu names make
        a real multi-process store."""
        spec = self._kvstore_params['kvstore']
        from .. import kvstore as kvs
        if isinstance(spec, kvs.KVStore):
            return spec
        if isinstance(spec, str) and spec and \
                ('dist' in spec or 'tpu' in spec):
            return kvs.create(spec)
        return None

    def _init_kvstore(self):
        kv = self._resolve_kvstore()
        if kv is not None:
            if self._compression_params:
                kv.set_gradient_compression(self._compression_params)
            for i, param in enumerate(self._params):
                if param._data is not None:
                    kv.init(i, param.data())
        self._kvstore = kv
        wanted = self._kvstore_params['update_on_kvstore']
        self._update_on_kvstore = bool(wanted) if wanted is not None \
            else False
        if kv is not None and self._update_on_kvstore:
            kv.set_optimizer(self._optimizer)
        self._kv_initialized = True
        self._params_to_init = [p for p in self._params_to_init
                                if p._deferred_init]

    # -- properties -------------------------------------------------------
    @property
    def learning_rate(self):
        sched = self._optimizer.lr_scheduler
        return self._optimizer.lr if sched is None \
            else sched(self._optimizer.num_update)

    @property
    def optimizer(self):
        return self._optimizer

    def set_learning_rate(self, lr):
        self._optimizer.set_learning_rate(lr)

    # -- the step ---------------------------------------------------------
    def allreduce_grads(self):
        """Cross-worker gradient reduction (reference: trainer.py:331).

        With ``MXNET_GRAD_OVERLAP=1`` the dense-gradient exchange goes
        through ``parallel.grad_sync.bucketed_kvstore_sync`` — one
        concatenated push/pull per size-capped bucket instead of one
        per key (exact: concatenation and the store's elementwise sum
        commute). Hosted updates (``update_on_kvstore``) keep the
        per-key loop: the server's optimizer runs per key."""
        if not self._kv_initialized:
            self._init_kvstore()
        if self._kvstore is None:
            return
        if not self._update_on_kvstore:
            from ..parallel import grad_sync
            if grad_sync.overlap_enabled():
                items = [(i, p.grad()) for i, p in
                         enumerate(self._params) if p.grad_req != 'null']
                if grad_sync.bucketed_kvstore_sync(self._kvstore, items):
                    return
        for i, param in enumerate(self._params):
            if param.grad_req != 'null':
                self._kvstore.push(i, param.grad())
                if not self._update_on_kvstore:
                    self._kvstore.pull(i, param.grad())

    def _step_rescale(self, batch_size):
        """1/batch_size rescale, additionally unscaling by the dynamic
        loss scale when the scale_backoff guard is active (the user
        multiplies the loss by ``fault.loss_scale()`` before backward;
        the updater sees unit-scale gradients and the guard's NaN/Inf
        skip + backoff handles overflowed steps). Straight 1/batch when
        the guard is off."""
        from .. import fault
        scale = self._scale / batch_size
        if fault.guard_policy() == 'scale_backoff':
            scale /= fault.loss_scale()
        self._sync_rescale(scale)

    def step(self, batch_size, ignore_stale_grad=False):
        """allreduce + update, rescaled by batch size
        (reference: trainer.py:302).

        Telemetry: each call is one step boundary (tick mode — the
        step spans from the previous ``step``), with the cross-worker
        reduce under the ``sync`` phase and the parameter update under
        ``optimizer`` (README "Observability")."""
        from .. import telemetry, tracing
        telemetry.maybe_start(meta={"source": "gluon.Trainer"})
        with tracing.span("trainer.step"):
            self._step_rescale(batch_size)
            if not self._kv_initialized:
                self._init_kvstore()
            if self._kvstore is not None:
                with tracing.span("step.sync", phase="sync"):
                    self.allreduce_grads()
            with tracing.span("step.optimizer", phase="optimizer"):
                self._apply_updates(ignore_stale_grad)
        telemetry.step_tick(samples=batch_size)

    def update(self, batch_size, ignore_stale_grad=False):
        """Update only — the caller already ran allreduce_grads
        (reference: trainer.py:363)."""
        from .. import telemetry, tracing
        telemetry.maybe_start(meta={"source": "gluon.Trainer"})
        with tracing.span("trainer.step"):
            if not self._kv_initialized:
                self._init_kvstore()
            if self._kvstore and self._update_on_kvstore:
                raise AssertionError(
                    'update() when parameters are updated on kvstore '
                    'is not supported. Try setting `update_on_kvstore` '
                    'to False when creating trainer.')
            self._step_rescale(batch_size)
            with tracing.span("step.optimizer", phase="optimizer"):
                self._apply_updates(ignore_stale_grad)
        telemetry.step_tick(samples=batch_size)

    def _sync_rescale(self, scale):
        if self._optimizer.rescale_grad != scale:
            self._optimizer.rescale_grad = scale

    @staticmethod
    def _stale(param):
        return not param._data._fresh_grad

    def _raise_stale(self, param):
        raise UserWarning(
            "Gradient of Parameter `%s` on context %s has not been "
            "updated by backward since last `step`. This could mean a "
            "bug in your model that made it only use a subset of the "
            "Parameters (Blocks) for this iteration. If you are "
            "intentionally only using a subset, call step with "
            "ignore_stale_grad=True to suppress this warning and skip "
            "updating of Parameters with stale gradient"
            % (param.name, str(param.list_ctx()[0])))

    @staticmethod
    def _to_row_sparse(param, grad):
        """Build the row_sparse gradient view from the row ids the
        forward recorded (true touched rows — keeps rows whose grad is
        exactly zero and avoids scanning the dense grad); falls back to
        a non-zero-row scan when nothing was stashed."""
        ids = getattr(param, '_sparse_row_ids', None)
        if ids is None:
            return grad.tostype('row_sparse')
        import numpy as _np
        from ..ndarray import array as _nd_array
        from ..ndarray.sparse import RowSparseNDArray
        param._sparse_row_ids = None
        rows = _np.unique(_np.concatenate(
            [i.asnumpy().astype(_np.int64).ravel() for i in ids]))
        rows_nd = _nd_array(rows, ctx=grad.context, dtype='int64')
        return RowSparseNDArray(grad.take(rows_nd), rows_nd, grad.shape,
                                ctx=grad.context)

    def _sync_mesh(self):
        """The mesh the in-program bucketed sync would run over: the
        params' own NamedSharding mesh when it has a ``dp`` axis and
        ``MXNET_GRAD_OVERLAP=1`` — or when any param lives
        FSDP-sharded on it (a residency only the rules layer places,
        so it is itself the opt-in): those route the update through
        the same machinery (the ``fused_step:fsdp`` program) so they
        return to their sharded residency — None otherwise (plain
        fused update)."""
        from ..parallel import grad_sync
        mesh = None
        any_sharded = False
        for p in self._params:
            if p._data is None:
                continue
            sharding = getattr(p._data._data, "sharding", None)
            m = getattr(sharding, "mesh", None)
            if mesh is None:
                if m is None or "dp" not in getattr(m, "axis_names",
                                                    ()):
                    return None
                mesh = m if m.devices.size > 1 else None
                if mesh is None:
                    return None
            if not p._data._data.is_fully_replicated:
                any_sharded = True
                break
        # a sharded residency IS the opt-in (apply_param_sharding /
        # shard_params placed it deliberately, gate or no gate) — the
        # sync machinery is what returns updated params to their
        # shards; replicated rosters keep the plain fused update
        # unless the overlap gate asks for bucketing
        if any_sharded:
            return mesh
        return mesh if grad_sync.overlap_enabled() else None

    def _get_fused(self):
        """The fused all-parameter update program (fused_step.py): one
        donated XLA dispatch per step instead of ~2·P eager launches.
        None when MXNET_FUSED_STEP=0; the FusedUpdater itself reports
        False (→ eager loop) for optimizers without a compiled path.
        On a dp mesh with ``MXNET_GRAD_OVERLAP=1`` the updater carries
        the sync mesh: the update lowers through the bucketed
        reduce-scatter + ZeRO-1 sharded-state composition of
        ``parallel.grad_sync``."""
        from ..fused_step import FusedUpdater, fused_step_enabled
        if not fused_step_enabled():
            if self._fused_updater is not None:
                # the gate can be flipped off mid-run: the live
                # moments may sit in the updater's ZeRO-sharded flats
                # — put them back before the eager loop reads the
                # shared Updater, or momentum/Adam state resets
                self._fused_updater.export_states_to_updater()
                self._fused_updater.invalidate_sync()
            return None
        mesh = self._sync_mesh()
        fused = self._fused_updater
        if fused is not None and fused._opt is self._optimizer and \
                fused._updater is self._updaters[0] and \
                fused._sync_mesh == mesh:
            return fused
        if fused is not None:
            # don't strand ZeRO-sharded state in a discarded updater —
            # put it back into the shared Updater's per-param layout
            fused.export_states_to_updater()
        self._fused_updater = FusedUpdater(self._optimizer,
                                           self._updaters[0],
                                           sync_mesh=mesh)
        return self._fused_updater

    def _apply_updates(self, ignore_stale_grad=False):
        updater = self._updaters[0]
        hosted = self._kvstore is not None and self._update_on_kvstore
        work, sparse = [], False
        for i, param in enumerate(self._params):
            if param.grad_req == 'null' or param._data is None:
                continue
            if self._stale(param):
                if not ignore_stale_grad:
                    self._raise_stale(param)
                continue
            if hosted:
                continue        # kvstore ran the update in allreduce
            work.append((i, param))
            sparse = sparse or param._grad_stype == 'row_sparse'
        fused_done = False
        if work and not sparse:
            fused = self._get_fused()
            if fused is not None:
                fused_done = fused.update(
                    [(i, p.data(), p.grad()) for i, p in work])
        elif work and sparse:
            from ..fused_step import fused_step_enabled
            if fused_step_enabled():
                from .. import profiler
                profiler.increment_counter("fused_step_fallbacks")
        for i, param in work:
            if not fused_done:
                grad = param.grad()
                if param._grad_stype == 'row_sparse':
                    grad = self._to_row_sparse(param, grad)
                updater(i, grad, param.data())
            param._data._fresh_grad = False
        # drop row-id stashes on EVERY param (also frozen/stale-skipped
        # ones) so forwards from this step never leak into the next
        for param in self._params:
            if getattr(param, '_sparse_row_ids', None) is not None:
                param._sparse_row_ids = None
        if hosted:
            for i, param in enumerate(self._params):
                if param.grad_req != 'null':
                    self._kvstore.pull(i, param.data())

    # legacy spelling used by older call sites
    _update = _apply_updates

    # -- optimizer-state checkpointing ------------------------------------
    def save_states(self, fname, background=False):
        """Durably write the optimizer state (tmp + fsync +
        ``os.replace`` through ``mxnet_tpu.checkpoint``, so the write
        is fault-injectable at ``ckpt_write``/``ckpt_fsync`` and a
        kill mid-save never strands a torn file). The pickle snapshot
        always happens here, on the calling thread (state buffers are
        replaced per step); ``background=True`` hands the durable
        write itself to the shared checkpoint writer thread —
        ``mxnet_tpu.checkpoint.flush_async_writes()`` blocks until it
        lands and raises on a write that failed (the deferred
        equivalent of the exception the synchronous path would have
        raised here)."""
        if self._optimizer is None:
            raise AssertionError("no optimizer to save")
        if not self._kv_initialized:
            self._init_kvstore()
        from .. import checkpoint as ckpt
        if self._update_on_kvstore and self._kvstore is not None:
            # same durable/async write as the local-updater path — the
            # kvstore only supplies the state bytes
            updater = getattr(self._kvstore, '_updater', None)
            assert updater is not None, \
                "Cannot save states for distributed training " \
                "without updater"
            payload = updater.get_states(dump_optimizer=True)
        else:
            fused = self._fused_updater
            if fused is not None:
                # materialize ZeRO-sharded flat state back into the
                # Updater's per-param layout so the .states pickle
                # stays interchangeable with every non-sync run
                fused.export_states_to_updater()
            payload = self._updaters[0].get_states(dump_optimizer=True)
        if background:
            ckpt.write_bytes_async(fname, payload)
        else:
            ckpt.atomic_write_file(fname, payload)

    def load_states(self, fname):
        if not self._kv_initialized:
            self._init_kvstore()
        if self._update_on_kvstore and self._kvstore is not None:
            self._kvstore.load_optimizer_states(fname)
            self._optimizer = self._kvstore._updater.optimizer
        else:
            with open(fname, 'rb') as src:
                blob = src.read()
            for updater in self._updaters:
                updater.set_states(blob)
                updater.optimizer = self._updaters[0].optimizer
            self._optimizer = self._updaters[0].optimizer
        self._optimizer.param_dict = dict(enumerate(self._params))
        if self._fused_updater is not None:
            # the Updater's per-param states were just replaced — the
            # next sync-mode update must re-seed its sharded flats
            self._fused_updater.invalidate_sync()
