"""Executor — binds a Symbol to devices and runs it.

Parity target: include/mxnet/executor.h + src/executor/graph_executor.cc.

TPU-native design (SURVEY §7): ``bind`` lowers the ENTIRE symbolic graph
to one jitted XLA computation. This single design move replaces the
reference's NNVM pass pipeline:
- PlanMemory/inplace/pooling  → XLA buffer assignment + donation
- AttachOpExecs + engine push per node → one compiled executable
- op bulking (BulkTrainingOpSegs)      → whole-program fusion
- InferShape pass → jax.eval_shape at trace time (+ symbol/infer hooks)
- gradient graph (pass::Gradient)      → jax.vjp over the traced program

``forward`` runs the forward executable; ``backward`` / the fused
``forward_backward`` run a forward+vjp executable (compiled once per
train/eval mode and input-shape signature; the shape-signature cache is
jax.jit's own, which is what CachedOp::SetForwardGraph re-implemented).
"""
from __future__ import annotations

from typing import Any, Dict, List, Optional, Sequence

from .base import MXNetError
from .context import Context
from . import ops as _ops

__all__ = ["Executor"]


class Executor:
    def __init__(self, symbol, ctx, args, args_grad=None, grad_req="write",
                 aux_states=None, batch_args=None, group2ctx=None,
                 cw_bucket=None):
        from .ndarray import NDArray, zeros as nd_zeros

        self._symbol = symbol
        # shape-bucketing identity: when this executor is one bucket of
        # a ladder (BucketingModule / bucketed fit), its programs stage
        # under the bucket's own compile-watch site (`bucketing:<key>`,
        # statics carry the key) so the ladder is a FIXED program set —
        # site_stats("bucketing") counts it and a bucket switch is
        # specialization, never storm churn.
        self._cw_bucket = cw_bucket
        # Multi-context bind = in-program data parallelism: ONE compiled
        # program over a 'dp' device mesh; batch args are sharded on dim
        # 0, params/aux replicated, and XLA's SPMD partitioner inserts
        # the gradient psum the reference routed through KVStore
        # (executor_group.py:281 decide_slices + kvstore_dist.h:44).
        self._ctx_arg = ctx
        if isinstance(ctx, (list, tuple)) and len(ctx) > 1:
            ctxs = [c if isinstance(c, Context) else Context(c)
                    for c in ctx]
            self._ctx = ctxs[0]
            # The reference tolerates repeated contexts (one executor
            # per list entry on the same GPU); a mesh needs distinct
            # devices, and deduping is numerically equivalent since the
            # program computes the global batch either way.
            from .parallel.mesh import dp_mesh, distinct_devices
            devices = distinct_devices(ctxs)
            self._mesh = dp_mesh(devices) if len(devices) > 1 else None
        else:
            if isinstance(ctx, (list, tuple)):
                ctx = ctx[0]
            self._ctx = ctx if isinstance(ctx, Context) else Context(ctx)
            self._mesh = None
        self._batch_args = set(batch_args or ())
        self.arg_names = symbol.list_arguments()
        self.aux_names = symbol.list_auxiliary_states()
        self.output_names = symbol.list_outputs()

        # normalize args
        if isinstance(args, dict):
            missing = [n for n in self.arg_names if n not in args]
            if missing:
                raise MXNetError("bind: missing arguments %s" % missing)
            self.arg_arrays = [args[n] for n in self.arg_names]
        else:
            args = list(args)
            if len(args) != len(self.arg_names):
                raise MXNetError(
                    "bind: expected %d args, got %d"
                    % (len(self.arg_names), len(args)))
            self.arg_arrays = args

        # grad_req normalize
        if isinstance(grad_req, str):
            self._grad_req = {n: grad_req for n in self.arg_names}
        elif isinstance(grad_req, (list, tuple)):
            self._grad_req = dict(zip(self.arg_names, grad_req))
        else:
            self._grad_req = {n: grad_req.get(n, "null")
                              for n in self.arg_names}
        if args_grad is None:
            args_grad = {}
            for n in self.arg_names:
                if self._grad_req[n] != "null":
                    self._grad_req[n] = "null"
        if isinstance(args_grad, dict):
            self.grad_arrays = [args_grad.get(n) for n in self.arg_names]
        else:
            args_grad = list(args_grad)
            self.grad_arrays = list(args_grad) + \
                [None] * (len(self.arg_names) - len(args_grad))
        for n, g in zip(self.arg_names, self.grad_arrays):
            if g is None and self._grad_req.get(n, "null") != "null":
                self._grad_req[n] = "null"

        # aux states
        if aux_states is None:
            aux_states = []
        if isinstance(aux_states, dict):
            self.aux_arrays = [aux_states[n] for n in self.aux_names]
        else:
            self.aux_arrays = list(aux_states)
        if len(self.aux_arrays) != len(self.aux_names):
            raise MXNetError("bind: expected %d aux states, got %d"
                             % (len(self.aux_names), len(self.aux_arrays)))

        self.arg_dict = dict(zip(self.arg_names, self.arg_arrays))
        self.grad_dict = {n: g for n, g in zip(self.arg_names,
                                               self.grad_arrays)}
        self.aux_dict = dict(zip(self.aux_names, self.aux_arrays))

        # FSDP (MXNET_PARAM_SHARD=1) on a mesh bind: non-batch args
        # rule-resolve to sharded placements (parallel.sharding_rules)
        # — _dp_place keeps them resident at 1/N and the compiled
        # programs gather them at entry. NDArray handles keep their
        # logical shapes, so a param the rules would need to PAD stays
        # replicated here (with a one-time telemetry note naming it);
        # the padded-storage form lives in DistributedTrainer.
        self._param_shard_plans = None
        if self._mesh is not None:
            from .parallel.sharding_rules import (ShardingRules,
                                                  param_shard_enabled)
            if param_shard_enabled():
                rules = ShardingRules(self._mesh)
                plans = {}
                for n, arr in zip(self.arg_names, self.arg_arrays):
                    if n in self._batch_args:
                        continue
                    pl = rules.plan(n, arr.shape)
                    if not pl.sharded:
                        continue
                    if pl.padded:
                        from . import telemetry
                        telemetry.note("param_shard_fallback:%s" % n)
                        continue
                    plans[n] = pl
                self._param_shard_plans = plans or None

        # persistent output buffers
        self.outputs = [None] * len(self._symbol._outputs)
        self._fns: Dict[Any, Any] = {}
        self._rng_count = sum(
            1 for n in symbol._topo_nodes()
            if n.op is not None and n.op.needs_rng)
        self._monitor_callback = None
        self._build_plan()
        # ctx_group placement: partition into device-pinned segment
        # programs (placement.py; ref graph_executor.cc:907
        # AssignContext) when group2ctx names any group the graph uses
        self._grouped = None
        if group2ctx:
            has_groups = any(
                n._extra_attrs.get("ctx_group") in group2ctx
                for n in getattr(self, "_plan_nodes", []))
            if has_groups:
                if self._mesh is not None:
                    raise MXNetError(
                        "group2ctx placement cannot be combined with a "
                        "multi-context data-parallel bind")
                from .placement import GroupedProgram
                self._grouped = GroupedProgram(self, group2ctx)

    # -- graph plan ------------------------------------------------------
    def _build_plan(self):
        nodes = self._symbol._topo_nodes()
        self._nodes = nodes
        arg_pos = {n: i for i, n in enumerate(self.arg_names)}
        aux_pos = {n: i for i, n in enumerate(self.aux_names)}
        self._plan = []
        node_slot = {}
        slot = 0
        rng_slot = 0
        for nd_ in nodes:
            if nd_.is_variable():
                if nd_.name in aux_pos:
                    src = ("aux", aux_pos[nd_.name])
                elif nd_.name in arg_pos:
                    src = ("arg", arg_pos[nd_.name])
                else:
                    raise MXNetError("unbound variable %s" % nd_.name)
                node_slot[id(nd_)] = ("var", src)
            else:
                nattrs = _ops.normalize_attrs(nd_.op, nd_.attrs)
                bindings = []
                for (s, i) in nd_.inputs:
                    kind, ref = node_slot[id(s)]
                    if kind == "var":
                        bindings.append(ref)
                    else:
                        bindings.append(("res", ref, i))
                rs = None
                if nd_.op.needs_rng:
                    rs = rng_slot
                    rng_slot += 1
                # aux writeback mapping: mutable input idx → aux slot
                aux_wb = []
                for mi in nd_.op.mutable_inputs:
                    if mi < len(nd_.inputs):
                        src, _ = nd_.inputs[mi]
                        if src.is_variable() and src.name in aux_pos:
                            aux_wb.append(aux_pos[src.name])
                        else:
                            aux_wb.append(None)
                self._plan_names = getattr(self, "_plan_names", [])
                self._plan_names.append(nd_.name)
                self._plan_nodes = getattr(self, "_plan_nodes", [])
                self._plan_nodes.append(nd_)
                self._plan.append((nd_.op, nattrs, tuple(bindings), rs,
                                   aux_wb, slot))
                node_slot[id(nd_)] = ("res", slot)
                slot += 1
        self._head_refs = []
        for (n, i) in self._symbol._outputs:
            kind, ref = node_slot[id(n)]
            if kind == "var":
                self._head_refs.append((ref[0], ref[1], 0))
            else:
                self._head_refs.append(("res", ref, i))
        self._grad_positions = [i for i, n in enumerate(self.arg_names)
                                if self._grad_req.get(n, "null") != "null"]
        self._plan_bias_defer()

    def _plan_bias_defer(self):
        """Peephole: Convolution-with-bias whose SOLE consumer is a
        train-mode channel-axis BatchNorm.

        Normalization makes the conv bias a no-op on the normalized
        output: BN subtracts the batch mean, which contains the bias, so
        ``BN(conv(x)+b)`` ≡ ``BN(conv(x))`` with the batch/running means
        shifted by exactly ``b`` (variance is shift-invariant, and the
        bias gradient is the per-channel sum of BN's input gradient,
        which is identically zero). XLA cannot discover this algebra, so
        without the rewrite every train step pays a full HBM pass per
        biased conv to reduce a gradient that is mathematically zero —
        ~10% of a ResNet-50 train step (the model zoo's BottleneckV1
        keeps the reference's biased 1x1 convs,
        ref python/mxnet/gluon/model_zoo/vision/resnet.py:108).

        The compiled train program runs the conv biasless and adds the
        bias back into the BatchNorm mean outputs (head mean when
        ``output_mean_var``, and the ``moving_mean`` writeback), keeping
        checkpoint/inference semantics identical. Eval-mode programs are
        untouched — with running stats the bias is live.
        """
        consumers: Dict[tuple, list] = {}
        for pi, (op, nattrs, bindings, rs, aux_wb, slot) \
                in enumerate(self._plan):
            for b in bindings:
                if b[0] == "res":
                    consumers.setdefault((b[1], b[2]), []).append(pi)
        for h in self._head_refs:
            if h[0] == "res":
                consumers.setdefault((h[1], h[2]), []).append("head")
        self._bias_defer = {}
        for pi, (op, nattrs, bindings, rs, aux_wb, slot) \
                in enumerate(self._plan):
            if op.name != "Convolution" or bool(nattrs.get("no_bias")) \
                    or len(bindings) != 3:
                continue
            cons = consumers.get((slot, 0), [])
            if len(cons) != 1 or cons[0] == "head":
                continue
            bn_pi = cons[0]
            bn_op, bn_attrs, bn_bind, _, _, _ = self._plan[bn_pi]
            if bn_op.name != "BatchNorm" \
                    or int(bn_attrs.get("axis", 1)) != 1 \
                    or bool(bn_attrs.get("use_global_stats", False)) \
                    or bn_bind[0] != ("res", slot, 0):
                continue
            self._bias_defer[pi] = (bn_pi, bindings[2])

    def _make_graph_fn(self, is_train, allow_rewrites=True):
        plan = self._plan
        plan_names = getattr(self, "_plan_names", [])
        head_refs = self._head_refs
        n_aux = len(self.aux_names)
        # the monitored eager path must see the model's DEFINED per-op
        # values (conv output incl. bias), not the rewritten program's
        bias_defer = self._bias_defer \
            if (is_train and allow_rewrites) else {}
        # BN plan-index -> (bias binding, BN momentum) for the mean
        # corrections
        bn_bias = {bn_pi: (bias_b,
                           float(self._plan[bn_pi][1].get("momentum", 0.9)))
                   for bn_pi, bias_b in bias_defer.values()}
        def run(arg_vals, aux_vals, rng_keys):
            results: List[tuple] = []
            new_aux = list(aux_vals)
            def resolve(b):
                if b[0] == "arg":
                    return arg_vals[b[1]]
                if b[0] == "aux":
                    return new_aux[b[1]]
                return results[b[1]][b[2]]
            for pi, (op, nattrs, bindings, rs, aux_wb, slot) \
                    in enumerate(plan):
                if pi in bias_defer:
                    bindings = bindings[:2]
                vals = [resolve(b) for b in bindings]
                attrs = nattrs
                if pi in bias_defer:
                    attrs = dict(attrs, no_bias=True)
                if "__train__" in op.defaults:
                    attrs = dict(attrs, __train__=is_train)
                if rs is not None:
                    out = op.forward(attrs, *vals, rng=rng_keys[rs])
                else:
                    out = op.forward(attrs, *vals)
                if not isinstance(out, (tuple, list)):
                    out = (out,)
                if pi in bn_bias:
                    bias_b, bn_mom = bn_bias[pi]
                    out = self._bn_add_bias(out, resolve(bias_b), bn_mom,
                                            op.resolve_num_outputs(attrs))
                n_out = op.resolve_num_outputs(attrs)
                if getattr(self, "_tap_eager", False):
                    # per-op monitor taps: only reached on the eager
                    # interpreted debug path (_forward_monitored) —
                    # values here are concrete arrays
                    for oi in range(n_out):
                        tag = plan_names[pi] + "_output" + \
                            (str(oi) if n_out > 1 else "")
                        self._host_tap(tag, out[oi])
                results.append(tuple(out[:n_out]))
                extras = out[n_out:]
                for wb, val in zip(aux_wb, extras):
                    if wb is not None:
                        new_aux[wb] = val
            outs = []
            for h in head_refs:
                if h[0] == "arg":
                    outs.append(arg_vals[h[1]])
                elif h[0] == "aux":
                    outs.append(new_aux[h[1]])
                else:
                    outs.append(results[h[1]][h[2]])
            return tuple(outs), tuple(new_aux)

        return run

    @staticmethod
    def _bn_add_bias(out, bias, momentum, n_out):
        """Shift a BatchNorm node's mean outputs by a deferred conv
        bias (see ``_plan_bias_defer``): the head batch-mean (when
        output_mean_var) shifts by the full bias, while the moving_mean
        writeback blends ``new = momentum*old + (1-momentum)*batch_mean``
        so only the ``(1-momentum)`` share of the bias enters per step —
        the recurrence then converges to exactly ``true_mean + bias``.
        Variance is shift-invariant; the normalized output needs no
        correction. The bias is stop-gradient here: the BN core's
        custom VJP already treats the mean/var heads as
        non-differentiable (ops/nn.py _bn_train_core), so the
        un-rewritten program gives the bias no gradient through the
        mean head either — without the stop, the rewritten program
        would leak the head cotangent straight into the bias."""
        from jax import lax as _lax
        bias = _lax.stop_gradient(bias)
        out = list(out)
        if n_out == 3:
            out[1] = out[1] + bias.astype(out[1].dtype)
        out[n_out] = out[n_out] \
            + ((1.0 - momentum) * bias).astype(out[n_out].dtype)
        return tuple(out)

    def _get_fn(self, kind, is_train, raw=False):
        """The compiled (or with ``raw=True`` the traceable, unjitted)
        forward / fwdbwd program. ``raw`` is for callers composing the
        program inside their OWN jit (a scanned train loop, a pipeline
        stage): nesting the jitted form is legal but a nested jit cannot
        carry compiler options, and the raw callable traces straight
        into the outer program."""
        import jax
        if raw and self._mesh is not None:
            # the jitted form's out_shardings keep aux/grads replicated
            # on the dp mesh; a raw caller's own jit would lose that
            # invariant and later eager math would mix device sets
            raise MXNetError(
                "_get_fn(raw=True) is not supported on a multi-device "
                "bind; jit the executor's compiled fn or bind one ctx")
        key = (kind, is_train, bool(raw))
        fn = self._fns.get(key)
        if fn is not None:
            return fn
        from . import compile_watch
        from .engine import compiler_options
        copts = compiler_options(self._ctx)
        run = self._make_graph_fn(is_train)
        site = "executor:%s:%s" % (kind, "train" if is_train else "eval")
        rep = None
        statics = None
        if self._cw_bucket is not None:
            from .bucketing.ladder import bucket_site
            site = bucket_site(self._cw_bucket)
            statics = ("bucket", kind, is_train, self._cw_bucket)
        if self._mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec as P
            rep = NamedSharding(self._mesh, P())
        gather_entry = None
        if rep is not None and self._param_shard_plans:
            # FSDP entry gather: pin the sharded params to replicated
            # FIRST inside the program (the partitioner's just-in-time
            # all-gather). The fwdbwd vjp is taken over the GATHERED
            # values — the gather sits outside the differentiated
            # function, so the cotangents (and every downstream op)
            # are the identical traced computation as a replicated
            # bind. Distinct compile-watch identity: a replicated↔
            # sharded flip is a new program, not churn of this site.
            wsc = jax.lax.with_sharding_constraint
            shard_pos = frozenset(
                i for i, n in enumerate(self.arg_names)
                if n in self._param_shard_plans)
            statics = (statics or ()) + ("param_shard",)

            def gather_entry(arg_vals):
                return tuple(wsc(v, rep) if i in shard_pos else v
                             for i, v in enumerate(arg_vals))
        if kind == "fwd":
            if gather_entry is not None:
                inner_run = run

                def run(arg_vals, aux_vals, rng_keys):
                    return inner_run(gather_entry(arg_vals), aux_vals,
                                     rng_keys)
            if raw:
                fn = run
            elif rep is not None:
                # outputs auto-sharded; updated aux replicated so eager
                # math on them never mixes device sets
                fn = compile_watch.jit(
                    run, site, describe=self._cw_describe,
                    statics=statics,
                    out_shardings=(None, rep), compiler_options=copts)
            else:
                fn = compile_watch.jit(run, site,
                                       describe=self._cw_describe,
                                       statics=statics,
                                       compiler_options=copts)
        else:
            gpos = self._grad_positions

            def fwdbwd(arg_vals, aux_vals, rng_keys, out_grads):
                if gather_entry is not None:
                    # gather BEFORE the vjp: the diff variables are
                    # the full logical values, exactly as on a
                    # replicated bind
                    arg_vals = gather_entry(arg_vals)
                def f(gvals):
                    full = list(arg_vals)
                    for p, v in zip(gpos, gvals):
                        full[p] = v
                    outs, new_aux = run(tuple(full), aux_vals, rng_keys)
                    return outs, new_aux
                outs, vjp_fn, new_aux = jax.vjp(
                    f, [arg_vals[p] for p in gpos], has_aux=True)
                grads, = vjp_fn(tuple(out_grads))
                return outs, new_aux, grads

            if raw:
                fn = fwdbwd
            elif rep is not None:
                # grads replicated = the in-program allreduce
                fn = compile_watch.jit(
                    fwdbwd, site, describe=self._cw_describe,
                    statics=statics,
                    out_shardings=(None, rep, rep),
                    compiler_options=copts)
            else:
                fn = compile_watch.jit(fwdbwd, site,
                                       describe=self._cw_describe,
                                       statics=statics,
                                       compiler_options=copts)
        self._fns[key] = fn
        return fn

    def _cw_describe(self, arg_vals, aux_vals, rng_keys, out_grads=None):
        """compile_watch describe hook: name the compiled program's
        argument leaves with the symbol's own arg/aux names, so a
        recompile-cause diff says "data: f32[32,784] -> f32[48,784]"
        instead of a positional index."""
        from .compile_watch import describe_arrays
        d = describe_arrays(self.arg_names, arg_vals)
        d.update(describe_arrays(["aux:%s" % n for n in self.aux_names],
                                 aux_vals))
        if rng_keys:
            d.update(describe_arrays(
                ["rng%d" % i for i in range(len(rng_keys))], rng_keys))
        if out_grads is not None:
            d.update(describe_arrays(
                ["out_grad:%s" % n for n in self.output_names],
                out_grads))
        return d

    # -- execution -------------------------------------------------------
    def _dp_shardings(self):
        from jax.sharding import NamedSharding, PartitionSpec as P
        return (NamedSharding(self._mesh, P()),
                NamedSharding(self._mesh, P("dp")))

    def _dp_place(self, args, aux):
        """Commit persistent buffers to their mesh shardings: batch args
        split on dim 0 over 'dp', everything else replicated. The NDArray
        handles are updated in place so subsequent eager math (optimizer
        updates on weights+grads) stays within one device set."""
        import jax
        rep, shard = self._dp_shardings()
        n_dp = self._mesh.devices.size
        plans = self._param_shard_plans
        placed = []
        for name, arr, val in zip(self.arg_names, self.arg_arrays, args):
            if name in self._batch_args and val.ndim >= 1 \
                    and val.shape[0] % n_dp == 0:
                tgt = shard
            elif plans is not None and name in plans:
                # FSDP residency: the param lives as its 1/N shard
                # between dispatches; an eager update that returned a
                # differently-placed value is re-sliced here (local —
                # the value is already materialized on these devices)
                tgt = plans[name].sharding(self._mesh)
            else:
                tgt = rep
            if val.sharding != tgt:
                val = jax.device_put(val, tgt)
                arr._set_data(val)
            placed.append(val)
        placed_aux = []
        for arr, val in zip(self.aux_arrays, aux):
            if val.sharding != rep:
                val = jax.device_put(val, rep)
                arr._set_data(val)
            placed_aux.append(val)
        return tuple(placed), tuple(placed_aux)

    def _gather_inputs(self, kwargs):
        from .ndarray import NDArray
        if kwargs:
            for k, v in kwargs.items():
                if k not in self.arg_dict:
                    raise MXNetError("unknown argument %s" % k)
                if isinstance(v, NDArray):
                    self.arg_dict[k]._set_data(v._data)
                else:
                    import jax.numpy as jnp
                    self.arg_dict[k]._set_data(
                        jnp.asarray(v, dtype=self.arg_dict[k].dtype))
        args = tuple(a._data for a in self.arg_arrays)
        aux = tuple(a._data for a in self.aux_arrays)
        if self._mesh is not None:
            args, aux = self._dp_place(args, aux)
        return args, aux

    def _rngs(self):
        from . import random as _random
        keys = tuple(_random.new_key() for _ in range(self._rng_count))
        if self._mesh is not None and keys:
            import jax
            rep, _ = self._dp_shardings()
            keys = tuple(jax.device_put(k, rep) for k in keys)
        return keys

    def _store_outputs(self, outs):
        from .ndarray import NDArray
        for i, o in enumerate(outs):
            if self.outputs[i] is None:
                self.outputs[i] = NDArray(o, ctx=self._ctx)
            else:
                self.outputs[i]._set_data(o)

    def _store_aux(self, new_aux):
        for arr, val in zip(self.aux_arrays, new_aux):
            arr._set_data(val)

    def forward(self, is_train=False, **kwargs):
        args, aux = self._gather_inputs(kwargs)
        rngs = self._rngs()
        self._last_rngs = rngs  # backward() must replay this draw
        if self._monitor_callback is not None and \
                getattr(self, "_monitor_all", False):
            # per-op monitoring runs the plan EAGERLY (interpreted,
            # like the reference's NaiveEngine debug mode) so every
            # intermediate can be tapped without host callbacks inside
            # compiled code
            self._tap_eager = True
            try:
                run = self._make_graph_fn(bool(is_train),
                                          allow_rewrites=False)
                outs, new_aux = run(args, aux, rngs)
            finally:
                self._tap_eager = False
            self._store_outputs(outs)
            if is_train:
                self._store_aux(new_aux)
            return self.outputs
        if self._grouped is not None:
            outs, new_aux = self._grouped.forward(args, aux, rngs,
                                                  bool(is_train))
        else:
            fn = self._get_fn("fwd", bool(is_train))
            outs, new_aux = fn(args, aux, rngs)
        self._store_outputs(outs)
        if is_train:
            self._store_aux(new_aux)
        if self._monitor_callback is not None:
            self._run_monitor()
        return self.outputs

    def backward(self, out_grads=None, is_train=True):
        self.forward_backward(out_grads=out_grads, is_train=is_train,
                              _refresh_outputs=True, _reuse_rngs=True)

    def forward_backward(self, out_grads=None, is_train=True,
                         _refresh_outputs=True, _reuse_rngs=False,
                         **kwargs):
        """Fused forward+backward in ONE XLA computation (the TPU
        replacement for the reference's overlap of backprop with engine-
        scheduled gradient reduction).

        When invoked through ``backward()`` the RNG keys of the
        caller's last ``forward()`` are replayed so stochastic ops
        (Dropout, rrelu) are differentiated at the SAME random draw the
        caller observed — the reference guarantees this by construction
        since its backward consumes stored forward activations.
        """
        import jax.numpy as jnp
        from .ndarray import NDArray
        if not self._grad_positions:
            # nothing requires grad: just forward
            self.forward(is_train=is_train, **kwargs)
            return
        args, aux = self._gather_inputs(kwargs)
        fn = None if self._grouped is not None \
            else self._get_fn("fwdbwd", bool(is_train))
        if out_grads is None:
            ogs = tuple(
                jnp.ones(tuple(s.shape), s.dtype)
                for s in self._out_structs(args, aux))
        else:
            if isinstance(out_grads, NDArray):
                out_grads = [out_grads]
            ogs = tuple(g._data for g in out_grads)
        rngs = getattr(self, "_last_rngs", None) \
            if _reuse_rngs else None
        if rngs is None:
            rngs = self._rngs()
        self._last_rngs = None  # one replay per forward
        if self._grouped is not None:
            outs, new_aux, grads = self._grouped.forward_backward(
                args, aux, rngs, ogs)
        else:
            outs, new_aux, grads = fn(args, aux, rngs, ogs)
        if _refresh_outputs:
            self._store_outputs(outs)
        if is_train:
            self._store_aux(new_aux)
        for p, g in zip(self._grad_positions, grads):
            name = self.arg_names[p]
            tgt = self.grad_arrays[p]
            if tgt is None:
                continue
            if self._grad_req[name] == "add":
                td = tgt._data
                if self._mesh is not None and td.sharding != g.sharding:
                    # first accumulation: the zeros buffer was created
                    # pre-mesh on one device; move it to the grad's
                    # (replicated) sharding before the eager add
                    import jax
                    td = jax.device_put(td, g.sharding)
                tgt._set_data(td + g)
            else:
                tgt._set_data(g)
        if self._monitor_callback is not None:
            self._run_monitor()

    def fused_plan(self):
        """The pieces the fused train-step executor (fused_step.py)
        composes into ITS OWN jit: the raw (unjitted) train-mode
        fwd+bwd program, the grad-carrying arg positions, and the
        traced output structs (for the default all-ones cotangents).
        Raises on a multi-device bind — raw tracing is unsupported
        there and the caller falls back to the eager path."""
        fn = self._get_fn("fwdbwd", True, raw=True)
        args = tuple(a._data for a in self.arg_arrays)
        aux = tuple(a._data for a in self.aux_arrays)
        return fn, list(self._grad_positions), self._out_structs(args, aux)

    def _out_structs(self, args, aux):
        import jax
        key = ("ostruct", tuple((a.shape, str(a.dtype)) for a in args))
        cached = self._fns.get(key)
        if cached is None:
            run = self._make_graph_fn(True)
            rngs = self._rngs() if self._rng_count else ()
            outs, _ = jax.eval_shape(run, args, aux, rngs)
            cached = outs
            self._fns[key] = cached
        return cached

    # -- misc API parity -------------------------------------------------
    def reshape(self, partial_shaping=False, allow_up_sizing=False, **kwargs):
        """Return an executor for new input shapes, sharing parameters."""
        from .ndarray import zeros as nd_zeros
        arg_shapes, _, aux_shapes = self._symbol.infer_shape(**kwargs)
        new_args = []
        for name, arr, shape in zip(self.arg_names, self.arg_arrays,
                                    arg_shapes):
            if tuple(arr.shape) == tuple(shape):
                new_args.append(arr)
            else:
                new_args.append(nd_zeros(shape, ctx=self._ctx,
                                         dtype=arr.dtype))
        grads = {}
        for name, g in zip(self.arg_names, self.grad_arrays):
            if g is not None:
                idx = self.arg_names.index(name)
                if tuple(g.shape) == tuple(arg_shapes[idx]):
                    grads[name] = g
                else:
                    grads[name] = nd_zeros(arg_shapes[idx], ctx=self._ctx,
                                           dtype=g.dtype)
        return Executor(self._symbol, self._ctx_arg, new_args, grads,
                        self._grad_req, self.aux_arrays,
                        batch_args=self._batch_args,
                        cw_bucket=self._cw_bucket)

    def copy_params_from(self, arg_params, aux_params=None,
                         allow_extra_params=False):
        for name, arr in arg_params.items():
            if name in self.arg_dict:
                self.arg_dict[name]._set_data(
                    arr.astype(self.arg_dict[name].dtype)._data)
            elif not allow_extra_params:
                raise MXNetError("Found name \"%s\" that is not in the "
                                 "arguments" % name)
        if aux_params:
            for name, arr in aux_params.items():
                if name in self.aux_dict:
                    self.aux_dict[name]._set_data(
                        arr.astype(self.aux_dict[name].dtype)._data)
                elif not allow_extra_params:
                    raise MXNetError("Found name \"%s\" that is not in the "
                                     "auxiliary states" % name)

    def set_monitor_callback(self, callback, monitor_all=False):
        """Per-op taps (monitor_all) run on the eager interpreted path;
        compiled programs are untouched, so no cache invalidation."""
        self._monitor_callback = callback
        self._monitor_all = monitor_all

    def _host_tap(self, name, value):
        """jax.debug.callback target: value arrives as host numpy."""
        from .ndarray import array as nd_array
        cb = self._monitor_callback
        if cb is not None:
            cb(name, nd_array(value))

    def _run_monitor(self):
        for name, out in zip(self.output_names, self.outputs):
            self._monitor_callback(name, out)

    @property
    def output_dict(self):
        return dict(zip(self.output_names, self.outputs))

    def debug_str(self):
        lines = ["Symbol Outputs:"]
        for n in self.output_names:
            lines.append("\toutput=%s" % n)
        for op, nattrs, bindings, rs, aux_wb, slot in self._plan:
            lines.append("Op:%s" % op.name)
        return "\n".join(lines)
