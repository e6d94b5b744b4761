"""Pipeline parallelism over the ``pp`` mesh axis.

A GPipe-style schedule expressed the TPU-native way: every pipeline
stage is one shard of a ``shard_map`` over the ``pp`` axis, stage
parameters are sharded on their leading (stage) dimension, and
activations move stage-to-stage with ``lax.ppermute`` over ICI. The
whole schedule — fill, steady state, drain — is a single ``lax.scan``
inside one jitted program, so XLA overlaps the ppermute transfer of
microbatch *i* with the stage compute of microbatch *i+1*.

The reference framework has no pipeline schedule (its only "model
parallelism" is manual `ctx_group` placement,
ref: python/mxnet/symbol/symbol.py:1369-1416 and
src/executor/graph_executor.cc:907 AssignContext); this is the
capability extension SURVEY §5.7/§2.2 mandates for the TPU build.
"""
from __future__ import annotations

__all__ = ["pipeline_apply", "stack_stage_params"]


def stack_stage_params(per_stage_params):
    """Stack a list of per-stage param pytrees into one pytree whose
    leaves gain a leading stage dimension (shard it with P('pp', ...))."""
    import jax
    import jax.numpy as jnp
    return jax.tree_util.tree_map(
        lambda *leaves: jnp.stack(leaves, axis=0), *per_stage_params)


def pipeline_apply(stage_fn, stacked_params, microbatches, *, mesh,
                   axis="pp", mb_spec=None):
    """Run ``microbatches`` through a chain of pipeline stages.

    Parameters
    ----------
    stage_fn : callable(params_one_stage, x) -> y with ``y.shape ==
        x.shape`` (activations must keep one shape so they can flow
        through the ring buffer; project outside the pipeline).
    stacked_params : pytree whose leaves have leading dim ``n_stages``
        (see :func:`stack_stage_params`), sharded ``P(axis, ...)``.
    microbatches : array ``(n_micro, mb, ...)`` — replicated over the
        ``pp`` axis (shard other dims over dp/sp as you like).
    mesh : the device mesh; ``mesh.shape[axis]`` is the stage count.
    mb_spec : PartitionSpec for the microbatch stack over the *other*
        mesh axes (e.g. ``P(None, 'dp')`` to keep batch dim sharded over
        dp while the schedule runs over pp). Defaults to replicated.

    Returns ``(n_micro, mb, ...)`` outputs (identical on every pp
    shard). Differentiable: the schedule is a scan of ppermutes and
    stage applications, so ``jax.grad`` pipelines the backward pass in
    reverse stage order automatically.
    """
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    shard_map = jax.shard_map

    n_stages = dict(zip(mesh.axis_names, mesh.devices.shape))[axis]
    n_micro = microbatches.shape[0]
    if n_micro < n_stages:
        raise ValueError(
            "pipeline_apply needs n_micro >= n_stages for a full "
            "schedule; got %d microbatches for %d stages"
            % (n_micro, n_stages))

    # Every param leaf is P(axis, *replicated); activations replicated
    # over pp (they're sharded over dp/sp on *other* dims by the caller's
    # in-shardings, which shard_map leaves alone via P(None...)).
    param_specs = jax.tree_util.tree_map(lambda _: P(axis), stacked_params)

    def schedule(params, mbs):
        # inside shard_map: each leaf of params has leading dim 1 (my
        # stage's slice); mbs is the full replicated microbatch stack.
        my_params = jax.tree_util.tree_map(lambda w: w[0], params)
        stage = jax.lax.axis_index(axis)
        fwd_ring = [(j, (j + 1) % n_stages) for j in range(n_stages)]

        def tick(carry, i):
            state, outs = carry
            # stage 0 ingests microbatch i while it exists, later ticks
            # recirculate garbage that is masked out of the result.
            mb_in = jax.lax.dynamic_index_in_dim(
                mbs, jnp.minimum(i, n_micro - 1), 0, keepdims=False)
            x = jnp.where(stage == 0, mb_in, state)
            y = stage_fn(my_params, x)
            out_i = i - (n_stages - 1)
            written = jax.lax.dynamic_update_index_in_dim(
                outs, y, jnp.maximum(out_i, 0), 0)
            take = (stage == n_stages - 1) & (out_i >= 0)
            outs = jnp.where(take, written, outs)
            state = jax.lax.ppermute(y, axis, fwd_ring)
            return (state, outs), None

        zero = jnp.zeros(mbs.shape[1:], mbs.dtype)
        outs0 = jnp.zeros_like(mbs)
        (_, outs), _ = jax.lax.scan(
            tick, (zero, outs0), jnp.arange(n_micro + n_stages - 1))
        # outputs were accumulated on the last stage only; replicate them
        # so out_specs can be P() (a masked psum is a broadcast here).
        outs = jax.lax.psum(
            jnp.where(stage == n_stages - 1, outs, jnp.zeros_like(outs)),
            axis)
        return outs

    if mb_spec is None:
        mb_spec = P()
    kwargs = dict(mesh=mesh, in_specs=(param_specs, mb_spec),
                  out_specs=mb_spec)
    sharded = shard_map(schedule, check_vma=False, **kwargs)
    return sharded(stacked_params, microbatches)
