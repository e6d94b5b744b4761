"""Name-rule PartitionSpecs: the sharding-rules layer for
sharded-parameter (FSDP) training.

PR 7 landed the ZeRO-1 half of ROADMAP item 1 — optimizer state lives
dp-sharded at 1/N per device — but the *parameters* themselves stayed
fully replicated, so peak HBM per device still scales with total model
size. This module is the missing rules layer, the ZeRO stage-3
partitioning (Rajbhandari et al., SC 2020) expressed in GSPMD/pjit
idiom: every parameter carries a :class:`~jax.sharding.PartitionSpec`
chosen by *name heuristics* over a :class:`SpecLayout` of named mesh
axes (``data``/``fsdp``/``tp``), user-overridable per parameter, and
the compiled train step keeps the weights resident in that sharded
placement — per-device parameter memory drops to ~1/N and models
larger than one shard's HBM become trainable.

Three pieces:

- :class:`SpecLayout` — the axis-name vocabulary. A mesh rarely spells
  all three axes; :meth:`SpecLayout.for_mesh` resolves the layout
  against the mesh's real axis names (on the common 1-D ``dp`` mesh
  the ``fsdp`` axis *is* ``dp`` — batch and parameter shards live on
  the same devices, exactly ZeRO's arrangement).
- :func:`parameter_spec_from_name` — the heuristic rule table mapping
  parameter names/roles to specs: embeddings and projection/ffn/dense
  weights shard their leading (row) dim over ``fsdp`` (and, when the
  mesh has one, columns over ``tp``); norms, biases, scalars and
  anything 1-D stay replicated; names no heuristic recognizes stay
  replicated — sharding is opt-in by role, never by accident.
- :class:`ShardingRules` — the per-mesh resolver: user overrides
  (ordered substring → spec, first match wins; ``None`` forces
  replicated) take precedence over the heuristics, and every chosen
  spec is made *feasible* for the actual mesh: a leading dim that does
  not divide the axis size is zero-padded up to the next multiple (the
  same pad-and-slice convention as ``collectives.reduce_scatter`` —
  ``jax.device_put`` refuses uneven shards outright), recorded in the
  returned :class:`ParamShardPlan` and telemetry-noted once per param;
  a non-leading dim that does not divide simply drops that axis.

The consumer contract is :class:`ParamShardPlan`: the resolved spec,
the padded storage shape, and the pad/slice helpers the compiled step
uses to gather a logical view at program entry and re-pad the updated
value at exit. ``MXNET_PARAM_SHARD=1`` (default OFF) is the global
gate — with it closed every training path is byte-identical to PR 7.
"""
from __future__ import annotations


import numpy as _np

__all__ = ["SpecLayout", "parameter_spec_from_name", "ShardingRules",
           "ParamShardPlan", "param_shard_enabled", "held_experts"]


def param_shard_enabled():
    """The ``MXNET_PARAM_SHARD`` gate — default OFF; ``1``/``true``/
    ``on`` enable (re-read per build so tests and benchmarks can
    toggle it)."""
    from .. import envs
    return envs.get_bool("MXNET_PARAM_SHARD")


class SpecLayout:
    """Named mesh axes for parameter sharding (SNIPPETS.md [3] shape).

    ``data`` carries the batch, ``fsdp`` the parameter row shards,
    ``tp`` the tensor-parallel column shards. The names are logical:
    :meth:`for_mesh` maps them onto whatever axes the mesh actually
    spells — in particular, on the 1-axis ``dp`` mesh every repo
    entry point builds, ``data`` and ``fsdp`` BOTH resolve to ``dp``
    (ZeRO: the data-parallel workers are the shard holders)."""

    __slots__ = ("data_axis", "fsdp_axis", "tp_axis", "ep_axis")

    def __init__(self, data_axis="data", fsdp_axis="fsdp",
                 tp_axis="tp", ep_axis=None):
        self.data_axis = data_axis
        self.fsdp_axis = fsdp_axis
        self.tp_axis = tp_axis
        # the expert axis: stacked expert weights ``(E, ...)`` divide
        # their leading dim over it (no default name: a layout says so)
        self.ep_axis = ep_axis

    @classmethod
    def for_mesh(cls, mesh):
        """Resolve the logical axis names against ``mesh.axis_names``:
        ``fsdp`` prefers a literal ``fsdp`` axis, else rides ``dp``;
        ``tp`` only survives when the mesh has a ``tp`` axis of size
        > 1 (a trivial axis would annotate without sharding);
        ``data`` prefers ``data``, else ``dp``; ``ep`` is the mesh's
        ``ep`` axis where it has one of size > 1."""
        names = tuple(getattr(mesh, "axis_names", ()))
        sizes = dict(zip(names, mesh.devices.shape)) if names else {}
        data = "data" if "data" in names else \
            ("dp" if "dp" in names else None)
        fsdp = "fsdp" if "fsdp" in names else \
            ("dp" if "dp" in names else None)
        tp = "tp" if sizes.get("tp", 0) > 1 else None
        ep = "ep" if sizes.get("ep", 0) > 1 else None
        return cls(data_axis=data, fsdp_axis=fsdp, tp_axis=tp,
                   ep_axis=ep)

    def __repr__(self):
        return "SpecLayout(data=%r, fsdp=%r, tp=%r, ep=%r)" % (
            self.data_axis, self.fsdp_axis, self.tp_axis, self.ep_axis)


# name fragments that mark a parameter as replicated regardless of
# rank: normalization stats/affine terms and biases are tiny and their
# shard would cost a gather per use for no memory win
_REPLICATED_ROLES = ("bias", "beta", "gamma", "moving_mean",
                     "moving_var", "running_mean", "running_var",
                     "norm", "scale", "alpha")

# name fragments that mark a row-shardable projection/ffn weight
_PROJECTION_ROLES = ("q_proj", "k_proj", "v_proj", "o_proj", "qkv",
                     "query", "key", "value", "attn", "proj", "ffn",
                     "fc", "dense", "hidden", "output", "conv",
                     "weight")

_EMBEDDING_ROLES = ("embed", "embedding", "lookup_table", "wte",
                    "wpe")

# a stack of routed experts' matrices, ``(E, ...)``: the leading dim is
# the expert, and goes over ``ep`` and nothing else
_EXPERT_ROLES = ("experts.",)


def held_experts(n_experts, ep_size=1, ep_rank=0):
    """``(lo, hi)``: the experts of ``n_experts`` that shard ``ep_rank``
    of an ``ep`` axis of ``ep_size`` holds — the contiguous block the
    ``P(ep)`` rule of an expert stack gives it, and what
    ``parallel.moe.expert_ffn`` is told as ``held``. On an axis of one
    it is every expert."""
    n_experts, ep_size, ep_rank = int(n_experts), int(ep_size), \
        int(ep_rank)
    if ep_size < 1 or n_experts % ep_size or not 0 <= ep_rank < ep_size:
        raise ValueError(
            "held_experts: %d experts do not divide over an ep axis of "
            "%d (rank %d)" % (n_experts, ep_size, ep_rank))
    share = n_experts // ep_size
    return ep_rank * share, (ep_rank + 1) * share


def parameter_spec_from_name(name, shape=None, layout=None):
    """Heuristic PartitionSpec for one parameter name (SNIPPETS.md
    [3]'s ``parameter_spec_from_name`` shape, adapted to this repo's
    naming). Precedence:

    0. a stack of routed experts (``experts.`` in the name, rank >= 3)
       → its leading (expert) dim over ``ep`` when the layout has one,
       else replicated: never over ``fsdp`` (:func:`held_experts`
       names the block a shard then holds);
    1. rank ≤ 1 (when ``shape`` is known) → replicated — there is no
       row dim worth sharding and 1-D tensors are noise-sized;
    2. replicated roles (bias/beta/gamma/norm stats/scales) → ``P()``;
    3. embeddings → rows over ``fsdp``;
    4. projection/ffn/dense/conv ``weight``-like names → rows over
       ``fsdp`` and, when the layout has a live ``tp`` axis, columns
       over ``tp``;
    5. anything else → replicated (unknown names never shard by
       accident).

    Returns a :class:`jax.sharding.PartitionSpec`."""
    from jax.sharding import PartitionSpec as P
    layout = layout or SpecLayout()
    low = name.lower()
    if any(r in low for r in _EXPERT_ROLES) \
            and (shape is None or len(shape) >= 3):
        # rule 0: an expert stack's leading dim over ``ep``; without a
        # live ``ep`` axis (a mesh of one) it stays whole
        return P(layout.ep_axis) if layout.ep_axis is not None else P()
    if layout.fsdp_axis is None:
        return P()
    if shape is not None and len(shape) <= 1:
        return P()
    if any(r in low for r in _REPLICATED_ROLES):
        return P()
    if any(r in low for r in _EMBEDDING_ROLES):
        return P(layout.fsdp_axis)
    if any(r in low for r in _PROJECTION_ROLES):
        if layout.tp_axis is not None and shape is not None \
                and len(shape) >= 2:
            return P(layout.fsdp_axis, layout.tp_axis)
        return P(layout.fsdp_axis)
    return P()


class ParamShardPlan:
    """One parameter's resolved placement: the feasible spec, the
    (possibly padded) storage shape, and the pad/slice bridges between
    the logical value and the sharded resident array."""

    __slots__ = ("name", "spec", "shape", "padded_shape", "sharded",
                 "padded")

    def __init__(self, name, spec, shape, padded_shape):
        self.name = name
        self.spec = spec
        self.shape = tuple(int(s) for s in shape)
        self.padded_shape = tuple(int(s) for s in padded_shape)
        self.sharded = any(ax is not None for ax in spec)
        self.padded = self.padded_shape != self.shape

    def sharding(self, mesh):
        from jax.sharding import NamedSharding
        return NamedSharding(mesh, self.spec)

    def pad(self, value):
        """Zero-pad a logical value up to the storage shape (a no-op
        for divisible params). Works on numpy and jax arrays; exact —
        the padding rows are zeros the step slices back off."""
        if not self.padded:
            return value
        import jax.numpy as jnp
        pads = [(0, p - s) for s, p in zip(self.shape,
                                           self.padded_shape)]
        if isinstance(value, _np.ndarray):
            return _np.pad(value, pads)
        return jnp.pad(value, pads)

    def logical(self, value):
        """Slice a (padded) resident value back to the logical shape.
        Traceable — the compiled step calls this right after the
        entry gather."""
        if not self.padded:
            return value
        ix = tuple(slice(0, s) for s in self.shape)
        return value[ix]

    def bytes_per_device(self, dtype, mesh):
        """Resident bytes per device for this plan: the padded shard
        for sharded params, the full size for replicated ones."""
        n = 1
        sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
        for ax in self.spec:
            if ax is not None:
                n *= sizes.get(ax, 1)
        total = int(_np.prod(self.padded_shape)) if self.padded_shape \
            else 1
        return (total // n) * _np.dtype(dtype).itemsize


class ShardingRules:
    """The per-mesh rule resolver: overrides → heuristics → mesh
    feasibility (pad-and-slice).

    ``overrides`` is an ordered mapping of name substring →
    ``PartitionSpec`` (first match wins; ``None`` forces replicated —
    the escape hatch for a heuristic that guessed wrong). Anything the
    overrides miss falls to :func:`parameter_spec_from_name` under
    this rules object's :class:`SpecLayout`.

    Feasibility against the actual mesh, per spec dim:

    - the axis exists on the mesh and the dim divides its size →
      shard as asked;
    - the LEADING dim does not divide → keep the axis and zero-pad the
      storage up to the next multiple (``collectives.reduce_scatter``'s
      pad-and-slice convention; :class:`ParamShardPlan` carries the
      bridges), telemetry-noting ``param_shard_padded:<name>`` once so
      the padding is observable per run;
    - a non-leading dim does not divide, or the axis is unknown → drop
      that axis entry (replicate that dim).
    """

    def __init__(self, mesh, layout=None, overrides=None):
        self.mesh = mesh
        self.layout = layout if layout is not None \
            else SpecLayout.for_mesh(mesh)
        self.overrides = dict(overrides or {})
        self._axis_sizes = dict(zip(mesh.axis_names,
                                    mesh.devices.shape))
        self._noted_pads = set()

    # -- resolution -------------------------------------------------------
    def raw_spec(self, name, shape=None):
        """The pre-feasibility spec: first-match override, else the
        name heuristic. (Unit-testable without a value.)"""
        from jax.sharding import PartitionSpec as P
        for pat, spec in self.overrides.items():
            if pat in name:
                return P() if spec is None else spec
        return parameter_spec_from_name(name, shape=shape,
                                        layout=self.layout)

    def plan(self, name, shape):
        """The feasible :class:`ParamShardPlan` for one parameter."""
        from jax.sharding import PartitionSpec as P
        shape = tuple(int(s) for s in shape)
        spec = self.raw_spec(name, shape)
        entries = list(spec) + [None] * (len(shape) - len(spec))
        entries = entries[:len(shape)]
        feasible, padded = [], list(shape)
        for d, ax in enumerate(entries):
            if ax is None:
                feasible.append(None)
                continue
            # tuple entries (fsdp, tp) on one dim: keep only if the
            # dim divides the PRODUCT of the named axes
            axes = ax if isinstance(ax, tuple) else (ax,)
            n = 1
            known = True
            for a in axes:
                size = self._axis_sizes.get(a)
                if size is None:
                    known = False
                    break
                n *= size
            if not known or n <= 1:
                feasible.append(None)
                continue
            if shape[d] % n == 0:
                feasible.append(ax)
            elif d == 0:
                # pad-and-slice: keep the shard, grow the storage
                feasible.append(ax)
                padded[d] = -(-shape[d] // n) * n
            else:
                feasible.append(None)
        return ParamShardPlan(name, P(*feasible), shape, padded)

    def plans(self, shapes):
        """``{name: plan}`` for a ``{name: shape}`` roster."""
        return {n: self.plan(n, s) for n, s in shapes.items()}

    def note_padded(self, name):
        """One-time (per rules object, per param) telemetry note +
        log line naming a padded parameter — consumers call this when
        they actually place the padded storage; the pad is exact but
        it costs padded-fraction extra bytes, so it must be
        observable."""
        if name in self._noted_pads:
            return
        self._noted_pads.add(name)
        from .. import telemetry
        telemetry.note("param_shard_padded:%s" % name)
        import logging
        logging.getLogger(__name__).info(
            "param shard: %s leading dim padded up to the next "
            "multiple of the shard axis (pad-and-slice, exact)", name)

    # -- ledger -----------------------------------------------------------
    def bytes_per_device(self, shapes, dtypes):
        """``(sharded_bytes, replicated_bytes)`` resident per device
        for a ``{name: shape}`` roster — the split the telemetry
        memory table renders and the 1/N tests check."""
        sharded = replicated = 0
        for name, shape in shapes.items():
            plan = self.plan(name, shape)
            b = plan.bytes_per_device(dtypes[name], self.mesh)
            if plan.sharded:
                sharded += b
            else:
                replicated += b
        return sharded, replicated
