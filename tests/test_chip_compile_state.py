"""Sandbox compiles for the chip, continued from ``test_chip_compile.py``
(a file of its own so that no file is the floor of a ``--dist loadfile``
run): the state form's programs at ``Ling-3.0-flash``'s and
``Laguna-S-2.1``'s published widths, compiled by the TPU's own
compiler for a DESCRIBED v5e. A compile that passes is not a chip run."""
import json
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from test_chip_compile import ROOT, fa

pytestmark = pytest.mark.usefixtures("_persistent_cache_off")


def _state_holder(model, **attrs):
    """What the state form's step programs read of their server, without
    one (a described device holds no pool): no mesh, so the whole model
    is what a program traces."""
    from mxnet_tpu.serving import DecodeServer
    holder = object.__new__(DecodeServer)
    vars(holder).update(_model=model, _chip_model=model, _mesh=None,
                        **attrs)
    return holder


def _named(text, kernel):
    """The compiled program's calls of the Mosaic kernel ``mx_<kernel>``."""
    return re.findall(r"^\s*(?:ROOT )?%%mx_%s\.[\w.]* = .* custom-call\("
                      % kernel, text, re.M)


def _sorted(text):
    """The first operand's dimensions of every ``sort`` of a compiled
    program, as its HLO line writes them (``"1088,512"``)."""
    return re.findall(r"^\s*(?:ROOT )?%[\w.-]+ = \(?\w+\[([\d,]*)\][^=]* "
                      r"sort\(", text, re.M)


def _hybrid_linear_case(chip):
    """``benchmark/configs/Ling-3.0-flash.json`` as both of its cases
    compile it: ``(model, holder, W, rung, M, feed, carried, carried
    bytes, spec)`` — ``feed`` the step programs' arguments in front of the
    carried arrays (the mixed step's chunk goes between them)."""
    import types
    from mxnet_tpu.serving.hybrid_linear_moe import HybridLinearMoEDecoderLM
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "Ling-3.0-flash.json")) as f:
        cfg = json.load(f)
    srv = cfg["server"]["kwargs"]
    W, S, pages = srv["window"], srv["page_size"], srv["pool_pages"]
    rung, = srv["seq_ladder"]
    M = -(-(rung + srv["max_new_tokens"]) // S)
    model = HybridLinearMoEDecoderLM(**cfg["model"]["kwargs"])
    assert model.held == (0, 128) and model.row_width == 640
    assert (model.cache_layers, model.state_layers, model.chunk) \
        == (1, 5, 16)
    H, d = model.n_heads, model.head_dim
    params = jax.eval_shape(lambda: model.init_params(seed=0))
    weights = sum(int(np.prod(a.shape)) * a.dtype.itemsize
                  for a in params.values())
    assert 8.70e9 < weights < 8.75e9

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)

    carried = (
        spec((1, pages, S, model.row_width), jnp.bfloat16),
        spec((5, W, H, d, d), jnp.float32),
        spec((5, W, 3 * 3 * H * d), jnp.bfloat16))
    carried_bytes = sum(int(np.prod(a.shape)) * a.dtype.itemsize
                        for a in carried)
    assert 0.88e9 < carried_bytes < 0.89e9
    n_counts = len(model.step_counters[1])
    feed = (jax.tree_util.tree_map(lambda a: spec(a.shape, a.dtype), params),
            spec((W,), jnp.int32), spec((W,), jnp.int32),
            spec((W,), jnp.int32), spec((), jnp.int32),
            spec((W, M), jnp.int32), spec((W + n_counts,), jnp.int32),
            spec((W,), jnp.int32))
    return types.SimpleNamespace(
        model=model, W=W, rung=rung, M=M, feed=feed, carried=carried,
        carried_bytes=carried_bytes, spec=spec,
        holder=_state_holder(model, _window=W, _max_pages=M))


def test_hybrid_linear_programs_compile_and_fit(chip, monkeypatch):
    """``benchmark/configs/Ling-3.0-flash.json`` at its published widths
    (2560 wide, 32 heads of 128, five delta-rule linear-attention layers
    whose state is 32 x 128 x 128 float32 a row beside ONE latent layer of
    rank 512, experts of 768, 128 of 512 held, 1 dense + 5 expert layers,
    window 64, 1,152 bf16 pages of 128 x 640 in one cache layer): the
    state form's ``decode:step`` and 1024-rung ``decode:prefill`` programs
    compiled for one described v5e (the prefill is what a server of a
    model that does not declare ``chunk_lanes`` runs, and the oracle of
    the chunks; since PR 49 this model's own server runs the mixed step
    of the next case in its place). In each: the Mosaic kernels under the
    names a profile's reader looks for — the delta-rule step a linear
    layer, the paged latent decode kernel and the in-place row write
    (step), the flash kernel at 256-wide heads (prefill), the two grouped
    matmuls of every expert layer — the planned bytes inside the chip
    with room for the reference that decides ``correct`` beside the
    weights, the donated pool AND the donated state arrays updated in
    place, and NO copy of either among the temporaries (the state is 0.67
    GB: one copy of it a layer would double the step)."""
    from mxnet_tpu.serving import DecodeServer
    monkeypatch.setattr(fa, "_on_tpu", lambda: True)
    case = _hybrid_linear_case(chip)
    model, holder, spec, named = case.model, case.holder, case.spec, _named
    W, rung, M, carried = case.W, case.rung, case.M, case.carried
    tree, carried_bytes = case.feed[0], case.carried_bytes
    H, d, moe_layers = model.n_heads, model.head_dim, model.n_moe_layers

    step = jax.jit(lambda *a: DecodeServer._state_decode_fn(holder, *a),
                   donate_argnums=(8, 9, 10)).lower(
        *case.feed, *carried).compile()
    text = step.as_text()
    assert len(named(text, "kda_step")) == 5
    assert "mx_kda_step.b%d.h%d.d%d" % (W, H, d) in text
    assert len(named(text, "mla_decode")) == 1
    assert len(named(text, "latent_write")) == 1
    assert len(named(text, "grouped_matmul")) == 2 * moe_layers
    assert ".e128.m2560.k2560.n768.bfloat16.r16.gated" in text
    assert text.count('custom_call_target="tpu_custom_call"') \
        == 5 + 2 + 2 * moe_layers
    # the router sorts nothing (the next case's docstring): one sort an
    # expert layer, expert_ffn's ordering of rows x k slots
    assert _sorted(text) == ["%d" % (W * model.top_k)] * moe_layers
    mem = step.memory_analysis()
    assert mem.alias_size_in_bytes >= carried_bytes, mem
    assert mem.temp_size_in_bytes < 0.1e9, mem      # no state or pool copy
    planned = (mem.argument_size_in_bytes + mem.output_size_in_bytes
               + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert 9.5e9 < planned < 9.8e9, mem

    prefill = jax.jit(lambda *a: DecodeServer._state_prefill_fn(holder, *a),
                      donate_argnums=(5, 6, 7)).lower(
        tree, spec((1, rung), jnp.int32), spec((), jnp.int32),
        spec((M,), jnp.int32), spec((), jnp.int32), *carried).compile()
    text = prefill.as_text()
    assert len(named(text, "flash_fwd")) == 1
    assert ".q%d.k%d.d256.bfloat16" % (rung, rung) in text
    assert len(named(text, "grouped_matmul")) == 2 * moe_layers
    # the rung's 64 slots an expert take tiles of 32 rows
    assert ".e128.m12288.k2560.n768.bfloat16.r32.gated" in text
    # the chunkwise rule's walk: one loop a linear layer, carrying S
    assert len(re.findall(r"%while[.\d]* = \(s32\[\][^,]*, "
                          r"f32\[1,32,128,128\]", text)) == 5
    mem = prefill.memory_analysis()
    assert mem.alias_size_in_bytes >= carried_bytes, mem
    assert mem.temp_size_in_bytes < 0.5e9, mem
    planned = (mem.argument_size_in_bytes + mem.output_size_in_bytes
               + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert planned < 10.2e9, mem


def test_hybrid_linear_mixed_step_compiles_and_fits(chip, monkeypatch):
    """``benchmark/configs/Ling-3.0-flash.json``'s MIXED step
    ``decode:step:chunk:c1024`` — the 64 lanes that decode and 1,024 that
    are one prompt's chunk: the ladder's one rung, so every prompt of the
    cell is one chunk; since PR 49 this server runs no prefill program —
    for one described v5e. The rows keep their kernels beside a chunk
    (``mx_kda_step`` a linear layer, the paged latent decode kernel and
    its in-place row write, the grouped matmuls; nothing fell to ``jnp``),
    the chunk's delta rule is one walk a linear layer carrying ONE row's
    ``S``, only the rows and ONE lane of the chunk reach the head, and
    the donated pool and state arrays are updated in place: the chunk's
    row of ``s`` is read and written as a 2 MB slice a layer, and NO copy
    of the whole array (0.67 GB: 1.6 ms a layer) lies among the
    temporaries, which fit beside 9.6 GB of weights, pool and state.
    Since PR 51 the router chooses by reductions: this compiler made of
    its three ``lax.top_k`` full sorts (``sort f32[1088,8,64]`` 0.69 ms
    and ``sort f32[1088,512]`` 0.14 ms a layer on the chip, ``PERF.md``
    section 5), and what the program still sorts is ``expert_ffn``'s
    ordering of the 8,704 (lane, choice) slots, once an expert layer —
    counted here in the COMPILED program, where the jaxpr's pin
    (``test_latent_moe_serving.py``) cannot see what a lowering makes of
    an arg-max."""
    from mxnet_tpu import profiler
    from mxnet_tpu.serving import DecodeServer
    monkeypatch.setattr(fa, "_on_tpu", lambda: True)
    case = _hybrid_linear_case(chip)
    model, W, C, M = case.model, case.W, case.rung, case.M
    assert (W, C) == (64, 1024) and model.chunk_lanes
    H, d, moe_layers = model.n_heads, model.head_dim, model.n_moe_layers
    before = dict(profiler.counters())
    step = jax.jit(
        lambda *a: DecodeServer._state_decode_fn_chunk(case.holder, *a),
        donate_argnums=(9, 10, 11)).lower(
        *case.feed, case.spec((C + M + 3,), jnp.int32),
        *case.carried).compile()
    chose = {k: v - before.get(k, 0)
             for k, v in profiler.counters().items()
             if k.endswith(("_pallas", "_jnp")) and v != before.get(k, 0)}
    assert not any(k.endswith("_jnp") for k in chose), chose
    text = step.as_text()
    assert chose["kda_step_pallas"] == 5
    assert chose["grouped_matmul_pallas"] == moe_layers

    assert len(_named(text, "kda_step")) == 5
    assert "mx_kda_step.b%d.h%d.d%d" % (W, H, d) in text
    assert len(_named(text, "mla_decode")) == 1
    assert len(_named(text, "latent_write")) == 1
    assert len(_named(text, "grouped_matmul")) == 2 * moe_layers
    # 1,088 lanes x 8 choices over 128 held experts: tiles of 32 rows
    assert re.search(r"\.e128\.m\d+\.k2560\.n768\.bfloat16\.r32\.gated",
                     text)
    assert text.count('custom_call_target="tpu_custom_call"') \
        == 5 + 2 + 2 * moe_layers
    # no sort of lanes x 8 x 64, lanes x 8 or lanes x 512: the slot
    # ordering's one sort an expert layer is all there is
    assert _sorted(text) == ["%d" % ((W + C) * model.top_k)] * moe_layers
    # the chunkwise rule's walk: one loop a linear layer, carrying ONE
    # row's S
    assert len(re.findall(r"%while[.\d]* = \(s32\[\][^,]*, "
                          r"f32\[1,32,128,128\]", text)) == 5
    # only the rows and ONE lane of the chunk reach the head
    assert "f32[%d,%d]" % (W + 1, model.vocab) in text
    assert "f32[%d,%d]" % (W + C, model.vocab) not in text
    # no copy of the state or of a plane of it: whatever holds all 64
    # rows of S is the donated argument or its alias
    assert not re.findall(r"= f32\[(?:5,|1,)?%d,%d,%d,%d\]\S* copy\("
                          % (W, H, d, d), text)
    mem = step.memory_analysis()
    assert mem.alias_size_in_bytes >= case.carried_bytes, mem
    assert mem.temp_size_in_bytes < 0.5e9, mem      # no state or pool copy
    planned = (mem.argument_size_in_bytes + mem.output_size_in_bytes
               + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert planned < 10.2e9, mem


@pytest.mark.parametrize("program", ["step", "chunk"])
def test_window_moe_programs_compile_and_fit(chip, monkeypatch, program):
    """``benchmark/configs/Laguna-S-2.1.json`` at its published widths
    (3072 wide, 48 and 72 gated query heads over 8 key/value heads of
    128, three sliding-window layers whose last 512 keys and values are a
    ring a row beside two full-attention layers' packed pages, experts of
    1024, 64 of 256 held, 1 dense + 4 expert layers, window 64, 4,608
    bf16 pages of 128 x 1024 in two cache layers): the state form's
    ``decode:step`` and its MIXED step ``decode:step:chunk:c512`` — 64
    lanes that decode and 512 that are one prompt's chunk; since PR 46
    this server runs no prefill program, and the case that compiled its
    512- and 8192-rung prefills compiles this — for one described v5e. In
    each: the Mosaic kernels under the names a profile's reader looks for
    — the ring decode a sliding layer (9 query heads a key head), the
    paged block kernel a full layer (6) and its in-place row write, the
    two grouped matmuls of every expert layer and, in the mixed step, the
    banded grouped forward with its queries offset behind the ring's 512
    keys, a sliding layer — the planned bytes inside the chip, the
    donated pool AND the donated rings updated in place, and NO copy of
    either among the temporaries (the rings are 0.4 GB, the pool 4.8 GB).
    The mixed step's temporaries: 0.20 GB found (the 8192-rung prefill it
    replaces planned 2.5 GB), held under 0.3."""
    from mxnet_tpu.serving import DecodeServer, WindowMoEDecoderLM
    monkeypatch.setattr(fa, "_on_tpu", lambda: True)
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "Laguna-S-2.1.json")) as f:
        cfg = json.load(f)
    srv = cfg["server"]["kwargs"]
    W, S, pages = srv["window"], srv["page_size"], srv["pool_pages"]
    M = -(-(max(srv["seq_ladder"]) + srv["max_new_tokens"]) // S)
    # the ladder 512 / 8192 gives one mixed program, of the first rung
    C, = [r for r in sorted(srv["seq_ladder"])
          if r <= 2 * min(srv["seq_ladder"])]
    assert C == 512
    model = WindowMoEDecoderLM(**cfg["model"]["kwargs"])
    assert model.held == (0, 64) and model.heads == (48, 72, 72, 72, 48)
    assert (model.cache_layers, model.state_layers) == (2, 3)
    assert model.chunk_lanes and model.window == 512
    moe_layers = model.n_moe_layers
    params = jax.eval_shape(lambda: model.init_params(seed=0))
    weights = sum(int(np.prod(a.shape)) * a.dtype.itemsize
                  for a in params.values())
    assert 6.00e9 < weights < 6.02e9

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)

    tree = jax.tree_util.tree_map(lambda a: spec(a.shape, a.dtype), params)
    carried = (
        spec((2, pages, S, 1024), jnp.bfloat16),
        spec((2, pages, S, 1024), jnp.bfloat16),
        spec((3, W, 512, 1024), jnp.bfloat16),
        spec((3, W, 512, 1024), jnp.bfloat16))
    carried_bytes = sum(int(np.prod(a.shape)) * a.dtype.itemsize
                        for a in carried)
    assert 5.23e9 < carried_bytes < 5.24e9
    holder = _state_holder(model, _window=W, _max_pages=M)
    n_counts = len(model.step_counters[1])

    def named(text, kernel):
        return re.findall(r"^\s*(?:ROOT )?%%mx_%s\.[\w.]* = .* custom-call\("
                          % kernel, text, re.M)

    feed = (tree, spec((W,), jnp.int32), spec((W,), jnp.int32),
            spec((W,), jnp.int32), spec((), jnp.int32),
            spec((W, M), jnp.int32), spec((W + n_counts,), jnp.int32),
            spec((W,), jnp.int32))
    if program == "step":
        step = jax.jit(lambda *a: DecodeServer._state_decode_fn(holder, *a),
                       donate_argnums=(8, 9, 10, 11)).lower(
            *feed, *carried).compile()
    else:
        step = jax.jit(
            lambda *a: DecodeServer._state_decode_fn_chunk(holder, *a),
            donate_argnums=(9, 10, 11, 12)).lower(
            *feed, spec((C + M + 3,), jnp.int32), *carried).compile()
    text = step.as_text()
    # the rows that decode keep their kernels beside a chunk
    assert len(named(text, "ring_decode")) == 3
    assert "mx_ring_decode.bh%d.q1.k512.d128.bfloat16.kv8" % (W * 72) in text
    assert len(named(text, "block_decode")) == 2
    assert "mx_block_decode.bh%d.q1.k%d.d128.bfloat16.kv8.paged" % (
        W * 48, M * S) in text
    assert len(named(text, "block_write")) == 2
    assert len(named(text, "grouped_matmul")) == 2 * moe_layers
    mem = step.memory_analysis()
    assert mem.alias_size_in_bytes >= carried_bytes, mem
    planned = (mem.argument_size_in_bytes + mem.output_size_in_bytes
               + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    if program == "step":
        assert ".e64.m1664.k3072.n1024.bfloat16.r16.gated" in text
        assert text.count('custom_call_target="tpu_custom_call"') \
            == 3 + 2 + 2 + 2 * moe_layers
        assert mem.temp_size_in_bytes < 0.1e9, mem   # no pool or ring copy
        assert 11.2e9 < planned < 11.4e9, mem
        return
    # the chunk's sliding layers: 512 queries behind the ring's 512 keys,
    # two key blocks of 512 a query block, never a third
    assert len(named(text, "grouped_fwd")) == 3
    assert "mx_grouped_fwd.bh72.q%d.k%d.d128.bfloat16.kv8.w512.o512" % (
        C, 512 + C) in text
    # 576 lanes x 10 choices: 90 slots an expert, tiles of 32 rows
    assert ".e64.m7808.k3072.n1024.bfloat16.r32.gated" in text
    assert text.count('custom_call_target="tpu_custom_call"') \
        == 3 + 3 + 2 + 2 + 2 * moe_layers
    # only the rows and ONE lane of the chunk reach the head
    assert "f32[%d,%d]" % (W + 1, model.vocab) in text
    assert "f32[%d,%d]" % (W + C, model.vocab) not in text
    assert mem.temp_size_in_bytes < 0.3e9, mem       # no pool or ring copy
    assert 11.3e9 < planned < 11.6e9, mem


@pytest.mark.parametrize("program", ["step", "chunk"])
def test_sharded_window_moe_programs_compile_for_four_chips(
        chip, monkeypatch, program):
    """``benchmark/configs/Mellum2-12B-A2.5B-Instruct.json`` WHOLE — 28
    layers, 64 experts, 98,304 rows of vocabulary, 24.3 GB — over a mesh
    of the four described chips of a v5e 2x2: the state form's
    ``decode:step`` and its MIXED step ``decode:step:chunk:c512`` under
    ``shard_map`` (``DecodeServer._over_mesh``), as the server builds
    them. On every chip: ONE key/value head's rings (``mx_ring_decode
    ...kv1``, 8 query heads a group) and packed pages of 128 lanes
    (``mx_block_decode...kv1.paged``), 16 experts' grouped matmuls
    (``.e16...r16`` a step, ``.r128`` beside 512 chunk lanes), the
    chunk's banded forward behind the ring's 1,024 keys — every one the
    Pallas kernel, none fallen to ``jnp`` at the one-head shapes — two
    all-reduces a layer and one for the arg-max's pairs, 8.4 GB of
    arguments a chip (6.4 of weights, 1.9 of pool and rings, updated in
    place) and no copy of pool or rings among the temporaries."""
    import types
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from mxnet_tpu import profiler
    from mxnet_tpu.serving import DecodeServer, WindowMoEDecoderLM, kvcache
    monkeypatch.setattr(fa, "_on_tpu", lambda: True)
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "Mellum2-12B-A2.5B-Instruct.json")) as f:
        cfg = json.load(f)
    srv = cfg["server"]["kwargs"]
    W, S, pages = srv["window"], srv["page_size"], srv["pool_pages"]
    M = -(-(max(srv["seq_ladder"]) + srv["max_new_tokens"]) // S)
    C, = [r for r in sorted(srv["seq_ladder"])
          if r <= 2 * min(srv["seq_ladder"])]
    assert (C, M) == (512, 40)
    # the four described chips of the fixture's topology, one axis
    from jax.experimental import topologies
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    mesh = Mesh(np.asarray(topo.devices).reshape(4), ("tp",))
    model = WindowMoEDecoderLM(**cfg["model"]["kwargs"]).sharded_over(mesh)
    shapes = jax.eval_shape(
        WindowMoEDecoderLM(**cfg["model"]["kwargs"]).init_params, 0)
    where = model.param_shardings()
    tree = {n: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=where[n])
            for n, a in shapes.items()}
    layout = kvcache._with_row_state(
        kvcache.cache_layout(kvcache.declared_arrays(model)[0],
                             jnp.dtype("bfloat16")),
        *kvcache.declared_state(model))

    def spec(shape, dtype, at=P()):
        return jax.ShapeDtypeStruct(shape, dtype,
                                    sharding=NamedSharding(mesh, at))

    carried = tuple(
        spec(shape, dt, at) for (_n, shape, dt), at in zip(
            layout.arrays(model.cache_layers, pages, S)
            + layout.state_arrays(W),
            kvcache.shard_specs(model, layout, "tp")))
    assert [a.shape for a in carried] == [
        (7, pages, S, 512)] * 2 + [(21, W, 1024, 512)] * 2
    holder = object.__new__(DecodeServer)
    vars(holder).update(
        _model=model, _chip_model=model.local(), _mesh=mesh, _shards=4,
        _window=W, _max_pages=M,
        _pool=types.SimpleNamespace(layout=layout))
    n_counts = 4 * len(model.step_counters[1])
    feed = (tree, spec((W,), jnp.int32), spec((W,), jnp.int32),
            spec((W,), jnp.int32), spec((), jnp.int32),
            spec((W, M), jnp.int32), spec((W + n_counts,), jnp.int32),
            spec((W,), jnp.int32))
    before = dict(profiler.counters())
    if program == "step":
        step = jax.jit(holder._over_mesh(holder._state_decode_fn, 7),
                       donate_argnums=(8, 9, 10, 11)).lower(
            *feed, *carried).compile()
    else:
        step = jax.jit(
            holder._over_mesh(holder._state_decode_fn_chunk, 8),
            donate_argnums=(9, 10, 11, 12)).lower(
            *feed, spec((C + M + 3,), jnp.int32), *carried).compile()
    chose = {k: v - before.get(k, 0)
             for k, v in profiler.counters().items()
             if k.endswith(("_pallas", "_jnp")) and v != before.get(k, 0)}
    assert not any(k.endswith("_jnp") for k in chose), chose
    assert chose["ring_decode_pallas"] == 21
    assert chose["grouped_matmul_pallas"] == 28
    assert chose["block_decode_pallas"] == 7
    text = step.as_text()

    def named(kernel):
        return re.findall(r"^\s*(?:ROOT )?%%mx_%s\.[\w.]* = .* custom-call\("
                          % kernel, text, re.M)

    assert len(named("ring_decode")) == 21
    assert "mx_ring_decode.bh%d.q1.k1024.d128.bfloat16.kv1" % (W * 8) in text
    assert len(named("block_decode")) == 7
    assert "mx_block_decode.bh%d.q1.k%d.d128.bfloat16.kv1.paged" % (
        W * 8, M * S) in text
    assert len(named("grouped_matmul")) == 2 * 28
    assert len(re.findall(r" all-reduce(?:-start)?\(", text)) in (56, 57, 58)
    mem = step.memory_analysis()
    carried_bytes = sum(int(np.prod(a.shape)) * a.dtype.itemsize
                        for a in carried) // 4
    assert 1.93e9 < carried_bytes < 1.94e9
    assert mem.alias_size_in_bytes >= carried_bytes, mem
    assert 8.3e9 < mem.argument_size_in_bytes < 8.5e9, mem
    assert mem.temp_size_in_bytes < 0.3e9, mem       # no pool or ring copy
    if program == "step":
        assert ".e16.m768.k2304.n896.bfloat16.r16.gated" in text
        return
    assert chose["ring_chunk_pallas"] == 21
    assert len(named("grouped_fwd")) == 21
    assert "mx_grouped_fwd.bh8.q%d.k%d.d128.bfloat16.kv1.w1024.o1024" % (
        C, 1024 + C) in text
    assert ".e16.m6656.k2304.n896.bfloat16.r128.gated" in text
    # only the rows and ONE lane of the chunk reach a chip's columns of
    # the head
    assert "f32[%d,%d]" % (W + 1, model.vocab // 4) in text
    assert "f32[%d,%d]" % (W + C, model.vocab // 4) not in text
