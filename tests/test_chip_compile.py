"""Sandbox compiles for the chip: every ``pallas_call`` of
``parallel/flash_attention.py`` and the decode step program, compiled by
the TPU's own compiler for a DESCRIBED v5e (no chip attached, nothing
runs). Interpret mode on the CPU accepts block shapes Mosaic refuses —
these cases are what keeps the kernels loadable between chip runs.
A compile that passes is not a chip run: results are checked by
``chip_smoke.py`` on the chip."""
import json
import os
import re
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import mxnet_tpu.parallel  # noqa: F401 — the package re-exports the
fa = sys.modules["mxnet_tpu.parallel.flash_attention"]  # function


pytestmark = pytest.mark.usefixtures("_persistent_cache_off")

B, H, D = 4, 8, 128
SCALE = 1.0 / np.sqrt(D)


def _attn(seg):
    return lambda q, k, v, *s: fa._flash(
        q, k, v, s[0] if seg else None, SCALE, True, 512, 512, False)


def _attn_grad(seg):
    f = _attn(seg)
    return jax.grad(lambda *a: f(*a).astype(jnp.float32).sum(),
                    argnums=(0, 1, 2))


def _decode(quant):
    def run(q, k, v, lens, *scales):
        ks, vs = scales if quant else (None, None)
        return fa._pallas_decode(q, k, v, lens, SCALE, 128, False,
                                 k_scale=ks, v_scale=vs)
    return run


def _decode_paged(quant):
    def run(q, k_new, v_new, k_pages, v_pages, table, lens, *scales):
        ks, vs = scales if quant else (None, None)
        return fa._pallas_paged_decode(q, k_new, v_new, k_pages, v_pages,
                                       1, table, lens, SCALE, False,
                                       k_scale=ks, v_scale=vs)
    return run


def _attn_args(T, dtype, seg):
    qkv = [((B, T, H, D), dtype)] * 3
    return qkv + ([((B, T), jnp.int32)] if seg else [])


def _decode_args(T, dtype, quant):
    cache = jnp.int8 if quant else dtype
    args = [((B * H, 1, D), dtype), ((B * H, T, D), cache),
            ((B * H, T, D), cache), ((B * H,), jnp.int32)]
    return args + ([((B * H, T), jnp.float32)] * 2 if quant else [])


def _decode_paged_args(T, dtype, quant):
    """A two-layer pool of 128-token pages, a table ``T`` keys wide."""
    M = T // 128
    pool = ((2, B * M + 1, 128, H, D), jnp.int8 if quant else dtype)
    new = ((B, H, D), jnp.float32 if quant else dtype)
    args = [((B, H, D), jnp.float32), new, new, pool, pool,
            ((B, M), jnp.int32), ((B,), jnp.int32)]
    return args + ([((B, M), jnp.float32)] * 2 if quant else [])


FWD = ("flash_fwd",)
GRAD = ("flash_fwd", "flash_bwd_dkdv", "flash_bwd_dq")
# kind -> (function, the kernels its program calls, argument builder,
#          the builder's segments/quantized flag)
KINDS = {
    "forward": (_attn(False), FWD, _attn_args, False),
    "forward_grad": (_attn_grad(False), GRAD, _attn_args, False),
    "segments_forward": (_attn(True), FWD, _attn_args, True),
    "segments_grad": (_attn_grad(True), GRAD, _attn_args, True),
    "decode": (_decode(False), ("flash_decode",), _decode_args, False),
    "decode_int8": (_decode(True), ("flash_decode_q8",), _decode_args,
                    True),
    # the paged kernel carries the contiguous one's name, ".paged" after
    # the pool's dtype
    "decode_paged": (_decode_paged(False), ("flash_decode",),
                     _decode_paged_args, False),
    "decode_paged_int8": (_decode_paged(True), ("flash_decode",),
                          _decode_paged_args, True),
}


def _named_calls(text, kernel):
    """The Mosaic calls of a compiled program that carry ``kernel``'s
    stable name and shapes as their instruction's name (differentiation
    puts ``jvp_`` / ``transpose_jvp_`` in front): what a profile shows
    as the operation's event."""
    return re.findall(
        r"^\s*(?:ROOT )?%%\w*?mx_%s\.bh\d+\.q\d+\.k\d+\.d\d+\.[a-z]+\d+"
        r"(?:\.paged)?[._\d]* = .* custom-call\(" % kernel, text, re.M)


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
@pytest.mark.parametrize("T", [512, 2048])
@pytest.mark.parametrize("kind", sorted(KINDS))
def test_kernel_compiles_for_v5e(chip, kind, T, dtype):
    fn, kernels, make_args, flag = KINDS[kind]
    args = [jax.ShapeDtypeStruct(shape, dt, sharding=chip)
            for shape, dt in make_args(T, dtype, flag)]
    text = jax.jit(fn).lower(*args).compile().as_text()
    # the Mosaic kernels are IN the program: not a jnp path, not
    # interpret mode
    assert text.count('custom_call_target="tpu_custom_call"') \
        == len(kernels)
    # and each under the name a reader of a profile looks for
    for kernel in kernels:
        assert len(_named_calls(text, kernel)) == 1, kernel


ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _step_holder(model):
    """What the step program needs of its server, unbound: the model
    and the step's body."""
    from mxnet_tpu.serving import DecodeServer
    return type("S", (), {"_model": model,
                          "_step_fn": DecodeServer._step_fn})()


def _smoke_shapes():
    sys.path.insert(0, ROOT)
    import chip_smoke
    cfg = chip_smoke.FULL
    return (cfg["lm"], cfg["window"], cfg["page_size"], cfg["pool_pages"],
            max(cfg["ladder"]) + cfg["new_tokens"])


def _benchmark_shapes():
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "opt-6.7b.json")) as f:
        cfg = json.load(f)
    srv = cfg["server"]["kwargs"]
    return (cfg["model"]["kwargs"], srv["window"], srv["page_size"],
            srv["pool_pages"],
            max(srv["seq_ladder"]) + srv["max_new_tokens"])


@pytest.mark.parametrize("shapes", [_smoke_shapes, _benchmark_shapes],
                         ids=["chip_smoke", "opt-6.7b"])
def test_decode_step_program_compiles_and_fits(chip, monkeypatch, shapes):
    """The ``decode:step`` program — ToyDecoderLM.decode attending the
    pool through the paged Pallas kernel, one kernel call a layer, then
    the token's row writes — at chip_smoke's widths and at the
    benchmark's own (depth 8, window 8, 16 pages a row, 160 float32
    pool pages: ``benchmark/configs/opt-6.7b.json``), compiled for one
    v5e from ``jax.eval_shape``-made
    shapes: inside the chip's 16 GB, the donated pools updated in place,
    and no copy of the pool or of a gathered cache among its
    temporaries (PR 22's step planned 4.57 GB of them). The platform
    predicate is steered here, in the test: the sandbox's JAX sees a
    CPU."""
    from mxnet_tpu.serving import DecodeServer, ToyDecoderLM
    monkeypatch.setattr(fa, "_on_tpu", lambda: True)
    lm, W, S, pool_pages, context = shapes()
    model = ToyDecoderLM(**lm)
    params = jax.eval_shape(lambda: model.init_params(seed=0))
    L, Hh, Dh = model.n_layers, model.n_heads, model.head_dim
    M = -(-context // S)

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)

    pool = spec((L, pool_pages, S, Hh, Dh), jnp.float32)
    holder = _step_holder(model)
    compiled = jax.jit(
        lambda *a: DecodeServer._decode_fn(holder, *a),
        donate_argnums=(6, 7)).lower(
        jax.tree_util.tree_map(lambda a: spec(a.shape, a.dtype), params),
        spec((W,), jnp.int32), spec((W,), jnp.int32),
        spec((W, M), jnp.int32), spec((W,), jnp.int32),
        spec((W,), jnp.int32), pool, pool).compile()
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == L
    assert len(_named_calls(text, "flash_decode")) == L
    assert ".k%d.d%d.float32.paged" % (M * S, Dh) in text
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert 0 < total < 16e9, mem
    pools = 2 * L * pool_pages * S * Hh * Dh * 4
    assert mem.alias_size_in_bytes >= pools, mem
    assert mem.temp_size_in_bytes < 0.5e9, mem


def _mixed_holder(model, window, max_pages):
    """What the mixed step program needs of its server, unbound."""
    return type("S", (), {"_model": model, "_window": window,
                          "_max_pages": max_pages})()


@pytest.mark.parametrize("config,C", [("opt-6.7b", 256), ("opt-6.7b", 512),
                                      ("dots.vlm1.inst", 256)])
def test_mixed_step_program_compiles_and_fits(chip, monkeypatch, config, C):
    """The ``decode:step:chunk:c<C>`` programs — the step's ``window``
    lanes and ``C`` more that are one prompt's chunk — at the benchmark's
    own sizes, every rung within twice the ladder's smallest
    (``benchmark/configs/opt-6.7b.json``: 264 and 520 lanes, float32,
    per-head K and V; ``dots.vlm1.inst.json``: 320 lanes, bf16, the
    latent pool, 16 of 256 experts), compiled for one described v5e: the
    decode rows
    keep their paged Mosaic kernel, one call a layer (and dots its row
    write and its two grouped matmuls an expert layer), the donated
    pools are updated in place, and NO copy of a pool is among the
    temporaries. Two traps this compile found, both held here: a chunk's
    page writes over all layers at once made XLA re-lay the whole
    per-head pool out page-major and copy it back (2.7 GB of
    temporaries: the writes go a layer at a time), and the bfloat16
    rounding of a float32 pool's keys was moved up through the walk's
    gather and out of it — the WHOLE pool rounded once a step, 2.7 GB of
    temporaries and 8 GB of traffic (``kvcache._block_pages``)."""
    from mxnet_tpu.serving import DecodeServer, ToyDecoderLM
    from mxnet_tpu.serving.latent_moe import LatentMoEDecoderLM
    monkeypatch.setattr(fa, "_on_tpu", lambda: True)
    with open(os.path.join(ROOT, "benchmark", "configs",
                           config + ".json")) as f:
        cfg = json.load(f)
    srv = cfg["server"]["kwargs"]
    W, S, pages = srv["window"], srv["page_size"], srv["pool_pages"]
    assert C in [r for r in srv["seq_ladder"]
                 if r <= 2 * min(srv["seq_ladder"])]
    M = -(-(max(srv["seq_ladder"]) + srv["max_new_tokens"]) // S)

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)

    if config == "opt-6.7b":
        model = ToyDecoderLM(**cfg["model"]["kwargs"])
        pools = [spec((model.n_layers, pages, S, model.n_heads,
                       model.head_dim), jnp.float32)] * 2
        kernels = {"flash_decode": model.n_layers}
        n_counts, temp, rows = 0, 0.2e9, ""
    else:
        model = LatentMoEDecoderLM(**cfg["model"]["kwargs"])
        pools = [spec((model.n_layers, pages, S, model.row_width),
                      jnp.bfloat16)]
        kernels = {"mla_decode": model.n_layers, "latent_write": 1,
                   "grouped_matmul": 2 * model.n_moe_layers}
        n_counts, temp = len(model.step_counters[1]), 0.5e9
        # W + C = 320 rows x top 8 on 16 held experts: 160 slots an
        # expert can average, so tiles of 64 rows (a step's are 16)
        rows = ".e16.m3584.k7168.n2048.bfloat16.r64.gated"
    params = jax.eval_shape(lambda: model.init_params(seed=0))
    compiled = jax.jit(
        lambda *a: DecodeServer._decode_fn_chunk(
            _mixed_holder(model, W, M), *a),
        donate_argnums=tuple(range(7, 7 + len(pools)))).lower(
        jax.tree_util.tree_map(lambda a: spec(a.shape, a.dtype), params),
        spec((W,), jnp.int32), spec((W,), jnp.int32),
        spec((W, M), jnp.int32), spec((W + n_counts,), jnp.int32),
        spec((W,), jnp.int32), spec((C + M + 3,), jnp.int32),
        *pools).compile()
    text = compiled.as_text()
    assert rows in text
    for kernel, calls in kernels.items():
        assert len(re.findall(
            r"^\s*(?:ROOT )?%%mx_%s\.[\w.]* = .* custom-call\(" % kernel,
            text, re.M)) == calls, kernel
    assert text.count('custom_call_target="tpu_custom_call"') \
        == sum(kernels.values())
    mem = compiled.memory_analysis()
    pool_bytes = sum(int(np.prod(a.shape)) * a.dtype.itemsize
                     for a in pools)
    assert mem.alias_size_in_bytes >= pool_bytes, mem
    assert mem.temp_size_in_bytes < temp, mem        # no pool copy
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert 0 < total < 14.5e9, mem


def test_block_diffusion_programs_compile_and_fit(chip, monkeypatch):
    """``benchmark/configs/SDAR-30B-A3B-Chat.json`` at its published
    widths (2048 wide, 32 query heads over 4 key/value heads of 128, 128
    experts of 768, top 8, 151,936 rows, 7 layers, window 32, 512 bf16
    pages of 128 tokens): the ONE block-step program and the 256-rung
    prefill compiled for one described v5e. In each: the Mosaic kernels
    under the names a profile's reader looks for — the paged block-decode
    kernel a layer, over a row's block and the block after it (2 x 32
    kernel rows), and the in-place block write an array and layer, in
    front of it (step), the two
    grouped matmuls of every expert layer a program needs (the prefill
    drops the last layer's: nothing reads its output) — the planned
    bytes inside the chip with room for the reference that decides
    ``correct`` beside the weights, the donated pools updated in place
    and NO copy of them among the temporaries. The pool packs a token's
    four key heads into one 512-lane row: the arrays are ``(7, 512, 128,
    512)`` and what the compiler hands the step is their logical bytes
    (declared ``(..., 4, 128)`` a bf16 array gets the tile T(4,128)(2,1)
    here, so nothing is padded to 16 sublanes either way; the packed row
    is what gives the kernel a lane-aligned ``(S, 128)`` operand a
    head)."""
    from mxnet_tpu.serving import DecodeServer, kvcache
    from mxnet_tpu.serving.block_diffusion import BlockDiffusionMoEDecoderLM
    monkeypatch.setattr(fa, "_on_tpu", lambda: True)
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "SDAR-30B-A3B-Chat.json")) as f:
        cfg = json.load(f)
    srv = cfg["server"]["kwargs"]
    W, S, pages = srv["window"], srv["page_size"], srv["pool_pages"]
    rung = max(srv["seq_ladder"])
    M = -(-(rung + srv["max_new_tokens"]) // S)
    model = BlockDiffusionMoEDecoderLM(**cfg["model"]["kwargs"])
    assert model.held == (0, 128) and model.block_length == 4
    L, Q = model.n_layers, model.block_length
    params = jax.eval_shape(lambda: model.init_params(seed=0))
    weights = sum(int(np.prod(a.shape)) * a.dtype.itemsize
                  for a in params.values())
    assert 9.9e9 < weights < 10.05e9

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)

    tree = jax.tree_util.tree_map(lambda a: spec(a.shape, a.dtype), params)
    specs, dtype = kvcache.declared_arrays(model)
    layout = kvcache.cache_layout(specs, jnp.dtype(dtype))
    shapes = [shape for _n, shape, _d in layout.arrays(L, pages, S)]
    assert shapes == [(L, pages, S, 512)] * 2
    pool = spec(shapes[0], jnp.bfloat16)
    pool_bytes = 2 * int(np.prod(shapes[0])) * 2
    assert pool_bytes == pages * S * layout.token_bytes(L)
    holder = type("S", (), {"_model": model, "_window": W, "_block": Q})()
    n_counts = len(model.step_counters[1])

    def named(text, kernel):
        return re.findall(r"^\s*(?:ROOT )?%%mx_%s\.[\w.]* = .* custom-call\("
                          % kernel, text, re.M)

    step = jax.jit(lambda *a: DecodeServer._block_decode_fn(holder, *a),
                   donate_argnums=(8, 9)).lower(
        tree, spec((W, Q), jnp.int32), spec((W,), jnp.int32),
        spec((W,), jnp.int32), spec((W,), jnp.int32),
        spec((W, M), jnp.int32),
        spec((W * (Q + 2) + n_counts,), jnp.int32), spec((W,), jnp.int32),
        pool, pool).compile()
    text = step.as_text()
    # a row's block and the block after it: 2 W kernel rows of Q queries
    assert len(named(text, "block_decode")) == L
    assert ".bh%d.q%d.k%d.d128.bfloat16.kv4.paged" % (
        2 * W * model.n_heads, Q, M * S) in text
    # the committing rows' K and V, a layer, before the layer attends
    assert len(named(text, "block_write")) == 2 * L
    assert "mx_block_write.b%d.q%d.l1.s%d.d512.bfloat16" % (W, Q, S) in text
    assert len(named(text, "grouped_matmul")) == 2 * L
    assert ".e128.m4096.k2048.n768.bfloat16.r16.gated" in text
    assert text.count('custom_call_target="tpu_custom_call"') == 5 * L
    # only Q positions a row reach the head
    assert "f32[%d,%d,%d]" % (W, Q, model.vocab) in text
    assert "f32[%d,%d,%d]" % (W, 2 * Q, model.vocab) not in text
    mem = step.memory_analysis()
    assert mem.alias_size_in_bytes >= pool_bytes, mem
    assert mem.temp_size_in_bytes < 0.1e9, mem      # no pool copy
    planned = (mem.argument_size_in_bytes + mem.output_size_in_bytes
               + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert 10.8e9 < planned < 11.1e9, mem

    prefill = jax.jit(
        lambda *a: DecodeServer._block_prefill_fn(holder, *a),
        donate_argnums=(4, 5)).lower(
        tree, spec((1, rung), jnp.int32), spec((), jnp.int32),
        spec((M,), jnp.int32), pool, pool).compile()
    text = prefill.as_text()
    assert len(named(text, "grouped_matmul")) == 2 * (L - 1)
    mem = prefill.memory_analysis()
    assert mem.alias_size_in_bytes >= pool_bytes, mem
    assert mem.temp_size_in_bytes < 0.3e9, mem
    planned = (mem.argument_size_in_bytes + mem.output_size_in_bytes
               + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert planned < 11.1e9, mem


def _walk_calls(M, layer=1):
    """The three paged MXU kernels at a window of 8 rows and a table
    ``M`` columns wide, reading ``layer``, as (function, arguments)
    pairs."""
    Bn, S, W, R, P = 8, 128, 640, 512, 8 * 4 + 1
    f32, bf16, i32 = jnp.float32, jnp.bfloat16, jnp.int32
    sds = jax.ShapeDtypeStruct
    pool, tbl, lens = sds((2, P, S, W), bf16), sds((Bn, M), i32), \
        sds((Bn,), i32)
    packed = sds((2, P, S, 4 * 128), bf16)
    return {
        "mla_decode.q1": (
            lambda q, n, p, t, l: fa._pallas_latent_decode(
                q, n, p, layer, t, l, R, False),
            (sds((Bn, 128, W), bf16), sds((Bn, 1, W), bf16), pool, tbl,
             lens)),
        "mla_decode.q2": (
            lambda q, n, p, t, l: fa._pallas_latent_verify(
                q, n, p, layer, t, l, R, False),
            (sds((Bn, 2, 32, W), bf16), sds((Bn, 2, W), bf16), pool, tbl,
             lens)),
        "block_decode": (
            lambda q, kn, vn, kp, vp, t, l: fa._pallas_block_decode(
                q, kn, vn, kp, vp, layer, t, l, False),
            (sds((Bn, 4, 32, 128), bf16), sds((Bn, 4, 4, 128), bf16),
             sds((Bn, 4, 4, 128), bf16), packed, packed, tbl, lens)),
    }


def _pallas_calls(jaxpr):
    """Every ``pallas_call`` of a jaxpr, those inside a ``jit`` too."""
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            found.append(eqn)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            found += _pallas_calls(sub)
    return found


@pytest.mark.parametrize("kernel", ["mla_decode.q1", "mla_decode.q2",
                                    "block_decode"])
def test_paged_mxu_kernels_take_one_grid_step_a_row(kernel):
    """The kernel, not the grid, walks the pages: a table 10 wide and a
    table 40 wide give the same ``(rows,)`` grid (read from the jaxpr),
    the pools stay where they are (no block of them is an operand of the
    pipeline) and the table's width is left in the name alone."""
    for M in (10, 40):
        fn, args = _walk_calls(M)[kernel]
        calls = _pallas_calls(jax.make_jaxpr(fn)(*args).jaxpr)
        assert len(calls) == 1
        mapping = calls[0].params["grid_mapping"]
        assert tuple(mapping.grid) == (8,), (kernel, M, mapping.grid)
        pools = [bm for bm in mapping.block_mappings
                 if bm.array_aval.shape[:1] == (2,)]
        assert len(pools) == (2 if kernel == "block_decode" else 1)
        assert all("<any>" in str(bm.transformed_block_aval)
                   for bm in pools), pools
        assert ".k%d." % (M * 128) in calls[0].params["name"]
    # a program's calls, one a layer, are ONE traced kernel: the layer is
    # an operand (baked in, every layer traced and lowered its own, 6 s
    # of a seven-layer model's warm-up)
    both = jax.make_jaxpr(lambda *a: [
        _walk_calls(10, layer)[kernel][0](*a) for layer in (0, 1)])(*args)
    inner = [e.params["jaxpr"] for e in both.eqns
             if "jaxpr" in e.params and _pallas_calls(e.params["jaxpr"].jaxpr)]
    assert len(inner) == 2 and inner[0] is inner[1]
