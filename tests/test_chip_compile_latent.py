"""Sandbox compiles for the chip, continued from ``test_chip_compile.py``
(a file of its own so that no file is the floor of a ``--dist loadfile``
run): the latent-attention expert model's programs at
``dots.vlm1.inst``'s and ``Xing4.0-29B-A4B``'s published widths, compiled
by the TPU's own compiler for a DESCRIBED v5e. A compile that passes is
not a chip run."""
import json
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from test_chip_compile import ROOT, _step_holder, fa

pytestmark = pytest.mark.usefixtures("_persistent_cache_off")


def test_latent_moe_programs_compile_and_fit(chip, monkeypatch):
    """``benchmark/configs/dots.vlm1.inst.json`` at its published widths
    (7168 wide, 128 heads of 128+64 / 128, ranks 1536 and 512, experts
    of 2048, 16 of 256 held, 1 dense + 5 expert layers, window 64, 768
    bf16 pages of 128 x 640): the ``decode:step`` and the 256-rung
    ``decode:prefill`` programs compiled for one described v5e. In each:
    the Mosaic kernels under the names a profile's reader looks for —
    the paged latent decode kernel and the in-place row write (step),
    the flash kernel at 256-wide heads (prefill), the two grouped
    matmuls of every expert layer — the planned bytes inside the chip
    with room for the reference that decides ``correct`` beside the
    weights, the donated pool updated in place and NO copy of it among
    the temporaries (declared 576 wide, XLA laid the pool out token-minor
    and copied 0.68 GB three times a step; written by XLA's own row
    writes, it moved the layer axis next to the lanes and copied twice)."""
    from mxnet_tpu.serving import DecodeServer
    from mxnet_tpu.serving.latent_moe import LatentMoEDecoderLM
    monkeypatch.setattr(fa, "_on_tpu", lambda: True)
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "dots.vlm1.inst.json")) as f:
        cfg = json.load(f)
    srv = cfg["server"]["kwargs"]
    W, S, pages = srv["window"], srv["page_size"], srv["pool_pages"]
    rung = max(srv["seq_ladder"])
    M = -(-(rung + srv["max_new_tokens"]) // S)
    model = LatentMoEDecoderLM(**cfg["model"]["kwargs"])
    assert model.held == (0, 16) and model.row_width == 640
    L, moe_layers = model.n_layers, model.n_moe_layers
    params = jax.eval_shape(lambda: model.init_params(seed=0))
    weights = sum(int(np.prod(a.shape)) * a.dtype.itemsize
                  for a in params.values())
    assert 10.9e9 < weights < 11.1e9

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)

    tree = jax.tree_util.tree_map(lambda a: spec(a.shape, a.dtype), params)
    pool = spec((L, pages, S, model.row_width), jnp.bfloat16)
    pool_bytes = L * pages * S * model.row_width * 2
    holder = _step_holder(model)
    n_counts = len(model.step_counters[1])

    def named(text, kernel):
        return re.findall(r"^\s*(?:ROOT )?%%mx_%s\.[\w.]* = .* custom-call\("
                          % kernel, text, re.M)

    step = jax.jit(lambda *a: DecodeServer._decode_fn(holder, *a),
                   donate_argnums=(6,)).lower(
        tree, spec((W,), jnp.int32), spec((W,), jnp.int32),
        spec((W, M), jnp.int32), spec((W + n_counts,), jnp.int32),
        spec((W,), jnp.int32), pool).compile()
    text = step.as_text()
    assert len(named(text, "mla_decode")) == L
    assert ".k%d.d%d.bfloat16.r%d.paged" % (
        M * S, model.row_width, model.kv_rank) in text
    assert len(named(text, "latent_write")) == 1
    assert len(named(text, "grouped_matmul")) == 2 * moe_layers
    # a step's 32 slots an expert keep the 16-row tiles they were drawn for
    assert ".e16.m768.k7168.n2048.bfloat16.r16.gated" in text
    assert text.count('custom_call_target="tpu_custom_call"') \
        == L + 1 + 2 * moe_layers
    mem = step.memory_analysis()
    assert mem.alias_size_in_bytes >= pool_bytes, mem
    assert mem.temp_size_in_bytes < 0.1e9, mem      # no pool copy
    planned = (mem.argument_size_in_bytes + mem.output_size_in_bytes
               + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert 11.5e9 < planned < 12.5e9, mem

    prefill = jax.jit(lambda *a: DecodeServer._prefill_fn(holder, *a),
                      donate_argnums=(4,)).lower(
        tree, spec((1, rung), jnp.int32), spec((), jnp.int32),
        spec((M,), jnp.int32), pool).compile()
    text = prefill.as_text()
    assert len(named(text, "flash_fwd")) == L
    assert ".q%d.k%d.d256.bfloat16" % (rung, rung) in text
    assert len(named(text, "grouped_matmul")) == 2 * moe_layers
    mem = prefill.memory_analysis()
    assert mem.alias_size_in_bytes >= pool_bytes, mem
    assert mem.temp_size_in_bytes < 0.5e9, mem
    planned = (mem.argument_size_in_bytes + mem.output_size_in_bytes
               + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert planned < 12.5e9, mem


def test_speculative_latent_programs_compile_and_fit(chip, monkeypatch):
    """``benchmark/configs/Xing4.0-29B-A4B.json`` at its published widths
    (3584 wide in 4 residual streams, 32 heads of 128+64 / 128, ranks 768
    and 512, all 64 experts of 1024, 131,072 rows, 1 dense + 5 expert
    layers and the next-token module's block, window 64, 768 bf16 pages
    of 128 x 640 in 7 cache layers): the ONE speculative step program
    and the 256-rung prefill compiled for one described v5e. In the
    step: the two-query paged latent kernel a block (64 = 2 x 32 query
    rows against a page in one product) under the name a profile's
    reader looks for, ONE in-place write of both new rows over all 7
    cache layers, the two grouped matmuls of every expert layer, the
    module's among them; the planned bytes inside the chip with room
    for the reference that decides ``correct`` beside the weights, the
    donated pool updated in place and NO copy of it among the
    temporaries. The table covers the two positions a step dispatched
    ahead may write past a row's budget: 11 pages, not 10."""
    from mxnet_tpu.serving import DecodeServer
    from mxnet_tpu.serving.latent_moe import LatentMoEDecoderLM
    monkeypatch.setattr(fa, "_on_tpu", lambda: True)
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "Xing4.0-29B-A4B.json")) as f:
        cfg = json.load(f)
    srv = cfg["server"]["kwargs"]
    W, S, pages = srv["window"], srv["page_size"], srv["pool_pages"]
    rung = max(srv["seq_ladder"])
    M = -(-(rung + srv["max_new_tokens"] + 2) // S)
    model = LatentMoEDecoderLM(**cfg["model"]["kwargs"])
    assert model.held == (0, 64) and model.row_width == 640
    assert (model.hc, model.draft_length, model.cache_layers) == (4, 1, 7)
    L, moe_layers, H = model.cache_layers, model.n_moe_layers, model.n_heads
    params = jax.eval_shape(lambda: model.init_params(seed=0))
    weights = sum(int(np.prod(a.shape)) * a.dtype.itemsize
                  for a in params.values())
    assert 11.1e9 < weights < 11.2e9

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)

    tree = jax.tree_util.tree_map(lambda a: spec(a.shape, a.dtype), params)
    pool = spec((L, pages, S, model.row_width), jnp.bfloat16)
    pool_bytes = L * pages * S * model.row_width * 2
    holder = type("S", (), {"_model": model, "_window": W,
                            "_counters": model.step_counters})()
    n_counts = len(model.step_counters[1])

    def named(text, kernel):
        return re.findall(r"^\s*(?:ROOT )?%%mx_%s\.[\w.]* = .* custom-call\("
                          % kernel, text, re.M)

    step = jax.jit(lambda *a: DecodeServer._spec_decode_fn(holder, *a),
                   donate_argnums=(6,)).lower(
        tree, spec((W, 2), jnp.int32), spec((W,), jnp.int32),
        spec((W, M), jnp.int32), spec((W * 5 + n_counts,), jnp.int32),
        spec((W,), jnp.int32), pool).compile()
    text = step.as_text()
    assert len(named(text, "mla_decode")) == L
    assert ".bh%d.q2.k%d.d%d.bfloat16.r%d.paged" % (
        W * 2 * H, M * S, model.row_width, model.kv_rank) in text
    assert len(named(text, "latent_write")) == 1
    assert "mx_latent_write.b%d.q2.l%d.s%d" % (W, L, S) in text
    assert len(named(text, "grouped_matmul")) == 2 * moe_layers
    assert ".e64.m1536.k3584.n1024.bfloat16.r16.gated" in text
    assert text.count('custom_call_target="tpu_custom_call"') \
        == L + 1 + 2 * moe_layers
    mem = step.memory_analysis()
    assert mem.alias_size_in_bytes >= pool_bytes, mem
    assert mem.temp_size_in_bytes < 0.15e9, mem      # no pool copy
    planned = (mem.argument_size_in_bytes + mem.output_size_in_bytes
               + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert 11.9e9 < planned < 12.4e9, mem

    prefill = jax.jit(lambda *a: DecodeServer._spec_prefill_fn(holder, *a),
                      donate_argnums=(4,)).lower(
        tree, spec((1, rung), jnp.int32), spec((), jnp.int32),
        spec((M,), jnp.int32), pool).compile()
    text = prefill.as_text()
    assert len(named(text, "flash_fwd")) == L
    assert ".q%d.k%d.d256.bfloat16" % (rung, rung) in text
    assert len(named(text, "grouped_matmul")) == 2 * moe_layers
    mem = prefill.memory_analysis()
    assert mem.alias_size_in_bytes >= pool_bytes, mem
    assert mem.temp_size_in_bytes < 0.5e9, mem
    planned = (mem.argument_size_in_bytes + mem.output_size_in_bytes
               + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert planned < 12.5e9, mem
