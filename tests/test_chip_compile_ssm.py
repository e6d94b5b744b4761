"""Sandbox compiles for the chip, continued from ``test_chip_compile.py``
(a file of its own so that no file is the floor of a ``--dist loadfile``
run): the state form's step and widest mixed step at
``AI21-Jamba2-3B``'s published widths, whole — 28 layers, the whole
vocabulary — compiled by the TPU's own compiler for a DESCRIBED v5e. A
compile that passes is not a chip run."""
import json
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from test_chip_compile import ROOT, fa
from test_chip_compile_state import _named, _state_holder

pytestmark = pytest.mark.usefixtures("_persistent_cache_off")


def _case(chip):
    """``benchmark/configs/AI21-Jamba2-3B.json`` as both cases compile
    it: the model, the step programs' arguments in front of the carried
    arrays (the mixed step's chunk goes between them) and the carried
    arrays — K and V pages of two cache layers, ``h`` and the
    convolution rows of 26 state layers and 128 rows."""
    import types
    from mxnet_tpu.serving.ssm_hybrid import SSMHybridDecoderLM
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "AI21-Jamba2-3B.json")) as f:
        cfg = json.load(f)
    srv = cfg["server"]["kwargs"]
    W, S, pages = srv["window"], srv["page_size"], srv["pool_pages"]
    M = -(-(max(srv["seq_ladder"]) + srv["max_new_tokens"]) // S)
    model = SSMHybridDecoderLM(**cfg["model"]["kwargs"])
    assert (model.cache_layers, model.state_layers, W, M) == (2, 26, 128, 12)
    N, E, K = model.d_state, model.d_inner, model.conv
    params = jax.eval_shape(lambda: model.init_params(seed=0))
    weights = sum(int(np.prod(a.shape)) * a.dtype.itemsize
                  for a in params.values())
    assert 6.05e9 < weights < 6.08e9

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)

    carried = (spec((2, pages, S, 128), jnp.bfloat16),
               spec((2, pages, S, 128), jnp.bfloat16),
               spec((26, W, N, E), jnp.float32),
               spec((26, W, (K - 1) * E), jnp.bfloat16))
    carried_bytes = sum(int(np.prod(a.shape)) * a.dtype.itemsize
                        for a in carried)
    assert 1.40e9 < carried_bytes < 1.42e9
    feed = (jax.tree_util.tree_map(lambda a: spec(a.shape, a.dtype), params),
            spec((W,), jnp.int32), spec((W,), jnp.int32),
            spec((W,), jnp.int32), spec((), jnp.int32),
            spec((W, M), jnp.int32), spec((W,), jnp.int32),
            spec((W,), jnp.int32))
    return types.SimpleNamespace(
        model=model, W=W, M=M, feed=feed, carried=carried,
        carried_bytes=carried_bytes, spec=spec,
        holder=_state_holder(model, _window=W, _max_pages=M))


@pytest.mark.parametrize("lanes", [0, 512], ids=["step", "mixed-c512"])
def test_ssm_hybrid_step_programs_compile_and_fit(chip, monkeypatch, lanes):
    """``decode:step`` and ``decode:step:chunk:c512`` of the whole model
    for one described v5e: ``mx_ssm_conv`` and ``mx_ssm_step`` a
    state-space layer, the second under the name a profile's reader looks
    for (and ``mx_ssm_chunk`` beside them on the mixed step: the rows
    keep their kernels beside a chunk) — the step runs in slot order, so
    NO operation outside a kernel gathers, shifts or writes a plane of
    the ``conv`` rows (``bf16[128,15360]``) and none stacks ``delta`` on
    ``delta u`` (``f32[128,2,5120]``): what PR 53 took out of a step — the
    20-over-1 attention layers through the packed pool's multi-query
    kernel and its in-place write, nothing fallen to ``jnp``; the donated
    pages AND the donated state arrays updated in place, NO copy of
    either among the temporaries (``h`` is 1.09 GB: one copy a layer
    would triple the step), and the planned bytes 47-48% of the chip."""
    from mxnet_tpu import profiler
    from mxnet_tpu.serving import DecodeServer
    monkeypatch.setattr(fa, "_on_tpu", lambda: True)
    case = _case(chip)
    before = dict(profiler.counters())
    if lanes:
        step = jax.jit(
            lambda *a: DecodeServer._state_decode_fn_chunk(case.holder, *a),
            donate_argnums=(9, 10, 11, 12)).lower(
            *case.feed, case.spec((lanes + case.M + 3,), jnp.int32),
            *case.carried).compile()
    else:
        step = jax.jit(
            lambda *a: DecodeServer._state_decode_fn(case.holder, *a),
            donate_argnums=(8, 9, 10, 11)).lower(
            *case.feed, *case.carried).compile()
    chose = {k: v - before.get(k, 0)
             for k, v in profiler.counters().items()
             if k.endswith(("_pallas", "_jnp")) and v != before.get(k, 0)}
    assert not any(k.endswith("_jnp") for k in chose), chose
    assert chose["ssm_step_pallas"] == chose["ssm_conv_pallas"] == 26
    assert chose["block_decode_pallas"] == 2
    text = step.as_text()
    assert len(_named(text, "ssm_step")) == 26
    assert "mx_ssm_step.b128.e5120.n16" in text
    assert len(_named(text, "ssm_conv")) == 26
    assert "mx_ssm_conv.b128.e5120.k4" in text
    assert len(_named(text, "block_decode")) == 2
    assert len(_named(text, "block_write")) == 2
    assert len(_named(text, "ssm_chunk")) == (26 if lanes else 0)
    if lanes:
        assert chose["ssm_chunk_pallas"] == 26
        assert "mx_ssm_chunk.c512.e5120.n16" in text
    assert text.count('custom_call_target="tpu_custom_call"') \
        == 56 + (26 if lanes else 0)
    # what walking the state in slot order took away: no plane of the
    # ``conv`` rows gathered, shifted or written outside the kernel, and
    # no ``delta`` stacked on ``delta u``
    assert not re.findall(r"^\s*(?:ROOT )?%[\w.-]+ = bf16\[128,15360\]", text,
                          re.M)
    assert "f32[128,2,5120]" not in text
    mem = step.memory_analysis()
    assert mem.alias_size_in_bytes >= case.carried_bytes, mem
    assert mem.temp_size_in_bytes < 0.2e9, mem      # no state or pool copy
    planned = (mem.argument_size_in_bytes + mem.output_size_in_bytes
               + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert 7.4e9 < planned < 7.7e9, mem
