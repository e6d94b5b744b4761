"""What guards the chip path from the sandbox: ``chip_smoke.py`` refuses
to run off the TPU, the one compile cache sits where the environment or
the checkout says, the kernel-or-jnp choice is made from the shape in
one counted place, and an unknown device has no peak."""
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(code_or_script, cwd, **env):
    full = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT)
    full.pop("JAX_COMPILATION_CACHE_DIR", None)
    full.update(env)
    argv = [sys.executable] + (
        [code_or_script] if code_or_script.endswith(".py")
        else ["-c", code_or_script])
    return subprocess.run(argv, cwd=cwd, env=full, capture_output=True,
                          text=True, timeout=120)


def test_chip_smoke_refuses_the_cpu(tmp_path):
    """No accelerator: a non-zero exit, a message, and no result line."""
    r = _run(os.path.join(ROOT, "chip_smoke.py"), str(tmp_path))
    assert r.returncode != 0
    assert '"ok"' not in r.stdout
    assert "needs a TPU" in r.stderr


_WHERE = ("from mxnet_tpu import runtime; import jax; "
          "print(runtime.enable_compile_cache()); "
          "print(jax.config.jax_compilation_cache_dir)")


def test_compile_cache_honours_the_environment(tmp_path):
    """With JAX_COMPILATION_CACHE_DIR set, nothing is set in code."""
    want = str(tmp_path / "elsewhere")
    r = _run(_WHERE, str(tmp_path), JAX_COMPILATION_CACHE_DIR=want)
    assert r.returncode == 0, r.stderr
    assert r.stdout.split() == [want, want]


def test_compile_cache_path_is_fixed_in_the_checkout(tmp_path):
    """Unset: the same absolute in-checkout path from two different
    working directories — a cache that moves never hits."""
    other = tmp_path / "other"
    other.mkdir()
    r = _run("import os; %s; os.chdir(%r); %s"
             % (_WHERE, str(other), _WHERE.split("; ", 1)[1]),
             str(tmp_path))
    assert r.returncode == 0, r.stderr
    assert r.stdout.split() == [os.path.join(ROOT, ".jax_cache")] * 4


def _flash_module():
    import mxnet_tpu.parallel  # noqa: F401
    return sys.modules["mxnet_tpu.parallel.flash_attention"]


@pytest.mark.parametrize("head_dim,blocks,tiles", [
    (128, (512, 512), True),
    (256, (128,), True),
    (8, (512, 512), False),        # the CPU tests' toy head
    (64, (128,), False),
    (128, (64,), False),           # a 64-key cache bucket
], ids=["d128", "d256", "d8", "d64", "block64"])
def test_shape_predicate(head_dim, blocks, tiles):
    assert _flash_module().tiles_on_chip(head_dim, *blocks) is tiles


@pytest.mark.parametrize("head_dim,path", [(128, "pallas"), (8, "jnp")])
def test_kernel_choice_is_made_from_the_shape_and_counted(
        monkeypatch, head_dim, path):
    """On the TPU (steered here: the sandbox's JAX sees a CPU) D=128
    takes the kernel and head_dim 8 the jnp composition, each choice
    counted."""
    from mxnet_tpu import profiler
    fa = _flash_module()
    monkeypatch.setattr(fa, "_on_tpu", lambda: True)
    before = profiler.counters().get("flash_decode_" + path, 0)
    assert fa._choose_path("flash_decode", head_dim, (128,), False) \
        is (path == "pallas")
    assert profiler.counters()["flash_decode_" + path] == before + 1


def test_off_the_tpu_only_force_takes_the_kernel_interpreted():
    fa = _flash_module()
    seen = []

    def kernel(interpret, x):
        seen.append(interpret)
        return x

    assert fa._dispatch("flash_attention", 128, (512, 512), False,
                        kernel, lambda x: -x, 1.0) == -1.0
    assert fa._dispatch("flash_attention", 8, (64, 64), True,
                        kernel, lambda x: -x, 1.0) == 1.0
    assert seen == [True]


def test_unknown_device_kind_has_no_peak():
    from mxnet_tpu import compile_watch
    assert compile_watch._lookup_peak(compile_watch.PEAK_FLOPS,
                                      "TPU v5 lite") == 197e12
    assert compile_watch._lookup_peak(compile_watch.PEAK_BW,
                                      "TPU v5 lite") == 819e9
    for table in (compile_watch.PEAK_FLOPS, compile_watch.PEAK_BW):
        with pytest.raises(KeyError, match="not in the peak table"):
            compile_watch._lookup_peak(table, "TPU v9 imaginary")


@pytest.mark.parametrize("platform,interpret", [("tpu", False),
                                                ("cpu", True)])
def test_rtc_interpret_follows_the_resolved_device(monkeypatch, platform,
                                                   interpret):
    """``mx.gpu(0)`` is the accelerator on this stack: a Pallas module
    launched under it decides interpret mode from the jax.Device the
    context resolves to, not from the context's name."""
    import numpy as np
    import mxnet_tpu as mx
    from jax.experimental import pallas as pl
    seen = {}
    real = pl.pallas_call

    def spy(body, **kw):
        seen["interpret"] = kw["interpret"]
        return real(body, **dict(kw, interpret=True))

    monkeypatch.setattr(pl, "pallas_call", spy)
    monkeypatch.setattr(
        mx.context.Context, "jax_device",
        lambda self: type("D", (), {"platform": platform})())
    k = mx.rtc.PallasModule(
        "def twice(x_ref, y_ref):\n    y_ref[...] = 2 * x_ref[...]\n"
    ).get_kernel("twice", "const float *x, float *y")
    y = mx.nd.zeros((2, 3))
    k.launch((mx.nd.ones((2, 3)), y), mx.gpu(0))
    assert seen["interpret"] is interpret
    np.testing.assert_allclose(y.asnumpy(), 2.0)


def test_accelerator_context_on_the_cpu_mesh_is_counted():
    import mxnet_tpu as mx
    from mxnet_tpu import profiler
    before = profiler.counters().get("context_accelerator_on_cpu", 0)
    assert mx.tpu(0).jax_device().platform == "cpu"
    assert profiler.counters()["context_accelerator_on_cpu"] == before + 1
