"""NDArray unit tests (mirrors tests/python/unittest/test_ndarray.py)."""
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu.test_utils import assert_almost_equal, default_context


def test_creation():
    x = mx.nd.zeros((3, 4))
    assert x.shape == (3, 4)
    assert x.dtype == np.float32
    assert (x.asnumpy() == 0).all()
    y = mx.nd.ones((2, 2), dtype="int32")
    assert y.dtype == np.int32
    z = mx.nd.full((2, 3), 7.5)
    assert (z.asnumpy() == 7.5).all()
    a = mx.nd.array([[1, 2], [3, 4]])
    assert a.dtype == np.float32
    b = mx.nd.array(np.array([1, 2], dtype=np.int32))
    assert b.dtype == np.int32


def test_arange_linspace_eye():
    assert_almost_equal(mx.nd.arange(5).asnumpy(), np.arange(5,
                        dtype=np.float32))
    assert_almost_equal(mx.nd.arange(2, 10, 2).asnumpy(),
                        np.arange(2, 10, 2, dtype=np.float32))
    assert_almost_equal(mx.nd.linspace(0, 1, 5).asnumpy(),
                        np.linspace(0, 1, 5, dtype=np.float32))
    assert_almost_equal(mx.nd.eye(3).asnumpy(), np.eye(3, dtype=np.float32))


def test_elementwise():
    a = mx.nd.array([[1., 2.], [3., 4.]])
    b = mx.nd.array([[5., 6.], [7., 8.]])
    assert_almost_equal((a + b).asnumpy(), [[6, 8], [10, 12]])
    assert_almost_equal((a - b).asnumpy(), [[-4, -4], [-4, -4]])
    assert_almost_equal((a * b).asnumpy(), [[5, 12], [21, 32]])
    assert_almost_equal((b / a).asnumpy(), [[5, 3], [7 / 3, 2]], rtol=1e-6)
    assert_almost_equal((a ** 2).asnumpy(), [[1, 4], [9, 16]])
    assert_almost_equal((2 ** a).asnumpy(), [[2, 4], [8, 16]])
    assert_almost_equal((1 - a).asnumpy(), [[0, -1], [-2, -3]])
    assert_almost_equal((10 / a).asnumpy(), [[10, 5], [10 / 3, 2.5]],
                        rtol=1e-6)
    assert_almost_equal((-a).asnumpy(), [[-1, -2], [-3, -4]])
    assert_almost_equal(abs(-a).asnumpy(), a.asnumpy())


def test_inplace_ops():
    a = mx.nd.ones((2, 2))
    a += 1
    assert (a.asnumpy() == 2).all()
    a *= 3
    assert (a.asnumpy() == 6).all()
    a -= 2
    assert (a.asnumpy() == 4).all()
    a /= 4
    assert (a.asnumpy() == 1).all()


def test_comparisons():
    a = mx.nd.array([1., 2., 3.])
    b = mx.nd.array([3., 2., 1.])
    assert_almost_equal((a == b).asnumpy(), [0, 1, 0])
    assert_almost_equal((a != b).asnumpy(), [1, 0, 1])
    assert_almost_equal((a > b).asnumpy(), [0, 0, 1])
    assert_almost_equal((a >= 2).asnumpy(), [0, 1, 1])
    assert_almost_equal((a < b).asnumpy(), [1, 0, 0])
    # comparison keeps input dtype (MXNet convention)
    assert (a == b).dtype == np.float32


def test_indexing():
    a = mx.nd.array(np.arange(24).reshape(2, 3, 4))
    assert_almost_equal(a[0].asnumpy(), np.arange(12).reshape(3, 4))
    assert_almost_equal(a[1, 2].asnumpy(), np.arange(20, 24))
    assert_almost_equal(a[:, 1].asnumpy(),
                        np.arange(24).reshape(2, 3, 4)[:, 1])
    assert_almost_equal(a[0, 1, 2].asnumpy(), 6)
    assert_almost_equal(a[:, :, 1:3].asnumpy(),
                        np.arange(24).reshape(2, 3, 4)[:, :, 1:3])


def test_indexing_bool_scalar():
    """x[True]/x[False] follow numpy 0-d-mask semantics (bool is an int
    subclass — a bare bool must NOT be treated as a row index)."""
    n = np.arange(6).reshape(2, 3)
    a = mx.nd.array(n)
    assert a[True].shape == n[True].shape == (1, 2, 3)
    assert_almost_equal(a[True].asnumpy(), n[True])
    assert a[False].shape == n[False].shape == (0, 2, 3)
    assert a[np.bool_(True)].shape == (1, 2, 3)


def test_indexing_int_shares_compiled_program():
    """x[0], x[1], ... must share ONE compiled program: the integer is
    an array input, not a baked attribute."""
    from mxnet_tpu.ops import registry
    a = mx.nd.array(np.arange(32).reshape(8, 4))
    _ = a[0]
    before = len(registry._jit_cache) if hasattr(registry, "_jit_cache") \
        else None
    for i in range(1, 8):
        assert_almost_equal(a[i].asnumpy(), np.arange(32).reshape(8, 4)[i])
        assert_almost_equal(a[-i].asnumpy(),
                            np.arange(32).reshape(8, 4)[-i])
    if before is not None:
        assert len(registry._jit_cache) == before, \
            "integer indexing recompiles per index value"


def test_setitem():
    a = mx.nd.zeros((3, 3))
    a[1] = 1.0
    assert_almost_equal(a.asnumpy(), [[0, 0, 0], [1, 1, 1], [0, 0, 0]])
    a[0, 2] = 5.0
    assert a.asnumpy()[0, 2] == 5.0
    a[:] = 2.0
    assert (a.asnumpy() == 2).all()
    a[1:3] = mx.nd.ones((2, 3)) * 7
    assert (a.asnumpy()[1:] == 7).all()


def test_reshape_special_codes():
    a = mx.nd.zeros((2, 3, 4))
    assert a.reshape((4, 6)).shape == (4, 6)
    assert a.reshape((-1,)).shape == (24,)
    assert a.reshape((0, -1)).shape == (2, 12)
    assert a.reshape((-2,)).shape == (2, 3, 4)
    assert a.reshape((0, -2)).shape == (2, 3, 4)
    assert a.reshape((-3, 4)).shape == (6, 4)
    assert a.reshape((0, -4, 3, 1, 4)).shape == (2, 3, 1, 4)
    assert a.reshape((-4, 1, 2, -2)).shape == (1, 2, 3, 4)
    assert a.reshape((2, -4, -1, 3, 4)).shape == (2, 1, 3, 4)


def test_transpose_ops():
    a = mx.nd.array(np.arange(6).reshape(2, 3))
    assert_almost_equal(a.T.asnumpy(), np.arange(6).reshape(2, 3).T)
    b = mx.nd.array(np.arange(24).reshape(2, 3, 4))
    assert b.transpose(2, 0, 1).shape == (4, 2, 3)
    assert b.swapaxes(0, 2).shape == (4, 3, 2)


def test_reductions():
    x = np.random.uniform(-1, 1, (3, 4, 5)).astype(np.float32)
    a = mx.nd.array(x)
    assert_almost_equal(a.sum().asnumpy(), x.sum(), rtol=1e-5, atol=1e-5)
    assert_almost_equal(a.sum(axis=1).asnumpy(), x.sum(axis=1), rtol=1e-5,
                        atol=1e-5)
    assert_almost_equal(a.mean(axis=(0, 2)).asnumpy(), x.mean(axis=(0, 2)),
                        rtol=1e-5, atol=1e-5)
    assert_almost_equal(a.max().asnumpy(), x.max())
    assert_almost_equal(a.min(axis=2).asnumpy(), x.min(axis=2))
    assert_almost_equal(a.argmax(axis=1).asnumpy(), x.argmax(axis=1))
    assert_almost_equal(a.norm().asnumpy(), np.linalg.norm(x.reshape(-1)),
                        rtol=1e-5)


def test_dot():
    x = np.random.uniform(-1, 1, (4, 5)).astype(np.float32)
    y = np.random.uniform(-1, 1, (5, 6)).astype(np.float32)
    a, b = mx.nd.array(x), mx.nd.array(y)
    assert_almost_equal(mx.nd.dot(a, b).asnumpy(), x.dot(y), rtol=1e-5,
                        atol=1e-5)
    assert_almost_equal(mx.nd.dot(a, a, transpose_b=True).asnumpy(),
                        x.dot(x.T), rtol=1e-5, atol=1e-5)
    # batch dot
    p = np.random.uniform(-1, 1, (3, 4, 5)).astype(np.float32)
    q = np.random.uniform(-1, 1, (3, 5, 2)).astype(np.float32)
    assert_almost_equal(
        mx.nd.batch_dot(mx.nd.array(p), mx.nd.array(q)).asnumpy(),
        np.matmul(p, q), rtol=1e-5, atol=1e-5)


def test_broadcast():
    a = mx.nd.array([[1.], [2.]])
    b = a.broadcast_to((2, 3))
    assert_almost_equal(b.asnumpy(), [[1, 1, 1], [2, 2, 2]])
    c = mx.nd.broadcast_add(mx.nd.ones((2, 1)), mx.nd.ones((1, 3)))
    assert c.shape == (2, 3)
    assert (c.asnumpy() == 2).all()


def test_concat_split_stack():
    a = mx.nd.ones((2, 3))
    b = mx.nd.zeros((2, 3))
    c = mx.nd.concat(a, b, dim=0)
    assert c.shape == (4, 3)
    d = mx.nd.stack(a, b, axis=0)
    assert d.shape == (2, 2, 3)
    parts = mx.nd.split(c, num_outputs=2, axis=0)
    assert len(parts) == 2
    assert_almost_equal(parts[0].asnumpy(), a.asnumpy())
    s = mx.nd.split(mx.nd.ones((2, 4)), num_outputs=4, axis=1,
                    squeeze_axis=True)
    assert s[0].shape == (2,)


def test_astype_copy():
    a = mx.nd.array([1.5, 2.5])
    b = a.astype("int32")
    assert b.dtype == np.int32
    c = a.copy()
    c[:] = 0
    assert (a.asnumpy() == [1.5, 2.5]).all()


def test_take_embedding():
    w = np.random.uniform(-1, 1, (10, 4)).astype(np.float32)
    idx = np.array([1, 3, 5], dtype=np.float32)
    out = mx.nd.Embedding(mx.nd.array(idx), mx.nd.array(w), input_dim=10,
                          output_dim=4)
    assert_almost_equal(out.asnumpy(), w[[1, 3, 5]])
    t = mx.nd.take(mx.nd.array(w), mx.nd.array(idx))
    assert_almost_equal(t.asnumpy(), w[[1, 3, 5]])


def test_one_hot_pick():
    oh = mx.nd.one_hot(mx.nd.array([0, 2]), depth=3)
    assert_almost_equal(oh.asnumpy(), [[1, 0, 0], [0, 0, 1]])
    data = mx.nd.array([[1., 2., 3.], [4., 5., 6.]])
    p = mx.nd.pick(data, mx.nd.array([1, 2]), axis=1)
    assert_almost_equal(p.asnumpy(), [2, 6])


def test_ordering():
    x = np.random.permutation(20).astype(np.float32).reshape(4, 5)
    a = mx.nd.array(x)
    assert_almost_equal(a.sort(axis=1).asnumpy(), np.sort(x, axis=1))
    assert_almost_equal(a.argsort(axis=1).asnumpy(), np.argsort(x, axis=1))
    tk = mx.nd.topk(a, axis=1, k=2, ret_typ="value")
    exp = np.sort(x, axis=1)[:, ::-1][:, :2]
    assert_almost_equal(tk.asnumpy(), exp)


def test_wait_and_scalar():
    a = mx.nd.ones((1,))
    a.wait_to_read()
    assert a.asscalar() == 1.0
    assert float(a) == 1.0
    assert int(mx.nd.array([3.7])) == 3


def test_save_load(tmp_path):
    fname = str(tmp_path / "arrs")
    arrs = [mx.nd.ones((2, 2)), mx.nd.zeros((3,))]
    mx.nd.save(fname, arrs)
    loaded = mx.nd.load(fname)
    assert isinstance(loaded, list) and len(loaded) == 2
    assert_almost_equal(loaded[0].asnumpy(), arrs[0].asnumpy())
    d = {"w": mx.nd.ones((2,)), "b": mx.nd.zeros((2,))}
    mx.nd.save(fname, d)
    loaded = mx.nd.load(fname)
    assert set(loaded.keys()) == {"w", "b"}


def test_random_basic():
    mx.random.seed(42)
    a = mx.nd.random.uniform(0, 1, shape=(100,))
    b = mx.nd.random.uniform(0, 1, shape=(100,))
    assert not np.allclose(a.asnumpy(), b.asnumpy())
    assert (a.asnumpy() >= 0).all() and (a.asnumpy() <= 1).all()
    mx.random.seed(42)
    a2 = mx.nd.random.uniform(0, 1, shape=(100,))
    assert_almost_equal(a.asnumpy(), a2.asnumpy())
    n = mx.nd.random.normal(0, 1, shape=(2000,))
    assert abs(n.asnumpy().mean()) < 0.2
    r = mx.nd.random.randint(0, 10, shape=(50,))
    assert r.dtype == np.int32
    assert (r.asnumpy() >= 0).all() and (r.asnumpy() < 10).all()


def test_context_placement():
    ctx = default_context()
    x = mx.nd.ones((2, 2), ctx=ctx)
    assert x.context == ctx
    y = x.as_in_context(mx.cpu())
    assert y.context == mx.cpu()


def test_where_clip():
    cond = mx.nd.array([1., 0., 1.])
    x = mx.nd.array([1., 2., 3.])
    y = mx.nd.array([4., 5., 6.])
    assert_almost_equal(mx.nd.where(cond, x, y).asnumpy(), [1, 5, 3])
    assert_almost_equal(mx.nd.clip(y, 4.5, 5.5).asnumpy(), [4.5, 5, 5.5])


def test_tile_repeat_pad():
    a = mx.nd.array([[1., 2.], [3., 4.]])
    assert_almost_equal(a.tile((2, 1)).asnumpy(),
                        np.tile(a.asnumpy(), (2, 1)))
    assert_almost_equal(a.repeat(2, axis=0).asnumpy(),
                        np.repeat(a.asnumpy(), 2, axis=0))
    x4 = mx.nd.ones((1, 1, 2, 2))
    p = mx.nd.Pad(x4, mode="constant", pad_width=(0, 0, 0, 0, 1, 1, 1, 1),
                  constant_value=9)
    assert p.shape == (1, 1, 4, 4)
    assert p.asnumpy()[0, 0, 0, 0] == 9


# ---------------------------------------------------------------------------
# mx.nd.array's contract outside any input pipeline: dtype, values, ctx
# and device of the result. The constructor settles the dtype on the host
# and sends the host array straight to the context's device.
# ---------------------------------------------------------------------------

def _bf16(values):
    import ml_dtypes
    return np.asarray(values).astype(ml_dtypes.bfloat16)


_ARRAY_CASES = {
    # name: (source, kwargs, dtype, values, ctx, device index, committed)
    "list": (lambda: [[1, 2], [3, 4]], {}, "float32",
             [[1, 2], [3, 4]], mx.cpu(0), 0, True),
    "list_of_floats": (lambda: [1.5, 2.5], {}, "float32", [1.5, 2.5],
                       mx.cpu(0), 0, True),
    "python_scalar": (lambda: 3, {}, "float32", 3, mx.cpu(0), 0, True),
    "numpy_scalar": (lambda: np.float64(2.5), {}, "float32", 2.5,
                     mx.cpu(0), 0, True),
    "float64": (lambda: np.arange(4, dtype=np.float64) / 3, {}, "float32",
                (np.arange(4) / 3).astype(np.float32), mx.cpu(0), 0, True),
    "float32": (lambda: np.arange(4, dtype=np.float32), {}, "float32",
                [0, 1, 2, 3], mx.cpu(0), 0, True),
    "int64_demoted": (lambda: np.array([1, 2 ** 31 + 5, -7], np.int64), {},
                      "int32", [1, -2147483643, -7], mx.cpu(0), 0, True),
    "int32": (lambda: np.arange(4, dtype=np.int32), {}, "int32",
              [0, 1, 2, 3], mx.cpu(0), 0, True),
    "uint8_kept": (lambda: np.arange(4, dtype=np.uint8), {}, "uint8",
                   [0, 1, 2, 3], mx.cpu(0), 0, True),
    "bool_kept": (lambda: np.array([True, False]), {}, "bool",
                  [True, False], mx.cpu(0), 0, True),
    "float16_kept": (lambda: np.arange(4, dtype=np.float16), {}, "float16",
                     [0, 1, 2, 3], mx.cpu(0), 0, True),
    "bfloat16_kept": (lambda: _bf16([0, 1, 2, 3]), {}, "bfloat16",
                      [0, 1, 2, 3], mx.cpu(0), 0, True),
    "ndarray_source": (lambda: mx.nd.array(np.arange(4, dtype=np.int32)), {},
                       "int32", [0, 1, 2, 3], mx.cpu(0), 0, True),
    "jax_source": (lambda: __import__("jax").numpy.arange(4.0), {},
                   "float32", [0, 1, 2, 3], mx.cpu(0), 0, True),
    "explicit_ctx": (lambda: np.arange(4, dtype=np.float32),
                     {"ctx": mx.cpu(2)}, "float32", [0, 1, 2, 3],
                     mx.cpu(2), 2, True),
    "accelerator_ctx_on_the_cpu_mesh": (
        lambda: [1, 2], {"ctx": mx.gpu(3)}, "float32", [1, 2], mx.gpu(3), 3,
        True),
    "ctx_with_no_device": (lambda: [1, 2], {"ctx": mx.cpu(64)}, "float32",
                           [1, 2], mx.cpu(64), 0, False),
    "dtype_by_name": (lambda: [1.7, -2.2], {"dtype": "int32"}, "int32",
                      [1, -2], mx.cpu(0), 0, True),
    "dtype_float64_demoted": (lambda: np.arange(3, dtype=np.float32),
                              {"dtype": np.float64}, "float32", [0, 1, 2],
                              mx.cpu(0), 0, True),
    "dtype_bfloat16": (lambda: np.array([1.001, 2.003], np.float32),
                       {"dtype": "bfloat16"}, "bfloat16", [1.0, 2.0],
                       mx.cpu(0), 0, True),
    "not_contiguous": (
        lambda: np.arange(12, dtype=np.float32).reshape(3, 4).T, {},
        "float32", np.arange(12, dtype=np.float32).reshape(3, 4).T,
        mx.cpu(0), 0, True),
    "empty": (lambda: np.zeros((0, 3), np.float32), {}, "float32",
              np.zeros((0, 3)), mx.cpu(0), 0, True),
}


@pytest.mark.parametrize("case", sorted(_ARRAY_CASES))
def test_array_constructor_contract(case):
    import jax
    import warnings
    source, kwargs, dtype, values, ctx, device, committed = \
        _ARRAY_CASES[case]
    with warnings.catch_warnings():
        warnings.simplefilter("error")      # no implicit-truncation warning
        out = mx.nd.array(source(), **kwargs)
    assert type(out) is mx.nd.NDArray
    assert out.dtype == np.dtype(dtype)
    assert out.context == ctx
    np.testing.assert_array_equal(
        out.asnumpy().astype(np.float64),
        np.asarray(values).astype(np.float64))
    assert out.shape == np.asarray(values).shape
    assert out._data.devices() == {jax.devices()[device]}
    assert out._data.committed is committed
    assert not out._data.weak_type


def test_array_follows_the_threads_default_context():
    import jax
    with mx.cpu(3):
        out = mx.nd.array([1])
    assert out.context == mx.cpu(3)
    assert out._data.devices() == {jax.devices()[3]}
