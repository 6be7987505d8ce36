"""NDArray basics — mirrors reference tests/python/unittest/test_ndarray.py."""
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import nd


def test_creation():
    a = nd.zeros((3, 4))
    assert a.shape == (3, 4)
    assert a.dtype == np.float32
    assert a.asnumpy().sum() == 0

    b = nd.ones((2, 2), dtype="int32")
    assert b.dtype == np.int32
    assert b.asnumpy().sum() == 4

    c = nd.full((2,), 7.5)
    np.testing.assert_allclose(c.asnumpy(), [7.5, 7.5])

    d = nd.array([[1, 2], [3, 4]])
    assert d.shape == (2, 2)
    assert d.dtype == np.float32

    e = nd.arange(0, 10, 2)
    np.testing.assert_allclose(e.asnumpy(), [0, 2, 4, 6, 8])


def test_arithmetic():
    a = nd.array([[1.0, 2.0], [3.0, 4.0]])
    b = nd.array([[10.0, 20.0], [30.0, 40.0]])
    np.testing.assert_allclose((a + b).asnumpy(), [[11, 22], [33, 44]])
    np.testing.assert_allclose((b - a).asnumpy(), [[9, 18], [27, 36]])
    np.testing.assert_allclose((a * b).asnumpy(), [[10, 40], [90, 160]])
    np.testing.assert_allclose((b / a).asnumpy(), [[10, 10], [10, 10]])
    np.testing.assert_allclose((a + 1).asnumpy(), [[2, 3], [4, 5]])
    np.testing.assert_allclose((1 + a).asnumpy(), [[2, 3], [4, 5]])
    np.testing.assert_allclose((a - 1).asnumpy(), [[0, 1], [2, 3]])
    np.testing.assert_allclose((10 - a).asnumpy(), [[9, 8], [7, 6]])
    np.testing.assert_allclose((a * 2).asnumpy(), [[2, 4], [6, 8]])
    np.testing.assert_allclose((a / 2).asnumpy(), [[0.5, 1], [1.5, 2]])
    np.testing.assert_allclose((2 / a).asnumpy(), [[2, 1], [2/3, 0.5]],
                               rtol=1e-6)
    np.testing.assert_allclose((a ** 2).asnumpy(), [[1, 4], [9, 16]])
    np.testing.assert_allclose((-a).asnumpy(), [[-1, -2], [-3, -4]])
    np.testing.assert_allclose(abs(-a).asnumpy(), [[1, 2], [3, 4]])


def test_inplace_arithmetic():
    a = nd.ones((2, 2))
    a += 1
    np.testing.assert_allclose(a.asnumpy(), 2 * np.ones((2, 2)))
    a *= 3
    np.testing.assert_allclose(a.asnumpy(), 6 * np.ones((2, 2)))
    a -= 2
    np.testing.assert_allclose(a.asnumpy(), 4 * np.ones((2, 2)))
    a /= 4
    np.testing.assert_allclose(a.asnumpy(), np.ones((2, 2)))


def test_comparisons():
    a = nd.array([1.0, 2.0, 3.0])
    b = nd.array([3.0, 2.0, 1.0])
    np.testing.assert_allclose((a == b).asnumpy(), [0, 1, 0])
    np.testing.assert_allclose((a != b).asnumpy(), [1, 0, 1])
    np.testing.assert_allclose((a > b).asnumpy(), [0, 0, 1])
    np.testing.assert_allclose((a >= b).asnumpy(), [0, 1, 1])
    np.testing.assert_allclose((a < b).asnumpy(), [1, 0, 0])
    np.testing.assert_allclose((a <= b).asnumpy(), [1, 1, 0])


def test_indexing():
    a = nd.array(np.arange(24).reshape(2, 3, 4))
    np.testing.assert_allclose(a[0].asnumpy(), np.arange(12).reshape(3, 4))
    np.testing.assert_allclose(a[1, 2].asnumpy(), [20, 21, 22, 23])
    np.testing.assert_allclose(a[:, 1].asnumpy(),
                               np.arange(24).reshape(2, 3, 4)[:, 1])
    np.testing.assert_allclose(a[0, 1:3].asnumpy(),
                               np.arange(24).reshape(2, 3, 4)[0, 1:3])


def test_setitem():
    a = nd.zeros((3, 3))
    a[1] = 5.0
    expected = np.zeros((3, 3))
    expected[1] = 5
    np.testing.assert_allclose(a.asnumpy(), expected)
    a[:] = 1.0
    np.testing.assert_allclose(a.asnumpy(), np.ones((3, 3)))
    a[0, 1] = 9
    assert a.asnumpy()[0, 1] == 9


def test_reshape_transpose():
    a = nd.array(np.arange(12).reshape(3, 4))
    assert a.reshape(4, 3).shape == (4, 3)
    assert a.reshape((2, 6)).shape == (2, 6)
    assert a.reshape(-1).shape == (12,)
    assert a.reshape(0, -1).shape == (3, 4)
    assert a.T.shape == (4, 3)
    np.testing.assert_allclose(a.T.asnumpy(),
                               np.arange(12).reshape(3, 4).T)


def test_reduce_methods():
    a = nd.array(np.arange(12, dtype=np.float32).reshape(3, 4))
    assert a.sum().asscalar() == 66
    np.testing.assert_allclose(a.sum(axis=0).asnumpy(),
                               np.arange(12).reshape(3, 4).sum(0))
    np.testing.assert_allclose(a.mean(axis=1).asnumpy(),
                               np.arange(12).reshape(3, 4).mean(1))
    assert a.max().asscalar() == 11
    assert a.min().asscalar() == 0
    np.testing.assert_allclose(a.argmax(axis=1).asnumpy(), [3, 3, 3])


def test_dot():
    a = nd.array(np.random.rand(3, 4).astype(np.float32))
    b = nd.array(np.random.rand(4, 5).astype(np.float32))
    np.testing.assert_allclose(nd.dot(a, b).asnumpy(),
                               a.asnumpy() @ b.asnumpy(), rtol=1e-5)


def test_conversion():
    a = nd.array([3.5])
    assert a.asscalar() == 3.5
    assert float(a) == 3.5
    assert int(nd.array([7])) == 7
    assert len(nd.zeros((5, 2))) == 5
    assert nd.zeros((2, 3)).size == 6
    assert nd.zeros((2, 3)).ndim == 2


def test_astype_copy():
    a = nd.array([1.5, 2.5])
    b = a.astype("int32")
    assert b.dtype == np.int32
    c = a.copy()
    c[:] = 0.0
    np.testing.assert_allclose(a.asnumpy(), [1.5, 2.5])


def test_context():
    a = nd.zeros((2, 2), ctx=mx.cpu(0))
    assert a.context.device_type in ("cpu", "gpu")
    b = a.as_in_context(mx.cpu(0))
    assert b.shape == (2, 2)


def test_accelerator_context_never_wraps_or_falls_back():
    """mx.tpu(k) / mx.gpu(k) name one chip: an id past the device count
    raises (it used to wrap onto chip 0, and on a chipless host to land
    on the CPU). cpu ids stay logical, as in the reference."""
    import jax
    import pytest
    chips = [d for d in jax.devices() if d.platform != "cpu"]
    for make in (mx.tpu, mx.gpu):
        with pytest.raises(ValueError, match="accelerator device"):
            make(len(chips)).jax_device()
    assert mx.cpu(len(jax.devices("cpu"))).jax_device().platform == "cpu"


def test_broadcast_ops():
    a = nd.array(np.ones((2, 1, 3)))
    b = nd.array(np.ones((1, 4, 3)))
    assert (a + b).shape == (2, 4, 3)
    c = nd.broadcast_to(nd.array([[1.0], [2.0]]), shape=(2, 3))
    np.testing.assert_allclose(c.asnumpy(), [[1, 1, 1], [2, 2, 2]])


def test_concat_split_stack():
    a = nd.ones((2, 3))
    b = nd.zeros((2, 3))
    c = nd.concat(a, b, dim=0)
    assert c.shape == (4, 3)
    parts = nd.split(nd.array(np.arange(12).reshape(2, 6)), num_outputs=2,
                     axis=1)
    assert len(parts) == 2 and parts[0].shape == (2, 3)
    s = nd.stack(a, b, axis=0)
    assert s.shape == (2, 2, 3)


def test_save_load(tmp_path):
    fname = str(tmp_path / "arrays")
    data = {"w": nd.array([1.0, 2.0]), "b": nd.zeros((2, 2))}
    nd.save(fname, data)
    loaded = nd.load(fname)
    np.testing.assert_allclose(loaded["w"].asnumpy(), [1, 2])
    lst = [nd.ones((2,)), nd.zeros((3,))]
    nd.save(fname, lst)
    loaded = nd.load(fname)
    assert isinstance(loaded, list) and len(loaded) == 2


def test_unary_method_fallback():
    a = nd.array([[0.5, 1.0]])
    np.testing.assert_allclose(a.exp().asnumpy(), np.exp([[0.5, 1.0]]),
                               rtol=1e-6)
    np.testing.assert_allclose(a.log().asnumpy(), np.log([[0.5, 1.0]]),
                               rtol=1e-6)
    np.testing.assert_allclose(a.sqrt().asnumpy(), np.sqrt([[0.5, 1.0]]),
                               rtol=1e-6)


def test_take_embedding():
    w = nd.array(np.arange(12, dtype=np.float32).reshape(4, 3))
    idx = nd.array([0, 2])
    out = nd.Embedding(idx, w, input_dim=4, output_dim=3)
    np.testing.assert_allclose(out.asnumpy(),
                               np.arange(12).reshape(4, 3)[[0, 2]])
    out2 = nd.take(w, idx)
    np.testing.assert_allclose(out2.asnumpy(),
                               np.arange(12).reshape(4, 3)[[0, 2]])


def test_onehot():
    out = nd.one_hot(nd.array([0, 2]), depth=3)
    np.testing.assert_allclose(out.asnumpy(), [[1, 0, 0], [0, 0, 1]])


def test_random_seeded():
    mx.random.seed(42)
    a = nd.random_uniform(shape=(5,))
    mx.random.seed(42)
    b = nd.random_uniform(shape=(5,))
    np.testing.assert_allclose(a.asnumpy(), b.asnumpy())
    assert ((a.asnumpy() >= 0) & (a.asnumpy() < 1)).all()

    n = nd.random_normal(loc=5.0, scale=0.001, shape=(100,))
    assert abs(n.asnumpy().mean() - 5.0) < 0.1
