"""Max pooling (ops/nn.py:_pooling, `lax.reduce_window` and its
autodiff) against a NumPy reference that walks the windows: forward and
input gradient across strides, pads and ceil mode on tie-free data, and
what the gradient does with a tied window (one maximum takes all of
dy)."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from mxnet_tpu.ops.nn import _pooling


def _reference_max_pool(x, dy, kernel, stride, pad, convention):
    """Window-by-window forward and gradient. The gradient goes to the
    first maximum of each window (the same as any, off ties)."""
    (kh, kw), (sh, sw), (ph, pw) = kernel, stride, pad
    N, C, H, W = x.shape
    rnd = np.ceil if convention == "full" else np.floor
    OH = int(rnd((H + 2 * ph - kh) / float(sh))) + 1
    OW = int(rnd((W + 2 * pw - kw) / float(sw))) + 1
    # low pad as given; high pad as far as the last window reaches
    HP = max(H + 2 * ph, (OH - 1) * sh + kh)
    WP = max(W + 2 * pw, (OW - 1) * sw + kw)
    xp = np.full((N, C, HP, WP), -np.inf, x.dtype)
    xp[:, :, ph:ph + H, pw:pw + W] = x
    assert dy.shape == (N, C, OH, OW)
    y = np.empty_like(dy)
    dxp = np.zeros_like(xp)
    for n in range(N):
        for c in range(C):
            for i in range(OH):
                for j in range(OW):
                    win = xp[n, c, i * sh:i * sh + kh,
                             j * sw:j * sw + kw]
                    a, b = np.unravel_index(np.argmax(win), win.shape)
                    y[n, c, i, j] = win[a, b]
                    dxp[n, c, i * sh + a, j * sw + b] += dy[n, c, i, j]
    return y, dxp[:, :, ph:ph + H, pw:pw + W]


@pytest.mark.parametrize("kernel,stride,pad,convention", [
    ((2, 2), (2, 2), (0, 0), "valid"),
    ((3, 3), (2, 2), (1, 1), "valid"),      # the ResNet stem shape
    ((3, 3), (1, 1), (1, 1), "valid"),
    ((3, 2), (2, 3), (1, 0), "valid"),      # asymmetric
    ((3, 3), (2, 2), (0, 0), "full"),       # ceil mode: extra hi pad
])
def test_max_pool_matches_numpy_reference(kernel, stride, pad,
                                          convention):
    rng = np.random.RandomState(0)
    x = rng.randn(2, 3, 9, 9).astype(np.float32)   # ties measure-zero
    attrs = dict(kernel=kernel, stride=stride, pad=pad,
                 pooling_convention=convention)
    y = _pooling(jnp.asarray(x), pool_type="max", **attrs)
    dy = rng.randn(*y.shape).astype(np.float32)
    y_ref, dx_ref = _reference_max_pool(x, dy, kernel, stride, pad,
                                        convention)
    np.testing.assert_array_equal(np.asarray(y), y_ref)

    def loss(x_):
        return jnp.sum(_pooling(x_, pool_type="max", **attrs)
                       * jnp.asarray(dy))

    dx = np.asarray(jax.grad(loss)(jnp.asarray(x)))
    np.testing.assert_allclose(dx, dx_ref, rtol=1e-6, atol=1e-6)


def test_tie_semantics_one_maximum_takes_all():
    """A tied window gives ALL of dy to exactly one of its maxima
    (XLA's SelectAndScatter), so the gradient's mass equals dy's and
    tie-heavy quantized inputs are not inflated, as they would be if
    every tied maximum received the full dy."""
    def loss(x_):
        return jnp.sum(_pooling(x_, pool_type="max", kernel=(2, 2),
                                stride=(2, 2), pad=(0, 0)) * 3.0)

    dx = np.asarray(jax.grad(loss)(jnp.ones((1, 1, 2, 2), jnp.float32)))
    assert dx.sum() == 3.0
    assert sorted(dx.ravel()) == [0.0, 0.0, 0.0, 3.0]
    # partial tie: one of the two maxima takes it, non-maxima nothing
    x2 = jnp.asarray([[[[2.0, 2.0], [1.0, 0.0]]]], jnp.float32)
    dx2 = np.asarray(jax.grad(loss)(x2))
    assert dx2.sum() == 3.0
    assert sorted(dx2[0, 0, 0]) == [0.0, 3.0]
    np.testing.assert_array_equal(dx2[0, 0, 1], [0.0, 0.0])


def test_int_and_3d_forward():
    """Integer dtypes (init is iinfo.min, not -inf) and 3-D windows."""
    xi = jnp.asarray(np.arange(16).reshape(1, 1, 4, 4), jnp.int32)
    yi = _pooling(xi, pool_type="max", kernel=(2, 2), stride=(2, 2),
                  pad=(0, 0))
    assert yi.dtype == jnp.int32
    np.testing.assert_array_equal(
        np.asarray(yi), [[[[5, 7], [13, 15]]]])
    x3 = np.random.RandomState(1).randn(1, 1, 4, 4, 4).astype(np.float32)
    y3 = _pooling(jnp.asarray(x3), pool_type="max", kernel=(2, 2, 2),
                  stride=(2, 2, 2), pad=(0, 0, 0))
    np.testing.assert_array_equal(
        np.asarray(y3)[0, 0],
        x3[0, 0].reshape(2, 2, 2, 2, 2, 2).max(axis=(1, 3, 5)))
