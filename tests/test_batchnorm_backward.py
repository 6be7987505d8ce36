"""Training-mode BatchNorm (ops/nn.py:_batch_norm) against a float64
NumPy reference with the textbook closed-form backward: outputs,
moving-stat updates and ALL gradients (data/gamma/beta) must agree,
across axes, fix_gamma and the two dtypes that train (float32, and the
benchmark cell's bfloat16 activations over float32 statistics).
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu import autograd, nd


def _reference_bn(x, gamma, beta, dy, eps, axis, fix_gamma):
    """float64 forward and closed-form backward of training BN:
    dx = (g*inv/m) * (m*dy - sum(dy) - xhat*sum(dy*xhat))."""
    x, dy = np.asarray(x, np.float64), np.asarray(dy, np.float64)
    red = tuple(i for i in range(x.ndim) if i != axis)
    bshape = tuple(x.shape[axis] if i == axis else 1
                   for i in range(x.ndim))
    g = np.ones_like(gamma, np.float64) if fix_gamma \
        else np.asarray(gamma, np.float64)
    m = x.size // x.shape[axis]
    mean, var = x.mean(axis=red), x.var(axis=red)
    inv = 1.0 / np.sqrt(var + eps)
    xhat = (x - mean.reshape(bshape)) * inv.reshape(bshape)
    y = xhat * g.reshape(bshape) + \
        np.asarray(beta, np.float64).reshape(bshape)
    dbeta = dy.sum(axis=red)
    dxhat = (dy * xhat).sum(axis=red)
    dx = (g * inv / m).reshape(bshape) * (
        m * dy - dbeta.reshape(bshape) - xhat * dxhat.reshape(bshape))
    dgamma = np.zeros_like(dxhat) if fix_gamma else dxhat
    return y, mean, var, (dx, dgamma, dbeta)


@pytest.mark.parametrize("axis", [1, 3])
@pytest.mark.parametrize("fix_gamma", [False, True])
@pytest.mark.parametrize("dtype,tol", [("float32", 2e-4),
                                       ("bfloat16", 3e-2)])
def test_train_bn_matches_naive(axis, fix_gamma, dtype, tol):
    """Output in the input dtype, statistics in float32 whatever the
    input dtype, and the autodiff gradients equal the closed form (to
    the input dtype's rounding)."""
    from mxnet_tpu.ops.nn import _batch_norm

    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(4, 5, 6, 7) * 2.0 + 0.5, dtype)
    C = x.shape[axis]
    gamma = rng.rand(C).astype(np.float32) + 0.5
    beta = rng.randn(C).astype(np.float32)
    dy = jnp.asarray(rng.randn(*x.shape), dtype)
    eps = 1e-3

    def framework(x_, g_, b_):
        return _batch_norm(x_, g_, b_, jnp.zeros(C), jnp.ones(C),
                           eps=eps, momentum=0.0, fix_gamma=fix_gamma,
                           axis=axis, is_train=True)

    y_n, mean_n, var_n, grads_n = _reference_bn(
        x, gamma, beta, dy, eps, axis, fix_gamma)
    y_f, mean_f, var_f = framework(x, gamma, beta)
    assert y_f.dtype == x.dtype
    assert mean_f.dtype == var_f.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(y_f, np.float64), y_n,
                               rtol=tol, atol=tol)
    # momentum 0: the new moving stats ARE the batch statistics
    np.testing.assert_allclose(np.asarray(mean_f), mean_n, rtol=1e-5,
                               atol=1e-5)
    np.testing.assert_allclose(np.asarray(var_f), var_n, rtol=1e-5,
                               atol=1e-5)

    def loss(x_, g_, b_):
        return jnp.sum(framework(x_, g_, b_)[0].astype(jnp.float32)
                       * dy.astype(jnp.float32))

    grads_f = jax.grad(loss, argnums=(0, 1, 2))(x, gamma, beta)
    assert grads_f[0].dtype == x.dtype
    for a, b, name in zip(grads_f, grads_n, ("dx", "dgamma", "dbeta")):
        scale = max(1.0, float(np.abs(b).max()))
        np.testing.assert_allclose(
            np.asarray(a, np.float64), b, rtol=tol, atol=tol * scale,
            err_msg="%s mismatch (axis=%d fix_gamma=%s %s)"
                    % (name, axis, fix_gamma, dtype))


@pytest.mark.parametrize("dtype,tol", [("float32", 1e-4),
                                       ("bfloat16", 2e-2)])
def test_moving_stats_and_eval_path(dtype, tol):
    """Through the public op: moving stats update from the batch
    mean/var and stay float32 under bfloat16 activations; eval mode
    uses them."""
    rng = np.random.RandomState(1)
    x = nd.array(rng.randn(8, 3, 5, 5).astype(np.float32)).astype(dtype)
    gamma, beta = nd.ones((3,)), nd.zeros((3,))
    mm, mv = nd.zeros((3,)), nd.ones((3,))
    with autograd.record():
        out = nd.BatchNorm(x, gamma, beta, mm, mv, fix_gamma=False,
                           momentum=0.9, eps=1e-3)
    assert out.dtype == x.dtype
    assert mm.dtype == mv.dtype == np.float32
    xn = x.asnumpy().astype(np.float64)     # what the op was given
    got_mm = mm.asnumpy()
    np.testing.assert_allclose(got_mm, 0.1 * xn.mean(axis=(0, 2, 3)),
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(
        mv.asnumpy(), 0.9 + 0.1 * xn.var(axis=(0, 2, 3)), rtol=1e-5)

    # eval: normalize with the (updated) moving stats
    out_eval = nd.BatchNorm(x, gamma, beta, mm, mv, fix_gamma=False)
    ref = (xn - got_mm[None, :, None, None]) / np.sqrt(
        mv.asnumpy()[None, :, None, None] + 1e-3)
    np.testing.assert_allclose(out_eval.asnumpy().astype(np.float64),
                               ref, rtol=tol, atol=tol)


def test_mean_var_output_cotangents():
    """A graph that differentiates THROUGH the mean and inverse-std
    outputs (output_mean_var consumers) must get correct gradients:
    their cotangents reach dx, not only the normalized output's."""
    from mxnet_tpu.ops.nn import _batch_norm

    rng = np.random.RandomState(3)
    x = rng.randn(4, 3, 5, 5).astype(np.float32)
    gamma = rng.rand(3).astype(np.float32) + 0.5
    beta = rng.randn(3).astype(np.float32)
    eps = 1e-3
    red, bshape = (0, 2, 3), (1, 3, 1, 1)
    w_y = rng.randn(*x.shape).astype(np.float32)
    w_m = rng.randn(3).astype(np.float32)
    w_v = rng.randn(3).astype(np.float32)

    def op_loss(x_, g_, b_):
        y, mean, inv_std = _batch_norm(
            jnp.asarray(x_), g_, b_, jnp.zeros(3), jnp.ones(3),
            eps=eps, fix_gamma=False, output_mean_var=True,
            is_train=True)[:3]
        return (jnp.sum(y * w_y) + jnp.sum(mean * w_m)
                + jnp.sum(inv_std * w_v))

    def naive_loss(x_, g_, b_):
        # two explicit passes, no jnp.var: E[x^2 - 2*x*mean + mean^2]
        xf = jnp.asarray(x_).astype(jnp.float32)
        m = xf.size // 3
        mean = jnp.sum(xf, axis=red) / m
        var = jnp.sum(jnp.square(xf - mean.reshape(bshape)),
                      axis=red) / m
        inv = 1.0 / jnp.sqrt(var + eps)
        y = (xf - mean.reshape(bshape)) * inv.reshape(bshape) \
            * g_.reshape(bshape) + b_.reshape(bshape)
        return (jnp.sum(y * w_y) + jnp.sum(mean * w_m)
                + jnp.sum(inv * w_v))

    gf = jax.grad(op_loss, argnums=(0, 1, 2))(x, gamma, beta)
    gn = jax.grad(naive_loss, argnums=(0, 1, 2))(x, gamma, beta)
    for a, b, name in zip(gf, gn, ("dx", "dgamma", "dbeta")):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=2e-4, atol=2e-4,
            err_msg="%s mismatch through mean/var outputs" % name)


def test_variance_large_mean_accuracy():
    """E[x^2]-E[x]^2 catastrophically cancels when |mean| >> std (in
    float32 the 1e-4 true variance of a mean=1e4, std=1e-2 batch
    vanishes under an ulp of ~8 and the output blows up against eps).
    The statistics must not be computed that way: such a batch
    normalises to unit std."""
    from mxnet_tpu.ops.nn import _batch_norm

    rng = np.random.RandomState(4)
    noise = rng.randn(64, 2, 8, 8).astype(np.float32)
    x = (1e4 + 1e-2 * noise).astype(np.float32)
    out = _batch_norm(jnp.asarray(x), jnp.ones(2), jnp.zeros(2),
                      jnp.zeros(2), jnp.ones(2), eps=1e-5,
                      fix_gamma=False, is_train=True)
    y = np.asarray(out[0], np.float64)
    for c in range(2):
        assert 0.9 < y[:, c].std() < 1.1, y[:, c].std()
    # and equals the float64 normalisation of the same float32 input,
    # to the float32 mean's own floor: half an ulp at 1e4 is 4.9e-4,
    # which is 0.049 of this batch's std
    x64 = x.astype(np.float64)
    mean = x64.mean(axis=(0, 2, 3)).reshape(1, 2, 1, 1)
    var = x64.var(axis=(0, 2, 3)).reshape(1, 2, 1, 1)
    np.testing.assert_allclose(y, (x64 - mean) / np.sqrt(var + 1e-5),
                               rtol=1e-3, atol=5e-2)


def test_var_nonnegative():
    """A constant input has zero variance; rounding must not take it
    below zero, where rsqrt(var + eps) would stop being finite."""
    x = jnp.full((4, 2, 8, 8), 3.14159, jnp.float32)
    from mxnet_tpu.ops.nn import _batch_norm
    out = _batch_norm(x, jnp.ones(2), jnp.zeros(2), jnp.zeros(2),
                      jnp.ones(2), eps=1e-3, is_train=True)
    assert np.isfinite(np.asarray(out[0])).all()
