"""BatchNorm STATISTICS formulation microbench: VPU reduce vs MXU
contraction.

The live v5e trace (bench_out/trace_summary.txt) shows BN statistics
as `%convert_reduce_fusion` ops costing ~18% of the ResNet-50 step at
~2% of peak HBM bandwidth: XLA lowers the (N,H,W)-reduction keeping C
to a VPU cross-lane reduce it cannot tile well in the NCHW layout. The
same sums are contractions, and contractions run on the MXU at full
tile rate:

    s1_c = sum_nx x[n,c,x]        = einsum('ncx,nx->c', x, ones)
    s2_c = sum_nx x[n,c,x]^2      = einsum('ncx,ncx->c', x, x)

(bf16 x bf16 products are EXACT in f32 accumulation on the MXU — an
8-bit significand squared fits f32 — so the einsum s2 is not less
accurate than an elementwise square + reduce in bf16.)

Variants, fwd+bwd through a full normalize-and-scale BN:
  reduce  — jnp.mean / jnp.var (the default op's formulation)
  dot     — einsum mean + einsum E[x^2], var = E[x^2] - mean^2
  dot2p   — einsum mean, then einsum self-product of (x - mean)
            (two-pass: no cancellation, one extra elementwise pass)

Run on the chip; every line names the device.
One JSON line per shape.
"""
import json
import os
import sys

import jax  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from _bench_util import chain_time, require_accelerator  # noqa: E402

SHAPES = [
    (128, 64, 112, 112),
    (128, 256, 56, 56),
    (128, 512, 28, 28),
    (128, 1024, 14, 14),
    (128, 2048, 7, 7),
]
if os.environ.get("BENCH_BN_SMOKE") == "1":
    SHAPES = [(4, 8, 6, 6), (2, 16, 4, 4)]
ITERS = int(os.environ.get("BENCH_ITERS", "30"))
EPS = 1e-3


def _finish(x, mean, var, gamma, beta):
    C = x.shape[1]
    bshape = (1, C, 1, 1)
    inv = jax.lax.rsqrt(var.reshape(bshape) + EPS)
    return ((x.astype(jnp.float32) - mean.reshape(bshape)) * inv
            * gamma.astype(jnp.float32).reshape(bshape)
            + beta.astype(jnp.float32).reshape(bshape)).astype(x.dtype)


def bn_reduce(x, gamma, beta):
    xf = x.astype(jnp.float32)
    mean = jnp.mean(xf, axis=(0, 2, 3))
    var = jnp.var(xf, axis=(0, 2, 3))
    return _finish(x, mean, var, gamma, beta)


def _dot_sums(x3):
    """(s1, s2) per channel via MXU contractions, f32 accumulation."""
    N, C, X = x3.shape
    ones = jnp.ones((N, X), x3.dtype)
    f32 = jnp.float32
    s1 = jnp.einsum("ncx,nx->c", x3, ones,
                    preferred_element_type=f32)
    s2 = jnp.einsum("ncx,ncx->c", x3, x3,
                    preferred_element_type=f32)
    return s1, s2


def bn_dot(x, gamma, beta):
    N, C, H, W = x.shape
    m = N * H * W
    s1, s2 = _dot_sums(x.reshape(N, C, H * W))
    mean = s1 / m
    var = jnp.maximum(s2 / m - jnp.square(mean), 0.0)
    return _finish(x, mean, var, gamma, beta)


def bn_dot2p(x, gamma, beta):
    N, C, H, W = x.shape
    m = N * H * W
    x3 = x.reshape(N, C, H * W)
    ones = jnp.ones((N, H * W), x.dtype)
    mean = jnp.einsum("ncx,nx->c", x3, ones,
                      preferred_element_type=jnp.float32) / m
    xc = x3.astype(jnp.float32) - mean[None, :, None]
    var = jnp.einsum("ncx,ncx->c", xc, xc,
                     preferred_element_type=jnp.float32) / m
    return _finish(x, mean, var, gamma, beta)


VARIANTS = [("reduce", bn_reduce), ("dot", bn_dot),
            ("dot2p", bn_dot2p)]


def timed(fn, shape):
    N, C, H, W = shape
    rng = np.random.RandomState(0)
    x0 = jnp.asarray(rng.randn(*shape), jnp.bfloat16)
    gamma = jnp.ones((C,), jnp.float32)
    beta = jnp.zeros((C,), jnp.float32)
    dy = jnp.asarray(rng.randn(*shape), jnp.bfloat16)

    def step(x):
        def loss(x_, g_, b_):
            return jnp.sum(fn(x_, g_, b_).astype(jnp.float32)
                           * dy.astype(jnp.float32))
        dx, dg, db = jax.grad(loss, argnums=(0, 1, 2))(x, gamma, beta)
        return dx.astype(x.dtype)

    return chain_time(step, x0, ITERS)


def check_close():
    """All variants agree on a small f32-ish case before timing."""
    rng = np.random.RandomState(1)
    x = jnp.asarray(rng.randn(4, 8, 6, 6) * 2 + 0.5, jnp.float32)
    g = jnp.asarray(rng.rand(8) + 0.5, jnp.float32)
    b = jnp.asarray(rng.randn(8), jnp.float32)
    ref = np.asarray(bn_reduce(x, g, b))
    for name, fn in VARIANTS[1:]:
        got = np.asarray(fn(x, g, b))
        np.testing.assert_allclose(got, ref, rtol=2e-4, atol=2e-4,
                                   err_msg=name)


def main():
    check_close()
    dev = require_accelerator("bench_bn_stats.py")
    for shape in SHAPES:
        rec = {"metric": "batchnorm_stats_formulation",
               "shape": list(shape), **dev}
        for name, fn in VARIANTS:
            rec["%s_ms" % name] = round(timed(fn, shape) * 1e3, 3)
        rec["dot_speedup"] = round(rec["reduce_ms"] / rec["dot_ms"], 3)
        print(json.dumps(rec))


if __name__ == "__main__":
    main()
