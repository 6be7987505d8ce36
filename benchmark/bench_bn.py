"""BatchNorm training fwd+bwd microbench: one-pass/closed-form (the
framework op) vs the naive two-pass autodiff formulation, at
ResNet-50's dominant BN shapes (batch 128, bf16 activations).

Quantifies the _bn_train_core rewrite (docs/mfu_analysis.md measured BN
statistics at ~18% of the ResNet-50 step). Run on the chip:

    python benchmark/bench_bn.py

Chains iterations on device (_bench_util.chain_time). Prints one JSON
line per shape, naming the device.
"""
import json
import os
import sys

import jax  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from _bench_util import chain_time, require_accelerator  # noqa: E402

# (N, C, H, W) — ResNet-50 stage shapes at batch 128.
# BENCH_BN_SMOKE=1 shrinks them for CPU CI (Pallas interpret mode runs
# the grid in Python — full shapes would take minutes per call).
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


def naive_bn(x, gamma, beta, eps=1e-3):
    xf = x.astype(jnp.float32)
    mean = jnp.mean(xf, axis=(0, 2, 3))
    var = jnp.var(xf, axis=(0, 2, 3))
    inv = jax.lax.rsqrt(var[None, :, None, None] + eps)
    out = (xf - mean[None, :, None, None]) * inv \
        * gamma.astype(jnp.float32)[None, :, None, None] \
        + beta.astype(jnp.float32)[None, :, None, None]
    return out.astype(x.dtype)


def framework_bn(x, gamma, beta, eps=1e-3):
    """The r4 one-pass/closed-form core. Since the default flipped
    back to two-pass autodiff (the 'two_pass'/naive column here IS the
    default now), this column must pin the routing explicitly or the
    A/B silently times the default twice. The routing env var is read
    at trace time inside _batch_norm, so save/restore around the call
    keeps the override from leaking into the rest of the process (the
    naive/pallas columns, or anything importing this module)."""
    from mxnet_tpu.ops.nn import _batch_norm
    C = x.shape[1]
    prev = os.environ.get("MXNET_BN_IMPL")
    os.environ["MXNET_BN_IMPL"] = "onepass"
    try:
        return _batch_norm(x, gamma, beta, jnp.zeros(C), jnp.ones(C),
                           eps=eps, fix_gamma=False, is_train=True)[0]
    finally:
        if prev is None:
            os.environ.pop("MXNET_BN_IMPL", None)
        else:
            os.environ["MXNET_BN_IMPL"] = prev


def pallas_bn(x, gamma, beta, eps=1e-3):
    """The below-XLA explicit-pass kernels (ops/bn_pallas.py)."""
    from mxnet_tpu.ops.bn_pallas import bn_train_pallas
    return bn_train_pallas(x, gamma, beta, eps)[0]


def timed(fn, shape):
    """fwd+bwd step, chained on device via _bench_util.chain_time."""
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
        return dx.astype(x.dtype)      # feeds the next iteration

    return chain_time(step, x0, ITERS)


def main():
    dev = require_accelerator("bench_bn.py")
    for shape in SHAPES:
        t_new = timed(framework_bn, shape)
        t_old = timed(naive_bn, shape)
        try:
            # the Pallas explicit-pass variant: a Mosaic rejection on
            # some shape must not kill the XLA A/B numbers
            t_pallas = timed(pallas_bn, shape)
        except Exception as e:  # noqa: BLE001
            print("pallas variant failed on %s: %s"
                  % (shape, str(e)[:200]), file=sys.stderr)
            t_pallas = None
        bytes_tensor = int(np.prod(shape)) * 2      # bf16
        print(json.dumps({
            "metric": "batchnorm_train_fwd_bwd",
            "shape": list(shape),
            "one_pass_ms": round(t_new * 1e3, 3),
            "two_pass_ms": round(t_old * 1e3, 3),
            "pallas_ms": round(t_pallas * 1e3, 3)
            if t_pallas else None,
            "speedup": round(t_old / t_new, 3),
            "pallas_vs_one_pass": round(t_new / t_pallas, 3)
            if t_pallas else None,
            "tensor_mb": round(bytes_tensor / 1e6, 1),
            **dev}))


if __name__ == "__main__":
    main()
