"""Max-pool fwd+bwd microbench: dense custom backward
(MXNET_POOL_DENSE_BWD=1, an off-by-default experiment) vs XLA's
SelectAndScatter autodiff (the default). The first live run decided
the default: dense is 10-12x slower at every conv-net pool shape
(bench_out/pool_micro.jsonl) — each of its 2*kh*kw passes streams the
full padded tensor from HBM. Shapes: the ResNet-50 stem pool plus
inception-style grids. Run on the chip:

    python benchmark/bench_pool.py

Chains iterations on device (_bench_util.chain_time). One JSON line per
shape, naming the device.
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

# (N, C, H, W, kernel, stride, pad)
SHAPES = [
    (128, 64, 112, 112, 3, 2, 1),    # ResNet-50 stem max pool
    (128, 192, 56, 56, 3, 2, 1),     # inception-bn grid reductions
    (128, 320, 28, 28, 3, 2, 1),
    (64, 192, 71, 71, 3, 2, 0),      # inception-v3 (299px path)
]
if os.environ.get("BENCH_POOL_SMOKE") == "1":
    SHAPES = [(2, 3, 8, 8, 2, 2, 0)]
ITERS = int(os.environ.get("BENCH_ITERS", "30"))


def timed(env, shape):
    os.environ["MXNET_POOL_DENSE_BWD"] = env
    from mxnet_tpu.ops.nn import _pooling
    N, C, H, W, k, s, p = shape
    rng = np.random.RandomState(0)
    x0 = jnp.asarray(rng.randn(N, C, H, W), jnp.bfloat16)
    attrs = dict(kernel=(k, k), stride=(s, s), pad=(p, p))
    dy_shape = _pooling(x0, pool_type="max", **attrs).shape
    dy = jnp.asarray(rng.randn(*dy_shape), jnp.bfloat16)

    def step(x):
        def loss(x_):
            return jnp.sum(_pooling(x_, pool_type="max", **attrs)
                           .astype(jnp.float32)
                           * dy.astype(jnp.float32))
        dx = jax.grad(loss)(x)
        return dx.astype(x.dtype)     # feeds the next iteration

    return chain_time(step, x0, ITERS)


def main():
    dev = require_accelerator("bench_pool.py")
    for shape in SHAPES:
        t_dense = timed("1", shape)
        t_sas = timed("0", shape)
        print(json.dumps({
            "metric": "maxpool_train_fwd_bwd",
            "shape": list(shape[:4]),
            "kernel": shape[4], "stride": shape[5], "pad": shape[6],
            "dense_bwd_ms": round(t_dense * 1e3, 3),
            "select_scatter_ms": round(t_sas * 1e3, 3),
            "speedup": round(t_sas / t_dense, 3),
            **dev}))


if __name__ == "__main__":
    main()
