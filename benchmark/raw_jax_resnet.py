"""Control experiment: handwritten raw-JAX ResNet-50 train step.

Establishes how much of the framework bench's step time is framework
overhead vs the XLA ceiling for this model: the same fwd+bwd+momentum
update written directly against jax.numpy/lax, no mxnet_tpu layers, no
symbol graph, NHWC layout (TPU-preferred). Run side by side with
`python bench.py` (NCHW symbol path):

    python benchmark/raw_jax_resnet.py          # raw-JAX control
    python bench.py                             # framework path

Round-2 measurement on one v5e chip (batch 128, bf16 compute):
framework 52.3 ms/step vs control 50.5 ms/step => ~3% framework
overhead; see docs/mfu_analysis.md for the device-time breakdown.
"""
import argparse
import functools
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import numpy as np

# stage sizes for ResNet-50: (blocks, filters)
_STAGES = ((3, 256), (4, 512), (6, 1024), (3, 2048))


def _conv(x, w, stride=1):
    import jax.lax as lax
    return lax.conv_general_dilated(
        x, w, (stride, stride), "SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"))


def _bn(x, scale, bias, training=True, eps=1e-5):
    import jax.numpy as jnp
    # batch statistics in f32 regardless of compute dtype
    xf = x.astype(jnp.float32)
    mean = xf.mean(axis=(0, 1, 2))
    var = xf.var(axis=(0, 1, 2))
    y = (xf - mean) * (scale / jnp.sqrt(var + eps)) + bias
    return y.astype(x.dtype)


def init_params(rng):
    import jax
    import jax.numpy as jnp
    params = {}
    k = iter(jax.random.split(rng, 256))

    def conv_p(name, kh, kw, cin, cout):
        fan_in = kh * kw * cin
        params[name] = jax.random.normal(
            next(k), (kh, kw, cin, cout), jnp.float32) * \
            np.sqrt(2.0 / fan_in)

    def bn_p(name, c):
        params[name + "_scale"] = jnp.ones((c,), jnp.float32)
        params[name + "_bias"] = jnp.zeros((c,), jnp.float32)

    conv_p("stem", 7, 7, 3, 64)
    bn_p("stem_bn", 64)
    cin = 64
    for si, (blocks, cout) in enumerate(_STAGES):
        mid = cout // 4
        for bi in range(blocks):
            p = "s%d_b%d" % (si, bi)
            conv_p(p + "_c1", 1, 1, cin, mid)
            bn_p(p + "_bn1", mid)
            conv_p(p + "_c2", 3, 3, mid, mid)
            bn_p(p + "_bn2", mid)
            conv_p(p + "_c3", 1, 1, mid, cout)
            bn_p(p + "_bn3", cout)
            if bi == 0:
                conv_p(p + "_proj", 1, 1, cin, cout)
                bn_p(p + "_bnp", cout)
            cin = cout
    params["fc_w"] = jax.random.normal(
        next(k), (2048, 1000), jnp.float32) * 0.01
    params["fc_b"] = jnp.zeros((1000,), jnp.float32)
    return params


def forward(params, x, dtype):
    import jax.lax as lax
    import jax.numpy as jnp
    p = {k: (v.astype(dtype) if v.ndim == 4 else v)
         for k, v in params.items()}
    x = x.astype(dtype)
    x = _conv(x, p["stem"], 2)
    x = _bn(x, p["stem_bn_scale"], p["stem_bn_bias"])
    x = jnp.maximum(x, 0)
    x = lax.reduce_window(x, -jnp.inf, lax.max, (1, 3, 3, 1),
                          (1, 2, 2, 1), "SAME")
    cin = 64
    for si, (blocks, cout) in enumerate(_STAGES):
        for bi in range(blocks):
            pre = "s%d_b%d" % (si, bi)
            stride = 2 if (bi == 0 and si > 0) else 1
            sc = x
            if bi == 0:
                sc = _conv(x, p[pre + "_proj"], stride)
                sc = _bn(sc, p[pre + "_bnp_scale"], p[pre + "_bnp_bias"])
            h = _conv(x, p[pre + "_c1"], 1)
            h = jnp.maximum(_bn(h, p[pre + "_bn1_scale"],
                                p[pre + "_bn1_bias"]), 0)
            h = _conv(h, p[pre + "_c2"], stride)
            h = jnp.maximum(_bn(h, p[pre + "_bn2_scale"],
                                p[pre + "_bn2_bias"]), 0)
            h = _conv(h, p[pre + "_c3"], 1)
            h = _bn(h, p[pre + "_bn3_scale"], p[pre + "_bn3_bias"])
            x = jnp.maximum(h + sc, 0)
            cin = cout
    x = x.mean(axis=(1, 2)).astype(jnp.float32)
    return x @ params["fc_w"] + params["fc_b"]


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--batch", type=int, default=128)
    ap.add_argument("--iters", type=int, default=20)
    ap.add_argument("--image", type=int, default=224)
    ap.add_argument("--dtype", default="bfloat16")
    args = ap.parse_args()
    import jax
    import jax.numpy as jnp
    from bench_common import require_accelerator
    dev = require_accelerator("raw_jax_resnet.py")

    dtype = jnp.dtype(args.dtype)
    params = init_params(jax.random.PRNGKey(0))
    mom = jax.tree.map(jnp.zeros_like, params)
    x = np.random.RandomState(0).standard_normal(
        (args.batch, args.image, args.image, 3)).astype(np.float32)
    y = np.random.RandomState(1).randint(0, 1000, args.batch)

    def loss_fn(params, x, y):
        logits = forward(params, x, dtype)
        logp = jax.nn.log_softmax(logits)
        return -logp[jnp.arange(x.shape[0]), y].mean()

    @jax.jit
    def step(params, mom, x, y):
        loss, grads = jax.value_and_grad(loss_fn)(params, x, y)
        new_mom = jax.tree.map(lambda m, g: 0.9 * m + g, mom, grads)
        new_p = jax.tree.map(lambda p, m: p - 0.1 * m, params, new_mom)
        return new_p, new_mom, loss

    xd, yd = jax.device_put(x), jax.device_put(y)
    for _ in range(2):
        params, mom, loss = step(params, mom, xd, yd)
    jax.block_until_ready(loss)
    t0 = time.time()
    for _ in range(args.iters):
        params, mom, loss = step(params, mom, xd, yd)
    jax.block_until_ready(loss)
    dt = (time.time() - t0) / args.iters
    print("raw-JAX NHWC resnet50: %.2f ms/step, %.1f img/s (batch %d, %s) "
          "on %s x%d"
          % (dt * 1e3, args.batch / dt, args.batch, args.dtype,
             dev["device_kind"], dev["device_count"]))


if __name__ == "__main__":
    main()
