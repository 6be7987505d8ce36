"""Control experiments: handwritten raw-JAX AlexNet and Inception-v3
train steps — the per-net companions of raw_jax_resnet.py (VERDICT r3:
every sub-30% MFU number must carry the control evidence ResNet-50
has).

Same discipline: fwd+bwd+momentum written directly against
jax.numpy/lax, no mxnet_tpu code in the hot path, NHWC layout, bf16
compute with f32 batch-norm statistics and f32 master weights. The
layer schedules mirror mxnet_tpu/models/{alexnet,inception_v3}.py
exactly (which themselves mirror the reference's symbols), so a
framework-vs-control gap is framework overhead, not model drift.

    python benchmark/raw_jax_controls.py --network alexnet
    python benchmark/raw_jax_controls.py --network inception-v3
"""
import argparse
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import numpy as np


def _conv(x, w, stride=1, pad="SAME"):
    import jax.lax as lax
    if isinstance(pad, tuple):
        pad = [pad, pad] if isinstance(pad[0], int) else list(pad)
        pad = [(p, p) if isinstance(p, int) else p for p in pad]
    return lax.conv_general_dilated(
        x, w, (stride, stride) if isinstance(stride, int) else stride,
        pad, dimension_numbers=("NHWC", "HWIO", "NHWC"))


def _bn(x, scale, bias, eps=2e-5):
    import jax.numpy as jnp
    xf = x.astype(jnp.float32)
    mean = xf.mean(axis=(0, 1, 2))
    var = xf.var(axis=(0, 1, 2))
    y = (xf - mean) * (scale / jnp.sqrt(var + eps)) + bias
    return y.astype(x.dtype)


def _maxpool(x, k=3, s=2, pad="VALID"):
    import jax.lax as lax
    import jax.numpy as jnp
    return lax.reduce_window(x, -jnp.inf, lax.max, (1, k, k, 1),
                             (1, s, s, 1), pad)


def _avgpool(x, k=3, s=1, pad="SAME"):
    import jax.lax as lax
    ones = lax.reduce_window(x * 0 + 1, 0.0, lax.add, (1, k, k, 1),
                             (1, s, s, 1), pad)
    return lax.reduce_window(x, 0.0, lax.add, (1, k, k, 1),
                             (1, s, s, 1), pad) / ones


def _lrn(x, nsize=5, alpha=1e-4, beta=0.75, knorm=2.0):
    import jax.lax as lax
    import jax.numpy as jnp
    sq = jnp.square(x.astype(jnp.float32))
    pad = nsize // 2
    s = lax.reduce_window(sq, 0.0, lax.add, (1, 1, 1, nsize),
                          (1, 1, 1, 1), [(0, 0), (0, 0), (0, 0),
                                         (pad, pad)])
    return (x.astype(jnp.float32)
            / jnp.power(knorm + (alpha / nsize) * s, beta)).astype(
        x.dtype)


# -- AlexNet (models/alexnet.py schedule) ------------------------------------

_ALEX_CONVS = [
    # name, nf, k, stride, pad
    ("conv1", 96, 11, 4, (0, 0)),
    ("conv2", 256, 5, 1, (2, 2)),
    ("conv3", 384, 3, 1, (1, 1)),
    ("conv4", 384, 3, 1, (1, 1)),
    ("conv5", 256, 3, 1, (1, 1)),
]


def alexnet_init(rng):
    import jax
    import jax.numpy as jnp
    k = iter(jax.random.split(rng, 32))
    params = {}
    cin = 3
    for name, nf, ksz, _s, _p in _ALEX_CONVS:
        fan = ksz * ksz * cin
        params[name + "_w"] = jax.random.normal(
            next(k), (ksz, ksz, cin, nf), jnp.float32) * np.sqrt(
            2.0 / fan)
        params[name + "_b"] = jnp.zeros((nf,), jnp.float32)
        cin = nf
    # 224 -> conv1(v,s4) 54 -> pool 26 -> pool 12 -> pool 5: 256*5*5
    dims = [(256 * 5 * 5, 4096), (4096, 4096), (4096, 1000)]
    for i, (a, b) in enumerate(dims):
        params["fc%d_w" % i] = jax.random.normal(
            next(k), (a, b), jnp.float32) * np.sqrt(1.0 / a)
        params["fc%d_b" % i] = jnp.zeros((b,), jnp.float32)
    return params


def alexnet_fwd(params, x, dtype, rng):
    import jax
    import jax.numpy as jnp
    p = {k: v.astype(dtype) for k, v in params.items()}
    x = x.astype(dtype)
    for i, (name, nf, ksz, s, pad) in enumerate(_ALEX_CONVS):
        x = _conv(x, p[name + "_w"], s,
                  "VALID" if pad == (0, 0) else (pad, pad))
        x = jnp.maximum(x + p[name + "_b"], 0)
        if i < 2:
            x = _lrn(x)
            x = _maxpool(x)
    x = _maxpool(x)
    x = x.reshape(x.shape[0], -1)
    keys = jax.random.split(rng, 2)
    for i in range(2):
        x = jnp.maximum(x @ p["fc%d_w" % i] + p["fc%d_b" % i], 0)
        keep = jax.random.bernoulli(keys[i], 0.5, x.shape)
        x = jnp.where(keep, x / 0.5, 0).astype(dtype)
    x = x.astype(jnp.float32)
    return x @ params["fc2_w"] + params["fc2_b"]


# -- Inception-v3 (models/inception_v3.py schedule) --------------------------

class _IncBuilder:
    """Init-time: records conv/bn param shapes. Run-time: applies them.
    One class, two passes, zero framework code."""

    def __init__(self):
        self.shapes = {}

    def init(self, rng):
        import jax
        import jax.numpy as jnp
        ks = jax.random.split(rng, len(self.shapes))
        params = {}
        for (name, shp), kk in zip(sorted(self.shapes.items()), ks):
            if name.endswith("_w"):
                fan = shp[0] if len(shp) == 2 else \
                    shp[0] * shp[1] * shp[2]
                params[name] = jax.random.normal(
                    kk, shp, jnp.float32) * np.sqrt(2.0 / fan)
            elif name.endswith("_scale"):
                params[name] = jnp.ones(shp, jnp.float32)
            else:
                params[name] = jnp.zeros(shp, jnp.float32)
        return params


def _inc_conv(B, p, x, name, nf, kernel, stride=1, pad=(0, 0)):
    import jax.numpy as jnp
    kh, kw = kernel if isinstance(kernel, tuple) else (kernel, kernel)
    cin = x.shape[-1]
    if p is None:                       # shape-recording pass
        B.shapes[name + "_w"] = (kh, kw, cin, nf)
        B.shapes[name + "_scale"] = (nf,)
        B.shapes[name + "_bias"] = (nf,)
        import jax
        w = jnp.zeros((kh, kw, cin, nf), x.dtype)
        scale = jnp.ones((nf,), jnp.float32)
        bias = jnp.zeros((nf,), jnp.float32)
    else:
        w = p[name + "_w"].astype(x.dtype)
        scale, bias = p[name + "_scale"], p[name + "_bias"]
    pad_arg = "VALID" if pad == (0, 0) else ((pad[0], pad[0]),
                                             (pad[1], pad[1]))
    y = _conv(x, w, stride, pad_arg)
    y = _bn(y, scale, bias)
    return jnp.maximum(y, 0)


def inception_fwd(B, params, x, dtype):
    import jax.numpy as jnp
    cv = lambda x, n, nf, k, s=1, pd=(0, 0): _inc_conv(
        B, params, x, n, nf, k, s, pd)
    cat = lambda *ts: jnp.concatenate(ts, axis=-1)

    x = x.astype(dtype)
    x = cv(x, "conv0", 32, 3, 2)
    x = cv(x, "conv1", 32, 3)
    x = cv(x, "conv2", 64, 3, 1, (1, 1))
    x = _maxpool(x)
    x = cv(x, "conv3", 80, 1)
    x = cv(x, "conv4", 192, 3)
    x = _maxpool(x)

    def module_a(x, name, proj):
        t1 = cv(x, name + "_1x1", 64, 1)
        t5 = cv(cv(x, name + "_5x5r", 48, 1), name + "_5x5", 64, 5, 1,
                (2, 2))
        t3 = cv(cv(cv(x, name + "_d3r", 64, 1), name + "_d3a", 96, 3,
                   1, (1, 1)), name + "_d3b", 96, 3, 1, (1, 1))
        tp = cv(_avgpool(x), name + "_proj", proj, 1)
        return cat(t1, t5, t3, tp)

    def reduce_a(x, name):
        t3 = cv(x, name + "_3x3", 384, 3, 2)
        td = cv(cv(cv(x, name + "_d3r", 64, 1), name + "_d3a", 96, 3,
                   1, (1, 1)), name + "_d3b", 96, 3, 2)
        return cat(t3, td, _maxpool(x))

    def module_b(x, name, c7):
        t1 = cv(x, name + "_1x1", 192, 1)
        t7 = cv(cv(cv(x, name + "_7r", c7, 1), name + "_7a", c7,
                   (1, 7), 1, (0, 3)), name + "_7b", 192, (7, 1), 1,
                (3, 0))
        td = x
        for suf, nf, kk, pp in (("_d7r", c7, 1, (0, 0)),
                                ("_d7a", c7, (7, 1), (3, 0)),
                                ("_d7b", c7, (1, 7), (0, 3)),
                                ("_d7c", c7, (7, 1), (3, 0)),
                                ("_d7d", 192, (1, 7), (0, 3))):
            td = cv(td, name + suf, nf, kk, 1, pp)
        tp = cv(_avgpool(x), name + "_proj", 192, 1)
        return cat(t1, t7, td, tp)

    def reduce_b(x, name):
        t3 = cv(cv(x, name + "_3r", 192, 1), name + "_3", 320, 3, 2)
        t7 = cv(cv(cv(cv(x, name + "_7r", 192, 1), name + "_7a", 192,
                      (1, 7), 1, (0, 3)), name + "_7b", 192, (7, 1),
                   1, (3, 0)), name + "_7c", 192, 3, 2)
        return cat(t3, t7, _maxpool(x))

    def module_c(x, name, pool):
        t1 = cv(x, name + "_1x1", 320, 1)
        t3 = cv(x, name + "_3r", 384, 1)
        t3 = cat(cv(t3, name + "_3a", 384, (1, 3), 1, (0, 1)),
                 cv(t3, name + "_3b", 384, (3, 1), 1, (1, 0)))
        td = cv(cv(x, name + "_d3r", 448, 1), name + "_d3", 384, 3, 1,
                (1, 1))
        td = cat(cv(td, name + "_d3a", 384, (1, 3), 1, (0, 1)),
                 cv(td, name + "_d3b", 384, (3, 1), 1, (1, 0)))
        tp = cv(pool(x), name + "_proj", 192, 1)
        return cat(t1, t3, td, tp)

    x = module_a(x, "mixed0", 32)
    x = module_a(x, "mixed1", 64)
    x = module_a(x, "mixed2", 64)
    x = reduce_a(x, "mixed3")
    x = module_b(x, "mixed4", 128)
    x = module_b(x, "mixed5", 160)
    x = module_b(x, "mixed6", 160)
    x = module_b(x, "mixed7", 192)
    x = reduce_b(x, "mixed8")
    x = module_c(x, "mixed9", _avgpool)
    x = module_c(x, "mixed10", lambda t: _maxpool(t, 3, 1, "SAME"))

    x = x.mean(axis=(1, 2)).astype("float32")
    if params is None:
        B.shapes["fc_w"] = (x.shape[-1], 1000)
        B.shapes["fc_b"] = (1000,)
        import jax.numpy as jnp
        return x @ jnp.zeros((x.shape[-1], 1000), jnp.float32)
    return x @ params["fc_w"] + params["fc_b"]


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--network", default="alexnet",
                    choices=["alexnet", "inception-v3"])
    ap.add_argument("--batch", type=int, default=None)
    ap.add_argument("--iters", type=int, default=20)
    ap.add_argument("--dtype", default="bfloat16")
    args = ap.parse_args()
    import jax
    import jax.numpy as jnp
    from bench_common import require_accelerator
    dev = require_accelerator("raw_jax_controls.py")

    dtype = jnp.dtype(args.dtype)
    if args.network == "alexnet":
        batch = args.batch or 512
        image = 224
        params = alexnet_init(jax.random.PRNGKey(0))
        fwd = lambda p, x, rng: alexnet_fwd(p, x, dtype, rng)
    else:
        batch = args.batch or 64
        image = 299
        B = _IncBuilder()
        # shape-recording pass on a tiny batch
        inception_fwd(B, None,
                      jnp.zeros((1, image, image, 3), jnp.float32),
                      dtype)
        params = B.init(jax.random.PRNGKey(0))
        fwd = lambda p, x, rng: inception_fwd(B, p, x, dtype)

    mom = jax.tree.map(jnp.zeros_like, params)
    x = np.random.RandomState(0).standard_normal(
        (batch, image, image, 3)).astype(np.float32)
    y = np.random.RandomState(1).randint(0, 1000, batch)

    def loss_fn(params, x, y, rng):
        logits = fwd(params, x, rng)
        logp = jax.nn.log_softmax(logits)
        return -logp[jnp.arange(x.shape[0]), y].mean()

    @jax.jit
    def step(params, mom, x, y, rng):
        loss, grads = jax.value_and_grad(loss_fn)(params, x, y, rng)
        new_mom = jax.tree.map(lambda m, g: 0.9 * m + g, mom, grads)
        new_p = jax.tree.map(lambda p, m: p - 0.1 * m, params, new_mom)
        return new_p, new_mom, loss

    rng = jax.random.PRNGKey(7)
    xd, yd = jax.device_put(x), jax.device_put(y)
    for _ in range(2):
        params, mom, loss = step(params, mom, xd, yd, rng)
    jax.block_until_ready(loss)
    t0 = time.time()
    for _ in range(args.iters):
        params, mom, loss = step(params, mom, xd, yd, rng)
    jax.block_until_ready(loss)
    dt = (time.time() - t0) / args.iters
    print("raw-JAX NHWC %s: %.2f ms/step, %.1f img/s (batch %d, %s) "
          "on %s x%d"
          % (args.network, dt * 1e3, batch / dt, batch, args.dtype,
             dev["device_kind"], dev["device_count"]))


if __name__ == "__main__":
    main()
