"""Plain reference for the ResNet family (He et al. 2015, depth table;
pre-activation bottleneck units, He et al. 2016), training: forward
with batch statistics, softmax cross-entropy, gradients, SGD with
momentum and weight decay. Straightforward `jax.numpy`/`jax.lax` in
float32 at `highest` precision, NCHW; each residual unit is
rematerialised so that a batch of 128 fits beside nothing else. It
imports nothing of the program.

The weights and the data belong to the benchmark: `make_params` and
`make_batches` draw them from the seed (uniform integers times a
constant, exact in any compilation) and the program is handed the
result under its own parameter names.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np

from cellbench.reference._seeded import base_key, uniform

BN_EPS = 2e-5
_UNITS = {50: (3, 4, 6, 3), 101: (3, 4, 23, 3), 152: (3, 8, 36, 3)}
_FILTERS = (64, 256, 512, 1024, 2048)


def param_shapes(cfg):
    """Ordered {name: shape} under the program's parameter names."""
    units = _UNITS[int(cfg["num_layers"])]
    classes = int(cfg["num_classes"])
    out = {"conv0_weight": (_FILTERS[0], 3, 7, 7),
           "bn0_gamma": (_FILTERS[0],), "bn0_beta": (_FILTERS[0],)}
    cin = _FILTERS[0]
    for s, n_units in enumerate(units):
        nf = _FILTERS[s + 1]
        mid = nf // 4
        for u in range(n_units):
            p = "stage%d_unit%d_" % (s + 1, u + 1)
            out[p + "bn1_gamma"] = out[p + "bn1_beta"] = (cin,)
            out[p + "conv1_weight"] = (mid, cin, 1, 1)
            out[p + "bn2_gamma"] = out[p + "bn2_beta"] = (mid,)
            out[p + "conv2_weight"] = (mid, mid, 3, 3)
            out[p + "bn3_gamma"] = out[p + "bn3_beta"] = (mid,)
            out[p + "conv3_weight"] = (nf, mid, 1, 1)
            if u == 0:
                out[p + "sc_weight"] = (nf, cin, 1, 1)
            cin = nf
    out["bn1_gamma"] = out["bn1_beta"] = (cin,)
    out["fc1_weight"] = (classes, cin)
    out["fc1_bias"] = (classes,)
    return out


def make_params(cfg, seed):
    """Float32 master weights, made on the device in one jitted call:
    He-scaled convolutions, gammas around 1, small betas and head."""
    shapes = param_shapes(cfg)

    @jax.jit
    def build(key):
        out = {}
        for i, (name, shape) in enumerate(shapes.items()):
            k = jax.random.fold_in(key, i)
            if name.endswith("gamma"):
                out[name] = uniform(k, shape, 0.1, 1.0)
            elif name.endswith(("beta", "bias")):
                out[name] = uniform(k, shape, 0.02)
            elif len(shape) == 4:
                fan_in = shape[1] * shape[2] * shape[3]
                out[name] = uniform(k, shape, (2.0 / fan_in) ** 0.5)
            else:
                out[name] = uniform(k, shape, 0.01)
        return out

    return build(jax.random.fold_in(base_key(seed), 1))


def make_batches(cfg, traffic, seed, first=0, count=None):
    """(data, label) for batches [first, first+count) of the synthetic
    set, on the device: images uniform around 0 with unit deviation,
    every row different; labels uniform over the classes, as float32
    (the reference MXNet's label type)."""
    batch = int(traffic["batch_per_chip"]) * int(traffic.get("chips", 1))
    n = int(traffic["batches"]) if count is None else count
    side = int(cfg["image_size"])
    classes = int(cfg["num_classes"])

    @jax.jit
    def build(key):
        data, label = [], []
        for b in range(first, first + n):
            k = jax.random.fold_in(key, b)
            data.append(uniform(jax.random.fold_in(k, 0),
                                 (batch, 3, side, side), 1.0))
            label.append(jax.random.randint(
                jax.random.fold_in(k, 1), (batch,), 0,
                classes).astype(jnp.float32))
        return jnp.concatenate(data), jnp.concatenate(label)

    return build(jax.random.fold_in(base_key(seed), 2))


def _q(x, dtype):
    """Round to an 8-bit float under a per-tensor scale, and back."""
    scale = jnp.max(jnp.abs(x)) / float(jnp.finfo(dtype).max)
    scale = jnp.where(scale == 0, 1.0, scale)
    return (x / scale).astype(dtype).astype(jnp.float32) * scale


def _fp8(fn):
    """`fn(x, w)` the way an fp8 training path computes it: operands
    rounded to e4m3 on the way forward, the incoming gradient to e5m2
    on the way back, products accumulated in float32."""
    @jax.custom_vjp
    def low(x, w):
        return fn(_q(x, jnp.float8_e4m3fn), _q(w, jnp.float8_e4m3fn))

    def fwd(x, w):
        xq, wq = _q(x, jnp.float8_e4m3fn), _q(w, jnp.float8_e4m3fn)
        return fn(xq, wq), (xq, wq)

    def bwd(res, g):
        return jax.vjp(fn, *res)[1](_q(g, jnp.float8_e5m2))

    low.defvjp(fwd, bwd)
    return low


def _conv(x, w, stride, pad, low):
    def conv(x, w):
        return jax.lax.conv_general_dilated(
            x, w, (stride, stride), [(pad, pad), (pad, pad)],
            dimension_numbers=("NCHW", "OIHW", "NCHW"))
    return _fp8(conv)(x, w) if low else conv(x, w)


def _bn_relu(x, gamma, beta, relu=True):
    mean = x.mean((0, 2, 3), keepdims=True)
    var = ((x - mean) ** 2).mean((0, 2, 3), keepdims=True)
    y = (x - mean) / jnp.sqrt(var + BN_EPS) * gamma[None, :, None, None] \
        + beta[None, :, None, None]
    return jax.nn.relu(y) if relu else y


def _unit(x, p, stride, first, low):
    a1 = _bn_relu(x, p["bn1_gamma"], p["bn1_beta"])
    y = _conv(a1, p["conv1_weight"], 1, 0, low)
    y = _bn_relu(y, p["bn2_gamma"], p["bn2_beta"])
    y = _conv(y, p["conv2_weight"], stride, 1, low)
    y = _bn_relu(y, p["bn3_gamma"], p["bn3_beta"])
    y = _conv(y, p["conv3_weight"], 1, 0, low)
    short = _conv(a1, p["sc_weight"], stride, 0, low) if first else x
    return y + short


def loss_fn(params, data, label, num_layers, low=False):
    """Mean softmax cross-entropy of one batch, training mode."""
    units = _UNITS[int(num_layers)]
    x = _conv(data, params["conv0_weight"], 2, 3, low)
    x = _bn_relu(x, params["bn0_gamma"], params["bn0_beta"])
    x = jax.lax.reduce_window(x, -jnp.inf, jax.lax.max, (1, 1, 3, 3),
                              (1, 1, 2, 2),
                              [(0, 0), (0, 0), (1, 1), (1, 1)])
    for s, n_units in enumerate(units):
        for u in range(n_units):
            pre = "stage%d_unit%d_" % (s + 1, u + 1)
            p = {k[len(pre):]: v for k, v in params.items()
                 if k.startswith(pre)}
            stride = 2 if (u == 0 and s > 0) else 1
            x = jax.checkpoint(functools.partial(
                _unit, stride=stride, first=(u == 0), low=low))(x, p)
    x = _bn_relu(x, params["bn1_gamma"], params["bn1_beta"])
    x = x.mean((2, 3))
    dense = lambda x, w: x @ w.T
    logits = (_fp8(dense) if low else dense)(x, params["fc1_weight"]) \
        + params["fc1_bias"]
    logp = jax.nn.log_softmax(logits, -1)
    picked = jnp.take_along_axis(
        logp, label.astype(jnp.int32)[:, None], axis=1)
    return -picked.mean()


def norms(tree):
    return {k: jnp.sqrt(jnp.sum(jnp.square(v.astype(jnp.float32))))
            for k, v in tree.items()}


@functools.lru_cache(maxsize=None)
def _step_programs(layers, lr, mom_c, wd, low):
    """The reference's step and the norm of a change, compiled once
    per (depth, hyper-parameters, precision)."""
    @jax.jit
    def step(params, mom, data, label):
        with jax.default_matmul_precision("highest"):
            loss, grads = jax.value_and_grad(loss_fn)(
                params, data, label, layers, low)
        mom = {k: mom_c * mom[k] - lr * (grads[k] + wd * params[k])
               for k in params}
        new = {k: params[k] + mom[k] for k in params}
        return loss, norms(grads), new, mom

    @jax.jit
    def change(a, b):
        return norms({k: a[k] - b[k] for k in a})

    return step, change


def follow(cfg, traffic, seed, steps=3, low=False):
    """The first `steps` steps from the seed: each step's loss, the
    per-leaf norm of the first gradient as the optimizer gets it (mean
    over the batch, before weight decay), and the per-leaf norm of the
    parameters' change after all of them."""
    step, change = _step_programs(
        int(cfg["num_layers"]), float(traffic["learning_rate"]),
        float(traffic["momentum"]), float(traffic["weight_decay"]),
        bool(low))
    params0 = make_params(cfg, seed)
    params = params0
    mom = jax.tree.map(jnp.zeros_like, params0)
    losses, grad_norms = [], None
    for s in range(steps):
        data, label = make_batches(cfg, traffic, seed, s, 1)
        loss, gn, params, mom = step(params, mom, data, label)
        losses.append(float(loss))
        if s == 0:
            grad_norms = {k: float(v) for k, v in gn.items()}
    delta = {k: float(v) for k, v in change(params, params0).items()}
    return {"loss": losses, "grad_norm": grad_norms,
            "update_norm": delta}


def total_gap(got, want):
    """The gap between the norms over all leaves together."""
    g = float(np.sqrt(sum(v * v for v in got.values())))
    w = float(np.sqrt(sum(v * v for v in want.values())))
    return abs(g - w) / w


def _leaf_gaps(got, want):
    """(got - want) of each leaf's norm, against the reference's norm
    of that leaf or of the median leaf, whichever is larger (some
    gradients are all but zero)."""
    med = float(np.median(list(want.values())))
    return {k: (got[k] - w) / max(w, med) for k, w in want.items()}, med


def leaf_deficit(got, want):
    """How far the program's norm of some leaf falls short of the
    reference's, by the leaf that falls shortest: 1 for a leaf at or
    above the median that was left unchanged or whose gradient was
    zeroed, and 0 where no leaf falls short."""
    return max(0.0, -min(_leaf_gaps(got, want)[0].values()))


def widest_leaves(got, want, n=4):
    """The `n` leaves whose norms differ most, for the log."""
    gaps, med = _leaf_gaps(got, want)
    return ["%s %+.3g (%.3g against %.3g, median %.3g)"
            % (k, gaps[k], got[k], want[k], med)
            for k in sorted(gaps, key=lambda k: -abs(gaps[k]))[:n]]
