"""Pallas BatchNorm training kernels — the below-XLA experiment for
the ResNet-50 MFU gap (docs/mfu_analysis.md measured BN statistics at
~18% of the step; reference hand-optimized BN too,
src/operator/batch_norm.cc).

Why Pallas here: training BN's HBM floor is 2 reads of x + 1 write of
y forward (stats pass, then apply pass) and 2 reads of (dy, x) + 1
write of dx backward. ops/nn.py's one-pass rewrite reaches that floor
only IF XLA fuses the sibling sum(x)/sum(x^2) reductions into one loop
and the apply into its consumer — a fusion decision we cannot pin from
the HLO level. These kernels make the pass structure EXPLICIT:

* `_stats` — one sequential-grid pass over x accumulating the shifted
  sibling sums (s1, s2) in f32 VMEM accumulators (grid over N, one
  sample's (C, HW) tile per step);
* `_apply` — one pass computing y = A*x + B with per-channel A/B
  precomputed host-side (tiny (C,) math);
* `_bwd_reduce` — one pass over (dy, x) accumulating sum(dy) and
  sum(dy*(x-mean));
* `_bwd_dx` — one pass computing dx = A*dy + C2*(x-mean) + B.

Numerics match ops/nn.py's shifted one-pass core: the same per-channel
shift c (first sample's channel mean) guards the E[x^2]-E[x]^2
cancellation, and the same closed-form backward (including the
mean/var output cotangents) is used.

Routing: `MXNET_BN_PALLAS=1` switches ops/nn.py's training BatchNorm
core to this path for 4-D NCHW inputs on TPU; anywhere else it runs in
Pallas interpret mode (tests pin it against the jnp core on CPU).
Measured A/B vs the XLA one-pass core: `benchmark/bench_bn.py`.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

from ._pallas import interpret as _interpret


# ---------------------------------------------------------------------------
# kernels (grid over N; one (1, C, HW) sample tile per step)
# ---------------------------------------------------------------------------

def _stats_kernel(x_ref, c_ref, s1_ref, s2_ref):
    i = pl.program_id(0)
    x = x_ref[...].astype(jnp.float32)          # (1, C, HW)
    xc = x - c_ref[...][:, :, None]             # shift: kills E[x^2]
    ps1 = jnp.sum(xc, axis=(0, 2))              # cancellation
    ps2 = jnp.sum(xc * xc, axis=(0, 2))

    @pl.when(i == 0)
    def _init():
        s1_ref[...] = jnp.zeros_like(s1_ref)
        s2_ref[...] = jnp.zeros_like(s2_ref)

    s1_ref[...] += ps1[None]
    s2_ref[...] += ps2[None]


def _apply_kernel(x_ref, a_ref, b_ref, y_ref):
    x = x_ref[...].astype(jnp.float32)
    y = x * a_ref[...][:, :, None] + b_ref[...][:, :, None]
    y_ref[...] = y.astype(y_ref.dtype)


def _bwd_reduce_kernel(dy_ref, x_ref, mean_ref, db_ref, dxc_ref):
    i = pl.program_id(0)
    dy = dy_ref[...].astype(jnp.float32)
    xc = x_ref[...].astype(jnp.float32) - mean_ref[...][:, :, None]
    pdb = jnp.sum(dy, axis=(0, 2))
    pdxc = jnp.sum(dy * xc, axis=(0, 2))

    @pl.when(i == 0)
    def _init():
        db_ref[...] = jnp.zeros_like(db_ref)
        dxc_ref[...] = jnp.zeros_like(dxc_ref)

    db_ref[...] += pdb[None]
    dxc_ref[...] += pdxc[None]


def _bwd_dx_kernel(dy_ref, x_ref, a_ref, c2_ref, b_ref, mean_ref,
                   dx_ref):
    dy = dy_ref[...].astype(jnp.float32)
    xc = x_ref[...].astype(jnp.float32) - mean_ref[...][:, :, None]
    dx = (dy * a_ref[...][:, :, None]
          + xc * c2_ref[...][:, :, None]
          + b_ref[...][:, :, None])
    dx_ref[...] = dx.astype(dx_ref.dtype)


# ---------------------------------------------------------------------------
# pallas_call wrappers
# ---------------------------------------------------------------------------

def _sample_spec(C, HW):
    return pl.BlockSpec((1, C, HW), lambda i: (i, 0, 0))


def _chan_spec(C):
    return pl.BlockSpec((1, C), lambda i: (0, 0))


def _stats(x3, c):
    N, C, HW = x3.shape
    s1, s2 = pl.pallas_call(
        _stats_kernel,
        grid=(N,),
        in_specs=[_sample_spec(C, HW), _chan_spec(C)],
        out_specs=[_chan_spec(C), _chan_spec(C)],
        out_shape=[jax.ShapeDtypeStruct((1, C), jnp.float32)] * 2,
        interpret=_interpret(),
    )(x3, c[None])
    return s1[0], s2[0]


def _apply(x3, a, b):
    N, C, HW = x3.shape
    return pl.pallas_call(
        _apply_kernel,
        grid=(N,),
        in_specs=[_sample_spec(C, HW), _chan_spec(C), _chan_spec(C)],
        out_specs=_sample_spec(C, HW),
        out_shape=jax.ShapeDtypeStruct((N, C, HW), x3.dtype),
        interpret=_interpret(),
    )(x3, a[None], b[None])


def _bwd_reduce(dy3, x3, mean):
    N, C, HW = x3.shape
    db, dxc = pl.pallas_call(
        _bwd_reduce_kernel,
        grid=(N,),
        in_specs=[_sample_spec(C, HW), _sample_spec(C, HW),
                  _chan_spec(C)],
        out_specs=[_chan_spec(C), _chan_spec(C)],
        out_shape=[jax.ShapeDtypeStruct((1, C), jnp.float32)] * 2,
        interpret=_interpret(),
    )(dy3, x3, mean[None])
    return db[0], dxc[0]


def _bwd_dx(dy3, x3, a, c2, b, mean, out_dtype):
    N, C, HW = x3.shape
    return pl.pallas_call(
        _bwd_dx_kernel,
        grid=(N,),
        in_specs=[_sample_spec(C, HW), _sample_spec(C, HW),
                  _chan_spec(C), _chan_spec(C), _chan_spec(C),
                  _chan_spec(C)],
        out_specs=_sample_spec(C, HW),
        out_shape=jax.ShapeDtypeStruct((N, C, HW), out_dtype),
        interpret=_interpret(),
    )(dy3, x3, a[None], c2[None], b[None], mean[None])


# ---------------------------------------------------------------------------
# the training core (same contract as ops/nn.py:_bn_train_core for the
# NCHW case: returns (y, mean, var) with the closed-form custom VJP)
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def bn_train_pallas(x, g, beta, eps):
    y, mean, var, _ = _fwd_impl(x, g, beta, eps)
    return y, mean, var


def _fwd_impl(x, g, beta, eps):
    N, C, H, W = x.shape
    x3 = x.reshape(N, C, H * W)
    m = N * H * W
    # per-channel shift: first sample's channel mean (tiny 1/N pass)
    c = lax.stop_gradient(
        jnp.mean(x3[0].astype(jnp.float32), axis=1))
    s1, s2 = _stats(x3, c)
    mean_s = s1 / m
    mean = c + mean_s
    var = jnp.maximum(s2 / m - jnp.square(mean_s), 0.0)
    inv = lax.rsqrt(var + eps)
    # y = A*x + B with per-channel A/B (tiny host-side math)
    a = g.astype(jnp.float32) * inv
    b = beta.astype(jnp.float32) - mean * a
    y = _apply(x3, a, b).reshape(x.shape)
    return y, mean, var, inv


def _fwd(x, g, beta, eps):
    y, mean, var, inv = _fwd_impl(x, g, beta, eps)
    return (y, mean, var), (x, g, jnp.zeros((), beta.dtype),
                            mean, inv)


def _bwd(eps, res, cts):
    # dy stays in its incoming dtype: an .astype here would
    # materialize a full f32 copy that XLA cannot fuse into the
    # pallas_call operand (the kernels upcast tile-wise internally,
    # exactly like they do for x) — casting would break the 2-read
    # backward this module exists to guarantee
    dy = cts[0]
    dmean = cts[1].astype(jnp.float32)
    dvar = cts[2].astype(jnp.float32)
    x, g, beta_proto, mean, inv = res
    N, C, H, W = x.shape
    m = N * H * W
    x3 = x.reshape(N, C, H * W)
    dy3 = dy.reshape(N, C, H * W)
    db, dxc = _bwd_reduce(dy3, x3, mean)
    dgx = dxc * inv                      # = sum(dy * xhat)
    gf = g.astype(jnp.float32)
    k = gf * inv / m
    # dx = A*dy + C2*(x-mean) + B, coefficients per channel:
    a = gf * inv                         # k*m
    c2 = -k * inv * dgx + (2.0 / m) * dvar
    b = -k * db + dmean / m
    dx = _bwd_dx(dy3, x3, a, c2, b, mean, x.dtype).reshape(x.shape)
    return (dx, dgx.astype(g.dtype),
            db.astype(beta_proto.dtype))


bn_train_pallas.defvjp(_fwd, _bwd)
