"""Neural-network layer ops — the MXU-heavy kernels.

Reference: src/operator/*.{cc,cu,-inl.h} (SURVEY.md N9): Convolution,
FullyConnected, BatchNorm, Pooling, Activation, LeakyReLU, Dropout, LRN,
InstanceNorm, UpSampling, sequence ops…

TPU-native notes:
 * Convolution/FC lower to ``lax.conv_general_dilated``/``dot_general`` —
   XLA tiles these onto the MXU; layouts stay NCHW at the API surface
   (reference compatible) and XLA picks the internal layout.
 * BatchNorm threads its moving stats functionally; the registry writes them
   back into the aux NDArrays (aux-state parity with the reference's
   mutable aux arrays).
 * Dropout takes a traced PRNG key (needs_rng) so compiled graphs stay pure.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax, nn as jnn

from .registry import register


def _pair(v, n=2):
    if isinstance(v, (tuple, list)):
        t = tuple(int(x) for x in v)
        return t
    return (int(v),) * n


# ---------------------------------------------------------------------------
# FullyConnected — reference fully_connected-inl.h:112-176 (linalg_gemm)
# ---------------------------------------------------------------------------

@register("FullyConnected", arg_names=("data", "weight", "bias"),
          defaults={"num_hidden": 0, "no_bias": False, "flatten": True})
def _fully_connected(data, weight, bias=None, num_hidden=0, no_bias=False,
                     flatten=True, **_):
    if flatten:
        x = data.reshape(data.shape[0], -1)
    else:
        x = data
    if weight.dtype != x.dtype:
        weight = weight.astype(x.dtype)
    # no preferred_element_type: the MXU accumulates in f32 internally
    # for bf16 operands anyway, and mixed-dtype conv/dot transpose rules
    # reject an f32 cotangent against bf16 residuals
    out = jnp.dot(x, weight.T)
    if not no_bias and bias is not None:
        out = out + bias
    return out


# ---------------------------------------------------------------------------
# Convolution — reference convolution-inl.h; NCHW/OIHW like the reference,
# grouped conv via feature_group_count.
# ---------------------------------------------------------------------------

@register("Convolution", arg_names=("data", "weight", "bias"),
          aliases=("Convolution_v1",),
          defaults={"kernel": (), "stride": (), "dilate": (), "pad": (),
                    "num_filter": 0, "num_group": 1, "no_bias": False,
                    "workspace": 1024, "cudnn_tune": None,
                    "cudnn_off": False, "layout": None})
def _convolution(data, weight, bias=None, kernel=(), stride=(), dilate=(),
                 pad=(), num_filter=0, num_group=1, no_bias=False, **_):
    nd = len(kernel) if kernel else data.ndim - 2
    stride = _pair(stride, nd) if stride else (1,) * nd
    dilate = _pair(dilate, nd) if dilate else (1,) * nd
    pad = _pair(pad, nd) if pad else (0,) * nd
    if nd == 1:
        # lift 1D conv to 2D (TPU MXU prefers 2D convs)
        out = _convolution(data[..., None], weight[..., None],
                           bias, kernel=(kernel[0], 1),
                           stride=(stride[0], 1), dilate=(dilate[0], 1),
                           pad=(pad[0], 0), num_filter=num_filter,
                           num_group=num_group, no_bias=True)
        out = out[..., 0]
        if not no_bias and bias is not None:
            out = out + bias.reshape((1, -1, 1))
        return out
    dn_spec = ("NCHW", "OIHW", "NCHW") if nd == 2 else \
        ("NCDHW", "OIDHW", "NCDHW")
    dn = lax.conv_dimension_numbers(data.shape, weight.shape, dn_spec)
    if weight.dtype != data.dtype:
        # mixed-precision tolerance: compute in the activation dtype
        weight = weight.astype(data.dtype)
    out = lax.conv_general_dilated(
        data, weight,
        window_strides=stride,
        padding=tuple((p, p) for p in pad),
        rhs_dilation=dilate,
        dimension_numbers=dn,
        feature_group_count=num_group)
    if not no_bias and bias is not None:
        out = out + bias.reshape((1, -1) + (1,) * nd)
    return out


@register("Deconvolution", arg_names=("data", "weight", "bias"),
          defaults={"kernel": (), "stride": (), "dilate": (), "pad": (),
                    "adj": (), "target_shape": (), "num_filter": 0,
                    "num_group": 1, "no_bias": True, "workspace": 512,
                    "cudnn_tune": None, "cudnn_off": False, "layout": None})
def _deconvolution(data, weight, bias=None, kernel=(), stride=(), dilate=(),
                   pad=(), adj=(), target_shape=(), num_filter=0,
                   num_group=1, no_bias=True, **_):
    nd = len(kernel) if kernel else 2
    stride = _pair(stride, nd) if stride else (1,) * nd
    dilate = _pair(dilate, nd) if dilate else (1,) * nd
    pad = _pair(pad, nd) if pad else (0,) * nd
    adj = _pair(adj, nd) if adj else (0,) * nd
    if weight.dtype != data.dtype:
        # mixed-precision tolerance (same as Convolution)
        weight = weight.astype(data.dtype)
    # ConvTranspose = grad of conv w.r.t. input: lhs-dilated conv with
    # flipped kernel. weight layout: (in_c, out_c/g, kh, kw) like reference.
    w = jnp.flip(weight, axis=tuple(range(2, 2 + nd)))
    w = jnp.swapaxes(w, 0, 1)  # -> (out_c/g, in_c, kh, kw)
    if num_group > 1:
        # regroup for feature_group_count semantics
        ic = data.shape[1]
        w = weight.reshape(num_group, ic // num_group, -1, *weight.shape[2:])
        w = jnp.flip(w, axis=tuple(range(3, 3 + nd)))
        w = jnp.swapaxes(w, 1, 2).reshape(-1, ic // num_group,
                                          *weight.shape[2:])
    dn_spec = ("NCHW", "OIHW", "NCHW") if nd == 2 else ("NCDHW", "OIDHW", "NCDHW")
    dn = lax.conv_dimension_numbers(data.shape, w.shape, dn_spec)
    padding = tuple(
        (dilate[i] * (kernel[i] - 1) - pad[i],
         dilate[i] * (kernel[i] - 1) - pad[i] + adj[i])
        for i in range(nd))
    out = lax.conv_general_dilated(
        data, w, window_strides=(1,) * nd, padding=padding,
        lhs_dilation=stride, rhs_dilation=dilate, dimension_numbers=dn,
        feature_group_count=num_group)
    if not no_bias and bias is not None:
        out = out + bias.reshape((1, -1) + (1,) * nd)
    return out


# ---------------------------------------------------------------------------
# Pooling — reference pooling-inl.h; NCHW reduce_window.
# ---------------------------------------------------------------------------

@register("Pooling", arg_names=("data",), aliases=("Pooling_v1",),
          defaults={"kernel": (), "pool_type": "max", "stride": (),
                    "pad": (), "global_pool": False,
                    "pooling_convention": "valid", "cudnn_off": False})
def _pooling(data, kernel=(), pool_type="max", stride=(), pad=(),
             global_pool=False, pooling_convention="valid", **_):
    nd = data.ndim - 2
    if global_pool:
        axes = tuple(range(2, data.ndim))
        if pool_type == "max":
            return jnp.max(data, axis=axes, keepdims=True)
        return jnp.mean(data, axis=axes, keepdims=True)
    kernel = _pair(kernel, nd)
    stride = _pair(stride, nd) if stride else (1,) * nd
    pad = _pair(pad, nd) if pad else (0,) * nd
    window = (1, 1) + kernel
    strides = (1, 1) + stride
    if pooling_convention == "full":
        # ceil-mode: pad on the high side so ceil division is achieved
        extra = []
        for i in range(nd):
            size = data.shape[2 + i]
            out_f = int(np.ceil((size + 2 * pad[i] - kernel[i]) /
                                float(stride[i]))) + 1
            needed = (out_f - 1) * stride[i] + kernel[i] - size - 2 * pad[i]
            extra.append(max(0, needed))
        padding = ((0, 0), (0, 0)) + tuple(
            (pad[i], pad[i] + extra[i]) for i in range(nd))
    else:
        padding = ((0, 0), (0, 0)) + tuple((p, p) for p in pad)
    if pool_type == "max":
        init = -jnp.inf if jnp.issubdtype(data.dtype, jnp.floating) else \
            jnp.iinfo(data.dtype).min
        return lax.reduce_window(data, init, lax.max, window, strides,
                                 padding)
    if pool_type == "avg":
        summed = lax.reduce_window(data, 0.0, lax.add, window, strides,
                                   padding)
        counts = lax.reduce_window(jnp.ones_like(data), 0.0, lax.add,
                                   window, strides, padding)
        return summed / counts
    if pool_type == "sum":
        return lax.reduce_window(data, 0.0, lax.add, window, strides,
                                 padding)
    raise ValueError("unknown pool_type %r" % pool_type)


# ---------------------------------------------------------------------------
# BatchNorm — reference batch_norm-inl.h; aux moving stats are state.
# fn returns (out[, mean, var], new_moving_mean, new_moving_var)
# ---------------------------------------------------------------------------

@register("BatchNorm", arg_names=("data", "gamma", "beta", "moving_mean",
                                  "moving_var"),
          aliases=("BatchNorm_v1",), takes_is_train=True,
          state_inputs=(3, 4),
          defaults={"eps": 1e-3, "momentum": 0.9, "fix_gamma": True,
                    "use_global_stats": False, "output_mean_var": False,
                    "axis": 1, "cudnn_off": False})
def _batch_norm(data, gamma, beta, moving_mean, moving_var, eps=1e-3,
                momentum=0.9, fix_gamma=True, use_global_stats=False,
                output_mean_var=False, axis=1, is_train=False, **_):
    axis = axis % data.ndim
    red = tuple(i for i in range(data.ndim) if i != axis)
    bshape = tuple(data.shape[axis] if i == axis else 1
                   for i in range(data.ndim))
    g = jnp.ones_like(gamma) if fix_gamma else gamma
    # statistics in float32 regardless of compute dtype (mixed-precision
    # discipline: bf16 activations, f32 batch stats), output back in the
    # input dtype so downstream convs see one dtype
    if is_train and not use_global_stats:
        # Plain mean/var with autodiff backward: no custom_vjp boundary,
        # so XLA fuses the statistics into the neighbouring convolutions.
        # The rewrites tried against it (one-pass closed-form vjp, einsum
        # statistics always and shape-gated, Pallas; and a dense max-pool
        # backward) all lost on the chip and were deleted in PR 29.
        # fix_gamma: g is ones_like(gamma), a constant, so gamma's
        # gradient is zero as in the reference.
        xf = data.astype(jnp.float32)
        mean = jnp.mean(xf, axis=red)
        var = jnp.var(xf, axis=red)
        inv = lax.rsqrt(var.reshape(bshape) + eps)
        out = ((xf - mean.reshape(bshape)) * inv
               * g.reshape(bshape).astype(jnp.float32)
               + beta.reshape(bshape).astype(jnp.float32)
               ).astype(data.dtype)
        new_mm = moving_mean * momentum + mean * (1 - momentum)
        new_mv = moving_var * momentum + var * (1 - momentum)
        use_mean, use_var = mean, var
    else:
        xf = data.astype(jnp.float32)
        mean = moving_mean.astype(jnp.float32)
        var = moving_var.astype(jnp.float32)
        new_mm, new_mv = moving_mean, moving_var
        use_mean, use_var = mean, var
        inv = lax.rsqrt(use_var.reshape(bshape) + eps)
        out = ((xf - use_mean.reshape(bshape)) * inv *
               g.reshape(bshape).astype(jnp.float32) +
               beta.reshape(bshape).astype(jnp.float32)).astype(
            data.dtype)
    if output_mean_var:
        return (out, use_mean, lax.rsqrt(use_var + eps),
                lax.stop_gradient(new_mm), lax.stop_gradient(new_mv))
    return (out, lax.stop_gradient(new_mm), lax.stop_gradient(new_mv))


@register("InstanceNorm", arg_names=("data", "gamma", "beta"),
          defaults={"eps": 1e-3})
def _instance_norm(data, gamma, beta, eps=1e-3, **_):
    red = tuple(range(2, data.ndim))
    mean = jnp.mean(data, axis=red, keepdims=True)
    var = jnp.var(data, axis=red, keepdims=True)
    bshape = (1, -1) + (1,) * (data.ndim - 2)
    return (data - mean) * lax.rsqrt(var + eps) * gamma.reshape(bshape) + \
        beta.reshape(bshape)


@register("LayerNorm", arg_names=("data", "gamma", "beta"),
          defaults={"axis": -1, "eps": 1e-5, "output_mean_var": False})
def _layer_norm(data, gamma, beta=None, axis=-1, eps=1e-5,
                output_mean_var=False, **_):
    """no_bias (an attr; ops/shape_hooks.py drops the argument): a
    gain and no "<name>_beta"."""
    mean = jnp.mean(data, axis=axis, keepdims=True)
    var = jnp.var(data, axis=axis, keepdims=True)
    out = (data - mean) * lax.rsqrt(var + eps)
    bshape = [1] * data.ndim
    bshape[axis] = data.shape[axis]
    out = out * gamma.reshape(bshape)
    if beta is not None:
        out = out + beta.reshape(bshape)
    if output_mean_var:
        return out, jnp.squeeze(mean, axis), jnp.squeeze(var, axis)
    return out


# ---------------------------------------------------------------------------
# Activations
# ---------------------------------------------------------------------------

@register("Activation", arg_names=("data",),
          defaults={"act_type": "relu"})
def _activation(data, act_type="relu", **_):
    if act_type == "relu":
        return jnp.maximum(data, 0)
    if act_type == "sigmoid":
        return jnn.sigmoid(data)
    if act_type == "tanh":
        return jnp.tanh(data)
    if act_type == "softrelu":
        return jnn.softplus(data)
    if act_type == "softsign":
        return data / (1 + jnp.abs(data))
    if act_type == "silu":
        return jnn.silu(data)
    raise ValueError("unknown act_type %r" % act_type)


@register("LeakyReLU", arg_names=("data", "gamma"), needs_rng=True,
          takes_is_train=True,
          defaults={"act_type": "leaky", "slope": 0.25,
                    "lower_bound": 0.125, "upper_bound": 0.334})
def _leaky_relu(data, gamma=None, act_type="leaky", slope=0.25,
                lower_bound=0.125, upper_bound=0.334, is_train=False,
                rng=None, **_):
    if act_type == "leaky":
        return jnp.where(data >= 0, data, slope * data)
    if act_type == "elu":
        return jnp.where(data >= 0, data, slope * jnp.expm1(data))
    if act_type == "prelu":
        g = gamma.reshape((1, -1) + (1,) * (data.ndim - 2)) \
            if gamma.ndim == 1 and data.ndim > 1 else gamma
        return jnp.where(data >= 0, data, g * data)
    if act_type == "rrelu":
        if is_train:
            import jax
            s = jax.random.uniform(rng, data.shape, data.dtype,
                                   lower_bound, upper_bound)
        else:
            s = (lower_bound + upper_bound) / 2.0
        return jnp.where(data >= 0, data, s * data)
    raise ValueError("unknown act_type %r" % act_type)


@register("SoftmaxActivation", arg_names=("data",),
          defaults={"mode": "instance"})
def _softmax_activation(data, mode="instance", **_):
    if mode == "channel":
        return jnn.softmax(data, axis=1)
    return jnn.softmax(data.reshape(data.shape[0], -1), axis=-1).reshape(
        data.shape)


# ---------------------------------------------------------------------------
# Dropout — traced PRNG key keeps jitted training steps pure.
# ---------------------------------------------------------------------------

@register("Dropout", arg_names=("data",), needs_rng=True,
          takes_is_train=True,
          defaults={"p": 0.5, "mode": "training"})
def _dropout(data, p=0.5, mode="training", is_train=False, rng=None, **_):
    import jax
    if p <= 0 or (not is_train and mode != "always"):
        return data
    keep = 1.0 - p
    mask = jax.random.bernoulli(rng, keep, data.shape)
    return jnp.where(mask, data / keep, 0).astype(data.dtype)


# ---------------------------------------------------------------------------
# LRN — reference lrn-inl.h
# ---------------------------------------------------------------------------

@register("LRN", arg_names=("data",),
          defaults={"alpha": 1e-4, "beta": 0.75, "knorm": 2.0, "nsize": 5})
def _lrn(data, alpha=1e-4, beta=0.75, knorm=2.0, nsize=5, **_):
    sq = jnp.square(data)
    half = nsize // 2
    sq_pad = jnp.pad(sq, ((0, 0), (half, half)) + ((0, 0),) * (data.ndim - 2))
    window = jnp.zeros_like(sq)
    for i in range(nsize):
        window = window + lax.dynamic_slice_in_dim(sq_pad, i, data.shape[1],
                                                   axis=1)
    return data / jnp.power(knorm + alpha / nsize * window, beta)


# ---------------------------------------------------------------------------
# UpSampling / Crop
# ---------------------------------------------------------------------------

@register("UpSampling", arg_names=None,
          defaults={"scale": 1, "sample_type": "nearest", "num_args": 1,
                    "num_filter": 0, "multi_input_mode": "concat",
                    "workspace": 512})
def _upsampling(*args, scale=1, sample_type="nearest",
                multi_input_mode="concat", **_):
    import jax
    outs = []
    data = args[0]
    h, w = data.shape[2] * scale, data.shape[3] * scale
    for x in (args if sample_type == "nearest" else args[:1]):
        if sample_type == "nearest":
            out = jnp.repeat(jnp.repeat(x, scale, axis=2), scale, axis=3)
        else:
            out = jax.image.resize(x.astype(jnp.float32),
                                   x.shape[:2] + (h, w),
                                   method="bilinear").astype(x.dtype)
        outs.append(out)
    if len(outs) == 1:
        return outs[0]
    if multi_input_mode == "sum":
        return sum(outs)
    return jnp.concatenate(outs, axis=1)


@register("Crop", arg_names=None,
          defaults={"num_args": 1, "offset": (0, 0), "h_w": (0, 0),
                    "center_crop": False})
def _crop(*args, offset=(0, 0), h_w=(0, 0), center_crop=False, **_):
    data = args[0]
    if len(args) == 2:
        h, w = args[1].shape[2], args[1].shape[3]
    else:
        h, w = h_w
    if center_crop:
        oy = (data.shape[2] - h) // 2
        ox = (data.shape[3] - w) // 2
    else:
        oy, ox = offset
    return data[:, :, oy:oy + h, ox:ox + w]


# ---------------------------------------------------------------------------
# Sequence ops — reference src/operator/sequence_*.cc
# ---------------------------------------------------------------------------

@register("SequenceMask", arg_names=("data", "sequence_length"),
          nondiff_inputs=(1,),
          defaults={"use_sequence_length": False, "value": 0.0, "axis": 0})
def _sequence_mask(data, sequence_length=None, use_sequence_length=False,
                   value=0.0, axis=0, **_):
    if not use_sequence_length or sequence_length is None:
        return data
    maxlen = data.shape[axis]
    steps = jnp.arange(maxlen)
    mask = steps[:, None] < sequence_length[None, :].astype(jnp.int32)
    if axis == 1:
        mask = mask.T
    mask = mask.reshape(mask.shape + (1,) * (data.ndim - 2))
    return jnp.where(mask, data, value)


@register("SequenceLast", arg_names=("data", "sequence_length"),
          nondiff_inputs=(1,),
          defaults={"use_sequence_length": False, "axis": 0})
def _sequence_last(data, sequence_length=None, use_sequence_length=False,
                   axis=0, **_):
    if not use_sequence_length or sequence_length is None:
        return jnp.take(data, data.shape[axis] - 1, axis=axis)
    idx = (sequence_length.astype(jnp.int32) - 1)
    batch = jnp.arange(data.shape[1 - axis])
    if axis == 0:
        return data[idx, batch]
    return data[batch, idx]


@register("SequenceReverse", arg_names=("data", "sequence_length"),
          nondiff_inputs=(1,),
          defaults={"use_sequence_length": False, "axis": 0})
def _sequence_reverse(data, sequence_length=None, use_sequence_length=False,
                      axis=0, **_):
    if not use_sequence_length or sequence_length is None:
        return jnp.flip(data, axis=0)
    maxlen = data.shape[0]
    lens = sequence_length.astype(jnp.int32)
    steps = jnp.arange(maxlen)[:, None]
    rev_idx = jnp.where(steps < lens[None, :], lens[None, :] - 1 - steps,
                        steps)
    batch = jnp.arange(data.shape[1])[None, :]
    return data[rev_idx, batch]
