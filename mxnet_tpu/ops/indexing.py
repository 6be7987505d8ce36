"""Indexing ops: Embedding, take, one_hot, pick, gather/scatter.

Reference: src/operator/tensor/indexing_op.* (SURVEY.md N11). Embedding's
backward is a scatter-add over the weight — XLA lowers the gather/scatter
pair onto the TPU natively; no custom kernel needed.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from .registry import register


# Embedding backward default. The staged A/B
# (benchmark/bench_embgrad.py at the flagship LM shape) has only been
# run on the host CPU, where scatter-add beat sort+segment-sum; on the
# chip it is not measured. The segsum formulation stays one env var
# away until the chip decides, where the traced ~8x-off-roofline
# scatter+Adam update (bench_out/trace_tlm_summary.txt) is still the
# open question (ROADMAP Speed 5).
_EMBED_GRAD_DEFAULT = "scatter"


@register("Embedding", arg_names=("data", "weight"), nondiff_inputs=(0,),
          defaults={"input_dim": 0, "output_dim": 0, "dtype": "float32"})
def _embedding(data, weight, **_):
    from .. import config as _config
    choice = _config.get("MXNET_EMBED_GRAD") or _EMBED_GRAD_DEFAULT
    if choice == "segsum":
        # backward as sort + segment-sum instead of autodiff's
        # scatter-add. Same values (duplicate ids accumulate in id
        # order after a stable sort).
        return _embedding_segsum(data, weight)
    if choice != "scatter":
        raise ValueError(
            "MXNET_EMBED_GRAD must be 'scatter', 'segsum' or unset "
            "(measured default: %r), got %r"
            % (_EMBED_GRAD_DEFAULT, choice))
    return jnp.take(weight, data.astype(jnp.int32), axis=0)


@jax.custom_vjp
def _embedding_segsum(data, weight):
    return jnp.take(weight, data.astype(jnp.int32), axis=0)


def _embedding_segsum_fwd(data, weight):
    return _embedding_segsum(data, weight), (data, weight.shape[0])


def _embedding_segsum_bwd(res, dy):
    data, V = res
    ids = data.astype(jnp.int32).reshape(-1)
    D = dy.shape[-1]
    if ids.shape[0] == 0:        # empty batch: reshape(-1) can't infer
        dw = jnp.zeros((V, D), dy.dtype)
        return jnp.zeros(data.shape, data.dtype), dw
    dy2 = dy.reshape(ids.shape[0], D)
    # stable sort by id, then tell the segment reduce the ids ARE
    # sorted — otherwise it lowers to the very scatter-add this
    # experiment exists to beat. Duplicate-id partials accumulate in
    # f32 here where scatter-add rounds to the weight dtype per step:
    # bit-equal in f32, equal up to (strictly less) rounding in bf16.
    order = jnp.argsort(ids, stable=True)
    dw = jax.ops.segment_sum(
        jnp.take(dy2, order, axis=0).astype(jnp.float32),
        jnp.take(ids, order), num_segments=V,
        indices_are_sorted=True)
    # ids are not differentiable; they ride the float32-input
    # convention, so their cotangent is explicit zeros
    return jnp.zeros(data.shape, data.dtype), dw.astype(dy.dtype)


_embedding_segsum.defvjp(_embedding_segsum_fwd, _embedding_segsum_bwd)


@register("take", arg_names=("a", "indices"), nondiff_inputs=(1,),
          defaults={"axis": 0, "mode": "clip"})
def _take(a, indices, axis=0, mode="clip", **_):
    idx = indices.astype(jnp.int32)
    if mode == "wrap":
        idx = jnp.mod(idx, a.shape[axis])
    else:
        idx = jnp.clip(idx, 0, a.shape[axis] - 1)
    return jnp.take(a, idx, axis=axis)


@register("batch_take", arg_names=("a", "indices"), nondiff_inputs=(1,))
def _batch_take(a, indices, **_):
    idx = indices.astype(jnp.int32)
    return jnp.take_along_axis(a, idx[:, None], axis=1)[:, 0]


@register("pick", arg_names=("data", "index"), nondiff_inputs=(1,),
          defaults={"axis": -1, "keepdims": False})
def _pick(data, index, axis=-1, keepdims=False, **_):
    idx = index.astype(jnp.int32)
    idx_exp = jnp.expand_dims(idx, axis if axis >= 0 else data.ndim + axis)
    out = jnp.take_along_axis(data, idx_exp, axis=axis)
    if not keepdims:
        out = jnp.squeeze(out, axis=axis)
    return out


@register("one_hot", arg_names=("indices",), differentiable=False,
          defaults={"depth": 0, "on_value": 1.0, "off_value": 0.0,
                    "dtype": "float32"})
def _one_hot(indices, depth=0, on_value=1.0, off_value=0.0,
             dtype="float32", **_):
    from ..base import np_dtype
    idx = indices.astype(jnp.int32)
    oh = jnp.equal(idx[..., None], jnp.arange(depth)).astype(np_dtype(dtype))
    return oh * on_value + (1 - oh) * off_value


@register("gather_nd", arg_names=("data", "indices"), nondiff_inputs=(1,))
def _gather_nd(data, indices, **_):
    idx = indices.astype(jnp.int32)
    m = idx.shape[0]
    return data[tuple(idx[i] for i in range(m))]


@register("scatter_nd", arg_names=("data", "indices"), nondiff_inputs=(1,),
          defaults={"shape": ()})
def _scatter_nd(data, indices, shape=(), **_):
    idx = indices.astype(jnp.int32)
    m = idx.shape[0]
    out = jnp.zeros(tuple(shape), data.dtype)
    return out.at[tuple(idx[i] for i in range(m))].set(data)


@register("_sparse_retain", arg_names=("data", "indices"), nondiff_inputs=(1,))
def _sparse_retain(data, indices, **_):
    idx = indices.astype(jnp.int32)
    mask = jnp.zeros((data.shape[0],), jnp.bool_).at[idx].set(True)
    return jnp.where(mask.reshape((-1,) + (1,) * (data.ndim - 1)), data, 0)


@register("_square_sum", arg_names=("data",),
          defaults={"axis": None, "keepdims": False})
def _square_sum(x, axis=None, keepdims=False, **_):
    out = jnp.sum(jnp.square(x), axis=axis, keepdims=keepdims)
    return out.reshape((1,)) if out.ndim == 0 else out
