"""Indexing ops: Embedding, take, one_hot, pick, gather/scatter.

Reference: src/operator/tensor/indexing_op.* (SURVEY.md N11). Embedding's
backward is a scatter-add over the weight — XLA lowers the gather/scatter
pair onto the TPU natively; no custom kernel needed.
"""
from __future__ import annotations

import jax.numpy as jnp
from jax import lax

from .registry import register


@register("Embedding", arg_names=("data", "weight"), nondiff_inputs=(0,),
          defaults={"input_dim": 0, "output_dim": 0, "dtype": "float32"})
def _embedding(data, weight, **_):
    # backward is autodiff's scatter-add; sort + segment-sum read a
    # third slower on the chip at OPT-1.3B's table (PERF.md §6, PR 45)
    return jnp.take(weight, data.astype(jnp.int32), axis=0)


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
