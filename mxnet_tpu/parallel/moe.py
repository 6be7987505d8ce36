"""Mixture-of-experts FFN with expert parallelism (Switch-style top-1
routing over ``lax.all_to_all``).

New TPU-native capability (SURVEY §2.3: the reference has no MoE/expert
parallelism). Experts shard over a mesh axis; each device routes its
local tokens, packs them into per-expert capacity buffers, exchanges
buffers with one all_to_all (ICI), runs its resident experts' FFN, and
all_to_alls results back — the canonical TPU MoE dataflow (Shazeer et
al. 2017; Fedus et al., Switch Transformer, 2021).

Top-1 routing with capacity dropping: tokens beyond an expert's
capacity contribute zeros (add the usual residual connection around the
layer so dropped tokens pass through).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P


__all__ = ["moe_ffn", "dense_moe"]


def _route(x, gate_w, num_experts, capacity):
    """Top-1 routing of local tokens: returns (expert_id, slot, keep,
    gate_prob) per token — slot is the token's position in its expert's
    capacity buffer, assigned in token order (first come first served,
    the Switch discipline)."""
    probs = jax.nn.softmax(
        (x.astype(jnp.float32) @ gate_w.astype(jnp.float32)), axis=-1)
    gate = jnp.max(probs, axis=-1)
    expert = jnp.argmax(probs, axis=-1)
    onehot = jax.nn.one_hot(expert, num_experts, dtype=jnp.int32)
    slot = jnp.sum((jnp.cumsum(onehot, axis=0) - 1) * onehot, axis=-1)
    keep = slot < capacity
    return expert, jnp.clip(slot, 0, capacity - 1), keep, gate


def _dispatch(x, expert, slot, keep, num_buckets, cap):
    """Scatter kept tokens into (num_buckets, cap, D) capacity
    buffers."""
    disp = jnp.zeros((num_buckets, cap, x.shape[-1]), x.dtype)
    return disp.at[expert, slot].add(jnp.where(keep[:, None], x, 0))


def _combine(y, expert, slot, keep, gate, dtype):
    """Gather each token's expert output back, gated; dropped tokens
    zero."""
    out = y[expert, slot] * gate[:, None].astype(dtype)
    return jnp.where(keep[:, None], out, 0.0).astype(dtype)


def dense_moe(x, gate_w, w1, w2, capacity_factor=1.25):
    """Single-program Switch MoE: route local tokens into capacity
    buffers, run every expert's FFN, combine. Shares _route/_dispatch/
    _combine with the expert-parallel moe_ffn below (which inserts the
    all_to_all exchanges between the same stages).

    x (N, D); gate_w (D, E); w1 (E, D, H); w2 (E, H, D) -> (N, D),
    capacity-dropped tokens zero."""
    N = x.shape[0]
    E = gate_w.shape[1]
    cap = max(1, int(math.ceil(N * float(capacity_factor) / E)))
    expert, slot, keep, gate = _route(x, gate_w, E, cap)
    disp = _dispatch(x, expert, slot, keep, E, cap)
    h = jax.nn.relu(jnp.einsum("ecd,edh->ech", disp, w1))
    y = jnp.einsum("ech,ehd->ecd", h, w2)
    return _combine(y, expert, slot, keep, gate, x.dtype)


def moe_ffn(x, gate_w, w1, w2, mesh, axis_name="expert",
            capacity_factor=1.25):
    """Expert-parallel MoE FFN.

    x: (T, D) tokens, T sharded over ``axis_name``.
    gate_w: (D, E) router weights (replicated).
    w1: (E, D, H), w2: (E, H, D) expert weights, E sharded over the axis.
    Returns (T, D) with x's sharding; dropped-capacity tokens yield 0.
    """
    n = mesh.shape[axis_name]
    E = gate_w.shape[1]
    if E % n:
        raise ValueError("num_experts %d must divide over %d devices"
                         % (E, n))

    def local(xl, gw, w1l, w2l):
        # xl (Tl, D); w1l (El, D, H); w2l (El, H, D)
        Tl, D = xl.shape
        El = E // n
        cap = max(1, int(math.ceil(Tl * capacity_factor / E)))
        expert, slot, keep, gate = _route(xl, gw, E, cap)
        disp = _dispatch(xl, expert, slot, keep, E, cap)
        # exchange: device d keeps buffers for its El resident experts
        # from every sender -> (n senders, El, cap, D)
        recv = lax.all_to_all(disp.reshape(n, El, cap, D), axis_name,
                              split_axis=0, concat_axis=0, tiled=False)
        # recv: (n senders, El, cap, D) -> expert-major token queues
        tokens = recv.transpose(1, 0, 2, 3).reshape(El, n * cap, D)

        h = jax.nn.relu(jnp.einsum("ecd,edh->ech", tokens, w1l))
        y = jnp.einsum("ech,ehd->ecd", h, w2l)          # (El, n*cap, D)

        # back to sender-major and return to the owning devices
        y = y.reshape(El, n, cap, D).transpose(1, 0, 2, 3)
        back = lax.all_to_all(y, axis_name,
                              split_axis=0, concat_axis=0, tiled=False)
        # back: (n expert-groups, El, cap, D); group-major flatten IS
        # global expert order -> my tokens' buffers (E, cap, D)
        mine = back.reshape(E, cap, D)
        return _combine(mine, expert, slot, keep, gate, xl.dtype)

    fn = jax.shard_map(local, mesh=mesh,
                    in_specs=(P(axis_name), P(), P(axis_name), P(axis_name)),
                    out_specs=P(axis_name))
    return fn(x, gate_w, w1, w2)
