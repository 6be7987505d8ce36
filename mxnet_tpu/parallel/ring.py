"""Ring attention — sequence/context parallelism over the device mesh.

New TPU-native capability (SURVEY §2.3: the reference has NO sequence
parallelism; its long-sequence story was bucketing + BPTT truncation).
This is the standard ring schedule (Liu et al., Ring Attention, 2023):
queries stay put, key/value blocks rotate around the mesh axis via
``lax.ppermute`` (riding ICI neighbour links), and the flash-style
online softmax merges each visiting block — every device holds only
T/n of the sequence at any moment, so max context scales linearly with
the mesh axis while compute stays MXU-dense per block.

Compose with data/tensor parallel axes freely: q/k/v enter sharded
(B, H, T, D) with T split over ``axis_name``; output keeps that
sharding.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P


__all__ = ["ring_attention"]

_NEG_INF = -1e30


def _varying(x, axis_name):
    """Mark a fresh (replicated-typed) constant device-varying over the
    ring axis, as shard_map's type check wants of loop carries and
    branch outputs that meet varying values."""
    return lax.pcast(x, (axis_name,), to="varying")


def _ring_local(q, k, v, *, axis_name, causal, scale):
    """Per-device body: q (B,H,Tq,D) local; k/v local blocks that will
    rotate n-1 times.

    Each visiting block runs the Pallas flash kernel (MXU-dense,
    O(Tq + Tk) memory — no (Tq, Tk) score materialization, so local
    shards can be tens of thousands of tokens) returning normalized
    (o, lse); blocks combine by logsumexp merge. The causal mask over
    GLOBAL positions reduces, for equal shards, to three whole-block
    cases on the visiting block id: src < me fully visible, src == me
    the standard diagonal, src > me skipped."""
    n = lax.psum(1, axis_name)
    me = lax.axis_index(axis_name)
    B, H, Tq, D = q.shape
    Tk = k.shape[2]
    from ..ops.attention import flash_attention_with_lse
    q3 = q.reshape(B * H, Tq, D)

    def block_attend(k_cur, v_cur, src):
        k3 = k_cur.reshape(B * H, Tk, D)
        v3 = v_cur.reshape(B * H, Tk, D)

        def full(_):
            return flash_attention_with_lse(q3, k3, v3, scale=scale,
                                            causal=False)

        def diag(_):
            return flash_attention_with_lse(q3, k3, v3, scale=scale,
                                            causal=True)

        def skip(_):
            # fresh constants are replicated-typed; match the kernel
            # branches' device-varying outputs for lax.switch
            return tuple(_varying(x, axis_name) for x in (
                jnp.zeros(q3.shape, q3.dtype),
                jnp.full((B * H, Tq), _NEG_INF, jnp.float32)))

        if not causal:
            return full(None)
        if Tq != Tk:
            raise ValueError("causal ring attention needs equal "
                             "sequence shards (Tq=%d, Tk=%d)"
                             % (Tq, Tk))
        idx = jnp.where(src == me, 1, jnp.where(src < me, 0, 2))
        return lax.switch(idx, [full, diag, skip], None)

    def merge(o_acc, lse_acc, o_b, lse_b):
        lse = jnp.logaddexp(lse_acc, lse_b)
        w_a = jnp.exp(lse_acc - lse)[..., None]
        w_b = jnp.exp(lse_b - lse)[..., None]
        return (o_acc * w_a + o_b.astype(jnp.float32) * w_b, lse)

    o0 = jnp.zeros((B * H, Tq, D), jnp.float32)
    lse0 = jnp.full((B * H, Tq), _NEG_INF, jnp.float32)
    # constants enter the loop carry device-varying (their updates vary
    # over the ring axis; shard_map type-checks this)
    o0, lse0 = (_varying(x, axis_name) for x in (o0, lse0))
    perm = [(j, (j + 1) % n) for j in range(n)]

    def step(t, carry):
        k_cur, v_cur, o_acc, lse_acc = carry
        o_b, lse_b = block_attend(k_cur, v_cur, (me - t) % n)
        o_acc, lse_acc = merge(o_acc, lse_acc, o_b, lse_b)
        # rotate KV to the next neighbour (ICI hop), overlapping with
        # the next block's compute under XLA's async collectives
        k_nxt = lax.ppermute(k_cur, axis_name, perm)
        v_nxt = lax.ppermute(v_cur, axis_name, perm)
        return k_nxt, v_nxt, o_acc, lse_acc

    # n-1 rotations visit every remote block; the final visiting block is
    # consumed without a wasted last rotation (a collective in the loop
    # tail cannot be DCE'd by XLA)
    k_last, v_last, o_acc, lse_acc = lax.fori_loop(
        0, n - 1, step, (k, v, o0, lse0))
    o_b, lse_b = block_attend(k_last, v_last, (me - (n - 1)) % n)
    o_acc, _ = merge(o_acc, lse_acc, o_b, lse_b)
    return o_acc.reshape(B, H, Tq, D).astype(q.dtype)


def _ring_local_windowed(q, k, v, *, axis_name, scale, window, n):
    """Windowed (banded causal) ring body, UNROLLED over visiting-block
    distance t — n is static, so each step's band offset t*Tb is a
    static kernel parameter and, crucially, the loop runs only
    r = ceil((window-1)/Tb) rotations instead of n-1: a window reaches
    at most r predecessor blocks, so the ring only has to carry K/V
    that far (communication O(window), not O(T))."""
    me = lax.axis_index(axis_name)
    B, H, Tq, D = q.shape
    Tb = k.shape[2]
    if Tq != Tb:
        raise ValueError("windowed ring attention needs equal "
                         "sequence shards (Tq=%d, Tk=%d)" % (Tq, Tb))
    from ..ops.attention import flash_attention_with_lse
    q3 = q.reshape(B * H, Tq, D)
    r = 0 if window <= 1 else min(n - 1, (window - 2) // Tb + 1)

    def merge(o_acc, lse_acc, o_b, lse_b):
        lse = jnp.logaddexp(lse_acc, lse_b)
        w_a = jnp.exp(lse_acc - lse)[..., None]
        w_b = jnp.exp(lse_b - lse)[..., None]
        return (o_acc * w_a + o_b.astype(jnp.float32) * w_b, lse)

    o_acc = _varying(jnp.zeros((B * H, Tq, D), jnp.float32), axis_name)
    lse_acc = _varying(jnp.full((B * H, Tq), _NEG_INF, jnp.float32),
                       axis_name)
    perm = [(j, (j + 1) % n) for j in range(n)]
    k_cur, v_cur = k, v
    for t in range(r + 1):
        k3 = k_cur.reshape(B * H, Tb, D)
        v3 = v_cur.reshape(B * H, Tb, D)

        def compute(_, k3=k3, v3=v3, t=t):
            return flash_attention_with_lse(
                q3, k3, v3, scale=scale, causal=True, window=window,
                band_offset=t * Tb)

        def skip(_):
            return tuple(_varying(x, axis_name) for x in (
                jnp.zeros(q3.shape, q3.dtype),
                jnp.full((B * H, Tq), _NEG_INF, jnp.float32)))

        if t == 0:
            o_b, lse_b = compute(None)
        else:
            # devices whose t-th predecessor wraps past position 0
            # have no such block (causal): skip at run time
            o_b, lse_b = lax.cond(me >= t, compute, skip, None)
        o_acc, lse_acc = merge(o_acc, lse_acc, o_b, lse_b)
        if t < r:
            k_cur = lax.ppermute(k_cur, axis_name, perm)
            v_cur = lax.ppermute(v_cur, axis_name, perm)
    return o_acc.reshape(B, H, Tq, D).astype(q.dtype)


def ring_attention(q, k, v, mesh, axis_name="sp", causal=False,
                   scale=None, window=0):
    """Sequence-parallel attention: (B, H, T, D) inputs with T sharded
    over ``mesh`` axis ``axis_name``; output sharded the same way.

    window > 0 (causal only) runs the BANDED ring: each device visits
    only the predecessor blocks its window reaches, so both compute
    and ring communication scale with the window, not the context."""
    if scale is None:
        scale = q.shape[-1] ** -0.5
    if window and not causal:
        raise ValueError("window attention requires causal=True")
    spec = P(None, None, axis_name, None)
    if window:
        body = functools.partial(
            _ring_local_windowed, axis_name=axis_name,
            scale=float(scale), window=int(window),
            n=int(mesh.shape[axis_name]))
    else:
        body = functools.partial(_ring_local, axis_name=axis_name,
                                 causal=causal, scale=float(scale))
    fn = jax.shard_map(body, mesh=mesh, in_specs=(spec, spec, spec),
                    out_specs=spec)
    return fn(q, k, v)
