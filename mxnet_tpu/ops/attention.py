"""Fused scaled-dot-product attention — Pallas flash kernel.

New TPU-native capability (the 2017 reference predates attention; this
is the hot op the framework's long-context story is built on — see
mxnet_tpu/parallel/ring.py for the sequence-parallel ring variant).

Design: classic flash attention. Grid (batch*heads, q_blocks, k_blocks)
with the k axis innermost ("arbitrary" semantics); online-softmax
running max/denominator/accumulator live in VMEM scratch; each
(block_q, d) @ (d, block_k) product lands on the MXU with float32
accumulation. O(T) memory instead of the naive (T, T) score matrix.

Backward is the FlashAttention-2 split: the forward additionally emits
the per-row logsumexp; the backward runs two Pallas kernels — a dq pass
(grid over q blocks, k innermost) and a dk/dv pass (grid over k blocks,
q innermost) — plus a cheap jnp delta = rowsum(do * o) precompute.
Nothing ever materializes a (T, T) score tensor, so the backward stays
HBM-light at long context (the dense-recompute alternative cost ~60 ms
/step on the v5e transformer bench from (BH, T, T) f32 traffic alone).

On the CPU (the tests) the same kernel executes in interpreter mode, so
numerics are identical everywhere; ops/_pallas.py makes that choice.
"""
from __future__ import annotations

import contextlib
import functools
import math
import threading

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import _pallas
from .registry import register

_NEG_INF = -1e30


def _flash_fwd_kernel(q_ref, k_ref, v_ref, o_ref, *rest,
                      scale, causal, block_q, block_k, num_kb, seq_k,
                      want_lse, window=0, band_offset=0):
    # the lse output only exists under differentiation (want_lse);
    # forward-only calls skip its ~BH*T*128 f32 HBM writes entirely
    if want_lse:
        lse_ref, m_ref, l_ref, acc_ref = rest
    else:
        m_ref, l_ref, acc_ref = rest
    kb = pl.program_id(2)
    qb = pl.program_id(1)

    @pl.when(kb == 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    def _block():
        q = q_ref[0]                          # (bq, d)
        k = k_ref[0]                          # (bk, d)
        # precision is pinned to DEFAULT: native-dtype MXU passes with f32
        # accumulation (preferred_element_type) — the flash numerics
        # contract. Inheriting the ambient jax_default_matmul_precision
        # (MXNET_MATMUL_PRECISION=highest sets float32 globally) would ask
        # Mosaic for an fp32-contract bf16 matmul, which it rejects
        # ("Bad lhs type") — the global knob is an XLA-lowering policy for
        # f32 arrays, not a Pallas one.
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            precision=jax.lax.Precision.DEFAULT,
            preferred_element_type=jnp.float32) * scale   # (bq, bk)
        cols = kb * block_k + jax.lax.broadcasted_iota(
            jnp.int32, s.shape, 1)
        valid = cols < seq_k        # ragged tail: padded keys masked out
        if causal:
            rows = qb * block_q + jax.lax.broadcasted_iota(
                jnp.int32, s.shape, 0)
            valid = _band_valid(valid, rows, cols, window, band_offset)
        s = jnp.where(valid, s, _NEG_INF)
        m_prev = m_ref[:]
        m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        p = jnp.exp(s - m_new)                # (bq, bk)
        alpha = jnp.exp(m_prev - m_new)       # (bq, 1)
        l_ref[:] = l_ref[:] * alpha + p.sum(axis=1, keepdims=True)
        # padded tail rows of V must be zeroed, not just down-weighted:
        # 0 * garbage (NaN-filled pad in interpret mode) would poison acc
        v_blk = _masked_block(v_ref, kb * block_k, seq_k, block_k)
        acc_ref[:] = acc_ref[:] * alpha + jax.lax.dot_general(
            p.astype(v_blk.dtype), v_blk, (((1,), (0,)), ((), ())),
            precision=jax.lax.Precision.DEFAULT,
            preferred_element_type=jnp.float32)
        m_ref[:] = m_new

    if causal:
        # whole block outside the band: skip (half the FLOPs for plain
        # causal; O(T*window) total with a window)
        pl.when(_band_run(qb, kb, block_q, block_k, window,
                          band_offset))(_block)
    else:
        _block()

    @pl.when(kb == num_kb - 1)
    def _finish():
        denom = jnp.maximum(l_ref[:], 1e-30)
        o_ref[0] = (acc_ref[:] / denom).astype(o_ref.dtype)
        # logsumexp per row — the backward's softmax recompute key,
        # replicated across the 128-lane minor dim (Mosaic's block rules
        # want the last dim %128; a (BH, T) layout would put a size-1
        # sublane dim in the block — same trick as jax's own TPU flash
        # kernel's l/m residuals). Fully-masked (padded) rows have
        # l == 0; the max() keeps their lse finite so the backward's
        # exp() stays NaN-free (their contributions are masked there).
        if want_lse:
            lse_ref[0] = jnp.broadcast_to(m_ref[:] + jnp.log(denom),
                                          lse_ref.shape[1:])




def _band_valid(valid, rows, cols, window, offset=0):
    """Causal + optional sliding-window mask shared by all kernels.

    offset: static amount by which q GLOBAL positions lead the k
    positions (rows + offset is the true position of row `rows`) — the
    windowed-ring case, where the visiting k block sits `offset`
    positions earlier in the sequence than the local q block. offset=0
    is the ordinary same-block band."""
    valid = valid & (rows + offset >= cols)
    if window:
        valid = valid & (rows + offset - cols < window)
    return valid


def _band_run(qb, kb, block_q, block_k, window, offset=0):
    """Block participates iff the (q-block x k-block) rectangle meets
    the causal band: below-or-on diagonal, and (with a window) not
    entirely below it. Shared by the fwd/dq/dkv kernels."""
    run = qb * block_q + block_q - 1 + offset >= kb * block_k
    if window:
        run = run & (kb * block_k + block_k - 1
                     > qb * block_q + offset - window)
    return run


_LANES = 128   # minor-dim replication for per-row stats


def _snap_blocks(T, Tk, block_q, block_k, interpret):
    """Clamp blocks to the sequence and, on the compiled TPU path, snap
    them to Mosaic's sublane rule (second-to-last block dim divisible
    by 8, or equal to the array dim). Interpret mode keeps arbitrary
    requests, giving tests coverage of odd blockings."""
    block_q = min(block_q, T)
    block_k = min(block_k, Tk)
    if not interpret:
        if block_q < T and block_q % 8:
            block_q = min(T, max(8, (block_q // 8) * 8))
        if block_k < Tk and block_k % 8:
            block_k = min(Tk, max(8, (block_k // 8) * 8))
    return block_q, block_k


def _flash_forward(q, k, v, scale, causal, block_q, block_k, want_lse,
                   window=0, band_offset=0):
    interpret = _pallas.interpret()
    q, k, v = _uniform_vma(q, k, v)
    BH, T, D = q.shape
    Tk = k.shape[1]
    block_q, block_k = _snap_blocks(T, Tk, block_q, block_k, interpret)
    nq = -(-T // block_q)
    nk = -(-Tk // block_k)

    kernel = functools.partial(
        _flash_fwd_kernel, scale=scale, causal=causal, block_q=block_q,
        block_k=block_k, num_kb=nk, seq_k=Tk, want_lse=want_lse,
        window=window, band_offset=band_offset)
    shapes = [jax.ShapeDtypeStruct(q.shape, q.dtype)]              # o
    out_specs = [pl.BlockSpec((1, block_q, D),
                              lambda b, i, j: (b, i, 0))]
    if want_lse:
        shapes.append(
            jax.ShapeDtypeStruct((BH, T, _LANES), jnp.float32))
        out_specs.append(pl.BlockSpec((1, block_q, _LANES),
                                      lambda b, i, j: (b, i, 0)))
    outs = pl.pallas_call(
        kernel,
        grid=(BH, nq, nk),
        in_specs=[
            pl.BlockSpec((1, block_q, D), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, D), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, block_k, D), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=out_specs,
        out_shape=_with_vma(shapes, (q, k, v)),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, D), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v)
    return outs if want_lse else (outs[0], None)


def _with_vma(shapes, operands):
    """Attach varying-over-mesh-axes info to output avals.

    Under a vma-checking shard_map (e.g. a pipeline stage) the output
    aval must declare how it varies over mesh axes — the union of the
    inputs' variance (q may be replicated while k/v rotate, or vice
    versa). An empty union is attached too: under a vma-checking
    shard_map with fully-replicated operands the out aval must SAY
    replicated."""
    vma = frozenset().union(*(jax.typeof(x).vma for x in operands))
    return [jax.ShapeDtypeStruct(s.shape, s.dtype, vma=vma)
            for s in shapes]


def _uniform_vma(*operands):
    """Broadcast every operand to the union of their mesh variances.

    A pallas_call cannot mix replicated and axis-varying inputs (its
    internal loads trip shard_map's vma check); pvary-ing the
    replicated ones up to the union is a free device-local broadcast,
    and _narrow_vma psums the corresponding cotangents back down."""
    vmas = [jax.typeof(x).vma for x in operands]
    union = frozenset().union(*vmas)
    if not union:
        return operands
    return tuple(
        jax.lax.pcast(x, tuple(sorted(union - v)), to="varying")
        if union - v else x
        for x, v in zip(operands, vmas))


def _dense_with_lse(q, k, v, scale, causal, window=0, band_offset=0):
    """Dense (o, lse) oracle — the single implementation behind
    _attn_reference and the interpret-mode fallbacks."""
    s = jnp.einsum("bqd,bkd->bqk", q, k,
                   preferred_element_type=jnp.float32) * scale
    if causal:
        T, Tk = s.shape[-2], s.shape[-1]
        rows = jnp.arange(T)[:, None] + band_offset
        cols = jnp.arange(Tk)[None, :]
        mask = rows >= cols
        if window:
            mask = mask & (rows - cols < window)
        s = jnp.where(mask, s, _NEG_INF)
    lse = jax.scipy.special.logsumexp(s, axis=-1)
    p = jnp.exp(s - lse[..., None])
    o = jnp.einsum("bqk,bkd->bqd", p.astype(v.dtype), v)
    return o.astype(q.dtype), lse


def _attn_reference(q, k, v, scale, causal):
    """Plain jnp attention (oracle + backward building block)."""
    return _dense_with_lse(q, k, v, scale, causal)[0]


def _masked_block(ref, rows_base, limit, block_rows):
    """Load a (1, block, D) ref, zeroing rows past ``limit`` (the padded
    ragged tail is garbage in interpret mode; 0 * NaN would poison the
    MXU accumulators)."""
    rows = rows_base + jax.lax.broadcasted_iota(
        jnp.int32, (block_rows, 1), 0)
    return jnp.where(rows < limit, ref[0], 0)


def _flash_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                     dq_ref, dq_acc, *, scale, causal, block_q, block_k,
                     num_kb, seq_q, seq_k, window=0, band_offset=0):
    qb, kb = pl.program_id(1), pl.program_id(2)

    @pl.when(kb == 0)
    def _init():
        dq_acc[:] = jnp.zeros_like(dq_acc)

    def _block():
        q = q_ref[0]
        k = _masked_block(k_ref, kb * block_k, seq_k, block_k)
        v = _masked_block(v_ref, kb * block_k, seq_k, block_k)
        do = do_ref[0]
        lse = lse_ref[0][:, :1]              # (bq, 1)
        delta = delta_ref[0][:, :1]          # (bq, 1)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            precision=jax.lax.Precision.DEFAULT,
            preferred_element_type=jnp.float32) * scale
        cols = kb * block_k + jax.lax.broadcasted_iota(
            jnp.int32, s.shape, 1)
        valid = cols < seq_k
        if causal:
            rows = qb * block_q + jax.lax.broadcasted_iota(
                jnp.int32, s.shape, 0)
            valid = _band_valid(valid, rows, cols, window, band_offset)
        p = jnp.where(valid, jnp.exp(s - lse), 0)       # (bq, bk)
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            precision=jax.lax.Precision.DEFAULT,
            preferred_element_type=jnp.float32)         # (bq, bk)
        # the where() must wrap the whole product: p is already 0 at
        # masked slots, but 0 * (dp - NaN-padded delta) would be NaN
        ds = jnp.where(valid, p * (dp - delta) * scale,
                       0).astype(k.dtype)
        dq_acc[:] += jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())),
            precision=jax.lax.Precision.DEFAULT,
            preferred_element_type=jnp.float32)

    if causal:
        pl.when(_band_run(qb, kb, block_q, block_k, window,
                          band_offset))(_block)
    else:
        _block()

    @pl.when(kb == num_kb - 1)
    def _finish():
        dq_ref[0] = dq_acc[:].astype(dq_ref.dtype)


def _flash_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                      dk_ref, dv_ref, dk_acc, dv_acc,
                      *, scale, causal, block_q, block_k, num_qb,
                      seq_q, seq_k, window=0, band_offset=0):
    kb, qb = pl.program_id(1), pl.program_id(2)

    @pl.when(qb == 0)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    def _block():
        q = _masked_block(q_ref, qb * block_q, seq_q, block_q)
        do = _masked_block(do_ref, qb * block_q, seq_q, block_q)
        k = k_ref[0]
        v = v_ref[0]
        lse = lse_ref[0][:, :1]
        delta = delta_ref[0][:, :1]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            precision=jax.lax.Precision.DEFAULT,
            preferred_element_type=jnp.float32) * scale  # (bq, bk)
        rows = qb * block_q + jax.lax.broadcasted_iota(
            jnp.int32, s.shape, 0)
        valid = rows < seq_q
        if causal:
            cols = kb * block_k + jax.lax.broadcasted_iota(
                jnp.int32, s.shape, 1)
            valid = _band_valid(valid, rows, cols, window, band_offset)
        p = jnp.where(valid, jnp.exp(s - lse), 0)
        dv_acc[:] += jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            precision=jax.lax.Precision.DEFAULT,
            preferred_element_type=jnp.float32)          # (bk, d)
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            precision=jax.lax.Precision.DEFAULT,
            preferred_element_type=jnp.float32)          # (bq, bk)
        # see dq kernel: NaN-padded delta rows must not reach the MXU
        ds = jnp.where(valid, p * (dp - delta) * scale,
                       0).astype(q.dtype)
        dk_acc[:] += jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            precision=jax.lax.Precision.DEFAULT,
            preferred_element_type=jnp.float32)          # (bk, d)

    if causal:
        # k block outside the band contributes 0
        pl.when(_band_run(qb, kb, block_q, block_k, window,
                          band_offset))(_block)
    else:
        _block()

    @pl.when(qb == num_qb - 1)
    def _finish():
        dk_ref[0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)


def _flash_backward(q, k, v, o, lse, do, scale, causal, block_q,
                    block_k, dlse=None, window=0, band_offset=0):
    interpret = _pallas.interpret()
    if dlse is None:
        q, k, v, o, lse, do = _uniform_vma(q, k, v, o, lse, do)
    else:
        q, k, v, o, lse, do, dlse = _uniform_vma(q, k, v, o, lse, do,
                                                 dlse)
    BH, T, D = q.shape
    Tk = k.shape[1]
    block_q, block_k = _snap_blocks(T, Tk, block_q, block_k, interpret)
    nq = -(-T // block_q)
    nk = -(-Tk // block_k)

    # delta_i = rowsum(do_i * o_i): one cheap fused elementwise+reduce,
    # lane-replicated like lse (see _flash_fwd_kernel). When the lse
    # output itself carries a cotangent (the ring-merge path), its
    # whole contribution folds into this term: ds_ij = p_ij * (dp_ij -
    # delta_i + dlse_i), since d lse_i / d s_ij = p_ij — so the kernels
    # run unchanged on delta' = delta - dlse.
    delta2 = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32),
                     axis=-1, keepdims=True)
    if dlse is not None:
        delta2 = delta2 - dlse.astype(jnp.float32)[..., None]
    delta = jnp.broadcast_to(delta2, (BH, T, _LANES))
    # the residual stores one lane; re-broadcast transiently for the
    # kernels' (1, block_q, _LANES) stat blocks
    lse = jnp.broadcast_to(lse[..., None], (BH, T, _LANES))

    q_spec = pl.BlockSpec((1, block_q, D), lambda b, i, j: (b, i, 0))
    k_spec = pl.BlockSpec((1, block_k, D), lambda b, i, j: (b, j, 0))
    r_spec = pl.BlockSpec((1, block_q, _LANES),
                          lambda b, i, j: (b, i, 0))
    dq = pl.pallas_call(
        functools.partial(
            _flash_dq_kernel, scale=scale, causal=causal,
            block_q=block_q, block_k=block_k, num_kb=nk,
            seq_q=T, seq_k=Tk, window=window,
            band_offset=band_offset),
        grid=(BH, nq, nk),
        in_specs=[q_spec, k_spec, k_spec, q_spec, r_spec, r_spec],
        out_specs=q_spec,
        out_shape=_with_vma(
            [jax.ShapeDtypeStruct(q.shape, q.dtype)], (q, k, v, do))[0],
        scratch_shapes=[pltpu.VMEM((block_q, D), jnp.float32)],
        interpret=interpret,
    )(q, k, v, do, lse, delta)

    # dk/dv: grid's middle axis walks k blocks, inner axis q blocks
    q_spec2 = pl.BlockSpec((1, block_q, D), lambda b, i, j: (b, j, 0))
    k_spec2 = pl.BlockSpec((1, block_k, D), lambda b, i, j: (b, i, 0))
    r_spec2 = pl.BlockSpec((1, block_q, _LANES),
                           lambda b, i, j: (b, j, 0))
    kv_shapes = [jax.ShapeDtypeStruct(k.shape, k.dtype),
                 jax.ShapeDtypeStruct(v.shape, v.dtype)]
    dk, dv = pl.pallas_call(
        functools.partial(
            _flash_dkv_kernel, scale=scale, causal=causal,
            block_q=block_q, block_k=block_k, num_qb=nq,
            seq_q=T, seq_k=Tk, window=window,
            band_offset=band_offset),
        grid=(BH, nk, nq),
        in_specs=[q_spec2, k_spec2, k_spec2, q_spec2, r_spec2, r_spec2],
        out_specs=[k_spec2, k_spec2],
        out_shape=_with_vma(kv_shapes, (q, k, v, do)),
        scratch_shapes=[pltpu.VMEM((block_k, D), jnp.float32),
                        pltpu.VMEM((block_k, D), jnp.float32)],
        interpret=interpret,
    )(q, k, v, do, lse, delta)
    return dq, dk, dv


def _dense_fallback(q, k, v, scale, causal, window=0):
    """Pallas's interpret mode cannot execute with mesh-varying
    operands (its internal block loads mix varying data with replicated
    grid indices, tripping shard_map's vma check). Compiled TPU
    execution is an opaque custom call and unaffected — so only the
    CPU-mesh test path takes this dense recompute, wrapped in
    checkpoint so strips rematerialize instead of caching (T, T)."""
    return jax.checkpoint(
        lambda a, b, c: _dense_with_lse(a, b, c, scale, causal,
                                        window)[0]
    )(q, k, v)


def _interpret_needs_fallback(*xs):
    return _pallas.interpret() and any(jax.typeof(x).vma for x in xs)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash(q, k, v, scale, causal, block_q, block_k, window=0):
    if _interpret_needs_fallback(q, k, v):
        return _dense_fallback(q, k, v, scale, causal,
                               window).astype(q.dtype)
    o, _ = _flash_forward(q, k, v, scale, causal, block_q, block_k,
                          want_lse=False, window=window)
    return o


def _flash_fwd_rule(q, k, v, scale, causal, block_q, block_k,
                    window=0):
    if _interpret_needs_fallback(q, k, v):
        o = _dense_fallback(q, k, v, scale, causal,
                            window).astype(q.dtype)
        return o, (q, k, v, None, None)
    o, lse = _flash_forward(q, k, v, scale, causal, block_q, block_k,
                            want_lse=True, window=window)
    # residual keeps ONE lane — the 128-lane replication is a Mosaic
    # block-layout need of the backward kernels' INPUT, re-broadcast
    # transiently there, not worth holding across the whole forward
    return o, (q, k, v, o, lse[..., 0])


def _narrow_vma(ct, primal):
    """Reduce a cotangent to its primal's mesh variance.

    The backward kernels stamp every output with the union of the
    inputs' vma (_with_vma). Under a vma-checking shard_map with mixed
    variance (e.g. q replicated while k/v rotate) the correct adjoint
    of the implicit broadcast is a psum over the extra axes."""
    extra = tuple(sorted(jax.typeof(ct).vma - jax.typeof(primal).vma))
    return jax.lax.psum(ct, extra) if extra else ct


def _flash_bwd_rule(scale, causal, block_q, block_k, window, res, do):
    q, k, v, o, lse = res
    if lse is None:          # dense interpret-mode fallback (see above)
        _, vjp = jax.vjp(
            lambda a, b, c: _dense_fallback(
                a, b, c, scale, causal, window).astype(q.dtype),
            q, k, v)
        return vjp(do)
    dq, dk, dv = _flash_backward(q, k, v, o, lse, do, scale, causal,
                                 block_q, block_k, window=window)
    return _narrow_vma(dq, q), _narrow_vma(dk, k), _narrow_vma(dv, v)


_flash.defvjp(_flash_fwd_rule, _flash_bwd_rule)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def _flash_lse(q, k, v, scale, causal, block_q, block_k, window=0,
               band_offset=0):
    """Flash attention that also returns the per-row logsumexp, with
    real gradient flow through BOTH outputs. The ring-attention merge
    consumes (o, lse) pairs per visiting KV block.

    window/band_offset: static banded mask over GLOBAL positions
    (q row r sits at r + band_offset) — the windowed-ring case, where
    the visiting k block is band_offset positions earlier than the
    local q block. Defaults preserve the classic behavior exactly."""
    if _interpret_needs_fallback(q, k, v):
        return _dense_with_lse(q, k, v, scale, causal, window,
                               band_offset)
    o, lse3 = _flash_forward(q, k, v, scale, causal, block_q, block_k,
                             want_lse=True, window=window,
                             band_offset=band_offset)
    return o, lse3[..., 0]


def _flash_lse_fwd_rule(q, k, v, scale, causal, block_q, block_k,
                        window=0, band_offset=0):
    if _interpret_needs_fallback(q, k, v):
        o, lse = _dense_with_lse(q, k, v, scale, causal, window,
                                 band_offset)
        return (o, lse), (q, k, v, None, None)
    o, lse3 = _flash_forward(q, k, v, scale, causal, block_q, block_k,
                             want_lse=True, window=window,
                             band_offset=band_offset)
    lse = lse3[..., 0]
    return (o, lse), (q, k, v, o, lse)   # single-lane residual


def _flash_lse_bwd_rule(scale, causal, block_q, block_k, window,
                        band_offset, res, cts):
    q, k, v, o, lse = res
    do, dlse = cts
    if lse is None:          # dense interpret-mode fallback (see above)
        _, vjp = jax.vjp(
            lambda a, b, c: _dense_with_lse(a, b, c, scale, causal,
                                            window, band_offset),
            q, k, v)
        return vjp((do, dlse))
    dq, dk, dv = _flash_backward(q, k, v, o, lse, do, scale, causal,
                                 block_q, block_k, dlse=dlse,
                                 window=window, band_offset=band_offset)
    return _narrow_vma(dq, q), _narrow_vma(dk, k), _narrow_vma(dv, v)


_flash_lse.defvjp(_flash_lse_fwd_rule, _flash_lse_bwd_rule)


def flash_attention_with_lse(query, key, value, scale=None,
                             causal=False, block_q=512, block_k=512,
                             window=0, band_offset=0):
    """(o, lse) over (BH, T, D) inputs — both differentiable; the
    building block for ring attention's block merge. window/band_offset
    select a banded mask over global positions (see _flash_lse)."""
    if scale is None:
        scale = query.shape[-1] ** -0.5
    return _flash_lse(query, key, value, float(scale), bool(causal),
                      int(block_q), int(block_k), int(window or 0),
                      int(band_offset or 0))


def flash_attention(query, key, value, scale=None, causal=False,
                    block_q=512, block_k=512, window=None):
    """Fused attention over (B, H, T, D) or (BH, T, D) inputs.

    window: sliding-window width W (causal only): row t attends
    [t-W+1, t]. Compute AND memory become O(T*W); blocks fully outside
    the band are skipped on the grid."""
    if window and not causal:
        raise ValueError("window attention requires causal=True")
    q4 = query.ndim == 4
    if q4:
        B, H, T, D = query.shape
        query = query.reshape(B * H, T, D)
        key = key.reshape(B * H, key.shape[2], D)
        value = value.reshape(B * H, value.shape[2], D)
    if scale is None:
        scale = query.shape[-1] ** -0.5
    out = _flash(query, key, value, float(scale), bool(causal),
                 int(block_q), int(block_k), int(window or 0))
    if q4:
        out = out.reshape(B, H, T, D)
    return out


def _token_rows(x):
    """(B, Hkv, Tn, ...) projections -> (B, Tn, Hkv * ...) cache rows:
    one token's heads side by side, as the caches hold them."""
    B, Hkv, Tn = x.shape[:3]
    return jnp.moveaxis(x, 1, 2).reshape(B, Tn, -1)


def _check_heads(query, k_cache):
    """(Hkv, G): the kv heads a (B, C, Hkv*D) cache holds for this
    query's head size, and the query heads that share each."""
    H, D = query.shape[1], query.shape[3]
    Hkv, rest = divmod(k_cache.shape[2], D)
    if rest or not Hkv or H % Hkv:
        raise ValueError(
            "query heads (%d x %d) must be a multiple of the cache's "
            "kv heads (row width %d / head size) — grouped-query "
            "attention groups q heads over kv heads"
            % (H, D, k_cache.shape[2]))
    return Hkv, H // Hkv


# rows of one product above which _attend takes a kv head at a time:
# the MXU's pass is 128 rows deep
_MXU_ROWS = 128

# float32 scores, bytes, that one step of _attend's map may hold. A
# v5e core has 128 MiB of fast memory, and beside the scores the step
# keeps their bfloat16 cast (half as many bytes) and the mask broadcast
# over the group (a quarter) there: 1.75 x 72 MiB = 126 MiB. The
# command-a cell's sliding layers (68.0 MiB a kv head) are within it
# and stay whole; its full layer (132.0 MiB) is cut in two. Settled on
# the chip (my chip runs, PR 44; one chunk forward of that cell, ms on
# the device): no budget 22.04, 72 MiB 18.64, 36 MiB 18.62 (the
# sliding layers cut in two as well: 2.91 against 2.97 ms for three,
# within what two runs differ by), 18 MiB 18.95
_SCORE_BYTES = 72 << 20

_split = threading.local()


def split_traces():
    """How many times, on this thread, a trace of :func:`_attend` took
    blocks of rows inside a kv head. A caller that traces a program
    reads it before and after to learn whether the program splits
    (``Generator.attend_split_programs``)."""
    return getattr(_split, "traces", 0)


def _score_blocks(B, G, Tn, C):
    """(Bb, Gb, Tb): the batch rows, the query heads of a group and
    the rows of a head whose float32 scores, ``Bb * Gb * Tb * C * 4``
    bytes, one step of :func:`_attend`'s map may hold. (B, G, Tn), a
    whole kv head, where that is within ``_SCORE_BYTES``. Else batch
    rows go first (the largest divisor of B that fits), then, at one
    batch row, the ``G * Tn`` query rows: whole query heads, then rows
    inside one, the largest block that fits and divides, never under
    ``_MXU_ROWS`` rows (the smallest such block where none fits)."""
    def fits(rows):
        return rows * C * 4 <= _SCORE_BYTES

    for Bb in range(B, 0, -1):
        if B % Bb == 0 and fits(Bb * G * Tn):
            return Bb, G, Tn
    blocks = sorted({h * Tn for h in range(1, G + 1) if G % h == 0}
                    | {t for t in range(1, Tn + 1) if Tn % t == 0})
    blocks = [r for r in blocks if r >= _MXU_ROWS] or [G * Tn]
    rows = max([r for r in blocks if fits(r)] or blocks[:1])
    return 1, max(1, rows // Tn), min(rows, Tn)


def _attend(query, k_cache, v_cache, valid, scale, k_scale=None,
            v_scale=None):
    """softmax(q k^T * scale, over the ``valid`` columns) v, read from
    caches that lie token-contiguous: (B, C, Hkv*D), one token's heads
    side by side. query: (B, H, Tn, D); valid: (1 or B, Tn, C) bool;
    k_scale/v_scale: (B, C, Hkv) f32 where the caches are int8 rows
    (the scales multiply scores and probabilities, so the int8 bytes
    are what HBM moves). Returns (B, H, Tn, D).

    THE one reader every cached-attention variant goes through. Both
    products are matrix products that contract the cache's own axes,
    over the lanes of ``g`` kv heads at a time: scores against a query
    that is block-diagonal inside its group (head h's D values in its
    own kv head's lanes, zeros in the others'), values as
    (g*G*Tn, C) @ (C, g*D) of which each head keeps its own block.
    GQA: a kv head's G query heads sit in its group, so each cache
    head is still read once for all of them.

    How many heads make a group is read from the shapes. A decode step
    (few rows: g*G*Tn within one 128-row pass of the MXU) takes the
    heads of one 128-lane tile together (g = 2 at D = 64, 1 at D =
    128; all of them where a row is narrower than a tile), views the
    cache as (B, C, J, g*D) and batches both products over (B, J): the
    zeros ride in a pass the MXU makes anyway, XLA reads the cache
    where it lies, and the compiled step holds no loop. A prefill
    chunk (more rows) takes one kv head at a time, in a ``lax.map``
    over the heads that slices that head's lanes out of the cache
    where it lies: the zeros would double its arithmetic, and the body
    compiles once (unrolled over 32 heads x 24 layers the prefill
    program compiled for 58 s a prompt length instead of 11). Measured
    on a v5e (my chip runs, PR 30; us a layer, write + attend, (8,
    1536, 32 x 64) bf16): one token 158 batched against 401 a head at
    a time (228 before, head-major); 1 024 tokens 2 877 mapped and
    3 706 unrolled a head at a time against 14 420 batched (6 116
    before). An einsum over a (B, C, Hkv, D) view instead makes the
    TPU compiler copy each whole cache into a head-major layout and
    back, every call (662 and 6 602).

    A step of that map must keep its float32 scores in the chip's fast
    memory between the two products, so it holds no more of them than
    ``_SCORE_BYTES``: where a head's (B, G*Tn, C) scores are over it
    the map runs over (kv head, block of rows) instead
    (:func:`_score_blocks`: batch rows first, then query heads of a
    group, then rows of a head), every block against all C columns,
    so a row's softmax is the one it had and blocking changes no
    reduction. A shape within the budget lowers as it always did.
    Compiled for a v5e (PR 44; a chunk of 256 tokens, 16 query heads a
    kv head of 128, bf16): over 4 352 columns a head's scores are 71.3
    MB and the program keeps them, their bfloat16 cast (half) and the
    mask broadcast over the group (a quarter) in memory space S(1),
    with 0.1 MB of temporaries in HBM; over 8 448 columns they are
    138.4 MB, the product's fusion writes them to HBM and the sum's
    and the cast's fusions read them back (415 MB a head, 138.5 MB of
    temporaries), and only the cast stays in S(1); as two blocks of
    2 048 rows, 69.2 MB, they are in S(1) again. On the chip (my chip
    runs, PR 44; ms a layer inside one chunk forward): the sliding
    layer 0.99; the full layer whole 5.19, of which the product that
    writes the scores 1.56 and the two fusions that read them back
    1.47 each (8 x 138.4 MB at 751 GB/s: HBM's pace); in two blocks
    1.77 (0.44 + 0.24 + 0.36, the values' product 0.45, the mask's
    broadcast 0.22), in four 1.86, in eight 1.80 with the sliding
    layers, then cut too, at 1.08. The kernel alone, the full layer's
    shape (wall ms): whole 5.98, two blocks 2.42, four 2.56, eight
    2.80, sixteen 3.24, blocks of 128 rows 6.38; the sliding layer's
    whole 1.61, in two 1.64, in four 1.71: nothing gains from blocks
    smaller than fast memory asks for."""
    B, H, Tn, D = query.shape
    C, F = k_cache.shape[1:]
    Hkv, G = _check_heads(query, k_cache)
    W = D * 128 // math.gcd(D, 128)          # lcm: whole lane tiles
    if F % W:
        W = F
    batched = W // D * G * Tn <= _MXU_ROWS
    if not batched:
        W = D
    g, J = W // D, F // W
    # own[m, j]: query head m of a group reads the group's kv head j
    own = jnp.repeat(jnp.eye(g, dtype=bool), G, axis=0)[:, None, :, None]

    def products(q, k, v, ks, vs, valid=valid, own=own):
        """q (B, j, g*G, Tn, D) over k, v (B, C, j, W) [and their
        scales (B, C, j, g)]: (B, j, g*G, Tn, D) for the j groups
        given. Or a block of it: fewer batch rows, query heads of a
        group or rows of a head, with ``valid`` and ``own`` cut to
        match."""
        B, j, _, Tn = q.shape[:4]
        G = q.shape[2] // g
        if ks is not None:
            # a row of the block-diagonal query meets one kv head only,
            # so that head's scales multiply the small scores and
            # probabilities, never a dequantized copy of the cache
            def of_rows(sc):        # (B, C, j, g) -> (B, j, g*G, 1, C)
                return jnp.repeat(jnp.moveaxis(sc, 1, 3), G,
                                  axis=2)[:, :, :, None]
            k, v = k.astype(jnp.float32), v.astype(jnp.float32)
        qbd = jnp.where(own, q[..., None, :], 0).reshape(
            B, j, g * G * Tn, W)
        s = jnp.einsum("bjmw,bcjw->bjmc", qbd, k,
                       precision=jax.lax.Precision.DEFAULT,
                       preferred_element_type=jnp.float32) * scale
        s = s.reshape(B, j, g * G, Tn, C)
        if ks is not None:
            s = s * of_rows(ks)
        p = jax.nn.softmax(jnp.where(valid[:, None, None], s, _NEG_INF),
                           axis=-1).astype(v.dtype)
        if ks is not None:
            p = p * of_rows(vs)
        o = jnp.einsum("bjmc,bcjw->bjmw",
                       p.reshape(B, j, g * G * Tn, C), v,
                       precision=jax.lax.Precision.DEFAULT)
        return jnp.where(own, o.reshape(B, j, g * G, Tn, g, D),
                         0).sum(axis=4)

    q = query.reshape(B, J, g * G, Tn, D)

    def lanes(take):
        # each cache (and the int8 caches' scales) through ``take``
        return [None if c is None else take(c, w)
                for c, w in ((k_cache, W), (v_cache, W),
                             (k_scale, g), (v_scale, g))]

    if batched:
        o = products(q, *lanes(lambda c, w: c.reshape(B, C, J, w)))
        return o.reshape(B, H, Tn, D)
    Bb, Gb, Tb = _score_blocks(B, G, Tn, C)
    if (Bb, Gb, Tb) == (B, G, Tn):
        def group(x):
            j, qj = x
            return products(qj[:, None], *lanes(
                lambda c, w: jax.lax.dynamic_slice_in_dim(
                    c, j * w, w, axis=2)[:, :, None]))[:, 0]

        o = jnp.moveaxis(jax.lax.map(
            group, (jnp.arange(J), jnp.moveaxis(q, 1, 0))), 0, 1)
        return o.reshape(B, H, Tn, D)

    # a kv head's scores are over the budget: the map takes (kv head,
    # block of rows), the kv head outermost; every row still meets all
    # C columns, so each row's softmax is the one it had
    _split.traces = split_traces() + 1
    steps = (J, B // Bb, G // Gb, Tn // Tb)

    def block(x):
        j, b, t, qb = x                               # qb (Bb, Gb, Tb, D)
        ok = jax.lax.dynamic_slice(
            valid, (b * Bb if valid.shape[0] == B else 0, t * Tb, 0),
            (min(Bb, valid.shape[0]), Tb, C))
        return products(qb[:, None], *lanes(
            lambda c, w: jax.lax.dynamic_slice(
                c, (b * Bb, 0, j * w), (Bb, C, w))[:, :, None]),
            valid=ok, own=own[:Gb])[:, 0]

    j, b, _, t = (i.reshape(-1) for i in jnp.indices(steps))
    qb = q.reshape(steps[1], Bb, J, steps[2], Gb, steps[3], Tb, D)
    o = jax.lax.map(block, (j, b, t, qb.transpose(
        2, 0, 3, 5, 1, 4, 6, 7).reshape(-1, Bb, Gb, Tb, D)))
    return o.reshape(steps + (Bb, Gb, Tb, D)).transpose(
        1, 4, 0, 2, 5, 3, 6, 7).reshape(B, H, Tn, D)


def _causal(pos, Tn, C, window, block=0):
    """(1 or B, Tn, C) mask: new row r of batch row b, which sits at
    pos[b] + r, attends cache column c iff c <= pos[b] + r and, under
    a window, pos[b] + r - c < window. pos: () or (B,) int.

    block=L (> 0) is the BLOCK mask instead: the row at position i
    attends column j iff floor(j / L) <= floor(i / L) — causal across
    blocks of L positions, both ways inside one, so the new rows of
    one block see each other whatever their order."""
    at = jnp.reshape(pos, (-1, 1, 1)) + jnp.arange(Tn)[None, :, None]
    cols = jnp.arange(C)[None, None, :]
    if block:
        if window:
            raise ValueError("the block mask takes no window")
        with jax.named_scope("attn.block"):
            return cols // block <= at // block
    valid = cols <= at
    if window:
        valid = valid & (at - cols < window)
    return valid


def _check_capacity(op, pos, Tn, C):
    """Raise where ``pos`` is concrete and a row would overrun."""
    if not isinstance(pos, jax.core.Tracer):
        import numpy as _np
        worst = int(_np.asarray(pos).max())
        if worst + Tn > C:
            raise ValueError(
                "%s overrun: pos (%d) + Tnew (%d) exceeds cache "
                "capacity Tmax=%d — dynamic_update_slice would clamp "
                "and silently corrupt the cache" % (op, worst, Tn, C))


def _row_pos(pos, B):
    """pos as int32: () for one shared position, (B,) per row."""
    pos = jnp.asarray(pos)
    if pos.ndim >= 1 and pos.size > 1:
        if pos.size != B:
            raise ValueError(
                "per-row pos must have one entry per batch row: got "
                "%r for batch %d" % (pos.shape, B))
        return jnp.reshape(pos, (B,)).astype(jnp.int32)
    return jnp.reshape(pos, ()).astype(jnp.int32)


def _write_rows(cache, new, pos):
    """``cache[b, pos[b]:pos[b]+Tn] = new[b]`` for a (B, C, ...) cache
    and (B, Tn, ...) rows. A token is one contiguous row of the cache
    (at (8, 1536, 32 x 64) bf16: 4 KB, 16 adjacent lane tiles), so a
    write touches only that row: the 384 writes of an OPT-1.3B decode
    step take 0.39 ms of its 6.95, where a (B, Hkv, C, hd) cache (the
    TPU makes C the lane axis: a token is one lane column in 128
    tiles) paid 2.88 of 10.05 (my chip runs, PR 30).

    pos (): one ``dynamic_update_slice`` for all rows. pos (B,): one a
    row at a STATIC row index, each on the result of the last — not a
    ``vmap`` of one: a batched ``dynamic_update_slice`` is a scatter,
    which the TPU compiler expands into a ``while`` over the rows that
    carries the whole cache array through fast memory and back (3.9 +
    2.7 ms of a 12.4 ms decode step over 48 arrays; my chip run, PR
    28). Either way the donated buffer is updated where it lies, and a
    start past ``C - Tn`` clamps as ``dynamic_update_slice`` does."""
    tail = (0,) * (cache.ndim - 2)
    if pos.ndim == 0:
        return jax.lax.dynamic_update_slice(cache, new, (0, pos) + tail)
    for b in range(cache.shape[0]):
        cache = jax.lax.dynamic_update_slice(
            cache, new[b:b + 1], (b, pos[b]) + tail)
    return cache


def cached_attention(query, key, value, k_cache, v_cache, pos,
                     scale=None, window=0, block=0):
    """Incremental-decode attention over a KV cache.

    query/key/value: (B, H, Tnew, hd) — projections of the Tnew tokens
    being appended (Tnew = prompt length at prefill, 1 per step after;
    key/value carry Hkv <= H heads under grouped-query attention).
    k_cache/v_cache: (B, Tmax, Hkv*hd) — TOKEN-CONTIGUOUS: one token's
    kv heads side by side in one row, which is how the TPU stores it
    too (the row axis is the lane axis), so appending a token writes
    one contiguous row (:func:`_write_rows`) and both products read
    the cache where it lies (:func:`_attend`). pos: (1,) int — number
    of tokens already cached; the new keys land at [pos, pos+Tnew) and
    query row r may attend cache columns <= pos+r.

    CAPACITY CONTRACT: pos + Tnew must be <= Tmax. Past it,
    dynamic_update_slice CLAMPS the start index rather than raising, so
    an overrun silently overwrites the most recent cache rows (and the
    causal mask then attends corrupted history). `Generator` guards
    this on the host; direct users of the op (get_decode_symbol /
    _contrib_CachedAttention) must enforce it themselves. Under
    `jax.disable_jit()` — this framework's NaiveEngine-style debug mode
    — pos is concrete and the op raises on violation.

    PER-ROW POSITIONS (continuous batching): pos may instead be (B,) —
    one cache position per batch row. Each row's new k/v land at its
    own offset and its causal window masks against its own position,
    which is what lets a serving slot pool hold sequences at different
    decode depths in ONE compiled step (mxnet_tpu/serve/decode.py).

    Decode is bandwidth-bound (one (Tnew, Tmax) strip per head), so
    this is a plain jnp composition — XLA fuses the mask+softmax; the
    MXU-dense training path stays with the Pallas flash kernel.

    BLOCK MASK (block=L > 0; generation by diffusion over blocks):
    row i attends column j iff floor(j / L) <= floor(i / L)
    (:func:`_causal`). The Tnew new rows are written first, as ever,
    so the rows of one block see each other both ways. A forward that
    must leave the cache as it was (a denoising pass over a block that
    is not final) is this same call with the caller keeping ``pos``
    where it was: its rows land past the cached prefix, where the next
    forward at the same ``pos`` overwrites them before any row can
    attend them — the rule that makes a rejected speculative entry
    harmless. The forward that keeps its rows is the one after which
    the caller advances ``pos``.
    Returns (out, new_k_cache, new_v_cache)."""
    B, H, Tn, D = query.shape
    _check_heads(query, k_cache)
    C = k_cache.shape[1]
    if scale is None:
        scale = D ** -0.5
    pos = _row_pos(pos, B)
    _check_capacity("cached_attention", pos, Tn, C)
    k_cache = _write_rows(k_cache,
                          _token_rows(key).astype(k_cache.dtype), pos)
    v_cache = _write_rows(v_cache,
                          _token_rows(value).astype(v_cache.dtype), pos)
    out = _attend(query, k_cache, v_cache,
                  _causal(pos, Tn, C, int(window or 0), int(block or 0)),
                  float(scale))
    return out.astype(query.dtype), k_cache, v_cache


def rope(x, positions, base=10000.0):
    """Rotary position embedding over (B, H, T, hd).

    positions: (T,) absolute position ids shared across the batch, or
    (B, T) per-row ids (the continuous-batching decode path, where
    each serving slot sits at its own depth). HALF-SPLIT pairing (GPT
    -NeoX convention): (x[i], x[i+hd/2]) rotate together by
    pos * base^(-2i/hd) — NOT the interleaved (x[2i], x[2i+1])
    RoFormer/LLaMA layout; checkpoints crossing implementations must
    repack. Relative-position attention with no learned table and
    graceful length extrapolation (RoFormer, Su et al. 2021). Applied
    to q AND k before attention; cached keys are stored rotated, so
    incremental decode needs only the new tokens' positions."""
    B, H, T, D = x.shape
    half = D // 2
    freqs = jnp.power(
        float(base), -jnp.arange(0, half, dtype=jnp.float32) / half)
    ang = positions.astype(jnp.float32)[..., None] * freqs
    if ang.ndim == 2:                         # shared (T, half)
        cos = jnp.cos(ang)[None, None]        # (1, 1, T, half)
        sin = jnp.sin(ang)[None, None]
    else:                                     # per-row (B, T, half)
        cos = jnp.cos(ang)[:, None]           # (B, 1, T, half)
        sin = jnp.sin(ang)[:, None]
    x1 = x[..., :half].astype(jnp.float32)
    x2 = x[..., half:].astype(jnp.float32)
    out = jnp.concatenate([x1 * cos - x2 * sin,
                           x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


@register("_contrib_RoPE", arg_names=("data", "positions"),
          nondiff_inputs=(1,), defaults={"base": 10000.0})
def _rope_op(data, positions, base=10000.0, **_):
    """(B, H, T, hd) rotary position embedding; positions (T,)."""
    return rope(data, positions, base=float(base))


def _write_ring(cache, new, pos):
    """Rows ``new[b]`` into the circular ``cache[b]`` at slots
    ``(pos[b] + r) % C``: a (B, C, ...) cache, (B, Tn, ...) rows, pos
    () or (B,). One token never wraps, so a decode step is
    :func:`_write_rows` at ``pos % C``. A chunk of Tn > 1 rows may
    wrap once (C >= Tn), and is written as two windows of Tn slots
    each, read, blended and put back where they lie: the one that ends
    at the buffer's end (or starts at the first slot written, where
    nothing wraps) and the buffer's first Tn slots for what wrapped.
    A rotation of the new rows and two ``dynamic_update_slice`` a row:
    no scatter (the TPU runs one as a loop over its updates), and only
    2 Tn rows of the buffer move."""
    C, Tn = cache.shape[1], new.shape[1]
    if Tn == 1:
        return _write_rows(cache, new, pos % C)
    if Tn > C:
        raise ValueError("a circular cache of %d rows cannot take %d "
                         "new rows at once" % (C, Tn))
    at = jnp.arange(Tn).reshape((1, Tn) + (1,) * (cache.ndim - 2))
    tail = (0,) * (cache.ndim - 2)

    def blend(buf, rows, row, start, shift, keep):
        # slot start + i takes rows[(i - shift) % Tn] where keep[i]
        old = jax.lax.dynamic_slice(
            buf, (row, start) + tail, (rows.shape[0], Tn) + buf.shape[2:])
        mix = jnp.where(keep, jnp.roll(rows, shift, axis=1), old)
        return jax.lax.dynamic_update_slice(buf, mix, (row, start) + tail)

    def one(buf, rows, row, p):
        s = p % C
        a = jnp.minimum(s, C - Tn)
        buf = blend(buf, rows, row, a, s - a, at >= s - a)
        return blend(buf, rows, row, 0, s - C, at < Tn - (C - s))

    if pos.ndim == 0:
        return one(cache, new, 0, pos)
    for b in range(cache.shape[0]):
        cache = one(cache, new[b:b + 1], b, pos[b])
    return cache


def rolling_cached_attention(query, key, value, k_cache, v_cache, pos,
                             window, scale=None):
    """Sliding-window decode attention over a CIRCULAR cache.

    Caches are (B, C, Hkv*hd), token-contiguous like
    cached_attention's, with fixed capacity C; position p of row b
    lives in slot p % C OF ITS ROW, so memory stays O(C) however long
    generation runs (pair with RoPE or with no position at all — a
    learned position table would still bound absolute positions).
    pos: (1,) int, one depth for every row (a prefill, or a chunk of
    one at a shared offset), or (B,), one depth a row (the serving slot
    pool's step: a row prefilled by chunks at a shared offset merges
    into the pool and steps on from its own depth, in the same
    buffer). Correctness needs C >= window + Tnew - 1 from the first
    forward that wraps: appending Tnew tokens may overwrite up to
    Tnew-1 older slots, and every new row must still find its full
    window (the Generator sizes the buffer for the chunk it feeds and
    checks a prompt against it).

    Masking derives each slot's ABSOLUTE position in closed form from
    its row's own depth: after appending through pos_end = pos[b] +
    Tnew - 1, slot s holds p_s = pos_end - ((pos_end - s) mod C) — the
    newest position congruent to s. Valid for query row r iff
    0 <= p_s <= pos[b]+r and pos[b]+r - p_s < window. A slot never
    written reads p_s < 0 (pos_end < C), so a fresh or merged row
    needs no clearing."""
    B, H, Tn, D = query.shape
    _check_heads(query, k_cache)
    C = k_cache.shape[1]
    if scale is None:
        scale = D ** -0.5
    pos = _row_pos(pos, B)
    with jax.named_scope("attn.window"):
        k_cache = _write_ring(
            k_cache, _token_rows(key).astype(k_cache.dtype), pos)
        v_cache = _write_ring(
            v_cache, _token_rows(value).astype(v_cache.dtype), pos)
        p0 = jnp.reshape(pos, (-1, 1, 1))                   # (1|B, 1, 1)
        pos_end = p0 + Tn - 1
        slot_ids = jnp.arange(C)[None, None, :]
        p_s = pos_end - ((pos_end - slot_ids) % C)          # (1|B, 1, C)
        rows = p0 + jnp.arange(Tn)[None, :, None]           # (1|B, Tn, 1)
        valid = (p_s >= 0) & (p_s <= rows) & (rows - p_s < window)
        out = _attend(query, k_cache, v_cache, valid, float(scale))
    return out.astype(query.dtype), k_cache, v_cache


@register("_contrib_RollingCachedAttention",
          arg_names=("query", "key", "value", "k_cache", "v_cache",
                     "pos"),
          state_inputs=(3, 4), nondiff_inputs=(5,),
          differentiable=False,
          defaults={"scale": None, "max_len": 0, "window": 0})
def _rolling_cached_attention_op(query, key, value, k_cache, v_cache,
                                 pos, scale=None, window=0, **_):
    """Circular-buffer twin of _contrib_CachedAttention for sliding-
    window layers; max_len is the cache CAPACITY here, not a sequence
    bound, and pos is (1,) or one depth a row, (B,)."""
    if not window:
        raise ValueError("_contrib_RollingCachedAttention needs "
                         "window > 0")
    return rolling_cached_attention(query, key, value, k_cache,
                                    v_cache, pos, int(window),
                                    scale=scale)


@register("_contrib_CachedAttention",
          arg_names=("query", "key", "value", "k_cache", "v_cache",
                     "pos"),
          state_inputs=(3, 4), nondiff_inputs=(5,),
          differentiable=False,
          defaults={"scale": None, "max_len": 0, "window": 0,
                    "block": 0})
def _cached_attention_op(query, key, value, k_cache, v_cache, pos,
                         scale=None, window=0, block=0, scope=None,
                         **_):
    """(B, H, Tnew, hd) decode attention; k_cache/v_cache
    ((B, max_len, Hkv*hd)) are aux states updated in place (the executor threads them like BN moving
    stats — but unconditionally, since appending to the cache is the
    op's purpose at inference). scope: a ``jax.named_scope`` around
    the write and the read, where the graph names one (a stack whose
    attention differs by layer tells its kinds apart in a device
    trace: "attn.full" beside the circular op's "attn.window")."""
    with jax.named_scope(scope) if scope else contextlib.nullcontext():
        return cached_attention(query, key, value, k_cache, v_cache,
                                pos, scale=scale,
                                window=int(window or 0),
                                block=int(block or 0))


def _q8_quantize(x):
    """Per-token-per-head symmetric int8: absmax/127 scale over the
    head dim. The 1e-8 clamp stores an all-zero k/v row as zeros, not
    NaNs. Shared by the shared-position and per-row cache writers so
    both paths store BIT-IDENTICAL cache entries for the same row."""
    xf = x.astype(jnp.float32)
    s = jnp.maximum(jnp.max(jnp.abs(xf), axis=-1), 1e-8) / 127.0
    q = jnp.round(xf / s[..., None]).astype(jnp.int8)
    return q, s


def cached_attention_q8(query, key, value, k_cache, v_cache, k_scale,
                        v_scale, pos, scale=None, window=0):
    """cached_attention with INT8 caches — the KV-bandwidth half of
    serving quantization (weight-only int8 covers parameters; at long
    prompts the CACHE dominates decode HBM traffic, and it is read
    every step while each weight is read once).

    k_cache/v_cache: (B, Tmax, Hkv*hd) int8, token-contiguous like
    cached_attention's. k_scale/v_scale: (B, Tmax, Hkv) f32
    per-token-per-head absmax/127 scales — written once when the
    token's k/v enters the cache, so quantization is independent of
    later reads (a token's cache entry never changes). No dequantized
    cache is ever built: the int8→f32 convert sits in the products'
    operand reads and the scales multiply the scores and the
    probabilities (:func:`_attend`), so HBM moves ~half the bytes of
    the bf16 cache (+1.6% for scales at hd=128). Scales clamp at 1e-8:
    an all-zero k/v row stores zeros, not NaNs.

    PER-ROW POSITIONS (continuous batching): like cached_attention,
    pos may be (B,) — row b's new int8 rows AND its f32 scale rows
    land at pos[b] (:func:`_write_rows`, for all four caches), and its
    causal/window mask reads against pos[b]. This is what lets the
    serving slot pool run int8 caches: one compiled (B, 1) step
    whatever depths the slots sit at (mxnet_tpu/serve/decode.py).
    Quantization is _q8_quantize whatever pos is, so the stored cache
    entry for a row is independent of which path wrote it.

    Same capacity contract and GQA grouping as cached_attention.
    Returns (out, k_cache, v_cache, k_scale, v_scale)."""
    B, H, Tn, D = query.shape
    Hkv, _ = _check_heads(query, k_cache)
    C = k_cache.shape[1]
    if scale is None:
        scale = D ** -0.5
    pos = _row_pos(pos, B)
    _check_capacity("cached_attention_q8", pos, Tn, C)
    kq, ks = _q8_quantize(key)       # (B, Hkv, Tn, D), (B, Hkv, Tn)
    vq, vs = _q8_quantize(value)
    k_cache = _write_rows(k_cache, _token_rows(kq), pos)
    v_cache = _write_rows(v_cache, _token_rows(vq), pos)
    k_scale = _write_rows(k_scale, _token_rows(ks), pos)
    v_scale = _write_rows(v_scale, _token_rows(vs), pos)

    out = _attend(query.astype(jnp.float32), k_cache, v_cache,
                  _causal(pos, Tn, C, int(window or 0)), float(scale),
                  k_scale, v_scale)
    return out.astype(query.dtype), k_cache, v_cache, k_scale, v_scale


@register("_contrib_CachedAttentionQ8",
          arg_names=("query", "key", "value", "k_cache", "v_cache",
                     "k_scale", "v_scale", "pos"),
          state_inputs=(3, 4, 5, 6), nondiff_inputs=(7,),
          differentiable=False,
          defaults={"scale": None, "max_len": 0, "window": 0})
def _cached_attention_q8_op(query, key, value, k_cache, v_cache,
                            k_scale, v_scale, pos, scale=None,
                            window=0, **_):
    """Int8-cache decode attention; caches AND their per-token scales
    are aux states threaded by the executor."""
    return cached_attention_q8(query, key, value, k_cache, v_cache,
                               k_scale, v_scale, pos, scale=scale,
                               window=int(window or 0))


@register("_contrib_FlashAttention",
          arg_names=("query", "key", "value"),
          aliases=("_contrib_flash_attention",),
          defaults={"scale": None, "causal": False, "block_q": 512,
                    "block_k": 512, "seq_axis": None, "window": 0})
def _flash_attention_op(query, key, value, scale=None, causal=False,
                        block_q=512, block_k=512, seq_axis=None,
                        window=0, **_):
    """(B, H, T, D) fused attention; returns same shape.

    seq_axis: name of a mesh axis to sequence-parallelize over. When the
    surrounding graph is lowered over a mesh carrying that axis (>1
    devices), the op runs RING attention — q stays put, k/v blocks
    rotate via ppermute, each device holds T/n of the sequence
    (parallel/ring.py; the symbol-level long-context path). Otherwise
    (eager, no mesh, or axis absent/size-1) it is the single-chip
    Pallas flash kernel. Inputs must be 4-D (B, H, T, D) for the ring
    path.

    Grouped-query attention: k/v may carry FEWER heads than q (Hkv
    dividing H); they are broadcast to the q-head count here, before
    the kernel. Training compute is MXU-bound so the repeat costs
    little; the GQA win is the decode cache (cached_attention keeps
    Hkv heads and never materializes the repeat)."""
    if query.ndim == 4 and key.shape[1] != query.shape[1]:
        H, Hkv = query.shape[1], key.shape[1]
        if H % Hkv:
            raise ValueError("query heads (%d) must be a multiple of "
                             "kv heads (%d)" % (H, Hkv))
        key = jnp.repeat(key, H // Hkv, axis=1)
        value = jnp.repeat(value, H // Hkv, axis=1)
    if seq_axis:
        from ._mesh_ctx import active_mesh_axis
        mesh = active_mesh_axis(seq_axis)
        if mesh is not None:
            if query.ndim != 4:
                raise ValueError(
                    "seq_axis ring attention needs (B, H, T, D) inputs, "
                    "got ndim=%d" % query.ndim)
            from ..parallel.ring import ring_attention
            return ring_attention(query, key, value, mesh, seq_axis,
                                  causal=bool(causal), scale=scale,
                                  window=int(window or 0))
    kernel = functools.partial(
        flash_attention, scale=scale, causal=causal, block_q=block_q,
        block_k=block_k, window=int(window or 0) or None)
    if query.ndim == 4:
        kernel = _over_batch_shards(kernel, query.shape[0])
    return kernel(query, key, value)


def _over_batch_shards(kernel, batch):
    """``kernel`` over (B, H, T, D) operands, wrapped so that each device
    of the ambient mesh runs it on its own batch shard.

    A compiled pallas_call is an opaque custom call and GSPMD refuses to
    partition it ("Mosaic kernels cannot be automatically partitioned.
    Please wrap the call in a shard_map"). Rows are independent, so the
    batch splits over the mesh's replica axes. check_vma=False keeps the
    CPU mesh on the interpreted kernel (no dense fallback). Unwrapped
    when there is no mesh, nothing to split, an indivisible batch, or a
    caller already inside a shard_map."""
    import math

    from ..parallel.sharding import REPLICA_AXES
    from ._mesh_ctx import ambient_mesh
    mesh = ambient_mesh()
    if mesh is None or jax.sharding.get_abstract_mesh().manual_axes:
        return kernel
    axes = tuple(a for a in REPLICA_AXES
                 if a in mesh.axis_names and mesh.shape[a] > 1)
    if not axes or batch % math.prod(mesh.shape[a] for a in axes):
        return kernel
    spec = jax.sharding.PartitionSpec(axes)
    return jax.shard_map(kernel, mesh=mesh, in_specs=(spec, spec, spec),
                         out_specs=spec, check_vma=False)
