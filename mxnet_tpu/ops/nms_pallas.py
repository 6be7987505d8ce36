"""Blocked greedy NMS as a Pallas TPU kernel.

The XLA path in detection_ops._detect_one materializes the full A x A IoU
matrix before the greedy suppression loop — for SSD's 8732 anchors that is
~300 MB of HBM traffic per sample. This kernel runs the same greedy
algorithm (reference semantics: multibox_detection.cc:107 NMS loop) in
score-sorted block order and only ever holds one (block x chunk) IoU tile
in VMEM:

  grid (row block b, column chunk c), both sequential, c innermost:
    c == 0: intra-block — greedy suppression inside block b (fori_loop
            over its rows, vectorized across lanes)
    every chunk that holds rows later than block b: one (block x chunk)
            IoU tile suppresses them against the block's survivors

Greedy order is preserved because grid steps run sequentially on TPU and
the keep mask stays resident in the output block across steps. The boxes
come in twice, as columns (rows of the block down the sublanes) and
transposed (coordinates as lane-dense rows), so no step pays for an
(A, 4) array padded to 128 lanes or for a relayout. On the CPU the
kernel runs in Pallas interpret mode (ops/_pallas.py), so numerics are
identical everywhere.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

from . import _pallas

_BLOCK = 128     # rows whose suppression is resolved per grid row
_CHUNK = 1024    # columns per IoU tile: (128, 1024) f32 = 512 KiB
_FIELDS = 8      # x1 y1 x2 y2 class + padding to a sublane tile


def _iou_tile(a, b):
    """IoU of corner boxes -> (Na, Nb). a: the four coordinates as
    (Na, 1) columns, b: as (1, Nb) rows.

    Same formula as detection_ops._box_iou_corner, restated on split
    coordinates: Mosaic rejects jnp.split on the 4-wide minor dimension,
    so the shared helper cannot be reused inside the kernel (a unit test
    pins the two implementations equal)."""
    ax1, ay1, ax2, ay2 = a
    bx1, by1, bx2, by2 = b
    iw = jnp.maximum(0.0, jnp.minimum(ax2, bx2) - jnp.maximum(ax1, bx1))
    ih = jnp.maximum(0.0, jnp.minimum(ay2, by2) - jnp.maximum(ay1, by1))
    inter = iw * ih
    union = (ax2 - ax1) * (ay2 - ay1) + (bx2 - bx1) * (by2 - by1) - inter
    return jnp.where(union <= 0, 0.0, inter / jnp.maximum(union, 1e-12))


def _nms_kernel(rows_ref, blk_t_ref, chunk_t_ref, keep_in_ref, keep_ref,
                *, block, chunk, nms_threshold, force_suppress):
    bi, ci = pl.program_id(0), pl.program_id(1)
    offs = pl.multiple_of(bi * block, block)

    @pl.when((bi == 0) & (ci == 0))
    def _seed():
        keep_ref[...] = keep_in_ref[...]

    rows = rows_ref[...]                                  # (block, 8)
    a = [rows[:, i:i + 1] for i in range(5)]              # (block, 1)

    def suppresses(t):
        """0/1 f32 (block, n): block row r would suppress column c of
        the transposed fields t (8, n). All masks live as 0/1 float32:
        Mosaic cannot vector-truncate wider ints to i1, so boolean-valued
        selects/reductions are avoided."""
        b = [t[i:i + 1, :] for i in range(5)]             # (1, n)
        sup = (_iou_tile(a[:4], b[:4]) >= nms_threshold) \
            .astype(jnp.float32)
        if not force_suppress:
            sup = sup * (a[4] == b[4]).astype(jnp.float32)
        return sup

    @pl.when(ci == 0)
    def _intra():
        sup_bb = suppresses(blk_t_ref[...])               # (block, block)
        row_id = lax.broadcasted_iota(jnp.int32, (block, 1), 0)
        col_id = lax.broadcasted_iota(jnp.int32, (1, block), 1)

        def intra(i, k):                                  # k (1, block)
            alive = jnp.max(jnp.where(col_id == i, k, 0.0))
            row = jnp.max(jnp.where(row_id == i, sup_bb, 0.0), axis=0,
                          keepdims=True)
            kill = alive * row * (col_id > i).astype(jnp.float32)
            return k * (1.0 - kill)

        keep_ref[:, pl.ds(offs, block)] = lax.fori_loop(
            0, block, intra, keep_ref[:, pl.ds(offs, block)])

    # survivors of this block suppress the later rows of this chunk; the
    # block's own keep entries are final once c == 0 has run
    @pl.when((ci + 1) * chunk > offs + block)
    def _inter():
        cols = pl.ds(pl.multiple_of(ci * chunk, chunk), chunk)
        sup = suppresses(chunk_t_ref[...])                # (block, chunk)
        # survivors (1, block) @ sup: how many survivors hit each
        # column. 0/1 operands and sums <= block are exact in one bf16
        # MXU pass, whatever the ambient matmul precision asks for
        hits = jnp.dot(keep_ref[:, pl.ds(offs, block)], sup,
                       precision=lax.Precision.DEFAULT,
                       preferred_element_type=jnp.float32)
        later = ci * chunk + lax.broadcasted_iota(
            jnp.int32, (1, chunk), 1) >= offs + block
        kill = (hits > 0.0).astype(jnp.float32) * later.astype(jnp.float32)
        keep_ref[:, cols] = keep_ref[:, cols] * (1.0 - kill)


@functools.partial(jax.jit,
                   static_argnames=("nms_threshold", "force_suppress"))
def nms_keep(boxes, cls_ids, valid, nms_threshold, force_suppress=False):
    """Greedy NMS over score-sorted corner boxes.

    boxes (A,4), cls_ids (A,) float class labels, valid (A,) bool.
    Returns the surviving-row bool mask — bit-identical to the dense
    XLA path in detection_ops (tested in tests/test_detection_ops.py).
    """
    A = boxes.shape[0]
    pad = (-A) % _CHUNK
    padded = A + pad
    fields = jnp.concatenate(
        [boxes.astype(jnp.float32), cls_ids.astype(jnp.float32)[:, None]],
        axis=1)
    fields = jnp.pad(fields, ((0, pad), (0, _FIELDS - 5)),
                     constant_values=-1.0)                # (padded, 8)
    fields_t = fields.T                                   # (8, padded)
    keep0 = jnp.pad(valid.astype(jnp.float32), (0, pad))[None, :]

    kernel = functools.partial(
        _nms_kernel, block=_BLOCK, chunk=_CHUNK,
        nms_threshold=nms_threshold, force_suppress=force_suppress)
    out = pl.pallas_call(
        kernel,
        grid=(padded // _BLOCK, padded // _CHUNK),
        in_specs=[
            pl.BlockSpec((_BLOCK, _FIELDS), lambda b, c: (b, 0)),
            pl.BlockSpec((_FIELDS, _BLOCK), lambda b, c: (0, b)),
            pl.BlockSpec((_FIELDS, _CHUNK), lambda b, c: (0, c)),
            pl.BlockSpec((1, padded), lambda b, c: (0, 0)),
        ],
        out_specs=pl.BlockSpec((1, padded), lambda b, c: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((1, padded), jnp.float32),
        interpret=_pallas.interpret(),
    )(fields, fields_t, fields_t, keep0)
    return out[0, :A] > 0.0
