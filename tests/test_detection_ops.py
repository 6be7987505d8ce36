"""SSD op stack vs numpy oracles (reference
src/operator/contrib/multibox_*.cc, src/operator/roi_pooling.cc)."""
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import nd


# -- numpy oracles (independent re-implementations of the reference
#    loops) -------------------------------------------------------------


def np_prior(h, w, sizes, ratios, clip=False, steps=(-1, -1),
             offsets=(0.5, 0.5)):
    step_y = steps[0] if steps[0] > 0 else 1.0 / h
    step_x = steps[1] if steps[1] > 0 else 1.0 / w
    out = []
    for r in range(h):
        cy = (r + offsets[0]) * step_y
        for c in range(w):
            cx = (c + offsets[1]) * step_x
            for s in sizes:
                bw = s * h / w / 2
                bh = s / 2
                out.append([cx - bw, cy - bh, cx + bw, cy + bh])
            for ratio in ratios[1:]:
                sr = np.sqrt(ratio)
                bw = sizes[0] * h / w * sr / 2
                bh = sizes[0] / sr / 2
                out.append([cx - bw, cy - bh, cx + bw, cy + bh])
    out = np.array(out, np.float32)
    if clip:
        out = np.clip(out, 0, 1)
    return out[None]


def np_iou(a, b):
    iw = max(0.0, min(a[2], b[2]) - max(a[0], b[0]))
    ih = max(0.0, min(a[3], b[3]) - max(a[1], b[1]))
    i = iw * ih
    u = (a[2] - a[0]) * (a[3] - a[1]) + (b[2] - b[0]) * (b[3] - b[1]) - i
    return 0.0 if u <= 0 else i / u


def np_nms(rows, nms_threshold, force_suppress):
    rows = rows.copy()
    n = len(rows)
    for i in range(n):
        if rows[i, 0] < 0:
            continue
        for j in range(i + 1, n):
            if rows[j, 0] < 0:
                continue
            if force_suppress or rows[i, 0] == rows[j, 0]:
                if np_iou(rows[i, 2:6], rows[j, 2:6]) >= nms_threshold:
                    rows[j] = -1
    return rows


def test_multibox_prior_matches_reference_loop():
    x = nd.zeros((1, 3, 4, 6))
    out = nd._contrib_MultiBoxPrior(
        x, sizes=(0.5, 0.3), ratios=(1.0, 2.0, 0.5), clip=True).asnumpy()
    want = np_prior(4, 6, [0.5, 0.3], [1.0, 2.0, 0.5], clip=True)
    assert out.shape == (1, 4 * 6 * 4, 4)
    np.testing.assert_allclose(out, want, rtol=1e-5, atol=1e-6)


def test_multibox_prior_steps_offsets():
    x = nd.zeros((1, 1, 2, 2))
    out = nd.MultiBoxPrior(x, sizes=(0.4,), ratios=(1.0,),
                           steps=(0.6, 0.4), offsets=(0.3, 0.7)).asnumpy()
    want = np_prior(2, 2, [0.4], [1.0], steps=(0.6, 0.4),
                    offsets=(0.3, 0.7))
    np.testing.assert_allclose(out, want, rtol=1e-5, atol=1e-6)


def _simple_target_setup():
    # 4 anchors, 2 gt boxes, 3 classes (bg + 2)
    anchors = np.array([[0.0, 0.0, 0.5, 0.5],
                        [0.5, 0.5, 1.0, 1.0],
                        [0.0, 0.5, 0.5, 1.0],
                        [0.2, 0.2, 0.4, 0.4]], np.float32)[None]
    label = np.array([[[0, 0.05, 0.05, 0.45, 0.45],
                       [1, 0.55, 0.55, 0.95, 0.95],
                       [-1, -1, -1, -1, -1]]], np.float32)
    cls_pred = np.zeros((1, 3, 4), np.float32)
    return anchors, label, cls_pred


def test_multibox_target_matching_and_encoding():
    anchors, label, cls_pred = _simple_target_setup()
    loc_t, loc_m, cls_t = nd._contrib_MultiBoxTarget(
        nd.array(anchors), nd.array(label), nd.array(cls_pred))
    cls_t = cls_t.asnumpy()[0]
    loc_m = loc_m.asnumpy()[0].reshape(4, 4)
    loc_t = loc_t.asnumpy()[0].reshape(4, 4)

    # anchor0 matches gt0 (class 0 -> target 1), anchor1 gt1 (-> 2)
    assert cls_t[0] == 1.0 and cls_t[1] == 2.0
    # others below overlap threshold: negatives (background 0), since
    # negative_mining_ratio defaults to -1 (use all negatives)
    assert cls_t[2] == 0.0 and cls_t[3] == 0.0
    assert loc_m[0].all() and loc_m[1].all()
    assert not loc_m[2].any() and not loc_m[3].any()

    # loc encoding vs hand formula for anchor0/gt0
    a = anchors[0, 0]
    g = label[0, 0, 1:5]
    aw, ah = a[2] - a[0], a[3] - a[1]
    ax, ay = (a[0] + a[2]) / 2, (a[1] + a[3]) / 2
    gw, gh = g[2] - g[0], g[3] - g[1]
    gx, gy = (g[0] + g[2]) / 2, (g[1] + g[3]) / 2
    want = [(gx - ax) / aw / 0.1, (gy - ay) / ah / 0.1,
            np.log(gw / aw) / 0.2, np.log(gh / ah) / 0.2]
    np.testing.assert_allclose(loc_t[0], want, rtol=1e-4, atol=1e-5)


def test_multibox_target_negative_mining():
    anchors, label, cls_pred = _simple_target_setup()
    # anchor 3 is confidently background, anchor 2 is not: hard-negative
    # mining keeps the HARDEST negative (lowest bg prob) — reference
    # multibox_target.cc:229 sorts by -softmax_bg ascending-in-prob
    cls_pred[0, 0, :] = [0.1, 0.1, 0.1, 5.0]
    loc_t, loc_m, cls_t = nd._contrib_MultiBoxTarget(
        nd.array(anchors), nd.array(label), nd.array(cls_pred),
        negative_mining_ratio=0.5, negative_mining_thresh=0.5)
    cls_t = cls_t.asnumpy()[0]
    # 2 positives * 0.5 = 1 negative: anchor 2 (hard); anchor 3 ignored
    assert cls_t[0] == 1.0 and cls_t[1] == 2.0
    assert cls_t[2] == 0.0
    assert cls_t[3] == -1.0


def test_multibox_target_no_gt():
    anchors = np.array([[[0, 0, 0.5, 0.5]]], np.float32)
    label = -np.ones((1, 2, 5), np.float32)
    cls_pred = np.zeros((1, 2, 1), np.float32)
    loc_t, loc_m, cls_t = nd.MultiBoxTarget(
        nd.array(anchors), nd.array(label), nd.array(cls_pred))
    assert (cls_t.asnumpy() == -1).all()
    assert (loc_m.asnumpy() == 0).all()
    assert (loc_t.asnumpy() == 0).all()


def test_multibox_detection_nms_vs_numpy():
    rng = np.random.RandomState(0)
    A, C = 8, 3
    anchors = np.zeros((A, 4), np.float32)
    centers = rng.uniform(0.2, 0.8, (A, 2))
    anchors[:, 0:2] = centers - 0.1
    anchors[:, 2:4] = centers + 0.1
    # two clusters of overlapping anchors
    anchors[1] = anchors[0] + 0.01
    anchors[3] = anchors[2] + 0.01
    cls_prob = rng.uniform(0, 1, (1, C, A)).astype(np.float32)
    cls_prob /= cls_prob.sum(1, keepdims=True)
    loc_pred = (rng.uniform(-0.2, 0.2, (1, A * 4))).astype(np.float32)

    out = nd._contrib_MultiBoxDetection(
        nd.array(cls_prob), nd.array(loc_pred), nd.array(anchors[None]),
        nms_threshold=0.45, threshold=0.1).asnumpy()[0]

    # numpy oracle: decode + sort + nms
    scores = cls_prob[0, 1:].max(0)
    ids = cls_prob[0, 1:].argmax(0) + 1
    valid = scores >= 0.1
    boxes = np.zeros((A, 4), np.float32)
    for i in range(A):
        a = anchors[i]
        p = loc_pred[0, i * 4:i * 4 + 4]
        aw, ah = a[2] - a[0], a[3] - a[1]
        ax, ay = (a[0] + a[2]) / 2, (a[1] + a[3]) / 2
        ox = p[0] * 0.1 * aw + ax
        oy = p[1] * 0.1 * ah + ay
        ow = np.exp(p[2] * 0.2) * aw / 2
        oh = np.exp(p[3] * 0.2) * ah / 2
        boxes[i] = np.clip([ox - ow, oy - oh, ox + ow, oy + oh], 0, 1)
    order = np.argsort(-np.where(valid, scores, -1), kind="stable")
    rows = np.full((A, 6), -1, np.float32)
    for r, i in enumerate(order):
        if valid[i]:
            rows[r] = [ids[i] - 1, scores[i], *boxes[i]]
    want = np_nms(rows, 0.45, False)

    np.testing.assert_allclose(out, want, rtol=1e-4, atol=1e-5)


def test_multibox_detection_force_suppress_and_topk():
    cls_prob = np.array([[[0.1, 0.2, 0.1],
                          [0.8, 0.1, 0.8],
                          [0.1, 0.7, 0.1]]], np.float32)  # (1,3,3)
    anchors = np.array([[[0.1, 0.1, 0.4, 0.4],
                         [0.12, 0.12, 0.42, 0.42],
                         [0.6, 0.6, 0.9, 0.9]]], np.float32)
    loc_pred = np.zeros((1, 12), np.float32)
    out = nd.MultiBoxDetection(
        nd.array(cls_prob), nd.array(loc_pred), nd.array(anchors),
        force_suppress=True, nms_threshold=0.5).asnumpy()[0]
    kept = out[out[:, 0] >= 0]
    # anchors 0/1 overlap heavily; different classes, but force_suppress
    # kills the lower-scoring one
    assert len(kept) == 2
    out2 = nd.MultiBoxDetection(
        nd.array(cls_prob), nd.array(loc_pred), nd.array(anchors),
        force_suppress=True, nms_threshold=0.5, nms_topk=1).asnumpy()[0]
    assert (out2[:, 0] >= 0).sum() == 1


def test_multibox_detection_background_id():
    # class 2 is background: anchor 0's best foreground is class 0,
    # anchor 1's is class 1 (renumbered to 1 — below background, so kept)
    cls_prob = np.array([[[0.9, 0.1],
                          [0.05, 0.6],
                          [0.05, 0.3]]], np.float32)  # (1,3,2)
    anchors = np.array([[[0.1, 0.1, 0.4, 0.4],
                         [0.6, 0.6, 0.9, 0.9]]], np.float32)
    loc_pred = np.zeros((1, 8), np.float32)
    out = nd.MultiBoxDetection(
        nd.array(cls_prob), nd.array(loc_pred), nd.array(anchors),
        background_id=2, threshold=0.01,
        nms_threshold=0.0).asnumpy()[0]
    kept = out[out[:, 0] >= 0]
    assert set(kept[:, 0].astype(int)) == {0, 1}
    np.testing.assert_allclose(sorted(kept[:, 1]), [0.6, 0.9], rtol=1e-5)


def test_roi_pooling_vs_numpy():
    rng = np.random.RandomState(1)
    data = rng.randn(2, 3, 8, 8).astype(np.float32)
    rois = np.array([[0, 0, 0, 7, 7],
                     [1, 2, 2, 6, 6],
                     [0, 4, 4, 7, 5]], np.float32)
    out = nd.ROIPooling(nd.array(data), nd.array(rois),
                        pooled_size=(2, 2), spatial_scale=1.0).asnumpy()

    def np_roi(img, x1, y1, x2, y2, ph, pw):
        rw = max(x2 - x1 + 1, 1)
        rh = max(y2 - y1 + 1, 1)
        out = np.zeros((img.shape[0], ph, pw), np.float32)
        for i in range(ph):
            for j in range(pw):
                hs = int(np.floor(i * rh / ph)) + y1
                he = int(np.ceil((i + 1) * rh / ph)) + y1
                ws = int(np.floor(j * rw / pw)) + x1
                we = int(np.ceil((j + 1) * rw / pw)) + x1
                hs, he = max(hs, 0), min(he, img.shape[1])
                ws, we = max(ws, 0), min(we, img.shape[2])
                if he > hs and we > ws:
                    out[:, i, j] = img[:, hs:he, ws:we].max((1, 2))
        return out

    for r, roi in enumerate(rois):
        want = np_roi(data[int(roi[0])], int(roi[1]), int(roi[2]),
                      int(roi[3]), int(roi[4]), 2, 2)
        np.testing.assert_allclose(out[r], want, rtol=1e-5, atol=1e-6,
                                   err_msg="roi %d" % r)


def test_roi_pooling_spatial_scale():
    data = np.arange(16, dtype=np.float32).reshape(1, 1, 4, 4)
    rois = np.array([[0, 0, 0, 15, 15]], np.float32)
    out = nd.ROIPooling(nd.array(data), nd.array(rois),
                        pooled_size=(1, 1), spatial_scale=0.25).asnumpy()
    assert out.reshape(()) == 15.0


def test_detection_ops_jittable():
    """The whole target+detection path must trace under jit (static
    shapes, no host sync) — that's the TPU-native requirement."""
    import jax

    anchors, label, cls_pred = _simple_target_setup()

    from mxnet_tpu.ops.registry import get_op

    tgt = get_op("_contrib_MultiBoxTarget")
    f = jax.jit(lambda a, l, c: tgt.fn(a, l, c,
                                       negative_mining_ratio=2.0))
    outs = f(anchors, label, cls_pred)
    assert outs[2].shape == (1, 4)

    det = get_op("_contrib_MultiBoxDetection")
    g = jax.jit(lambda c, l, a: det.fn(c, l, a))
    res = g(np.zeros((1, 3, 4), np.float32),
            np.zeros((1, 16), np.float32), anchors)
    assert res.shape == (1, 4, 6)


def test_nms_pallas_matches_xla_path():
    """The blocked Pallas NMS must agree with the dense-matrix XLA path
    on full MultiBoxDetection outputs (including vmap over the batch)."""
    import os
    import mxnet_tpu as mx
    from mxnet_tpu import nd
    rng = np.random.RandomState(7)
    B, C, A = 2, 4, 300
    cls_prob = rng.rand(B, C, A).astype(np.float32)
    cls_prob /= cls_prob.sum(1, keepdims=True)
    loc_pred = (rng.rand(B, A * 4).astype(np.float32) - 0.5) * 0.4
    xy = rng.rand(1, A, 2).astype(np.float32)
    wh = rng.rand(1, A, 2).astype(np.float32) * 0.3
    anchor = np.concatenate([xy, xy + wh], axis=2)

    def run(impl, force):
        return nd._contrib_MultiBoxDetection(
            nd.array(cls_prob), nd.array(loc_pred), nd.array(anchor),
            nms_threshold=0.45, threshold=0.05, nms_topk=200,
            force_suppress=force, impl=impl).asnumpy()

    # impl is an op attr (part of the jit cache key), so the two runs
    # really trace + execute different NMS implementations; both the
    # class-aware and force_suppress branches are compared
    for force in (False, True):
        out_pallas = run("pallas", force)
        out_xla = run("xla", force)
        np.testing.assert_allclose(out_pallas, out_xla,
                                   rtol=1e-6, atol=1e-6)
        assert (out_pallas[:, :, 0] >= 0).sum() > 0  # something survived


def test_nms_pallas_iou_matches_shared_helper():
    """_iou_tile restates _box_iou_corner (Mosaic can't reuse it); pin
    the two implementations to identical numerics."""
    from mxnet_tpu.ops.nms_pallas import _iou_tile
    from mxnet_tpu.ops.detection_ops import _box_iou_corner
    rng = np.random.RandomState(3)
    xy = rng.rand(60, 2).astype(np.float32)
    a = np.concatenate([xy, xy + rng.rand(60, 2).astype(np.float32)], 1)
    b = a[rng.permutation(60)[:40]]
    tile = _iou_tile([a[:, i:i + 1] for i in range(4)],
                     [b.T[i:i + 1] for i in range(4)])
    np.testing.assert_array_equal(np.asarray(tile),
                                  np.asarray(_box_iou_corner(a, b)))
