"""Detection training-path ops: yolov3_loss, roi_pool, bipartite_match,
target_assign, rpn_target_assign, generate_proposals, detection_map.

Goldens are independent numpy transcriptions of the reference kernels
(operators/detection/yolov3_loss_op.h, roi_pool_op.h, bipartite_match_op.cc,
target_assign_op.h), following the reference OpTest files."""
import numpy as np
import pytest

import paddle_tpu as fluid


def _run_prog(build, feed):
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        fetches = build()
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    exe.run(startup, scope=scope)
    outs = exe.run(main, feed=feed, fetch_list=list(fetches), scope=scope)
    return [np.asarray(o) for o in outs]


# --------------------------------------------------------------------------
# yolov3_loss golden (numpy transcription of yolov3_loss_op.h loops)
# --------------------------------------------------------------------------

def _sig(x):
    return 1.0 / (1.0 + np.exp(-x))


def _sce(x, label):
    return np.maximum(x, 0.0) - x * label + np.log1p(np.exp(-abs(x)))


def _ciou(b1, b2):
    inter_w = max(0.0, min(b1[0] + b1[2] / 2, b2[0] + b2[2] / 2)
                  - max(b1[0] - b1[2] / 2, b2[0] - b2[2] / 2))
    inter_h = max(0.0, min(b1[1] + b1[3] / 2, b2[1] + b2[3] / 2)
                  - max(b1[1] - b1[3] / 2, b2[1] - b2[3] / 2))
    inter = inter_w * inter_h
    return inter / max(b1[2] * b1[3] + b2[2] * b2[3] - inter, 1e-10)


def _np_yolo_loss(x, gt_box, gt_label, anchors, anchor_mask, C,
                  ignore_thresh, downsample, smooth):
    n, _, h, w = x.shape
    m = len(anchor_mask)
    an_num = len(anchors) // 2
    b = gt_box.shape[1]
    input_size = downsample * h
    xr = x.reshape(n, m, 5 + C, h, w)
    loss = np.zeros(n)
    if smooth:
        delta = min(1.0 / C, 1.0 / 40)
        pos, neg = 1.0 - delta, delta
    else:
        pos, neg = 1.0, 0.0
    for i in range(n):
        obj_mask = np.zeros((m, h, w))
        for j in range(m):
            for k in range(h):
                for l in range(w):
                    a = anchor_mask[j]
                    pb = [(l + _sig(xr[i, j, 0, k, l])) / w,
                          (k + _sig(xr[i, j, 1, k, l])) / h,
                          np.exp(xr[i, j, 2, k, l]) * anchors[2 * a] / input_size,
                          np.exp(xr[i, j, 3, k, l]) * anchors[2 * a + 1] / input_size]
                    best = 0.0
                    for t in range(b):
                        if gt_box[i, t, 2] <= 0 or gt_box[i, t, 3] <= 0:
                            continue
                        best = max(best, _ciou(pb, gt_box[i, t]))
                    if best > ignore_thresh:
                        obj_mask[j, k, l] = -1
        for t in range(b):
            g = gt_box[i, t]
            if g[2] <= 0 or g[3] <= 0:
                continue
            gi, gj = int(g[0] * w), int(g[1] * h)
            best_iou, best_n = 0.0, 0
            for a in range(an_num):
                an = [0, 0, anchors[2 * a] / input_size, anchors[2 * a + 1] / input_size]
                iou = _ciou(an, [0, 0, g[2], g[3]])
                if iou > best_iou:
                    best_iou, best_n = iou, a
            if best_n not in anchor_mask:
                continue
            mi = anchor_mask.index(best_n)
            tx, ty = g[0] * w - gi, g[1] * h - gj
            tw = np.log(g[2] * input_size / anchors[2 * best_n])
            th = np.log(g[3] * input_size / anchors[2 * best_n + 1])
            scale = 2.0 - g[2] * g[3]
            loss[i] += _sce(xr[i, mi, 0, gj, gi], tx) * scale
            loss[i] += _sce(xr[i, mi, 1, gj, gi], ty) * scale
            loss[i] += abs(xr[i, mi, 2, gj, gi] - tw) * scale
            loss[i] += abs(xr[i, mi, 3, gj, gi] - th) * scale
            obj_mask[mi, gj, gi] = 1.0
            lab = gt_label[i, t]
            for c in range(C):
                loss[i] += _sce(xr[i, mi, 5 + c, gj, gi], pos if c == lab else neg)
        for j in range(m):
            for k in range(h):
                for l in range(w):
                    o = obj_mask[j, k, l]
                    if o > 1e-5:
                        loss[i] += _sce(xr[i, j, 4, k, l], 1.0) * o
                    elif o > -0.5:
                        loss[i] += _sce(xr[i, j, 4, k, l], 0.0)
    return loss


ANCHORS = [10, 13, 16, 30, 33, 23, 30, 61, 62, 45, 59, 119]
MASK = [0, 1, 2]


def test_yolov3_loss_golden():
    rng = np.random.RandomState(5)
    n, h, w, C = 2, 5, 5, 4
    m = len(MASK)
    x = rng.randn(n, m * (5 + C), h, w).astype("f4") * 0.5
    gt_box = rng.uniform(0.1, 0.9, (n, 3, 4)).astype("f4")
    gt_box[:, :, 2:] = rng.uniform(0.05, 0.4, (n, 3, 2))
    gt_box[1, 2] = 0.0  # invalid gt row (w = h = 0)
    gt_label = rng.randint(0, C, (n, 3)).astype("int32")

    expect = _np_yolo_loss(x, gt_box, gt_label, ANCHORS, MASK, C, 0.7, 32, True)

    def build():
        xv = fluid.layers.data("x", [m * (5 + C), h, w], dtype="float32")
        gb = fluid.layers.data("gb", [3, 4], dtype="float32")
        gl = fluid.layers.data("gl", [3], dtype="int32")
        loss = fluid.layers.yolov3_loss(xv, gb, gl, ANCHORS, MASK, C,
                                        ignore_thresh=0.7, downsample_ratio=32)
        return [loss]

    (got,) = _run_prog(build, {"x": x, "gb": gt_box, "gl": gt_label})
    np.testing.assert_allclose(got.reshape(-1), expect, rtol=2e-4, atol=2e-4)


def test_yolov3_trains():
    """tiny conv head + yolov3_loss trains to decreasing loss (the e2e gate
    r3 review asked for)."""
    rng = np.random.RandomState(0)
    n, h, w, C = 4, 4, 4, 3
    m = len(MASK)
    main, startup = fluid.Program(), fluid.Program()
    startup.random_seed = 5
    with fluid.program_guard(main, startup):
        img = fluid.layers.data("img", [3, 32, 32], dtype="float32")
        gb = fluid.layers.data("gb", [2, 4], dtype="float32")
        gl = fluid.layers.data("gl", [2], dtype="int32")
        c1 = fluid.layers.conv2d(img, 16, 3, stride=2, padding=1, act="relu")
        c2 = fluid.layers.conv2d(c1, 32, 3, stride=2, padding=1, act="relu")
        head = fluid.layers.conv2d(c2, m * (5 + C), 3, stride=2, padding=1)
        loss = fluid.layers.mean(fluid.layers.yolov3_loss(
            head, gb, gl, ANCHORS, MASK, C, ignore_thresh=0.7,
            downsample_ratio=8))
        fluid.optimizer.Adam(1e-3).minimize(loss)
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    exe.run(startup, scope=scope)
    imgs = rng.rand(n, 3, 32, 32).astype("f4")
    boxes = rng.uniform(0.2, 0.8, (n, 2, 4)).astype("f4")
    boxes[:, :, 2:] = rng.uniform(0.1, 0.5, (n, 2, 2))
    labels = rng.randint(0, C, (n, 2)).astype("int32")
    feed = {"img": imgs, "gb": boxes, "gl": labels}
    losses = []
    for _ in range(40):
        (lv,) = exe.run(main, feed=feed, fetch_list=[loss], scope=scope)
        losses.append(float(np.asarray(lv).reshape(-1)[0]))
    assert np.isfinite(losses).all()
    assert losses[-1] < losses[0] * 0.6, (losses[0], losses[-1])


# --------------------------------------------------------------------------
# roi_pool golden
# --------------------------------------------------------------------------

def _np_roi_pool(x, rois, batch_idx, ph, pw, scale):
    R = rois.shape[0]
    C, H, W = x.shape[1:]
    out = np.zeros((R, C, ph, pw), "f4")
    for r in range(R):
        x0 = int(round(rois[r, 0] * scale))
        y0 = int(round(rois[r, 1] * scale))
        x1 = int(round(rois[r, 2] * scale))
        y1 = int(round(rois[r, 3] * scale))
        rh, rw = max(y1 - y0 + 1, 1), max(x1 - x0 + 1, 1)
        bh, bw = rh / ph, rw / pw
        for i in range(ph):
            for j in range(pw):
                hs = min(max(int(np.floor(i * bh)) + y0, 0), H)
                he = min(max(int(np.ceil((i + 1) * bh)) + y0, 0), H)
                ws = min(max(int(np.floor(j * bw)) + x0, 0), W)
                we = min(max(int(np.ceil((j + 1) * bw)) + x0, 0), W)
                if he <= hs or we <= ws:
                    continue
                out[r, :, i, j] = x[batch_idx[r], :, hs:he, ws:we].max(axis=(1, 2))
    return out


def test_roi_pool_golden():
    rng = np.random.RandomState(11)
    x = rng.randn(2, 3, 8, 8).astype("f4")
    rois = np.array([[0, 0, 7, 7], [2, 2, 11, 11], [1, 0, 5, 3]], "f4")
    bidx = np.array([0, 1, 1], "int32")
    expect = _np_roi_pool(x, rois, bidx, 2, 2, 0.5)

    def build():
        xv = fluid.layers.data("x", [3, 8, 8], dtype="float32")
        rv = fluid.layers.data("rois", [4], dtype="float32")
        bv = fluid.layers.data("bidx", [], dtype="int32")
        out = fluid.layers.roi_pool(xv, rv, 2, 2, 0.5, rois_batch=bv)
        return [out]

    (got,) = _run_prog(build, {"x": x, "rois": rois, "bidx": bidx})
    np.testing.assert_allclose(got, expect, rtol=1e-5, atol=1e-5)


# --------------------------------------------------------------------------
# bipartite_match golden (reference greedy algorithm in numpy)
# --------------------------------------------------------------------------

def _np_bipartite(dist, match_type="bipartite", thresh=0.5):
    R, C = dist.shape
    idx = np.full(C, -1, "int32")
    dst = np.zeros(C, "f4")
    row_pool = list(range(R))
    while row_pool:
        best = (-1, -1, -1.0)
        for j in range(C):
            if idx[j] != -1:
                continue
            for r in row_pool:
                if dist[r, j] < 1e-6:
                    continue
                if dist[r, j] > best[2]:
                    best = (r, j, dist[r, j])
        if best[0] == -1:
            break
        idx[best[1]] = best[0]
        dst[best[1]] = best[2]
        row_pool.remove(best[0])
    if match_type == "per_prediction":
        for j in range(C):
            if idx[j] != -1:
                continue
            best_r, best_d = -1, -1.0
            for r in range(R):
                d = dist[r, j]
                if d >= 1e-6 and d >= thresh and d > best_d:
                    best_r, best_d = r, d
            if best_r != -1:
                idx[j] = best_r
                dst[j] = best_d
    return idx, dst


@pytest.mark.parametrize("mtype", ["bipartite", "per_prediction"])
def test_bipartite_match_golden(mtype):
    rng = np.random.RandomState(4)
    dist = rng.rand(2, 4, 7).astype("f4")
    dist[0, :, 5] = 0.0  # col with no usable row

    def build():
        d = fluid.layers.data("d", [4, 7], dtype="float32")
        idx, dst = fluid.layers.bipartite_match(d, match_type=mtype,
                                                dist_threshold=0.6)
        return [idx, dst]

    gi, gd = _run_prog(build, {"d": dist})
    for i in range(2):
        ei, ed = _np_bipartite(dist[i], mtype, 0.6)
        np.testing.assert_array_equal(gi[i], ei)
        np.testing.assert_allclose(gd[i], ed, rtol=1e-6)


def test_target_assign_golden():
    rng = np.random.RandomState(6)
    x = rng.randn(2, 3, 4).astype("f4")
    match = np.array([[0, -1, 2, 1], [-1, -1, 0, 0]], "int32")
    neg = np.array([[1, -1], [0, 1]], "int32")

    def build():
        xv = fluid.layers.data("x", [3, 4], dtype="float32")
        mv = fluid.layers.data("m", [4], dtype="int32")
        nv = fluid.layers.data("n", [2], dtype="int32")
        out, wt = fluid.layers.target_assign(xv, mv, negative_indices=nv,
                                             mismatch_value=0)
        return [out, wt]

    out, wt = _run_prog(build, {"x": x, "m": match, "n": neg})
    for i in range(2):
        for j in range(4):
            if match[i, j] >= 0:
                np.testing.assert_allclose(out[i, j], x[i, match[i, j]])
                assert wt[i, j, 0] == 1.0
            else:
                assert (out[i, j] == 0).all()
                expected_w = 1.0 if j in neg[i] else 0.0
                assert wt[i, j, 0] == expected_w, (i, j)


# --------------------------------------------------------------------------
# rpn_target_assign properties
# --------------------------------------------------------------------------

def _grid_anchors():
    # 4x4 grid of 16px cells, one 24x24 anchor per cell
    xs, ys = np.meshgrid(np.arange(4) * 16 + 8, np.arange(4) * 16 + 8)
    ctr = np.stack([xs.ravel(), ys.ravel()], 1).astype("f4")
    return np.concatenate([ctr - 12, ctr + 12], 1)  # [16, 4]


def test_rpn_target_assign_rules():
    anchors = _grid_anchors()
    gt = np.array([[[6, 6, 26, 26], [40, 40, 60, 60]]], "f4")
    im_info = np.array([[64, 64, 1.0]], "f4")

    def build():
        av = fluid.layers.data("a", [4], dtype="float32")
        gv = fluid.layers.data("g", [2, 4], dtype="float32")
        iv = fluid.layers.data("i", [3], dtype="float32")
        bp = fluid.layers.data("bp", [16, 4], dtype="float32")
        cl = fluid.layers.data("cl", [16, 1], dtype="float32")
        rets = fluid.layers.rpn_target_assign(
            bp, cl, av, None, gv, im_info=iv, rpn_batch_size_per_im=8,
            rpn_straddle_thresh=100.0, use_random=False)
        return rets[2:]  # label, tgt, inw, score_w

    feed = {"a": anchors, "g": gt, "i": im_info,
            "bp": np.zeros((1, 16, 4), "f4"), "cl": np.zeros((1, 16, 1), "f4")}
    label, tgt, inw, score_w = _run_prog(build, feed)
    # per-gt best anchors are positive even below the overlap threshold
    assert label.sum() >= 2
    # sampled set bounded by batch size
    assert score_w.sum() <= 8
    # fg rows have inside weight and finite bbox targets; bg rows are zero
    fg = label[0] == 1
    assert (inw[0][fg] == 1).all() and (inw[0][~fg] == 0).all()
    assert np.isfinite(tgt).all()
    # every fg anchor is also counted in the score weights
    assert (score_w[0][fg] == 1).all()


def test_rpn_target_assign_random_reproducible():
    anchors = _grid_anchors()
    gt = np.tile(np.array([[[6, 6, 26, 26]]], "f4"), (1, 1, 1))
    im_info = np.array([[64, 64, 1.0]], "f4")

    def build():
        av = fluid.layers.data("a", [4], dtype="float32")
        gv = fluid.layers.data("g", [1, 4], dtype="float32")
        iv = fluid.layers.data("i", [3], dtype="float32")
        bp = fluid.layers.data("bp", [16, 4], dtype="float32")
        cl = fluid.layers.data("cl", [16, 1], dtype="float32")
        rets = fluid.layers.rpn_target_assign(
            bp, cl, av, None, gv, im_info=iv, rpn_batch_size_per_im=4,
            rpn_straddle_thresh=100.0, use_random=True)
        return [rets[2], rets[5]]

    feed = {"a": anchors, "g": gt, "i": im_info,
            "bp": np.zeros((1, 16, 4), "f4"), "cl": np.zeros((1, 16, 1), "f4")}
    label, score_w = _run_prog(build, feed)
    assert score_w.sum() <= 4


# --------------------------------------------------------------------------
# generate_proposals
# --------------------------------------------------------------------------

def test_generate_proposals_identity_deltas():
    """zero deltas decode back to (clipped) anchors; padding slots have
    prob 0; min_size filters degenerate anchors."""
    rng = np.random.RandomState(9)
    N, A, H, W = 1, 2, 3, 3
    K = A * H * W
    scores = rng.rand(N, A, H, W).astype("f4")
    deltas = np.zeros((N, 4 * A, H, W), "f4")
    # anchors laid out [H, W, A, 4]
    anchors = np.zeros((H, W, A, 4), "f4")
    for h in range(H):
        for w in range(W):
            for a in range(A):
                cx, cy = w * 8 + 4, h * 8 + 4
                sz = 6 + 6 * a
                anchors[h, w, a] = [cx - sz / 2, cy - sz / 2, cx + sz / 2, cy + sz / 2]
    variances = np.ones((H, W, A, 4), "f4")
    im_info = np.array([[24, 24, 1.0]], "f4")

    def build():
        sv = fluid.layers.data("s", [A, H, W], dtype="float32")
        dv = fluid.layers.data("d", [4 * A, H, W], dtype="float32")
        iv = fluid.layers.data("i", [3], dtype="float32")
        av = fluid.layers.data("anc", [W, A, 4], dtype="float32")
        vv = fluid.layers.data("var", [W, A, 4], dtype="float32")
        rois, probs = fluid.layers.generate_proposals(
            sv, dv, iv, av, vv, pre_nms_top_n=K, post_nms_top_n=6,
            nms_thresh=0.9, min_size=1.0)
        return [rois, probs]

    rois, probs = _run_prog(build, {"s": scores, "d": deltas, "i": im_info,
                                    "anc": anchors, "var": variances})
    probs = probs[0, :, 0]
    rois = rois[0]
    valid = probs > 0
    assert valid.sum() > 0
    # every valid roi lies inside the image and meets min_size
    v = rois[valid]
    assert (v[:, 0] >= 0).all() and (v[:, 2] <= 23).all()
    assert ((v[:, 2] - v[:, 0] + 1) >= 1).all()
    # probs sorted descending over valid slots
    pv = probs[valid]
    assert (np.diff(pv) <= 1e-6).all()
    # the top-scoring surviving anchor decodes to itself (zero deltas)
    flat_scores = scores.transpose(0, 2, 3, 1).reshape(-1)
    top_anchor = anchors.reshape(-1, 4)[flat_scores.argmax()]
    expect = np.array([max(top_anchor[0], 0), max(top_anchor[1], 0),
                       min(top_anchor[2], 23), min(top_anchor[3], 23)])
    np.testing.assert_allclose(rois[0], expect, atol=1e-4)


# --------------------------------------------------------------------------
# detection_map
# --------------------------------------------------------------------------

def test_detection_map_perfect_and_mixed():
    # 2 classes (1, 2); image 0 has one gt of each; detections: one perfect
    # match per gt plus one false positive of class 1 (normalized boxes —
    # the reference ClipBBox clamps to [0, 1])
    det = np.array([[[1, 0.9, .1, .1, .2, .2],
                     [2, 0.8, .3, .3, .4, .4],
                     [1, 0.7, .5, .5, .6, .6],
                     [-1, 0.0, 0, 0, 0, 0]]], "f4")
    gt = np.array([[[1, .1, .1, .2, .2],
                    [2, .3, .3, .4, .4]]], "f4")

    def build():
        dv = fluid.layers.data("det", [4, 6], dtype="float32")
        gv = fluid.layers.data("gt", [2, 5], dtype="float32")
        m = fluid.layers.detection_map(dv, gv, class_num=3,
                                       overlap_threshold=0.5,
                                       ap_version="integral")
        return [m]

    (m,) = _run_prog(build, {"det": det, "gt": gt})
    # class 1: det .9 TP, det .7 FP -> AP = 1.0 (recall reached at rank 1)
    # class 2: perfect -> AP = 1.0
    np.testing.assert_allclose(float(m.reshape(-1)[0]), 1.0, atol=1e-6)


def test_detection_map_difficult_excluded():
    """6-col labels carry the difficult flag; evaluate_difficult=False
    drops difficult gts from npos and their matches from TP/FP."""
    det = np.array([[[1, 0.9, .1, .1, .2, .2],
                     [1, 0.8, .5, .5, .6, .6]]], "f4")
    gt = np.array([[[1, 0, .1, .1, .2, .2],
                    [1, 1, .5, .5, .6, .6]]], "f4")  # second gt difficult

    def build():
        dv = fluid.layers.data("det", [2, 6], dtype="float32")
        gv = fluid.layers.data("gt", [2, 6], dtype="float32")
        m1 = fluid.layers.detection_map(dv, gv, class_num=2,
                                        evaluate_difficult=False)
        m2 = fluid.layers.detection_map(dv, gv, class_num=2,
                                        evaluate_difficult=True)
        return [m1, m2]

    m1, m2 = _run_prog(build, {"det": det, "gt": gt})
    # excluded: npos=1, the difficult match is skipped -> AP 1.0
    np.testing.assert_allclose(float(m1.reshape(-1)[0]), 1.0, atol=1e-6)
    # included: both gts count, both dets TP -> AP 1.0 as well
    np.testing.assert_allclose(float(m2.reshape(-1)[0]), 1.0, atol=1e-6)


def test_yolov3_padding_gt_does_not_clobber_real_gt():
    """regression: a zero padding gt row used to scatter a stale value over
    a real gt's objectness score at cell (0, 0)/anchor 0."""
    rng = np.random.RandomState(2)
    n, h, w, C = 1, 4, 4, 2
    m = len(MASK)
    x = rng.randn(n, m * (5 + C), h, w).astype("f4") * 0.3
    # real gt centered in cell (0, 0), sized to match anchor 0 exactly
    gt_box = np.zeros((n, 2, 4), "f4")
    gt_box[0, 0] = [0.1, 0.1, 10 / 32.0, 13 / 32.0]
    gt_label = np.zeros((n, 2), "int32")

    expect = _np_yolo_loss(x, gt_box, gt_label, ANCHORS, MASK, C, 0.7, 8, True)

    def build():
        xv = fluid.layers.data("x", [m * (5 + C), h, w], dtype="float32")
        gb = fluid.layers.data("gb", [2, 4], dtype="float32")
        gl = fluid.layers.data("gl", [2], dtype="int32")
        loss = fluid.layers.yolov3_loss(xv, gb, gl, ANCHORS, MASK, C,
                                        ignore_thresh=0.7, downsample_ratio=8)
        return [loss]

    (got,) = _run_prog(build, {"x": x, "gb": gt_box, "gl": gt_label})
    np.testing.assert_allclose(got.reshape(-1), expect, rtol=2e-4, atol=2e-4)


def test_rpn_target_assign_without_im_info():
    anchors = _grid_anchors()
    gt = np.array([[[6, 6, 26, 26]]], "f4")

    def build():
        av = fluid.layers.data("a", [4], dtype="float32")
        gv = fluid.layers.data("g", [1, 4], dtype="float32")
        bp = fluid.layers.data("bp", [16, 4], dtype="float32")
        cl = fluid.layers.data("cl", [16, 1], dtype="float32")
        rets = fluid.layers.rpn_target_assign(
            bp, cl, av, None, gv, rpn_batch_size_per_im=8, use_random=False)
        return [rets[2], rets[5]]

    label, score_w = _run_prog(build, {
        "a": anchors, "g": gt,
        "bp": np.zeros((1, 16, 4), "f4"), "cl": np.zeros((1, 16, 1), "f4")})
    assert label.sum() >= 1 and score_w.sum() <= 8


def test_roi_pool_argmax_golden():
    """Argmax holds the flat h*W+w index of each bin's max (reference
    roi_pool_op.h records it for the backward; here it's an output-parity
    check — autodiff owns the gradient)."""
    rng = np.random.RandomState(13)
    x = rng.randn(1, 2, 6, 6).astype("f4")
    rois = np.array([[0, 0, 5, 5]], "f4")

    def build():
        xv = fluid.layers.data("x", [2, 6, 6], dtype="float32")
        rv = fluid.layers.data("rois", [4], dtype="float32")
        out = fluid.layers.roi_pool(xv, rv, 2, 2, 1.0)
        prog = fluid.default_main_program()
        argmax_name = [o for o in prog.global_block().ops
                       if o.type == "roi_pool"][0].output("Argmax")[0]
        return [out, argmax_name]

    out, arg = _run_prog(build, {"x": x, "rois": rois})
    H = W = 6
    for c in range(2):
        for i in range(2):
            for j in range(2):
                flat = int(arg[0, c, i, j])
                assert x[0, c, flat // W, flat % W] == out[0, c, i, j]


def test_ssd_end_to_end_trains():
    """multi_box_head + ssd_loss assemble a small SSD that trains to
    decreasing loss; detection_output emits padded static detections
    (r3 review #4's end-to-end gate for the SSD path)."""
    rng = np.random.RandomState(7)
    main, startup = fluid.Program(), fluid.Program()
    startup.random_seed = 13
    with fluid.program_guard(main, startup):
        img = fluid.layers.data("img", [3, 32, 32], dtype="float32")
        gb = fluid.layers.data("gb", [2, 4], dtype="float32")
        gl = fluid.layers.data("gl", [2], dtype="int32")
        c1 = fluid.layers.conv2d(img, 8, 3, stride=2, padding=1, act="relu")
        c2 = fluid.layers.conv2d(c1, 16, 3, stride=2, padding=1, act="relu")
        locs, confs, boxes, variances = fluid.layers.multi_box_head(
            [c1, c2], img, base_size=32, num_classes=4,
            aspect_ratios=[[1.0], [1.0, 2.0]],
            min_sizes=[8.0, 16.0], max_sizes=[16.0, 28.0], clip=True)
        loss = fluid.layers.mean(fluid.layers.ssd_loss(
            locs, confs, gb, gl, boxes, variances))
        fluid.optimizer.Adam(2e-3).minimize(loss)
    infer_prog = main.clone(for_test=True)
    with fluid.program_guard(infer_prog):
        blk = infer_prog.global_block()
        nmsed = fluid.layers.detection_output(
            blk.var(locs.name), blk.var(confs.name), blk.var(boxes.name),
            blk.var(variances.name), keep_top_k=10)
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    exe.run(startup, scope=scope)
    n = 4
    imgs = rng.rand(n, 3, 32, 32).astype("f4")
    gt = rng.uniform(0.1, 0.6, (n, 2, 4)).astype("f4")
    gt[:, :, 2:] = gt[:, :, :2] + rng.uniform(0.2, 0.4, (n, 2, 2))
    gt = np.clip(gt, 0, 1)
    labels = rng.randint(1, 4, (n, 2)).astype("int32")  # 0 = background
    feed = {"img": imgs, "gb": gt, "gl": labels}
    losses = []
    for _ in range(40):
        (lv,) = exe.run(main, feed=feed, fetch_list=[loss], scope=scope)
        losses.append(float(np.asarray(lv).reshape(-1)[0]))
    assert np.isfinite(losses).all()
    assert losses[-1] < losses[0] * 0.7, (losses[0], losses[-1])
    (det,) = exe.run(infer_prog, feed=feed, fetch_list=[nmsed], scope=scope)
    det = np.asarray(det)
    assert det.shape == (n, 10, 6)


def test_retinanet_target_assign_and_focal_training():
    """RetinaNet assignment rules + a focal-loss head training end-to-end
    (class targets, no subsampling, fg_num normalizer)."""
    anchors = _grid_anchors()
    gt = np.array([[[6, 6, 26, 26], [40, 40, 60, 60]]], "f4")
    gt_lab = np.array([[1, 2]], "int32")

    main, startup = fluid.Program(), fluid.Program()
    startup.random_seed = 3
    with fluid.program_guard(main, startup):
        av = fluid.layers.data("a", [4], dtype="float32")
        gv = fluid.layers.data("g", [2, 4], dtype="float32")
        lv = fluid.layers.data("gl", [2], dtype="int32")
        bp = fluid.layers.data("bp", [16, 4], dtype="float32")
        cl = fluid.layers.data("cl", [16, 3], dtype="float32")
        rets = fluid.layers.retinanet_target_assign(
            bp, cl, av, None, gv, lv, positive_overlap=0.5,
            negative_overlap=0.4)
        _, _, label, tgt, inw, fg_num, score_w = rets
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    exe.run(startup, scope=scope)
    feed = {"a": anchors, "g": gt, "gl": gt_lab,
            "bp": np.zeros((1, 16, 4), "f4"), "cl": np.zeros((1, 16, 3), "f4")}
    lab, t, w_in, fg, sw = exe.run(
        main, feed=feed, fetch_list=[label, tgt, inw, fg_num, score_w],
        scope=scope)
    lab = np.asarray(lab)[0]
    # best anchors carry the gt CLASS labels
    assert set(lab[lab > 0].tolist()) == {1, 2}
    assert int(np.asarray(fg).reshape(-1)[0]) == (lab > 0).sum() + 1
    # no subsampling: every anchor is fg or bg or ignored, none dropped
    sw = np.asarray(sw)[0]
    assert ((lab == -1) == (sw == 0)).all()


def test_retinanet_detection_output_shapes():
    rng = np.random.RandomState(11)
    anchors = _grid_anchors()  # [16, 4]

    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        b1 = fluid.layers.data("b1", [16, 4], dtype="float32")
        s1 = fluid.layers.data("s1", [16, 3], dtype="float32")
        av = fluid.layers.data("a", [4], dtype="float32")
        im = fluid.layers.data("im", [3], dtype="float32")
        out = fluid.layers.retinanet_detection_output(
            [b1], [s1], [av], im, keep_top_k=5, score_threshold=0.0)
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    exe.run(startup, scope=scope)
    (det,) = exe.run(main, feed={
        "b1": rng.randn(2, 16, 4).astype("f4") * 0.1,
        "s1": rng.randn(2, 16, 3).astype("f4"),
        "a": anchors, "im": np.array([[64, 64, 1.0]] * 2, "f4")},
        fetch_list=[out], scope=scope)
    det = np.asarray(det)
    assert det.shape == (2, 5, 6)
    valid = det[det[:, :, 0] >= 0]
    assert np.isfinite(valid).all()


def test_generate_proposal_labels_and_faster_rcnn_stage2():
    """proposals + gts sampled into a fixed-size RoI batch with per-class
    regression targets; a stage-2 head (roi_pool -> fc) trains on them —
    the Faster-RCNN assembly gate."""
    rng = np.random.RandomState(12)
    R, C = 16, 3
    main, startup = fluid.Program(), fluid.Program()
    startup.random_seed = 21
    with fluid.program_guard(main, startup):
        feat = fluid.layers.data("feat", [4, 16, 16], dtype="float32")
        props = fluid.layers.data("props", [12, 4], dtype="float32")
        gcls = fluid.layers.data("gcls", [2], dtype="int32")
        gbox = fluid.layers.data("gbox", [2, 4], dtype="float32")
        rois, labels, tgt, inw, outw, sw = fluid.layers.generate_proposal_labels(
            props, gcls, None, gbox, batch_size_per_im=R, fg_thresh=0.5,
            class_nums=C, use_random=False)
        flat_rois = fluid.layers.reshape(rois, [-1, 4])
        pooled = fluid.layers.roi_pool(feat, flat_rois, 4, 4,
                                       spatial_scale=0.25)
        fcin = fluid.layers.reshape(pooled, [-1, 4 * 16])
        cls_logits = fluid.layers.fc(fcin, C)
        flat_lab = fluid.layers.reshape(labels, [-1, 1])
        ce = fluid.layers.softmax_with_cross_entropy(
            cls_logits, fluid.layers.cast(flat_lab, "int64"))
        w = fluid.layers.reshape(sw, [-1, 1])
        loss = fluid.layers.reduce_sum(ce * w) / (fluid.layers.reduce_sum(w) + 1.0)
        fluid.optimizer.Adam(1e-3).minimize(loss)
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    exe.run(startup, scope=scope)
    props_v = rng.uniform(0, 40, (1, 12, 4)).astype("f4")
    props_v[..., 2:] = props_v[..., :2] + rng.uniform(8, 20, (1, 12, 2))
    gt_v = np.array([[[4, 4, 20, 20], [30, 30, 50, 50]]], "f4")
    feed = {"feat": rng.rand(1, 4, 16, 16).astype("f4"),
            "props": props_v, "gcls": np.array([[1, 2]], "int32"),
            "gbox": gt_v}
    out = exe.run(main, feed=feed,
                  fetch_list=[rois, labels, tgt, inw, sw, loss], scope=scope)
    rois_v, lab_v, tgt_v, inw_v, sw_v, _ = [np.asarray(o) for o in out]
    assert rois_v.shape == (1, R, 4) and lab_v.shape == (1, R)
    assert tgt_v.shape == (1, R, 4 * C)
    # the gt boxes themselves are fg candidates, so fg exists with class 1/2
    assert set(lab_v[0][lab_v[0] > 0].tolist()) <= {1, 2}
    assert (lab_v[0] > 0).sum() >= 2
    # inside weights fire exactly on the label's 4-col block for fg rows
    fg_rows = np.where(lab_v[0] > 0)[0]
    for r in fg_rows[:3]:
        c = lab_v[0, r]
        blk = inw_v[0, r].reshape(C, 4)
        assert (blk[c] == 1).all() and blk.sum() == 4
    losses = []
    for _ in range(20):
        (lv,) = exe.run(main, feed=feed, fetch_list=[loss], scope=scope)
        losses.append(float(np.asarray(lv).reshape(-1)[0]))
    assert np.isfinite(losses).all() and losses[-1] < losses[0]


def test_fpn_distribute_and_collect():
    rng = np.random.RandomState(13)
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        rois = fluid.layers.data("rois", [8, 4], dtype="float32")
        flat = fluid.layers.reshape(rois, [-1, 4])
        multi_rois, restore, masks = fluid.layers.distribute_fpn_proposals(
            flat, 2, 5, 4, 224)
        r1 = fluid.layers.data("r1", [6, 4], dtype="float32")
        s1 = fluid.layers.data("s1", [6, 1], dtype="float32")
        r2 = fluid.layers.data("r2", [6, 4], dtype="float32")
        s2 = fluid.layers.data("s2", [6, 1], dtype="float32")
        fs1 = fluid.layers.reshape(s1, [0, -1])
        fs2 = fluid.layers.reshape(s2, [0, -1])
        collected = fluid.layers.collect_fpn_proposals(
            [r1, r2], [fs1, fs2], 2, 5, post_nms_top_n=5)
        fetches = masks + [collected]
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    exe.run(startup, scope=scope)
    # sizes chosen to land on distinct levels: 224 -> level 4
    sizes = [16, 32, 64, 112, 224, 224, 448, 900]
    rois_v = np.zeros((1, 8, 4), "f4")
    for i, s in enumerate(sizes):
        rois_v[0, i] = [0, 0, s - 1, s - 1]  # +1-offset area convention
    r1v = rng.uniform(0, 50, (1, 6, 4)).astype("f4")
    r2v = rng.uniform(0, 50, (1, 6, 4)).astype("f4")
    s1v = rng.rand(1, 6, 1).astype("f4")
    s2v = rng.rand(1, 6, 1).astype("f4")
    out = exe.run(main, feed={"rois": rois_v, "r1": r1v, "s1": s1v,
                              "r2": r2v, "s2": s2v},
                  fetch_list=fetches, scope=scope)
    m = [np.asarray(o) for o in out[:4]]
    # every roi routed to exactly one level
    total = sum(mm for mm in m)
    np.testing.assert_allclose(total, np.ones(8), atol=1e-6)
    # small rois to low levels, big to high
    assert m[0][0] == 1.0 and m[3][-1] == 1.0
    col = np.asarray(out[4])[0]
    assert col.shape == (5, 4)
    # collected rois are the 5 highest-scoring across both levels
    all_s = np.concatenate([s1v.reshape(-1), s2v.reshape(-1)])
    all_r = np.concatenate([r1v.reshape(-1, 4), r2v.reshape(-1, 4)])
    expect = all_r[np.argsort(-all_s)[:5]]
    np.testing.assert_allclose(col, expect, rtol=1e-6)


def test_box_decoder_and_assign_golden():
    prior = np.array([[0, 0, 9, 9]], "f4")
    deltas = np.zeros((1, 8), "f4")  # C=2, zero deltas decode to the prior
    score = np.array([[0.1, 0.9]], "f4")
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        pv = fluid.layers.data("p", [4], dtype="float32")
        dv = fluid.layers.data("d", [8], dtype="float32")
        sv = fluid.layers.data("s", [2], dtype="float32")
        dec, asg = fluid.layers.box_decoder_and_assign(pv, [0.1, 0.1, 0.2, 0.2],
                                                       dv, sv)
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    exe.run(startup, scope=scope)
    d_out, a_out = exe.run(main, feed={"p": prior, "d": deltas, "s": score},
                           fetch_list=[dec, asg], scope=scope)
    np.testing.assert_allclose(np.asarray(a_out)[0], [0, 0, 9, 9], atol=1e-4)
    np.testing.assert_allclose(np.asarray(d_out)[0].reshape(2, 4)[1],
                               [0, 0, 9, 9], atol=1e-4)


def test_generate_mask_labels_square_polygon():
    """a square polygon rasterizes to a filled block in the matched fg
    roi's class slice."""
    rois = np.array([[[0, 0, 8, 8], [20, 20, 28, 28]]], "f4")
    labels = np.array([[2, 0]], "int32")  # roi 0 fg class 2, roi 1 bg
    # polygon covering the left half of roi 0: x in [0, 4], y in [0, 8]
    segms = np.array([[[[0, 0], [4, 0], [4, 8], [0, 8]]]], "f4")

    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        rv = fluid.layers.data("r", [2, 4], dtype="float32")
        lv = fluid.layers.data("l", [2], dtype="int32")
        sv = fluid.layers.data("s", [1, 4, 2], dtype="float32")
        mask_rois, has, masks = fluid.layers.generate_mask_labels(
            None, None, None, sv, rv, lv, num_classes=3, resolution=4)
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    exe.run(startup, scope=scope)
    hv, mv = exe.run(main, feed={"r": rois, "l": labels, "s": segms},
                     fetch_list=[has, masks], scope=scope)
    hv, mv = np.asarray(hv), np.asarray(mv)
    assert hv[0].tolist() == [1, 0]
    m = mv[0, 0].reshape(3, 4, 4)
    assert (m[0] == 0).all() and (m[1] == 0).all()  # only class 2 block
    # left half of the roi (columns 0-1 at res 4) filled, right half empty
    assert (m[2][:, :2] == 1).all() and (m[2][:, 2:] == 0).all()
