"""Detection op family (reference: operators/detection/, 15.3k LoC CUDA/C++).

TPU-first subset of the most-used ops: SSD anchors (prior_box), box
encode/decode (box_coder), IoU (iou_similarity), YOLOv3 head decode
(yolo_box), and a STATIC-SHAPE multiclass NMS — the reference emits
LoD-shaped variable-length detections (multiclass_nms_op.cc); XLA wants
fixed shapes, so nms returns a padded [keep_top_k, 6] block per image with
label -1 in empty slots, the standard accelerator-native formulation.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from ..core.registry import register_op
from .common import first


def expand_aspect_ratios(input_ars, flip):
    """Dedup + flip expansion of prior_box aspect ratios (reference
    prior_box_op.h ExpandAspectRatios); shared with multi_box_head's
    prior-count computation."""
    ars = [1.0]
    for ar in input_ars:
        if any(abs(ar - a) < 1e-6 for a in ars):
            continue
        ars.append(ar)
        if flip:
            ars.append(1.0 / ar)
    return ars


@register_op("prior_box")
def _prior_box(ctx, op, ins):
    """reference detection/prior_box_op.h (loop at :100): SSD anchors per
    feature-map cell.  Everything is static (shapes+attrs), so the boxes
    are computed in numpy at trace time and constant-folded by XLA."""
    feat = first(ins, "Input")    # [N, C, H, W]
    image = first(ins, "Image")   # [N, C, IH, IW]
    H, W = feat.shape[2], feat.shape[3]
    IH, IW = image.shape[2], image.shape[3]
    min_sizes = list(op.attr("min_sizes"))
    max_sizes = list(op.attr("max_sizes", []) or [])
    input_ars = list(op.attr("aspect_ratios", [1.0]))
    variances = list(op.attr("variances", [0.1, 0.1, 0.2, 0.2]))
    flip = op.attr("flip", False)
    clip = op.attr("clip", False)
    step_w = op.attr("step_w", 0.0) or IW / W
    step_h = op.attr("step_h", 0.0) or IH / H
    offset = op.attr("offset", 0.5)
    mmar_order = op.attr("min_max_aspect_ratios_order", False)

    ars = expand_aspect_ratios(input_ars, flip)

    boxes = []
    for h in range(H):
        for w in range(W):
            cx = (w + offset) * step_w
            cy = (h + offset) * step_h
            cell = []

            def emit(bw, bh):
                cell.append([(cx - bw) / IW, (cy - bh) / IH,
                             (cx + bw) / IW, (cy + bh) / IH])

            for s, ms in enumerate(min_sizes):
                if mmar_order:
                    emit(ms / 2.0, ms / 2.0)
                    if max_sizes:
                        sq = math.sqrt(ms * max_sizes[s]) / 2.0
                        emit(sq, sq)
                    for ar in ars:
                        if abs(ar - 1.0) < 1e-6:
                            continue
                        emit(ms * math.sqrt(ar) / 2.0, ms / math.sqrt(ar) / 2.0)
                else:
                    for ar in ars:
                        emit(ms * math.sqrt(ar) / 2.0, ms / math.sqrt(ar) / 2.0)
                    if max_sizes:
                        sq = math.sqrt(ms * max_sizes[s]) / 2.0
                        emit(sq, sq)
            boxes.append(cell)
    num_priors = len(boxes[0])
    out = np.asarray(boxes, dtype=np.float32).reshape(H, W, num_priors, 4)
    if clip:
        out = np.clip(out, 0.0, 1.0)
    var = np.tile(np.asarray(variances, np.float32), (H, W, num_priors, 1))
    return {"Boxes": jnp.asarray(out), "Variances": jnp.asarray(var)}


@register_op("iou_similarity")
def _iou_similarity(ctx, op, ins):
    """reference detection/iou_similarity_op.h: pairwise IoU [N,4]x[M,4]."""
    x = first(ins, "X")
    y = first(ins, "Y")
    norm = op.attr("box_normalized", True)
    one = 0.0 if norm else 1.0
    ax = (x[:, 2] - x[:, 0] + one) * (x[:, 3] - x[:, 1] + one)
    ay = (y[:, 2] - y[:, 0] + one) * (y[:, 3] - y[:, 1] + one)
    lt = jnp.maximum(x[:, None, :2], y[None, :, :2])
    rb = jnp.minimum(x[:, None, 2:], y[None, :, 2:])
    wh = jnp.maximum(rb - lt + one, 0.0)
    inter = wh[..., 0] * wh[..., 1]
    union = ax[:, None] + ay[None, :] - inter
    return {"Out": jnp.where(union > 0, inter / union, 0.0)}


def _decode_center_size(prior, prior_var, target, norm, axis=0):
    pw = prior[:, 2] - prior[:, 0] + (0.0 if norm else 1.0)
    ph = prior[:, 3] - prior[:, 1] + (0.0 if norm else 1.0)
    pcx = prior[:, 0] + pw * 0.5
    pcy = prior[:, 1] + ph * 0.5
    # target [N, M?, 4] broadcasting over priors on `axis`
    tcx = target[..., 0] * prior_var[:, 0] * pw + pcx
    tcy = target[..., 1] * prior_var[:, 1] * ph + pcy
    tw = jnp.exp(prior_var[:, 2] * target[..., 2]) * pw
    th = jnp.exp(prior_var[:, 3] * target[..., 3]) * ph
    return jnp.stack([tcx - tw / 2, tcy - th / 2,
                      tcx + tw / 2 - (0.0 if norm else 1.0),
                      tcy + th / 2 - (0.0 if norm else 1.0)], axis=-1)


@register_op("box_coder")
def _box_coder(ctx, op, ins):
    """reference detection/box_coder_op.h: encode/decode center-size."""
    prior = first(ins, "PriorBox")       # [N, 4]
    pvar = ins.get("PriorBoxVar")
    target = first(ins, "TargetBox")
    code_type = op.attr("code_type", "encode_center_size")
    norm = op.attr("box_normalized", True)
    if pvar:
        prior_var = pvar[0]
    else:
        prior_var = jnp.ones((prior.shape[0], 4), prior.dtype)
    one = 0.0 if norm else 1.0
    pw = prior[:, 2] - prior[:, 0] + one
    ph = prior[:, 3] - prior[:, 1] + one
    pcx = prior[:, 0] + pw * 0.5
    pcy = prior[:, 1] + ph * 0.5
    if code_type.startswith("encode"):
        # target [M, 4] vs priors [N, 4] -> [M, N, 4]
        tw = target[:, 2] - target[:, 0] + one
        th = target[:, 3] - target[:, 1] + one
        tcx = target[:, 0] + tw * 0.5
        tcy = target[:, 1] + th * 0.5
        dx = (tcx[:, None] - pcx[None, :]) / pw[None, :] / prior_var[None, :, 0]
        dy = (tcy[:, None] - pcy[None, :]) / ph[None, :] / prior_var[None, :, 1]
        dw = jnp.log(tw[:, None] / pw[None, :]) / prior_var[None, :, 2]
        dh = jnp.log(th[:, None] / ph[None, :]) / prior_var[None, :, 3]
        return {"OutputBox": jnp.stack([dx, dy, dw, dh], axis=-1)}
    # decode: target [N, 4] (or batched [B, N, 4]) deltas against priors
    # [N, 4]; prior dims broadcast over the leading batch axis
    if op.attr("axis", 0) != 0 or target.ndim not in (2, 3):
        raise NotImplementedError(
            "box_coder decode: axis=0 with 2-D or 3-D targets only")
    return {"OutputBox": _decode_center_size(prior, prior_var, target, norm)}


@register_op("yolo_box")
def _yolo_box(ctx, op, ins):
    """reference detection/yolo_box_op.h: decode a YOLOv3 head."""
    x = first(ins, "X")               # [N, A*(5+C), H, W]
    img_size = first(ins, "ImgSize")  # [N, 2] (h, w)
    anchors = list(op.attr("anchors"))
    class_num = op.attr("class_num")
    conf_thresh = op.attr("conf_thresh", 0.01)
    downsample = op.attr("downsample_ratio", 32)
    A = len(anchors) // 2
    N, _, H, W = x.shape
    x = x.reshape(N, A, 5 + class_num, H, W)
    grid_x = jnp.arange(W).reshape(1, 1, 1, W)
    grid_y = jnp.arange(H).reshape(1, 1, H, 1)
    bx = (jax.nn.sigmoid(x[:, :, 0]) + grid_x) / W
    by = (jax.nn.sigmoid(x[:, :, 1]) + grid_y) / H
    aw = jnp.asarray(anchors[0::2], x.dtype).reshape(1, A, 1, 1)
    ah = jnp.asarray(anchors[1::2], x.dtype).reshape(1, A, 1, 1)
    input_w = downsample * W
    input_h = downsample * H
    bw = jnp.exp(x[:, :, 2]) * aw / input_w
    bh = jnp.exp(x[:, :, 3]) * ah / input_h
    conf = jax.nn.sigmoid(x[:, :, 4])
    probs = jax.nn.sigmoid(x[:, :, 5:]) * conf[:, :, None]
    # below-threshold detections are zeroed (reference sets score 0)
    probs = jnp.where(conf[:, :, None] >= conf_thresh, probs, 0.0)
    imgh = img_size[:, 0].reshape(N, 1, 1, 1).astype(x.dtype)
    imgw = img_size[:, 1].reshape(N, 1, 1, 1).astype(x.dtype)
    x0 = (bx - bw / 2) * imgw
    y0 = (by - bh / 2) * imgh
    x1 = (bx + bw / 2) * imgw
    y1 = (by + bh / 2) * imgh
    boxes = jnp.stack([x0, y0, x1, y1], axis=-1).reshape(N, A * H * W, 4)
    scores = probs.transpose(0, 1, 3, 4, 2).reshape(N, A * H * W, class_num)
    return {"Boxes": boxes, "Scores": scores}


def _nms_single_class(boxes, scores, iou_threshold, top_k, normalized=True):
    """Static-shape greedy NMS over the top_k candidates only (reference
    multiclass_nms pre-selects nms_top_k before suppression — also keeps
    the IoU matrix at O(top_k^2) instead of O(M^2))."""
    n = min(top_k, boxes.shape[0])
    k = n
    order = jnp.argsort(-scores)[:n]
    b = boxes[order]
    s = scores[order]
    one = 0.0 if normalized else 1.0
    lt = jnp.maximum(b[:, None, :2], b[None, :, :2])
    rb = jnp.minimum(b[:, None, 2:], b[None, :, 2:])
    wh = jnp.maximum(rb - lt + one, 0.0)
    inter = wh[..., 0] * wh[..., 1]
    area = jnp.maximum((b[:, 2] - b[:, 0] + one) * (b[:, 3] - b[:, 1] + one), 0.0)
    union = area[:, None] + area[None, :] - inter
    iou = jnp.where(union > 0, inter / union, 0.0)

    def body(i, keep):
        # suppressed if any higher-ranked KEPT box overlaps too much
        mask = (jnp.arange(n) < i) & keep & (iou[i] > iou_threshold)
        return keep.at[i].set(~jnp.any(mask))

    keep = jax.lax.fori_loop(0, n, body, jnp.ones((n,), bool))
    kept_scores = jnp.where(keep, s, -1.0)
    sel = jnp.argsort(-kept_scores)[:k]
    valid = kept_scores[sel] > 0
    return b[sel], jnp.where(valid, s[sel], -1.0)


@register_op("multiclass_nms")
def _multiclass_nms(ctx, op, ins):
    """Static-shape multiclass NMS (reference multiclass_nms_op.cc emits a
    variable-length LoD result; here each image yields a padded
    [keep_top_k, 6] block (label, score, x0, y0, x1, y1) with label -1 in
    empty slots — the accelerator-native fixed-size formulation)."""
    bboxes = first(ins, "BBoxes")   # [N, M, 4]
    scores = first(ins, "Scores")   # [N, C, M]
    score_threshold = op.attr("score_threshold", 0.0)
    nms_top_k = op.attr("nms_top_k", 64)
    keep_top_k = op.attr("keep_top_k", 100)
    nms_threshold = op.attr("nms_threshold", 0.3)
    background_label = op.attr("background_label", 0)
    normalized = op.attr("normalized", True)
    N, C, M = scores.shape
    if nms_top_k < 0:
        nms_top_k = M
    n_classes_kept = C - (1 if 0 <= background_label < C else 0)
    if keep_top_k < 0:  # reference: -1 keeps everything
        keep_top_k = n_classes_kept * min(nms_top_k, M)

    def per_image(box, sc):
        outs = []
        for c in range(C):
            if c == background_label:
                continue
            s = jnp.where(sc[c] >= score_threshold, sc[c], -1.0)
            bb, ss = _nms_single_class(box, s, nms_threshold, min(nms_top_k, M),
                                       normalized=normalized)
            lab = jnp.where(ss > 0, float(c), -1.0)
            outs.append(jnp.concatenate([lab[:, None], ss[:, None], bb], axis=1))
        allc = jnp.concatenate(outs, axis=0)
        order = jnp.argsort(-allc[:, 1])[:keep_top_k]
        picked = allc[order]
        pad = keep_top_k - picked.shape[0]
        if pad > 0:
            picked = jnp.concatenate(
                [picked, jnp.full((pad, 6), -1.0, picked.dtype)], axis=0)
        return picked

    out = jax.vmap(per_image)(bboxes, scores)
    return {"Out": out}


@register_op("roi_align")
def _roi_align(ctx, op, ins):
    """reference detection/roi_align_op: average of bilinear samples per
    output bin.  ROIs are dense [R, 4] plus a batch-index vector RoisLod
    replaces the reference's LoD (static-shape form)."""
    x = first(ins, "X")                   # [N, C, H, W]
    rois = first(ins, "ROIs")             # [R, 4] (x0, y0, x1, y1)
    batch_idx = ins.get("RoisBatch")      # [R] batch indices (dense LoD stand-in)
    batch_idx = (batch_idx[0].reshape(-1).astype(jnp.int32)
                 if batch_idx else jnp.zeros((rois.shape[0],), jnp.int32))
    ph = op.attr("pooled_height", 1)
    pw = op.attr("pooled_width", 1)
    scale = op.attr("spatial_scale", 1.0)
    ratio = op.attr("sampling_ratio", -1)
    # sampling_ratio <= 0: the reference uses an adaptive
    # ceil(roi_size/pooled) grid, which is not jittable (data-dependent
    # size); a fixed 2x2 grid per bin is the documented static stand-in —
    # pass an explicit sampling_ratio for reference-exact sampling density.
    n_samples = ratio if ratio > 0 else 2
    H, W = x.shape[2], x.shape[3]

    def bilinear(img, y, xq):
        # reference roi_align_op.h: samples below -1 or beyond size are
        # zero; [-1, 0] clamps to the border
        valid = (y >= -1.0) & (y <= H) & (xq >= -1.0) & (xq <= W)
        y = jnp.clip(y, 0.0, H - 1.0)
        xq = jnp.clip(xq, 0.0, W - 1.0)
        y0 = jnp.clip(jnp.floor(y).astype(jnp.int32), 0, H - 1)
        x0 = jnp.clip(jnp.floor(xq).astype(jnp.int32), 0, W - 1)
        y1 = jnp.clip(y0 + 1, 0, H - 1)
        x1 = jnp.clip(x0 + 1, 0, W - 1)
        wy = y - y0
        wx = xq - x0
        v00 = img[:, y0, x0]
        v01 = img[:, y0, x1]
        v10 = img[:, y1, x0]
        v11 = img[:, y1, x1]
        out = ((v00 * (1 - wx) + v01 * wx) * (1 - wy)
               + (v10 * (1 - wx) + v11 * wx) * wy)
        return jnp.where(valid[None, :], out, 0.0)

    def one_roi(roi, bi):
        img = x[bi]  # [C, H, W]
        rx0, ry0, rx1, ry1 = roi[0] * scale, roi[1] * scale, roi[2] * scale, roi[3] * scale
        rw = jnp.maximum(rx1 - rx0, 1.0)
        rh = jnp.maximum(ry1 - ry0, 1.0)
        bin_h = rh / ph
        bin_w = rw / pw
        # sample grid: n_samples x n_samples per bin
        iy = (jnp.arange(ph)[:, None, None, None]
              * bin_h + (jnp.arange(n_samples)[None, :, None, None] + 0.5)
              * bin_h / n_samples + ry0)
        ix = (jnp.arange(pw)[None, None, :, None]
              * bin_w + (jnp.arange(n_samples)[None, None, None, :] + 0.5)
              * bin_w / n_samples + rx0)
        ys = jnp.broadcast_to(iy, (ph, n_samples, pw, n_samples)).reshape(-1)
        xs = jnp.broadcast_to(ix, (ph, n_samples, pw, n_samples)).reshape(-1)
        vals = bilinear(img, ys, xs)  # [C, ph*ns*pw*ns]
        vals = vals.reshape(x.shape[1], ph, n_samples, pw, n_samples)
        return jnp.mean(vals, axis=(2, 4))  # [C, ph, pw]

    out = jax.vmap(one_roi)(rois, batch_idx)
    return {"Out": out}


@register_op("sigmoid_focal_loss")
def _sigmoid_focal_loss(ctx, op, ins):
    """reference detection/sigmoid_focal_loss_op: per-class focal loss over
    logits [N, C], labels [N, 1] in 0..C (0 = background), FgNum
    normalizer."""
    x = first(ins, "X")
    label = first(ins, "Label").reshape(-1)
    fg = first(ins, "FgNum")
    gamma = op.attr("gamma", 2.0)
    alpha = op.attr("alpha", 0.25)
    C = x.shape[1]
    # one-hot target over classes 1..C mapped to columns 0..C-1;
    # label -1 = ignore (reference kernel masks both loss terms)
    t = (label[:, None] == (jnp.arange(C)[None, :] + 1)).astype(x.dtype)
    p = jax.nn.sigmoid(x)
    ce = jnp.maximum(x, 0) - x * t + jnp.log1p(jnp.exp(-jnp.abs(x)))
    p_t = p * t + (1 - p) * (1 - t)
    a_t = alpha * t + (1 - alpha) * (1 - t)
    loss = a_t * ((1 - p_t) ** gamma) * ce
    loss = jnp.where((label >= 0)[:, None], loss, 0.0)
    norm = jnp.maximum(fg.reshape(()).astype(x.dtype), 1.0)
    return {"Out": loss / norm}


@register_op("anchor_generator")
def _anchor_generator(ctx, op, ins):
    """reference detection/anchor_generator_op.h:53-84, formula-exact:
    x_ctr = w*stride + offset*(stride-1); base_w = round(sqrt(area/ar)),
    base_h = round(base_w*ar) (ar = height/width), scaled by
    anchor_size/stride; extents are +/-0.5*(anchor_size_px - 1)."""
    feat = first(ins, "Input")
    H, W = feat.shape[2], feat.shape[3]
    sizes = list(op.attr("anchor_sizes"))
    ratios = list(op.attr("aspect_ratios"))
    variances = list(op.attr("variances", [0.1, 0.1, 0.2, 0.2]))
    stride = list(op.attr("stride"))
    offset = op.attr("offset", 0.5)
    sw, sh = float(stride[0]), float(stride[1])
    anchors = []
    for h in range(H):
        for w in range(W):
            x_ctr = w * sw + offset * (sw - 1)
            y_ctr = h * sh + offset * (sh - 1)
            cell = []
            for ar in ratios:
                for size in sizes:
                    area = sw * sh
                    base_w = round(math.sqrt(area / ar))
                    base_h = round(base_w * ar)
                    aw = (size / sw) * base_w
                    ah = (size / sh) * base_h
                    cell.append([x_ctr - 0.5 * (aw - 1), y_ctr - 0.5 * (ah - 1),
                                 x_ctr + 0.5 * (aw - 1), y_ctr + 0.5 * (ah - 1)])
            anchors.append(cell)
    A = len(ratios) * len(sizes)
    out = np.asarray(anchors, np.float32).reshape(H, W, A, 4)
    var = np.tile(np.asarray(variances, np.float32), (H, W, A, 1))
    return {"Anchors": jnp.asarray(out), "Variances": jnp.asarray(var)}


@register_op("box_clip")
def _box_clip(ctx, op, ins):
    """reference detection/box_clip_op.h over bbox_util.h ClipTiledBoxes:
    boxes live in ORIGINAL-image coordinates, so the bound is
    round(im_info/scale) - 1."""
    boxes = first(ins, "Input")      # [..., 4]
    im_info = first(ins, "ImInfo")   # [N, 3] (resized h, resized w, scale)
    h = jnp.round(im_info[:, 0] / im_info[:, 2]) - 1.0
    w = jnp.round(im_info[:, 1] / im_info[:, 2]) - 1.0
    bshape = (-1,) + (1,) * (boxes.ndim - 2)
    x0 = jnp.clip(boxes[..., 0], 0.0, w.reshape(bshape))
    y0 = jnp.clip(boxes[..., 1], 0.0, h.reshape(bshape))
    x1 = jnp.clip(boxes[..., 2], 0.0, w.reshape(bshape))
    y1 = jnp.clip(boxes[..., 3], 0.0, h.reshape(bshape))
    return {"Output": jnp.stack([x0, y0, x1, y1], axis=-1)}


@register_op("density_prior_box")
def _density_prior_box(ctx, op, ins):
    """reference detection/density_prior_box_op.h: dense grids of shifted
    square priors per (fixed_size, density)."""
    feat = first(ins, "Input")
    image = first(ins, "Image")
    H, W = feat.shape[2], feat.shape[3]
    IH, IW = image.shape[2], image.shape[3]
    fixed_sizes = list(op.attr("fixed_sizes"))
    fixed_ratios = list(op.attr("fixed_ratios", [1.0]))
    densities = list(op.attr("densities"))
    variances = list(op.attr("variances", [0.1, 0.1, 0.2, 0.2]))
    step_w = op.attr("step_w", 0.0) or IW / W
    step_h = op.attr("step_h", 0.0) or IH / H
    offset = op.attr("offset", 0.5)
    if len(fixed_sizes) != len(densities):
        raise ValueError(
            f"density_prior_box: len(fixed_sizes)={len(fixed_sizes)} must "
            f"equal len(densities)={len(densities)}")
    # reference density_prior_box_op.h:69-110: the density grid spreads over
    # the (integer) step window, and every corner clamps to [0, 1]
    # unconditionally (the clip attr is a redundant second clamp)
    step_average = int((step_w + step_h) * 0.5)
    boxes = []
    for h in range(H):
        for w in range(W):
            cx = (w + offset) * step_w
            cy = (h + offset) * step_h
            cell = []
            for size, density in zip(fixed_sizes, densities):
                shift = step_average // density
                for ratio in fixed_ratios:
                    bw = size * math.sqrt(ratio)
                    bh = size / math.sqrt(ratio)
                    dcx = cx - step_average / 2.0 + shift / 2.0
                    dcy = cy - step_average / 2.0 + shift / 2.0
                    for di in range(density):
                        for dj in range(density):
                            ccx = dcx + dj * shift
                            ccy = dcy + di * shift
                            cell.append([max((ccx - bw / 2.0) / IW, 0.0),
                                         max((ccy - bh / 2.0) / IH, 0.0),
                                         min((ccx + bw / 2.0) / IW, 1.0),
                                         min((ccy + bh / 2.0) / IH, 1.0)])
            boxes.append(cell)
    P_ = len(boxes[0])
    out = np.asarray(boxes, np.float32).reshape(H, W, P_, 4)
    var = np.tile(np.asarray(variances, np.float32), (H, W, P_, 1))
    return {"Boxes": jnp.asarray(out), "Variances": jnp.asarray(var)}


def _cbox_iou(x1, y1, w1, h1, x2, y2, w2, h2):
    """IoU of center-format boxes, broadcasting."""
    inter_w = jnp.maximum(
        jnp.minimum(x1 + w1 / 2, x2 + w2 / 2) - jnp.maximum(x1 - w1 / 2, x2 - w2 / 2), 0.0)
    inter_h = jnp.maximum(
        jnp.minimum(y1 + h1 / 2, y2 + h2 / 2) - jnp.maximum(y1 - h1 / 2, y2 - h2 / 2), 0.0)
    inter = inter_w * inter_h
    return inter / jnp.maximum(w1 * h1 + w2 * h2 - inter, 1e-10)


def _sce(logit, label):
    """sigmoid cross-entropy, the reference's numerically-safe form
    (yolov3_loss_op.h:105 SigmoidCrossEntropy)."""
    return jnp.maximum(logit, 0.0) - logit * label + jnp.log1p(jnp.exp(-jnp.abs(logit)))


@register_op("yolov3_loss")
def _yolov3_loss(ctx, op, ins):
    """YOLOv3 training loss (reference detection/yolov3_loss_op.h:254).

    Same three terms as the reference's per-cell loops, vectorized:
      * ignore mask: decoded pred boxes vs every valid gt, best IoU >
        ignore_thresh drops that cell's objectness loss (matching is under
        stop_gradient, as the reference treats it as constant);
      * per-gt positive assignment: best full-anchor-set IoU on (w, h) at
        the origin picks the anchor; gts whose anchor is outside
        anchor_mask contribute nothing (GTMatchMask = -1);
      * location (SCE on tx/ty, L1 on tw/th, scaled by (2 - w*h) * score),
        label SCE with optional smoothing, and objectness SCE.
    Outputs Loss [n], ObjectnessMask [n, mask, h, w], GTMatchMask [n, b];
    gradients flow to X by autodiff (the reference hand-writes them).
    """
    x = first(ins, "X").astype(jnp.float32)            # [n, m*(5+C), h, w]
    gt_box = first(ins, "GTBox").astype(jnp.float32)   # [n, b, 4] center xywh
    gt_label = first(ins, "GTLabel").astype(jnp.int32) # [n, b]
    if gt_label.ndim == 3:
        gt_label = gt_label[..., 0]
    anchors = list(op.attr("anchors"))
    anchor_mask = list(op.attr("anchor_mask"))
    C = int(op.attr("class_num"))
    ignore_thresh = float(op.attr("ignore_thresh"))
    downsample = int(op.attr("downsample_ratio"))
    smooth = op.attr("use_label_smooth", True)

    n, _, h, w = x.shape
    m = len(anchor_mask)
    an_num = len(anchors) // 2
    b = gt_box.shape[1]
    input_size = downsample * h
    if "GTScore" in ins and ins["GTScore"]:
        gt_score = first(ins, "GTScore").astype(jnp.float32)
        if gt_score.ndim == 3:
            gt_score = gt_score[..., 0]
    else:
        gt_score = jnp.ones((n, b), jnp.float32)

    xr = x.reshape(n, m, 5 + C, h, w)
    tx, ty, tw, th, tobj = xr[:, :, 0], xr[:, :, 1], xr[:, :, 2], xr[:, :, 3], xr[:, :, 4]
    tcls = xr[:, :, 5:]  # [n, m, C, h, w]

    aw = jnp.asarray([anchors[2 * i] for i in anchor_mask], jnp.float32)
    ah = jnp.asarray([anchors[2 * i + 1] for i in anchor_mask], jnp.float32)
    grid_x = jnp.arange(w, dtype=jnp.float32)[None, None, None, :]
    grid_y = jnp.arange(h, dtype=jnp.float32)[None, None, :, None]

    gx, gy, gw, gh = gt_box[..., 0], gt_box[..., 1], gt_box[..., 2], gt_box[..., 3]
    gt_valid = (gw > 0) & (gh > 0)  # reference GtValid: w or h <= 0 -> skip

    # --- ignore mask (stop_gradient: constants to the loss) ---------------
    px = jax.lax.stop_gradient((grid_x + jax.nn.sigmoid(tx)) / w)  # [n,m,h,w]
    py = jax.lax.stop_gradient((grid_y + jax.nn.sigmoid(ty)) / h)
    pw = jax.lax.stop_gradient(jnp.exp(tw) * aw[None, :, None, None] / input_size)
    ph = jax.lax.stop_gradient(jnp.exp(th) * ah[None, :, None, None] / input_size)
    iou = _cbox_iou(px[..., None], py[..., None], pw[..., None], ph[..., None],
                    gx[:, None, None, None, :], gy[:, None, None, None, :],
                    gw[:, None, None, None, :], gh[:, None, None, None, :])
    iou = jnp.where(gt_valid[:, None, None, None, :], iou, 0.0)
    best_iou = jnp.max(iou, axis=-1) if b > 0 else jnp.zeros_like(px)
    obj_mask = jnp.where(best_iou > ignore_thresh, -1.0, 0.0)  # [n, m, h, w]

    # --- positive assignment per gt --------------------------------------
    all_aw = jnp.asarray(anchors[0::2], jnp.float32) / input_size
    all_ah = jnp.asarray(anchors[1::2], jnp.float32) / input_size
    an_iou = _cbox_iou(0.0, 0.0, all_aw[None, None, :], all_ah[None, None, :],
                       0.0, 0.0, gw[..., None], gh[..., None])  # [n, b, an]
    best_n = jnp.argmax(an_iou, axis=-1)  # [n, b]
    mask_lut = -jnp.ones((an_num,), jnp.int32)
    for mi, a in enumerate(anchor_mask):
        mask_lut = mask_lut.at[a].set(mi)
    mask_idx = jnp.where(gt_valid, mask_lut[best_n], -1)  # [n, b]
    matched = mask_idx >= 0

    gi = jnp.clip((gx * w).astype(jnp.int32), 0, w - 1)
    gj = jnp.clip((gy * h).astype(jnp.int32), 0, h - 1)
    ni = jnp.arange(n)[:, None]
    midx = jnp.maximum(mask_idx, 0)

    # targets at the matched cell
    t_x = gx * w - gi
    t_y = gy * h - gj
    anc_w = jnp.take(jnp.asarray(anchors[0::2], jnp.float32), best_n)
    anc_h = jnp.take(jnp.asarray(anchors[1::2], jnp.float32), best_n)
    safe = jnp.maximum(gw * input_size, 1e-9), jnp.maximum(gh * input_size, 1e-9)
    t_w = jnp.log(safe[0] / anc_w)
    t_h = jnp.log(safe[1] / anc_h)
    scale = (2.0 - gw * gh) * gt_score

    p_tx = tx[ni, midx, gj, gi]  # [n, b]
    p_ty = ty[ni, midx, gj, gi]
    p_tw = tw[ni, midx, gj, gi]
    p_th = th[ni, midx, gj, gi]
    loc = (_sce(p_tx, t_x) + _sce(p_ty, t_y)
           + jnp.abs(p_tw - t_w) + jnp.abs(p_th - t_h)) * scale
    loc_loss = jnp.sum(jnp.where(matched, loc, 0.0), axis=1)  # [n]

    if smooth:
        delta = min(1.0 / C, 1.0 / 40)
        pos, neg = 1.0 - delta, delta
    else:
        pos, neg = 1.0, 0.0
    p_cls = tcls[ni, midx, :, gj, gi]  # [n, b, C]
    onehot = jax.nn.one_hot(gt_label, C, dtype=jnp.float32)
    cls_tgt = onehot * pos + (1.0 - onehot) * neg
    cls = jnp.sum(_sce(p_cls, cls_tgt), axis=-1) * gt_score
    cls_loss = jnp.sum(jnp.where(matched, cls, 0.0), axis=1)

    # positive cells override ignore in the objectness mask (reference
    # writes -1 first, then score at matched cells).  Unmatched/padded gt
    # rows must not scatter at all — with duplicate indices their stale
    # read-back could clobber a real gt's write — so they are routed to a
    # dummy cell that is dropped afterwards.
    flat = obj_mask.reshape(n, -1)
    flat = jnp.concatenate([flat, jnp.zeros((n, 1), flat.dtype)], axis=1)
    cell = (midx * h + gj) * w + gi
    cell = jnp.where(matched, cell, m * h * w)  # dummy slot for non-matches
    flat = flat.at[ni, cell].set(jnp.where(matched, gt_score, 0.0))
    obj_mask = flat[:, :-1].reshape(n, m, h, w)
    obj_mask = jax.lax.stop_gradient(obj_mask)
    obj_pos = jnp.where(obj_mask > 1e-5, _sce(tobj, 1.0) * obj_mask, 0.0)
    obj_neg = jnp.where((obj_mask <= 1e-5) & (obj_mask > -0.5), _sce(tobj, 0.0), 0.0)
    obj_loss = jnp.sum(obj_pos + obj_neg, axis=(1, 2, 3))

    loss = loc_loss + cls_loss + obj_loss
    return {"Loss": loss, "ObjectnessMask": obj_mask,
            "GTMatchMask": mask_idx.astype(jnp.int32)}


@register_op("roi_pool")
def _roi_pool(ctx, op, ins):
    """reference roi_pool_op.h CPUROIPoolOpKernel: quantized-bin max pool.
    Same rounding/bin math (round coords, floor/ceil bin edges, malformed
    rois forced 1x1, empty bins -> 0); dense [R, 4] rois + RoisBatch vector
    replace the LoD (static-shape form, as roi_align above)."""
    x = first(ins, "X")                   # [N, C, H, W]
    rois = first(ins, "ROIs")             # [R, 4]
    batch_idx = ins.get("RoisBatch")
    batch_idx = (batch_idx[0].reshape(-1).astype(jnp.int32)
                 if batch_idx else jnp.zeros((rois.shape[0],), jnp.int32))
    ph = op.attr("pooled_height", 1)
    pw = op.attr("pooled_width", 1)
    scale = op.attr("spatial_scale", 1.0)
    H, W = x.shape[2], x.shape[3]
    NEG = jnp.finfo(jnp.float32).min

    def one_roi(roi, bi):
        img = x[bi].astype(jnp.float32)  # [C, H, W]
        x0 = jnp.round(roi[0] * scale).astype(jnp.int32)
        y0 = jnp.round(roi[1] * scale).astype(jnp.int32)
        x1 = jnp.round(roi[2] * scale).astype(jnp.int32)
        y1 = jnp.round(roi[3] * scale).astype(jnp.int32)
        rh = jnp.maximum(y1 - y0 + 1, 1).astype(jnp.float32)
        rw = jnp.maximum(x1 - x0 + 1, 1).astype(jnp.float32)
        bh, bw = rh / ph, rw / pw
        hs = jnp.clip(jnp.floor(jnp.arange(ph) * bh).astype(jnp.int32) + y0, 0, H)
        he = jnp.clip(jnp.ceil((jnp.arange(ph) + 1) * bh).astype(jnp.int32) + y0, 0, H)
        ws = jnp.clip(jnp.floor(jnp.arange(pw) * bw).astype(jnp.int32) + x0, 0, W)
        we = jnp.clip(jnp.ceil((jnp.arange(pw) + 1) * bw).astype(jnp.int32) + x0, 0, W)
        mh = ((jnp.arange(H)[None, :] >= hs[:, None])
              & (jnp.arange(H)[None, :] < he[:, None]))          # [ph, H]
        mw = ((jnp.arange(W)[None, :] >= ws[:, None])
              & (jnp.arange(W)[None, :] < we[:, None]))          # [pw, W]
        # masked max in two reductions: over W per pw bin, then H per ph bin
        masked_w = jnp.where(mw[None, None, :, :], img[:, :, None, :], NEG)  # [C, H, pw, W]
        vw = jnp.max(masked_w, axis=-1)                                      # [C, H, pw]
        aw = jnp.argmax(masked_w, axis=-1).astype(jnp.int32)                 # best w per (h, pw)
        masked_h = jnp.where(mh[None, :, :, None], vw[:, None, :, :], NEG)   # [C, ph, H, pw]
        out = jnp.max(masked_h, axis=2)                                      # [C, ph, pw]
        ah = jnp.argmax(masked_h, axis=2).astype(jnp.int32)                  # best h per (ph, pw)
        w_best = jnp.take_along_axis(aw, ah, axis=1)  # [C, ph, pw]
        arg = ah * W + w_best                       # flat index, reference Argmax layout
        empty = ((he <= hs)[:, None] | (we <= ws)[None, :])  # [ph, pw]
        return jnp.where(empty[None], 0.0, out), jnp.where(empty[None], 0, arg)

    out, argmax = jax.vmap(one_roi)(rois, batch_idx)
    return {"Out": out.astype(x.dtype), "Argmax": argmax}


_MATCH_EPS = 1e-6


def _greedy_match(d, valid_row, match_type, thresh):
    """Single-image matching (reference bipartite_match_op.cc): R rounds of
    greedy global argmax, then optional per_prediction argmax augmentation.
    d: [R, C] distances; valid_row: [R] mask.  Returns (col_to_row [C],
    col_dist [C]).  Shared by the bipartite_match op and the fused
    ssd_loss lowering."""
    R, C = d.shape

    def body(_, state):
        col_to_row, col_dist, row_used = state
        avail = (valid_row & ~row_used)[:, None] & (col_to_row < 0)[None, :]
        cand = jnp.where(avail & (d >= _MATCH_EPS), d, -1.0)
        flat = jnp.argmax(cand)
        r, c = flat // C, flat % C
        ok = cand[r, c] > 0
        col_to_row = jnp.where(ok, col_to_row.at[c].set(r.astype(jnp.int32)), col_to_row)
        col_dist = jnp.where(ok, col_dist.at[c].set(d[r, c]), col_dist)
        row_used = jnp.where(ok, row_used.at[r].set(True), row_used)
        return col_to_row, col_dist, row_used

    init = (jnp.full((C,), -1, jnp.int32), jnp.zeros((C,), jnp.float32),
            jnp.zeros((R,), bool))
    col_to_row, col_dist, _ = jax.lax.fori_loop(0, R, body, init)

    if match_type == "per_prediction":
        cand = jnp.where(valid_row[:, None] & (d >= _MATCH_EPS) & (d >= thresh),
                         d, -1.0)
        best = jnp.argmax(cand, axis=0).astype(jnp.int32)
        bd = jnp.max(cand, axis=0)
        fresh = (col_to_row < 0) & (bd > 0)
        col_to_row = jnp.where(fresh, best, col_to_row)
        col_dist = jnp.where(fresh, bd, col_dist)
    return col_to_row, col_dist


@register_op("bipartite_match")
def _bipartite_match(ctx, op, ins):
    """reference detection/bipartite_match_op.cc BipartiteMatch: greedy
    global-argmax matching — each of R rounds matches the largest remaining
    (row, col) entry with dist >= eps; optional per_prediction pass then
    argmax-matches leftover columns above dist_threshold.

    Dense redesign of the LoD contract: DistMat [N, R, C] padded (+RowLod
    valid-row counts) in place of the [sum_rows, C] LoD tensor; outputs keep
    the reference shapes [N, C]."""
    dist = first(ins, "DistMat").astype(jnp.float32)
    if dist.ndim == 2:
        dist = dist[None]
    row_lens = (first(ins, "RowLod").astype(jnp.int32) if ins.get("RowLod")
                else jnp.full((dist.shape[0],), dist.shape[1], jnp.int32))
    match_type = op.attr("match_type", "bipartite")
    thresh = op.attr("dist_threshold", 0.5)
    N, R, C = dist.shape

    def one(d, nrow):
        return _greedy_match(d, jnp.arange(R) < nrow, match_type, thresh)

    idx, dst = jax.vmap(one)(dist, row_lens)
    return {"ColToRowMatchIndices": idx, "ColToRowMatchDist": dst}


@register_op("target_assign")
def _target_assign(ctx, op, ins):
    """reference detection/target_assign_op.h TargetAssignFunctor: gather
    per-batch entities by match index; -1 -> mismatch_value with weight 0;
    NegIndices rows get weight 1 (out stays mismatch_value).

    Dense redesign: X [N, B, K] padded replaces the [sum_b, 1, K] LoD input;
    NegIndices is [N, Q] padded with -1."""
    x = first(ins, "X")                              # [N, B, K], any dtype
    match = first(ins, "MatchIndices").astype(jnp.int32)  # [N, M]
    mismatch = op.attr("mismatch_value", 0)
    N, B, K = x.shape
    safe = jnp.clip(match, 0, B - 1)
    out = jnp.take_along_axis(x, safe[:, :, None], axis=1)  # [N, M, K]
    hit = (match >= 0)[:, :, None]
    out = jnp.where(hit, out, jnp.asarray(mismatch, x.dtype))
    wt = hit.astype(jnp.float32)
    if ins.get("NegIndices"):
        neg = first(ins, "NegIndices").astype(jnp.int32)  # [N, Q], -1 pad
        M = match.shape[1]
        # scatter 1s at negative slots; -1 pads go to a dropped dummy column
        nw = jnp.zeros((N, M + 1), jnp.float32)
        ni = jnp.arange(N)[:, None]
        nw = nw.at[ni, jnp.where(neg >= 0, neg, M)].set(1.0)
        wt = jnp.maximum(wt, nw[:, :M, None])
    return {"Out": out, "OutWeight": wt}


def _corner_iou(a, b):
    """IoU of corner-format boxes a [M, 4] vs b [B, 4] -> [M, B]."""
    lt = jnp.maximum(a[:, None, :2], b[None, :, :2])
    rb = jnp.minimum(a[:, None, 2:], b[None, :, 2:])
    wh = jnp.maximum(rb - lt, 0.0)
    inter = wh[..., 0] * wh[..., 1]
    area_a = jnp.maximum((a[:, 2] - a[:, 0]) * (a[:, 3] - a[:, 1]), 0.0)
    area_b = jnp.maximum((b[:, 2] - b[:, 0]) * (b[:, 3] - b[:, 1]), 0.0)
    union = area_a[:, None] + area_b[None, :] - inter
    return jnp.where(union > 0, inter / union, 0.0)


def _rank_select(cand, pri, k):
    """Select up to k True entries of `cand`, highest `pri` first (the
    static-shape subsampling device shared by the rpn/retinanet/proposal
    assigners): returns the selection mask."""
    n = cand.shape[0]
    order = jnp.argsort(jnp.where(cand, -pri, jnp.inf))
    rank = jnp.zeros((n,), jnp.int32).at[order].set(jnp.arange(n, dtype=jnp.int32))
    return cand & (rank < k), rank


def _box_to_delta(anchor, gt):
    """encode gt relative to anchor (reference operators/detection/
    bbox_util.h BoxToDelta, unit weights)."""
    aw = anchor[:, 2] - anchor[:, 0] + 1.0
    ah = anchor[:, 3] - anchor[:, 1] + 1.0
    acx = anchor[:, 0] + aw * 0.5
    acy = anchor[:, 1] + ah * 0.5
    gw = gt[:, 2] - gt[:, 0] + 1.0
    gh = gt[:, 3] - gt[:, 1] + 1.0
    gcx = gt[:, 0] + gw * 0.5
    gcy = gt[:, 1] + gh * 0.5
    return jnp.stack([(gcx - acx) / aw, (gcy - acy) / ah,
                      jnp.log(jnp.maximum(gw, 1e-9) / aw),
                      jnp.log(jnp.maximum(gh, 1e-9) / ah)], axis=1)


@register_op("rpn_target_assign")
def _rpn_target_assign(ctx, op, ins):
    """RPN anchor labeling + subsampling (reference
    detection/rpn_target_assign_op.cc).  Same rules: straddle filter,
    positives = per-gt best anchor or IoU >= positive_overlap, negatives =
    max-IoU < negative_overlap, subsample to rpn_batch_size_per_im with
    rpn_fg_fraction positives (random under use_random via the trace RNG
    key, top-IoU otherwise), crowd gts excluded.

    STATIC-SHAPE redesign: instead of the reference's gathered [F, 4]/[F+B]
    outputs (dynamic shapes), every output spans all anchors and the
    sampling lives in weights: TargetLabel [N, M], ScoreWeight [N, M] (1 on
    sampled fg+bg), TargetBBox [N, M, 4], BBoxInsideWeight [N, M, 4] (1 on
    fg rows).  Losses multiply by the weights, which is mathematically the
    reference's gather."""
    anchors = first(ins, "Anchor").astype(jnp.float32).reshape(-1, 4)  # [M, 4]
    gt = first(ins, "GtBoxes").astype(jnp.float32)    # [N, B, 4]
    if gt.ndim == 2:
        gt = gt[None]
    N, B, _ = gt.shape
    gt_lens = (first(ins, "GtLod").astype(jnp.int32) if ins.get("GtLod")
               else jnp.full((N,), B, jnp.int32))
    is_crowd = (first(ins, "IsCrowd").reshape(N, -1).astype(jnp.int32)
                if ins.get("IsCrowd") else jnp.zeros((N, B), jnp.int32))
    if ins.get("ImInfo"):
        im_info = first(ins, "ImInfo").astype(jnp.float32).reshape(N, -1)  # [N, 3] h, w, scale
    else:
        # no image extents -> the straddle filter cannot run; keep all anchors
        im_info = jnp.full((N, 3), jnp.inf, jnp.float32)
    batch_size = op.attr("rpn_batch_size_per_im", 256)
    straddle = op.attr("rpn_straddle_thresh", 0.0)
    fg_frac = op.attr("rpn_fg_fraction", 0.5)
    pos_ov = op.attr("rpn_positive_overlap", 0.7)
    neg_ov = op.attr("rpn_negative_overlap", 0.3)
    use_random = op.attr("use_random", True)
    M = anchors.shape[0]
    num_fg_target = int(fg_frac * batch_size)

    keys = jax.random.split(ctx.next_key(), N) if use_random else None

    def one(i):
        g, nlen, crowd, info = gt[i], gt_lens[i], is_crowd[i], im_info[i]
        h, w = info[0], info[1]
        if straddle >= 0:
            inside = ((anchors[:, 0] >= -straddle) & (anchors[:, 1] >= -straddle)
                      & (anchors[:, 2] < w + straddle) & (anchors[:, 3] < h + straddle))
        else:
            inside = jnp.ones((M,), bool)
        gt_valid = (jnp.arange(B) < nlen) & (crowd == 0)
        iou = _corner_iou(anchors, g)                      # [M, B]
        iou = jnp.where(gt_valid[None, :] & inside[:, None], iou, 0.0)
        a2g_max = jnp.max(iou, axis=1) if B else jnp.zeros((M,))
        a2g_arg = jnp.argmax(iou, axis=1) if B else jnp.zeros((M,), jnp.int32)
        g_max = jnp.max(iou, axis=0)                       # [B]
        is_best = jnp.any((iou == g_max[None, :]) & (g_max[None, :] > 0)
                          & gt_valid[None, :], axis=1)
        fg_cand = inside & (is_best | (a2g_max >= pos_ov))
        bg_cand = inside & ~fg_cand & (a2g_max < neg_ov)

        if use_random:
            pri = jax.random.uniform(keys[i], (M,))
        else:
            pri = a2g_max  # deterministic: highest-IoU first
        # rank fg candidates by priority; keep the top num_fg_target
        fg, _ = _rank_select(fg_cand, pri, num_fg_target)
        n_fg = jnp.sum(fg)
        bg, _ = _rank_select(bg_cand, pri, batch_size - n_fg)

        label = fg.astype(jnp.int32)
        score_w = (fg | bg).astype(jnp.float32)
        tgt = _box_to_delta(anchors, g[jnp.clip(a2g_arg, 0, max(B - 1, 0))])
        tgt = jnp.where(fg[:, None], tgt, 0.0)
        inw = jnp.where(fg[:, None], 1.0, 0.0)
        return label, score_w, tgt, inw

    label, score_w, tgt, inw = jax.vmap(one)(jnp.arange(N))
    return {"TargetLabel": label, "ScoreWeight": score_w,
            "TargetBBox": tgt, "BBoxInsideWeight": inw}


@register_op("generate_proposals")
def _generate_proposals(ctx, op, ins):
    """RPN proposal generation (reference
    detection/generate_proposals_op.cc ProposalForOneImage): score top-k ->
    delta decode with variances -> clip to image -> min-size filter -> NMS
    -> post_nms_topN.  The reference emits LoD-concatenated rois; here each
    image yields padded static [post_nms_topN, 4] + prob blocks (prob 0 =
    empty slot), the accelerator formulation multiclass_nms above uses."""
    scores = first(ins, "Scores").astype(jnp.float32)       # [N, A, H, W]
    deltas = first(ins, "BboxDeltas").astype(jnp.float32)   # [N, 4A, H, W]
    im_info = first(ins, "ImInfo").astype(jnp.float32).reshape(scores.shape[0], -1)
    anchors = first(ins, "Anchors").astype(jnp.float32).reshape(-1, 4)  # [H*W*A, 4]
    variances = first(ins, "Variances").astype(jnp.float32).reshape(-1, 4)
    pre_n = op.attr("pre_nms_topN", 6000)
    post_n = op.attr("post_nms_topN", 1000)
    nms_thresh = op.attr("nms_thresh", 0.7)
    min_size = op.attr("min_size", 0.1)
    N, A, H, W = scores.shape
    K = A * H * W

    # [N, A, H, W] -> [N, H, W, A] flat, matching anchors' [H, W, A] layout
    sc = scores.transpose(0, 2, 3, 1).reshape(N, K)
    dl = deltas.reshape(N, A, 4, H, W).transpose(0, 3, 4, 1, 2).reshape(N, K, 4)

    def decode(anc, d, var):
        aw = anc[:, 2] - anc[:, 0] + 1.0
        ah = anc[:, 3] - anc[:, 1] + 1.0
        acx = anc[:, 0] + aw * 0.5
        acy = anc[:, 1] + ah * 0.5
        bbox_clip = jnp.log(1000.0 / 16.0)
        dx, dy, dw, dh = (d[:, 0] * var[:, 0], d[:, 1] * var[:, 1],
                          jnp.minimum(d[:, 2] * var[:, 2], bbox_clip),
                          jnp.minimum(d[:, 3] * var[:, 3], bbox_clip))
        cx = dx * aw + acx
        cy = dy * ah + acy
        w_ = jnp.exp(dw) * aw
        h_ = jnp.exp(dh) * ah
        return jnp.stack([cx - w_ / 2, cy - h_ / 2,
                          cx + w_ / 2 - 1, cy + h_ / 2 - 1], axis=1)

    def one(s, d, info):
        n_pre = min(pre_n, K)
        top_s, top_i = jax.lax.top_k(s, n_pre)
        boxes = decode(anchors[top_i], d[top_i], variances[top_i])
        h, w = info[0], info[1]
        boxes = jnp.stack([jnp.clip(boxes[:, 0], 0, w - 1),
                           jnp.clip(boxes[:, 1], 0, h - 1),
                           jnp.clip(boxes[:, 2], 0, w - 1),
                           jnp.clip(boxes[:, 3], 0, h - 1)], axis=1)
        ms = max(min_size, 1.0) * info[2]  # reference FilterBoxes clamps to >= 1px
        bw = boxes[:, 2] - boxes[:, 0] + 1.0
        bh = boxes[:, 3] - boxes[:, 1] + 1.0
        keep = (bw >= ms) & (bh >= ms)
        s_kept = jnp.where(keep, top_s, -1.0)
        b, s_out = _nms_single_class(boxes, s_kept, nms_thresh, n_pre,
                                     normalized=False)
        return b[:post_n], jnp.maximum(s_out[:post_n], 0.0)

    rois, probs = jax.vmap(one)(sc, dl, im_info)
    return {"RpnRois": rois, "RpnRoiProbs": probs[..., None]}


def _np_detection_map(det, gt_label, gt_box, gt_difficult, gt_lens, class_num,
                      overlap_threshold, ap_type, background_label,
                      evaluate_difficult):
    """numpy mAP (reference detection_map_op.h CalcTrueAndFalsePositive):
    per-class score-sorted greedy matching against gt at overlap_threshold
    (strict >, pred boxes clipped to [0, 1] as ClipBBox does), AP by
    11-point interpolation or integral.  With evaluate_difficult=False,
    difficult gts leave npos and matches to them count neither TP nor FP."""
    aps = []
    for c in range(class_num):
        if c == background_label:
            continue
        npos = 0
        records = []  # (score, tp)
        for i in range(det.shape[0]):
            g_idx = [t for t in range(int(gt_lens[i]))
                     if int(gt_label[i, t]) == c]
            npos += sum(1 for t in g_idx
                        if evaluate_difficult or not gt_difficult[i, t])
            used = set()
            dets = [(float(det[i, j, 1]), det[i, j, 2:6])
                    for j in range(det.shape[1]) if int(det[i, j, 0]) == c]
            dets.sort(key=lambda r: -r[0])
            for score, box in dets:
                box = np.clip(box, 0.0, 1.0)  # reference ClipBBox
                best, best_t = -1.0, -1
                for t in g_idx:
                    gb = gt_box[i, t]
                    ix = max(0.0, min(box[2], gb[2]) - max(box[0], gb[0]))
                    iy = max(0.0, min(box[3], gb[3]) - max(box[1], gb[1]))
                    inter = ix * iy
                    ua = (max(box[2] - box[0], 0) * max(box[3] - box[1], 0)
                          + max(gb[2] - gb[0], 0) * max(gb[3] - gb[1], 0) - inter)
                    ov = inter / ua if ua > 0 else 0.0
                    if ov > best:
                        best, best_t = ov, t
                if best > overlap_threshold:
                    if not evaluate_difficult and gt_difficult[i, best_t]:
                        continue  # matched a difficult gt: neither TP nor FP
                    tp = best_t not in used
                    if tp:
                        used.add(best_t)
                    records.append((score, 1.0 if tp else 0.0))
                else:
                    records.append((score, 0.0))
        if npos == 0:
            continue
        records.sort(key=lambda r: -r[0])
        tps = np.cumsum([r[1] for r in records]) if records else np.zeros(0)
        fps = np.cumsum([1 - r[1] for r in records]) if records else np.zeros(0)
        rec = tps / npos
        prec = tps / np.maximum(tps + fps, 1e-12)
        if ap_type == "11point":
            ap = 0.0
            for th in np.arange(0.0, 1.01, 0.1):
                p = prec[rec >= th].max() if np.any(rec >= th) else 0.0
                ap += p / 11.0
        else:  # integral
            ap = 0.0
            prev_rec = 0.0
            for k in range(len(rec)):
                ap += prec[k] * (rec[k] - prev_rec)
                prev_rec = rec[k]
        aps.append(ap)
    return np.float32(np.mean(aps) if aps else 0.0)


@register_op("detection_map")
def _detection_map(ctx, op, ins):
    """mAP metric (reference detection/detection_map_op.h).  Pure metric —
    not a training-path op — so it runs as a host callback over the padded
    static inputs: DetectRes [N, D, 6] (label, score, box; label < 0 pad,
    the multiclass_nms output format), Label [N, B, >=5] (label, box
    [, difficult]) + GtLod lens.  Output: batch mAP scalar; cross-batch
    accumulation lives in metrics.DetectionMAP (the reference's
    accumulative POS-count states are host state there)."""
    det = first(ins, "DetectRes").astype(jnp.float32)
    gt = first(ins, "Label").astype(jnp.float32)
    if gt.ndim == 2:
        gt = gt[None]
    N, B = gt.shape[0], gt.shape[1]
    gt_lens = (first(ins, "GtLod").astype(jnp.int32) if ins.get("GtLod")
               else jnp.full((N,), B, jnp.int32))
    class_num = op.attr("class_num")
    overlap_threshold = op.attr("overlap_threshold", 0.5)
    ap_type = op.attr("ap_type", "integral")
    background_label = op.attr("background_label", 0)
    evaluate_difficult = op.attr("evaluate_difficult", True)

    def host(det_v, gt_v, lens_v):
        # Label rows: [label, box] (5 cols) or [label, difficult, box]
        # (6 cols), the reference GetBoxes contract
        if gt_v.shape[2] >= 6:
            difficult = gt_v[:, :, 1] != 0
            box = gt_v[:, :, 2:6]
        else:
            difficult = np.zeros(gt_v.shape[:2], bool)
            box = gt_v[:, :, 1:5]
        return _np_detection_map(det_v, gt_v[:, :, 0], box, difficult, lens_v,
                                 class_num, overlap_threshold, ap_type,
                                 background_label, evaluate_difficult)

    out = jax.pure_callback(host, jax.ShapeDtypeStruct((), jnp.float32),
                            det, gt, gt_lens)
    return {"MAP": out.reshape(1)}


@register_op("ssd_loss")
def _ssd_loss(ctx, op, ins):
    """Fused SSD multibox loss (reference layers/detection.py ssd_loss
    pipeline: iou_similarity -> bipartite_match(per_prediction) ->
    mine_hard_examples(max_negative) -> target_assign -> smooth_l1 +
    softmax CE, normalized by the matched count).  One lowering per image
    via vmap instead of the reference's nine-op program fragment — the
    matching/mining selections are integer ranks, constants to the loss.

    Inputs: Location [N, P, 4], Confidence [N, P, C], GtBox [N, B, 4]
    padded corner boxes, GtLabel [N, B], GtLod lens, PriorBox [P, 4],
    PriorBoxVar [P, 4].  Output: Loss [N, 1]."""
    loc = first(ins, "Location").astype(jnp.float32)
    conf = first(ins, "Confidence").astype(jnp.float32)
    gt_box = first(ins, "GtBox").astype(jnp.float32)
    gt_label = first(ins, "GtLabel").astype(jnp.int32)
    if gt_label.ndim == 3:
        gt_label = gt_label[..., 0]
    prior = first(ins, "PriorBox").astype(jnp.float32).reshape(-1, 4)
    pvar = (first(ins, "PriorBoxVar").astype(jnp.float32).reshape(-1, 4)
            if ins.get("PriorBoxVar")
            else jnp.full((prior.shape[0], 4), 1.0, jnp.float32))
    N, B = gt_box.shape[0], gt_box.shape[1]
    gt_lens = (first(ins, "GtLod").astype(jnp.int32) if ins.get("GtLod")
               else jnp.full((N,), B, jnp.int32))
    background = op.attr("background_label", 0)
    overlap_t = op.attr("overlap_threshold", 0.5)
    neg_ratio = op.attr("neg_pos_ratio", 3.0)
    loc_w = op.attr("loc_loss_weight", 1.0)
    conf_w = op.attr("conf_loss_weight", 1.0)
    P = prior.shape[0]

    # prior encode constants
    pw = prior[:, 2] - prior[:, 0]
    ph = prior[:, 3] - prior[:, 1]
    pcx = prior[:, 0] + pw * 0.5
    pcy = prior[:, 1] + ph * 0.5

    match_type = op.attr("match_type", "per_prediction")

    def one(g, glab, nlen, cf, lc):
        valid = jnp.arange(B) < nlen
        iou = jnp.where(valid[:, None], _corner_iou(g, prior), 0.0)  # [B, P]
        match, dist = _greedy_match(iou, valid, match_type, overlap_t)
        matched = match >= 0
        safe = jnp.clip(match, 0, B - 1)
        tgt_label = jnp.where(matched, glab[safe], background)
        logp = jax.nn.log_softmax(cf, axis=-1)
        ce = -jnp.take_along_axis(logp, tgt_label[:, None], axis=1)[:, 0]  # [P]

        # max_negative mining (reference mine_hard_examples_op.h): candidate
        # = unmatched AND match_dist < neg_dist_threshold (dist is 0 for
        # unmatched columns, so the guard is literal reference semantics),
        # ranked by conf CE desc
        neg_overlap = op.attr("neg_overlap", 0.5)
        cand_neg = ~matched & (dist < neg_overlap)
        npos = jnp.sum(matched)
        n_neg = (neg_ratio * npos).astype(jnp.int32)
        neg_score = jnp.where(cand_neg, jax.lax.stop_gradient(ce), -jnp.inf)
        order = jnp.argsort(-neg_score)
        rank = jnp.zeros((P,), jnp.int32).at[order].set(jnp.arange(P, dtype=jnp.int32))
        neg = cand_neg & (rank < n_neg)

        # regression targets: encode matched gt against priors with variance
        gsel = g[safe]
        gw = gsel[:, 2] - gsel[:, 0]
        gh = gsel[:, 3] - gsel[:, 1]
        gcx = gsel[:, 0] + gw * 0.5
        gcy = gsel[:, 1] + gh * 0.5
        enc = jnp.stack([
            (gcx - pcx) / pw / pvar[:, 0],
            (gcy - pcy) / ph / pvar[:, 1],
            jnp.log(jnp.maximum(gw, 1e-9) / pw) / pvar[:, 2],
            jnp.log(jnp.maximum(gh, 1e-9) / ph) / pvar[:, 3]], axis=1)
        enc = jax.lax.stop_gradient(jnp.where(matched[:, None], enc, 0.0))
        d = jnp.where(matched[:, None], lc - enc, 0.0)
        ad = jnp.abs(d)
        sl1 = jnp.sum(jnp.where(ad < 1.0, 0.5 * d * d, ad - 0.5), axis=1)

        conf_loss = jnp.sum(jnp.where(matched | neg, ce, 0.0))
        loc_loss = jnp.sum(sl1)
        return conf_w * conf_loss + loc_w * loc_loss, npos

    losses, npos = jax.vmap(one)(gt_box, gt_label, gt_lens, conf, loc)
    if op.attr("normalize", True):
        losses = losses / jnp.maximum(jnp.sum(npos).astype(jnp.float32), 1.0)
    return {"Loss": losses.reshape(N, 1)}


@register_op("psroi_pool")
def _psroi_pool(ctx, op, ins):
    """Position-sensitive RoI average pool (reference psroi_pool_op.h):
    input channel (c*PH+ph)*PW+pw feeds output bin (c, ph, pw); float bin
    edges floor/ceil'd and clipped, empty bins -> 0.  Dense [R, 4] rois +
    RoisBatch vector (static-shape form, as roi_pool/roi_align)."""
    x = first(ins, "X")                    # [N, C_in, H, W]
    rois = first(ins, "ROIs").astype(jnp.float32)
    batch_idx = ins.get("RoisBatch")
    batch_idx = (batch_idx[0].reshape(-1).astype(jnp.int32)
                 if batch_idx else jnp.zeros((rois.shape[0],), jnp.int32))
    oc = op.attr("output_channels")
    ph = op.attr("pooled_height", 1)
    pw = op.attr("pooled_width", 1)
    scale = op.attr("spatial_scale", 1.0)
    N, C_in, H, W = x.shape

    def one_roi(roi, bi):
        v = x[bi].astype(jnp.float32).reshape(oc, ph, pw, H, W)
        x0 = jnp.round(roi[0]) * scale
        y0 = jnp.round(roi[1]) * scale
        x1 = (jnp.round(roi[2]) + 1.0) * scale
        y1 = (jnp.round(roi[3]) + 1.0) * scale
        rh = jnp.maximum(y1 - y0, 0.1)
        rw = jnp.maximum(x1 - x0, 0.1)
        bh, bw = rh / ph, rw / pw
        hs = jnp.clip(jnp.floor(jnp.arange(ph) * bh + y0), 0, H)
        he = jnp.clip(jnp.ceil((jnp.arange(ph) + 1) * bh + y0), 0, H)
        ws = jnp.clip(jnp.floor(jnp.arange(pw) * bw + x0), 0, W)
        we = jnp.clip(jnp.ceil((jnp.arange(pw) + 1) * bw + x0), 0, W)
        mh = ((jnp.arange(H)[None, :] >= hs[:, None])
              & (jnp.arange(H)[None, :] < he[:, None])).astype(jnp.float32)
        mw = ((jnp.arange(W)[None, :] >= ws[:, None])
              & (jnp.arange(W)[None, :] < we[:, None])).astype(jnp.float32)
        s = jnp.einsum("cpqhw,ph,qw->cpq", v, mh, mw)
        area = (he - hs)[:, None] * (we - ws)[None, :]
        return jnp.where(area > 0, s / jnp.maximum(area, 1.0), 0.0)

    out = jax.vmap(one_roi)(rois, batch_idx)
    return {"Out": out.astype(x.dtype)}


@register_op("retinanet_target_assign")
def _retinanet_target_assign(ctx, op, ins):
    """RetinaNet anchor labeling (reference retinanet_target_assign_op.cc):
    same best-anchor / IoU-threshold rules as the RPN assigner but with NO
    subsampling (focal loss owns the imbalance), class labels instead of a
    binary objectness target, and a fg_num output for loss normalization.

    STATIC-SHAPE form like rpn_target_assign: TargetLabel [N, M] (gt class,
    0 background, -1 ignore), ScoreWeight [N, M] (1 for fg+bg, 0 ignored),
    TargetBBox [N, M, 4], BBoxInsideWeight [N, M, 4], FgNum [N, 1]."""
    anchors = first(ins, "Anchor").astype(jnp.float32).reshape(-1, 4)
    gt = first(ins, "GtBoxes").astype(jnp.float32)
    if gt.ndim == 2:
        gt = gt[None]
    N, B, _ = gt.shape
    gt_labels = first(ins, "GtLabels").reshape(N, -1).astype(jnp.int32)
    gt_lens = (first(ins, "GtLod").astype(jnp.int32) if ins.get("GtLod")
               else jnp.full((N,), B, jnp.int32))
    is_crowd = (first(ins, "IsCrowd").reshape(N, -1).astype(jnp.int32)
                if ins.get("IsCrowd") else jnp.zeros((N, B), jnp.int32))
    pos_ov = op.attr("positive_overlap", 0.5)
    neg_ov = op.attr("negative_overlap", 0.4)
    M = anchors.shape[0]

    def one(i):
        g, nlen, crowd = gt[i], gt_lens[i], is_crowd[i]
        gt_valid = (jnp.arange(B) < nlen) & (crowd == 0)
        iou = jnp.where(gt_valid[None, :], _corner_iou(anchors, g), 0.0)
        a2g_max = jnp.max(iou, axis=1)
        a2g_arg = jnp.argmax(iou, axis=1)
        g_max = jnp.max(iou, axis=0)
        is_best = jnp.any((iou == g_max[None, :]) & (g_max[None, :] > 0)
                          & gt_valid[None, :], axis=1)
        fg = is_best | (a2g_max >= pos_ov)
        bg = ~fg & (a2g_max < neg_ov)
        label = jnp.where(fg, gt_labels[i][jnp.clip(a2g_arg, 0, max(B - 1, 0))],
                          jnp.where(bg, 0, -1)).astype(jnp.int32)
        score_w = (fg | bg).astype(jnp.float32)
        tgt = _box_to_delta(anchors, g[jnp.clip(a2g_arg, 0, max(B - 1, 0))])
        tgt = jnp.where(fg[:, None], tgt, 0.0)
        inw = jnp.where(fg[:, None], 1.0, 0.0)
        return label, score_w, tgt, inw, jnp.sum(fg).astype(jnp.int32)

    label, score_w, tgt, inw, fg_num = jax.vmap(one)(jnp.arange(N))
    return {"TargetLabel": label, "ScoreWeight": score_w, "TargetBBox": tgt,
            "BBoxInsideWeight": inw, "FgNum": fg_num.reshape(N, 1) + 1}


@register_op("generate_proposal_labels")
def _generate_proposal_labels(ctx, op, ins):
    """RCNN stage-2 RoI sampling (reference
    detection/generate_proposal_labels_op.cc): append gts to the proposals,
    label by IoU (fg >= fg_thresh, bg in [bg_thresh_lo, bg_thresh_hi)),
    subsample to batch_size_per_im with fg_fraction foregrounds, and emit
    per-class-expanded regression targets.

    STATIC-SHAPE form: every image yields exactly batch_size_per_im rows;
    sampling lives in SampleWeight (1 = drawn, 0 = padding), the same
    rank-mask device the RPN assigner uses.  Outputs: Rois [N, R, 4],
    LabelsInt32 [N, R], BboxTargets [N, R, 4C], BboxInsideWeights /
    BboxOutsideWeights [N, R, 4C], SampleWeight [N, R]."""
    rois_in = first(ins, "RpnRois").astype(jnp.float32)   # [N, P, 4]
    if ins.get("ImInfo"):
        # reference divides proposals by im_scale so they share the gt frame
        im_info = first(ins, "ImInfo").astype(jnp.float32).reshape(-1, 3)
        rois_in = rois_in / im_info[:, 2][:, None, None]
    gt_classes = first(ins, "GtClasses").astype(jnp.int32)
    gt_boxes = first(ins, "GtBoxes").astype(jnp.float32)  # [N, B, 4]
    if gt_boxes.ndim == 2:
        gt_boxes = gt_boxes[None]
    N, B = gt_boxes.shape[0], gt_boxes.shape[1]
    gt_classes = gt_classes.reshape(N, -1)
    is_crowd = (first(ins, "IsCrowd").reshape(N, -1).astype(jnp.int32)
                if ins.get("IsCrowd") else jnp.zeros((N, B), jnp.int32))
    gt_lens = (first(ins, "GtLod").astype(jnp.int32) if ins.get("GtLod")
               else jnp.full((N,), B, jnp.int32))
    R = op.attr("batch_size_per_im", 256)
    fg_fraction = op.attr("fg_fraction", 0.25)
    fg_thresh = op.attr("fg_thresh", 0.5)
    bg_hi = op.attr("bg_thresh_hi", 0.5)
    bg_lo = op.attr("bg_thresh_lo", 0.0)
    weights = op.attr("bbox_reg_weights", [0.1, 0.1, 0.2, 0.2])
    C = op.attr("class_nums")
    use_random = op.attr("use_random", True)
    P = rois_in.shape[1]
    fg_target = int(fg_fraction * R)
    wvec = jnp.asarray(weights, jnp.float32)

    keys = jax.random.split(ctx.next_key(), N) if use_random else None

    def one(i):
        gt_valid = (jnp.arange(B) < gt_lens[i]) & (is_crowd[i] == 0)
        # gts join the candidate pool (reference concatenates them)
        cand = jnp.concatenate([rois_in[i], gt_boxes[i]], axis=0)  # [P+B, 4]
        iou = jnp.where(gt_valid[None, :], _corner_iou(cand, gt_boxes[i]), 0.0)
        max_iou = jnp.max(iou, axis=1)
        argmax = jnp.argmax(iou, axis=1)
        gt_rows_valid = jnp.concatenate(
            [jnp.ones((P,), bool), gt_valid], axis=0)
        fg_cand = gt_rows_valid & (max_iou >= fg_thresh)
        bg_cand = gt_rows_valid & (max_iou < bg_hi) & (max_iou >= bg_lo)

        pri = (jax.random.uniform(keys[i], (P + B,)) if use_random
               else max_iou)
        fg, rank_fg = _rank_select(fg_cand, pri, fg_target)
        n_fg = jnp.sum(fg)
        bg, rank_bg = _rank_select(bg_cand, pri, R - n_fg)

        # pack drawn rows to the front: fg band [0, fg_target), bg band
        # [fg_target, fg_target + n_cand), undrawn after both; pool smaller
        # than R repeats the last slot as padding (weight 0)
        n_cand = P + B
        sel_rank = jnp.where(fg, rank_fg,
                             jnp.where(bg, fg_target + rank_bg,
                                       fg_target + n_cand + jnp.arange(n_cand)))
        order_full = jnp.argsort(sel_rank)
        if n_cand >= R:
            order = order_full[:R]
            in_pool = jnp.ones((R,), bool)
        else:
            order = jnp.concatenate(
                [order_full, jnp.broadcast_to(order_full[-1:], (R - n_cand,))])
            in_pool = jnp.arange(R) < n_cand
        drawn = (fg | bg)[order] & in_pool

        rois = cand[order]
        fg_row = fg[order] & in_pool
        labels = jnp.where(fg_row,
                           gt_classes[i][jnp.clip(argmax[order], 0, max(B - 1, 0))],
                           0).astype(jnp.int32)
        tgt = _box_to_delta(rois, gt_boxes[i][jnp.clip(argmax[order], 0,
                                                       max(B - 1, 0))])
        tgt = tgt / wvec[None, :]
        # per-class expansion: targets land in the label's 4-col block
        onehot = jax.nn.one_hot(labels, C, dtype=jnp.float32)  # [R, C]
        expanded = (onehot[:, :, None] * tgt[:, None, :]).reshape(R, 4 * C)
        inw = jnp.repeat(onehot, 4, axis=1) * fg_row[:, None]  # [R, 4C]
        expanded = jnp.where(fg_row[:, None], expanded, 0.0)
        return (rois, labels, expanded, inw,
                drawn.astype(jnp.float32))

    rois, labels, tgt, inw, sw = jax.vmap(one)(jnp.arange(N))
    return {"Rois": rois, "LabelsInt32": labels, "BboxTargets": tgt,
            "BboxInsideWeights": inw, "BboxOutsideWeights": inw,
            "SampleWeight": sw}


@register_op("distribute_fpn_proposals")
def _distribute_fpn_proposals(ctx, op, ins):
    """FPN level routing (reference
    detection/distribute_fpn_proposals_op.cc): each roi maps to level
    floor(log2(sqrt(area) / refer_scale + 1e-6)) + refer_level, clipped to
    [min_level, max_level].

    STATIC-SHAPE form: instead of variable-length per-level splits, emit a
    [L, R] one-hot level mask; the layer pools every roi on every level
    and selects by mask (the standard accelerator FPN formulation), so
    RestoreIndex is the identity."""
    rois = first(ins, "FpnRois").astype(jnp.float32).reshape(-1, 4)
    min_level = op.attr("min_level")
    max_level = op.attr("max_level")
    refer_level = op.attr("refer_level")
    refer_scale = op.attr("refer_scale")
    L = max_level - min_level + 1
    w = jnp.maximum(rois[:, 2] - rois[:, 0] + 1.0, 0.0)  # reference BBoxArea
    h = jnp.maximum(rois[:, 3] - rois[:, 1] + 1.0, 0.0)
    scale = jnp.sqrt(w * h)
    lvl = jnp.floor(jnp.log2(scale / refer_scale + 1e-6)) + refer_level
    lvl = jnp.clip(lvl, min_level, max_level).astype(jnp.int32)
    mask = jax.nn.one_hot(lvl - min_level, L, dtype=jnp.float32).T  # [L, R]
    restore = jnp.arange(rois.shape[0], dtype=jnp.int32)
    return {"MultiLevelMask": mask, "RestoreIndex": restore[:, None]}


@register_op("collect_fpn_proposals")
def _collect_fpn_proposals(ctx, op, ins):
    """reference detection/collect_fpn_proposals_op.cc: concat per-level
    proposals and keep the global top post_nms_topN by score.  Static
    shape: inputs are the padded per-level blocks; output is a padded
    [post_nms_topN, 4] block + kept scores (0 = empty slot)."""
    rois_list = [r if r.ndim == 3 else r[None] for r in ins["MultiLevelRois"]]

    def _canon_scores(s):
        if s.ndim == 3 and s.shape[-1] == 1:  # generate_proposals' [N, R, 1]
            s = s[..., 0]
        return s if s.ndim == 2 else s[None]

    scores_list = [_canon_scores(s) for s in ins["MultiLevelScores"]]
    post_n = op.attr("post_nms_topN")
    rois = jnp.concatenate(rois_list, axis=1)      # [N, sum_R, 4]
    scores = jnp.concatenate(scores_list, axis=1)  # [N, sum_R]
    k = min(post_n, scores.shape[1])

    def one(s, r):
        top_s, top_i = jax.lax.top_k(s, k)
        out = r[top_i]
        if k < post_n:
            out = jnp.pad(out, ((0, post_n - k), (0, 0)))
            top_s = jnp.pad(top_s, (0, post_n - k))
        return out, top_s

    out_rois, top_s = jax.vmap(one)(scores, rois)  # [N, post_n, 4]
    return {"FpnRois": out_rois, "RoisScores": top_s[..., None]}


@register_op("box_decoder_and_assign")
def _box_decoder_and_assign(ctx, op, ins):
    """reference detection/box_decoder_and_assign_op.cc (R-FCN): decode
    per-class deltas against the prior, then assign each roi its best
    class's decoded box (background column excluded)."""
    prior = first(ins, "PriorBox").astype(jnp.float32)      # [R, 4]
    deltas = first(ins, "TargetBox").astype(jnp.float32)    # [R, 4C]
    score = first(ins, "BoxScore").astype(jnp.float32)      # [R, C]
    clip = op.attr("box_clip", float(np.log(1000.0 / 16.0)))
    R = prior.shape[0]
    C = score.shape[1]
    if ins.get("PriorBoxVar"):
        var = first(ins, "PriorBoxVar").astype(jnp.float32).reshape(R, 1, 4)
    else:
        var = jnp.asarray(op.attr("box_var", [0.1, 0.1, 0.2, 0.2]),
                          jnp.float32)[None, None, :]
    pw = prior[:, 2] - prior[:, 0] + 1.0
    ph = prior[:, 3] - prior[:, 1] + 1.0
    pcx = prior[:, 0] + pw * 0.5
    pcy = prior[:, 1] + ph * 0.5
    d = deltas.reshape(R, C, 4) * var
    cx = d[..., 0] * pw[:, None] + pcx[:, None]
    cy = d[..., 1] * ph[:, None] + pcy[:, None]
    bw = jnp.exp(jnp.minimum(d[..., 2], clip)) * pw[:, None]
    bh = jnp.exp(jnp.minimum(d[..., 3], clip)) * ph[:, None]
    decoded = jnp.stack([cx - bw / 2, cy - bh / 2,
                         cx + bw / 2 - 1, cy + bh / 2 - 1], axis=-1)  # [R, C, 4]
    best = jnp.argmax(score[:, 1:], axis=1) + 1  # skip background col 0
    assigned = jnp.take_along_axis(
        decoded, best[:, None, None].repeat(4, -1), axis=1)[:, 0]
    return {"DecodeBox": decoded.reshape(R, 4 * C),
            "OutputAssignBox": assigned}


@register_op("polygon_box_transform")
def _polygon_box_transform(ctx, op, ins):
    """reference detection/polygon_box_transform_op.cc: EAST geometry maps
    to absolute quad coordinates — even channels 4*w_idx - in, odd
    channels 4*h_idx - in."""
    x = first(ins, "Input")  # [N, 8k, H, W]
    N, G, H, W = x.shape
    wgrid = 4.0 * jnp.arange(W, dtype=jnp.float32)[None, None, None, :]
    hgrid = 4.0 * jnp.arange(H, dtype=jnp.float32)[None, None, :, None]
    even = (jnp.arange(G) % 2 == 0).reshape(1, G, 1, 1)
    return {"Output": jnp.where(even, wgrid - x, hgrid - x)}


@register_op("roi_perspective_transform")
def _roi_perspective_transform(ctx, op, ins):
    """reference detection/roi_perspective_transform_op.cc: each quad roi
    maps to a [transformed_h, transformed_w] patch via the closed-form
    quad->rect homography (get_transform_matrix:110); out-of-quad samples
    are 0.  Dense [R, 8] rois + RoisBatch vector (static-shape form)."""
    x_in = first(ins, "X")
    x = x_in.astype(jnp.float32)                     # [N, C, H, W]
    rois = first(ins, "ROIs").astype(jnp.float32)    # [R, 8]
    batch_idx = ins.get("RoisBatch")
    batch_idx = (batch_idx[0].reshape(-1).astype(jnp.int32)
                 if batch_idx else jnp.zeros((rois.shape[0],), jnp.int32))
    TH = op.attr("transformed_height")
    TW = op.attr("transformed_width")
    scale = op.attr("spatial_scale", 1.0)
    N, C, H, W = x.shape

    def one(roi, bi):
        rx = roi[0::2] * scale
        ry = roi[1::2] * scale
        x0, x1, x2, x3 = rx[0], rx[1], rx[2], rx[3]
        y0, y1, y2, y3 = ry[0], ry[1], ry[2], ry[3]
        len1 = jnp.sqrt((x0 - x1) ** 2 + (y0 - y1) ** 2)
        len2 = jnp.sqrt((x1 - x2) ** 2 + (y1 - y2) ** 2)
        len3 = jnp.sqrt((x2 - x3) ** 2 + (y2 - y3) ** 2)
        len4 = jnp.sqrt((x3 - x0) ** 2 + (y3 - y0) ** 2)
        est_h = (len2 + len4) / 2.0
        est_w = (len1 + len3) / 2.0
        nh = TH
        nw = jnp.minimum(jnp.round(est_w * (nh - 1) / jnp.maximum(est_h, 1e-6)) + 1,
                         TW)
        dx1, dx2, dx3 = x1 - x2, x3 - x2, x0 - x1 + x2 - x3
        dy1, dy2, dy3 = y1 - y2, y3 - y2, y0 - y1 + y2 - y3
        den = dx1 * dy2 - dx2 * dy1
        den = jnp.where(jnp.abs(den) < 1e-12, 1e-12, den)
        m6 = (dx3 * dy2 - dx2 * dy3) / den / jnp.maximum(nw - 1, 1.0)
        m7 = (dx1 * dy3 - dx3 * dy1) / den / jnp.maximum(nh - 1, 1.0)
        m3 = (y1 - y0 + m6 * (nw - 1) * y1) / jnp.maximum(nw - 1, 1.0)
        m4 = (y3 - y0 + m7 * (nh - 1) * y3) / jnp.maximum(nh - 1, 1.0)
        m5 = y0
        m0 = (x1 - x0 + m6 * (nw - 1) * x1) / jnp.maximum(nw - 1, 1.0)
        m1 = (x3 - x0 + m7 * (nh - 1) * x3) / jnp.maximum(nh - 1, 1.0)
        m2 = x0
        ow = jnp.arange(TW, dtype=jnp.float32)[None, :]
        oh = jnp.arange(TH, dtype=jnp.float32)[:, None]
        denom = m6 * ow + m7 * oh + 1.0
        in_w = (m0 * ow + m1 * oh + m2) / denom
        in_h = (m3 * ow + m4 * oh + m5) / denom
        # reference in_quad check: only output cells within the normalized
        # patch extent sample; extrapolated columns/rows are zero
        inside = ((in_w >= -0.5) & (in_w < W - 0.5)
                  & (in_h >= -0.5) & (in_h < H - 0.5)
                  & (ow < nw) & (oh < nh))
        # reference bilinear_interpolate clamps near-border coordinates to
        # the border pixel (unlike the deformable-conv zero-attenuation)
        wcl = jnp.clip(in_w, 0.0, W - 1.0)
        hcl = jnp.clip(in_h, 0.0, H - 1.0)
        yl = jnp.floor(hcl).astype(jnp.int32)
        xl = jnp.floor(wcl).astype(jnp.int32)
        yh = jnp.clip(yl + 1, 0, H - 1)
        xh = jnp.clip(xl + 1, 0, W - 1)
        fy = hcl - yl
        fx = wcl - xl
        img = x[bi]
        v = ((img[:, yl, xl] * (1 - fx) + img[:, yl, xh] * fx) * (1 - fy)
             + (img[:, yh, xl] * (1 - fx) + img[:, yh, xh] * fx) * fy)
        return jnp.where(inside[None], v, 0.0)

    out = jax.vmap(one)(rois, batch_idx)
    return {"Out": out.astype(x.dtype)}


@register_op("deformable_psroi_pooling")
def _deformable_psroi_pooling(ctx, op, ins):
    """Deformable position-sensitive RoI pooling (reference
    deformable_psroi_pooling_op.h): psroi bins whose start positions shift
    by learned per-part offsets (Trans, scaled by trans_std), each bin
    averaging sample_per_part^2 clamped bilinear samples; out-of-image
    samples are dropped from the average."""
    x_in = first(ins, "Input")
    x = x_in.astype(jnp.float32)                     # [N, C, H, W]
    rois = first(ins, "ROIs").astype(jnp.float32)    # [R, 4]
    trans = (first(ins, "Trans").astype(jnp.float32)
             if ins.get("Trans") else None)          # [R, 2*ncls, PH_p, PW_p]
    batch_idx = ins.get("RoisBatch")
    batch_idx = (batch_idx[0].reshape(-1).astype(jnp.int32)
                 if batch_idx else jnp.zeros((rois.shape[0],), jnp.int32))
    no_trans = op.attr("no_trans", False) or trans is None
    scale = op.attr("spatial_scale", 1.0)
    od = op.attr("output_dim")
    gh_, gw_ = op.attr("group_size", [1, 1])
    PH = op.attr("pooled_height", 1)
    PW = op.attr("pooled_width", 1)
    part_h, part_w = op.attr("part_size", [PH, PW])
    S = op.attr("sample_per_part", 1)
    trans_std = op.attr("trans_std", 0.1)
    N, C, H, W = x.shape
    ncls = 1 if no_trans else trans.shape[1] // 2
    cec = od if no_trans else od // ncls  # channels per class

    # static per-output-cell index tables
    ph_i, pw_i = np.meshgrid(np.arange(PH), np.arange(PW), indexing="ij")
    gh_i = np.clip((ph_i * gh_ // PH), 0, gh_ - 1)
    gw_i = np.clip((pw_i * gw_ // PW), 0, gw_ - 1)
    prt_h = np.floor(ph_i / PH * part_h).astype(np.int32)
    prt_w = np.floor(pw_i / PW * part_w).astype(np.int32)
    ct = np.arange(od)
    c_idx = ((ct[:, None, None] * gh_ + gh_i[None]) * gw_
             + gw_i[None])                        # [OD, PH, PW]
    cls_id = (ct // cec)                          # [OD]

    def one(roi, tr, bi):
        img = x[bi]
        x0 = jnp.round(roi[0]) * scale - 0.5
        y0 = jnp.round(roi[1]) * scale - 0.5
        x1 = (jnp.round(roi[2]) + 1.0) * scale - 0.5
        y1 = (jnp.round(roi[3]) + 1.0) * scale - 0.5
        rw = jnp.maximum(x1 - x0, 0.1)
        rh = jnp.maximum(y1 - y0, 0.1)
        bw, bh = rw / PW, rh / PH
        sw, sh = bw / S, bh / S
        if no_trans:
            tx = jnp.zeros((od, PH, PW))
            ty = jnp.zeros((od, PH, PW))
        else:
            tx = tr[2 * cls_id[:, None, None], prt_h[None], prt_w[None]] * trans_std
            ty = tr[2 * cls_id[:, None, None] + 1, prt_h[None], prt_w[None]] * trans_std
        wstart = pw_i[None] * bw + x0 + tx * rw   # [OD, PH, PW]
        hstart = ph_i[None] * bh + y0 + ty * rh
        ws = wstart[..., None, None] + np.arange(S)[None, None, None, None, :] * sw
        hs = hstart[..., None, None] + np.arange(S)[None, None, None, :, None] * sh
        valid = ((ws >= -0.5) & (ws <= W - 0.5) & (hs >= -0.5) & (hs <= H - 0.5))
        wc = jnp.clip(ws, 0.0, W - 1.0)
        hc = jnp.clip(hs, 0.0, H - 1.0)
        xl = jnp.floor(wc).astype(jnp.int32)
        yl = jnp.floor(hc).astype(jnp.int32)
        xh = jnp.clip(xl + 1, 0, W - 1)
        yh = jnp.clip(yl + 1, 0, H - 1)
        fx = wc - xl
        fy = hc - yl
        cmap = jnp.asarray(c_idx)[..., None, None]
        cmap = jnp.broadcast_to(cmap, ws.shape)
        v00 = img[cmap, yl, xl]
        v01 = img[cmap, yl, xh]
        v10 = img[cmap, yh, xl]
        v11 = img[cmap, yh, xh]
        val = ((v00 * (1 - fx) + v01 * fx) * (1 - fy)
               + (v10 * (1 - fx) + v11 * fx) * fy)
        val = jnp.where(valid, val, 0.0)
        cnt = jnp.sum(valid, axis=(-2, -1))
        avg = jnp.where(cnt > 0, jnp.sum(val, axis=(-2, -1))
                        / jnp.maximum(cnt, 1), 0.0)
        return avg, cnt.astype(jnp.float32)

    if no_trans:
        out, counts = jax.vmap(lambda r, b: one(r, None, b))(rois, batch_idx)
    else:
        out, counts = jax.vmap(one)(rois, trans, batch_idx)
    return {"Output": out.astype(x_in.dtype), "TopCount": counts}


def _np_rasterize_poly(poly, x0, y0, x1, y1, res):
    """Even-odd point-in-polygon over the res x res grid of the roi
    (reference mask_util.cc Poly2MaskWrapper's role; polygons in image
    coordinates)."""
    xs = x0 + (np.arange(res) + 0.5) * (x1 - x0) / res
    ys = y0 + (np.arange(res) + 0.5) * (y1 - y0) / res
    gx, gy = np.meshgrid(xs, ys)
    inside = np.zeros((res, res), bool)
    n = len(poly)
    j = n - 1
    for i in range(n):
        xi, yi = poly[i]
        xj, yj = poly[j]
        cond = ((yi > gy) != (yj > gy)) & (
            gx < (xj - xi) * (gy - yi) / (yj - yi + 1e-12) + xi)
        inside ^= cond
        j = i
    return inside.astype(np.int32)


@register_op("generate_mask_labels")
def _generate_mask_labels(ctx, op, ins):
    """Mask-RCNN mask targets (reference
    detection/generate_mask_labels_op.cc): for each sampled foreground roi,
    rasterize its matched gt polygon (best bbox IoU) into the roi-cropped
    resolution grid, expanded into the label's class block.

    STATIC-SHAPE form over the generate_proposal_labels outputs: Rois
    [N, R, 4], LabelsInt32 [N, R], GtSegms [N, G, P, 2] padded polygons
    (+ GtPolyLens [N, G] point counts, GtLod gt counts).  Outputs
    MaskInt32 [N, R, num_classes*res*res] and RoiHasMaskInt32 [N, R].
    Host-side geometry -> a jax.pure_callback, like detection_map."""
    rois = first(ins, "Rois").astype(jnp.float32)        # [N, R, 4]
    labels = first(ins, "LabelsInt32").astype(jnp.int32)  # [N, R]
    segms = first(ins, "GtSegms").astype(jnp.float32)    # [N, G, P, 2]
    N, G = segms.shape[0], segms.shape[1]
    poly_lens = (first(ins, "GtPolyLens").astype(jnp.int32)
                 if ins.get("GtPolyLens")
                 else jnp.full((N, G), segms.shape[2], jnp.int32))
    gt_lens = (first(ins, "GtLod").astype(jnp.int32) if ins.get("GtLod")
               else jnp.full((N,), G, jnp.int32))
    C = op.attr("num_classes")
    res = op.attr("resolution")
    R = rois.shape[1]

    def host(rois_v, labels_v, segms_v, plens_v, glens_v):
        masks = np.zeros((N, R, C * res * res), np.int32)
        has = np.zeros((N, R), np.int32)
        for i in range(N):
            polys = []
            for g in range(int(glens_v[i])):
                p = segms_v[i, g, :int(plens_v[i, g])]
                if len(p) >= 3:
                    polys.append(p)
            if not polys:
                continue
            boxes = np.array([[p[:, 0].min(), p[:, 1].min(),
                               p[:, 0].max(), p[:, 1].max()] for p in polys])
            for r in range(R):
                lab = int(labels_v[i, r])
                if lab <= 0:
                    continue
                bx = rois_v[i, r]
                ix = np.maximum(0, np.minimum(bx[2], boxes[:, 2])
                                - np.maximum(bx[0], boxes[:, 0]))
                iy = np.maximum(0, np.minimum(bx[3], boxes[:, 3])
                                - np.maximum(bx[1], boxes[:, 1]))
                inter = ix * iy
                ua = ((bx[2] - bx[0]) * (bx[3] - bx[1])
                      + (boxes[:, 2] - boxes[:, 0]) * (boxes[:, 3] - boxes[:, 1])
                      - inter)
                best = int(np.argmax(np.where(ua > 0, inter / np.maximum(ua, 1e-12), 0)))
                m = _np_rasterize_poly(polys[best], bx[0], bx[1], bx[2], bx[3],
                                       res)
                masks[i, r, lab * res * res:(lab + 1) * res * res] = m.reshape(-1)
                has[i, r] = 1
        return masks, has

    masks, has = jax.pure_callback(
        host,
        (jax.ShapeDtypeStruct((N, R, C * res * res), jnp.int32),
         jax.ShapeDtypeStruct((N, R), jnp.int32)),
        rois, labels, segms, poly_lens, gt_lens)
    return {"MaskInt32": masks, "RoiHasMaskInt32": has, "MaskRois": rois}
