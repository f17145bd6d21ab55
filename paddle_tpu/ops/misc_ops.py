"""Round-4 op-tail lowerings: the loss family, normalization/activation
stragglers, and small tensor utilities.

Reference kernels (paddle/fluid/operators/): hinge_loss_op.h, log_loss_op.h,
rank_loss_op.h, margin_rank_loss_op.h, bpr_loss_op.h, kldiv_loss_op.h,
modified_huber_loss_op.h, selu_op.h, lrn_op.cc, math/maxouting.cc,
multiplex_op.cc, reverse_op.cc, diag_op.cc, affine_channel_op.cc,
grid_sampler_op.h, affine_grid_op.cc, spectral_norm_op.h, row_conv_op.cc,
im2sequence_op.h, edit_distance_op.h, conv_op.cc (conv3d:579), pool_op.cc.
Each lowering re-derives the math in jnp; goldens in
tests/test_ops_round4.py follow the reference OpTest conventions.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from ..core.registry import register_op
from .common import canon_dtype, first, match_dtype


# --- loss family -----------------------------------------------------------

@register_op("hinge_loss")
def _hinge_loss(ctx, op, ins):
    x = first(ins, "Logits")
    y = first(ins, "Labels")
    return {"Loss": jnp.maximum(1.0 - x * (2.0 * y - 1.0), 0.0)}


@register_op("log_loss")
def _log_loss(ctx, op, ins):
    p = first(ins, "Predicted")
    y = first(ins, "Labels")
    eps = op.attr("epsilon", 1e-4)
    return {"Loss": -(y * jnp.log(p + eps)) - (1.0 - y) * jnp.log(1.0 - p + eps)}


@register_op("rank_loss")
def _rank_loss(ctx, op, ins):
    label = first(ins, "Label")
    left = first(ins, "Left")
    right = first(ins, "Right")
    return {"Out": jnp.log(1.0 + jnp.exp(left - right)) - label * (left - right)}


@register_op("margin_rank_loss")
def _margin_rank_loss(ctx, op, ins):
    label = first(ins, "Label")
    x1 = first(ins, "X1")
    x2 = first(ins, "X2")
    margin = op.attr("margin", 0.0)
    out = jnp.maximum(-label * (x1 - x2) + margin, 0.0)
    return {"Out": out, "Activated": (out > 0).astype(out.dtype)}


@register_op("bpr_loss")
def _bpr_loss(ctx, op, ins):
    """Bayesian Personalized Ranking (bpr_loss_op.h): for each row, mean over
    negatives j != label of log(1 + exp(x_j - x_label))."""
    x = first(ins, "X")
    label = first(ins, "Label")
    nclass = x.shape[-1]
    x2 = x.reshape(-1, nclass)
    lbl = label.reshape(-1).astype(jnp.int32)
    pos = jnp.take_along_axis(x2, lbl[:, None], axis=1)
    # loss_i = -sum_{j != lbl} -log(1+exp(x_j - x_pos)) / (C-1)
    lg = jnp.log1p(jnp.exp(x2 - pos))
    mask = jax.nn.one_hot(lbl, nclass, dtype=x.dtype)
    loss = jnp.sum(lg * (1.0 - mask), axis=1, keepdims=True) / (nclass - 1)
    return {"Y": loss.astype(x.dtype)}


@register_op("kldiv_loss")
def _kldiv_loss(ctx, op, ins):
    x = first(ins, "X")
    target = first(ins, "Target")
    red = op.attr("reduction", "mean")
    out = jnp.where(target > 0, target * (jnp.log(jnp.where(target > 0, target, 1.0)) - x), 0.0)
    if red == "none":
        return {"Loss": out}
    if red == "batchmean":
        return {"Loss": (jnp.sum(out) / x.shape[0]).reshape(())}
    if red == "sum":
        return {"Loss": jnp.sum(out).reshape(())}
    return {"Loss": jnp.mean(out).reshape(())}


@register_op("modified_huber_loss")
def _modified_huber_loss(ctx, op, ins):
    x = first(ins, "X")
    y = first(ins, "Y")
    inter = x * (2.0 * y - 1.0)
    loss = jnp.where(inter < -1.0, -4.0 * inter,
                     jnp.where(inter < 1.0, jnp.square(1.0 - inter), 0.0))
    return {"Out": loss, "IntermediateVal": inter}


# --- activations / norms ---------------------------------------------------

@register_op("selu")
def _selu(ctx, op, ins):
    x = first(ins, "X")
    alpha = op.attr("alpha", 1.6732632423543772)
    scale = op.attr("scale", 1.0507009873554805)
    return {"Out": scale * jnp.where(x > 0, x, alpha * jnp.exp(x) - alpha)}


@register_op("lrn")
def _lrn(ctx, op, ins):
    """lrn_op.cc LRNFunctor: mid = k + alpha * sliding-window channel sum of
    x^2 (window n centered with pre_pad=(n-1)/2), out = x * mid^-beta."""
    x = first(ins, "X")
    n = op.attr("n", 5)
    k = op.attr("k", 2.0)
    alpha = op.attr("alpha", 1e-4)
    beta = op.attr("beta", 0.75)
    pre = (n - 1) // 2
    sq = jnp.square(x)
    pad = jnp.pad(sq, ((0, 0), (pre, n - 1 - pre), (0, 0), (0, 0)))
    # windowed channel sum via cumsum difference (static shapes)
    csum = jnp.cumsum(pad, axis=1)
    csum = jnp.pad(csum, ((0, 0), (1, 0), (0, 0), (0, 0)))
    C = x.shape[1]
    win = csum[:, n:n + C] - csum[:, 0:C]
    mid = k + alpha * win
    return {"Out": x * jnp.power(mid, -beta), "MidOut": mid}


@register_op("maxout")
def _maxout(ctx, op, ins):
    """math/maxouting.cc: out channel c = max over input channels
    [c*groups, (c+1)*groups)."""
    x = first(ins, "X")
    g = op.attr("groups")
    N, C, H, W = x.shape
    return {"Out": x.reshape(N, C // g, g, H, W).max(axis=2)}


@register_op("affine_channel")
def _affine_channel(ctx, op, ins):
    x = first(ins, "X")
    scale = match_dtype(x, first(ins, "Scale"))
    bias = match_dtype(x, first(ins, "Bias"))
    if op.attr("data_layout", "NCHW") == "NHWC":
        return {"Out": x * scale + bias}
    shape = (1, x.shape[1]) + (1,) * (x.ndim - 2)
    return {"Out": x * scale.reshape(shape) + bias.reshape(shape)}


# --- tensor utilities ------------------------------------------------------

@register_op("multiplex")
def _multiplex(ctx, op, ins):
    xs = jnp.stack(ins["X"], axis=0)  # [n_candidates, batch, ...]
    ids = first(ins, "Ids").reshape(-1).astype(jnp.int32)
    rows = jnp.arange(ids.shape[0])
    return {"Out": xs[ids, rows]}


@register_op("reverse")
def _reverse(ctx, op, ins):
    x = first(ins, "X")
    axes = op.attr("axis")
    if isinstance(axes, int):
        axes = [axes]
    return {"Out": jnp.flip(x, axis=tuple(axes))}


@register_op("diag")
def _diag(ctx, op, ins):
    return {"Out": jnp.diag(first(ins, "Diagonal").reshape(-1))}


# --- 3-D conv / pool -------------------------------------------------------

@register_op("conv3d")
def _conv3d(ctx, op, ins):
    """conv_op.cc:579 Conv3D — NCDHW activations, OIDHW filters."""
    x = first(ins, "Input")
    w = match_dtype(x, first(ins, "Filter"))
    strides = tuple(op.attr("strides", [1, 1, 1]))
    pads = op.attr("paddings", [0, 0, 0])
    dilations = tuple(op.attr("dilations", [1, 1, 1]))
    groups = op.attr("groups", 1) or 1
    out = jax.lax.conv_general_dilated(
        x, w,
        window_strides=strides,
        padding=[(p, p) for p in pads],
        rhs_dilation=dilations,
        dimension_numbers=("NCDHW", "OIDHW", "NCDHW"),
        feature_group_count=groups,
    )
    return {"Output": out}


@register_op("pool3d")
def _pool3d(ctx, op, ins):
    x = first(ins, "X")
    ptype = op.attr("pooling_type", "max")
    ksize = list(op.attr("ksize", [2, 2, 2]))
    strides = list(op.attr("strides", [1, 1, 1]))
    pads = list(op.attr("paddings", [0, 0, 0]))
    if op.attr("global_pooling", False):
        ksize = list(x.shape[2:])
        strides = [1, 1, 1]
        pads = [0, 0, 0]
    window = (1, 1) + tuple(ksize)
    strides_full = (1, 1) + tuple(strides)
    lo_hi = [[p, p] for p in pads]
    if op.attr("ceil_mode", False):
        # pad the high side so the last partial window is kept
        for i in range(3):
            span = x.shape[2 + i] + 2 * pads[i] - ksize[i]
            rem = span % strides[i]
            if rem:
                lo_hi[i][1] += strides[i] - rem
    padcfg = ((0, 0), (0, 0)) + tuple((lo, hi) for lo, hi in lo_hi)
    if ptype == "max":
        init = -jnp.inf
        out = jax.lax.reduce_window(x, init, jax.lax.max, window, strides_full, padcfg)
    else:
        s = jax.lax.reduce_window(x, 0.0, jax.lax.add, window, strides_full, padcfg)
        if op.attr("exclusive", True):
            ones = jnp.ones_like(x)
            cnt = jax.lax.reduce_window(ones, 0.0, jax.lax.add, window, strides_full, padcfg)
            out = s / cnt
        else:
            out = s / float(np.prod(ksize))
    return {"Out": out.astype(x.dtype)}


# --- spatial transforms ----------------------------------------------------

@register_op("affine_grid")
def _affine_grid(ctx, op, ins):
    """affine_grid_op.cc: theta (N,2,3) x normalized [-1,1] base grid ->
    sampling grid (N,H,W,2).  Paddle 1.5 normalizes with align_corners=True
    semantics (linspace -1..1 inclusive)."""
    theta = first(ins, "Theta")
    if "OutputShape" in ins and ins["OutputShape"]:
        oshape = first(ins, "OutputShape")
        h, w = int(oshape[2]), int(oshape[3])
    else:
        shape = op.attr("output_shape")
        h, w = int(shape[2]), int(shape[3])
    ys = jnp.linspace(-1.0, 1.0, h)
    xs = jnp.linspace(-1.0, 1.0, w)
    gx, gy = jnp.meshgrid(xs, ys)  # (h, w)
    base = jnp.stack([gx, gy, jnp.ones_like(gx)], axis=-1)  # (h, w, 3)
    out = jnp.einsum("hwk,nck->nhwc", base.astype(theta.dtype), theta)
    return {"Output": out}


@register_op("grid_sampler")
def _grid_sampler(ctx, op, ins):
    """grid_sampler_op.h: bilinear sample x (N,C,H,W) at grid (N,H,W,2) in
    [-1,1], zero padding outside, align_corners=True scaling
    ((g+1)/2*(S-1))."""
    x = first(ins, "X")
    grid = first(ins, "Grid")
    N, C, H, W = x.shape
    gx = (grid[..., 0] + 1.0) / 2.0 * (W - 1)
    gy = (grid[..., 1] + 1.0) / 2.0 * (H - 1)
    x0 = jnp.floor(gx)
    y0 = jnp.floor(gy)
    x1 = x0 + 1
    y1 = y0 + 1

    def gather(yi, xi):
        valid = (xi >= 0) & (xi <= W - 1) & (yi >= 0) & (yi <= H - 1)
        xi_c = jnp.clip(xi, 0, W - 1).astype(jnp.int32)
        yi_c = jnp.clip(yi, 0, H - 1).astype(jnp.int32)
        # x: (N,C,H,W); index per-batch grid points
        v = jax.vmap(lambda img, yy, xx: img[:, yy, xx])(x, yi_c, xi_c)  # (N, C, Hg, Wg)?
        return v, valid

    v00, m00 = gather(y0, x0)
    v01, m01 = gather(y0, x1)
    v10, m10 = gather(y1, x0)
    v11, m11 = gather(y1, x1)
    wx1 = (gx - x0).astype(x.dtype)
    wy1 = (gy - y0).astype(x.dtype)
    wx0 = 1.0 - wx1
    wy0 = 1.0 - wy1

    def term(v, m, wgt):
        return v * (wgt * m.astype(x.dtype))[:, None]

    out = (term(v00, m00, wy0 * wx0) + term(v01, m01, wy0 * wx1)
           + term(v10, m10, wy1 * wx0) + term(v11, m11, wy1 * wx1))
    return {"Output": out}


# --- spectral norm ---------------------------------------------------------

@register_op("spectral_norm")
def _spectral_norm(ctx, op, ins):
    """spectral_norm_op.h: power-iterate U/V (as inputs, NOT updated in the
    program — matches the reference kernel which writes only Out), then
    Out = W / sigma with sigma = u^T W v."""
    w = first(ins, "Weight")
    u = first(ins, "U").reshape(-1)
    v = first(ins, "V").reshape(-1)
    dim = op.attr("dim", 0)
    power_iters = op.attr("power_iters", 1)
    eps = op.attr("eps", 1e-12)
    perm = (dim,) + tuple(i for i in range(w.ndim) if i != dim)
    wmat = jnp.transpose(w, perm).reshape(w.shape[dim], -1)

    def l2norm(a):
        return a / (jnp.linalg.norm(a) + eps)

    for _ in range(power_iters):
        v = l2norm(wmat.T @ u)
        u = l2norm(wmat @ v)
    u = jax.lax.stop_gradient(u)
    v = jax.lax.stop_gradient(v)
    sigma = u @ wmat @ v
    return {"Out": w / sigma}


# --- sequence stragglers ---------------------------------------------------

@register_op("row_conv")
def _row_conv(ctx, op, ins):
    """row_conv_op.cc lookahead convolution on a PADDED batch (B, T, D):
    out[t] = sum_{j=0..ctx-1} W[j] * x[t+j] (zeros past the end).  The
    ragged path feeds padded carriers (paddle_tpu/lod.py)."""
    x = first(ins, "X")
    w = match_dtype(x, first(ins, "Filter"))  # (future_context, D)
    fc = w.shape[0]
    out = jnp.zeros_like(x)
    for j in range(fc):
        shifted = jnp.pad(x[:, j:, :], ((0, 0), (0, j), (0, 0)))
        out = out + shifted * w[j]
    return {"Out": out}


@register_op("im2sequence")
def _im2sequence(ctx, op, ins):
    """im2sequence_op.h: extract kernel patches row-major into a sequence
    [N*oh*ow, kh*kw*C] (channel-minor per the reference's im2col layout:
    each row is [c0 patch, c1 patch, ...] flattened C-major)."""
    x = first(ins, "X")
    kh, kw = op.attr("kernels")
    strides = op.attr("strides", [1, 1])
    pads = op.attr("paddings", [0, 0, 0, 0])  # up, left, down, right
    N, C, H, W = x.shape
    xp = jnp.pad(x, ((0, 0), (0, 0), (pads[0], pads[2]), (pads[1], pads[3])))
    Hp, Wp = xp.shape[2], xp.shape[3]
    oh = (Hp - kh) // strides[0] + 1
    ow = (Wp - kw) // strides[1] + 1
    patches = jax.lax.conv_general_dilated_patches(
        xp, (kh, kw), tuple(strides), padding=[(0, 0), (0, 0)],
        dimension_numbers=("NCHW", "OIHW", "NCHW"))  # (N, C*kh*kw, oh, ow)
    seq = jnp.transpose(patches, (0, 2, 3, 1)).reshape(N * oh * ow, C * kh * kw)
    return {"Out": seq}


@register_op("edit_distance")
def _edit_distance(ctx, op, ins):
    """edit_distance_op.h Levenshtein DP over PADDED int batches
    (B, Tmax) + companion length vectors via the @LOD convention; the DP
    runs as a lax.scan over the hypothesis axis (static trip count)."""
    hyp = first(ins, "Hyps")
    ref = first(ins, "Refs")
    hyp_lens = first(ins, "HypsLen")
    ref_lens = first(ins, "RefsLen")
    norm = op.attr("normalized", False)
    # ragged carriers arrive (B, T, 1) (paddle_tpu/lod.py); tokens are (B, T)
    if hyp.ndim == 3 and hyp.shape[-1] == 1:
        hyp = hyp[..., 0]
    if ref.ndim == 3 and ref.shape[-1] == 1:
        ref = ref[..., 0]
    B, Th = hyp.shape[0], hyp.shape[1]
    Tr = ref.shape[1]
    hyp_lens = hyp_lens.reshape(-1).astype(jnp.int32)
    ref_lens = ref_lens.reshape(-1).astype(jnp.int32)

    # DP row: d[j] = edit distance between hyp[:i] and ref[:j]
    init = jnp.broadcast_to(jnp.arange(Tr + 1, dtype=jnp.float32), (B, Tr + 1))

    def step(carry, i):
        prev = carry  # (B, Tr+1)
        hi = hyp[:, i]  # (B,)
        in_hyp = (i < hyp_lens)
        cost = (hi[:, None] != ref).astype(jnp.float32)  # (B, Tr)
        # cur[0] = i+1; build left-to-right with the running value as carry
        def scan_j(cur, j):
            sub = prev[:, j] + cost[:, j]
            ins_ = cur + 1.0
            del_ = prev[:, j + 1] + 1.0
            nxt = jnp.minimum(jnp.minimum(sub, ins_), del_)
            return nxt, nxt

        first_col = jnp.full((B,), i + 1.0)
        _, rest = jax.lax.scan(scan_j, first_col, jnp.arange(Tr))
        cur = jnp.concatenate([first_col[:, None], jnp.transpose(rest)], axis=1)
        cur = jnp.where(in_hyp[:, None], cur, prev)
        return cur, None

    final, _ = jax.lax.scan(step, init, jnp.arange(Th))
    dist = jnp.take_along_axis(final, ref_lens[:, None], axis=1).reshape(-1)
    # empty-ref convention (edit_distance_op.h): distance = hyp_len
    dist = jnp.where(ref_lens == 0, hyp_lens.astype(jnp.float32), dist)
    if norm:
        dist = dist / jnp.maximum(ref_lens.astype(jnp.float32), 1.0)
    seq_num = jnp.asarray([B], jnp.int64 if False else jnp.int32)
    return {"Out": dist.reshape(-1, 1), "SequenceNum": seq_num}


# --- sampled / tree classifiers -------------------------------------------

@register_op("nce")
def _nce(ctx, op, ins):
    """nce_op.h: noise-contrastive estimation.  Per example the sampled-label
    row is [true labels | negative samples]; o = exp(logit), b = q(class) *
    num_neg, cost = -log(o/(o+b)) on true columns and -log(b/(o+b)) on
    negatives.  Negative sampling is in-trace (uniform / log-uniform via the
    threaded PRNG key; fixed custom_neg_classes for OpTest determinism).
    The reference's alias-table custom sampler (sampler=2) is served by the
    same categorical draw over CustomDistProbs."""
    x = first(ins, "Input")                      # (B, D)
    label = first(ins, "Label").astype(jnp.int32)  # (B, num_true)
    w = first(ins, "Weight")                     # (C, D)
    bias = first(ins, "Bias")                    # (C,) or None
    sample_weight = first(ins, "SampleWeight")
    num_total = op.attr("num_total_classes")
    num_neg = op.attr("num_neg_samples", 10)
    sampler = op.attr("sampler", 0)
    custom_negs = op.attr("custom_neg_classes", None)
    B = x.shape[0]
    num_true = label.shape[1] if label.ndim > 1 else 1
    label = label.reshape(B, num_true)

    if custom_negs:
        negs = jnp.broadcast_to(jnp.asarray(custom_negs, jnp.int32)[None, :],
                                (B, len(custom_negs)))
        num_neg = len(custom_negs)
    elif sampler == 1:
        # log-uniform: P(k) = log((k+2)/(k+1)) / log(range+2); sample via
        # inverse CDF of the continuous approximation (TF/candidate-sampling
        # trick): k = floor(exp(u * log(range+2)) - 1)
        u = jax.random.uniform(ctx.next_key(), (B, num_neg))
        rng_range = num_total - 1
        negs = jnp.floor(jnp.exp(u * np.log(rng_range + 2.0)) - 1.0).astype(jnp.int32)
        negs = jnp.clip(negs, 0, rng_range)
    elif sampler == 2:
        probs = first(ins, "CustomDistProbs")
        negs = jax.random.categorical(
            ctx.next_key(), jnp.log(jnp.maximum(probs, 1e-30))[None, :],
            shape=(B, num_neg)).astype(jnp.int32)
    else:
        negs = jax.random.randint(ctx.next_key(), (B, num_neg), 0, num_total,
                                  dtype=jnp.int32)

    samples = jnp.concatenate([label, negs], axis=1)       # (B, S)
    ws = jnp.take(w, samples, axis=0)                      # (B, S, D)
    logits = jnp.einsum("bsd,bd->bs", ws, x)
    if bias is not None:
        logits = logits + jnp.take(bias.reshape(-1), samples)
    o = jnp.exp(logits)

    if sampler == 1:
        rng_range = num_total - 1
        q = (jnp.log((samples + 2.0) / (samples + 1.0))
             / np.log(rng_range + 2.0))
    elif sampler == 2:
        probs = first(ins, "CustomDistProbs")
        q = jnp.take(probs, samples)
    else:
        q = jnp.full(samples.shape, 1.0 / num_total)
    b = q * num_neg

    is_true = jnp.arange(samples.shape[1])[None, :] < num_true
    cost = jnp.where(is_true, -jnp.log(o / (o + b)), -jnp.log(b / (o + b)))
    total = jnp.sum(cost, axis=1, keepdims=True)
    if sample_weight is not None:
        total = total * sample_weight.reshape(B, 1)
    return {"Cost": total.astype(x.dtype), "SampleLogits": logits,
            "SampleLabels": samples.astype(canon_dtype("int64"))}


@register_op("hierarchical_sigmoid")
def _hierarchical_sigmoid(ctx, op, ins):
    """hierarchical_sigmoid_op.h + math/matrix_bit_code.h SimpleCode: leaf
    encoding c = label + num_classes; path node for bit j is (c>>(j+1))-1,
    branch bit is (c>>j)&1; loss = sum softplus(clip(pre,-40,40)) over ALL
    code_length columns (out-of-path columns contribute softplus(0)=log 2,
    faithfully reproducing the reference's recorded quirk) minus sum of
    bit*pre over in-path columns."""
    x = first(ins, "X")                      # (B, D)
    w = first(ins, "W")                      # (num_classes-1, D)
    label = first(ins, "Label").astype(jnp.int32).reshape(-1)  # (B,)
    bias = first(ins, "Bias")
    path_table = first(ins, "PathTable")
    path_code = first(ins, "PathCode")
    num_classes = op.attr("num_classes")
    B = x.shape[0]

    if path_table is not None:
        # custom tree: per-class rows of node ids / branch codes, -1 padded
        nodes = jnp.take(path_table, label, axis=0).astype(jnp.int32)  # (B, L)
        bits = jnp.take(path_code, label, axis=0).astype(jnp.int32)
        valid = nodes >= 0
        nodes_c = jnp.maximum(nodes, 0)
    else:
        code_length = int(num_classes - 1).bit_length()
        c = label + num_classes
        js = jnp.arange(code_length, dtype=jnp.int32)
        shifted = jnp.right_shift(c[:, None], js[None, :] + 1)
        nodes = shifted - 1
        bits = jnp.bitwise_and(jnp.right_shift(c[:, None], js[None, :]), 1)
        valid = shifted > 0
        nodes_c = jnp.maximum(nodes, 0)

    pre = jnp.einsum("bld,bd->bl", jnp.take(w, nodes_c, axis=0), x)
    if bias is not None:
        pre = pre + jnp.take(bias.reshape(-1), nodes_c)
    pre = jnp.clip(pre, -40.0, 40.0)
    pre = jnp.where(valid, pre, 0.0)
    softplus = jnp.log1p(jnp.exp(pre))
    out = jnp.sum(softplus, axis=1, keepdims=True) - jnp.sum(
        jnp.where(valid, bits * pre, 0.0), axis=1, keepdims=True)
    return {"Out": out.astype(x.dtype), "PreOut": pre}


# --- in-program beam search ------------------------------------------------

@register_op("beam_search")
def _beam_search(ctx, op, ins):
    """One beam-search selection step — the TPU-native redesign of the
    reference's LoD-walking beam_search op (operators/math/beam_search.cc:24,
    beam_search_op.cc): state is STATIC [b, k] tensors carried through a
    lax.while_loop instead of LoDTensorArrays, so the whole decode compiles
    to one XLA program.

    Inputs: Logits (b*k, L, V) full decoder logits (the step row is
    dynamically indexed at StepIdx-1, folding the reference's per-step
    lod_tensor_array read into the op); Seqs (b, k, L) int64; Scores (b, k)
    f32; Finished (b, k) bool; StepIdx (1,) int.
    Finished beams extend only with end_id at zero cost (the reference's
    is_finished handling)."""
    logits = first(ins, "Logits")
    seqs = first(ins, "Seqs")
    scores = first(ins, "Scores")
    fin = first(ins, "Finished").astype(bool)
    t = jnp.reshape(first(ins, "StepIdx"), ()).astype(jnp.int32)
    k = op.attr("beam_size")
    eos = op.attr("end_id")
    b, kk, L = seqs.shape
    step_logits = jax.lax.dynamic_slice_in_dim(logits, t - 1, 1, axis=1)[:, 0, :]
    V = step_logits.shape[-1]
    logp = jax.nn.log_softmax(step_logits.astype(jnp.float32), axis=-1).reshape(b, k, V)
    fin_row = jnp.full((V,), -1e9, jnp.float32).at[eos].set(0.0)
    logp = jnp.where(fin[:, :, None], fin_row[None, None, :], logp)
    cand = scores.astype(jnp.float32)[:, :, None] + logp
    top_scores, top_idx = jax.lax.top_k(cand.reshape(b, k * V), k)
    parent = top_idx // V
    token = (top_idx % V).astype(seqs.dtype)
    new_seqs = jnp.take_along_axis(seqs, parent[:, :, None], axis=1)
    col = (jnp.arange(L) == t)[None, None, :]
    new_seqs = jnp.where(col, token[:, :, None], new_seqs)
    new_fin = jnp.take_along_axis(fin, parent, axis=1) | (token == eos)
    return {"SelectedSeqs": new_seqs, "SelectedScores": top_scores.astype(scores.dtype),
            "FinishedOut": new_fin}


@register_op("beam_search_decode")
def _beam_search_decode(ctx, op, ins):
    """Final-beam extraction (reference beam_search_decode_op.cc backtracked
    a LoDTensorArray; the static state makes it an argmax + gather).
    The length penalty matches the host-loop reference implementation:
    scores / len(seq)^alpha when the length_penalty attr is nonzero (len
    counts non-end_id tokens)."""
    seqs = first(ins, "Seqs")
    scores = first(ins, "Scores").astype(jnp.float32)
    eos = op.attr("end_id")
    lp = op.attr("length_penalty", 0.0)
    if lp:
        lengths = jnp.sum((seqs != eos).astype(jnp.float32), axis=-1)
        scores = scores / jnp.power(lengths, lp)
    best = jnp.argmax(scores, axis=1)
    ids = jnp.take_along_axis(seqs, best[:, None, None], axis=1)[:, 0, :]
    best_scores = jnp.take_along_axis(scores, best[:, None], axis=1)[:, 0]
    return {"SentenceIds": ids, "SentenceScores": best_scores}


@register_op("key_padding_bias")
def _key_padding_bias(ctx, op, ins):
    """[b, Tk] 0/1 mask -> additive [b, 1, 1, Tk] bias (dense sibling of
    attention_bias, which derives its mask from LoD lengths)."""
    m = first(ins, "X")
    bias = (1.0 - m.astype(jnp.float32)) * -1e9
    return {"Out": bias[:, None, None, :]}


@register_op("ctc_greedy_decoder")
def _ctc_greedy_decoder(ctx, op, ins):
    """reference ctc_align_op (layers.ctc_greedy_decoder): argmax per step,
    collapse repeats, drop blanks.  Static-shape form: padded [b, T] int
    tokens compacted to a prefix (stable sort on the drop mask) plus an
    output-lengths companion in place of the LoD result."""
    x = first(ins, "Input")           # [b, T, C] probs/logits
    lens = first(ins, "XLod")
    blank = op.attr("blank", 0)
    b, T, _ = x.shape
    ids = jnp.argmax(x, axis=-1).astype(jnp.int32)     # [b, T]
    prev = jnp.concatenate([jnp.full((b, 1), -1, jnp.int32), ids[:, :-1]], axis=1)
    valid = jnp.arange(T)[None, :] < lens[:, None]
    keep = valid & (ids != blank) & (ids != prev)
    # stable compaction: kept tokens to the front, order preserved
    order = jnp.argsort(jnp.where(keep, 0, 1), axis=1, stable=True)
    compacted = jnp.take_along_axis(ids, order, axis=1)
    out_lens = jnp.sum(keep, axis=1).astype(jnp.int32)
    pos_valid = jnp.arange(T)[None, :] < out_lens[:, None]
    out = jnp.where(pos_valid, compacted, 0)
    return {"Out": out[..., None], "OutLod": out_lens}


_CHUNK_SCHEMES = {
    # scheme: (num_tag_types, begin, inside, end, single)
    "IOB": (2, 0, 1, -1, -1),
    "IOE": (2, -1, 0, 1, -1),
    "IOBES": (4, 0, 1, 2, 3),
    "plain": (1, -1, -1, -1, -1),
}


def _np_chunks(labels, length, scheme, num_chunk_types, excluded):
    """reference chunk_eval_op.h GetSegments/ChunkBegin/ChunkEnd."""
    ntag, t_begin, t_inside, t_end, t_single = _CHUNK_SCHEMES[scheme]
    other = num_chunk_types
    segs = []
    in_chunk, start = False, 0
    tag, typ = -1, other

    def chunk_end(pt, pty, t, ty):
        if pty == other:
            return False
        if ty == other or ty != pty:
            return True
        if pt in (t_begin, t_inside) and pt >= 0:
            return t in (t_begin, t_single) and t >= 0
        if pt == t_end and pt >= 0:
            return True
        if pt == t_single and pt >= 0:
            return True
        return False

    def chunk_begin(pt, pty, t, ty):
        if pty == other:
            return ty != other
        if ty == other:
            return False
        if ty != pty:
            return True
        if t == t_begin and t >= 0:
            return True
        if t == t_inside and t >= 0:
            return pt in (t_end, t_single) and pt >= 0
        if t == t_end and t >= 0:
            return pt in (t_end, t_single) and pt >= 0
        if t == t_single and t >= 0:
            return True
        return False

    for i in range(int(length)):
        pt, pty = tag, typ
        lab = int(labels[i])
        tag = lab % ntag
        typ = lab // ntag
        if in_chunk and chunk_end(pt, pty, tag, typ):
            if pty not in excluded:
                segs.append((start, i - 1, pty))
            in_chunk = False
        if chunk_begin(pt, pty, tag, typ):
            start, in_chunk = i, True
    if in_chunk and typ not in excluded:
        segs.append((start, int(length) - 1, typ))
    return segs


@register_op("chunk_eval")
def _chunk_eval(ctx, op, ins):
    """Chunking metric (reference chunk_eval_op.h): precision/recall/F1 of
    predicted vs labeled chunks under IOB/IOE/IOBES/plain tag schemes.
    Pure metric -> host callback over padded [b, T] tags + lens."""
    inf = first(ins, "Inference").astype(jnp.int32)
    lab = first(ins, "Label").astype(jnp.int32)
    if inf.ndim == 3:
        inf = inf[..., 0]
    if lab.ndim == 3:
        lab = lab[..., 0]
    lens = first(ins, "XLod")
    scheme = op.attr("chunk_scheme", "IOB")
    nct = op.attr("num_chunk_types")
    excluded = set(op.attr("excluded_chunk_types", []) or [])

    def host(inf_v, lab_v, lens_v):
        n_inf = n_lab = n_cor = 0
        for i in range(inf_v.shape[0]):
            si = _np_chunks(inf_v[i], lens_v[i], scheme, nct, excluded)
            sl = _np_chunks(lab_v[i], lens_v[i], scheme, nct, excluded)
            n_inf += len(si)
            n_lab += len(sl)
            n_cor += len(set(si) & set(sl))
        p = n_cor / n_inf if n_inf else 0.0
        r = n_cor / n_lab if n_lab else 0.0
        f1 = 2 * p * r / (p + r) if p + r else 0.0
        return (np.float32(p), np.float32(r), np.float32(f1),
                np.int32(n_inf), np.int32(n_lab), np.int32(n_cor))

    shapes = (jax.ShapeDtypeStruct((), jnp.float32),) * 3 + (
        jax.ShapeDtypeStruct((), jnp.int32),) * 3
    p, r, f1, ni, nl, nc = jax.pure_callback(host, shapes, inf, lab, lens)
    return {"Precision": p.reshape(1), "Recall": r.reshape(1),
            "F1-Score": f1.reshape(1), "NumInferChunks": ni.reshape(1),
            "NumLabelChunks": nl.reshape(1), "NumCorrectChunks": nc.reshape(1)}


@register_op("sample_logits")
def _sample_logits(ctx, op, ins):
    """Sampled softmax (reference sample_logits_op.cc, the kernel behind
    layers.sampled_softmax_with_cross_entropy): per row, unite the true
    labels with log-uniform negative samples, adjust each sampled logit by
    -log(expected_probability) (the sampled-softmax correction), mask
    accidental hits, and return the sampled logits + the in-sample label
    positions for a regular softmax CE."""
    logits = first(ins, "Logits")        # [B, C]
    label = first(ins, "Labels").astype(jnp.int32)  # [B, num_true]
    num_samples = op.attr("num_samples")
    remove_accidental = op.attr("remove_accidental_hits", True)
    B, C = logits.shape
    num_true = label.shape[1] if label.ndim > 1 else 1
    label = label.reshape(B, num_true)

    # log-uniform sampling (same inverse-CDF trick as the nce lowering)
    u = jax.random.uniform(ctx.next_key(), (B, num_samples))
    rng_range = C - 1
    negs = jnp.floor(jnp.exp(u * np.log(rng_range + 2.0)) - 1.0).astype(jnp.int32)
    negs = jnp.clip(negs, 0, rng_range)
    samples = jnp.concatenate([label, negs], axis=1)      # [B, T+S]

    q = (jnp.log((samples + 2.0) / (samples + 1.0)) / np.log(rng_range + 2.0))
    sampled = jnp.take_along_axis(logits, samples, axis=1)
    sampled = sampled - jnp.log(jnp.maximum(q * num_samples, 1e-20))
    # `uniq` semantics, static-shape form: a duplicate draw within the row
    # (and, with remove_accidental_hits, any draw equal to a true label)
    # is masked out of the sampled softmax instead of being resampled
    dup = (negs[:, :, None] == negs[:, None, :]) & (
        jnp.arange(num_samples)[None, :, None]
        > jnp.arange(num_samples)[None, None, :])
    drop = dup.any(-1)                                     # [B, S]
    if remove_accidental:
        drop = drop | (negs[:, :, None] == label[:, None, :]).any(-1)
    mask = jnp.concatenate([jnp.zeros((B, num_true), bool), drop], axis=1)
    sampled = jnp.where(mask, -1e20, sampled)
    pos = jnp.broadcast_to(jnp.arange(num_true, dtype=jnp.int32)[None, :],
                           (B, num_true))
    return {"SampledLogits": sampled, "SampledLabels": pos,
            "Samples": samples, "Probabilities": q}


def tree_conv_math(nodes, edges, w, max_depth):
    """TBCNN tree convolution (reference tree_conv_op.h +
    math/tree2col.cc).  nodes [N, F]; edges [E, 2] 1-indexed (0,0) padded;
    w [F, 3, out, nf].

    tree2col, traced: the DFS patch of root u = u plus descendants at
    depth d < max_depth; descendant-at-depth masks come from boolean
    powers of the child adjacency, and each node's continuous position
    weights (eta_t/l/r over depth, sibling index, sibling count) are
    node-local, so the whole patch tensor is one [N, N, 3] contraction —
    the MXU sees two matmuls."""
    N, F = nodes.shape
    E = edges.shape[0]
    valid = (edges[:, 0] > 0) & (edges[:, 1] > 0)
    par = jnp.where(valid, edges[:, 0], 0)  # 1-indexed parents
    chd = jnp.where(valid, edges[:, 1], 0)
    node_count = jnp.sum(valid) + 1

    # sibling order: rank of edge among earlier edges with the same parent
    same = (par[None, :] == par[:, None]) & valid[None, :] & valid[:, None]
    earlier = same & (jnp.arange(E)[None, :] < jnp.arange(E)[:, None])
    index = jnp.sum(earlier, axis=1) + 1               # [E], 1-based
    pclen = jnp.sum(same, axis=1)                      # [E]

    # per-node (index, pclen) scattered from edges (0-indexed node slots)
    idx_of = jnp.ones((N + 1,), jnp.float32).at[chd].set(
        jnp.where(valid, index.astype(jnp.float32), 1.0))
    pclen_of = jnp.ones((N + 1,), jnp.float32).at[chd].set(
        jnp.where(valid, pclen.astype(jnp.float32), 1.0))
    idx_of = idx_of[1:]      # [N] (slot i = node i+1)
    pclen_of = pclen_of[1:]

    # child adjacency A[u, v] = v is child of u (0-indexed slots)
    A = jnp.zeros((N + 1, N + 1), jnp.float32).at[par, chd].add(
        jnp.where(valid, 1.0, 0.0))
    A = jnp.minimum(A[1:, 1:], 1.0)

    fd = float(max_depth)
    out3 = jnp.zeros((N, N, 3), jnp.float32)
    # depth 0: the root itself (index 1, pclen 1): eta_t=1, eta_l=eta_r=0
    out3 = out3.at[jnp.arange(N), jnp.arange(N), 2].set(1.0)
    reach = A
    for d in range(1, max_depth):
        eta_t = (fd - d) / fd
        temp = jnp.where(pclen_of == 1.0, 0.5,
                         (idx_of - 1.0) / jnp.maximum(pclen_of - 1.0, 1e-12))
        eta_l = (1.0 - eta_t) * temp
        eta_r = (1.0 - eta_t) * (1.0 - eta_l)
        out3 = out3.at[:, :, 0].add(reach * eta_l[None, :])
        out3 = out3.at[:, :, 1].add(reach * eta_r[None, :])
        out3 = out3.at[:, :, 2].add(reach * eta_t)
        if d + 1 < max_depth:
            reach = jnp.minimum(reach @ A, 1.0)

    patch = jnp.einsum("uvk,vf->ufk", out3, nodes.astype(jnp.float32))
    patch = patch.reshape(N, 3 * F)               # (f, k)-major = W's flatten
    out = patch @ w.reshape(3 * F, -1)            # [N, out*nf]
    out_size, nf = w.shape[2], w.shape[3]
    is_node = (jnp.arange(N) < node_count)[:, None, None]
    return jnp.where(is_node, out.reshape(N, out_size, nf), 0.0)


@register_op("tree_conv")
def _tree_conv(ctx, op, ins):
    nodes = first(ins, "NodesVector")   # [B, N, F]
    edges = first(ins, "EdgeSet").astype(jnp.int32)  # [B, E, 2]
    w = first(ins, "Filter").astype(jnp.float32)     # [F, 3, out, nf]
    max_depth = op.attr("max_depth", 2)
    out = jax.vmap(lambda n, e: tree_conv_math(n, e, w, max_depth))(
        nodes, edges)
    return {"Out": out.astype(nodes.dtype)}


@register_op("similarity_focus")
def _similarity_focus(ctx, op, ins):
    """reference similarity_focus_op.h: for each selected index on `axis`,
    greedily pick max-valued positions whose remaining two coordinate lines
    are untagged (a greedy assignment over the plane), and set the focus
    mask 1 across the whole axis at the picked positions."""
    x_in = first(ins, "X")
    x = x_in.astype(jnp.float32)  # [B, d1, d2, d3]
    axis = op.attr("axis")
    indexes = list(op.attr("indexes"))
    if axis not in (1, 2, 3):
        raise NotImplementedError("similarity_focus: axis must be 1, 2 or 3")
    # canonicalize to axis=1
    perm = {1: (0, 1, 2, 3), 2: (0, 2, 1, 3), 3: (0, 3, 1, 2)}[axis]
    inv = {1: (0, 1, 2, 3), 2: (0, 2, 1, 3), 3: (0, 2, 3, 1)}[axis]
    xc = jnp.transpose(x, perm)  # [B, A, P, Q]
    B, A, P, Q = xc.shape
    steps = min(P, Q)

    def one(plane):  # [P, Q] -> mask [P, Q]
        def body(_, state):
            mask, tag_p, tag_q = state
            avail = ~tag_p[:, None] & ~tag_q[None, :]
            cand = jnp.where(avail, plane, -jnp.inf)
            flat = jnp.argmax(cand)
            p, q = flat // Q, flat % Q
            mask = mask.at[p, q].set(1.0)
            return mask, tag_p.at[p].set(True), tag_q.at[q].set(True)

        m, _, _ = jax.lax.fori_loop(
            0, steps, body,
            (jnp.zeros((P, Q)), jnp.zeros((P,), bool), jnp.zeros((Q,), bool)))
        return m

    masks = [jax.vmap(one)(xc[:, idx]) for idx in indexes]
    total = masks[0]
    for m in masks[1:]:
        total = jnp.maximum(total, m)
    out = jnp.broadcast_to(total[:, None], (B, A, P, Q))
    return {"Out": jnp.transpose(out, inv).astype(x_in.dtype)}


_XXP1 = np.uint64(0x9E3779B185EBCA87)
_XXP2 = np.uint64(0xC2B2AE3D27D4EB4F)
_XXP3 = np.uint64(0x165667B19E3779F9)
_XXP4 = np.uint64(0x85EBCA77C2B2AE63)
_XXP5 = np.uint64(0x27D4EB2F165667C5)


def _rotl64(x, r):
    r = np.uint64(r)
    return (x << r) | (x >> (np.uint64(64) - r))


def _xxh64(data: bytes, seed: int) -> int:
    """XXH64 (the exact hash the reference hash_op links); numpy uint64
    transcription of the specification, validated against the published
    test vectors in tests."""
    with np.errstate(over="ignore"):
        seed = np.uint64(seed)
        n = len(data)
        i = 0
        if n >= 32:
            v = [seed + _XXP1 + _XXP2, seed + _XXP2, seed + np.uint64(0),
                 seed - _XXP1]
            while i + 32 <= n:
                for k in range(4):
                    lane = np.uint64(int.from_bytes(data[i + 8 * k:i + 8 * k + 8],
                                                    "little"))
                    v[k] = _rotl64(v[k] + lane * _XXP2, 31) * _XXP1
                i += 32
            acc = (_rotl64(v[0], 1) + _rotl64(v[1], 7) + _rotl64(v[2], 12)
                   + _rotl64(v[3], 18))
            for vk in v:
                acc ^= _rotl64(vk * _XXP2, 31) * _XXP1
                acc = acc * _XXP1 + _XXP4
        else:
            acc = seed + _XXP5
        acc = acc + np.uint64(n)
        while i + 8 <= n:
            lane = np.uint64(int.from_bytes(data[i:i + 8], "little"))
            acc ^= _rotl64(lane * _XXP2, 31) * _XXP1
            acc = _rotl64(acc, 27) * _XXP1 + _XXP4
            i += 8
        if i + 4 <= n:
            lane = np.uint64(int.from_bytes(data[i:i + 4], "little"))
            acc ^= lane * _XXP1
            acc = _rotl64(acc, 23) * _XXP2 + _XXP3
            i += 4
        while i < n:
            acc ^= np.uint64(data[i]) * _XXP5
            acc = _rotl64(acc, 11) * _XXP1
            i += 1
        acc ^= acc >> np.uint64(33)
        acc *= _XXP2
        acc ^= acc >> np.uint64(29)
        acc *= _XXP3
        acc ^= acc >> np.uint64(32)
        return int(acc)


# --- in-graph 64-bit arithmetic on (hi, lo) uint32 pairs -------------------
# JAX runs x32 here, so XXH64 is built from vectorized uint32 ops.  Every
# byte position is static (input rows have static shape), so the whole
# digest unrolls at trace time into plain VPU arithmetic — no host
# callback on the lookup path.

def _u64c(v):
    """python int -> ((hi, lo) uint32 scalar constants)."""
    return (jnp.uint32((v >> 32) & 0xFFFFFFFF), jnp.uint32(v & 0xFFFFFFFF))


def _add64(a, b):
    lo = a[1] + b[1]
    carry = (lo < b[1]).astype(jnp.uint32)
    return (a[0] + b[0] + carry, lo)


def _xor64(a, b):
    return (a[0] ^ b[0], a[1] ^ b[1])


def _shr64(a, r):
    if r == 0:
        return a
    if r < 32:
        return (a[0] >> r, (a[1] >> r) | (a[0] << (32 - r)))
    if r == 32:
        return (jnp.zeros_like(a[0]), a[0])
    return (jnp.zeros_like(a[0]), a[0] >> (r - 32))


def _shl64(a, r):
    if r == 0:
        return a
    if r < 32:
        return ((a[0] << r) | (a[1] >> (32 - r)), a[1] << r)
    if r == 32:
        return (a[1], jnp.zeros_like(a[1]))
    return (a[1] << (r - 32), jnp.zeros_like(a[1]))


def _rot64(a, r):
    s, t = _shl64(a, r), _shr64(a, 64 - r)
    return (s[0] | t[0], s[1] | t[1])


def _mul32x32(a, b):
    """uint32 x uint32 -> (hi, lo) full 64-bit product (16-bit split)."""
    a0, a1 = a & 0xFFFF, a >> 16
    b0, b1 = b & 0xFFFF, b >> 16
    p00 = a0 * b0
    p01 = a0 * b1
    p10 = a1 * b0
    p11 = a1 * b1
    mid = (p00 >> 16) + (p01 & 0xFFFF) + (p10 & 0xFFFF)
    lo = (mid << 16) | (p00 & 0xFFFF)
    hi = p11 + (p01 >> 16) + (p10 >> 16) + (mid >> 16)
    return (hi, lo)


def _mul64(a, b):
    hi, lo = _mul32x32(a[1], b[1])
    return (hi + a[1] * b[0] + a[0] * b[1], lo)


def _mod64_u31(a, m):
    """(hi, lo) mod m for m < 2^31: 64-step restoring division (static
    unroll of cheap vector ops; remainder always fits uint32)."""
    m = jnp.uint32(m)
    r = jnp.zeros_like(a[0])
    for word in (a[0], a[1]):
        for bit in range(31, -1, -1):
            r = (r << 1) | ((word >> bit) & jnp.uint32(1))
            r = jnp.where(r >= m, r - m, r)
    return r


def _xxh64_jnp(words, seed):
    """Vectorized XXH64 over rows of uint32 `words` [rows, last] (each word
    = 4 little-endian bytes, matching int32 rows), python-int seed.
    Returns (hi, lo) uint32 arrays [rows].  Mirrors _xxh64 (the numpy spec
    oracle) with every loop unrolled over the static byte length."""
    rows, last = words.shape
    n = 4 * last
    P1, P2, P3, P4, P5 = (_u64c(int(_XXP1)), _u64c(int(_XXP2)),
                          _u64c(int(_XXP3)), _u64c(int(_XXP4)),
                          _u64c(int(_XXP5)))

    def bc(c64):
        return (jnp.broadcast_to(c64[0], (rows,)), jnp.broadcast_to(c64[1], (rows,)))

    def lane8(i):  # 8-byte lane starting at word index i: lo = words[i]
        return (words[:, i + 1], words[:, i])

    seed64 = _u64c(seed & 0xFFFFFFFFFFFFFFFF)
    i = 0
    if n >= 32:
        v = [bc(_add64(_add64(seed64, P1), P2)), bc(_add64(seed64, P2)),
             bc(seed64), bc(_add64(seed64, _u64c((-int(_XXP1)) & 0xFFFFFFFFFFFFFFFF)))]
        while 4 * i + 32 <= n:
            for k in range(4):
                v[k] = _mul64(_rot64(_add64(v[k], _mul64(lane8(i + 2 * k), P2)), 31), P1)
            i += 8
        acc = _add64(_add64(_rot64(v[0], 1), _rot64(v[1], 7)),
                     _add64(_rot64(v[2], 12), _rot64(v[3], 18)))
        for vk in v:
            acc = _xor64(acc, _mul64(_rot64(_mul64(vk, P2), 31), P1))
            acc = _add64(_mul64(acc, P1), P4)
    else:
        acc = bc(_add64(seed64, P5))
    acc = _add64(acc, bc(_u64c(n)))
    while 4 * i + 8 <= n:
        acc = _xor64(acc, _mul64(_rot64(_mul64(lane8(i), P2), 31), P1))
        acc = _add64(_mul64(_rot64(acc, 27), P1), P4)
        i += 2
    if 4 * i + 4 <= n:
        lane = (jnp.zeros_like(words[:, i]), words[:, i])
        acc = _xor64(acc, _mul64(lane, P1))
        acc = _add64(_mul64(_rot64(acc, 23), P2), P3)
        i += 1
    # n is always a multiple of 4 (int32 rows): the 1-byte tail never runs
    acc = _xor64(acc, _shr64(acc, 33))
    acc = _mul64(acc, P2)
    acc = _xor64(acc, _shr64(acc, 29))
    acc = _mul64(acc, P3)
    acc = _xor64(acc, _shr64(acc, 32))
    return acc


@register_op("hash")
def _hash(ctx, op, ins):
    """reference hash_op.h: per input row, num_hash XXH64 digests (seed =
    hash index) of the row's int32 bytes, mod mod_by.  The exact hash
    function is the contract (embedding slots depend on it); the digest is
    computed IN-GRAPH as vectorized uint32-pair arithmetic (no host
    callback on the embedding-slot path), pinned against
    the numpy spec oracle + published test vectors in tests."""
    x = first(ins, "X").astype(jnp.int32)
    mod_by = op.attr("mod_by")
    num_hash = op.attr("num_hash", 1)
    rows = int(np.prod(x.shape[:-1]))
    last = x.shape[-1]
    words = jax.lax.bitcast_convert_type(x.reshape(rows, last), jnp.uint32)
    outs = []
    for j in range(num_hash):
        digest = _xxh64_jnp(words, j)
        outs.append(_mod64_u31(digest, mod_by).astype(jnp.int32))
    out = jnp.stack(outs, axis=-1)
    return {"Out": out.reshape(tuple(x.shape[:-1]) + (num_hash,))}
