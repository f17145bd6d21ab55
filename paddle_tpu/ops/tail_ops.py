"""API-tail op lowerings (r3 review #6 audit): the remaining reference op
families behind `paddle.fluid.layers` entries that had no lowering yet.
Each cites its reference kernel; gradients come from autodiff.
"""
from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp

from ..core.registry import register_op
from .common import first, np_dtype as _np_dtype


# --- activations (reference operators/activation_op.h functors) -----------

@register_op("brelu")
def _brelu(ctx, op, ins):
    x = first(ins, "X")
    return {"Out": jnp.clip(x, op.attr("t_min", 0.0), op.attr("t_max", 24.0))}


@register_op("soft_relu")
def _soft_relu(ctx, op, ins):
    x = first(ins, "X")
    t = op.attr("threshold", 40.0)
    return {"Out": jnp.log1p(jnp.exp(jnp.clip(x, -t, t)))}


@register_op("thresholded_relu")
def _thresholded_relu(ctx, op, ins):
    x = first(ins, "X")
    t = op.attr("threshold", 1.0)
    return {"Out": jnp.where(x > t, x, 0.0).astype(x.dtype)}


# --- logic / reductions ---------------------------------------------------

@register_op("logical_xor")
def _logical_xor(ctx, op, ins):
    return {"Out": jnp.logical_xor(first(ins, "X"), first(ins, "Y"))}


from ..core import analysis as _A

_A.register_elementwise_infer("logical_xor", out_dtype="bool")


def _bool_reduce(fn):
    def lower(ctx, op, ins):
        x = first(ins, "X").astype(bool)
        dim = op.attr("dim", None)
        keep = op.attr("keep_dim", False)
        axes = tuple(d % x.ndim for d in dim) if dim else None
        return {"Out": fn(x, axis=axes, keepdims=keep)}
    return lower


register_op("reduce_all")(_bool_reduce(jnp.all))
register_op("reduce_any")(_bool_reduce(jnp.any))


@register_op("has_inf")
def _has_inf(ctx, op, ins):
    return {"Out": jnp.any(jnp.isinf(first(ins, "X"))).reshape((1,))}


@register_op("has_nan")
def _has_nan(ctx, op, ins):
    return {"Out": jnp.any(jnp.isnan(first(ins, "X"))).reshape((1,))}


@register_op("is_empty")
def _is_empty(ctx, op, ins):
    return {"Out": jnp.asarray([first(ins, "X").size == 0])}


# --- losses ---------------------------------------------------------------

@register_op("cos_sim")
def _cos_sim(ctx, op, ins):
    """reference cos_sim_op.h: per-row cosine; Y may be [1, D] (broadcast)."""
    x = first(ins, "X")
    y = first(ins, "Y")
    xn = jnp.sqrt(jnp.sum(jnp.square(x), axis=-1, keepdims=True))
    yn = jnp.sqrt(jnp.sum(jnp.square(y), axis=-1, keepdims=True))
    dot = jnp.sum(x * y, axis=-1, keepdims=True)
    return {"Out": dot / jnp.maximum(xn * yn, 1e-12),
            "XNorm": xn, "YNorm": yn}


@register_op("smooth_l1_loss")
def _smooth_l1_loss(ctx, op, ins):
    """reference smooth_l1_loss_op.h: huber with sigma^2 scaling and
    inside/outside weights; per-row sum -> [N, 1]."""
    x = first(ins, "X")
    y = first(ins, "Y")
    sigma = op.attr("sigma", 1.0)
    s2 = sigma * sigma
    inw = first(ins, "InsideWeight") if ins.get("InsideWeight") else jnp.ones_like(x)
    outw = first(ins, "OutsideWeight") if ins.get("OutsideWeight") else jnp.ones_like(x)
    d = (x - y) * inw
    ad = jnp.abs(d)
    el = jnp.where(ad < 1.0 / s2, 0.5 * d * d * s2, ad - 0.5 / s2) * outw
    n = x.shape[0]
    return {"Out": jnp.sum(el.reshape(n, -1), axis=1, keepdims=True),
            "Diff": d}


@register_op("teacher_student_sigmoid_loss")
def _ts_sigmoid_loss(ctx, op, ins):
    """reference teacher_student_sigmoid_loss_op.h:26 label encoding:
    label<-1: no q, clk=0; label in [-1,0): no q, clk=1; [0,1): q=label,
    clk=0; >=1: q=label-1, clk=1."""
    x = first(ins, "X").reshape(-1)
    z = first(ins, "Label").reshape(-1).astype(x.dtype)
    base = jnp.maximum(x, 0.0) + jnp.log1p(jnp.exp(-jnp.abs(x)))
    no_q_clk0 = base
    no_q_clk1 = base - x
    q_clk0 = base + base - x * z
    q_clk1 = base - x + base - x * (z - 1.0)
    out = jnp.where(z < -1.0, no_q_clk0,
                    jnp.where(z < 0.0, no_q_clk1,
                              jnp.where(z < 1.0, q_clk0, q_clk1)))
    return {"Y": out.reshape(-1, 1)}


# --- shape shufflers ------------------------------------------------------

@register_op("pixel_shuffle")
def _pixel_shuffle(ctx, op, ins):
    """reference pixel_shuffle_op.h: [N, C*r^2, H, W] -> [N, C, H*r, W*r]."""
    x = first(ins, "X")
    r = int(op.attr("upscale_factor"))
    n, c, h, w = x.shape
    x = x.reshape(n, c // (r * r), r, r, h, w)
    x = x.transpose(0, 1, 4, 2, 5, 3)
    return {"Out": x.reshape(n, c // (r * r), h * r, w * r)}


@register_op("shuffle_channel")
def _shuffle_channel(ctx, op, ins):
    """reference shuffle_channel_op.h: transpose group and channel/group."""
    x = first(ins, "X")
    g = int(op.attr("group", 1))
    n, c, h, w = x.shape
    return {"Out": x.reshape(n, g, c // g, h, w).transpose(0, 2, 1, 3, 4)
                    .reshape(n, c, h, w)}


@register_op("temporal_shift")
def _temporal_shift(ctx, op, ins):
    """reference temporal_shift_op.h: shift 1st channel quarter backward in
    time, 2nd forward, rest untouched (zero padding at the ends)."""
    x = first(ins, "X")  # [N*T, C, H, W]
    t = int(op.attr("seg_num"))
    ratio = op.attr("shift_ratio", 0.25)
    nt, c, h, w = x.shape
    n = nt // t
    c1 = int(c * ratio)
    c2 = int(c * 2 * ratio)
    v = x.reshape(n, t, c, h, w)
    back = jnp.concatenate([v[:, 1:, :c1], jnp.zeros_like(v[:, :1, :c1])], axis=1)
    fwd = jnp.concatenate([jnp.zeros_like(v[:, :1, c1:c2]), v[:, :-1, c1:c2]], axis=1)
    out = jnp.concatenate([back, fwd, v[:, :, c2:]], axis=2)
    return {"Out": out.reshape(nt, c, h, w)}


@register_op("fsp")
def _fsp(ctx, op, ins):
    """reference fsp_op.h: flow-of-solution-procedure matrix
    [b, c1, h, w] x [b, c2, h, w] -> [b, c1, c2] / (h*w)."""
    x = first(ins, "X")
    y = first(ins, "Y")
    b, c1, h, w = x.shape
    return {"Out": jnp.einsum("bchw,bdhw->bcd", x, y) / (h * w)}


@register_op("unfold")
def _unfold(ctx, op, ins):
    """reference unfold_op.h (im2col): [N, C, H, W] ->
    [N, C*kh*kw, L] with (C, kh, kw)-major patch layout."""
    x = first(ins, "X")
    kh, kw = op.attr("kernel_sizes")
    sh, sw = op.attr("strides", [1, 1])
    pads = op.attr("paddings", [0, 0, 0, 0])
    if len(pads) == 2:
        pads = [pads[0], pads[1], pads[0], pads[1]]
    dh, dw = op.attr("dilations", [1, 1])
    n, c, H, W = x.shape
    xp = jnp.pad(x, ((0, 0), (0, 0), (pads[0], pads[2]), (pads[1], pads[3])))
    oh = (H + pads[0] + pads[2] - (dh * (kh - 1) + 1)) // sh + 1
    ow = (W + pads[1] + pads[3] - (dw * (kw - 1) + 1)) // sw + 1
    cols = []
    for i in range(kh):
        for j in range(kw):
            patch = jax.lax.slice(
                xp, (0, 0, i * dh, j * dw),
                (n, c, i * dh + (oh - 1) * sh + 1, j * dw + (ow - 1) * sw + 1),
                (1, 1, sh, sw))
            cols.append(patch)  # [n, c, oh, ow]
    out = jnp.stack(cols, axis=2)  # [n, c, kh*kw, oh, ow]
    return {"Y": out.reshape(n, c * kh * kw, oh * ow)}


# --- adaptive pooling -----------------------------------------------------

def _adaptive_masks(in_size, out_size):
    """reference pool_op adaptive start/end: floor(i*H/out), ceil((i+1)*H/out)."""
    starts = [int(np.floor(i * in_size / out_size)) for i in range(out_size)]
    ends = [int(np.ceil((i + 1) * in_size / out_size)) for i in range(out_size)]
    m = np.zeros((out_size, in_size), bool)
    for i, (s, e) in enumerate(zip(starts, ends)):
        m[i, s:e] = True
    return jnp.asarray(m), jnp.asarray([e - s for s, e in zip(starts, ends)],
                                       np.float32)


def _adaptive_pool(x, out_sizes, ptype):
    """Masked reductions per spatial dim; masks are static (numpy at trace
    time), so XLA sees plain matmul-like contractions."""
    spatial = x.shape[2:]
    out = x.astype(jnp.float32)
    for d, (insz, outsz) in enumerate(zip(spatial, out_sizes)):
        m, cnt = _adaptive_masks(insz, outsz)
        axis = 2 + d
        out = jnp.moveaxis(out, axis, -1)
        if ptype == "max":
            big = jnp.finfo(jnp.float32).min
            out = jnp.max(jnp.where(m, out[..., None, :], big), axis=-1)
        else:
            out = jnp.sum(jnp.where(m, out[..., None, :], 0.0), axis=-1) / cnt.reshape(
                (1,) * (out.ndim - 1) + (-1,))
        out = jnp.moveaxis(out, -1, axis)
    return out


@register_op("adaptive_pool2d")
def _adaptive_pool2d(ctx, op, ins):
    x = first(ins, "X")
    out = _adaptive_pool(x, op.attr("pooled_size"), op.attr("pooling_type", "max"))
    return {"Out": out.astype(x.dtype)}


@register_op("adaptive_pool3d")
def _adaptive_pool3d(ctx, op, ins):
    x = first(ins, "X")
    out = _adaptive_pool(x, op.attr("pooled_size"), op.attr("pooling_type", "max"))
    return {"Out": out.astype(x.dtype)}


# --- batch-size-like fillers / sampling -----------------------------------

def _batch_size_like_shape(op, ins):
    ref = first(ins, "Input")
    shape = list(op.attr("shape"))
    in_idx = op.attr("input_dim_idx", 0)
    out_idx = op.attr("output_dim_idx", 0)
    shape[out_idx] = ref.shape[in_idx]
    return tuple(int(s) for s in shape)


@register_op("fill_constant_batch_size_like")
def _fill_constant_bsl(ctx, op, ins):
    shape = _batch_size_like_shape(op, ins)
    dtype = _np_dtype(op.attr("dtype", "float32"))
    return {"Out": jnp.full(shape, op.attr("value", 0.0), dtype)}


@register_op("uniform_random_batch_size_like")
def _uniform_random_bsl(ctx, op, ins):
    shape = _batch_size_like_shape(op, ins)
    dtype = _np_dtype(op.attr("dtype", "float32"))
    lo, hi = op.attr("min", -1.0), op.attr("max", 1.0)
    return {"Out": jax.random.uniform(ctx.next_key(), shape, jnp.float32,
                                      lo, hi).astype(dtype)}


@register_op("gaussian_random_batch_size_like")
def _gaussian_random_bsl(ctx, op, ins):
    shape = _batch_size_like_shape(op, ins)
    dtype = _np_dtype(op.attr("dtype", "float32"))
    mean, std = op.attr("mean", 0.0), op.attr("std", 1.0)
    return {"Out": (jax.random.normal(ctx.next_key(), shape, jnp.float32)
                    * std + mean).astype(dtype)}


@register_op("sampling_id")
def _sampling_id(ctx, op, ins):
    """reference sampling_id_op.h: sample one column index per row from the
    row's probability distribution."""
    x = first(ins, "X").astype(jnp.float32)  # [N, C] probs
    ids = jax.random.categorical(ctx.next_key(), jnp.log(jnp.maximum(x, 1e-20)),
                                 axis=-1)
    return {"Out": ids.astype(jnp.int32)}


# --- misc -----------------------------------------------------------------

@register_op("add_position_encoding")
def _add_position_encoding(ctx, op, ins):
    """reference add_position_encoding_op.h: out = alpha*x + beta*enc with
    enc[p, i<half] = sin(p / 10000^(i/half)), cos for the upper half."""
    x = first(ins, "X")  # [b, T, D]
    alpha = op.attr("alpha", 1.0)
    beta = op.attr("beta", 1.0)
    b, t, d = x.shape
    half = d // 2
    pos = np.arange(t, dtype=np.float32)[:, None]
    i = np.arange(half, dtype=np.float32)[None, :]
    angle = pos / np.power(10000.0, i / half)
    enc = np.concatenate([np.sin(angle), np.cos(angle)], axis=1)
    return {"Out": alpha * x + beta * jnp.asarray(enc, x.dtype)[None]}


@register_op("bilinear_tensor_product")
def _bilinear_tensor_product(ctx, op, ins):
    """reference bilinear_tensor_product_op.h: out[n,k] = x[n] W[k] y[n]^T + b."""
    x = first(ins, "X")  # [N, dx]
    y = first(ins, "Y")  # [N, dy]
    w = first(ins, "Weight")  # [K, dx, dy]
    out = jnp.einsum("nd,kde,ne->nk", x, w, y)
    if ins.get("Bias"):
        out = out + first(ins, "Bias")
    return {"Out": out}


@register_op("cvm")
def _cvm(ctx, op, ins):
    """reference cvm_op.h CvmComputeKernel: use_cvm keeps width and rewrites
    the leading (show, click) pair to (log(show+1), log(click+1)-log(show+1));
    otherwise those two columns are dropped."""
    x = first(ins, "X")  # [N, D], first 2 cols = show, click
    use_cvm = op.attr("use_cvm", True)
    if use_cvm:
        show = jnp.log(x[:, 0:1] + 1.0)
        click = jnp.log(x[:, 1:2] + 1.0) - show
        return {"Y": jnp.concatenate([show, click, x[:, 2:]], axis=1)}
    return {"Y": x[:, 2:]}


@register_op("sequence_reshape")
def _sequence_reshape(ctx, op, ins):
    """reference sequence_reshape_op.h: re-segment each row's flat
    (len*D) payload into new_dim columns; valid tokens are a contiguous
    prefix in the padded layout, so a per-row reshape preserves them."""
    x = first(ins, "X")  # [b, T, D]
    lens = first(ins, "XLod")
    nd = int(op.attr("new_dim"))
    b, t, d = x.shape
    out = x.reshape(b, t * d // nd, nd)
    return {"Out": out, "OutLod": (lens * d) // nd}


@register_op("data_norm")
def _data_norm(ctx, op, ins):
    """reference data_norm_op.cc: normalize by accumulated batch statistics
    (count/sum/square-sum), then accumulate the current batch into them."""
    x = first(ins, "X").astype(jnp.float32)  # [N, D]
    size = first(ins, "BatchSize")
    xsum = first(ins, "BatchSum")
    sqs = first(ins, "BatchSquareSum")
    eps = op.attr("epsilon", 1e-4)
    mean = xsum / size
    scale = jnp.sqrt(size / jnp.maximum(sqs - size * mean * mean + eps * size, eps))
    y = (x - mean) * scale
    n = x.shape[0]
    return {"Y": y, "Means": mean, "Scales": scale,
            "BatchSizeOut": size + n,
            "BatchSumOut": xsum + jnp.sum(x, axis=0),
            "BatchSquareSumOut": sqs + jnp.sum(jnp.square(x), axis=0)}


@register_op("get_tensor_from_selected_rows")
def _get_tensor_from_selected_rows(ctx, op, ins):
    from ..core.selected_rows import SelectedRows

    x = first(ins, "X")
    return {"Out": x.values if isinstance(x, SelectedRows) else x}


@register_op("merge_selected_rows")
def _merge_selected_rows(ctx, op, ins):
    from ..core.selected_rows import SelectedRows

    x = first(ins, "X")
    return {"Out": x.merged() if isinstance(x, SelectedRows) else x}


@register_op("gru_unit")
def _gru_unit(ctx, op, ins):
    """reference gru_unit_op.h: one GRU step over pre-projected input
    [b, 3D] and previous hidden [b, D]; gate order (u, r, c)."""
    x = first(ins, "Input")
    h = first(ins, "HiddenPrev")
    w = first(ins, "Weight")  # [D, 3D]
    b = first(ins, "Bias") if ins.get("Bias") else None
    d = h.shape[1]
    origin = op.attr("origin_mode", False)
    xb = x + b if b is not None else x
    ur = jax.nn.sigmoid(xb[:, :2 * d] + h @ w[:, :2 * d])
    u, r = ur[:, :d], ur[:, d:]
    c = jnp.tanh(xb[:, 2 * d:] + (r * h) @ w[:, 2 * d:])
    hn = u * h + (1 - u) * c if origin else (1 - u) * h + u * c
    return {"Hidden": hn, "ResetHiddenPrev": r * h,
            "Gate": jnp.concatenate([u, r, c], axis=1)}


@register_op("lstm_unit")
def _lstm_unit(ctx, op, ins):
    """reference lstm_unit_op.h: C = sigm(f + bias)*C_prev + sigm(i)*tanh(c);
    H = sigm(o)*tanh(C); X packs (i, f, c, o) along dim 1."""
    x = first(ins, "X")        # [b, 4D]
    c_prev = first(ins, "C_prev")
    fb = op.attr("forget_bias", 0.0)
    d = c_prev.shape[1]
    i, f, c, o = x[:, :d], x[:, d:2 * d], x[:, 2 * d:3 * d], x[:, 3 * d:]
    new_c = jax.nn.sigmoid(f + fb) * c_prev + jax.nn.sigmoid(i) * jnp.tanh(c)
    new_h = jax.nn.sigmoid(o) * jnp.tanh(new_c)
    return {"C": new_c, "H": new_h}


@register_op("random_crop")
def _random_crop(ctx, op, ins):
    """reference random_crop_op.h: crop `shape` (trailing dims) at a random
    offset, same offset across the batch prefix dims."""
    x = first(ins, "X")
    shape = list(op.attr("shape"))
    k = len(shape)
    lead = x.ndim - k
    key = ctx.next_key()
    starts = []
    for i, s in enumerate(shape):
        limit = x.shape[lead + i] - s
        key, sub = jax.random.split(key)
        starts.append(jax.random.randint(sub, (), 0, max(limit, 0) + 1))
    begin = [0] * lead + [st for st in starts]
    sizes = list(x.shape[:lead]) + shape
    return {"Out": jax.lax.dynamic_slice(x, begin, sizes)}


@register_op("decayed_adagrad")
def _decayed_adagrad(ctx, op, ins):
    """reference decayed_adagrad_op.h: moment = decay*moment +
    (1-decay)*g^2; param -= lr * g / (sqrt(moment) + eps)."""
    p = first(ins, "Param")
    g = first(ins, "Grad")
    m = first(ins, "Moment")
    lr = first(ins, "LearningRate").reshape(())
    decay = op.attr("decay", 0.95)
    eps = op.attr("epsilon", 1e-6)
    m2 = decay * m + (1.0 - decay) * g * g
    return {"ParamOut": p - lr * g / (jnp.sqrt(m2) + eps), "MomentOut": m2}


# --- static cost rules (core/resource_plan.py) ------------------------------

from ..core import resource_plan as _RP

_RP.register_elementwise_cost("logical_xor")
_RP.register_elementwise_cost("add_position_encoding", flops_per_elem=4.0)
