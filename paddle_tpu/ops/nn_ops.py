"""NN op lowerings: conv, pool, norms, dropout, losses, embedding, topk.

Reference kernels: conv_cudnn_op.cu.cc / conv_op.cc, pool_op.cc,
batch_norm_op.cc, layer_norm_op.cc, dropout_op.cc, cross_entropy_op.cc,
softmax_with_cross_entropy_op.cc, lookup_table_op.cc, top_k_op.cc.

One lowering per op.  What a lowering branches on is what the op can
observe: the platform, the dtype, the shapes and the program's own attributes
(conv/pool/batch_norm read the layout from `data_format`/`data_layout`: NCHW,
the public fluid default, which XLA relayouts internally, or whole-model
channels-last with no transpose in the program).  Nothing here is a
process-wide setting (PERF.md, PR 29, has the measurements that settled
it), and since PR 30 no attribute selects code either: `fused_attention`
takes one of its three attentions from the platform, the two lengths, the
head width, the dtype and the context's mesh (`_attention_path`).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from ..core.registry import register_op
from ..monitor import MONITOR as _MON
from .common import batch_shards, canon_dtype, first, match_dtype, kept_residuals, operand_of, over_batch_shards, residuals_name


@register_op("conv2d")
def _conv2d(ctx, op, ins):
    x = first(ins, "Input")
    w = match_dtype(x, first(ins, "Filter"))
    strides = tuple(op.attr("strides", [1, 1]))
    pads = op.attr("paddings", [0, 0])
    dilations = tuple(op.attr("dilations", [1, 1]))
    groups = op.attr("groups", 1) or 1
    if len(pads) == 4:
        # [top, bottom, left, right] — asymmetric (XLA-native; the s2d stem
        # needs (2,1) to avoid an off-by-one output row/col + slice copy)
        padding = [(pads[0], pads[1]), (pads[2], pads[3])]
    else:
        padding = [(pads[0], pads[0]), (pads[1], pads[1])]
    # NCHW, or NHWC end to end with no transpose in the program; the filter
    # stays OIHW either way, so parameters are layout-independent and XLA's
    # layout assignment picks the MXU's layout for it.
    layout = "NHWC" if op.attr("data_format", "NCHW") == "NHWC" else "NCHW"
    out = jax.lax.conv_general_dilated(
        x,
        w,
        window_strides=strides,
        padding=padding,
        rhs_dilation=dilations,
        dimension_numbers=(layout, "OIHW", layout),
        feature_group_count=groups,
    )
    return {"Output": out}


@register_op("depthwise_conv2d")
def _depthwise_conv2d(ctx, op, ins):
    return _conv2d(ctx, op, ins)


def conv2d_transpose_math(x, w, strides=(1, 1), pads=(0, 0), dilations=(1, 1),
                          groups=1):
    """Transposed conv as an lhs-dilated conv with flipped kernel; fluid
    filter layout (in, out/groups, kh, kw).  Shared by the graph lowering
    and the dygraph Conv2DTranspose layer."""
    kh, kw = w.shape[2], w.shape[3]
    pad_h = dilations[0] * (kh - 1) - pads[0]
    pad_w = dilations[1] * (kw - 1) - pads[1]
    wt = jnp.flip(w, axis=(2, 3))
    if groups > 1:
        # per group swap (in/groups, out/groups) then stack groups on O
        cin, cog = w.shape[0], w.shape[1]
        wt = wt.reshape(groups, cin // groups, cog, kh, kw)
        wt = jnp.swapaxes(wt, 1, 2)  # (g, out/g, in/g, kh, kw)
        wt = wt.reshape(groups * cog, cin // groups, kh, kw)
    else:
        wt = jnp.swapaxes(wt, 0, 1)  # -> (out, in, kh, kw)
    return jax.lax.conv_general_dilated(
        x,
        wt,
        window_strides=(1, 1),
        padding=[(pad_h, pad_h), (pad_w, pad_w)],
        lhs_dilation=tuple(strides),
        rhs_dilation=tuple(dilations),
        dimension_numbers=("NCHW", "OIHW", "NCHW"),
        feature_group_count=groups,
    )


@register_op("conv2d_transpose")
@register_op("depthwise_conv2d_transpose")
def _conv2d_transpose(ctx, op, ins):
    x = first(ins, "Input")
    w = match_dtype(x, first(ins, "Filter"))  # fluid layout: (in, out, kh, kw)
    out = conv2d_transpose_math(
        x, w,
        strides=tuple(op.attr("strides", [1, 1])),
        pads=op.attr("paddings", [0, 0]),
        dilations=tuple(op.attr("dilations", [1, 1])),
        groups=op.attr("groups", 1) or 1,
    )
    return {"Output": out}


@register_op("pool2d")
def _pool2d(ctx, op, ins):
    x = first(ins, "X")
    ptype = op.attr("pooling_type", "max")
    ksize = list(op.attr("ksize", [2, 2]))
    strides = list(op.attr("strides", [1, 1]))
    pads = list(op.attr("paddings", [0, 0]))
    h = 1 if op.attr("data_format", "NCHW") == "NHWC" else 2  # the first spatial axis

    def over_hw(pair, other):
        """A 4-tuple with `pair` on the two spatial axes and `other` elsewhere."""
        out = [other] * 4
        out[h:h + 2] = pair
        return tuple(out)

    if op.attr("global_pooling", False):
        ksize = [x.shape[h], x.shape[h + 1]]
        strides = [1, 1]
        pads = [0, 0]
    window = over_hw(ksize, 1)
    strides4 = over_hw(strides, 1)
    pad_hi = [pads[0], pads[1]]
    if op.attr("ceil_mode", False):
        # extra high-side padding so the window count rounds up
        for d in (0, 1):
            in_sz = x.shape[h + d]
            out_floor = (in_sz + 2 * pads[d] - ksize[d]) // strides[d] + 1
            out_ceil = -(-(in_sz + 2 * pads[d] - ksize[d]) // strides[d]) + 1
            pad_hi[d] += (out_ceil - out_floor) * strides[d]
    padding = over_hw([(pads[0], pad_hi[0]), (pads[1], pad_hi[1])], (0, 0))
    # exclusive avg pool must divide by the valid-element count whenever any
    # effective padding exists (explicit pads OR ceil-mode high padding)
    any_pad = bool(pads[0] or pads[1] or pad_hi[0] or pad_hi[1])
    if ptype == "max":
        init = -jnp.inf if jnp.issubdtype(x.dtype, jnp.floating) else jnp.iinfo(x.dtype).min
        out = jax.lax.reduce_window(x, init, jax.lax.max, window, strides4, padding)
    else:
        summed = jax.lax.reduce_window(x, 0.0, jax.lax.add, window, strides4, padding)
        if op.attr("exclusive", True) and any_pad:
            ones = jnp.ones_like(x)
            counts = jax.lax.reduce_window(ones, 0.0, jax.lax.add, window, strides4, padding)
            out = summed / counts
        else:
            out = summed / float(ksize[0] * ksize[1])
    return {"Out": out}


@register_op("batch_norm")
def _batch_norm(ctx, op, ins):
    x = first(ins, "X")
    scale = first(ins, "Scale")
    bias = first(ins, "Bias")
    mean_in = first(ins, "Mean")
    var_in = first(ins, "Variance")
    eps = op.attr("epsilon", 1e-5)
    momentum = op.attr("momentum", 0.9)
    ch_axis = 1 if op.attr("data_layout", "NCHW") == "NCHW" else x.ndim - 1
    axes = tuple(i for i in range(x.ndim) if i != ch_axis)
    bshape = [1] * x.ndim
    bshape[ch_axis] = x.shape[ch_axis]
    # Half-width activations keep their elementwise math in their own dtype;
    # the statistics always accumulate in float32, and HOW is chosen by dtype:
    # bf16 sums x and x^2 over one read of x (its 8-bit mantissa outweighs the
    # cancellation in E[x^2] - mean^2); fp16 takes the mean, then the centred
    # variance, never the one-read form (x^2 overflows at |x| >= 256); float32
    # is jnp.mean / jnp.var, exact against the reference's goldens.
    half = x.dtype in (jnp.bfloat16, jnp.float16)
    training = not (op.attr("is_test", False) or op.attr("use_global_stats", False))
    if not training:
        mean, var = mean_in, var_in
        mean_out, var_out = mean_in, var_in
    else:
        if x.dtype == jnp.bfloat16:
            inv_n = 1.0 / float(np.prod([x.shape[i] for i in axes]))
            s1 = jnp.sum(x, axis=axes, dtype=jnp.float32)
            s2 = jnp.sum(jnp.square(x), axis=axes, dtype=jnp.float32)
            mean = s1 * inv_n
            var = jnp.maximum(s2 * inv_n - jnp.square(mean), 0.0)
        elif x.dtype == jnp.float16:
            mean = jnp.mean(x, axis=axes, dtype=jnp.float32)
            centered = x - mean.astype(x.dtype).reshape(bshape)
            var = jnp.mean(jnp.square(centered), axis=axes, dtype=jnp.float32)
        else:
            mean = jnp.mean(x, axis=axes, dtype=jnp.float32)
            var = jnp.var(x, axis=axes)
        mean_out = momentum * mean_in + (1.0 - momentum) * mean
        var_out = momentum * var_in + (1.0 - momentum) * var

    inv = jax.lax.rsqrt(var.reshape(bshape) + eps)
    if half:
        # per-channel multipliers computed in f32, applied in x's dtype
        mul = (inv * scale.astype(jnp.float32).reshape(bshape)).astype(x.dtype)
        add = (bias.astype(jnp.float32).reshape(bshape)
               - mean.reshape(bshape) * inv * scale.astype(jnp.float32).reshape(bshape)
               ).astype(x.dtype)
        y = x * mul + add
    else:
        y = (x - mean.reshape(bshape)) * inv * scale.reshape(bshape) + bias.reshape(bshape)
    return {
        "Y": y.astype(x.dtype),
        "MeanOut": mean_out,
        "VarianceOut": var_out,
        "SavedMean": mean,
        "SavedVariance": var,
    }


@register_op("layer_norm")
def _layer_norm(ctx, op, ins):
    x = first(ins, "X")
    scale = first(ins, "Scale")
    bias = first(ins, "Bias")
    eps = op.attr("epsilon", 1e-5)
    begin = op.attr("begin_norm_axis", 1)
    axes = tuple(range(begin, x.ndim))
    # standard TPU LN numerics: stats/normalize in f32 even for bf16
    # activations (bf16's 8-bit mantissa loses the mean under cancellation)
    xf = x.astype(jnp.float32) if x.dtype in (jnp.bfloat16, jnp.float16) else x
    mean = jnp.mean(xf, axis=axes, keepdims=True)
    var = jnp.var(xf, axis=axes, keepdims=True)
    y = ((xf - mean) * jax.lax.rsqrt(var + eps)).astype(x.dtype)
    norm_shape = (1,) * begin + tuple(x.shape[begin:])
    if scale is not None:
        y = y * match_dtype(y, scale).reshape(norm_shape)
    if bias is not None:
        y = y + match_dtype(y, bias).reshape(norm_shape)
    return {
        "Y": y,
        "Mean": mean.reshape(x.shape[:begin]),
        "Variance": var.reshape(x.shape[:begin]),
    }


@register_op("dropout")
def _dropout(ctx, op, ins):
    x = first(ins, "X")
    p = op.attr("dropout_prob", 0.5)
    impl = op.attr("dropout_implementation", "downgrade_in_infer")
    if op.attr("is_test", False):
        if impl == "upscale_in_train":
            return {"Out": x, "Mask": jnp.ones_like(x)}
        return {"Out": x * (1.0 - p), "Mask": jnp.ones_like(x)}
    key = ctx.next_key() if not op.attr("fix_seed", False) else jax.random.PRNGKey(op.attr("seed", 0))
    keep = jax.random.bernoulli(key, 1.0 - p, x.shape)
    mask = keep.astype(x.dtype)
    if impl == "upscale_in_train":
        out = jnp.where(keep, x / (1.0 - p), 0.0).astype(x.dtype)
    else:
        out = x * mask
    return {"Out": out, "Mask": mask}


@register_op("softmax")
def _softmax(ctx, op, ins):
    x = first(ins, "X")
    axis = op.attr("axis", -1)
    return {"Out": jax.nn.softmax(x, axis=axis)}


@register_op("log_softmax")
def _log_softmax(ctx, op, ins):
    return {"Out": jax.nn.log_softmax(first(ins, "X"), axis=op.attr("axis", -1))}


@register_op("cross_entropy")
def _cross_entropy(ctx, op, ins):
    """reference cross_entropy_op.cc: input is a probability distribution."""
    x = first(ins, "X")
    label = first(ins, "Label")
    if op.attr("soft_label", False):
        loss = -jnp.sum(label * jnp.log(jnp.clip(x, 1e-20)), axis=-1, keepdims=True)
        return {"Y": loss}
    idx = label if label.ndim == x.ndim and label.shape[-1] == 1 else label[..., None]
    picked = jnp.take_along_axis(x, idx.astype(jnp.int32), axis=-1)
    loss = -jnp.log(jnp.clip(picked, 1e-20))
    ignore = op.attr("ignore_index", -100)
    loss = jnp.where(idx == ignore, 0.0, loss)
    return {"Y": loss}


@register_op("softmax_with_cross_entropy")
def _softmax_with_cross_entropy(ctx, op, ins):
    """Fused logsumexp formulation: loss = lse(x) - x[label].

    Never materializes the [N, V] log-prob tensor — at BERT's 30522 vocab
    the old log_softmax path streamed ~20 GB/step of f32 logp/softmax
    through HBM (r5 chip round profile: ~25 ms of a 261 ms step).  All
    reductions accumulate in f32 even for bf16 logits; the max shift is
    stop_gradient'd (pure numerical shift, the standard logsumexp trick),
    so autodiff yields the exact softmax-minus-onehot gradient as one
    fused pass over the logits."""
    logits = first(ins, "Logits")
    label = first(ins, "Label")
    m = jax.lax.stop_gradient(jnp.max(logits, axis=-1, keepdims=True))
    shifted = (logits - m).astype(jnp.float32)
    sumexp = jnp.sum(jnp.exp(shifted), axis=-1, keepdims=True)
    lse = jnp.log(sumexp) + m.astype(jnp.float32)
    # Softmax slot: only consumers pay for it (DCE'd when unfetched)
    softmax = (jnp.exp(shifted) / sumexp).astype(logits.dtype)
    if op.attr("soft_label", False):
        # -sum(label * (x - lse)) = lse*sum(label) - sum(label*x)
        wx = jnp.sum((label * logits).astype(jnp.float32), axis=-1, keepdims=True)
        wsum = jnp.sum(label.astype(jnp.float32), axis=-1, keepdims=True)
        loss = lse * wsum - wx
    else:
        # expand unless the label is already rank-matched with trailing dim 1
        # (shape test alone mis-handles a rank-1 label of batch size 1)
        idx = label if label.ndim == logits.ndim and label.shape[-1] == 1 else label[..., None]
        iota = jax.lax.broadcasted_iota(jnp.int32, logits.shape, logits.ndim - 1)
        onehot = iota == idx.astype(jnp.int32)
        picked = jnp.sum(jnp.where(onehot, logits, 0).astype(jnp.float32),
                         axis=-1, keepdims=True)
        loss = lse - picked
        ignore = op.attr("ignore_index", -100)
        loss = jnp.where(idx == ignore, 0.0, loss)
    return {"Loss": loss, "Softmax": softmax}


@register_op("sigmoid_cross_entropy_with_logits")
def _sigmoid_ce(ctx, op, ins):
    x = first(ins, "X")
    label = first(ins, "Label")
    # max(x,0) - x*z + log(1+exp(-|x|))
    loss = jnp.maximum(x, 0.0) - x * label + jnp.log1p(jnp.exp(-jnp.abs(x)))
    ignore = op.attr("ignore_index", -100)
    loss = jnp.where(label == ignore, 0.0, loss)
    if op.attr("normalize", False):
        n = jnp.maximum(jnp.sum((label != ignore).astype(x.dtype)), 1.0)
        loss = loss / n
    return {"Out": loss}


@register_op("square_error_cost")
def _square_error_cost(ctx, op, ins):
    x = first(ins, "X")
    y = first(ins, "Y")
    return {"Out": jnp.square(x - y)}


@register_op("huber_loss")
def _huber_loss(ctx, op, ins):
    x = first(ins, "X")
    y = first(ins, "Y")
    d = op.attr("delta", 1.0)
    r = y - x
    a = jnp.abs(r)
    loss = jnp.where(a <= d, 0.5 * r * r, d * (a - 0.5 * d))
    return {"Out": loss, "Residual": r}


@register_op("lookup_table")
def _lookup_table(ctx, op, ins):
    """reference lookup_table_op.cc; ids have trailing dim 1.  Under
    is_sparse=True with an active backward, the tap makes the table's
    gradient a SelectedRows slab (core/lowering.py SparseTapCollector)."""
    from .common import flatten_lookup_ids

    w = first(ins, "W")
    ids = first(ins, "Ids")
    flat = flatten_lookup_ids(ids)
    out = jnp.take(w, flat.astype(jnp.int32), axis=0)
    coll = getattr(ctx, "sparse_taps", None)
    if coll is not None and op.attr("is_sparse", False):
        # tap BEFORE padding_idx masking so padded positions get zero grad
        out = coll.tap(op.inputs["W"][0], op.inputs["Ids"][0], out)
    pad = op.attr("padding_idx", None)
    if pad is not None:
        real_pad = pad if pad >= 0 else w.shape[0] + pad
        out = jnp.where((flat == real_pad)[..., None], 0.0, out)
    return {"Out": out}


register_op("lookup_table_v2")(_lookup_table)


@register_op("ring_attention")
def _ring_attention(ctx, op, ins):
    """Sequence-parallel attention (parallel/ring_attention.py); falls back
    to single-device blockwise attention without an `sp` mesh axis."""
    from ..parallel.ring_attention import ring_attention

    q = first(ins, "Q")
    k = first(ins, "K")
    v = first(ins, "V")
    out = ring_attention(
        q, k, v,
        mesh=ctx.mesh,
        axis_name=op.attr("sp_axis", "sp"),
        causal=op.attr("causal", False),
        batch_axis=op.attr("batch_axis", "dp"),
    )
    return {"Out": out}


# fused_attention on the TPU: five attentions, chosen by `_attention_path`
# from what the op can observe.  Each threshold is a length, with the runs
# that set it (TPU v5e, BERT-base's own program through benchmark.run,
# samples/s; PERF.md, PRs 26, 29, 30 and 39).
#
# From this many keys on, the stock Pallas flash kernel (online softmax, O(L)
# memory): the [B,H,L,L] float32 scores are 128 MB a layer at 2048 and XLA's
# attention holds them in HBM, forward and backward (OLMoE, 4 x 4096 keys:
# 14.4 ms a layer against 58.8 ms and 8.6 GB, PR 26).
_FLASH_MIN_SEQ = 2048
# ... for at least this many queries: the kernel's smallest query block, and
# its own check refuses fewer (until PR 30 a decoding step's one query
# against 2048 keys raised there; its [B,H,1,L] scores are XLA's to keep).
_FLASH_MIN_QUERIES = 128
# Up to this many queries AND keys, the whole-row kernel of
# ops/pallas_attention.py: a (query, key) score block of a whole sequence
# fits VMEM, so the scores never reach HBM and backward recomputes them.
# 32 x 512: 212.547, 212.544, 212.543 against 181.362, 181.361, 181.363 for
# XLA's attention (+17.2%, PR 30; the stock flash kernel there 174.87; 239.54
# since the kernel reads the projections' layout, PR 39, below).  At
# 1024 the kernel's working set is 8.4 MB a pair against its 8 MB budget,
# and no run prices the lengths in (512, 2048): they keep XLA's attention.
_ROW_KERNEL_MAX_SEQ = 512
# ... and from this many on: the crossing lies between 128 and 256.  At ~16k
# tokens a step, XLA's attention | the kernel, two runs a side.  With the
# kernel over (B, H, L, dh) and four `transpose2` ops a layer round it (PR 30):
# 48 x 384: 288.646, 288.644 | 324.393, 324.394 (+12.4%);
# 64 x 256: 524.733 (and 494.012 with one step of 1.4 s) | 487.019, 487.022
# (-7.2%); 256 x 128: 1132.87 | 1003.21 (-11.45%, PR 29): what the kernel lost
# below 384 it lost at its edges.  With the kernel over the projections' own
# (B, L, H, dh) and no transpose in the program (PR 39):
# 32 x 512: 212.550, 212.541 (the parent, heads-major) | 239.540, 239.536
# (239.148, 239.153 with each direction's call one function of the module);
# 64 x 256: 525.369, 525.334 | 573.057, 573.066 (+9.08%, `peak_hbm_gb` 9.90 ->
# 7.15); 256 x 128: 1135.125, 1135.129 | 1132.162, 1132.205 (-0.26%, 15.45 ->
# 12.44 GB): the lowest length at which the step gains 1% is 256.  At 128 the
# [B, H, 128, 128] scores are 0.2 GB a layer and XLA's attention costs the
# same time, so the rule leaves it (the 3 GB are a batch's room, not speed).
# ONE bound, priced on the program that hands the op (B, L, H, dh).  An op
# handed (B, H, L, dh) at 256 to 383 keys (a rotary decoder that short: no
# cell and no builder's default) takes the kernel unpriced: PR 30's -7.2% was
# BERT's program with transposes that only the kernel made it pay, and alone
# the heads-major call is the faster from 256 on (1.28 against XLA's 2.17 ms a
# layer, PR 30); PERF.md section 7 has what would price it.
_ROW_KERNEL_MIN_SEQ = 256
# The kernel is compiled for the v5e (tests/test_chip_compile.py) and was run
# on it at bf16 and this head width only; lengths are whole lane tiles.
_ROW_KERNEL_HEAD_DIM = 64
_ROW_KERNEL_SEQ_MULTIPLE = 128
#: the op's `layout` -> (the heads' axis, the positions' axis) of Q, K, V and Out
_ATTENTION_AXES = {"bhld": (1, 2), "blhd": (2, 1)}


def _attention_path(platform, mesh, q, k, mask=None, causal=False, biased=False, layout="bhld", v_width=None,
                    batch_axis=None, picked=False):
    """Which attention `fused_attention` lowers to: "flash", "block_causal",
    "row_kernel", "block_sparse", "selected" or "xla", the lengths read by the
    op's `layout`.  Off the TPU always "xla".  Under a mask that is DATA
    (`picked`: the op's input `Picks`) "selected", the splash kernels on block
    maps made on the device from the picks (`ops/masked_attention.py:
    selected_attention`), where the other kernels' conditions hold (one device,
    bf16, as many keys as queries in whole blocks, one head width, a multiple
    of 64), else XLA's attention under the dense mask.  A short
    query against long keys (a decoding step) has no score block worth keeping
    out of HBM, hence BOTH lengths in the row kernel's rule.  Under a
    structured `mask` (`_structured_mask`: block diffusion's rule or a sliding
    window) "block_sparse", the splash kernels under that rule's block maps,
    or, as for the row kernel, XLA's attention where a custom call cannot be
    partitioned or the lengths are no whole number of the kernels' blocks;
    never the flash or the row kernel, which know no mask but a causal one.
    The head width the kernels were given differs by rule: block diffusion's
    own-block term was written for multiples of 128 and never given another;
    the window rule is the causal rule's kernels with fewer blocks and takes
    what they take (bf16 operands, multiples of 64).

    A chip runs the op whole (`one_device`) on one device, and also under a
    mesh whose `batch_axis` splits the operands' rows and nothing else
    (`ops.common.batch_shards`): `_fused_attention` then runs the chosen kernel
    in a `shard_map` over that axis, each chip on its own rows, and the choice
    reads what a chip sees (lengths, widths and dtype are the same; only the
    rows are fewer).  Under any other mesh (heads or positions split too) the
    forms GSPMD partitions by itself stand, as before."""
    if platform != "tpu":
        return "xla"
    from .masked_attention import kernel_block

    positions = _ATTENTION_AXES[layout][1]
    q_len, kv_len = q.shape[positions], k.shape[positions]
    one_device = batch_shards(mesh, batch_axis, q.shape[0]) >= 1
    # values of another width than queries and keys (latent attention: 192-wide q, k beside 128-wide v): the splash
    # kernels take the widths as they are; the flash, row and block-diffusion kernels were never given any
    one_width = v_width in (None, q.shape[-1])
    if picked:
        from .masked_attention import selected_block

        whole = (q_len == kv_len and selected_block(q_len) is not None and q.shape[-1] % 64 == 0
                 and q.dtype == k.dtype == jnp.bfloat16 and mask is None and not biased)
        return "selected" if whole and one_device and one_width else "xla"
    if mask is not None:
        if mask[0] == "sliding_window":
            # Phi-4-mini-flash's window layer, (1, 40 on 20, 8192, 64) under a window of 512: XLA's attention would
            # hold [1, 40, 8192, 8192] float32 scores, 10.7 GB, for the 1/8 of the causal triangle the rule allows;
            # the splash kernels over the band's blocks 8.36 ms forward + backward alone, the causal rule's 20.61
            # (my chip run, PR 50; `ops/masked_attention.py: _WINDOW_BLOCKS` has the runs by block).  What no run
            # prices keeps XLA's: operands other than bf16, a head width that is no multiple of 64
            from .masked_attention import window_block

            whole = (window_block(q_len, mask[1]) is not None and q.shape[-1] % 64 == 0
                     and q.dtype == k.dtype == jnp.bfloat16)
        else:
            whole = kernel_block(q_len) is not None and q.shape[-1] % 128 == 0
        return "block_sparse" if whole and one_device and one_width else "xla"
    if kv_len >= _FLASH_MIN_SEQ and q_len >= _FLASH_MIN_QUERIES:
        # A causal mask empties the blocks above the diagonal: the splash kernels never visit them and mask only the
        # blocks the diagonal cuts, where the flash kernel fetches every block, masks every one it runs and is handed
        # `di` spread to 1024 lanes in float32 and grouped key/value heads repeated.  TPU v5e, forward + backward of
        # a layer alone, flash | splash (tools/chip_block_attention.py), and in the cell's step (PERF.md, PR 37):
        # (4, 16, 4096, 128): 15.06 | 9.87 ms; OLMoE's step 128.2 -> 122.6 ms, 31.207 -> 32.627 samples/s (+4.55%);
        # (2, 32 on 8, 8192, 64): 48.22 | 32.10 ms; LFM2's step 269.0 -> 254.1 ms, 7.4205 -> 7.8485 samples/s (+5.8%).
        # What no cell or run prices keeps the flash kernel: a bias (the splash kernels take none), no causal mask
        # (nothing to skip), queries and keys of different lengths or of no whole number of the kernels' blocks, a
        # mesh that splits more than the rows, operands other than bf16, a head width that is no multiple of 64.
        if (causal and not biased and one_device and q_len == kv_len and kernel_block(q_len) is not None
                and q.shape[-1] % 64 == 0 and (v_width or q.shape[-1]) % 64 == 0 and q.dtype == k.dtype == jnp.bfloat16):
            return "block_causal"
        return "flash" if one_width else "xla"
    if not one_device:
        return "xla"
    if (all(_ROW_KERNEL_MIN_SEQ <= n <= _ROW_KERNEL_MAX_SEQ and n % _ROW_KERNEL_SEQ_MULTIPLE == 0
            for n in (q_len, kv_len))
            and q.shape[-1] == _ROW_KERNEL_HEAD_DIM and one_width and q.dtype == k.dtype == jnp.bfloat16):
        return "row_kernel"
    return "xla"


def _flash_block_sizes(block_sizes_cls, q_len, kv_len, biased):
    """Square blocks for the stock flash kernel, forward and both backward
    kernels: 1024 queries and keys without a bias, 512 with one (the float32
    bias tile is a fourth operand: at 1024 the dq kernel overruns the scoped
    VMEM, tests/test_chip_compile.py); the kernel's own default (128
    everywhere) where the lengths are not multiples of the block.  TPU v5e,
    (4, 16, 4096, 128) bf16, causal, no bias, forward + backward: 74.0 ms at
    the default, 29.8 at 256, 15.5 at 512, 14.4 at 1024 (XLA's own attention
    with the scores in HBM: 58.8 ms and 8.6 GB; PERF.md, PR 26)."""
    b = 512 if biased else 1024
    if q_len % b or kv_len % b:
        return None
    return block_sizes_cls(
        block_q=b, block_k_major=b, block_k=b, block_b=1,
        block_q_major_dkv=b, block_k_major_dkv=b, block_k_dkv=b, block_q_dkv=b,
        block_k_major_dq=b, block_k_dq=b, block_q_dq=b)


def _flash_attention_tpu(q, k, v, bias, causal, scale):
    """The stock Pallas online-softmax flash kernel as `fused_attention`
    calls it.  Only this kernel needs the bias pre-broadcast to per-head and
    in float32; fused_sdpa and the jnp path broadcast lazily (a materialized
    [B,H,L,L] bias is H x the HBM traffic)."""
    from jax.experimental.pallas.ops.tpu.flash_attention import BlockSizes, flash_attention

    ab = bias
    if ab is not None and ab.shape[1] == 1 and q.shape[1] != 1:
        ab = jnp.broadcast_to(ab, (ab.shape[0], q.shape[1]) + ab.shape[2:])
    ab = ab.astype(jnp.float32) if ab is not None else None
    sizes = _flash_block_sizes(BlockSizes, q.shape[2], k.shape[2], ab is not None)
    out = flash_attention(q, k, v, ab=ab, causal=causal, sm_scale=scale, block_sizes=sizes)
    return out.astype(q.dtype)


def _structured_mask(op, q, k, layout="bhld"):
    """The op's mask where it is a rule over positions: (kind, block length),
    or None.  It is part of the mathematics, as `causal` is, and not a choice
    among lowerings of one mathematics."""
    kind = op.attr("mask", None)
    if kind is None:
        return None
    from .masked_attention import MASKS

    block = op.attr("mask_block", None)
    axis = _ATTENTION_AXES[layout][1]
    positions, keys = q.shape[axis], k.shape[axis]
    if kind not in MASKS or not block or block < 1 or keys != positions:
        raise ValueError(f"fused_attention: mask {kind!r} with mask_block {block} over {positions} queries "
                         f"and {keys} keys; known masks {MASKS}, over as many keys as queries")
    if kind == "block_diffusion" and positions % (2 * block):
        raise ValueError(f"fused_attention: mask {kind!r} with mask_block {block} over {positions} queries "
                         f"and {keys} keys; known masks {MASKS}, over 2L positions in blocks that divide L")
    return kind, int(block)


def _xla_attention(q, k, v, bias, causal, scale, mask, allowed=None):
    """Two einsums round `jax.nn.softmax` over (B, H, L, dh), the scores in HBM.
    `allowed` bool (B, Lq, Lk): a mask that is data, each query's own keys."""
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                   preferred_element_type=jnp.float32) * scale
    if bias is not None:
        s = s + bias.astype(jnp.float32)
    if causal:
        Lq, Lk = s.shape[-2], s.shape[-1]
        s = jnp.where(jnp.tril(jnp.ones((Lq, Lk), bool), k=Lk - Lq), s, -1e30)
    if mask is not None:
        from .masked_attention import block_diffusion_allowed, window_allowed

        at = jnp.arange(s.shape[-1], dtype=jnp.int32)
        if mask[0] == "sliding_window":
            by_rule = window_allowed(at[:, None], at[None, :], mask[1])
        else:
            by_rule = block_diffusion_allowed(at[:, None], at[None, :], s.shape[-1] // 2, mask[1])
        s = jnp.where(by_rule, s, -1e30)
    if allowed is not None:
        s = jnp.where(allowed[:, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhqk,bhkd->bhqd", p.astype(q.dtype), v,
                     preferred_element_type=jnp.float32)
    if allowed is not None:   # ... and each query's log-sum-exp over its keys, the op's output `Lse`
        return out.astype(q.dtype), jax.nn.logsumexp(s, axis=-1)
    return out.astype(q.dtype)


@register_op("fused_attention")
def _fused_attention(ctx, op, ins):
    """Scaled-dot-product attention over (B, H, L, dh), or over (B, L, H, dh)
    where the op's `layout` is "blhd": softmax(q k^T * scale
    + bias, causal mask) v, with the operands in their own dtype on the MXU,
    float32 accumulation, float32 scores and softmax, and the probabilities
    rounded to the activations' dtype for the product with v.  One
    mathematics, six tilings, chosen by `_attention_path` and counted in
    `lowering.attention_flash|block_causal|row_kernel|block_sparse|selected|xla`:

    * `block_causal`: from `_FLASH_MIN_SEQ` keys on, a causal mask and no
      bias over as many bf16 keys as queries, on one device: the stock
      splash-attention forward kernel under the causal rule
      (`ops/masked_attention.py: causal_attention`) and ONE backward kernel of
      our own that keeps dq on the chip (`ops/attention_backward_kernels.py`),
      which never visit a block above the diagonal and mask only the blocks
      it cuts;
    * `flash`: the stock Pallas online-softmax kernel, every other attention
      from `_FLASH_MIN_SEQ` keys on (a bias, no causal mask, a mesh): no
      cell runs it since PR 37;
    * `row_kernel`: `ops/pallas_attention.py:fused_sdpa`, queries and keys
      both in [`_ROW_KERNEL_MIN_SEQ`, `_ROW_KERNEL_MAX_SEQ`]: a whole row of
      scores lives in VMEM, forward and backward;
    * `block_sparse`: under a structured mask (the attributes `mask` and
      `mask_block`: a rule over positions, `ops/masked_attention.py`), the
      stock splash-attention kernel with the rule as its mask: blocks the
      rule empties are skipped, forward and backward, the blocks it cuts read
      the few distinct cut blocks (block diffusion) or compute the rule from
      the positions (a sliding window, `mask_block` its width in keys), the
      backward under either the causal rule's one kernel over the blocks the
      rule leaves, and no mask or score of the whole square is in HBM;
    * `selected`: under a mask that is DATA (the input `Picks`, int32 (B, Lq,
      Lk / 32): bit j of word w of a query set where it holds key 32 w + j;
      `sparse_index` makes it), the same stock forward kernel on a block map
      made on the device from the picks and the same one backward kernel on
      the row's byte mask: every block brings its stored block of the
      mask, a block that holds no chosen pair is skipped, and no pair outside
      the picks has weight (with `causal`, none above the diagonal either).
      Counted in `lowering.selected_attention_ops`;
    * `xla`: two einsums round `jax.nn.softmax`, the scores in HBM:
      everything else on the TPU, and every other platform (CPU tests and
      virtual meshes compute the same function, so goldens transfer); a
      structured mask is built densely here from the same rule.

    K and V may have fewer heads than Q (a divisor): query head j reads
    key/value head j div (Hq / Hkv).  The splash kernels (`block_causal`,
    `block_sparse`) read them so; the other three are given K and V repeated
    at their edge.

    `layout` is part of the op's signature, as `causal` is, and no switch
    among lowerings: "blhd" hands Q, K, V over as (B, L, H, dh), what a
    projection's output reshapes to for nothing, and takes Out back so.  The
    row kernel reads either layout as it is (its BlockSpecs' matter); every
    other path is written over (B, H, L, dh) and transposes a "blhd" op's
    operands and result at its own edge, which is the device work of the
    `transpose2` ops a program would otherwise hold.
    `lowering.attention_layout_native` counts the ops whose path read what it
    was handed, `lowering.attention_layout_transposed` the others.

    A `pallas_call` is a custom call that GSPMD cannot partition: under a mesh
    it would run on every chip over ALL the rows.  Where the mesh's batch axis
    splits the operands' rows and nothing else, every kernel path therefore
    runs in a `shard_map` over that axis, each chip on its own rows
    (`ops.common.over_batch_shards`, counted in
    `lowering.kernels_under_shard_map`; the four-chip Jamba cell's causal
    attention at 8192 keys takes the splash kernels so).  Under a mesh that
    splits heads or positions too, the kernels step aside for the XLA path,
    which GSPMD partitions at no new code, and the stock flash kernel runs
    replicated as before.  The bias derives from lengths and causality in every
    caller, so the row kernel treats it as a constant."""
    bias = first(ins, "Bias") if "Bias" in ins and ins["Bias"] else None
    picks = first(ins, "Picks") if "Picks" in ins and ins["Picks"] else None
    out = attention(ctx, op, first(ins, "Q"), first(ins, "K"), first(ins, "V"), bias, picks=picks)
    # under picks also each query's float32 log-sum-exp over its keys, (B, H, Lq): what `index_alignment` steadies its own
    # softmax by (an op that declares no `Lse` drops it)
    return {"Out": out} if picks is None else {"Out": out[0], "Lse": out[1]}


def attention_scale(op, width: int) -> float:
    """The scores' scale: the op's attribute, else the queries' width^-0.5."""
    scale = op.attr("scale", None)
    return 1.0 / float(np.sqrt(width)) if scale is None else scale


def attention(ctx, op, q, k, v, bias=None, assembled=False, picks=None):
    """`fused_attention`'s lowering on its operands.  `assembled` (the latent
    attention's unit, `ops/latent_operands.py`): q, k, v and the result are
    heads-major whatever the op's `layout`, and the queries carry the scale, so
    that no path transposes at its edge and none scales.  Under `picks` the
    result is (out, log-sum-exp (B, H, Lq) float32)."""
    causal = op.attr("causal", False)
    layout = "bhld" if assembled else op.attr("layout", "bhld")
    # assembled queries carry the scale: the splash kernels, which scale the queries at their edge, are told so (None),
    # and every other path scales its scores by 1
    scale = 1.0 if assembled else attention_scale(op, q.shape[-1])
    carried = None if assembled else float(scale)
    mask = _structured_mask(op, q, k, layout)
    path = _attention_path(ctx.platform, ctx.mesh, q, k, mask, causal, bias is not None, layout, v.shape[-1],
                           ctx.batch_axis, picks is not None)
    _MON.counter(f"lowering.attention_{path}").inc()
    if picks is not None:
        _MON.counter("lowering.selected_attention_ops").inc()
    if v.shape[-1] != q.shape[-1]:
        _MON.counter("lowering.latent_attention_layers").inc()
    if op.attr("kept_kv", False):
        _MON.counter("lowering.kept_tensor_readers").inc()
    native = layout == "bhld" or path == "row_kernel"
    _MON.counter("lowering.attention_layout_native" if native else "lowering.attention_layout_transposed").inc()
    keep = kept_residuals(ctx, op)

    def attend(q, k, v, bias=None):
        if not native:
            q, k, v = (jnp.swapaxes(t, 1, 2) for t in (q, k, v))
        heads = _ATTENTION_AXES[layout][0] if native else 1
        if path == "selected":
            from .masked_attention import selected_attention

            out = selected_attention(q, k, v, picks, carried, bool(causal), keep=keep)
        elif path == "block_sparse":
            from .masked_attention import block_sparse_attention, window_attention

            under = window_attention if mask[0] == "sliding_window" else block_sparse_attention
            out = under(q, k, v, mask[1], carried, keep=keep)
        elif path == "block_causal":
            from .masked_attention import causal_attention

            out = causal_attention(q, k, v, carried, keep=keep)
        else:
            if k.shape[heads] != q.shape[heads]:
                k, v = (jnp.repeat(t, q.shape[heads] // t.shape[heads], axis=heads) for t in (k, v))
            if path == "flash":
                out = _flash_attention_tpu(q, k, v, bias, causal, scale)
            elif path == "row_kernel":
                from .pallas_attention import fused_sdpa

                b = jax.lax.stop_gradient(bias) if bias is not None else None
                out = fused_sdpa(q, k, v, b, bool(causal), float(scale), False, layout).astype(q.dtype)
            elif picks is not None:
                from .sparse_index_ops import unpack_bits

                out = _xla_attention(q, k, v, bias, causal, scale, mask, unpack_bits(picks, k.shape[2]))
            else:
                out = _xla_attention(q, k, v, bias, causal, scale, mask)
        if picks is not None:
            return (out[0] if native else jnp.swapaxes(out[0], 1, 2)), out[1]
        return out if native else jnp.swapaxes(out, 1, 2)

    if path != "xla" and batch_shards(ctx.mesh, ctx.batch_axis, q.shape[0]) > 1:
        # a kernel under a mesh that splits the rows alone: each chip on its own rows (a bias that has the rows' axis
        # is split with them, one that is broadcast over the rows is handed over whole)
        rows = bias is not None and bias.shape[0] == q.shape[0]
        batched, whole = ((q, k, v, bias), ()) if rows else ((q, k, v), () if bias is None else (bias,))
        return over_batch_shards(ctx, attend, batched, whole)
    return attend(q, k, v, bias)


@register_op("top_k")
def _top_k(ctx, op, ins):
    x = first(ins, "X")
    k = op.attr("k", 1)
    vals, idx = jax.lax.top_k(x, k)
    return {"Out": vals, "Indices": idx.astype(canon_dtype("int64"))}


@register_op("arg_max")
def _arg_max(ctx, op, ins):
    x = first(ins, "X")
    axis = op.attr("axis", -1)
    return {"Out": jnp.argmax(x, axis=axis).astype(canon_dtype("int64"))}


@register_op("arg_min")
def _arg_min(ctx, op, ins):
    x = first(ins, "X")
    return {"Out": jnp.argmin(x, axis=op.attr("axis", -1)).astype(canon_dtype("int64"))}


@register_op("accuracy")
def _accuracy(ctx, op, ins):
    """reference metrics/accuracy_op.cc: Out/Indices from top_k + Label."""
    indices = first(ins, "Indices")
    label = first(ins, "Label")
    correct_any = jnp.any(indices == label.astype(indices.dtype), axis=-1)
    num_correct = jnp.sum(correct_any.astype(jnp.int32))
    total = indices.shape[0]
    acc = num_correct.astype(jnp.float32) / float(total)
    return {
        "Accuracy": acc.reshape((1,)),
        "Correct": num_correct.reshape((1,)),
        "Total": jnp.full((1,), total, dtype=jnp.int32),
    }


@register_op("label_smooth")
def _label_smooth(ctx, op, ins):
    x = first(ins, "X")
    eps = op.attr("epsilon", 0.1)
    prior = first(ins, "PriorDist")
    if prior is not None:
        out = (1.0 - eps) * x + eps * prior
    else:
        out = (1.0 - eps) * x + eps / x.shape[-1]
    return {"Out": out}


@register_op("smooth_l1_loss")
def _smooth_l1(ctx, op, ins):
    x = first(ins, "X")
    y = first(ins, "Y")
    sigma = op.attr("sigma", 1.0)
    s2 = sigma * sigma
    d = x - y
    a = jnp.abs(d)
    elem = jnp.where(a < 1.0 / s2, 0.5 * s2 * d * d, a - 0.5 / s2)
    return {"Out": jnp.sum(elem, axis=tuple(range(1, x.ndim)), keepdims=False).reshape(-1, 1), "Diff": d}


@register_op("prelu")
def _prelu(ctx, op, ins):
    x = first(ins, "X")
    alpha = first(ins, "Alpha")
    mode = op.attr("mode", "all")
    if mode == "channel":
        a = alpha.reshape((1, -1) + (1,) * (x.ndim - 2))
    elif mode == "element":
        a = alpha.reshape((1,) + x.shape[1:])
    else:
        a = alpha.reshape(())
    return {"Out": jnp.where(x > 0, x, a * x)}


@register_op("mean_iou")
def _mean_iou(ctx, op, ins):
    """reference operators/metrics/mean_iou_op.h: per-class intersection /
    union over the batch; classes absent from both pred and label are
    excluded from the mean."""
    pred = first(ins, "Predictions").reshape(-1).astype(jnp.int32)
    label = first(ins, "Labels").reshape(-1).astype(jnp.int32)
    C = op.attr("num_classes")
    match = pred == label
    correct = jax.ops.segment_sum(match.astype(jnp.int32), label, num_segments=C)
    pred_cnt = jax.ops.segment_sum(jnp.ones_like(pred), pred, num_segments=C)
    label_cnt = jax.ops.segment_sum(jnp.ones_like(label), label, num_segments=C)
    union = pred_cnt + label_cnt - correct
    valid = union > 0
    iou = jnp.where(valid, correct / jnp.maximum(union, 1), 0.0)
    mean = jnp.sum(iou) / jnp.maximum(jnp.sum(valid), 1)
    return {
        "OutMeanIou": mean.astype(jnp.float32).reshape((1,)),
        # all mismatches touching class c (false neg + false pos), so the
        # streaming invariant iou = correct/(correct+wrong) holds
        # (reference mean_iou_op.h)
        "OutWrong": (pred_cnt + label_cnt - 2 * correct).astype(jnp.int32),
        "OutCorrect": correct.astype(jnp.int32),
    }


@register_op("auc")
def _auc(ctx, op, ins):
    """reference operators/metrics/auc_op.h: bucket predicted positive
    probability into num_thresholds+1 histogram bins per class polarity,
    accumulate across steps (StatPos/StatNeg are persistable state), and
    integrate the ROC curve by trapezoid."""
    predict = first(ins, "Predict")
    label = first(ins, "Label").reshape(-1)
    stat_pos = first(ins, "StatPos")
    stat_neg = first(ins, "StatNeg")
    T = op.attr("num_thresholds", 4095)
    # positive-class probability: column 1 of [b,2], or the flat input
    p = predict[:, 1] if predict.ndim == 2 and predict.shape[1] == 2 else predict.reshape(-1)
    bucket = jnp.clip((p * T).astype(jnp.int32), 0, T)
    is_pos = (label > 0).astype(stat_pos.dtype)
    pos_new = stat_pos.at[bucket].add(is_pos)
    neg_new = stat_neg.at[bucket].add(1 - is_pos)
    # walk thresholds high->low: cumulative TP/FP above each bucket.
    # Integer math throughout (x32 would silently round float64 to float32
    # past 2^24 examples); only the final ratio goes to float, where error
    # is relative, not absolute.
    tp = jnp.cumsum(pos_new[::-1])[::-1]
    fp = jnp.cumsum(neg_new[::-1])[::-1]
    tot_pos = tp[0]
    tot_neg = fp[0]
    # 2x trapezoid area over consecutive (fp, tp) points incl. the (0,0) end
    tp_ext = jnp.concatenate([tp, jnp.zeros((1,), tp.dtype)])
    fp_ext = jnp.concatenate([fp, jnp.zeros((1,), fp.dtype)])
    area2 = jnp.sum((fp_ext[:-1] - fp_ext[1:]) * (tp_ext[:-1] + tp_ext[1:]))
    denom2 = 2 * tot_pos * tot_neg
    auc_v = jnp.where(
        denom2 > 0,
        area2.astype(jnp.float32) / jnp.maximum(denom2, 1).astype(jnp.float32),
        0.0,
    )
    return {
        "AUC": auc_v.astype(jnp.float32).reshape((1,)),
        "StatPosOut": pos_new,
        "StatNegOut": neg_new,
    }


def _interp_2d(x, out_h, out_w, method, align_corners, align_mode=1):
    """Shared bilinear/nearest resize on NCHW (reference interpolate_op.h).

    align_corners=False bilinear has TWO reference formulas, picked by
    align_mode: 0 = half-pixel (src = (dst+0.5)*scale - 0.5), 1 (the
    reference DEFAULT) = plain scaling (src = dst*scale)."""
    n, c, h, w = x.shape
    if method == "nearest":
        if align_corners:
            hi = jnp.round(jnp.linspace(0.0, h - 1.0, out_h)).astype(jnp.int32)
            wi = jnp.round(jnp.linspace(0.0, w - 1.0, out_w)).astype(jnp.int32)
        else:
            hi = jnp.floor(jnp.arange(out_h) * (h / out_h)).astype(jnp.int32)
            wi = jnp.floor(jnp.arange(out_w) * (w / out_w)).astype(jnp.int32)
        return x[:, :, hi][:, :, :, wi]
    # bilinear
    if align_corners and out_h > 1:
        ys = jnp.linspace(0.0, h - 1.0, out_h)
    elif align_mode == 1:
        ys = jnp.arange(out_h) * (h / out_h)
    else:
        ys = jnp.maximum((jnp.arange(out_h) + 0.5) * (h / out_h) - 0.5, 0.0)
    if align_corners and out_w > 1:
        xs = jnp.linspace(0.0, w - 1.0, out_w)
    elif align_mode == 1:
        xs = jnp.arange(out_w) * (w / out_w)
    else:
        xs = jnp.maximum((jnp.arange(out_w) + 0.5) * (w / out_w) - 0.5, 0.0)
    y0 = jnp.clip(jnp.floor(ys).astype(jnp.int32), 0, h - 1)
    y1 = jnp.clip(y0 + 1, 0, h - 1)
    x0 = jnp.clip(jnp.floor(xs).astype(jnp.int32), 0, w - 1)
    x1 = jnp.clip(x0 + 1, 0, w - 1)
    wy = (ys - y0).astype(x.dtype).reshape(1, 1, out_h, 1)
    wx = (xs - x0).astype(x.dtype).reshape(1, 1, 1, out_w)
    g00 = x[:, :, y0][:, :, :, x0]
    g01 = x[:, :, y0][:, :, :, x1]
    g10 = x[:, :, y1][:, :, :, x0]
    g11 = x[:, :, y1][:, :, :, x1]
    top = g00 * (1 - wx) + g01 * wx
    bot = g10 * (1 - wx) + g11 * wx
    return top * (1 - wy) + bot * wy


@register_op("bilinear_interp")
def _bilinear_interp(ctx, op, ins):
    x = first(ins, "X")
    out_h = op.attr("out_h")
    out_w = op.attr("out_w")
    scale = op.attr("scale", 0.0)
    if scale:
        out_h = int(x.shape[2] * scale)
        out_w = int(x.shape[3] * scale)
    return {"Out": _interp_2d(x, out_h, out_w, "bilinear",
                              op.attr("align_corners", True),
                              op.attr("align_mode", 1))}


@register_op("nearest_interp")
def _nearest_interp(ctx, op, ins):
    x = first(ins, "X")
    out_h = op.attr("out_h")
    out_w = op.attr("out_w")
    scale = op.attr("scale", 0.0)
    if scale:
        out_h = int(x.shape[2] * scale)
        out_w = int(x.shape[3] * scale)
    return {"Out": _interp_2d(x, out_h, out_w, "nearest",
                              op.attr("align_corners", True))}


@register_op("pad2d")
def _pad2d(ctx, op, ins):
    """reference pad2d_op.cc: NCHW spatial padding, constant/reflect/edge."""
    x = first(ins, "X")
    p = op.attr("paddings", [0, 0, 0, 0])  # top, bottom, left, right
    mode = op.attr("mode", "constant")
    value = op.attr("pad_value", 0.0)
    cfg = ((0, 0), (0, 0), (p[0], p[1]), (p[2], p[3]))
    np_mode = {"constant": "constant", "reflect": "reflect", "edge": "edge"}[mode]
    if mode == "constant":
        return {"Out": jnp.pad(x, cfg, mode="constant", constant_values=value)}
    return {"Out": jnp.pad(x, cfg, mode=np_mode)}


@register_op("crop")
def _crop(ctx, op, ins):
    """reference crop_op.cc: static offsets/shape crop."""
    x = first(ins, "X")
    offsets = op.attr("offsets")
    shape = op.attr("shape")
    idx = tuple(slice(o, o + s) for o, s in zip(offsets, shape))
    return {"Out": x[idx]}


@register_op("print")
def _print(ctx, op, ins):
    """reference print_op.cc (layers.Print): passthrough + host callback
    printing the value at execution time; first_n throttles across
    executions via a host-side counter in the callback closure."""
    x = first(ins, "X")
    msg = op.attr("message", "")
    first_n = op.attr("first_n", -1)
    count = {"n": 0}

    def _cb(v, _msg=msg, _first_n=first_n, _count=count):
        if _first_n < 0 or _count["n"] < _first_n:
            print(f"{_msg}{v}", flush=True)
            _count["n"] += 1

    jax.debug.callback(_cb, x)
    return {"Out": x}


@register_op("group_norm")
def _group_norm(ctx, op, ins):
    """reference group_norm_op: normalize within channel groups [N, C, *]."""
    x = first(ins, "X")
    scale = first(ins, "Scale")
    bias = first(ins, "Bias")
    eps = op.attr("epsilon", 1e-5)
    groups = op.attr("groups", 1)
    n, c = x.shape[0], x.shape[1]
    xf = x.astype(jnp.float32).reshape((n, groups, c // groups) + x.shape[2:])
    axes = tuple(range(2, xf.ndim))
    mean = jnp.mean(xf, axis=axes, keepdims=True)
    var = jnp.var(xf, axis=axes, keepdims=True)
    y = ((xf - mean) * jax.lax.rsqrt(var + eps)).reshape(x.shape)
    cshape = (1, c) + (1,) * (x.ndim - 2)
    if scale is not None:
        y = y * scale.reshape(cshape)
    if bias is not None:
        y = y + bias.reshape(cshape)
    return {"Y": y.astype(x.dtype),
            "Mean": mean.reshape(n, groups),
            "Variance": var.reshape(n, groups)}


@register_op("instance_norm")
def _instance_norm(ctx, op, ins):
    """reference instance_norm_op: per-(sample, channel) normalization."""
    x = first(ins, "X")
    scale = first(ins, "Scale")
    bias = first(ins, "Bias")
    eps = op.attr("epsilon", 1e-5)
    xf = x.astype(jnp.float32)
    axes = tuple(range(2, x.ndim))
    mean = jnp.mean(xf, axis=axes, keepdims=True)
    var = jnp.var(xf, axis=axes, keepdims=True)
    y = (xf - mean) * jax.lax.rsqrt(var + eps)
    cshape = (1, x.shape[1]) + (1,) * (x.ndim - 2)
    if scale is not None:
        y = y * scale.reshape(cshape)
    if bias is not None:
        y = y + bias.reshape(cshape)
    n, c = x.shape[0], x.shape[1]
    return {"Y": y.astype(x.dtype),
            "SavedMean": mean.reshape(n, c),
            "SavedVariance": var.reshape(n, c)}


def conv3d_transpose_math(x, w, strides=(1, 1, 1), pads=(0, 0, 0),
                          dilations=(1, 1, 1), groups=1):
    """3-D analogue of conv2d_transpose_math (fluid layout
    (in, out/groups, kd, kh, kw)); shared by graph + dygraph paths."""
    kd, kh, kw = w.shape[2], w.shape[3], w.shape[4]
    pad = [dilations[i] * (k - 1) - pads[i] for i, k in enumerate((kd, kh, kw))]
    wt = jnp.flip(w, axis=(2, 3, 4))
    if groups > 1:
        cin, cog = w.shape[0], w.shape[1]
        wt = wt.reshape(groups, cin // groups, cog, kd, kh, kw)
        wt = jnp.swapaxes(wt, 1, 2)
        wt = wt.reshape(groups * cog, cin // groups, kd, kh, kw)
    else:
        wt = jnp.swapaxes(wt, 0, 1)
    return jax.lax.conv_general_dilated(
        x, wt, window_strides=(1, 1, 1),
        padding=[(p, p) for p in pad],
        lhs_dilation=tuple(strides), rhs_dilation=tuple(dilations),
        dimension_numbers=("NCDHW", "OIDHW", "NCDHW"),
        feature_group_count=groups,
    )


@register_op("conv3d_transpose")
def _conv3d_transpose(ctx, op, ins):
    """reference conv_transpose_op.cc conv3d_transpose."""
    x = first(ins, "Input")
    w = first(ins, "Filter")
    strides = op.attr("strides", [1, 1, 1])
    pads = op.attr("paddings", [0, 0, 0])
    dilations = op.attr("dilations", [1, 1, 1])
    groups = op.attr("groups", 1)
    return {"Output": conv3d_transpose_math(x, w, strides, pads, dilations,
                                            groups)}


def _bilinear_sample_grid(img, ys, xs):
    """Bilinear sample img [C, H, W] at float grids ys/xs [*spatial].
    Reference deformable_im2col_bilinear semantics: each of the four
    corners contributes only if it lies inside the image — a sample within
    1px of the border attenuates rather than clamping to the edge pixel."""
    H, W = img.shape[1], img.shape[2]
    y0 = jnp.floor(ys)
    x0 = jnp.floor(xs)
    wy = ys - y0
    wx = xs - x0
    y0i = y0.astype(jnp.int32)
    x0i = x0.astype(jnp.int32)

    def corner(yi, xi):
        ok = (yi >= 0) & (yi < H) & (xi >= 0) & (xi < W)
        v = img[:, jnp.clip(yi, 0, H - 1), jnp.clip(xi, 0, W - 1)]
        return jnp.where(ok[None], v, 0.0)

    v00 = corner(y0i, x0i)
    v01 = corner(y0i, x0i + 1)
    v10 = corner(y0i + 1, x0i)
    v11 = corner(y0i + 1, x0i + 1)
    return ((v00 * (1 - wx) + v01 * wx) * (1 - wy)
            + (v10 * (1 - wx) + v11 * wx) * wy)


@register_op("deformable_conv")
def _deformable_conv(ctx, op, ins):
    """Deformable convolution v1/v2 (reference deformable_conv_op.cc /
    deformable_conv_v1; DCN arXiv:1703.06211, modulated arXiv:1811.11168).

    Each kernel tap samples the input at its integer position plus a
    learned per-position offset (bilinear), optionally scaled by a learned
    modulation mask (v2).  The sampled-patch tensor contracts with the
    filter as a plain einsum — the MXU sees one big matmul, the gathers are
    the only irregular part.  Gradients (incl. through the sampling
    coordinates to Offset/Mask) come from autodiff; the reference hand-
    writes the three backward kernels."""
    x = first(ins, "Input").astype(jnp.float32)     # [N, C, H, W]
    offset = first(ins, "Offset").astype(jnp.float32)  # [N, 2*dg*kh*kw, Ho, Wo]
    mask = (first(ins, "Mask").astype(jnp.float32)
            if ins.get("Mask") else None)              # [N, dg*kh*kw, Ho, Wo]
    w = first(ins, "Filter").astype(jnp.float32)     # [O, C/g, kh, kw]
    strides = op.attr("strides", [1, 1])
    pads = op.attr("paddings", [0, 0])
    dilations = op.attr("dilations", [1, 1])
    groups = op.attr("groups", 1) or 1
    dg = op.attr("deformable_groups", 1) or 1
    N, C, H, W = x.shape
    O, _, kh, kw = w.shape
    Ho = (H + 2 * pads[0] - (dilations[0] * (kh - 1) + 1)) // strides[0] + 1
    Wo = (W + 2 * pads[1] - (dilations[1] * (kw - 1) + 1)) // strides[1] + 1

    base_y = (jnp.arange(Ho) * strides[0] - pads[0])[:, None]  # [Ho, 1]
    base_x = (jnp.arange(Wo) * strides[1] - pads[1])[None, :]  # [1, Wo]
    cpg = C // dg  # channels per deformable group

    def one_image(img, off, mk):
        cols = []
        for k in range(kh * kw):
            i, j = k // kw, k % kw
            taps = []
            for g in range(dg):
                dy = off[2 * (g * kh * kw + k)]       # [Ho, Wo]
                dx = off[2 * (g * kh * kw + k) + 1]
                ys = base_y + i * dilations[0] + dy
                xs = base_x + j * dilations[1] + dx
                v = _bilinear_sample_grid(img[g * cpg:(g + 1) * cpg], ys, xs)
                if mk is not None:
                    v = v * mk[g * kh * kw + k][None]
                taps.append(v)
            cols.append(jnp.concatenate(taps, axis=0))  # [C, Ho, Wo]
        return jnp.stack(cols, axis=1)  # [C, kh*kw, Ho, Wo]

    if mask is None:
        patches = jax.vmap(lambda a, b: one_image(a, b, None))(x, offset)
    else:
        patches = jax.vmap(one_image)(x, offset, mask)
    # grouped contraction: [N, C, K, Ho, Wo] x [O, C/g, K] -> [N, O, Ho, Wo]
    cg = C // groups
    og = O // groups
    wk = w.reshape(O, cg, kh * kw)
    outs = []
    for g in range(groups):
        outs.append(jnp.einsum(
            "nckhw,ock->nohw",
            patches[:, g * cg:(g + 1) * cg], wk[g * og:(g + 1) * og]))
    out = jnp.concatenate(outs, axis=1) if groups > 1 else outs[0]
    return {"Output": out.astype(first(ins, "Input").dtype)}


register_op("deformable_conv_v1")(_deformable_conv)


# --- build-time shape/dtype inference --------------------------------------
# (core/analysis.py; reference: each op's InferShape — conv2d_op.cc,
# pool_op.cc, batch_norm_op.cc, layer_norm_op.cc, softmax_op.cc, ...)

from ..core import analysis as _A

_A.register_unary_infer("softmax", "log_softmax", "label_smooth",
                        "prelu", "sigmoid_cross_entropy_with_logits")
_A.register_elementwise_infer("square_error_cost")


def _infer_dropout(ctx):
    # one rule for BOTH outputs: set_infer replaces, so registering Out and
    # Mask separately would leave whichever registered first unchecked
    xs = ctx.in_shape("X")
    dt = ctx.in_dtype("X")
    ctx.set_out("Out", xs, dt)
    ctx.set_out("Mask", xs, dt)


_A.register_rule(["dropout"], _infer_dropout)


def _conv_dim(in_sz, k, stride, pad_lo, pad_hi, dil):
    if in_sz == _A.DYN or k == _A.DYN:
        return _A.DYN
    eff = (k - 1) * dil + 1
    return (in_sz + pad_lo + pad_hi - eff) // stride + 1


def _infer_conv2d(ctx):
    xs = ctx.in_shape("Input")
    ws = ctx.in_shape("Filter")
    if xs is None or ws is None or len(xs) != 4 or len(ws) != 4:
        return
    op = ctx.op
    strides = list(op.attr("strides", [1, 1]))
    pads = list(op.attr("paddings", [0, 0]))
    dil = list(op.attr("dilations", [1, 1]))
    groups = op.attr("groups", 1) or 1
    if len(pads) == 4:
        plo, phi = (pads[0], pads[2]), (pads[1], pads[3])
    else:
        plo = phi = (pads[0], pads[1])
    nhwc = op.attr("data_format", "NCHW") == "NHWC"
    n, h, w, c = ((xs[0], xs[1], xs[2], xs[3]) if nhwc
                  else (xs[0], xs[2], xs[3], xs[1]))
    o, i_g, kh, kw = ws
    if c != _A.DYN and i_g != _A.DYN and c != i_g * groups:
        ctx.fail(
            f"input channels {c} != Filter in-channels*groups "
            f"{i_g}*{groups}", var=op.input("Input")[0])
    oh = _conv_dim(h, kh, strides[0], plo[0], phi[0], dil[0])
    ow = _conv_dim(w, kw, strides[1], plo[1], phi[1], dil[1])
    out = (n, oh, ow, o) if nhwc else (n, o, oh, ow)
    ctx.set_out("Output", out, ctx.in_dtype("Input"))


_A.register_rule(["conv2d", "depthwise_conv2d"], _infer_conv2d)


def _infer_pool2d(ctx):
    xs = ctx.in_shape("X")
    if xs is None or len(xs) != 4:
        return
    op = ctx.op
    cl = op.attr("data_format", "NCHW") == "NHWC"
    n = xs[0]
    h, w = (xs[1], xs[2]) if cl else (xs[2], xs[3])
    c = xs[3] if cl else xs[1]
    if op.attr("global_pooling", False):
        oh = ow = 1
    else:
        ksize = list(op.attr("ksize", [2, 2]))
        strides = list(op.attr("strides", [1, 1]))
        pads = list(op.attr("paddings", [0, 0]))
        ceil = op.attr("ceil_mode", False)

        def od(in_sz, k, s, p):
            if in_sz == _A.DYN:
                return _A.DYN
            if ceil:
                return -(-(in_sz + 2 * p - k) // s) + 1
            return (in_sz + 2 * p - k) // s + 1

        oh = od(h, ksize[0], strides[0], pads[0])
        ow = od(w, ksize[1], strides[1], pads[1])
    out = (n, oh, ow, c) if cl else (n, c, oh, ow)
    ctx.set_out("Out", out, ctx.in_dtype("X"))


_A.register_rule(["pool2d"], _infer_pool2d)


def _infer_batch_norm(ctx):
    xs = ctx.in_shape("X")
    if xs is None:
        return
    layout = ctx.op.attr("data_layout", "NCHW")
    ch_axis = 1 if layout == "NCHW" else len(xs) - 1
    ch = xs[ch_axis]
    for slot in ("Scale", "Bias", "Mean", "Variance"):
        s = ctx.in_shape(slot)
        if s is not None and ch != _A.DYN and _A.unify_shape(s, (ch,)) is None:
            ctx.fail(f"{slot} shape {tuple(s)} != (C,) = ({ch},)",
                     var=ctx.op.input(slot)[0])
    ctx.set_out("Y", xs, ctx.in_dtype("X"))
    for slot in ("MeanOut", "VarianceOut", "SavedMean", "SavedVariance"):
        ctx.set_out(slot, (ch,) if ch != _A.DYN else None)


_A.register_rule(["batch_norm"], _infer_batch_norm)


def _infer_layer_norm(ctx):
    xs = ctx.in_shape("X")
    if xs is None:
        return
    begin = ctx.op.attr("begin_norm_axis", 1)
    ctx.set_out("Y", xs, ctx.in_dtype("X"))
    ctx.set_out("Mean", tuple(xs[:begin]))
    ctx.set_out("Variance", tuple(xs[:begin]))


_A.register_rule(["layer_norm"], _infer_layer_norm)


def _label_rank_match(logits, label):
    """label is index-shaped: either logits rank with trailing 1, or
    logits rank - 1."""
    return (len(label) == len(logits) and label[-1] == 1) or \
        (len(label) == len(logits) - 1)


def _infer_softmax_ce(ctx):
    ls = ctx.in_shape("Logits")
    lab = ctx.in_shape("Label")
    if ls is None:
        return
    if lab is not None and not ctx.op.attr("soft_label", False):
        if not (_label_rank_match(ls, lab)
                and _A.unify_shape(tuple(ls[:-1]),
                                   tuple(lab[:len(ls) - 1])) is not None):
            ctx.fail(
                f"Label shape {tuple(lab)} does not index "
                f"Logits{tuple(ls)} (want {tuple(ls[:-1])} or "
                f"{tuple(ls[:-1]) + (1,)})", var=ctx.op.input("Label")[0])
    ctx.set_out("Loss", tuple(ls[:-1]) + (1,))
    ctx.set_out("Softmax", ls, ctx.in_dtype("Logits"))


_A.register_rule(["softmax_with_cross_entropy"], _infer_softmax_ce)


def _infer_cross_entropy(ctx):
    xs = ctx.in_shape("X")
    if xs is None:
        return
    ctx.set_out("Y", tuple(xs[:-1]) + (1,), ctx.in_dtype("X"))


_A.register_rule(["cross_entropy"], _infer_cross_entropy)


def _infer_lookup_table(ctx):
    ws = ctx.in_shape("W")
    ids = ctx.in_shape("Ids")
    if ws is None or ids is None or not ids:
        return
    if ids[-1] == _A.DYN:
        return  # cannot tell whether the trailing dim-1 strip applies
    base = tuple(ids[:-1]) if ids[-1] == 1 else tuple(ids)
    ctx.set_out("Out", base + tuple(ws[1:]), ctx.in_dtype("W"))


_A.register_rule(["lookup_table", "lookup_table_v2"], _infer_lookup_table)


def _infer_top_k(ctx):
    xs = ctx.in_shape("X")
    if xs is None:
        return
    k = ctx.op.attr("k", 1)
    if xs[-1] != _A.DYN and k > xs[-1]:
        ctx.fail(f"k={k} > last dim of X{tuple(xs)}",
                 var=ctx.op.input("X")[0])
    out = tuple(xs[:-1]) + (k,)
    ctx.set_out("Out", out, ctx.in_dtype("X"))
    ctx.set_out("Indices", out, "int64")


_A.register_rule(["top_k"], _infer_top_k)


def _infer_arg_extreme(ctx):
    xs = ctx.in_shape("X")
    if xs is None:
        return
    axis = ctx.op.attr("axis", -1) % len(xs)
    ctx.set_out("Out", tuple(d for i, d in enumerate(xs) if i != axis),
                "int64")


_A.register_rule(["arg_max", "arg_min"], _infer_arg_extreme)


def _infer_accuracy(ctx):
    ind = ctx.in_shape("Indices")
    lab = ctx.in_shape("Label")
    if ind is not None and lab is not None:
        if _A.unify_dim(ind[0], lab[0]) is None:
            ctx.fail(f"Indices batch {ind[0]} != Label batch {lab[0]}",
                     var=ctx.op.input("Label")[0])
    ctx.set_out("Accuracy", (1,))
    ctx.set_out("Correct", (1,))
    ctx.set_out("Total", (1,))


_A.register_rule(["accuracy"], _infer_accuracy)


def _infer_ring_attention(ctx):
    qs = ctx.in_shape("Q")
    if qs is None:
        return
    ctx.set_out("Out", qs, ctx.in_dtype("Q"))


_A.register_rule(["ring_attention"], _infer_ring_attention)


def _infer_fused_attention(ctx):
    """Out is Q's shape in either layout but for V's head width; K has Q's
    head width, V may have another (latent attention), K and V agree in the
    rest and, by the op's `layout`, hold a divisor of Q's heads."""
    layout = ctx.op.attr("layout", "bhld")
    if layout not in _ATTENTION_AXES:
        ctx.fail(f"layout {layout!r}: (B, H, L, dh) is \"bhld\", (B, L, H, dh) is \"blhd\"")
    qs, ks, vs = ctx.in_shape("Q"), ctx.in_shape("K"), ctx.in_shape("V")
    if qs is None:
        return
    if len(qs) != 4:
        ctx.fail(f"Q must have four axes, {layout}, got {qs}")
    heads = _ATTENTION_AXES[layout][0]
    for name, shape in (("K", ks), ("V", vs)):
        if shape is None:
            continue
        if len(shape) != 4 or (name == "K" and shape[-1] != qs[-1] and _A.DYN not in (shape[-1], qs[-1])):
            ctx.fail(f"{name} must have four axes, {layout}, and K Q's head width {qs[-1]}, got {shape}")
        if _A.DYN not in (shape[heads], qs[heads]) and (shape[heads] < 1 or qs[heads] % shape[heads]):
            ctx.fail(f"{name}'s {shape[heads]} heads (axis {heads} of {layout}) do not divide Q's {qs[heads]}")
    if ks is not None and vs is not None and tuple(ks[:3]) != tuple(vs[:3]):
        ctx.fail(f"K {ks} and V {vs} differ in more than the head width")
    picks = ctx.in_shape("Picks")
    if picks is not None and ks is not None:
        positions = _ATTENTION_AXES[layout][1]
        want = (qs[positions], -(-ks[positions] // 32))
        if len(picks) != 3 or (_A.DYN not in tuple(picks[1:]) + want and tuple(picks[1:]) != want):
            ctx.fail(f"Picks must be (B, {want[0]}, {want[1]}): a word of 32 keys' bits a query (sparse_index's), got {picks}")
    ctx.set_out("Out", qs if vs is None else tuple(qs[:3]) + (vs[3],), ctx.in_dtype("Q"))
    if picks is not None:
        ctx.set_out("Lse", (qs[0], qs[heads], qs[_ATTENTION_AXES[layout][1]]), "float32")


_A.register_rule(["fused_attention"], _infer_fused_attention)


# --- static cost rules (core/resource_plan.py) ------------------------------

from ..core import registry as _REG
from ..core import resource_plan as _RP

_RP.register_elementwise_cost("square_error_cost", "label_smooth",
                              flops_per_elem=3.0)
_RP.register_elementwise_cost("dropout", flops_per_elem=2.0)
_RP.register_elementwise_cost("softmax", "log_softmax", "sigmoid_cross_entropy_with_logits",
                              flops_per_elem=8.0)
_RP.register_elementwise_cost("batch_norm", flops_per_elem=6.0)
_RP.register_elementwise_cost("layer_norm", flops_per_elem=10.0)
_RP.register_elementwise_cost("cross_entropy", flops_per_elem=8.0)


def _cost_softmax_ce(ctx):
    """Fused logsumexp formulation (the lowering above, composite AND
    Pallas kernel): the [N, V] logits stream ONCE plus the Label and the
    [N, 1] Loss.  The [N, V] Softmax slot is DCE'd when unfetched, so the
    default io_bytes would double-charge the dominant stream — the exact
    miscosting the ISSUE-17 gap ranking exists to avoid."""
    b = 0
    for slot in ("Logits", "Label"):
        n = ctx.in_name(slot)
        if n is not None:
            b += ctx.env.nbytes(n)
    n = ctx.out_name("Loss")
    if n is not None:
        b += ctx.env.nbytes(n)
    return 8.0 * ctx.in_elems("Logits"), float(b)


_RP.register_cost(["softmax_with_cross_entropy"], _cost_softmax_ce)
_RP.register_elementwise_cost("accuracy", "arg_max", "arg_min",
                              flops_per_elem=2.0)
_RP.register_elementwise_cost("top_k", flops_per_elem=6.0)


def _cost_conv2d(ctx):
    """2 * out_elems * (Cin/groups * kh * kw) — the MACs of the implicit
    GEMM; traffic = img + filter + out."""
    out = ctx.out_shape("Output") or ctx.out_shape("Out")
    filt = ctx.in_shape("Filter")
    if out is None or filt is None:
        return float(ctx.out_elems_total()), ctx.io_bytes()
    cout = max(filt[0], 1)
    per_out = 1
    for d in filt:
        per_out *= max(int(d), 1)
    per_out //= cout  # Cin/groups * kh * kw
    n = 1
    for d in out:
        n *= max(int(d), 1)
    return 2.0 * n * per_out, ctx.io_bytes()


_RP.register_cost(["conv2d", "depthwise_conv2d"], _cost_conv2d)


def _cost_pool2d(ctx):
    k = ctx.attr("ksize", [1, 1]) or [1, 1]
    kk = 1
    for d in (k if isinstance(k, (list, tuple)) else [k]):
        kk *= max(int(d), 1)
    if ctx.attr("global_pooling", False):
        xs = ctx.in_shape("X")
        kk = _elems_xs(xs[2:]) if xs and len(xs) > 2 else kk
    out = ctx.out_elems("Out")
    return float(out * kk), ctx.io_bytes()


def _elems_xs(shape):
    n = 1
    for d in shape:
        n *= max(int(d), 1)
    return n


_RP.register_cost(["pool2d"], _cost_pool2d)


def _cost_lookup_table(ctx):
    """Row gather: traffic = gathered rows in+out plus the ids; the full
    table is NOT streamed (the default io_bytes would charge it)."""
    out_b = 0
    for n in ctx.op.output_arg_names:
        out_b += ctx.env.nbytes(n)
    ids_b = ctx.env.nbytes(ctx.in_name("Ids")) if ctx.in_name("Ids") else 0
    return 0.0, float(2 * out_b + ids_b)


_RP.register_cost(["lookup_table", "lookup_table_v2"], _cost_lookup_table)


def _cost_fused_attention(ctx):
    """QK^T + PV: 4 * B*H*Lq*Lk*dh MACs -> 2 flops each; flash streaming
    keeps the [B,H,Lq,Lk] score tensor out of HBM, so traffic is just
    Q/K/V/Bias in + Out."""
    qs, ks = ctx.in_shape("Q"), ctx.in_shape("K")
    if qs is None or ks is None or len(qs) < 4 or len(ks) < 3:
        return float(ctx.out_elems_total()), ctx.io_bytes()
    heads, positions = _ATTENTION_AXES[ctx.op.attr("layout", "bhld")]
    b, h, lq, dh = qs[0], qs[heads], qs[positions], qs[3]
    vs = ctx.in_shape("V")
    dv = vs[3] if vs is not None and len(vs) == 4 else dh   # QK^T over dh, PV over V's width
    lk = ks[positions]
    pairs = _elems_xs((lq, lk))
    if ctx.op.attr("mask", None) is not None and ctx.op.attr("mask_block", None):
        from .masked_attention import allowed_pairs, window_pairs

        count = window_pairs if ctx.op.attr("mask") == "sliding_window" else allowed_pairs
        pairs = count(lq, ctx.op.attr("mask_block"))  # the pairs the rule allows
    if ctx.op.attr("picks_topk", None):
        topk = min(ctx.op.attr("picks_topk"), lk)   # a query holds min(topk, its position + 1) keys
        pairs = topk * (topk + 1) // 2 + max(lq - topk, 0) * topk
    return 2.0 * _elems_xs((b, h)) * (dh + dv) * pairs, ctx.io_bytes()


_RP.register_cost(["fused_attention"], _cost_fused_attention)
_RP.register_cost(["ring_attention"], _cost_fused_attention)


def _kept_attention(ctx, op, shapes):
    """Where the op takes the splash kernels of `ops/masked_attention.py`
    (`block_causal`, `block_sparse`, `selected`): the output and the float32 log-sum-exp a
    query and head, which only the forward kernels make.  The other paths name
    nothing: no cell runs the flash kernel, the row kernel keeps nothing but its
    output, XLA's attention is XLA's to make again."""
    q, k, v = (operand_of(shapes, op.input(slot)[0]) for slot in ("Q", "K", "V"))
    layout = op.attr("layout", "bhld")
    path = _attention_path(ctx.platform, ctx.mesh, q, k, _structured_mask(op, q, k, layout), op.attr("causal", False),
                           bool(op.input("Bias")), layout, v.shape[-1], ctx.batch_axis, bool(op.input("Picks")))
    if path not in ("block_causal", "block_sparse", "selected"):
        return None
    heads, positions = _ATTENTION_AXES[layout]
    lse_bytes = 4 * q.shape[0] * q.shape[heads] * q.shape[positions]
    return residuals_name(op), shapes.nbytes(op.output("Out")[0]) + lse_bytes


_REG.set_kept("fused_attention", _kept_attention)
