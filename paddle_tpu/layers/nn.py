"""NN layers (reference: python/paddle/fluid/layers/nn.py — fc:213,
conv2d:1991, batch_norm:3036, etc.).  Builders only: each appends program
ops; all numerics live in ops/ lowerings."""
from __future__ import annotations

import numpy as np

from ..core.dtypes import canonical_dtype
from ..core.layer_helper import LayerHelper
from ..core.program import Variable


def _out(helper, dtype, shape=None):
    return helper.create_variable_for_type_inference(dtype, shape=shape)


def _keep_lod(src, out):
    """Propagate the ragged lengths companion through a layer whose output
    keeps the time axis (dropout/scale/embedding/layer_norm/...), so model
    code doesn't hand-thread `_lod_ref` (paddle_tpu/lod.py)."""
    ref = getattr(src, "_lod_ref", None)
    if ref is not None:
        out._lod_ref = ref
        out.lod_level = 1
    return out


def fc(input, size, num_flatten_dims=1, param_attr=None, bias_attr=None, act=None, name=None, precision=None):
    """`precision` ("highest"): the product's precision where the chip's default,
    one pass over bf16-rounded operands, is not enough for a float32 input (a
    gate or a router read in float32); None is no attribute of the op."""
    helper = LayerHelper("fc", name=name, act=act)
    inputs = input if isinstance(input, (list, tuple)) else [input]
    mul_results = []
    for inp in inputs:
        in_shape = inp.shape
        fan_in = int(np.prod(in_shape[num_flatten_dims:]))
        w = helper.create_parameter(param_attr, [fan_in, size], inp.dtype)
        out = _out(helper, inp.dtype, shape=tuple(in_shape[:num_flatten_dims]) + (size,))
        helper.append_op(
            "mul",
            inputs={"X": [inp.name], "Y": [w.name]},
            outputs={"Out": [out.name]},
            attrs={"x_num_col_dims": num_flatten_dims, "y_num_col_dims": 1, **({"precision": precision} if precision else {})},
        )
        mul_results.append(out)
    if len(mul_results) == 1:
        pre_bias = mul_results[0]
    else:
        pre_bias = _out(helper, mul_results[0].dtype, shape=mul_results[0].shape)
        helper.append_op(
            "sum", inputs={"X": [v.name for v in mul_results]}, outputs={"Out": [pre_bias.name]}
        )
    pre_act = helper.append_bias_op(pre_bias, bias_attr, [size], dim_start=num_flatten_dims)
    out = helper.append_activation(pre_act)
    # time-axis-preserving projection keeps the ragged lengths companion
    return _keep_lod(inputs[0], out) if num_flatten_dims >= 2 else out


def embedding(input, size, is_sparse=False, is_distributed=False, padding_idx=None,
              param_attr=None, dtype="float32"):
    helper = LayerHelper("embedding")
    w = helper.create_parameter(param_attr, size, dtype)
    in_shape = input.shape
    out_shape = None
    if in_shape is not None:
        base = in_shape[:-1] if in_shape[-1] == 1 else in_shape
        out_shape = tuple(base) + (size[1],)
    out = _out(helper, dtype, shape=out_shape)
    helper.append_op(
        "lookup_table",
        inputs={"Ids": [input.name], "W": [w.name]},
        outputs={"Out": [out.name]},
        attrs={
            "is_sparse": is_sparse,
            "is_distributed": is_distributed,
            "padding_idx": padding_idx,
        },
    )
    return _keep_lod(input, out)


def conv2d(input, num_filters, filter_size, stride=1, padding=0, dilation=1, groups=None,
           param_attr=None, bias_attr=None, use_cudnn=True, act=None, name=None,
           data_format="NCHW"):
    helper = LayerHelper("conv2d", name=name, act=act)
    groups = groups or 1
    if isinstance(filter_size, int):
        filter_size = [filter_size, filter_size]
    if isinstance(stride, int):
        stride = [stride, stride]
    if isinstance(padding, int):
        padding = [padding, padding]
    if isinstance(dilation, int):
        dilation = [dilation, dilation]
    if data_format not in ("NCHW", "NHWC"):
        raise ValueError(f"conv2d: data_format must be NCHW or NHWC, got {data_format!r}")
    ch_axis = 1 if data_format == "NCHW" else 3
    num_channels = input.shape[ch_axis]
    # filter stays OIHW in both layouts so params are layout-independent
    filter_shape = [num_filters, num_channels // groups, filter_size[0], filter_size[1]]
    from ..core.initializer import NormalInitializer

    fan_in = (num_channels // groups) * filter_size[0] * filter_size[1]
    default_init = NormalInitializer(0.0, float(np.sqrt(2.0 / fan_in)))
    w = helper.create_parameter(param_attr, filter_shape, input.dtype, default_initializer=default_init)
    out_shape = None
    h_axis, w_axis = (2, 3) if data_format == "NCHW" else (1, 2)
    # padding may be [ph, pw] (symmetric) or [top, bottom, left, right]
    pad_hw = ((padding[0], padding[1]), (padding[2], padding[3])) \
        if len(padding) == 4 else ((padding[0], padding[0]), (padding[1], padding[1]))
    if input.shape is not None and input.shape[h_axis] is not None:
        def _osz(i, k, p2, s, d):
            if i is None or i < 0:
                return -1
            return (i + p2[0] + p2[1] - (d * (k - 1) + 1)) // s + 1
        oh = _osz(input.shape[h_axis], filter_size[0], pad_hw[0], stride[0], dilation[0])
        ow = _osz(input.shape[w_axis], filter_size[1], pad_hw[1], stride[1], dilation[1])
        if data_format == "NCHW":
            out_shape = (input.shape[0], num_filters, oh, ow)
        else:
            out_shape = (input.shape[0], oh, ow, num_filters)
    pre_bias = _out(helper, input.dtype, shape=out_shape)
    helper.append_op(
        "conv2d",
        inputs={"Input": [input.name], "Filter": [w.name]},
        outputs={"Output": [pre_bias.name]},
        attrs={
            "strides": stride,
            "paddings": padding,
            "dilations": dilation,
            "groups": groups,
            "data_format": data_format,
        },
    )
    pre_act = helper.append_bias_op(pre_bias, bias_attr, [num_filters], dim_start=ch_axis)
    return helper.append_activation(pre_act)


def conv2d_transpose(input, num_filters, output_size=None, filter_size=None, stride=1, padding=0,
                     dilation=1, groups=None, param_attr=None, bias_attr=None, act=None, name=None):
    helper = LayerHelper("conv2d_transpose", name=name, act=act)
    groups = groups or 1
    if isinstance(filter_size, int):
        filter_size = [filter_size, filter_size]
    if isinstance(stride, int):
        stride = [stride, stride]
    if isinstance(padding, int):
        padding = [padding, padding]
    if isinstance(dilation, int):
        dilation = [dilation, dilation]
    num_channels = input.shape[1]
    filter_shape = [num_channels, num_filters // groups, filter_size[0], filter_size[1]]
    w = helper.create_parameter(param_attr, filter_shape, input.dtype)
    pre_bias = _out(helper, input.dtype)
    helper.append_op(
        "conv2d_transpose",
        inputs={"Input": [input.name], "Filter": [w.name]},
        outputs={"Output": [pre_bias.name]},
        attrs={"strides": stride, "paddings": padding, "dilations": dilation, "groups": groups},
    )
    pre_act = helper.append_bias_op(pre_bias, bias_attr, [num_filters], dim_start=1)
    return helper.append_activation(pre_act)


def pool2d(input, pool_size=-1, pool_type="max", pool_stride=1, pool_padding=0,
           global_pooling=False, use_cudnn=True, ceil_mode=False, exclusive=True, name=None,
           data_format="NCHW"):
    helper = LayerHelper("pool2d", name=name)
    if isinstance(pool_size, int):
        pool_size = [pool_size, pool_size]
    if isinstance(pool_stride, int):
        pool_stride = [pool_stride, pool_stride]
    if isinstance(pool_padding, int):
        pool_padding = [pool_padding, pool_padding]
    if data_format not in ("NCHW", "NHWC"):
        raise ValueError(f"pool2d: data_format must be NCHW or NHWC, got {data_format!r}")
    h_axis, w_axis = (2, 3) if data_format == "NCHW" else (1, 2)
    out_shape = None
    if input.shape is not None and not global_pooling:
        def _osz(i, k, p, s):
            if i is None or i < 0:
                return -1
            return (i + 2 * p - k) // s + 1
        oh = _osz(input.shape[h_axis], pool_size[0], pool_padding[0], pool_stride[0])
        ow = _osz(input.shape[w_axis], pool_size[1], pool_padding[1], pool_stride[1])
        if data_format == "NCHW":
            out_shape = (input.shape[0], input.shape[1], oh, ow)
        else:
            out_shape = (input.shape[0], oh, ow, input.shape[3])
    elif input.shape is not None:
        if data_format == "NCHW":
            out_shape = (input.shape[0], input.shape[1], 1, 1)
        else:
            out_shape = (input.shape[0], 1, 1, input.shape[3])
    out = _out(helper, input.dtype, shape=out_shape)
    helper.append_op(
        "pool2d",
        inputs={"X": [input.name]},
        outputs={"Out": [out.name]},
        attrs={
            "pooling_type": pool_type,
            "ksize": pool_size,
            "strides": pool_stride,
            "paddings": pool_padding,
            "global_pooling": global_pooling,
            "ceil_mode": ceil_mode,
            "exclusive": exclusive,
            "data_format": data_format,
        },
    )
    return out


def batch_norm(input, act=None, is_test=False, momentum=0.9, epsilon=1e-5, param_attr=None,
               bias_attr=None, data_layout="NCHW", name=None, moving_mean_name=None,
               moving_variance_name=None, use_global_stats=False):
    helper = LayerHelper("batch_norm", name=name, act=act)
    ch = input.shape[1] if data_layout == "NCHW" else input.shape[-1]
    dtype = input.dtype
    # norm params and running stats stay fp32 even for bf16/fp16 activations
    # (reference batch_norm_op.cc keeps fp32 scale/bias for fp16 kernels);
    # the lowering normalizes in fp32 and casts Y back to the input dtype
    param_dtype = "float32" if str(dtype) in ("bfloat16", "float16") else dtype
    from ..core.initializer import ConstantInitializer
    from ..core.param_attr import ParamAttr

    scale = helper.create_parameter(param_attr, [ch], param_dtype, default_initializer=ConstantInitializer(1.0))
    bias = helper.create_parameter(bias_attr, [ch], param_dtype, is_bias=True)
    # moving stats: persistable, not trainable
    mean_attr = ParamAttr(name=moving_mean_name, initializer=ConstantInitializer(0.0), trainable=False)
    var_attr = ParamAttr(name=moving_variance_name, initializer=ConstantInitializer(1.0), trainable=False)
    mean = helper.create_parameter(mean_attr, [ch], param_dtype)
    variance = helper.create_parameter(var_attr, [ch], param_dtype)
    mean.stop_gradient = True
    variance.stop_gradient = True

    saved_mean = _out(helper, dtype, shape=(ch,))
    saved_var = _out(helper, dtype, shape=(ch,))
    out = _out(helper, dtype, shape=input.shape)
    helper.append_op(
        "batch_norm",
        inputs={
            "X": [input.name],
            "Scale": [scale.name],
            "Bias": [bias.name],
            "Mean": [mean.name],
            "Variance": [variance.name],
        },
        outputs={
            "Y": [out.name],
            "MeanOut": [mean.name],
            "VarianceOut": [variance.name],
            "SavedMean": [saved_mean.name],
            "SavedVariance": [saved_var.name],
        },
        attrs={
            "momentum": momentum,
            "epsilon": epsilon,
            "is_test": is_test,
            "data_layout": data_layout,
            "use_global_stats": use_global_stats,
        },
    )
    return helper.append_activation(out)


def layer_norm(input, scale=True, shift=True, begin_norm_axis=1, epsilon=1e-5,
               param_attr=None, bias_attr=None, act=None, name=None):
    helper = LayerHelper("layer_norm", name=name, act=act)
    dtype = input.dtype
    norm_size = int(np.prod(input.shape[begin_norm_axis:]))
    inputs = {"X": [input.name]}
    from ..core.initializer import ConstantInitializer

    if scale:
        s = helper.create_parameter(param_attr, [norm_size], dtype, default_initializer=ConstantInitializer(1.0))
        inputs["Scale"] = [s.name]
    if shift:
        b = helper.create_parameter(bias_attr, [norm_size], dtype, is_bias=True)
        inputs["Bias"] = [b.name]
    out = _out(helper, dtype, shape=input.shape)
    mean = _out(helper, dtype)
    var = _out(helper, dtype)
    helper.append_op(
        "layer_norm",
        inputs=inputs,
        outputs={"Y": [out.name], "Mean": [mean.name], "Variance": [var.name]},
        attrs={"epsilon": epsilon, "begin_norm_axis": begin_norm_axis},
    )
    return _keep_lod(input, helper.append_activation(out))


def dropout(x, dropout_prob, is_test=False, seed=None, name=None,
            dropout_implementation="downgrade_in_infer"):
    helper = LayerHelper("dropout", name=name)
    out = _out(helper, x.dtype, shape=x.shape)
    mask = _out(helper, x.dtype, shape=x.shape)
    helper.append_op(
        "dropout",
        inputs={"X": [x.name]},
        outputs={"Out": [out.name], "Mask": [mask.name]},
        attrs={
            "dropout_prob": dropout_prob,
            "is_test": is_test,
            "fix_seed": seed is not None,
            "seed": seed if seed is not None else 0,
            "dropout_implementation": dropout_implementation,
        },
    )
    return _keep_lod(x, out)


def softmax(input, use_cudnn=False, name=None, axis=-1):
    helper = LayerHelper("softmax", name=name)
    out = _out(helper, input.dtype, shape=input.shape)
    helper.append_op(
        "softmax", inputs={"X": [input.name]}, outputs={"Out": [out.name]}, attrs={"axis": axis}
    )
    return out


def cross_entropy(input, label, soft_label=False, ignore_index=-100):
    helper = LayerHelper("cross_entropy")
    shape = None
    if input.shape is not None:
        shape = tuple(input.shape[:-1]) + (1,)
    out = _out(helper, input.dtype, shape=shape)
    helper.append_op(
        "cross_entropy",
        inputs={"X": [input.name], "Label": [label.name]},
        outputs={"Y": [out.name]},
        attrs={"soft_label": soft_label, "ignore_index": ignore_index},
    )
    return out


def softmax_with_cross_entropy(logits, label, soft_label=False, ignore_index=-100,
                               numeric_stable_mode=True, return_softmax=False):
    helper = LayerHelper("softmax_with_cross_entropy")
    loss_shape = tuple(logits.shape[:-1]) + (1,) if logits.shape is not None else None
    softmax_out = _out(helper, logits.dtype, shape=logits.shape)
    loss = _out(helper, logits.dtype, shape=loss_shape)
    helper.append_op(
        "softmax_with_cross_entropy",
        inputs={"Logits": [logits.name], "Label": [label.name]},
        outputs={"Loss": [loss.name], "Softmax": [softmax_out.name]},
        attrs={"soft_label": soft_label, "ignore_index": ignore_index},
    )
    _keep_lod(logits, loss)
    if return_softmax:
        return loss, softmax_out
    return loss


def sigmoid_cross_entropy_with_logits(x, label, ignore_index=-100, name=None, normalize=False):
    helper = LayerHelper("sigmoid_cross_entropy_with_logits", name=name)
    out = _out(helper, x.dtype, shape=x.shape)
    helper.append_op(
        "sigmoid_cross_entropy_with_logits",
        inputs={"X": [x.name], "Label": [label.name]},
        outputs={"Out": [out.name]},
        attrs={"ignore_index": ignore_index, "normalize": normalize},
    )
    return out


def square_error_cost(input, label):
    helper = LayerHelper("square_error_cost")
    out = _out(helper, input.dtype, shape=input.shape)
    helper.append_op(
        "square_error_cost",
        inputs={"X": [input.name], "Y": [label.name]},
        outputs={"Out": [out.name]},
    )
    return out


def mean(x, name=None):
    helper = LayerHelper("mean", name=name)
    out = _out(helper, x.dtype, shape=(1,))
    helper.append_op("mean", inputs={"X": [x.name]}, outputs={"Out": [out.name]})
    return out


def mul(x, y, x_num_col_dims=1, y_num_col_dims=1):
    helper = LayerHelper("mul")
    out = _out(helper, x.dtype)
    helper.append_op(
        "mul",
        inputs={"X": [x.name], "Y": [y.name]},
        outputs={"Out": [out.name]},
        attrs={"x_num_col_dims": x_num_col_dims, "y_num_col_dims": y_num_col_dims},
    )
    return out


def matmul(x, y, transpose_x=False, transpose_y=False, alpha=1.0, name=None):
    helper = LayerHelper("matmul", name=name)
    out = _out(helper, x.dtype)
    helper.append_op(
        "matmul",
        inputs={"X": [x.name], "Y": [y.name]},
        outputs={"Out": [out.name]},
        attrs={"transpose_X": transpose_x, "transpose_Y": transpose_y, "alpha": float(alpha)},
    )
    return out


def reshape(x, shape, actual_shape=None, act=None, inplace=False, name=None):
    helper = LayerHelper("reshape2", name=name)
    out_shape = []
    for i, s in enumerate(shape):
        if s == 0:
            out_shape.append(x.shape[i] if x.shape is not None else -1)
        else:
            out_shape.append(s)
    out = _out(helper, x.dtype, shape=tuple(out_shape))
    xshape = _out(helper, x.dtype)
    helper.append_op(
        "reshape2",
        inputs={"X": [x.name]},
        outputs={"Out": [out.name], "XShape": [xshape.name]},
        attrs={"shape": list(shape)},
    )
    return out


def transpose(x, perm, name=None):
    helper = LayerHelper("transpose2", name=name)
    shape = tuple(x.shape[p] for p in perm) if x.shape is not None else None
    out = _out(helper, x.dtype, shape=shape)
    xshape = _out(helper, x.dtype)
    helper.append_op(
        "transpose2",
        inputs={"X": [x.name]},
        outputs={"Out": [out.name], "XShape": [xshape.name]},
        attrs={"axis": list(perm)},
    )
    return out


def split(input, num_or_sections, dim=-1, name=None):
    helper = LayerHelper("split", name=name)
    dim = dim if dim >= 0 else len(input.shape) + dim
    if isinstance(num_or_sections, int):
        n = num_or_sections
        attrs = {"num": n, "sections": [], "axis": dim}
    else:
        n = len(num_or_sections)
        attrs = {"num": 0, "sections": list(num_or_sections), "axis": dim}
    outs = [_out(helper, input.dtype) for _ in range(n)]
    helper.append_op(
        "split", inputs={"X": [input.name]}, outputs={"Out": [o.name for o in outs]}, attrs=attrs
    )
    return outs


def _reduce_layer(op_type):
    def f(input, dim=None, keep_dim=False, name=None):
        helper = LayerHelper(op_type, name=name)
        out = _out(helper, input.dtype)
        if dim is None:
            attrs = {"dim": [0], "keep_dim": keep_dim, "reduce_all": True}
        else:
            attrs = {
                "dim": [dim] if isinstance(dim, int) else list(dim),
                "keep_dim": keep_dim,
                "reduce_all": False,
            }
        helper.append_op(op_type, inputs={"X": [input.name]}, outputs={"Out": [out.name]}, attrs=attrs)
        return out

    f.__name__ = op_type
    return f


reduce_sum = _reduce_layer("reduce_sum")
reduce_mean = _reduce_layer("reduce_mean")
reduce_max = _reduce_layer("reduce_max")
reduce_min = _reduce_layer("reduce_min")
reduce_prod = _reduce_layer("reduce_prod")


def topk(input, k, name=None):
    helper = LayerHelper("top_k", name=name)
    shape = tuple(input.shape[:-1]) + (k,) if input.shape is not None else None
    values = _out(helper, input.dtype, shape=shape)
    indices = _out(helper, "int64", shape=shape)
    helper.append_op(
        "top_k",
        inputs={"X": [input.name]},
        outputs={"Out": [values.name], "Indices": [indices.name]},
        attrs={"k": k},
    )
    return values, indices


def one_hot(input, depth):
    helper = LayerHelper("one_hot")
    out = _out(helper, "float32")
    helper.append_op(
        "one_hot", inputs={"X": [input.name]}, outputs={"Out": [out.name]}, attrs={"depth": depth}
    )
    return out


def clip(x, min, max, name=None):
    helper = LayerHelper("clip", name=name)
    out = _out(helper, x.dtype, shape=x.shape)
    helper.append_op(
        "clip", inputs={"X": [x.name]}, outputs={"Out": [out.name]}, attrs={"min": min, "max": max}
    )
    return out


def clip_by_norm(x, max_norm, name=None):
    helper = LayerHelper("clip_by_norm", name=name)
    out = _out(helper, x.dtype, shape=x.shape)
    helper.append_op(
        "clip_by_norm",
        inputs={"X": [x.name]},
        outputs={"Out": [out.name]},
        attrs={"max_norm": max_norm},
    )
    return out


def label_smooth(label, prior_dist=None, epsilon=0.1, dtype="float32", name=None):
    helper = LayerHelper("label_smooth", name=name)
    out = _out(helper, dtype, shape=label.shape)
    inputs = {"X": [label.name]}
    if prior_dist is not None:
        inputs["PriorDist"] = [prior_dist.name]
    helper.append_op(
        "label_smooth", inputs=inputs, outputs={"Out": [out.name]}, attrs={"epsilon": float(epsilon)}
    )
    return out


def _elementwise_layer(op_type):
    def f(x, y, axis=-1, act=None, name=None):
        helper = LayerHelper(op_type, name=name, act=act)
        out = _out(helper, x.dtype, shape=x.shape)
        helper.append_op(
            op_type,
            inputs={"X": [x.name], "Y": [y.name]},
            outputs={"Out": [out.name]},
            attrs={"axis": axis},
        )
        return _keep_lod(x, helper.append_activation(out))

    f.__name__ = op_type
    return f


elementwise_add = _elementwise_layer("elementwise_add")
elementwise_sub = _elementwise_layer("elementwise_sub")
elementwise_mul = _elementwise_layer("elementwise_mul")
elementwise_div = _elementwise_layer("elementwise_div")
elementwise_max = _elementwise_layer("elementwise_max")
elementwise_min = _elementwise_layer("elementwise_min")
elementwise_pow = _elementwise_layer("elementwise_pow")


def _act_layer(op_type):
    def f(x, name=None):
        helper = LayerHelper(op_type, name=name)
        out = _out(helper, x.dtype, shape=x.shape)
        helper.append_op(op_type, inputs={"X": [x.name]}, outputs={"Out": [out.name]})
        return out

    f.__name__ = op_type
    return f


relu = _act_layer("relu")
relu6 = _act_layer("relu6")
sigmoid = _act_layer("sigmoid")
logsigmoid = _act_layer("logsigmoid")
tanh = _act_layer("tanh")
exp = _act_layer("exp")
log = _act_layer("log")
sqrt = _act_layer("sqrt")
abs = _act_layer("abs")
square = _act_layer("square")
softplus = _act_layer("softplus")
softsign = _act_layer("softsign")
gelu = _act_layer("gelu")
erf = _act_layer("erf")
floor = _act_layer("floor")
ceil = _act_layer("ceil")
round = _act_layer("round")
reciprocal = _act_layer("reciprocal")
sin = _act_layer("sin")
cos = _act_layer("cos")


def leaky_relu(x, alpha=0.02, name=None):
    helper = LayerHelper("leaky_relu", name=name)
    out = _out(helper, x.dtype, shape=x.shape)
    helper.append_op(
        "leaky_relu", inputs={"X": [x.name]}, outputs={"Out": [out.name]}, attrs={"alpha": alpha}
    )
    return out


def scale(x, scale=1.0, bias=0.0, bias_after_scale=True, act=None, name=None):
    helper = LayerHelper("scale", name=name, act=act)
    out = _out(helper, x.dtype, shape=x.shape)
    helper.append_op(
        "scale",
        inputs={"X": [x.name]},
        outputs={"Out": [out.name]},
        attrs={"scale": float(scale), "bias": float(bias), "bias_after_scale": bias_after_scale},
    )
    out = helper.append_activation(out)
    _keep_lod(x, out)
    return out


def pow(x, factor=1.0, name=None):
    helper = LayerHelper("pow", name=name)
    out = _out(helper, x.dtype, shape=x.shape)
    helper.append_op(
        "pow", inputs={"X": [x.name]}, outputs={"Out": [out.name]}, attrs={"factor": float(factor)}
    )
    return out


def squeeze(input, axes, name=None):
    helper = LayerHelper("squeeze2", name=name)
    out = _out(helper, input.dtype)
    xshape = _out(helper, input.dtype)
    helper.append_op(
        "squeeze2",
        inputs={"X": [input.name]},
        outputs={"Out": [out.name], "XShape": [xshape.name]},
        attrs={"axes": list(axes)},
    )
    return out


def unsqueeze(input, axes, name=None):
    helper = LayerHelper("unsqueeze2", name=name)
    out = _out(helper, input.dtype)
    xshape = _out(helper, input.dtype)
    helper.append_op(
        "unsqueeze2",
        inputs={"X": [input.name]},
        outputs={"Out": [out.name], "XShape": [xshape.name]},
        attrs={"axes": list(axes)},
    )
    return out


def stack(x, axis=0):
    helper = LayerHelper("stack")
    xs = x if isinstance(x, (list, tuple)) else [x]
    out = _out(helper, xs[0].dtype)
    helper.append_op(
        "stack", inputs={"X": [v.name for v in xs]}, outputs={"Y": [out.name]}, attrs={"axis": axis}
    )
    return out


def slice(input, axes, starts, ends):
    helper = LayerHelper("slice")
    shape = None
    if input.shape is not None:
        shape = list(input.shape)
        for ax, st, en in zip(axes, starts, ends):
            dim = shape[ax]
            if dim is None or dim < 0:
                continue
            st2 = max(st + dim, 0) if st < 0 else min(st, dim)
            en2 = max(en + dim, 0) if en < 0 else min(en, dim)
            shape[ax] = max(en2 - st2, 0)
        shape = tuple(shape)
    out = _out(helper, input.dtype, shape=shape)
    helper.append_op(
        "slice",
        inputs={"Input": [input.name]},
        outputs={"Out": [out.name]},
        attrs={"axes": list(axes), "starts": list(starts), "ends": list(ends)},
    )
    return out


def ring_attention(q, k, v, causal=False, sp_axis="sp", batch_axis="dp", name=None):
    """Sequence-parallel attention over (B, H, L, dh) tensors; L shards over
    the `sp` mesh axis when the program runs on a mesh carrying it (new
    capability vs the reference — SURVEY.md §5.7)."""
    helper = LayerHelper("ring_attention", name=name)
    out = _out(helper, q.dtype, shape=q.shape)
    helper.append_op(
        "ring_attention",
        inputs={"Q": [q.name], "K": [k.name], "V": [v.name]},
        outputs={"Out": [out.name]},
        attrs={"causal": causal, "sp_axis": sp_axis, "batch_axis": batch_axis},
    )
    return out


def space_to_depth(x, blocksize, name=None):
    """reference layers/nn.py:10411 space_to_depth over space_to_depth_op:
    [B, C, H, W] -> [B, C*bs^2, H/bs, W/bs] (C must divide bs^2 — the
    reference InferShape enforces this quirk)."""
    helper = LayerHelper("space_to_depth", name=name)
    bs = int(blocksize)
    shape = None
    if x.shape is not None and None not in x.shape[1:]:
        b, c, h, w = x.shape
        shape = (b, c * bs * bs, h // bs, w // bs)
    out = _out(helper, x.dtype, shape=shape)
    helper.append_op("space_to_depth", inputs={"X": [x.name]},
                     outputs={"Out": [out.name]}, attrs={"blocksize": bs})
    return out


def fused_attention(q, k, v, bias=None, causal=False, scale=None, mask=None, mask_block=None,
                    layout="bhld", name=None, kept_kv=False, picks=None, picks_topk=None, keep=None):
    """Fused scaled-dot-product attention over (B, H, L, dh) tensors, with
    float32 scores and softmax whatever the operands' dtype.  `layout="blhd"`
    says that `q`, `k`, `v` and the result are (B, L, H, dh) instead, the
    layout a projection's output reshapes to for nothing: the same
    mathematics, and no transpose in the program round the op (the whole-row
    kernel reads that layout as it is; every other lowering transposes at its
    own edge).  On the TPU the lowering takes its tiling from what it can
    observe (ops/nn_ops.py): from 2048 keys the block-skipping splash kernels
    for a causal mask without a bias and the streaming flash kernel otherwise,
    a whole-row kernel for bf16 sequences of 256 to 512 (from 384 where the
    operands are heads-major; the scores never reach HBM in any of them,
    forward or backward), XLA's attention otherwise.  `bias` is an additive
    pre-softmax mask, (B, 1|H, Lq, Lk).  `scale` defaults to 1/sqrt(dh).

    `k` and `v` may have fewer heads than `q`, a divisor of its count (grouped
    key/value heads): query head j reads key/value head j div (Hq / Hkv).  `v`
    may have another head width than `q` and `k` (latent attention's 192-wide
    queries and keys beside 128-wide values); the result has `v`'s.

    `mask="block_diffusion"` with `mask_block=B` is the mask of
    block-diffusion training over the 2L positions [noised ; clean] of L
    tokens in blocks of B (`ops/masked_attention.py: block_diffusion_allowed`):
    a noised block sees itself and the clean blocks before it, a clean block
    the clean blocks up to itself.  The mask is two attributes of the op, not
    a tensor: L is half the length and the rule is computed from positions,
    so nothing of [2L, 2L] exists on the TPU, where a block-sparse kernel
    skips the three quarters of the square the rule empties.

    `mask="sliding_window"` with `mask_block=W` (for this rule the attribute is
    the WINDOW, in keys) lets query i see the keys j with i - W < j <= i, its
    own position the last of W, over as many keys as queries
    (`ops/masked_attention.py: window_allowed`).  On the TPU the same kernels
    visit only the blocks the band touches; a window as long as the sequence
    is the causal mask.

    `kept_kv` says that `k` and `v` are tensors ANOTHER layer projected and
    kept (a cross-decoder's attention on the self-decoder's keys and values):
    the same mathematics, counted in `lowering.kept_tensor_readers`.

    `picks` is a mask that is DATA beside the masks that are rules: int32 (B,
    Lq, Lk / 32), bit j of word w of a query set where the query holds key
    32 w + j, as `layers.sparse_index` chooses them every step.  The softmax
    runs over a query's picks and no other pair has weight (with `causal`,
    none above the diagonal either); on the TPU the splash kernels read the
    mask a block at a time and skip a block that holds no chosen pair.
    `picks_topk`, the most keys a query holds, prices the op for the planner.
    Under `picks` the op also hands on each query's float32 log-sum-exp over
    its keys, (B, H, Lq): `keep`, a dict, receives it as `keep["lse"]`
    (`index_alignment` steadies its own softmax by it)."""
    helper = LayerHelper("fused_attention", name=name)
    out = _out(helper, q.dtype, shape=tuple(q.shape[:-1]) + (v.shape[-1],))
    inputs = {"Q": [q.name], "K": [k.name], "V": [v.name]}
    if bias is not None:
        inputs["Bias"] = [bias.name]
    attrs = {"causal": causal}
    if layout != "bhld":
        attrs["layout"] = layout  # the op's `infer=` rule refuses a layout it does not know
    if scale is not None:
        attrs["scale"] = float(scale)
    if mask is not None:
        attrs["mask"] = str(mask)
        attrs["mask_block"] = int(mask_block)
    if kept_kv:
        attrs["kept_kv"] = True
    if picks is not None:
        inputs["Picks"] = [picks.name]
        if picks_topk:
            attrs["picks_topk"] = int(picks_topk)
    outputs = {"Out": [out.name]}
    if picks is not None:
        heads, positions = (1, 2) if layout == "bhld" else (2, 1)
        lse = _out(helper, "float32", shape=(q.shape[0], q.shape[heads], q.shape[positions]))
        outputs["Lse"] = [lse.name]
        if keep is not None:
            keep["lse"] = lse
    helper.append_op("fused_attention", inputs=inputs, outputs=outputs, attrs=attrs)
    return out


def sparse_index(q_index, k_index, weights, topk, name=None):
    """The learned choice of keys of DeepSeek Sparse Attention's indexer
    (`ops/sparse_index_ops.py`): from the indexer's queries `q_index` (B, L, Hi,
    Di), its ONE key a token `k_index` (B, L, 1, Di) and a float32 weight a
    query and index head `weights` (B, L, Hi), the scores I[t, s] = sum_j w[t,
    j] Hi^-0.5 Di^-0.5 relu(qI[t, j] . kI[s]) in float32 over s <= t, and for
    every query the min(`topk`, t + 1) keys of the largest scores, the lower
    index first among equals.  Returns the picks, int32 (B, L, L / 32): bit j
    of word w of query t set where t holds key 32 w + j; `fused_attention(picks=)`
    and `index_alignment` read them.  No gradient passes the choice, and a
    `recompute_scope` round the op keeps it: the forward made again reads it.
    `train_loop` publishes a `kind="sparse_index"` record a logged step."""
    helper = LayerHelper("sparse_index", name=name)
    batch, length = q_index.shape[0], int(q_index.shape[1])
    picks = _out(helper, "int32", shape=(batch, length, length // 32))
    stats = _out(helper, "int32", shape=(5,))
    picks.stop_gradient = stats.stop_gradient = True
    helper.append_op("sparse_index", inputs={"QI": [q_index.name], "KI": [k_index.name], "W": [weights.name]},
                     outputs={"Picks": [picks.name], "Stats": [stats.name]}, attrs={"topk": int(topk)})
    return picks


def index_alignment(q_index, k_index, weights, picks, q, k, lse, scale=None, name=None):
    """The loss that trains `sparse_index`'s indexer: the mean over rows and
    queries of KL(p_t || softmax over the query's picks of its index scores),
    p_t the main attention's probabilities over the picks summed over its
    heads, a constant.  `q` (B, Hq, L, dh) and `k` (B, Hkv, L, dh) are that
    attention's operands, heads-major, `scale` its scores' scale (dh^-0.5),
    `lse` (B, Hq, L) its log-sum-exp a query (`fused_attention(keep=)`): the
    target's softmax is the op's own and `lse` only steadies it.  Returns [1]
    float32; its gradient reaches `q_index`, `k_index` and `weights` and
    nothing else."""
    helper = LayerHelper("index_alignment", name=name)
    out = _out(helper, "float32", shape=(1,))
    rows = _out(helper, "float32", shape=(q_index.shape[0],))     # each row's own term: the op's output `Rows`
    attrs = {} if scale is None else {"scale": float(scale)}
    helper.append_op("index_alignment",
                     inputs={"QI": [q_index.name], "KI": [k_index.name], "W": [weights.name], "Picks": [picks.name],
                             "Q": [q.name], "K": [k.name], "Lse": [lse.name]},
                     outputs={"Out": [out.name], "Rows": [rows.name]}, attrs=attrs)
    return out


def rms_norm(input, begin_norm_axis=1, epsilon=1e-5, param_attr=None, name=None):
    """Root-mean-square norm over the axes from `begin_norm_axis` on, with a
    learned gain (initialised to 1; none with `param_attr=False`) and no shift:
    y = x / sqrt(mean(x^2) + epsilon) * g.  Statistics are float32 whatever the
    input's dtype."""
    helper = LayerHelper("rms_norm", name=name)
    from ..core.initializer import ConstantInitializer

    norm_size = int(np.prod(input.shape[begin_norm_axis:]))
    inputs = {"X": [input.name]}
    if param_attr is not False:   # False: no gain (a plain normalisation, as an L2 norm a head is)
        inputs["Scale"] = [helper.create_parameter(param_attr, [norm_size], input.dtype,
                                                   default_initializer=ConstantInitializer(1.0)).name]
    out = _out(helper, input.dtype, shape=input.shape)
    helper.append_op(
        "rms_norm", inputs=inputs,
        outputs={"Y": [out.name]},
        attrs={"epsilon": epsilon, "begin_norm_axis": begin_norm_axis})
    return _keep_lod(input, out)


def exit_loss(ce, gate_logit, beta=0.0, name=None):
    """The loss of a model that may leave after any of T passes (`layers.Repeat`):
    `ce` and `gate_logit` are [T, ...], exit t's cross entropy and exit-gate
    logit at every position.  With lam_t = sigmoid(gate_logit_t), p_t = lam_t
    prod_{j<t} (1 - lam_j) for t < T and p_T the rest (the last gate is not
    read), the loss is mean over positions of sum_t p_t ce_t - beta H(p): the
    expected task loss under the learned exit distribution, held towards the
    uniform distribution by its entropy.  float32 throughout.  Returns (loss
    [1], p [T, ...]); the op's `ExitMass`, `Entropy` and `ExitCE` outputs are
    published a logged step by `train_loop` as a `kind="loop_exit"` record."""
    helper = LayerHelper("exit_loss", name=name)
    loss = _out(helper, "float32", shape=(1,))
    p = _out(helper, "float32", shape=ce.shape)
    stats = {slot: _out(helper, "float32") for slot in ("ExitMass", "Entropy", "ExitCE")}
    helper.append_op(
        "exit_loss", inputs={"CE": [ce.name], "Gate": [gate_logit.name]},
        outputs={"Loss": [loss.name], "P": [p.name], **{s: [v.name] for s, v in stats.items()}},
        attrs={"beta": float(beta)})
    return loss, p


def rotary_embedding(x, positions, theta=10000.0, name=None, layout="bhld", interleave=False, rotary_dim=None,
                     inv_freq=None, scale=1.0):
    """Rotary position embedding of (B, H, L, dh) queries or keys, or with
    `layout="blhd"` of (B, L, H, dh) as a projection's reshape leaves them;
    `positions` is the (B, L) integer position of every token, fed like the
    ids.  Feature i turns with feature i + dh/2 (the rotate-half convention),
    or with `interleave` feature 2i with 2i + 1.  The angles are float32.

    `rotary_dim` = r < dh turns the LEADING r features of a head alone (i with
    i + r/2) and passes the other dh - r as they are (a partial rotary
    embedding); `inv_freq`, r/2 frequencies, takes the place of theta^(-2i/r)
    (a stretched table: YaRN's blend, `models.transformer.yarn_frequencies`);
    `scale` multiplies cos and sin (YaRN's attention factor: the turned
    features grow by it, the passed ones do not).  A default is no attribute of
    the op: the programs that stood keep their text."""
    if layout not in ("bhld", "blhd"):
        raise ValueError(f"rotary_embedding: layout={layout!r}; \"bhld\" (B, H, L, dh) or \"blhd\" (B, L, H, dh)")
    width = int(x.shape[-1])
    turned = width if rotary_dim is None else int(rotary_dim)
    if not 0 < turned <= width or turned % 2:
        raise ValueError(f"rotary_embedding: rotary_dim={rotary_dim} of a head {width} wide; an even number of leading "
                         "features, at most the head")
    if inv_freq is not None and len(inv_freq) != turned // 2:
        raise ValueError(f"rotary_embedding: inv_freq holds {len(inv_freq)} frequencies for {turned // 2} pairs")
    helper = LayerHelper("rotary_embedding", name=name)
    out = _out(helper, x.dtype, shape=x.shape)
    attrs = {"theta": float(theta)}
    if layout != "bhld":
        attrs["layout"] = layout
    if interleave:
        attrs["interleave"] = True
    if turned != width:
        attrs["rotary_dim"] = turned
    if inv_freq is not None:
        attrs["inv_freq"] = tuple(float(f) for f in inv_freq)
    if float(scale) != 1.0:
        attrs["scale"] = float(scale)
    helper.append_op(
        "rotary_embedding", inputs={"X": [x.name], "Positions": [positions.name]},
        outputs={"Out": [out.name]}, attrs=attrs)
    return out


def moe(input, num_experts, expert_width, top_k, norm_topk_prob=False,
        router_attr=None, gate_attr=None, up_attr=None, down_attr=None, held=None, name=None,
        scoring="softmax", bias_attr=None, routed_scaling_factor=1.0, norm_eps=0.0,
        shared_experts=0, shared_attrs=None, activation="silu", gated=True, latent_size=None, latent_attrs=None,
        shared_width=None, router_input=None, shared_gate_attr=None):
    """A layer of routed experts over (..., d): a float32 router picks
    `top_k` of `num_experts` experts of width `expert_width` for every token;
    their outputs are summed, weighted by the router's scores (renormalised
    over the chosen ones only if `norm_topk_prob`, by their sum + `norm_eps`;
    times `routed_scaling_factor`).  No capacity limit: no token is dropped.

    The experts' form is two attributes of the one op `moe_experts`: `gated`
    (the default) with `activation="silu"` is W_down(silu(W_gate x) * (W_up x)),
    three matrices an expert; `gated=False` is W_down(act(W_up x)), TWO matrices
    and no gate; `activation` is one of three: "silu", "relu" (with the gate:
    W_down(relu(W_gate x) * (W_up x)), the gated ReLU) and "relu2", relu(.)^2.
    `latent_size=L` puts the
    experts in a latent: the layer projects the token into L dimensions ONCE (u
    = x W_in, `latent_attrs[0]`), the router still reads x, every expert's
    matrices are (L, F) and (F, L), and the weighted sum is projected back once
    (r W_out, `latent_attrs[1]`): once a token, not once a slot.  The two
    projections and the op stand in the scope `latent_experts`.

    `scoring` "softmax" scores a token's experts by the softmax over them,
    "sigmoid" each by its own sigmoid.  `bias_attr` (a `ParamAttr`: name and
    initializer) gives the router a bias [num_experts] that is added to the
    scores for the CHOICE only, the weights staying the unbiased scores.  It
    is a persistable float32 tensor and no parameter: it has no gradient and
    no optimizer state, and whoever balances the experts' load by it writes
    it from outside the step (no op here does).

    Returns (out, load_balance_loss, router_z_loss); the two [1] float32
    losses are for the caller to weigh into the training loss.  The experts
    are stacked parameters, (E, d, F) gate (where gated) and up and (E, F, d)
    down, d the latent's width where there is one.

    `held=(first, count)` is a layer that holds a share of its experts, as
    one chip of several that split the layer does: the stacked parameters are
    (count, d, F), (count, d, F) and (count, F, d), the experts `first` to
    `first + count - 1`; the router keeps its `num_experts` outputs, its
    top `top_k` and its renormalisation (over ALL the chosen, held or not),
    and what the absent experts would have added is left out: the layers of
    the chips that hold them add it.  Assignments to absent experts are never
    rows of the grouped products or of a gather; no assignment to a held
    expert is dropped.  `held=None` holds them all.

    `shared_experts=n` adds beside the routed sum what n shared experts
    compute: one feed-forward of the experts' form (gate and activation) and of
    width n x `expert_width`, or `shared_width` where given, that EVERY token
    passes at the layer's OWN width (never in the latent), added once and
    unweighted (`shared_attrs` = the ParamAttrs of its gate, up and down
    matrices; `mul` ops under the scope `shared_expert`).  It is outside the
    held path: where several chips split the routed experts each computes the
    shared one alike.  `shared_gate_attr` (a `ParamAttr`) gives the shared expert
    a sigmoid gate of its own (Qwen3-Next's): its output times sigmoid(x w_s),
    w_s [d, 1], ONE number a token, the projection read in float32 at the
    highest precision as the router's is, the product float32 and rounded once
    (the scope `moe_shared_gate`, inside `shared_expert`)."""
    import contextlib

    from ..core.program import name_scope
    from .tensor import cast

    helper = LayerHelper("moe", name=name)
    if activation not in ("silu", "relu", "relu2"):
        raise ValueError(f"moe: activation={activation!r}; \"silu\", \"relu\" or \"relu2\"")
    hidden_in, lead = input, tuple(input.shape[:-1])
    routed_on = hidden_in if router_input is None else router_input
    if tuple(routed_on.shape[:-1]) != lead:
        raise ValueError(f"moe: router_input {tuple(routed_on.shape)} beside an input {tuple(input.shape)}: a choice a token")
    in_latent = name_scope("latent_experts") if latent_size else contextlib.nullcontext()
    with in_latent:
        if latent_size:
            input = fc(hidden_in, int(latent_size), num_flatten_dims=len(lead), bias_attr=False,
                       param_attr=(latent_attrs or (None, None))[0])
        out, balance, z_loss = _routed_experts(
            helper, routed_on, input, num_experts, expert_width, top_k, norm_topk_prob, router_attr, gate_attr, up_attr,
            down_attr, held, scoring, bias_attr, routed_scaling_factor, norm_eps, shared_experts, activation, gated,
            ahead=routed_on is not hidden_in)
        if latent_size:
            out = fc(out, int(hidden_in.shape[-1]), num_flatten_dims=len(lead), bias_attr=False,
                     param_attr=(latent_attrs or (None, None))[1])
    if shared_experts:
        gate_a, up_a, down_a = shared_attrs or (None, None, None)
        width = int(shared_width or int(shared_experts) * expert_width)

        def project(t, size, attr, act=None):
            return fc(t, size, num_flatten_dims=len(lead), act=act, param_attr=attr, bias_attr=False)

        with name_scope("shared_expert"):
            act = {"silu": "swish", "relu": "relu", "relu2": "relu"}[activation]
            hidden = project(hidden_in, width, gate_a if gated else up_a, act=act)
            if activation == "relu2":
                hidden = square(hidden)
            if gated:
                hidden = elementwise_mul(hidden, project(hidden_in, width, up_a))
            shared = project(hidden, int(hidden_in.shape[-1]), down_a)
            if shared_gate_attr is not None:
                with name_scope("moe_shared_gate"):
                    gate = sigmoid(fc(cast(hidden_in, "float32"), 1, num_flatten_dims=len(lead), param_attr=shared_gate_attr,
                                      bias_attr=False, precision="highest"))
                    shared = cast(elementwise_mul(cast(shared, "float32"), gate), shared.dtype)
            out = elementwise_add(out, shared)
    return _keep_lod(hidden_in, out), balance, z_loss


def _routed_experts(helper, routed_on, input, num_experts, expert_width, top_k, norm_topk_prob, router_attr, gate_attr,
                    up_attr, down_attr, held, scoring, bias_attr, routed_scaling_factor, norm_eps, shared_experts,
                    activation, gated, ahead=False):
    """`moe`'s two ops: the router on `routed_on` (the layer's input) and the
    experts on `input` (the same, or its projection into the latent).  `ahead`:
    `routed_on` was made earlier than the experts' input, and the router's op
    stands where it is first read."""
    d = int(input.shape[-1])
    lead = tuple(input.shape[:-1])
    n_held = num_experts if held is None else int(held[1])
    if held is not None and not (0 <= held[0] and 0 < held[1] and held[0] + held[1] <= num_experts):
        raise ValueError(f"moe: held = {tuple(held)} is no range of the {num_experts} experts")
    router = helper.create_parameter(router_attr, [int(routed_on.shape[-1]), num_experts], "float32")
    gate = helper.create_parameter(gate_attr, [n_held, d, expert_width], input.dtype) if gated else None
    up = helper.create_parameter(up_attr, [n_held, d, expert_width], input.dtype)
    down = helper.create_parameter(down_attr, [n_held, expert_width, d], input.dtype)
    top_p = _out(helper, "float32", shape=lead + (top_k,))
    top_i = _out(helper, "int32", shape=lead + (top_k,))
    load = _out(helper, "int32", shape=(num_experts,))
    balance = _out(helper, "float32", shape=(1,))
    z_loss = _out(helper, "float32", shape=(1,))
    router_inputs = {"X": [routed_on.name], "W": [router.name]}
    router_outputs = {"TopKProb": [top_p.name], "TopKIndex": [top_i.name], "Load": [load.name],
                      "LoadBalanceLoss": [balance.name], "ZLoss": [z_loss.name]}
    router_attrs = {"top_k": int(top_k), "norm_topk_prob": bool(norm_topk_prob)}
    # what the 2024 router does not have is an attribute only where it is asked for
    if scoring != "softmax":
        router_attrs["scoring"] = str(scoring)
    if routed_scaling_factor != 1.0:
        router_attrs["routed_scaling_factor"] = float(routed_scaling_factor)
    if norm_eps:
        router_attrs["norm_eps"] = float(norm_eps)
    if bias_attr is not None:
        router_inputs["Bias"] = [_persistable_tensor(helper, bias_attr, [num_experts], "float32").name]
        router_outputs["BiasMoved"] = [_out(helper, "int32", shape=(1,)).name]
    helper.append_op("moe_router", inputs=router_inputs, outputs=router_outputs, attrs=router_attrs)
    if ahead:   # before the first op that reads the router's input (the layer's norm): nothing it reads is made later
        ops = helper.main_block.ops
        ops.insert(next(i for i, op in enumerate(ops) if routed_on.name in op.input_arg_names), ops.pop())
    out = _out(helper, input.dtype, shape=input.shape)
    dropped = _out(helper, "int32", shape=(1,))
    outputs = {"Out": [out.name], "Dropped": [dropped.name]}
    attrs = {"num_experts": int(num_experts), "top_k": int(top_k)}
    if held is not None:
        outputs["Held"] = [_out(helper, "int32", shape=(1,)).name]
        attrs["held"] = [int(held[0]), int(held[1])]
    if shared_experts:
        attrs["shared_experts"] = int(shared_experts)
    # what the 2024 experts do not have is an attribute only where it is asked for, as the router's are
    if activation != "silu":
        attrs["activation"] = str(activation)
    if not gated:
        attrs["gated"] = False
    helper.append_op(
        "moe_experts",
        inputs={"X": [input.name], "TopKProb": [top_p.name], "TopKIndex": [top_i.name],
                "Load": [load.name], **({"WGate": [gate.name]} if gated else {}), "WUp": [up.name],
                "WDown": [down.name]},
        outputs=outputs, attrs=attrs)
    return out, balance, z_loss


def _persistable_tensor(helper, attr, shape, dtype):
    """A named tensor the start-up program initialises and every step reads,
    which is NOT a parameter: no gradient, no optimizer state, not in
    `Program.all_parameters()`."""
    from ..core import unique_name
    from ..core.initializer import ConstantInitializer
    from ..core.param_attr import ParamAttr

    attr = ParamAttr._to_attr(attr)
    name = attr.name or unique_name.generate(f"{helper.name}.buffer")
    var = helper.main_program.global_block().create_var(name, shape=shape, dtype=dtype, persistable=True)
    var.stop_gradient = True
    startup = helper.startup_program.global_block()
    init = attr.initializer or ConstantInitializer(0.0)
    init(startup.create_var(name, shape=shape, dtype=dtype, persistable=True), startup)
    return var


def short_conv(input, kernel_size=3, in_attr=None, filter_attr=None, out_attr=None, name=None,
               gated=True, activation=None, bias_attr=None):
    """A gated short convolution over (b, T, d), the operator that stands
    where attention does in most layers of a convolution-attention hybrid
    (LFM2): [B, C, u] = split3(x W_in); y = (C * conv_K(B * u)) W_out with one
    causal filter of `kernel_size` taps a channel (depthwise), zeros before the
    sequence's start, no activation and no biases.  The two projections are
    `fc` (so `mul` ops, as attention's are) and the op between them is
    `short_conv`.  Sequences are whole: a row is one document.

    `gated=False, activation="silu"` is the op's plain mode and the taps alone,
    with no projection round them: y = silu(conv_K(x)) over (b, T, d), as the
    queries, keys and values of a linear-attention layer pass it (`filter_attr`
    names the [d, K] filter).  `bias_attr` (the plain mode's) adds a float32
    bias a channel before the SiLU, y = silu(conv_K(x) + b): the op's optional
    input `Bias`, which a state-space mixer's convolution has."""
    helper = LayerHelper("short_conv", name=name)
    d = int(input.shape[-1])
    source = fc(input, 3 * d, num_flatten_dims=2, param_attr=in_attr, bias_attr=False) if gated else input
    taps = helper.create_parameter(filter_attr, [d, int(kernel_size)], "float32")
    mixed = _out(helper, input.dtype, shape=tuple(input.shape))
    inputs = {"X": [source.name], "Filter": [taps.name]}
    if bias_attr:
        inputs["Bias"] = [helper.create_parameter(bias_attr, [d], "float32", is_bias=True).name]
    helper.append_op("short_conv", inputs=inputs,
                     outputs={"Out": [mixed.name]},
                     attrs=None if gated else {"gated": False, "activation": str(activation)})
    return fc(mixed, d, num_flatten_dims=2, param_attr=out_attr, bias_attr=False) if gated else mixed


def kda_gate(input, num_heads, a_log_attr=None, dt_bias_attr=None, name=None):
    """The log decay of a Kimi-Delta-Attention layer from its projection
    `input` (b, T, H . K): g = -exp(A_log[h]) . softplus(input + dt_bias),
    float32 (b, T, H, K), with a learned `A_log` a head and `dt_bias` a channel
    (float32 parameters)."""
    helper = LayerHelper("kda_gate", name=name)
    width = int(input.shape[-1])
    a_log = helper.create_parameter(a_log_attr, [int(num_heads)], "float32")
    dt_bias = helper.create_parameter(dt_bias_attr, [width], "float32")
    out = _out(helper, "float32", shape=tuple(input.shape[:-1]) + (int(num_heads), width // int(num_heads)))
    helper.append_op("kda_gate", inputs={"X": [input.name], "ALog": [a_log.name], "DtBias": [dt_bias.name]},
                     outputs={"Out": [out.name]})
    return out


def kda(q, k, v, g, beta, name=None):
    """A gated delta rule's recurrence over the sequence (`ops/
    linear_attention_ops.py`): q, k (b, T, H, K), v (b, T, H, V), the float32
    log decay g, a decay a channel (b, T, H, K) (Kimi Delta Attention) or a
    head (b, T, H) (Gated DeltaNet: g's rank says which), and the step beta (b,
    T, H); a head keeps a float32 [K, V] state, S_t = (I - beta_t k_t k_t^T)
    Diag(exp g_t) S_{t-1} + beta_t k_t v_t^T, and returns o_t = S_t^T q_t, (b, T,
    H, V) in v's dtype.  q and k may have fewer heads than v, a divisor of H:
    value head h reads key head h div (H / key heads).  Computed 64 tokens a chunk; T is a whole number of chunks (or
    at most one).  The state starts at zero with every row: sequences are
    whole.  The op's `Stats` (mean decay, mean step, largest |S| at the end)
    are published a logged step by `train_loop` as a `kind="kda_state"` record."""
    helper = LayerHelper("kda", name=name)
    out = _out(helper, v.dtype, shape=tuple(v.shape))
    stats = _out(helper, "float32", shape=(3,))
    helper.append_op("kda", inputs={"Q": [q.name], "K": [k.name], "V": [v.name], "G": [g.name], "Beta": [beta.name]},
                     outputs={"Out": [out.name], "Stats": [stats.name]})
    return out


def selective_scan(x, dt, b, c, a_log_attr=None, d_attr=None, dt_bias_attr=None, name=None):
    """A Mamba-1 mixer's selective scan over the sequence (`ops/ssm_ops.py`):
    x and the step's projection dt (b, T, d), the input and output matrices b,
    c (b, T, N) a token; a channel keeps a float32 state of N, h_t = exp(dt_t
    A) h_{t-1} + dt_t x_t B_t with dt_t = softplus(dt + dt_bias) and A =
    -exp(A_log), and returns y_t = h_t C_t + D x_t, (b, T, d) in x's dtype.
    `A_log` [d, N], `D` and `dt_bias` [d] are float32 parameters of the op
    (`a_log_attr`, `d_attr`, `dt_bias_attr`).  Computed a chunk of tokens at a
    time, each chunk made again in backward; any T.
    The state starts at zero with every row: sequences are whole.  The op's
    `Stats` (mean decay, mean step, largest |h| at the end) are published a
    logged step by `train_loop` as a `kind="ssm_state"` record."""
    helper = LayerHelper("selective_scan", name=name)
    d, n = int(x.shape[-1]), int(b.shape[-1])
    a_log = helper.create_parameter(a_log_attr, [d, n], "float32")
    d_skip = helper.create_parameter(d_attr, [d], "float32")
    dt_bias = helper.create_parameter(dt_bias_attr, [d], "float32", is_bias=True)
    out = _out(helper, x.dtype, shape=tuple(x.shape))
    stats = _out(helper, "float32", shape=(3,))
    helper.append_op("selective_scan",
                     inputs={"X": [x.name], "Dt": [dt.name], "ALog": [a_log.name], "B": [b.name], "C": [c.name],
                             "D": [d_skip.name], "DtBias": [dt_bias.name]},
                     outputs={"Out": [out.name], "Stats": [stats.name]})
    return out


def ssd_scan(x, dt, b, c, heads, groups=1, chunk=128, a_log_attr=None, d_attr=None, dt_bias_attr=None, name=None):
    """A Mamba-2 mixer's scalar-decay state-space scan over the sequence
    (`ops/ssd_ops.py`): x (b, T, heads x P), the step's projection dt (b, T,
    heads), the input and output matrices b, c (b, T, groups x N) a token; a head
    keeps a float32 state [P, N], h_t = exp(dt_t A) h_{t-1} + dt_t x_t (x) B_t
    with dt_t = softplus(dt + dt_bias) and A = -exp(A_log) ONE scalar a head,
    head h reading the B and C of group h // (heads / groups), and returns y_t =
    h_t C_t + D x_t, (b, T, heads x P) in x's dtype.  `A_log`, `D` and `dt_bias`
    [heads] are float32 parameters of the op (`a_log_attr`, `d_attr`,
    `dt_bias_attr`).  Computed `chunk` tokens at a time as matrix products, the
    state carried chunk to chunk; any T.  The state starts at zero with every
    row: sequences are whole.  The op's other outputs: `State`, the float32
    state after the last token, (b, heads, P, N), and `Stats` (mean decay, mean
    step, largest |h| at the end), published a logged step by `train_loop` as a
    `kind="ssd_state"` record."""
    helper = LayerHelper("ssd_scan", name=name)
    a_log = helper.create_parameter(a_log_attr, [int(heads)], "float32")
    d_skip = helper.create_parameter(d_attr, [int(heads)], "float32")
    dt_bias = helper.create_parameter(dt_bias_attr, [int(heads)], "float32", is_bias=True)
    out = _out(helper, x.dtype, shape=tuple(x.shape))
    state = _out(helper, "float32",
                 shape=(x.shape[0], int(heads), int(x.shape[-1]) // int(heads), int(b.shape[-1]) // int(groups)))
    stats = _out(helper, "float32", shape=(3,))
    helper.append_op("ssd_scan",
                     inputs={"X": [x.name], "Dt": [dt.name], "ALog": [a_log.name], "B": [b.name], "C": [c.name],
                             "D": [d_skip.name], "DtBias": [dt_bias.name]},
                     outputs={"Out": [out.name], "State": [state.name], "Stats": [stats.name]},
                     attrs={"groups": int(groups), "chunk": int(chunk)})
    return out


def memory_gate(gate, memory, name=None):
    """A Gated Memory Unit's gate (`ops/ssm_ops.py: memory_gate`): silu(gate) *
    memory over (b, T, d), `memory` a tensor another layer kept (a state-space
    scan's output before that layer's own gate), in `memory`'s dtype.  The op's
    `Stats` (mean |memory|, mean gate, all finite) are published a logged step
    by `train_loop` as a `kind="gmu_memory"` record."""
    helper = LayerHelper("memory_gate", name=name)
    out = _out(helper, memory.dtype, shape=tuple(memory.shape))
    stats = _out(helper, "float32", shape=(3,))
    helper.append_op("memory_gate", inputs={"Gate": [gate.name], "Memory": [memory.name]},
                     outputs={"Out": [out.name], "Stats": [stats.name]})
    return out


def dropout_prob_check(p):
    if not 0 <= p < 1:
        raise ValueError("dropout prob must be in [0,1)")


def resize_bilinear(input, out_shape=None, scale=None, name=None, align_corners=True):
    """reference nn.py resize_bilinear over bilinear_interp_op."""
    helper = LayerHelper("bilinear_interp", name=name)
    attrs = {"align_corners": align_corners}
    if out_shape is not None:
        attrs["out_h"], attrs["out_w"] = int(out_shape[0]), int(out_shape[1])
        oshape = None
        if input.shape is not None:
            oshape = (input.shape[0], input.shape[1], attrs["out_h"], attrs["out_w"])
    else:
        attrs["scale"] = float(scale)
        oshape = None
    out = _out(helper, input.dtype, shape=oshape)
    helper.append_op("bilinear_interp", inputs={"X": [input.name]},
                     outputs={"Out": [out.name]}, attrs=attrs)
    return out


def resize_nearest(input, out_shape=None, scale=None, name=None, align_corners=True):
    helper = LayerHelper("nearest_interp", name=name)
    attrs = {"align_corners": align_corners}
    if out_shape is not None:
        attrs["out_h"], attrs["out_w"] = int(out_shape[0]), int(out_shape[1])
    else:
        attrs["scale"] = float(scale)
    out = _out(helper, input.dtype)
    helper.append_op("nearest_interp", inputs={"X": [input.name]},
                     outputs={"Out": [out.name]}, attrs=attrs)
    return out


def pad2d(input, paddings=(0, 0, 0, 0), mode="constant", pad_value=0.0, name=None):
    helper = LayerHelper("pad2d", name=name)
    out = _out(helper, input.dtype)
    helper.append_op("pad2d", inputs={"X": [input.name]}, outputs={"Out": [out.name]},
                     attrs={"paddings": list(paddings), "mode": mode,
                            "pad_value": pad_value})
    return out


def crop(x, shape=None, offsets=None, name=None):
    if shape is None:
        raise ValueError("crop: `shape` is required (static output extents)")
    helper = LayerHelper("crop", name=name)
    out = _out(helper, x.dtype, shape=tuple(shape) if shape else None)
    helper.append_op("crop", inputs={"X": [x.name]}, outputs={"Out": [out.name]},
                     attrs={"offsets": list(offsets or [0] * len(shape)),
                            "shape": list(shape)})
    return out


def Print(input, first_n=-1, message=None, summarize=-1, print_tensor_name=True,
          print_tensor_type=True, print_tensor_shape=True, print_tensor_lod=True,
          print_phase="both"):
    """reference layers.Print (print_op.cc): identity that prints at
    execution (host callback through jax.debug.print)."""
    helper = LayerHelper("print")
    out = _out(helper, input.dtype, shape=input.shape)
    msg = message or f"{input.name}: " if print_tensor_name else (message or "")
    helper.append_op("print", inputs={"X": [input.name]}, outputs={"Out": [out.name]},
                     attrs={"message": msg, "first_n": first_n})
    return out


def group_norm(input, groups, epsilon=1e-5, param_attr=None, bias_attr=None,
               act=None, data_layout="NCHW", name=None):
    helper = LayerHelper("group_norm", name=name, act=act)
    if data_layout != "NCHW":
        raise NotImplementedError("group_norm: only NCHW")
    c = input.shape[1]
    from ..core.initializer import ConstantInitializer

    scale = helper.create_parameter(param_attr, [c], input.dtype,
                                    default_initializer=ConstantInitializer(1.0))
    bias = helper.create_parameter(bias_attr, [c], input.dtype, is_bias=True)
    out = _out(helper, input.dtype, shape=input.shape)
    mean = _out(helper, "float32")
    var = _out(helper, "float32")
    helper.append_op(
        "group_norm",
        inputs={"X": [input.name], "Scale": [scale.name], "Bias": [bias.name]},
        outputs={"Y": [out.name], "Mean": [mean.name], "Variance": [var.name]},
        attrs={"epsilon": epsilon, "groups": groups},
    )
    return helper.append_activation(out)


def instance_norm(input, epsilon=1e-5, param_attr=None, bias_attr=None, name=None):
    helper = LayerHelper("instance_norm", name=name)
    c = input.shape[1]
    from ..core.initializer import ConstantInitializer

    scale = helper.create_parameter(param_attr, [c], input.dtype,
                                    default_initializer=ConstantInitializer(1.0))
    bias = helper.create_parameter(bias_attr, [c], input.dtype, is_bias=True)
    out = _out(helper, input.dtype, shape=input.shape)
    smean = _out(helper, "float32")
    svar = _out(helper, "float32")
    helper.append_op(
        "instance_norm",
        inputs={"X": [input.name], "Scale": [scale.name], "Bias": [bias.name]},
        outputs={"Y": [out.name], "SavedMean": [smean.name],
                 "SavedVariance": [svar.name]},
        attrs={"epsilon": epsilon},
    )
    return out


def l2_normalize(x, axis, epsilon=1e-10, name=None):
    helper = LayerHelper("l2_normalize", name=name)
    out = _out(helper, x.dtype, shape=x.shape)
    norm = _out(helper, x.dtype)
    helper.append_op("norm", inputs={"X": [x.name]},
                     outputs={"Out": [out.name], "Norm": [norm.name]},
                     attrs={"axis": axis, "epsilon": epsilon})
    return out


def gather_nd(input, index, name=None):
    helper = LayerHelper("gather_nd", name=name)
    out = _out(helper, input.dtype)
    helper.append_op("gather_nd", inputs={"X": [input.name], "Index": [index.name]},
                     outputs={"Out": [out.name]})
    return out


def scatter(input, index, updates, overwrite=True, name=None):
    helper = LayerHelper("scatter", name=name)
    out = _out(helper, input.dtype, shape=input.shape)
    helper.append_op("scatter",
                     inputs={"X": [input.name], "Ids": [index.name],
                             "Updates": [updates.name]},
                     outputs={"Out": [out.name]}, attrs={"overwrite": overwrite})
    return out


def cumsum(x, axis=-1, exclusive=False, reverse=False, name=None):
    helper = LayerHelper("cumsum", name=name)
    out = _out(helper, x.dtype, shape=x.shape)
    helper.append_op("cumsum", inputs={"X": [x.name]}, outputs={"Out": [out.name]},
                     attrs={"axis": axis, "exclusive": exclusive, "reverse": reverse})
    return out


def argsort(input, axis=-1, descending=False, name=None):
    helper = LayerHelper("argsort", name=name)
    out = _out(helper, input.dtype, shape=input.shape)
    ids = _out(helper, "int64", shape=input.shape)
    helper.append_op("argsort", inputs={"X": [input.name]},
                     outputs={"Out": [out.name], "Indices": [ids.name]},
                     attrs={"axis": axis, "descending": descending})
    return out, ids


def flatten(x, axis=1, name=None):
    helper = LayerHelper("flatten", name=name)
    out = _out(helper, x.dtype)
    helper.append_op("flatten2", inputs={"X": [x.name]},
                     outputs={"Out": [out.name],
                              "XShape": [_out(helper, x.dtype).name]},
                     attrs={"axis": axis})
    return out


def gather(input, index, name=None):
    """rows of input at index (reference layers.gather over gather_op)."""
    helper = LayerHelper("gather", name=name)
    out = _out(helper, input.dtype)
    helper.append_op("gather", inputs={"X": [input.name], "Index": [index.name]},
                     outputs={"Out": [out.name]})
    return out


def py_func(func, x, out, backward_func=None, skip_vars_in_backward_input=None):
    """Embed a host python callable in the program (reference layers.py_func
    over py_func_op.cc).  `out` declares the output variables (shapes/dtypes
    must be exact — XLA needs them static); backward_func is not supported
    (the callback is opaque to autodiff; stop-gradient semantics)."""
    from ..ops.control_flow_ops import register_py_func

    if backward_func is not None:
        raise NotImplementedError(
            "py_func: backward_func is not supported — the host callback is "
            "opaque to the vjp; compute gradients with program ops instead")
    helper = LayerHelper("py_func")
    xs = x if isinstance(x, (list, tuple)) else [x]
    outs = out if isinstance(out, (list, tuple)) else [out]
    for o in outs:
        if o.shape is None or any(s is None or s < 0 for s in o.shape):
            raise ValueError(
                f"py_func: output {o.name!r} needs a fully static shape")
    fid = register_py_func(func)
    helper.append_op(
        "py_func",
        inputs={"X": [v.name for v in xs]},
        outputs={"Out": [o.name for o in outs]},
        attrs={"func_id": fid,
               "out_shapes": [list(o.shape) for o in outs],
               "out_dtypes": [str(o.dtype) for o in outs]},
    )
    return out
