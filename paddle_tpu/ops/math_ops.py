"""Math op lowerings: elementwise, activations, matmul, reductions, compare.

Reference kernels: operators/elementwise/ (4.4k LoC of broadcast+grad code —
here broadcasting is `bcast_y_to_x` + jnp and grads come from vjp),
activation_op.cc, mul_op.cc / matmul_op.cc (math/blas.h:81 cuBLAS facade —
here one jnp call that XLA tiles onto the MXU), reduce_ops/, compare ops
(operators/controlflow/compare_op.cc).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from ..core.registry import register_op, set_kept
from .common import bcast_y_to_x, first, match_dtype, normalize_axes


# --- elementwise binary ops ------------------------------------------------

def _ew(fn):
    def lower(ctx, op, ins):
        from ..core.selected_rows import SelectedRows

        x = first(ins, "X")
        y = first(ins, "Y")
        if isinstance(x, SelectedRows) and jnp.size(y) == 1:
            # SelectedRows op scalar (AMP grad unscale, clip-by-value):
            # apply to the value slab, keep the rows
            yv = jnp.reshape(y, ()).astype(x.values.dtype)
            return {"Out": SelectedRows(x.rows, fn(x.values, yv), x.height)}
        y = match_dtype(x, bcast_y_to_x(x, y, op.attr("axis", -1)))
        return {"Out": fn(x, y)}

    return lower


for _name, _fn in {
    "elementwise_add": jnp.add,
    "elementwise_sub": jnp.subtract,
    "elementwise_mul": jnp.multiply,
    "elementwise_div": jnp.divide,
    "elementwise_max": jnp.maximum,
    "elementwise_min": jnp.minimum,
    "elementwise_pow": jnp.power,
    "elementwise_mod": jnp.mod,
    "elementwise_floordiv": jnp.floor_divide,
}.items():
    register_op(_name)(_ew(_fn))


@register_op("sum")
def _sum(ctx, op, ins):
    xs = ins["X"]
    out = xs[0]
    for x in xs[1:]:
        out = out + x
    return {"Out": out}


# --- activations -----------------------------------------------------------

# (r5 chip round note: an output-residual custom-vjp relu — save y
# instead of the pre-activation for backward — measured NEUTRAL on the
# ResNet step in an interleaved A/B (105.1 vs 105.2 ms): XLA already elides
# the dead pre-activation buffer.  jax.nn.relu keeps higher-order autodiff.)
_UNARY = {
    "relu": jax.nn.relu,
    "relu6": lambda x: jnp.clip(x, 0.0, 6.0),
    "sigmoid": jax.nn.sigmoid,
    "logsigmoid": jax.nn.log_sigmoid,
    "tanh": jnp.tanh,
    "exp": jnp.exp,
    "log": jnp.log,
    "sqrt": jnp.sqrt,
    "rsqrt": jax.lax.rsqrt,
    "abs": jnp.abs,
    "square": jnp.square,
    "reciprocal": jnp.reciprocal,
    "floor": jnp.floor,
    "ceil": jnp.ceil,
    "round": jnp.round,
    "sin": jnp.sin,
    "cos": jnp.cos,
    "gelu": lambda x: jax.nn.gelu(x, approximate=False),
    "softplus": jax.nn.softplus,
    "softsign": jax.nn.soft_sign,
    "tanh_shrink": lambda x: x - jnp.tanh(x),
    "erf": jax.lax.erf,
    "sign": jnp.sign,
    "tan": jnp.tan,
    "asin": jnp.arcsin,
    "acos": jnp.arccos,
    "atan": jnp.arctan,
    "sinh": jnp.sinh,
    "cosh": jnp.cosh,
    "log2": jnp.log2,
    "log10": jnp.log10,
    "log1p": jnp.log1p,
    "expm1": jnp.expm1,
}

def _unary(fn):
    def lower(ctx, op, ins):
        return {"Out": fn(first(ins, "X"))}

    return lower


for _name, _fn in _UNARY.items():
    register_op(_name)(_unary(_fn))


@register_op("hard_shrink")
def _hard_shrink(ctx, op, ins):
    x = first(ins, "X")
    t = op.attr("threshold", 0.5)
    return {"Out": jnp.where(jnp.abs(x) > t, x, 0.0)}


@register_op("stanh")
def _stanh(ctx, op, ins):
    x = first(ins, "X")
    a = op.attr("scale_a", 0.67)  # reference activation_op.cc default
    b = op.attr("scale_b", 1.7159)
    return {"Out": b * jnp.tanh(a * x)}


@register_op("leaky_relu")
def _leaky_relu(ctx, op, ins):
    x = first(ins, "X")
    alpha = op.attr("alpha", 0.02)
    return {"Out": jnp.where(x >= 0, x, alpha * x)}


@register_op("elu")
def _elu(ctx, op, ins):
    return {"Out": jax.nn.elu(first(ins, "X"), alpha=op.attr("alpha", 1.0))}


@register_op("hard_sigmoid")
def _hard_sigmoid(ctx, op, ins):
    x = first(ins, "X")
    slope = op.attr("slope", 0.2)
    offset = op.attr("offset", 0.5)
    return {"Out": jnp.clip(slope * x + offset, 0.0, 1.0)}


@register_op("swish")
def _swish(ctx, op, ins):
    x = first(ins, "X")
    beta = op.attr("beta", 1.0)
    return {"Out": x * jax.nn.sigmoid(beta * x)}


@register_op("pow")
def _pow(ctx, op, ins):
    return {"Out": jnp.power(first(ins, "X"), op.attr("factor", 1.0))}


@register_op("clip")
def _clip(ctx, op, ins):
    x = first(ins, "X")
    return {"Out": jnp.clip(x, op.attr("min"), op.attr("max"))}


@register_op("clip_by_norm")
def _clip_by_norm(ctx, op, ins):
    x = first(ins, "X")
    max_norm = op.attr("max_norm")
    norm = jnp.sqrt(jnp.sum(jnp.square(x)))
    return {"Out": jnp.where(norm > max_norm, x * (max_norm / norm), x)}


# --- matmul family (the MXU path) -----------------------------------------

@register_op("mul")
def _mul(ctx, op, ins):
    """reference operators/mul_op.cc: flatten x to 2-D at x_num_col_dims,
    y at y_num_col_dims, then GEMM.  The attribute `precision` ("highest") asks
    more of a float32 product than the chip's one pass over bf16 operands."""
    x = first(ins, "X")
    y = first(ins, "Y")
    xd = op.attr("x_num_col_dims", 1)
    yd = op.attr("y_num_col_dims", 1)
    import numpy as _np

    y = match_dtype(x, y)
    xs, ys = x.shape, y.shape
    x2 = x if x.ndim == 2 else jnp.reshape(x, (int(_np.prod(xs[:xd])), int(_np.prod(xs[xd:]))))
    y2 = y if y.ndim == 2 else jnp.reshape(y, (int(_np.prod(ys[:yd])), int(_np.prod(ys[yd:]))))
    out = jnp.matmul(x2, y2, precision=op.attr("precision", None))   # None: the platform's default, as ever
    out_shape = xs[:xd] + ys[yd:]
    return {"Out": jnp.reshape(out, out_shape)}


@register_op("matmul")
def _matmul(ctx, op, ins):
    x = first(ins, "X")
    y = match_dtype(x, first(ins, "Y"))
    if op.attr("transpose_X", False):
        x = jnp.swapaxes(x, -1, -2)
    if op.attr("transpose_Y", False):
        y = jnp.swapaxes(y, -1, -2)
    out = jnp.matmul(x, y)
    alpha = op.attr("alpha", 1.0)
    if alpha != 1.0:
        out = out * alpha
    return {"Out": out}


# --- reductions ------------------------------------------------------------

def _reduce(fn):
    def lower(ctx, op, ins):
        x = first(ins, "X")
        if op.attr("reduce_all", False):
            axes = tuple(range(x.ndim))
        else:
            axes = normalize_axes(op.attr("dim", [0]), x.ndim)
        keep = op.attr("keep_dim", False)
        return {"Out": fn(x, axis=axes, keepdims=keep)}

    return lower


for _name, _fn in {
    "reduce_sum": jnp.sum,
    "reduce_mean": jnp.mean,
    "reduce_max": jnp.max,
    "reduce_min": jnp.min,
    "reduce_prod": jnp.prod,
}.items():
    register_op(_name)(_reduce(_fn))


@register_op("mean")
def _mean(ctx, op, ins):
    # reference mean_op.cc produces a (1,) tensor
    return {"Out": jnp.mean(first(ins, "X")).reshape((1,))}


@register_op("frobenius_norm")
def _frobenius_norm(ctx, op, ins):
    x = first(ins, "X")
    return {"Out": jnp.sqrt(jnp.sum(jnp.square(x)))}


# --- compare / logical -----------------------------------------------------

def _cmp(fn):
    def lower(ctx, op, ins):
        x = first(ins, "X")
        y = bcast_y_to_x(x, first(ins, "Y"), op.attr("axis", -1))
        return {"Out": fn(x, y)}

    return lower


for _name, _fn in {
    "equal": jnp.equal,
    "not_equal": jnp.not_equal,
    "less_than": jnp.less,
    "less_equal": jnp.less_equal,
    "greater_than": jnp.greater,
    "greater_equal": jnp.greater_equal,
}.items():
    register_op(_name)(_cmp(_fn))


@register_op("logical_and")
def _logical_and(ctx, op, ins):
    return {"Out": jnp.logical_and(first(ins, "X"), first(ins, "Y"))}


@register_op("logical_or")
def _logical_or(ctx, op, ins):
    return {"Out": jnp.logical_or(first(ins, "X"), first(ins, "Y"))}


@register_op("logical_not")
def _logical_not(ctx, op, ins):
    return {"Out": jnp.logical_not(first(ins, "X"))}


@register_op("softshrink")
def _softshrink(ctx, op, ins):
    """reference activation_op.h SoftShrinkFunctor: threshold attr `lambda`."""
    x = first(ins, "X")
    lam = op.attr("lambda", 0.5)
    return {"Out": jnp.where(x > lam, x - lam,
                             jnp.where(x < -lam, x + lam, 0.0))}


@register_op("isfinite")
def _isfinite(ctx, op, ins):
    from ..core.selected_rows import SelectedRows

    # reference isfinite_op.cc reduces to a single bool; on a SelectedRows
    # grad (AMP + is_sparse embeddings) only the touched-row slab is checked
    x = first(ins, "X")
    if isinstance(x, SelectedRows):
        x = x.values
    return {"Out": jnp.all(jnp.isfinite(x)).reshape((1,))}


@register_op("fake_quantize_abs_max")
def _fake_quantize_abs_max(ctx, op, ins):
    """reference fake_quantize_op.cc: symmetric abs-max fake quant — round
    to bit_length-bit ints in the forward, straight-through in backward."""
    x = first(ins, "X")
    bits = op.attr("bit_length", 8)
    qmax = float(2 ** (bits - 1) - 1)
    scale = jnp.max(jnp.abs(x))
    safe = jnp.maximum(scale, 1e-8)
    q = jnp.round(x / safe * qmax)
    out = q * safe / qmax
    # straight-through estimator: identity gradient
    out = x + jax.lax.stop_gradient(out - x)
    return {"Out": out, "OutScale": scale.reshape((1,))}


@register_op("fake_channel_wise_quantize_abs_max")
def _fake_channel_wise_quantize_abs_max(ctx, op, ins):
    """reference fake_quantize_op.cc fake_channel_wise_quantize_abs_max:
    per-output-channel (dim 0) symmetric abs-max grids — the conv/mul
    weight quantization granularity int8 deployment actually uses."""
    x = first(ins, "X")
    bits = op.attr("bit_length", 8)
    axis = op.attr("quant_axis", 0)  # conv filters: 0; mul/matmul Y: 1
    qmax = float(2 ** (bits - 1) - 1)
    moved = jnp.moveaxis(x, axis, 0)
    flat = moved.reshape(moved.shape[0], -1)
    scale = jnp.max(jnp.abs(flat), axis=1)           # [C_out]
    safe = jnp.maximum(scale, 1e-8).reshape((-1,) + (1,) * (x.ndim - 1))
    q = jnp.round(moved / safe * qmax)
    out = jnp.moveaxis(q * safe / qmax, 0, axis)
    out = x + jax.lax.stop_gradient(out - x)         # STE
    return {"Out": out, "OutScale": scale}


@register_op("fake_quantize_moving_average_abs_max")
def _fake_quantize_ma_abs_max(ctx, op, ins):
    """reference: activation fake-quant with a moving-average scale state."""
    x = first(ins, "X")
    in_scale = first(ins, "InScale").reshape(())
    bits = op.attr("bit_length", 8)
    rate = op.attr("moving_rate", 0.9)
    qmax = float(2 ** (bits - 1) - 1)
    cur = jnp.max(jnp.abs(x))
    scale = jnp.where(in_scale > 0, rate * in_scale + (1 - rate) * cur, cur)
    safe = jnp.maximum(scale, 1e-8)
    q = jnp.round(jnp.clip(x / safe, -1.0, 1.0) * qmax)
    out = q * safe / qmax
    out = x + jax.lax.stop_gradient(out - x)
    return {"Out": out, "OutScale": scale.reshape((1,))}


@register_op("fake_dequantize_max_abs")
def _fake_dequantize_max_abs(ctx, op, ins):
    x = first(ins, "X")
    scale = first(ins, "Scale").reshape(())
    max_range = op.attr("max_range", 127.0)
    return {"Out": x * scale / max_range}


# --- round-5 registry-audit fill-ins ---------------------------------------
# reference: minus_op.cc, l1_norm_op.cc, squared_l2_norm_op.cc,
# squared_l2_distance_op.cc, fill_op.cc, fill_zeros_like_op.cc (the *2
# variant differs only in grad wiring, which autodiff subsumes)

@register_op("minus")
def _minus(ctx, op, ins):
    x = first(ins, "X")
    return {"Out": x - match_dtype(x, first(ins, "Y"))}


@register_op("l1_norm")
def _l1_norm(ctx, op, ins):
    return {"Out": jnp.sum(jnp.abs(first(ins, "X"))).reshape(())}


@register_op("squared_l2_norm")
def _squared_l2_norm(ctx, op, ins):
    return {"Out": jnp.sum(jnp.square(first(ins, "X"))).reshape(())}


@register_op("squared_l2_distance")
def _squared_l2_distance(ctx, op, ins):
    x = first(ins, "X")
    y = match_dtype(x, first(ins, "Y"))
    n = x.shape[0]
    sub = x.reshape(n, -1) - y.reshape(y.shape[0], -1)  # y may broadcast [1,D]
    return {"sub_result": sub,
            "Out": jnp.sum(jnp.square(sub), axis=1, keepdims=True)}


@register_op("fill")
def _fill(ctx, op, ins):
    from .common import canon_dtype, np_dtype

    shape = tuple(op.attr("shape"))
    dtype = canon_dtype(np_dtype(op.attr("dtype", "float32")))
    vals = np.asarray(op.attr("value"), np.float32).reshape(shape)
    return {"Out": jnp.asarray(vals.astype(dtype))}


@register_op("fill_zeros_like2")
def _fill_zeros_like2(ctx, op, ins):
    x = first(ins, "X")
    from .common import canon_dtype, np_dtype

    dt = op.attr("dtype", None)
    dtype = x.dtype if dt in (None, -1) else canon_dtype(np_dtype(dt))
    return {"Out": jnp.zeros(x.shape, dtype)}


# --- build-time shape/dtype inference --------------------------------------
# (core/analysis.py rule factories; reference: each op's InferShape in its
# .cc file.  Registered after the lowerings so set_infer always finds the
# OpDef.)

from ..core import analysis as _A

_A.register_elementwise_infer(
    "elementwise_add", "elementwise_sub", "elementwise_mul",
    "elementwise_div", "elementwise_max", "elementwise_min",
    "elementwise_pow", "elementwise_mod", "elementwise_floordiv", "minus")
# (logical_xor lowers in ops/tail_ops.py, which imports after this module
# at package init — its infer rule registers there, next to the lowering)
_A.register_elementwise_infer(
    *sorted(_A.BOOL_OUT_OPS - {"logical_xor"}), out_dtype="bool")
_A.register_unary_infer("logical_not", out_dtype="bool")
_A.register_unary_infer(
    *_UNARY.keys(), "hard_shrink", "stanh", "leaky_relu", "elu",
    "hard_sigmoid", "swish", "pow", "clip", "clip_by_norm", "softshrink")
_A.register_reduce_infer("reduce_sum", "reduce_mean", "reduce_max",
                         "reduce_min", "reduce_prod")


def _infer_sum(ctx):
    out = None
    for i in range(ctx.n_inputs("X")):
        s = ctx.in_shape("X", i)
        if s is None:
            continue
        out = s if out is None else _A.fluid_broadcast(out, s, -1)
        if out is None:
            ctx.fail("summands have incompatible shapes",
                     var=ctx.op.input("X")[i])
    ctx.set_out("Out", out, ctx.in_dtype("X"))


_A.register_rule(["sum"], _infer_sum)


def _infer_mean(ctx):
    ctx.set_out("Out", (1,), ctx.in_dtype("X"))


_A.register_rule(["mean"], _infer_mean)


def _infer_mul(ctx):
    xs = ctx.in_shape("X")
    ys = ctx.in_shape("Y")
    if xs is None or ys is None:
        return
    xd = ctx.op.attr("x_num_col_dims", 1)
    yd = ctx.op.attr("y_num_col_dims", 1)
    if not (0 < xd <= len(xs) and 0 < yd < len(ys) + 1):
        ctx.fail(f"num_col_dims ({xd},{yd}) out of range for X{tuple(xs)} "
                 f"Y{tuple(ys)}")
    inner_x = xs[xd:]
    inner_y = ys[:yd]
    if all(d != _A.DYN for d in inner_x) and all(d != _A.DYN for d in inner_y):
        if int(np.prod(inner_x)) != int(np.prod(inner_y)):
            ctx.fail(
                f"flattened contraction dims do not match: "
                f"X{tuple(xs)} cols {tuple(inner_x)} vs Y{tuple(ys)} rows "
                f"{tuple(inner_y)}",
                var=ctx.op.input("Y")[0])
    ctx.set_out("Out", tuple(xs[:xd]) + tuple(ys[yd:]), ctx.in_dtype("X"))


_A.register_rule(["mul"], _infer_mul)


def _infer_matmul(ctx):
    xs = ctx.in_shape("X")
    ys = ctx.in_shape("Y")
    if xs is None or ys is None or len(xs) < 2 or len(ys) < 2:
        return
    if ctx.op.attr("transpose_X", False):
        xs = xs[:-2] + (xs[-1], xs[-2])
    if ctx.op.attr("transpose_Y", False):
        ys = ys[:-2] + (ys[-1], ys[-2])
    if _A.unify_dim(xs[-1], ys[-2]) is None:
        ctx.fail(f"contraction dims do not match: X[...,{xs[-1]}] vs "
                 f"Y[{ys[-2]},...]", var=ctx.op.input("Y")[0])
    bx, by = xs[:-2], ys[:-2]
    if len(bx) < len(by):
        bx, by = by, bx
    batch = _A.fluid_broadcast(bx, by, -1) if by else tuple(bx)
    if batch is None:
        ctx.fail(f"batch dims do not broadcast: {tuple(xs[:-2])} vs "
                 f"{tuple(ys[:-2])}")
    ctx.set_out("Out", tuple(batch) + (xs[-2], ys[-1]), ctx.in_dtype("X"))


_A.register_rule(["matmul"], _infer_matmul)


# --- static cost rules (core/resource_plan.py) ------------------------------
# Registered beside the infer rules: same families, FLOPs + HBM traffic
# instead of shapes.  Transcendental unaries are costed a few FLOPs/elem;
# the dense contractions get exact 2*M*K*N counts.

from ..core import resource_plan as _RP

_RP.register_elementwise_cost(
    "elementwise_add", "elementwise_sub", "elementwise_mul",
    "elementwise_div", "elementwise_max", "elementwise_min",
    "elementwise_pow", "elementwise_mod", "elementwise_floordiv", "minus",
    "logical_not", "relu", "relu6", "abs", "square", "floor", "ceil",
    "round", "sign", "reciprocal", "pow", "clip", "hard_shrink",
    "leaky_relu", "hard_sigmoid", "softshrink", "clip_by_norm",
    *sorted(_A.BOOL_OUT_OPS - {"logical_xor"}))
_RP.register_elementwise_cost(
    "sigmoid", "logsigmoid", "tanh", "exp", "log", "sqrt", "rsqrt", "sin",
    "cos", "gelu", "softplus", "softsign", "tanh_shrink", "erf", "tan",
    "asin", "acos", "atan", "sinh", "cosh", "log2", "log10", "log1p",
    "expm1", "stanh", "elu", "swish", flops_per_elem=8.0)


def _cost_reduce(ctx):
    return float(ctx.in_elems("X")), ctx.io_bytes()


_RP.register_cost(["reduce_sum", "reduce_mean", "reduce_max", "reduce_min",
                   "reduce_prod", "mean"], _cost_reduce)


def _cost_sum(ctx):
    total = sum(ctx.in_elems("X", i) for i in range(len(ctx.op.input("X"))))
    return float(total), ctx.io_bytes()


_RP.register_cost(["sum"], _cost_sum)


def _cost_mul(ctx):
    xs, ys = ctx.in_shape("X"), ctx.in_shape("Y")
    if xs is None or ys is None:
        return float(ctx.out_elems_total()), ctx.io_bytes()
    xd = ctx.attr("x_num_col_dims", 1)
    yd = ctx.attr("y_num_col_dims", 1)
    rows = _elems_of(xs[:xd])
    inner = _elems_of(xs[xd:])
    cols = _elems_of(ys[yd:])
    return 2.0 * rows * inner * cols, ctx.io_bytes()


def _cost_matmul(ctx):
    xs, ys = ctx.in_shape("X"), ctx.in_shape("Y")
    if xs is None or ys is None or len(xs) < 2 or len(ys) < 2:
        return float(ctx.out_elems_total()), ctx.io_bytes()
    if ctx.attr("transpose_X", False):
        xs = xs[:-2] + (xs[-1], xs[-2])
    if ctx.attr("transpose_Y", False):
        ys = ys[:-2] + (ys[-1], ys[-2])
    batch = _elems_of(ctx.out_shape("Out")[:-2]) if ctx.out_shape("Out") else _elems_of(xs[:-2])
    return 2.0 * batch * xs[-2] * xs[-1] * ys[-1], ctx.io_bytes()


def _elems_of(shape):
    n = 1
    for d in shape:
        n *= max(int(d), 1)
    return n


_RP.register_cost(["mul"], _cost_mul)
_RP.register_cost(["matmul"], _cost_matmul)


def _kept_product(ctx, op, shapes):
    """A product's output is dear to make again and the lowering names it."""
    out = op.output("Out")[0]
    return out, shapes.nbytes(out)


set_kept("mul", _kept_product)
set_kept("matmul", _kept_product)
