"""Tensor creation / manipulation op lowerings.

Reference kernels: operators/fill_constant_op.cc, uniform_random_op.cc,
gaussian_random_op.cc, cast_op.cc, reshape_op.cc, transpose_op.cc,
concat_op.cc, split_op.cc, assign_op.cc, scale_op.cc, slice_op.cc, etc.
Each maps to a jnp/lax call; XLA owns codegen.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from ..core.registry import register_op
from .common import canon_dtype, first, np_dtype


#: Elements from which `fill_constant` hands on a zero-strided view of its value instead of a filled array (the small
#: ones stay filled: every accepted cell's step and clone lower to the text they had).
_FILL_AS_VIEW = 1 << 20


@register_op("fill_constant")
def _fill_constant(ctx, op, ins):
    shape = tuple(op.attr("shape", []))
    dtype = np_dtype(op.attr("dtype", "float32"))
    value = op.attr("value", 0.0)
    # host-side constant: stays concrete through the trace so tensor-array
    # indices built from constants remain static; jnp coerces on use and
    # XLA constant-folds either way.  A large one (an optimizer's moment of a table's shape) is a VIEW of one value
    # with strides of zero, which JAX lowers as a broadcast: `np.full` wrote 12.8 GB on the host for the moments of
    # 1.6 G parameters, 86 s of a start-up program's trace here and four times that on the chip machine's host
    if int(np.prod(shape, dtype=np.int64)) >= _FILL_AS_VIEW:
        return {"Out": np.broadcast_to(np.asarray(value, dtype=dtype), shape)}
    return {"Out": np.full(shape, value, dtype=dtype)}


@register_op("uniform_random")
def _uniform_random(ctx, op, ins):
    shape = tuple(op.attr("shape"))
    dtype = np_dtype(op.attr("dtype", "float32"))
    lo = op.attr("min", -1.0)
    hi = op.attr("max", 1.0)
    key = _op_key(ctx, op)
    return {"Out": jax.random.uniform(key, shape, dtype=jnp.float32, minval=lo, maxval=hi).astype(dtype)}


@register_op("gaussian_random")
def _gaussian_random(ctx, op, ins):
    shape = tuple(op.attr("shape"))
    dtype = np_dtype(op.attr("dtype", "float32"))
    mean = op.attr("mean", 0.0)
    std = op.attr("std", 1.0)
    key = _op_key(ctx, op)
    return {"Out": (mean + std * jax.random.normal(key, shape, dtype=jnp.float32)).astype(dtype)}


@register_op("truncated_gaussian_random")
def _truncated_gaussian_random(ctx, op, ins):
    shape = tuple(op.attr("shape"))
    dtype = np_dtype(op.attr("dtype", "float32"))
    mean = op.attr("mean", 0.0)
    std = op.attr("std", 1.0)
    key = _op_key(ctx, op)
    # reference truncates at 2 std (truncated_gaussian_random_op.cc)
    z = jax.random.truncated_normal(key, -2.0, 2.0, shape, dtype=jnp.float32)
    return {"Out": (mean + std * z).astype(dtype)}


def _op_key(ctx, op):
    """Per-op RNG: an op-level seed attr pins the stream (reference ops all
    take a `seed` attr); otherwise consume the threaded scope key."""
    seed = op.attr("seed", 0)
    if seed:
        return jax.random.PRNGKey(seed)
    return ctx.next_key()


@register_op("cast")
def _cast(ctx, op, ins):
    x = first(ins, "X")
    return {"Out": x.astype(np_dtype(op.attr("out_dtype", op.attr("dtype", "float32"))))}


@register_op("space_to_depth")
def _space_to_depth(ctx, op, ins):
    """reference space_to_depth_op.h space_to_depth_compute: the flat buffer
    is written as [B, C/bs^2, H*bs, W*bs] (channel k of the input splits into
    offset=k//Cout picking the in-block (dy,dx) and c2=k%Cout) and then
    REINTERPRETED as [B, C*bs^2, H/bs, W/bs] — matched bit-for-bit here via
    reshape/transpose so OpTest goldens transfer."""
    x = first(ins, "X")
    bs = int(op.attr("blocksize"))
    B, C, H, W = x.shape
    if C % (bs * bs) != 0 or H % bs != 0 or W % bs != 0:
        raise ValueError(
            f"space_to_depth: C ({C}) must divide blocksize^2 and H/W ({H},{W}) "
            f"must divide blocksize ({bs}) — reference InferShape contract")
    cout = C // (bs * bs)
    # x[b, (dy*bs+dx)*cout + c2, j, i] -> A[b, c2, j*bs+dy, i*bs+dx]
    x6 = x.reshape(B, bs, bs, cout, H, W)           # [b, dy, dx, c2, j, i]
    a = jnp.transpose(x6, (0, 3, 4, 1, 5, 2))        # [b, c2, j, dy, i, dx]
    flat = a.reshape(B, cout, H * bs, W * bs)
    return {"Out": flat.reshape(B, C * bs * bs, H // bs, W // bs)}


@register_op("reshape2")
def _reshape2(ctx, op, ins):
    x = first(ins, "X")
    shape = list(op.attr("shape"))
    # fluid semantics: 0 copies the input dim, -1 infers (reshape_op.cc)
    out_shape = []
    for i, s in enumerate(shape):
        if s == 0:
            out_shape.append(x.shape[i])
        else:
            out_shape.append(s)
    return {"Out": jnp.reshape(x, out_shape), "XShape": jnp.zeros((0,) + x.shape, dtype=x.dtype)}


@register_op("reshape")
def _reshape(ctx, op, ins):
    out = _reshape2(ctx, op, ins)
    return {"Out": out["Out"]}


@register_op("transpose2")
def _transpose2(ctx, op, ins):
    x = first(ins, "X")
    axis = op.attr("axis")
    return {"Out": jnp.transpose(x, axis), "XShape": jnp.zeros((0,) + x.shape, dtype=x.dtype)}


@register_op("transpose")
def _transpose(ctx, op, ins):
    return {"Out": _transpose2(ctx, op, ins)["Out"]}


@register_op("concat")
def _concat(ctx, op, ins):
    xs = ins["X"]
    return {"Out": jnp.concatenate(xs, axis=op.attr("axis", 0))}


@register_op("split")
def _split(ctx, op, ins):
    x = first(ins, "X")
    axis = op.attr("axis", 0)
    num = op.attr("num", 0)
    sections = op.attr("sections", [])
    if num:
        parts = jnp.split(x, num, axis=axis)
    else:
        idx = np.cumsum(sections[:-1]).tolist()
        parts = jnp.split(x, idx, axis=axis)
    return {"Out": parts}


@register_op("assign")
def _assign(ctx, op, ins):
    return {"Out": first(ins, "X")}


@register_op("scale")
def _scale(ctx, op, ins):
    x = first(ins, "X")
    scale = op.attr("scale", 1.0)
    bias = op.attr("bias", 0.0)
    if op.attr("bias_after_scale", True):
        return {"Out": x * scale + bias}
    return {"Out": (x + bias) * scale}


@register_op("shape")
def _shape(ctx, op, ins):
    x = first(ins, "Input")
    return {"Out": jnp.asarray(x.shape, dtype=jnp.int32)}


@register_op("slice")
def _slice(ctx, op, ins):
    x = first(ins, "Input")
    axes = op.attr("axes")
    starts = op.attr("starts")
    ends = op.attr("ends")
    idx = [slice(None)] * x.ndim
    for ax, st, en in zip(axes, starts, ends):
        idx[ax] = slice(st, en)
    return {"Out": x[tuple(idx)]}


@register_op("expand")
def _expand(ctx, op, ins):
    x = first(ins, "X")
    times = op.attr("expand_times")
    return {"Out": jnp.tile(x, times)}


@register_op("stack")
def _stack(ctx, op, ins):
    return {"Y": jnp.stack(ins["X"], axis=op.attr("axis", 0))}


@register_op("unstack")
def _unstack(ctx, op, ins):
    x = first(ins, "X")
    axis = op.attr("axis", 0)
    n = x.shape[axis]
    parts = [jnp.squeeze(p, axis=axis) for p in jnp.split(x, n, axis=axis)]
    return {"Y": parts}


@register_op("squeeze2")
def _squeeze2(ctx, op, ins):
    x = first(ins, "X")
    axes = op.attr("axes", [])
    if axes:
        out = jnp.squeeze(x, axis=tuple(a % x.ndim for a in axes))
    else:
        out = jnp.squeeze(x)
    return {"Out": out, "XShape": jnp.zeros((0,) + x.shape, dtype=x.dtype)}


@register_op("squeeze")
def _squeeze(ctx, op, ins):
    return {"Out": _squeeze2(ctx, op, ins)["Out"]}


@register_op("unsqueeze2")
def _unsqueeze2(ctx, op, ins):
    x = first(ins, "X")
    out = x
    for a in sorted(op.attr("axes")):
        out = jnp.expand_dims(out, a)
    return {"Out": out, "XShape": jnp.zeros((0,) + x.shape, dtype=x.dtype)}


@register_op("unsqueeze")
def _unsqueeze(ctx, op, ins):
    return {"Out": _unsqueeze2(ctx, op, ins)["Out"]}


@register_op("gather")
def _gather(ctx, op, ins):
    x = first(ins, "X")
    index = first(ins, "Index")
    return {"Out": jnp.take(x, index.reshape(-1), axis=0)}


@register_op("one_hot")
def _one_hot(ctx, op, ins):
    x = first(ins, "X")
    depth = op.attr("depth")
    flat = x.reshape(x.shape[:-1]) if x.shape and x.shape[-1] == 1 else x
    return {"Out": jax.nn.one_hot(flat, depth, dtype=jnp.float32)}


@register_op("pad")
def _pad(ctx, op, ins):
    x = first(ins, "X")
    paddings = op.attr("paddings")  # flat [before0, after0, before1, ...]
    value = op.attr("pad_value", 0.0)
    cfg = [(paddings[2 * i], paddings[2 * i + 1]) for i in range(x.ndim)]
    return {"Out": jnp.pad(x, cfg, constant_values=value)}


@register_op("assign_value")
def _assign_value(ctx, op, ins):
    values = op.attr("values")
    dtype = np_dtype(op.attr("dtype", "float32"))
    arr = np.asarray(values).astype(dtype)
    shape = op.attr("shape")
    if shape:
        arr = arr.reshape(shape)
    return {"Out": jnp.asarray(arr)}


@register_op("increment")
def _increment(ctx, op, ins):
    x = first(ins, "X")
    step = np.asarray(op.attr("step", 1.0)).astype(x.dtype)  # keep int counters int
    return {"Out": x + step}


@register_op("fill_zeros_like")
def _fill_zeros_like(ctx, op, ins):
    return {"Out": jnp.zeros_like(first(ins, "X"))}


@register_op("range")
def _range(ctx, op, ins):
    start = first(ins, "Start")
    end = first(ins, "End")
    step = first(ins, "Step")
    # static-shape path: attrs carry python scalars when available
    s = op.attr("start_v", None)
    e = op.attr("end_v", None)
    st = op.attr("step_v", None)
    dtype = op.attr("dtype", None)
    out_dtype = np_dtype(dtype) if dtype else None
    if s is not None and e is not None and st is not None:
        fallback = start.dtype if start is not None else jnp.int32
        return {"Out": jnp.arange(s, e, st, dtype=out_dtype or fallback)}
    # mixed scalar/tensor operands: resolve each from attr or input
    sv = s if s is not None else int(start)
    ev = e if e is not None else int(end)
    stv = st if st is not None else int(step)
    out = jnp.arange(sv, ev, stv)
    return {"Out": out.astype(out_dtype) if out_dtype else out}


@register_op("gather_nd")
def _gather_nd(ctx, op, ins):
    """reference gather_nd_op: index [..., K] selects into x's first K dims."""
    x = first(ins, "X")
    index = first(ins, "Index").astype(jnp.int32)
    k = index.shape[-1]
    flat_idx = index.reshape(-1, k)
    out = x[tuple(flat_idx[:, i] for i in range(k))]
    return {"Out": out.reshape(index.shape[:-1] + x.shape[k:])}


@register_op("scatter")
def _scatter(ctx, op, ins):
    """reference scatter_op: write (or add) Updates rows into X at Ids."""
    x = first(ins, "X")
    ids = first(ins, "Ids").reshape(-1).astype(jnp.int32)
    upd = first(ins, "Updates")
    if op.attr("overwrite", True):
        return {"Out": x.at[ids].set(upd)}
    return {"Out": x.at[ids].add(upd)}


@register_op("scatter_nd_add")
def _scatter_nd_add(ctx, op, ins):
    x = first(ins, "X")
    index = first(ins, "Index").astype(jnp.int32)
    upd = first(ins, "Updates")
    k = index.shape[-1]
    flat_idx = index.reshape(-1, k)
    flat_upd = upd.reshape((flat_idx.shape[0],) + x.shape[k:])
    return {"Out": x.at[tuple(flat_idx[:, i] for i in range(k))].add(flat_upd)}


@register_op("cumsum")
def _cumsum(ctx, op, ins):
    x = first(ins, "X")
    axis = op.attr("axis", -1)
    rev = op.attr("reverse", False)
    excl = op.attr("exclusive", False)
    if rev:
        x = jnp.flip(x, axis)
    out = jnp.cumsum(x, axis=axis)
    if excl:
        out = out - x
    if rev:
        out = jnp.flip(out, axis)
    return {"Out": out}


@register_op("argsort")
def _argsort(ctx, op, ins):
    x = first(ins, "X")
    axis = op.attr("axis", -1)
    idx = jnp.argsort(x, axis=axis, descending=op.attr("descending", False))
    return {"Out": jnp.take_along_axis(x, idx, axis=axis),
            "Indices": idx.astype(canon_dtype("int64"))}


@register_op("expand_as")
def _expand_as(ctx, op, ins):
    x = first(ins, "X")
    target = first(ins, "target_tensor")
    if target is None:
        target = first(ins, "Y")
    times = tuple(t // s for t, s in zip(target.shape, x.shape))
    return {"Out": jnp.tile(x, times)}


@register_op("linspace")
def _linspace(ctx, op, ins):
    start = first(ins, "Start").reshape(())
    stop = first(ins, "Stop").reshape(())
    num = op.attr("num_v", None)
    if num is None:
        num_in = first(ins, "Num")
        if hasattr(num_in, "aval") and not isinstance(num_in, np.ndarray):
            # traced tensor Num: XLA needs a static length — tell the user
            # how to supply it instead of failing in int() mid-trace
            raise NotImplementedError(
                "linspace: the output length must be static under XLA; pass "
                "the point count via the num_v attr (layers.linspace does)")
        num = int(np.asarray(num_in).reshape(()))
    return {"Out": jnp.linspace(start, stop, num)}


@register_op("norm")
def _norm(ctx, op, ins):
    """reference norm_op: l2-normalize along axis; Norm is the l2 norm."""
    x = first(ins, "X")
    axis = op.attr("axis", -1)
    eps = op.attr("epsilon", 1e-10)
    n = jnp.sqrt(jnp.sum(jnp.square(x), axis=axis, keepdims=True) + eps)
    return {"Out": x / n, "Norm": n}


@register_op("flatten2")
def _flatten2(ctx, op, ins):
    x = first(ins, "X")
    ax = op.attr("axis", 1)
    lead = int(np.prod(x.shape[:ax]))  # prod of empty tuple is 1
    tail = int(np.prod(x.shape[ax:]))
    out = jnp.reshape(x, (lead, tail))
    return {"Out": out, "XShape": jnp.zeros((0,) + x.shape, dtype=x.dtype)}


@register_op("flatten")
def _flatten(ctx, op, ins):
    return {"Out": _flatten2(ctx, op, ins)["Out"]}


@register_op("shard_index")
def _shard_index(ctx, op, ins):
    """reference shard_index_op: map global ids to shard-local ids."""
    x = first(ins, "X")
    index_num = op.attr("index_num")
    nshards = op.attr("nshards")
    shard_id = op.attr("shard_id")
    ignore_value = op.attr("ignore_value", -1)
    shard_size = (index_num + nshards - 1) // nshards
    in_shard = (x // shard_size) == shard_id
    return {"Out": jnp.where(in_shard, x % shard_size, ignore_value)}


# --- build-time shape/dtype inference --------------------------------------

from ..core import analysis as _A
from ..core.dtypes import canonical_dtype as _canon


_A.register_unary_infer("assign", "scale", "increment", "fill_zeros_like",
                        "cumsum")


def _infer_filled(ctx):
    shape = ctx.op.attr("shape", None)
    if not shape:
        return
    ctx.set_out("Out", tuple(shape), _canon(ctx.op.attr("dtype", "float32")))


_A.register_rule(["fill_constant", "uniform_random", "gaussian_random",
                  "truncated_gaussian_random"], _infer_filled)


def _infer_cast(ctx):
    dt = ctx.op.attr("out_dtype", ctx.op.attr("dtype", None))
    ctx.set_out("Out", ctx.in_shape("X"), _canon(dt) if dt else None)


_A.register_rule(["cast"], _infer_cast)


def _infer_reshape(ctx):
    xs = ctx.in_shape("X")
    tgt = list(ctx.op.attr("shape", []))
    if not tgt:
        return
    if tgt.count(-1) > 1:
        ctx.fail(f"reshape target {tgt} has more than one -1")
    if xs is None:
        return
    out = []
    for i, s in enumerate(tgt):
        if s == 0:
            if i >= len(xs):
                ctx.fail(f"reshape target {tgt} copies dim {i} (the 0 "
                         f"entry) but X{tuple(xs)} has rank {len(xs)}")
            out.append(xs[i])
        else:
            out.append(int(s))
    x_known = all(d != _A.DYN for d in xs)
    if x_known:
        total = int(np.prod(xs)) if xs else 1
        if -1 in out:
            neg = out.index(-1)
            rest = int(np.prod([d for j, d in enumerate(out) if j != neg]) or 1)
            if rest <= 0 or total % rest != 0:
                ctx.fail(f"cannot reshape X{tuple(xs)} ({total} elements) "
                         f"into {tgt}")
            out[neg] = total // rest
        elif all(d != _A.DYN for d in out) and int(np.prod(out) if out else 1) != total:
            ctx.fail(f"cannot reshape X{tuple(xs)} ({total} elements) into "
                     f"{tgt} ({int(np.prod(out) if out else 1)} elements)")
    ctx.set_out("Out", tuple(out), ctx.in_dtype("X"))
    if ctx.op.output("XShape"):
        ctx.set_out("XShape", (0,) + tuple(xs), ctx.in_dtype("X"))


_A.register_rule(["reshape2", "reshape"], _infer_reshape)


def _infer_transpose(ctx):
    xs = ctx.in_shape("X")
    axis = ctx.op.attr("axis")
    if xs is None or axis is None:
        return
    if sorted(a % len(xs) for a in axis) != list(range(len(xs))):
        ctx.fail(f"transpose axis {list(axis)} is not a permutation of "
                 f"X{tuple(xs)}'s rank {len(xs)}")
    ctx.set_out("Out", tuple(xs[a] for a in axis), ctx.in_dtype("X"))
    if ctx.op.output("XShape"):
        ctx.set_out("XShape", (0,) + tuple(xs), ctx.in_dtype("X"))


_A.register_rule(["transpose2", "transpose"], _infer_transpose)


def _infer_concat(ctx):
    shapes = [ctx.in_shape("X", i) for i in range(ctx.n_inputs("X"))]
    if any(s is None for s in shapes) or not shapes:
        return
    rank = len(shapes[0])
    if any(len(s) != rank for s in shapes):
        ctx.fail(f"concat inputs have mixed ranks: "
                 f"{[tuple(s) for s in shapes]}")
    axis = ctx.op.attr("axis", 0) % rank
    out = list(shapes[0])
    for i, s in enumerate(shapes[1:], start=1):
        for d in range(rank):
            if d == axis:
                continue
            u = _A.unify_dim(out[d], s[d])
            if u is None:
                ctx.fail(f"concat input {i} shape {tuple(s)} mismatches "
                         f"{tuple(out)} outside axis {axis}",
                         var=ctx.op.input("X")[i])
            out[d] = u
    cat = 0
    for s in shapes:
        if s[axis] == _A.DYN:
            cat = _A.DYN
            break
        cat += s[axis]
    out[axis] = cat
    ctx.set_out("Out", tuple(out), ctx.in_dtype("X"))


_A.register_rule(["concat"], _infer_concat)


def _infer_split(ctx):
    xs = ctx.in_shape("X")
    if xs is None:
        return
    axis = ctx.op.attr("axis", 0) % len(xs)
    num = ctx.op.attr("num", 0)
    sections = ctx.op.attr("sections", [])
    names = ctx.op.output("Out")
    for i in range(len(names)):
        out = list(xs)
        if num:
            if xs[axis] == _A.DYN:
                out[axis] = _A.DYN
            elif xs[axis] % num:
                ctx.fail(f"split axis dim {xs[axis]} not divisible by "
                         f"num={num}")
            else:
                out[axis] = xs[axis] // num
        elif sections:
            if i < len(sections):
                out[axis] = sections[i]
        ctx.set_out("Out", tuple(out), ctx.in_dtype("X"), i=i)


_A.register_rule(["split"], _infer_split)


def _infer_one_hot(ctx):
    xs = ctx.in_shape("X")
    depth = ctx.op.attr("depth")
    if xs is None or depth is None:
        return
    base = tuple(xs[:-1]) if (xs and xs[-1] == 1) else tuple(xs)
    ctx.set_out("Out", base + (int(depth),), "float32")


_A.register_rule(["one_hot"], _infer_one_hot)


def _infer_stack(ctx):
    shapes = [ctx.in_shape("X", i) for i in range(ctx.n_inputs("X"))]
    if any(s is None for s in shapes) or not shapes:
        return
    base = shapes[0]
    for s in shapes[1:]:
        u = _A.unify_shape(base, s)
        if u is None:
            ctx.fail(f"stack inputs have mismatched shapes: "
                     f"{[tuple(s) for s in shapes]}")
        base = u
    axis = ctx.op.attr("axis", 0) % (len(base) + 1)
    out = tuple(base[:axis]) + (len(shapes),) + tuple(base[axis:])
    ctx.set_out("Y", out, ctx.in_dtype("X"))


_A.register_rule(["stack"], _infer_stack)


def _infer_gather(ctx):
    xs = ctx.in_shape("X")
    idx = ctx.in_shape("Index")
    if xs is None or idx is None:
        return
    n = _A.DYN
    if all(d != _A.DYN for d in idx):
        n = int(np.prod(idx)) if idx else 1
    ctx.set_out("Out", (n,) + tuple(xs[1:]), ctx.in_dtype("X"))


_A.register_rule(["gather"], _infer_gather)


# --- static cost rules (core/resource_plan.py) ------------------------------

from ..core import resource_plan as _RP

# pure data movement: zero FLOPs, in+out traffic
_RP.register_bytes_cost("assign", "cast", "reshape2", "reshape",
                        "transpose2", "transpose", "concat", "split",
                        "one_hot", "stack", "gather", "fill_zeros_like",
                        "expand", "squeeze2", "squeeze", "unsqueeze2",
                        "unsqueeze", "slice", "pad", "pad2d", "shape",
                        "flatten2", "flatten")
_RP.register_elementwise_cost("scale", "increment", "cumsum")


def _cost_filled(ctx):
    """Generators write their output once; RNG costs a few FLOPs/elem."""
    out_b = sum(ctx.env.nbytes(n) for n in ctx.op.output_arg_names)
    rng = ctx.op.type != "fill_constant"
    return float(ctx.out_elems_total() * (8 if rng else 0)), float(out_b)


_RP.register_cost(["fill_constant", "uniform_random", "gaussian_random",
                   "truncated_gaussian_random"], _cost_filled)
