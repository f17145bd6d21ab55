"""Control-flow op lowerings: while, conditional_block, tensor arrays.

Reference: operators/controlflow/while_op.cc (interpreter-recursive: a
sub-executor runs the sub-block per iteration with step scopes),
conditional_block_op.cc, tensor array ops (array_write/array_read).

TPU-first redesign: sub-blocks lower to `lax.while_loop` / `lax.cond`
bodies — compiled control flow, no host round-trips.  The carried state is
the set of sub-block-written vars that exist outside; shapes must be loop
invariant (XLA requirement), which the reference never guaranteed but all
its RNN/beam-search uses satisfy.

Tensor arrays (LoDTensorArray) are python lists in the env outside compiled
control flow; inside a `while` sub-block they are stacked buffers updated
with lax.dynamic_update_slice (`array_write` with a static-size hint).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ..core.registry import register_op
from .common import first


def _sub_block_ops(ctx, op, attr="sub_block"):
    block_idx = op.attr(attr)
    block = op.block.program.blocks[block_idx]
    return [o for o in block.ops if o.type not in ("feed", "fetch")]


def _written_names(ops):
    out = []
    seen = set()
    for o in ops:
        for n in o.output_arg_names:
            if n not in seen:
                seen.add(n)
                out.append(n)
    return out


@register_op("while")
def _while(ctx, op, ins):
    from ..core.lowering import run_ops

    sub_ops = _sub_block_ops(ctx, op)
    cond_name = op.input("Condition")[0]
    env = ctx.env  # current lowering environment (set by run_ops)
    carried = [n for n in _written_names(sub_ops) if n in env]
    if cond_name not in carried:
        carried = carried + [cond_name] if cond_name in env else carried

    base_env = dict(env)
    KEY = "__rng_key__"  # thread the RNG key through the loop carry so
    # RNG-consuming ops (dropout, uniform_random) in the body are legal

    def cond_fn(carry):
        return jnp.reshape(carry[cond_name], ()).astype(bool)

    def body_fn(carry):
        e = dict(base_env)
        ctx.key = carry[KEY]
        e.update({n: v for n, v in carry.items() if n != KEY})
        e = run_ops(ctx, sub_ops, e)
        out = {n: e[n] for n in carry if n != KEY}
        out[KEY] = ctx.key
        return out

    init = {n: env[n] for n in carried}
    if cond_name not in init:
        raise KeyError(f"while: condition var {cond_name!r} must exist before the loop")
    init[KEY] = ctx.key
    final = jax.lax.while_loop(cond_fn, body_fn, init)
    ctx.key = final.pop(KEY)
    # write back: executor splices these into env via the returned dict
    return {"__env_update__": final}


@register_op("repeat")
def _repeat(ctx, op, ins):
    """`layers.Repeat`: the sub-block `times` over, a pass reading the carried
    variables the last one wrote.  What the body reads from outside (its `X`:
    parameters among them) enters each pass as an argument, so the one
    gradient of a captured variable is the sum over the passes, added up in
    that variable's own dtype (float32 for a master weight: the cast to the
    activations' dtype is an op of the body).  With `recompute` each pass is a
    `jax.checkpoint`: backward keeps the pass's inputs and runs its forward
    again.  The RNG key rides in the carry, so a pass's random ops draw the
    same numbers when it is computed again.

    One `lax.scan` over the passes, the body traced once.  The other form, the
    sub-block interpreted `times` over, was timed beside it on a v5e at a
    looped decoder's published widths (PERF.md, PR 38: four passes of eight
    layers, one sequence of 4096): its step was 1.3% LONGER, its planned peak
    1.0 GB lower, its compiled text three times as long and its compile 3.5
    times; it was not kept.

    Scopes: the body's ops keep their `op<idx>:<type>` scopes (numbered within
    the body) under `loop_pass`, itself under this op's own scope."""
    from ..core.lowering import run_ops
    from ..monitor import MONITOR as _MON

    sub_ops = _sub_block_ops(ctx, op)
    times, recompute = int(op.attr("times")), bool(op.attr("recompute", False))
    carry_names, update_names = op.attr("carry_vars"), op.attr("carry_updates")
    out_names = op.attr("out_vars", [])
    captured = dict(zip(op.input("X"), ins.get("X", [])))
    _MON.counter("lowering.loop_passes").inc(times)
    _MON.counter("lowering.loop_body_ops").inc(len(sub_ops))
    _MON.counter("lowering.recomputed_segments").inc(times if recompute else 0)

    def one_pass(carries, key, outer):
        env = dict(outer)
        env.update(zip(carry_names, carries))
        ctx.key = key
        with jax.named_scope("loop_pass"):
            env = run_ops(ctx, sub_ops, env)
        return [env[n] for n in update_names], ctx.key, [env[n] for n in out_names]

    if recompute:
        one_pass = jax.checkpoint(one_pass)

    def step(carry, _):
        new, key, outs = one_pass(carry[0], carry[1], captured)
        return (new, key), outs

    (carries, ctx.key), stacked = jax.lax.scan(step, (list(ins["Init"]), ctx.key), None, length=times)
    return {"Out": list(stacked), "Final": list(carries)}


@register_op("conditional_block")
def _conditional_block(ctx, op, ins):
    from ..core.lowering import run_ops

    sub_ops = _sub_block_ops(ctx, op)
    env = ctx.env
    cond = first(ins, "Cond")
    pred = jnp.reshape(cond, ()).astype(bool)
    written = [n for n in _written_names(sub_ops)]
    # vars that exist outside keep their old value on the false branch;
    # fresh vars need a defined false-branch value -> zeros_like via tracing
    base_env = dict(env)

    def true_fn(key):
        e = dict(base_env)
        ctx.key = key
        e = run_ops(ctx, sub_ops, e)
        return {n: e[n] for n in written}, ctx.key

    # hoist the shape probe: trace the sub-block once here instead of once
    # per false-branch (which would compound 2^k for nested conds), and
    # restore ctx.key so the probe doesn't de-sync RNG threading
    key0 = ctx.key
    out_shapes, _ = jax.eval_shape(true_fn, key0)
    ctx.key = key0

    def false_fn(key):
        return {
            n: base_env[n] if n in base_env
            else jnp.zeros(out_shapes[n].shape, out_shapes[n].dtype)
            for n in written
        }, key

    final, new_key = jax.lax.cond(pred, true_fn, false_fn, ctx.key)
    ctx.key = new_key
    return {"__env_update__": final}


@register_op("select_input")
def _select_input(ctx, op, ins):
    xs = ins["X"]
    mask = jnp.reshape(first(ins, "Mask"), ()).astype(jnp.int32)
    out = xs[0]
    for i in range(1, len(xs)):
        out = jnp.where(mask == i, xs[i], out)
    return {"Out": out}


# --- tensor arrays ---------------------------------------------------------

def _static_index(i):
    """Static int for concrete values; None for traced (in-loop) indices."""
    try:
        import numpy as _np

        a = _np.asarray(i)
        if a.size != 1:
            return None
        return int(a.reshape(()))  # avoids the ndim>0 int() deprecation
    except Exception:
        return None


@register_op("create_array")
def _create_array(ctx, op, ins):
    return {"Out": [[]]}  # one output whose value is an empty array-list


@register_op("array_write")
def _array_write(ctx, op, ins):
    x = first(ins, "X")
    i = first(ins, "I")
    arr = first(ins, "Array", default=None)
    arr = list(arr) if arr is not None else []
    idx = _static_index(i)
    if idx is None:
        raise NotImplementedError(
            "array_write with a traced index inside compiled control flow "
            "requires the static-size stacked-buffer form (StaticRNN uses it)"
        )
    while len(arr) <= idx:
        arr.append(None)
    arr[idx] = x
    return {"Out": [arr]}


@register_op("array_read")
def _array_read(ctx, op, ins):
    arr = first(ins, "X")
    i = first(ins, "I")
    idx = _static_index(i)
    if idx is None:
        # traced index (beam-search-style decode loops): homogeneous entries
        # stack into one buffer and a dynamic slice picks the row — the
        # static-shape answer to the reference's LoDTensorArray indexing
        shapes = {tuple(a.shape) for a in arr}
        dtypes = {a.dtype for a in arr}
        if len(shapes) != 1 or len(dtypes) != 1:
            raise NotImplementedError(
                f"array_read with traced index needs homogeneous entries, "
                f"got shapes {shapes} dtypes {dtypes}")
        stacked = jnp.stack(list(arr))
        ii = jnp.asarray(i).reshape(()).astype(jnp.int32)
        return {"Out": jax.lax.dynamic_index_in_dim(stacked, ii, 0, keepdims=False)}
    return {"Out": arr[idx]}


@register_op("array_length")
def _array_length(ctx, op, ins):
    arr = first(ins, "X")
    return {"Out": jnp.asarray([len(arr)], dtype=jnp.int32)}


# Registry of python callables for py_func ops (the program stores an id —
# callables aren't serializable; reference py_func_op.cc keeps the same
# registry on the python side, py_func:PyFuncRegistry).  Ids come from a
# monotonic counter so entries COULD be released without collisions;
# lifetime matches the program that references the id.
import itertools as _itertools

_PY_FUNC_REGISTRY = {}
_PY_FUNC_IDS = _itertools.count()


def register_py_func(fn) -> int:
    fid = next(_PY_FUNC_IDS)
    _PY_FUNC_REGISTRY[fid] = fn
    return fid


def release_py_func(fid: int):
    """Drop a registered callable (call when its program is discarded)."""
    _PY_FUNC_REGISTRY.pop(fid, None)


@register_op("py_func")
def _py_func(ctx, op, ins):
    """reference operators/py_func_op.cc (layers.py_func): run a python
    callable on host inside the compiled program — lowered through
    jax.pure_callback with the declared output shapes/dtypes."""
    import numpy as np

    from ..core.dtypes import as_np_dtype

    fn = _PY_FUNC_REGISTRY[op.attr("func_id")]
    xs = ins.get("X", [])
    out_shapes = op.attr("out_shapes")
    out_dtypes = op.attr("out_dtypes")
    result_shape = [
        jax.ShapeDtypeStruct(tuple(s), as_np_dtype(d))
        for s, d in zip(out_shapes, out_dtypes)
    ]

    def host_fn(*arrays):
        outs = fn(*[np.asarray(a) for a in arrays])
        if not isinstance(outs, (list, tuple)):
            outs = (outs,)
        if len(outs) != len(result_shape):
            raise ValueError(
                f"py_func returned {len(outs)} outputs, program declares "
                f"{len(result_shape)}")
        # cast to the DECLARED dtypes: python lists/scalars arrive float64
        # and pure_callback hard-fails on any mismatch with an opaque error
        return tuple(np.asarray(o, dtype=rs.dtype)
                     for o, rs in zip(outs, result_shape))

    outs = jax.pure_callback(host_fn, tuple(result_shape), *xs)
    return {"Out": list(outs)}


# --- build-time shape/dtype inference --------------------------------------

from ..core import analysis as _A


def _infer_select_input(ctx):
    out = None
    for i in range(ctx.n_inputs("X")):
        s = ctx.in_shape("X", i)
        if s is None:
            continue
        if out is not None and _A.unify_shape(out, s) is None:
            ctx.fail(f"select_input branches disagree on shape: "
                     f"{tuple(out)} vs {tuple(s)}", var=ctx.op.input("X")[i])
        out = s if out is None else _A.unify_shape(out, s)
    ctx.set_out("Out", out, ctx.in_dtype("X"))


_A.register_rule(["select_input"], _infer_select_input)


def _infer_sub_block_op(ctx):
    """while / conditional_block: validate the sub_block attr eagerly so a
    broken builder fails at append time, not at lowering."""
    sub = ctx.op.attrs.get("sub_block")
    program = ctx.block.program
    if sub is None or not isinstance(sub, int) \
            or not (0 <= sub < len(program.blocks)) or sub == ctx.block.idx:
        ctx.fail(f"sub_block attr {sub!r} does not name a valid other "
                 f"block (program has {len(program.blocks)})")


_A.register_rule(["while", "conditional_block"], _infer_sub_block_op)


def _infer_repeat(ctx):
    """repeat: the sub-block exists, the trip count is a positive constant,
    every carried variable has an initial value and an update of its own shape
    and dtype, and every output is `times` of its body variable."""
    _infer_sub_block_op(ctx)
    attrs = ctx.op.attrs
    times = attrs.get("times")
    if not isinstance(times, int) or times < 1:
        ctx.fail(f"times={times!r}: the trip count is a positive integer known when the program is built")
    carries, updates = attrs.get("carry_vars", []), attrs.get("carry_updates", [])
    if not (len(carries) == len(updates) == ctx.n_inputs("Init")):
        ctx.fail(f"{len(carries)} carried variables, {len(updates)} updates and {ctx.n_inputs('Init')} initial values")
    body = ctx.block.program.blocks[attrs["sub_block"]]
    for i, (carried, update) in enumerate(zip(carries, updates)):
        new = body._find_var_recursive(update)
        if new is None:
            ctx.fail(f"the update {update!r} of {carried!r} is declared nowhere", var=update)
        init_shape, init_dtype = ctx.in_shape("Init", i), ctx.in_dtype("Init", i)
        if (new.shape is not None and init_shape is not None
                and _A.unify_shape(tuple(new.shape), tuple(init_shape)) is None) or \
                (new.dtype is not None and init_dtype is not None and str(new.dtype) != str(init_dtype)):
            ctx.fail(f"{carried!r} starts as {tuple(init_shape)} {init_dtype} and is updated with "
                     f"{tuple(new.shape)} {new.dtype}: a carried variable keeps its shape and dtype", var=update)
        ctx.set_out("Final", init_shape, init_dtype, i=i)
    for i, name in enumerate(attrs.get("out_vars", [])):
        v = body._find_var_recursive(name)
        if v is None:
            ctx.fail(f"the output {name!r} is declared nowhere", var=name)
        ctx.set_out("Out", None if v.shape is None else (times,) + tuple(v.shape), v.dtype, i=i)


_A.register_rule(["repeat"], _infer_repeat)


# Static cost rules (core/resource_plan.py): sub-block owners carry only
# their own carry/select traffic — the planner descends into the body and
# accounts its ops (one execution; a while's trip count is not static).

from ..core import resource_plan as _RP

_RP.register_bytes_cost("while", "conditional_block", "select_input")
# repeat's own row is its carry and stacked outputs; the planner counts the
# body's rows `times` over, and once more forward where it is recomputed
_RP.register_bytes_cost("repeat")
