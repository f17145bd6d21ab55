"""Op registry: lowering rules from program ops to JAX.

Reference counterpart: `framework/op_registry.h:66` + `framework/op_info.cc`
(static registration of ops, kernels, grad makers).  The TPU rebuild needs no
per-device kernel table and no grad makers:

  * every op registers ONE `lower` function that emits jax.numpy / lax calls;
    XLA does the per-backend codegen the reference's CPU/CUDA/MKLDNN kernels
    did by hand;
  * gradients come from `jax.vjp` over the lowered forward segment
    (core/autodiff.py), so there is no grad-op vocabulary to register.

`lower(ctx, op, ins)` receives `ins` as {slot: [jax values]} and returns
{slot: [jax values]}.  `ctx` is a LoweringContext (core/lowering.py) giving
RNG keys, train/eval mode and mesh info.

`infer(op, block)` is the compile-time InferShape role (reference
shape_inference.h): validate input shapes/dtypes and declare outputs at
`append_op` time.  Rules are registered next to the lowerings via
`set_infer` / `core.analysis.register_rule`; `infer_and_check` classifies
any failure as a `ShapeInferenceError` carrying op/var/block provenance.
"""
from __future__ import annotations

from typing import Callable, Dict, List, Optional

OpLowerFn = Callable  # (ctx, op, ins) -> {slot: [values]}
InferFn = Callable  # (op, block) -> None (sets output var shapes/dtypes)
CostFn = Callable  # (op, block, env) -> (flops, traffic_bytes)
StatsFn = Callable  # (step, {slot: [one value per op of the type]}) -> None


class OpDef:
    def __init__(self, type: str, lower: OpLowerFn, infer: Optional[InferFn] = None,
                 cost: Optional[CostFn] = None):
        self.type = type
        self.lower = lower
        self.infer = infer
        self.cost = cost
        self.step_stats = None  # (slots, publish, attrs): see set_step_stats
        self.kept = None  # what a recomputed segment may keep of the op: see set_kept


_REGISTRY: Dict[str, OpDef] = {}


def register_op(type: str, infer: Optional[InferFn] = None):
    """Decorator: @register_op("relu") def _relu(ctx, op, ins): ..."""

    def deco(fn: OpLowerFn):
        prev = _REGISTRY.get(type)
        d = OpDef(type, fn, infer)
        if infer is None and prev is not None and prev.infer is not None:
            d.infer = prev.infer  # re-registration keeps an attached infer
        if prev is not None and prev.cost is not None:
            d.cost = prev.cost  # re-registration keeps an attached cost rule
        if prev is not None:
            d.step_stats = prev.step_stats
            d.kept = prev.kept
        _REGISTRY[type] = d
        return fn

    return deco


def set_infer(type: str, infer: InferFn):
    """Attach a build-time shape/dtype inference fn to a registered op."""
    try:
        _REGISTRY[type].infer = infer
    except KeyError:
        raise KeyError(
            f"set_infer({type!r}): op has no registered lowering"
        ) from None


def set_cost(type: str, cost: CostFn):
    """Attach a static FLOPs/bytes cost rule to a registered op (the
    resource planner's per-op model, core/resource_plan.py).  Registered
    next to the lowerings in ops/* like the `infer=` rules."""
    try:
        _REGISTRY[type].cost = cost
    except KeyError:
        raise KeyError(
            f"set_cost({type!r}): op has no registered lowering"
        ) from None


def set_kept(type: str, rule):
    """Say what a `recompute_scope` segment may KEEP of a registered op instead
    of making it again in backward (core/lowering.py: `plan_kept`): a value that
    is dear to make (a matrix product, a kernel call) and that the op's lowering
    can name with `jax.ad_checkpoint.checkpoint_name`.  `rule(ctx, op, shapes)`
    (the lowering's context, the op, a `resource_plan.ShapeEnv`) returns (the
    name, the value's bytes over the whole batch), or None where this op makes
    no such value (its kernel is not the path taken); a third value True says
    that the value MUST be kept, whatever the room (made again it could come
    out another value: a choice from scores).  A name that is one of the
    op's output variables is given by the lowering itself; any other (a kernel's
    residuals) by the op's own lowering, where it finds the name in `ctx.keep`:
    a program that keeps nothing holds no name."""
    try:
        _REGISTRY[type].kept = rule
    except KeyError:
        raise KeyError(f"set_kept({type!r}): op has no registered lowering") from None


def set_step_stats(type: str, slots, publish: StatsFn, attrs=()):
    """Attach statistics of a training step to a registered op: `slots`
    names inputs or outputs of the op whose values `pipeline.train_loop`
    fetches with the step and reads on LOGGED steps only (when the loss is
    read: no sync of their own); `publish(step, values)` then gets {slot:
    [one array per op of this type, in program order]} and sets the op's
    gauges and step records on the monitor.  `attrs` names attributes of the
    op that the statistics are read against: `values` holds them the same
    way, [one value per op that has the attribute]."""
    try:
        _REGISTRY[type].step_stats = (tuple(slots), publish, tuple(attrs))
    except KeyError:
        raise KeyError(
            f"set_step_stats({type!r}): op has no registered lowering"
        ) from None


def suggest_ops(type: str, n: int = 3) -> List[str]:
    """Nearest-matching registered op types for an unknown-op error."""
    import difflib

    return difflib.get_close_matches(type, sorted(_REGISTRY), n=n)


def get_op_def(type: str, op=None, block=None) -> OpDef:
    """Look up an op's definition.  On a miss, the error names the op's
    block context (when given) and suggests nearest-matching registered
    types instead of dumping the whole registry."""
    try:
        return _REGISTRY[type]
    except KeyError:
        close = suggest_ops(type)
        hint = (f"; did you mean: {', '.join(close)}?" if close
                else "; see paddle_tpu.core.registry.registered_ops() for "
                     "the full list")
        where = ""
        if block is not None:
            idx = None
            if op is not None:
                try:
                    idx = block.ops.index(op)
                except ValueError:
                    idx = None
            where = (f" (block {block.idx}"
                     + (f", op #{idx}" if idx is not None else "")
                     + ")")
        raise NotImplementedError(
            f"op {type!r}{where} has no registered lowering{hint} "
            f"({len(_REGISTRY)} ops registered)"
        ) from None


def get_op_def_or_none(type: str) -> Optional[OpDef]:
    return _REGISTRY.get(type)


def has_op(type: str) -> bool:
    return type in _REGISTRY


def registered_ops():
    return sorted(_REGISTRY)


def infer_and_check(op, block):
    """Run build-time shape/dtype inference if the op registered one.

    Mirrors the reference's compile-time InferShape (shape_inference.h); ops
    the framework appends (feed/fetch/backward) are exempt.  Failures are
    classified `ShapeInferenceError`s (core/analysis.py) so `append_op`
    raises with op/var/block provenance instead of the program dying later
    inside JAX tracing."""
    d = _REGISTRY.get(op.type)
    if d is None or d.infer is None:
        return
    from ..flags import flag as _flag

    if _flag("FLAGS_verify_program") in ("", "off"):
        return  # 'off' trusts the builder: the escape hatch for a program
        # an (over-strict or wrong) infer rule would reject at build time
    from ..monitor import MONITOR as _MON
    from .analysis import ShapeInferenceError, StaticAnalysisError, _op_index

    try:
        d.infer(op, block)
        _MON.counter("analysis.infer_checks").inc()
    except StaticAnalysisError:
        _MON.counter("analysis.infer_failures").inc()
        raise
    except Exception as e:
        _MON.counter("analysis.infer_failures").inc()
        raise ShapeInferenceError(
            f"shape/dtype inference crashed for op #{_op_index(block, op)} "
            f"({op.type!r}) in block {block.idx}: {e!r}"
        ) from e
