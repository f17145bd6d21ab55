"""Block -> JAX lowering.

This replaces the reference's per-op interpreter hot loop
(`framework/executor.cc:416-421`: `for op in ctx->ops_: op->Run(...)`).
Instead of running kernels, `run_ops` symbolically interprets the op list
once inside a jax trace, producing a single XLA computation per block —
the seam SURVEY.md identifies at `executor.cc:337` (nGraph subgraph engine)
taken to its limit: the *whole* block is the subgraph.

The `backward` op (emitted by core/autodiff.py) splits the op list into a
forward segment and an update segment; gradients are obtained with `jax.vjp`
over the re-interpreted forward segment, so XLA sees forward+backward+update
as one program.  The gradients themselves are a fusion boundary
(`fence_grads`): the update reads them materialised.
"""
from __future__ import annotations

import contextlib
import re
import threading
import time
from typing import Any, Dict, List, NamedTuple, Optional, Sequence, Set

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from ..monitor import MONITOR as _MON, NULL_SPAN
from .program import Block, Operator
from .registry import get_op_def, get_op_def_or_none


class LoweringContext:
    """Per-trace state: RNG threading, train/eval mode, mesh info.

    JAX PRNG is explicit; the reference's global curand state maps to a key
    threaded through the trace.  Each RNG-consuming op calls `next_key()`.
    The final key is returned from the compiled function and stored back in
    the scope, so randomness advances across `Executor.run` calls.
    """

    def __init__(self, key, is_test: bool = False, mesh=None, platform: Optional[str] = None,
                 batch_axis: Optional[str] = None):
        self.key = key
        self.is_test = is_test
        self.mesh = mesh
        # the mesh axis the executor splits the feeds' rows over (None off a mesh): a lowering whose kernel GSPMD
        # cannot partition asks `ops.common.batch_shards` whether its operands are split over it alone
        self.batch_axis = batch_axis if mesh is not None else None
        # target backend ("tpu"/"cpu"); lowerings that have a Pallas TPU
        # kernel (fused_attention) pick it here and fall back to plain jnp
        # math elsewhere so CPU tests and virtual meshes still run
        self.platform = platform
        # current var env, set by run_ops; control-flow lowerings read it to
        # capture outer values and compute loop-carried state
        self.env: Dict[str, Any] = {}
        # set by run_block_with_backward while sparse-grad taps are active
        self.sparse_taps = None
        # backward-overlapped dp gradient all-reduce: when the executor runs
        # the step inside a manual (shard_map) dp region, this holds the
        # bucketed-psum callable from parallel.distributed.make_grad_sync;
        # _run_one_backward_region applies it to the assembled grads so the
        # optimizer segment consumes globally-reduced gradients
        self.grad_sync = None
        # fetch targets of the step being traced (set by the executor): a
        # lowering that fuses a chain of ops (ops/latent_operands.py: plan)
        # leaves a chain alone whose inner values are fetched
        self.fetch_names = ()
        # BuildStrategy.memory_optimize: rematerialize the forward during
        # backward (jax.checkpoint) instead of keeping activations
        self.remat = False
        # `plan_kept`'s choice: a `recompute_scope` segment's number -> the names of the values that segment keeps
        # for backward instead of making them again (none: the segment keeps what it reads and nothing it makes) ...
        self.kept_by_segment: Dict[int, Set[str]] = {}
        # ... and those of the segment being lowered (`_run_recomputed`): a lowering whose kernel has residuals that
        # only its forward makes gives them their name (`registry.set_kept`) where it is in here
        self.keep: Set[str] = set()
        # `plan_latent_operands`' finding: the `id` of every op of a latent attention's chain, and of the attention,
        # -> the unit they are lowered as (`ops/latent_operands.py`); `run_ops` lowers the unit where it meets the
        # attention and none of the chain's ops on its own
        self.latent_units: Dict[int, Any] = {}

    def next_key(self):
        self.key, sub = jax.random.split(self.key)
        return sub


# Ops handled by the executor itself, not by a registered lowering.
_STRUCTURAL_OPS = ("feed", "fetch", "backward")

#: The rows of a profile's table that leave the trace by name (`TraceProfile.by_op`); the rest are summed as `other`.
BY_OP_ROWS = 12
#: What a run of ops that `recompute_scope` marked as one segment is called in the table: the `jax.checkpoint` round
#: them (JAX's second pass over the segment's jaxpr), less the ops lowered inside it.
SEGMENT = "recompute_scope"

_TRACING = threading.local()    # .profile: the TraceProfile of the trace this thread is making (`profiled`)
_UNTIMED = contextlib.nullcontext()


class TraceProfile:
    """Where the Python of ONE trace went, while the monitor is on: the phases
    of the trace as spans under the executor's `executor.lower` (`phase`), and
    inside them the seconds and calls of every op's lowering by `(phase, op
    type)` (`timed`).  A row holds SELF time: `run_ops` nests (a `repeat`'s
    body, a recomputed segment, a `conditional_block`), and an op's seconds are
    its own, less those of the ops lowered inside it, so a phase's rows never
    add up to more than its span.  The program's own `jax.custom_vjp` rules are
    timed the same way under their op's type (`ops.common.counted_rules`): JAX
    calls a backward rule during `lowering.transpose`, and a forward rule again
    wherever it differentiates a recomputed segment.  What is left of a phase
    is JAX's own (linearisation, `backward_pass`): its span's `ops_s` says how
    much the rows hold."""

    __slots__ = ("what", "rows", "phases", "inside")

    def __init__(self, **what):
        self.what = what                        # `program=` and `module=`: every phase span carries its parent's
        self.rows: Dict[tuple, list] = {}       # (phase, op type) -> [self seconds, calls]
        self.phases: List[str] = []             # the open phase spans, innermost last
        self.inside: List[float] = []           # per open timed call, the seconds of the timed calls inside it

    @contextlib.contextmanager
    def timed(self, op_type: str):
        row = self.rows.setdefault((self.phases[-1] if self.phases else "", op_type), [0.0, 0])
        self.inside.append(0.0)
        t0 = time.perf_counter()
        try:
            yield
        finally:
            took = time.perf_counter() - t0
            row[0] += took - self.inside.pop()
            row[1] += 1
            if self.inside:
                self.inside[-1] += took

    def seconds_in(self, phase_name: str) -> float:
        return sum(row[0] for (at, _), row in self.rows.items() if at == phase_name)

    def by_op(self) -> Dict[str, list]:
        """The table as it leaves the trace: {"<phase>:<op type>": [self
        seconds, calls]} of the `BY_OP_ROWS` dearest rows, and `other`."""
        dearest = sorted(self.rows.items(), key=lambda kv: -kv[1][0])
        table = {f"{at}:{op_type}": list(row) for (at, op_type), row in dearest[:BY_OP_ROWS]}
        rest = [row for _, row in dearest[BY_OP_ROWS:]]
        table["other"] = [sum(row[0] for row in rest), sum(row[1] for row in rest)]
        return table


def open_profile() -> Optional[TraceProfile]:
    """The profile of the trace this thread is making, or None."""
    return getattr(_TRACING, "profile", None)


@contextlib.contextmanager
def profiled(**what):
    """Round one trace (the executor's miss path, inside its `executor.lower`
    span): a `TraceProfile` for this thread while the monitor is on, else None:
    no table is built and `phase` opens nothing."""
    if not _MON.enabled:
        yield None
        return
    profile = _TRACING.profile = TraceProfile(**what)
    try:
        yield profile
    finally:
        _TRACING.profile = None


@contextlib.contextmanager
def phase(name: str):
    """One phase of the trace being profiled, as the span `lowering.<name>`
    with the profile's `program=` and `module=`; the ops timed while it is the
    innermost open phase are its rows, and their seconds its `ops_s`."""
    profile = open_profile()
    if profile is None:
        yield NULL_SPAN
        return
    with _MON.span("lowering." + name, **profile.what) as span:
        held = profile.seconds_in(name)
        profile.phases.append(name)
        try:
            yield span
        finally:
            profile.phases.pop()
            span.annotate(ops_s=profile.seconds_in(name) - held)


def jaxpr_size(jaxpr) -> Dict[str, int]:
    """`jaxpr_eqns` and `pallas_calls` of a traced jaxpr by one walk, the
    sub-jaxprs in its equations' parameters included once a call site (a
    kernel's body, a loop's, a `jit`'s): what `lowering.to_hlo` goes over, and
    the two numbers behind "a Pallas kernel's set-up is its body's size times
    its call sites"."""
    from jax.extend.core import ClosedJaxpr, Jaxpr

    sizes: Dict[int, tuple] = {}

    def size(j) -> tuple:
        j = getattr(j, "jaxpr", j)
        if id(j) not in sizes:
            eqns, pallas = len(j.eqns), sum(eqn.primitive.name == "pallas_call" for eqn in j.eqns)
            for eqn in j.eqns:
                for value in eqn.params.values():
                    for sub in value if isinstance(value, (tuple, list)) else (value,):
                        if isinstance(sub, (Jaxpr, ClosedJaxpr)):
                            inner = size(sub)
                            eqns, pallas = eqns + inner[0], pallas + inner[1]
            sizes[id(j)] = (eqns, pallas)
        return sizes[id(j)]

    eqns, pallas = size(jaxpr)
    return {"jaxpr_eqns": eqns, "pallas_calls": pallas}


def run_ops(ctx: LoweringContext, ops: List[Operator], env: Dict[str, Any],
            first: int = 0, segments: bool = True) -> Dict[str, Any]:
    """Interpret `ops` over `env` (var name -> traced jax value), in order.

    Op-level provenance (ISSUE 8): each op's emission is wrapped in
    `jax.named_scope("op<idx>:<type>")`, so XLA op metadata — and with it
    device profiles, HLO dumps, and the merged gang traces — maps every
    fused region back to the ProgramDesc op(s) that produced it.  `first`
    is the index of `ops[0]` among the interpreted ops of its block, so an
    index names ONE op of the block: the tail after `backward` continues
    the forward's numbering.  Pure trace-time cost: the scope name lands
    in the jaxpr/HLO, nothing runs per step.

    A run of ops that `recompute_scope` marked as one segment is lowered as one
    `jax.checkpoint` (`_run_recomputed`, which calls back with `segments`
    off and `ctx.keep` set: an output variable that the segment's policy saves
    is given its own name as it is made).

    An op of a latent attention's chain (`ctx.latent_units`) is passed over and
    lowered with its attention, as one unit whose passes stand under the
    scopes of the ops they stand for (`ops/latent_operands.py: lower`)."""
    # the op census runs at TRACE time only (this loop is the trace), so
    # it costs nothing at execution
    mon_on = _MON.enabled
    profile = open_profile() if mon_on else None
    segment_end = 0
    met: Dict[int, int] = {}    # the index of each op of a unit's chain, by the op's `id`
    for at, op in enumerate(ops):
        idx = first + at
        if at < segment_end:
            continue    # an op of a recomputed segment, lowered with the segment's first
        segment = op.attrs.get("recompute_segment") if segments else None
        if segment is not None:
            segment_end = at + 1
            while segment_end < len(ops) and ops[segment_end].attrs.get("recompute_segment") == segment:
                segment_end += 1
            with profile.timed(SEGMENT) if profile is not None else _UNTIMED:
                _run_recomputed(ctx, ops[at:segment_end], env, idx)
            continue
        if op.type in _STRUCTURAL_OPS:
            raise RuntimeError(
                f"structural op {op.type!r} reached the lowering interpreter; "
                "the executor must handle it"
            )
        unit = ctx.latent_units.get(id(op)) if ctx.latent_units else None
        if unit is not None:
            met[id(op)] = idx
            if op is not unit.attention:
                continue
            from ..ops.latent_operands import lower as lower_unit

            with profile.timed("latent_operands") if profile is not None else _UNTIMED:
                lower_unit(ctx, unit, env, lambda o: "/".join(
                    filter(None, (o.attrs.get("op_namescope"), f"op{met[id(o)]}:{o.type}"))))
        else:
            # an op built under `fluid.name_scope` carries the path: its scope
            # stands round the op's own, so the innermost name is still the op
            part = op.attrs.get("op_namescope")
            with jax.named_scope(part) if part else contextlib.nullcontext():
                with jax.named_scope(f"op{idx}:{op.type}"), profile.timed(op.type) if profile is not None else _UNTIMED:
                    lower_one(ctx, op, env)
        if ctx.keep:
            for n in ctx.keep.intersection(op.output_arg_names):
                env[n] = checkpoint_name(env[n], n)
        if mon_on:
            _MON.counter("lowering.ops_total").inc()
    return env


def _run_recomputed(ctx: LoweringContext, ops: List[Operator], env: Dict[str, Any], first: int) -> None:
    """The ops of one `recompute_scope` as a `jax.checkpoint`: where the trace
    is differentiated, backward keeps what the segment reads from `env`, the
    RNG key and the values `plan_kept` chose for this segment
    (`ctx.kept_by_segment`: products' outputs, kernels' residuals, as far as
    the chip has room), and runs the other ops again (the key rides through, so
    a random op draws the same numbers the second time).  With nothing chosen
    the checkpoint has no policy: nothing the segment makes is kept.
    Everything the ops write goes back into `env`, as if they had run one by
    one."""
    reads, written = [], set()
    for op in ops:
        reads += [n for n in op.input_arg_names if n not in written and n in env and n not in reads]
        written.update(op.output_arg_names)
    keep = ctx.kept_by_segment.get(ops[0].attrs["recompute_segment"], set())

    def segment(values, key):
        inner = dict(zip(reads, values))
        ctx.key, ctx.keep = key, keep
        try:
            run_ops(ctx, ops, inner, first, segments=False)
        finally:
            ctx.keep = set()
        return {n: inner[n] for n in sorted(written) if n in inner}, ctx.key

    policy = jax.checkpoint_policies.save_only_these_names(*sorted(keep)) if keep else None
    made, ctx.key = jax.checkpoint(segment, policy=policy)([env[n] for n in reads], ctx.key)
    env.update(made)
    _MON.counter("lowering.recomputed_segments").inc()
    if any(op.type == "moe_experts" for op in ops):   # a sparse layer that backward makes again, routing and all
        _MON.counter("lowering.recomputed_sparse_segments").inc()


#: Of the chip's memory that the step's state does not hold, the share that the values kept for backward may take
#: (`plan_kept`); the rest is the room of the step's own temporaries: the gradients, the head's logits, a layer's
#: activations while it is made again.  PERF.md section 6, PR 51, has the two cells' plans behind it.
KEPT_SHARE = 0.5

#: Ops whose transposes read neither operand: a value that reaches nothing in its segment but through them (a layer's
#: last product, which the bias and the residual stream are added to) is read by nothing in that segment's backward.
_SUMS = ("elementwise_add", "elementwise_sub", "sum")


class Kept(NamedTuple):
    """A value a recomputed segment can keep for backward: what a chip pays to
    hold it and what it pays to make it again."""
    segment: int    # the `recompute_scope`'s number
    name: str       # what the lowering calls the value: `jax.checkpoint`'s policy saves by name
    nbytes: int     # a chip's
    flops: float    # of the op that makes it, a chip's
    must: bool = False   # kept whatever the room: made again it could come out another value (a choice from scores)


def choose_kept(candidates: Sequence[Kept], budget: float) -> List[Kept]:
    """The candidates a block keeps, in program order: greedily by the
    operations a kept byte saves, the dearest first and ties in program order,
    each taken if what is left of `budget` bytes holds it; those that MUST be
    kept first and whatever is left.  A pure function: the same candidates and
    budget give the same set."""
    chosen, left = [], budget
    for at in sorted(range(len(candidates)),
                     key=lambda i: (not candidates[i].must, -candidates[i].flops / max(candidates[i].nbytes, 1), i)):
        if candidates[at].must or candidates[at].nbytes <= left:
            chosen.append(at)
            left -= candidates[at].nbytes
    return [candidates[at] for at in sorted(chosen)]


def kept_candidates(ctx: LoweringContext, ops: List[Operator], shapes) -> List[Kept]:
    """What the recomputed segments among `ops` could keep, in program order:
    for every op inside a `recompute_scope` whose type has a `registry.set_kept`
    rule (a matrix product's output, a kernel's residuals), the value's bytes
    from the variables' static shapes (`shapes`, a `resource_plan.ShapeEnv`) and
    the operations of the op's cost rule, both divided by the chips the mesh
    splits the batch over.  An output variable that no op of its segment reads
    but through sums (`_SUMS`) is left out: the segment's backward reads it
    nowhere, and what leaves the segment is the next one's to keep."""
    from ..ops.common import batch_shards
    from .resource_plan import op_cost

    shards = max(batch_shards(ctx.mesh, ctx.batch_axis, shapes.batch), 1)
    readers: Dict[tuple, List[Operator]] = {}
    for op in ops:
        for n in op.input_arg_names:
            readers.setdefault((op.attrs.get("recompute_segment"), n), []).append(op)

    def read_in_backward(segment, name):
        return any(op.type not in _SUMS or any(read_in_backward(segment, n) for n in op.output_arg_names)
                   for op in readers.get((segment, name), ()))

    found = []
    for op in ops:
        segment = op.attrs.get("recompute_segment")
        rule = None if segment is None else getattr(get_op_def_or_none(op.type), "kept", None)
        value = rule(ctx, op, shapes) if rule is not None else None
        if value is not None and (value[0] not in op.output_arg_names or read_in_backward(segment, value[0])):
            found.append(Kept(segment, value[0], int(value[1]) // shards, op_cost(op, op.block, shapes)[0] / shards,
                              len(value) > 2 and bool(value[2])))
    return found


def plan_kept(ctx: LoweringContext, ops: List[Operator], feed_shapes: Dict[str, tuple], held_bytes: int) -> None:
    """Choose, once a trace and before any op of it is lowered, what the
    block's `recompute_scope` segments keep for backward
    (`ctx.kept_by_segment`), from what can be observed: the candidates and
    their prices from the program's shapes (`kept_candidates`), the budget from
    the device: its memory (`memory_stats()["bytes_limit"]`, else the chip
    model's `resource_plan.CHIP_HBM_BYTES`) less `held_bytes`, the state a chip
    holds for the step, times `KEPT_SHARE`.  A block with no `backward` op (a
    `for_test` clone) or no segment chooses nothing; a chip that is full keeps
    nothing and the program is the plain `jax.checkpoint`'s.  Counted:
    `lowering.recomputed_kept_values`, `_kept_bytes` (a chip's) and, of all the
    candidates, `_candidates_bytes`."""
    ctx.kept_by_segment = {}
    if not (any(op.type == "backward" for op in ops) and any("recompute_segment" in op.attrs for op in ops)):
        return
    from ..monitor.memstats import device_bytes_limit
    from .resource_plan import CHIP_HBM_BYTES, ShapeEnv

    candidates = kept_candidates(ctx, ops, ShapeEnv(ops[0].block.program, feed_shapes))
    limit = device_bytes_limit()
    budget = KEPT_SHARE * max((CHIP_HBM_BYTES if limit is None else limit) - held_bytes, 0)
    chosen = choose_kept(candidates, budget)
    for value in chosen:
        ctx.kept_by_segment.setdefault(value.segment, set()).add(value.name)
    _MON.counter("lowering.recomputed_kept_values").inc(len(chosen))
    _MON.counter("lowering.recomputed_kept_bytes").inc(sum(value.nbytes for value in chosen))
    _MON.counter("lowering.recomputed_candidates_bytes").inc(sum(value.nbytes for value in candidates))


def count_layer_forms(ops: List[Operator]) -> None:
    """Counted once a trace of a block with a backward pass, beside
    `plan_kept`'s counters and from the program's own ops:
    `lowering.routers_before_attention`, the `moe_router` ops between which and
    the `moe_experts` that reads their choice a `fused_attention` stands (a
    router that reads its layer's input ahead of the attention:
    `layers.moe(router_input=)`), and `lowering.attention_layers_without_positions`,
    the `fused_attention` ops whose queries no `rotary_embedding` reaches
    between their projection and the attention;
    `lowering.gated_attention_layers`, the attention layers whose output passes a
    sigmoid gate a head (`multi_head_attention(head_gate=)`: the `sigmoid` ops in
    a scope `attention_gate`); `lowering.rotary_tables`, the DISTINCT rotary
    descriptions among the `rotary_embedding` ops (theta, how much of a head
    turns, the pairing, a table of frequencies, a factor: 2 where stretched
    half-rotary layers stand beside plain ones); and
    `lowering.query_heads_by_layer.<i>`, the query heads of the i-th
    `fused_attention` op, a counter a layer so that the list can be read; and
    `lowering.scalar_decay_scans`, the `kda` ops whose log decay `G` is ONE
    number a head a token ([b, T, H]: Gated DeltaNet) and not one a channel."""
    if not any(op.type == "backward" for op in ops):
        return
    made_by = {name: op for op in ops for name in op.output_arg_names}

    def rotated(name, depth=3):   # a rotation between this value and the product that projected it
        op = made_by.get(name)
        if op is None or op.type in ("mul", "matmul") or not depth:
            return False
        return op.type == "rotary_embedding" or any(rotated(n, depth - 1) for n in op.input_arg_names)

    chosen_at = {op.output("TopKIndex")[0]: i for i, op in enumerate(ops) if op.type == "moe_router"}
    attentions = [i for i, op in enumerate(ops) if op.type == "fused_attention"]
    ahead = sum(any(chosen_at[op.input("TopKIndex")[0]] < a < i for a in attentions)
                for i, op in enumerate(ops) if op.type == "moe_experts" and op.input("TopKIndex")[0] in chosen_at)
    _MON.counter("lowering.routers_before_attention").inc(ahead)
    _MON.counter("lowering.attention_layers_without_positions").inc(
        sum(not rotated(ops[a].input("Q")[0]) for a in attentions))
    _MON.counter("lowering.gated_attention_layers").inc(
        sum(op.type == "sigmoid" and bool(re.search(r"(^|/)attention_gate(_\d+)?(/|$)", op.attrs.get("op_namescope") or ""))
            for op in ops))
    _MON.counter("lowering.scalar_decay_scans").inc(
        sum(op.type == "kda" and len(op.block.var(op.input("G")[0]).shape) == 3 for op in ops))
    _MON.counter("lowering.rotary_tables").inc(len({
        tuple(op.attr(n, None) for n in ("theta", "interleave", "rotary_dim", "inv_freq", "scale"))
        for op in ops if op.type == "rotary_embedding"}))
    for layer, a in enumerate(attentions):
        queries = ops[a].block.var(ops[a].input("Q")[0]).shape
        _MON.counter(f"lowering.query_heads_by_layer.{layer}").inc(
            int(queries[2 if ops[a].attr("layout", "bhld") == "blhd" else 1]))


def plan_latent_operands(ctx: LoweringContext, ops: List[Operator]) -> None:
    """Choose, once a trace and after `plan_kept`, which latent attentions
    among `ops` are lowered with the chain of ops between their projections and
    their kernels as one unit (`ctx.latent_units`; `ops/latent_operands.py` has
    the rule and the counters).  A block without a `fused_attention` whose
    values are narrower than its queries chooses nothing."""
    from ..ops.latent_operands import plan

    plan(ctx, ops)


def lower_one(ctx: LoweringContext, op: Operator, env: Dict[str, Any]) -> None:
    opdef = get_op_def(op.type, op=op, block=op.block)
    ins = {}
    for slot, names in op.inputs.items():
        vals = []
        for n in names:
            if n not in env:
                raise KeyError(
                    f"op {op.type!r} reads {n!r} which is not defined; "
                    "feed it, initialize it via the startup program, or check op order"
                )
            vals.append(env[n])
        ins[slot] = vals
    ctx.env = env
    outs = opdef.lower(ctx, op, ins)
    if "__env_update__" in outs:  # control-flow ops write vars wholesale
        env.update(outs.pop("__env_update__"))
    for slot, names in op.outputs.items():
        vals = outs.get(slot)
        if vals is None:
            continue
        if not isinstance(vals, (list, tuple)):
            vals = [vals]
        if len(vals) != len(names):
            raise RuntimeError(
                f"op {op.type!r} slot {slot!r}: lowering returned {len(vals)} "
                f"values for {len(names)} outputs"
            )
        for n, v in zip(names, vals):
            env[n] = v


# Trace-time report of the last lowered backward (inspection/test surface;
# static facts only — which params took the SelectedRows path).
LAST_TRACE_REPORT: Dict[str, Any] = {}


class SparseTapCollector:
    """Collects is_sparse lookup_table 'taps' so embedding-table gradients
    come out as SelectedRows instead of dense V×D arrays.

    Phase "record": the forward is abstractly evaluated (jax.eval_shape) and
    each sparse lookup registers (w_name, ids_name, out_shape/dtype).
    Phase "inject": the real vjp'd forward adds a zero `delta` to each
    tapped lookup output (before padding_idx masking); d(loss)/d(delta) is
    exactly the per-row gradient slab, and the ids come out of the aux env
    by var name — no dense table-shaped cotangent ever exists.
    """

    def __init__(self, params):
        self.params = set(params)
        self.taps: list = []  # (w_name, ids_name, shape, dtype)
        self.mode = "record"
        self.deltas: Optional[list] = None
        self.i = 0

    def tap(self, w_name: str, ids_name: str, out):
        if w_name not in self.params:
            return out
        if self.mode == "record":
            self.taps.append((w_name, ids_name, out.shape, out.dtype))
            return out
        d = self.deltas[self.i]
        self.i += 1
        return out + d


def run_block_with_backward(ctx: LoweringContext, ops: List[Operator], env: Dict[str, Any]) -> Dict[str, Any]:
    """Interpret a block that may contain `backward` ops.

    Forward ops re-run inside jax.vjp so forward+backward fuse into one XLA
    program; the aux env carries every forward intermediate out of the vjp
    (XLA keeps only what is actually used downstream).

    Multiple backward regions (calc_gradient + minimize in one program) are
    supported: each region differentiates the full op prefix before it —
    values produced by EARLIER regions (e.g. their grads) enter later
    regions as constants (stop-gradient), matching the reference's
    grad-of-grad-free semantics.  XLA CSEs the re-interpreted prefixes.

    The gradients a region hands on are a fusion boundary (`fence_grads`):
    forward and backward fuse as XLA likes, the ops after a `backward`
    (optimizer, clipping, regularisation, a fetch) read gradients that exist
    in memory.  Every block with a `backward` op gets the boundary, on one
    chip and on a mesh, whatever the optimizer: it is a property of the
    `Program` and of nothing else.

    The trace says where its seconds go (`TraceProfile`): the probe for the
    sparse tables, the forward with its linearisation, the transpose and the
    tail are the spans `lowering.sparse_probe`, `.forward`, `.transpose` and
    `.update`, once a region, under the executor's `lowering.trace`.

    The step says which phase an instruction belongs to: the forward
    interpretation runs under `jax.named_scope("fwd")`, the tail after the
    last `backward` under `"update"`, and the transposes JAX derives from
    `fwd` are the backward phase (their `op_name` holds `transpose(`).  A
    program without a `backward` op carries `fwd` only.
    """
    splits = [i for i, op in enumerate(ops) if op.type == "backward"]
    if not splits:
        with phase("forward"), jax.named_scope("fwd"):
            return run_ops(ctx, ops, env)

    report_sparse: List[str] = []
    # every region re-interprets its op prefix FROM THE BLOCK-START env
    # (so stateful-name ops apply exactly once no matter how many regions
    # re-trace them), with earlier regions' grads injected as constants;
    # the RNG stream is pinned so dropout masks etc. are IDENTICAL across
    # regions — all grads describe one forward pass
    key0 = ctx.key
    start_env = dict(env)
    grads_so_far: Dict[str, Any] = {}
    for si in splits:
        ctx.key = key0
        env = _run_one_backward_region(ctx, ops, si, start_env, grads_so_far,
                                       report_sparse)
    LAST_TRACE_REPORT.clear()
    LAST_TRACE_REPORT["sparse_grad_params"] = report_sparse
    tail_ops = ops[splits[-1] + 1:]
    with phase("update"), jax.named_scope("update"):
        return run_ops(ctx, tail_ops, env,
                       first=splits[-1] + 1 - len(splits))


def _run_one_backward_region(ctx: LoweringContext, ops: List[Operator], split: int,
                             start_env: Dict[str, Any], grads_so_far: Dict[str, Any],
                             report_sparse: List[str]) -> Dict[str, Any]:
    bw = ops[split]
    loss_name = bw.attrs["loss_name"]
    param_names: List[str] = list(bw.attrs["param_names"])
    grad_names: List[str] = list(bw.attrs["grad_names"])
    fwd_ops = [o for o in ops[:split] if o.type != "backward"]

    base_env = dict(start_env)
    base_env.update(grads_so_far)
    env = base_env

    for p in param_names:
        if p not in env:
            raise KeyError(f"backward: parameter {p!r} not initialized (run the startup program)")

    sparse_names = [n for n in bw.attrs.get("sparse_param_names", []) if n in param_names]
    dense_names = [p for p in param_names if p not in sparse_names]
    report_sparse.extend(n for n in sparse_names if n not in report_sparse)

    coll = None
    if sparse_names:
        # Phase "record": abstract-eval the forward to enumerate sparse taps
        # (cheap — no compute, no compile).  RNG key is saved/restored so the
        # probe doesn't advance the real stream.
        coll = SparseTapCollector(sparse_names)
        ctx.sparse_taps = coll
        saved_key = ctx.key

        def probe(params):
            e = dict(base_env)
            e.update(params)
            run_ops(ctx, fwd_ops, e)
            return 0

        with phase("sparse_probe"):
            jax.eval_shape(probe, {p: env[p] for p in param_names})
        ctx.key = saved_key
        coll.mode = "inject"

    def fwd(params: Dict[str, Any], deltas: Dict[str, Any]):
        if coll is not None:
            coll.deltas = [deltas[f"__tap{i}"] for i in range(len(coll.taps))]
            coll.i = 0
        e = dict(base_env)
        e.update(params)
        with jax.named_scope("fwd"):
            e = run_ops(ctx, fwd_ops, e)
        loss = e[loss_name]
        return loss, e

    primal_params = {p: env[p] for p in dense_names}
    deltas0 = {}
    if coll is not None:
        for i, (_, _, shape, dtype) in enumerate(coll.taps):
            deltas0[f"__tap{i}"] = jnp.zeros(shape, dtype)

    fwd_fn = jax.checkpoint(fwd) if ctx.remat else fwd
    with phase("forward"):      # the forward's interpretation and JAX's linearisation of it
        loss, vjp_fn, env_after = jax.vjp(fwd_fn, primal_params, deltas0, has_aux=True)
    with phase("transpose"):    # JAX's `backward_pass`, which calls the program's own `custom_vjp` backward rules
        (grads, dtaps) = vjp_fn(jnp.ones_like(loss))

    # merge the region's fresh intermediates over the incoming env so
    # earlier regions' grads survive for downstream consumers
    env = dict(env)
    env.update(env_after)
    ctx.sparse_taps = None
    named = []
    for p, g in zip(param_names, grad_names):
        if p in sparse_names:
            gval = _gather_sparse_grad(p, coll, dtaps, env)
        else:
            gval = grads[p]
            if gval is None:  # non-float param leaked in; treat as zero
                gval = jnp.zeros_like(env[p])
        named.append((g, gval))
    if ctx.grad_sync is not None:
        synced = ctx.grad_sync(named)
        named = [(g, synced.get(g, v)) for g, v in named]
    named = fence_grads(named)
    for g, gval in named:
        env[g] = gval
        grads_so_far[g] = gval
    return env


def fence_grads(named: List[tuple]) -> List[tuple]:
    """Make each gradient of a `backward` region a fusion boundary: it (or the
    `rows` and `values` of a SelectedRows) passes through an
    `optimization_barrier` of its own, so the ops after the region read it
    from memory and XLA cannot pull them into the GEMM or convolution that
    produced it.  The lowered arithmetic, its dtypes and its order are untouched;
    the bits a backend makes of it are not promised (a GEMM tiled another way
    sums in another order, a CPU fusion contracts another multiply-add:
    PERF.md, PR 25, "The same bits?").

    Why (TPU v5e, BERT-base, 256 x 128 tokens; PERF.md, PR 25): without it XLA
    fuses each parameter's Adam update, three f32 outputs, into the output of
    its weight-gradient GEMM and wrecks the GEMM's tiling: backward 174.2 ms
    against 142.7 ms, 1001 against 1133 samples/s.  One barrier per gradient,
    not one over the region: that keeps every f32 gradient alive until the last
    is made and read 1094 samples/s."""
    _MON.counter("lowering.fenced_grads").inc(len(named))
    return [(g, jax.lax.optimization_barrier(v)) for g, v in named]


def _gather_sparse_grad(param: str, coll: "SparseTapCollector", dtaps: Dict[str, Any], env: Dict[str, Any]):
    """Assemble a SelectedRows grad for `param` from its lookup taps: rows
    are the (traced) ids read from the aux env, values the delta-cotangents.
    Multiple lookups of one table concatenate (duplicates are legal and
    merged by the optimizer's MergeAdd)."""
    from ..ops.common import flatten_lookup_ids
    from .selected_rows import SelectedRows

    height = env[param].shape[0]
    dim = env[param].shape[1] if len(env[param].shape) > 1 else 1
    rows_parts = []
    vals_parts = []
    for i, (w_name, ids_name, _, _) in enumerate(coll.taps):
        if w_name != param:
            continue
        flat = flatten_lookup_ids(env[ids_name])
        rows_parts.append(flat.reshape(-1).astype(jnp.int32))
        vals_parts.append(dtaps[f"__tap{i}"].reshape(-1, dim))
    if not rows_parts:
        # table never actually looked up in the pruned program: empty slab
        return SelectedRows(
            jnp.zeros((0,), jnp.int32),
            jnp.zeros((0, dim), env[param].dtype),
            height,
        )
    return SelectedRows(
        jnp.concatenate(rows_parts), jnp.concatenate(vals_parts), height
    )
