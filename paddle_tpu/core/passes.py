"""Program-rewrite pass infrastructure.

Reference: framework/ir/ — `ir::Graph` + `Pass` registry + ~60 passes
(fusions, memory opt, multi-device lowering) applied by BuildStrategy.

TPU-first: XLA owns fusion/layout/scheduling, so the reference's kernel-
fusion passes have no residue to produce — the passes that REMAIN useful
are program-level rewrites ahead of lowering that mean the same on every
backend: dead-op pruning, identity elimination, algebraic folds, and
structural rewrites (PipelineOptimizer's stage cut is morally one of
these).  A chain of ops that one kernel should take is fused at LOWERING
time (ops/latent_operands.py: plan), where the fetch targets, the kept
values and the mesh are visible; no pass here rewrites for a kernel.  The
IR the passes walk is the Program itself (op/var lists) — the redesign
collapsed the separate ir::Graph; a pass is any callable Program -> None
mutating in place.
"""
from __future__ import annotations

from typing import Callable, Dict, List, Optional, Sequence

_PASS_REGISTRY: Dict[str, Callable] = {}


def register_pass(name: str):
    def deco(fn):
        _PASS_REGISTRY[name] = fn
        return fn

    return deco


def registered_passes() -> List[str]:
    return sorted(_PASS_REGISTRY)


def _verify_after(program, pass_name: str):
    """Pass-safety harness: under FLAGS_verify_program, re-verify the
    program after a rewrite so a pass bug surfaces as an immediate
    diagnostic naming the offending op/var instead of wrong numerics (or
    an opaque trace error) at lowering time."""
    from ..flags import flag

    level = flag("FLAGS_verify_program")
    if level in ("", "off"):
        return
    from .analysis import SEV_ERROR, PassVerificationError, verify_program

    diags = verify_program(program, level=level)
    errors = [d for d in diags if d.severity == SEV_ERROR]
    if errors:
        raise PassVerificationError(pass_name, errors)


def apply_pass(program, name: str, **kw):
    if name not in _PASS_REGISTRY:
        raise KeyError(f"unknown pass {name!r}; known: {registered_passes()}")
    _PASS_REGISTRY[name](program, **kw)
    _verify_after(program, name)
    return program


class PassBuilder:
    """reference core.PassBuilder (build_strategy._finalize surface): an
    ordered pass pipeline."""

    def __init__(self, passes: Optional[Sequence[str]] = None):
        self._passes: List[str] = list(passes or [])

    def append_pass(self, name: str) -> "PassBuilder":
        if name not in _PASS_REGISTRY:
            raise KeyError(f"unknown pass {name!r}")
        self._passes.append(name)
        return self

    def remove_pass(self, name: str) -> "PassBuilder":
        self._passes.remove(name)
        return self

    def all_passes(self) -> List[str]:
        return list(self._passes)

    def apply(self, program):
        """Apply the pipeline; under FLAGS_verify_program each pass is
        followed by a program verification (see `_verify_after`)."""
        for p in self._passes:
            apply_pass(program, p)
        return program


def _rewire(block, old: str, new: str, start: int):
    """Replace reads of `old` with `new` in ops from index `start` on."""
    for op in block.ops[start:]:
        for slot, names in op.inputs.items():
            op.inputs[slot] = [new if n == old else n for n in names]


@register_pass("remove_identity_ops")
def remove_identity_ops(program, keep=()):
    """Drop `assign` and no-op `scale` (scale=1, bias=0) ops, rewiring
    same-block consumers to the producer (reference: identity-elimination
    portion of the inplace/memory passes).

    `keep`: names that must stay written (fetch targets).  Identities whose
    output is kept, persistable, or read from another block (control-flow
    sub-blocks) are conservatively left in place."""
    keep = set(keep)
    for block, outside in zip(program.blocks, _outside_reads(program)):
        # var -> index of its LAST write (one pass; keeps the hazard check
        # below O(1) per candidate instead of a tail rescan)
        last_write: Dict[str, int] = {}
        for i, op in enumerate(block.ops):
            for out_name in op.output_arg_names:
                last_write[out_name] = i
        kept = []
        for i, op in enumerate(block.ops):
            is_identity = op.type == "assign" or (
                op.type == "scale"
                and op.attrs.get("scale", 1.0) == 1.0
                and op.attrs.get("bias", 0.0) == 0.0
            )
            if not is_identity:
                kept.append(op)
                continue
            src = op.input_arg_names[0]
            dst = op.output_arg_names[0]
            dst_var = block._find_var_recursive(dst)
            if (dst in keep or dst in outside
                    or (dst_var is not None and dst_var.persistable)):
                kept.append(op)  # fetched / captured / state: not removable
                continue
            # snapshot semantics: if any later op WRITES src or dst, the
            # assign is a real copy (t = x; x += 1; use t) — rewiring reads
            # of dst to src would observe the mutation.  Keep it.
            if last_write.get(src, -1) > i or last_write.get(dst, -1) > i:
                kept.append(op)
                continue
            _rewire(block, dst, src, i + 1)
        block.ops = kept
    program._bump()


@register_pass("fold_scale_chains")
def fold_scale_chains(program):
    """Fold consecutive scale ops (y = a2*(a1*x + b1) + b2) into one
    (reference: the algebraic-simplification family of ir passes).  The
    bypassed intermediate op stays in the program (it may feed other
    consumers or fetches); the executor's compile-time prune drops it when
    genuinely dead."""
    for block in program.blocks:
        by_output = {}
        for op in block.ops:
            if op.type == "scale" and op.attrs.get("bias_after_scale", True):
                src = op.input_arg_names[0]
                prev = by_output.get(src)
                if prev is not None and prev.attrs.get("bias_after_scale", True):
                    a1 = prev.attrs.get("scale", 1.0)
                    b1 = prev.attrs.get("bias", 0.0)
                    a2 = op.attrs.get("scale", 1.0)
                    b2 = op.attrs.get("bias", 0.0)
                    op.inputs["X"] = [prev.input_arg_names[0]]
                    op.attrs["scale"] = a1 * a2
                    op.attrs["bias"] = a2 * b1 + b2
                by_output[op.output_arg_names[0]] = op
            # ANY write invalidates cached chains that read or wrote the
            # same name (in-place ops like increment would otherwise be
            # folded across — wrong numerics)
            for out in op.output_arg_names:
                if op.type != "scale" or out != op.output_arg_names[0]:
                    by_output.pop(out, None)
                stale = [k for k, v in by_output.items()
                         if v.input_arg_names[0] == out and v is not op]
                for k in stale:
                    by_output.pop(k)
    program._bump()


def _outside_reads(program):
    """Per-block sets of names read by any op OUTSIDE that block (sub-block
    capture), aligned with program.blocks: one pass over the program instead
    of an O(blocks^2) rescan of every other block's op list per block.
    Read by remove_identity_ops."""
    block_reads = []
    n_blocks_reading: Dict[str, int] = {}
    for b in program.blocks:
        reads = set()
        for op in b.ops:
            reads.update(op.input_arg_names)
        block_reads.append(reads)
        for n in reads:
            n_blocks_reading[n] = n_blocks_reading.get(n, 0) + 1
    return [{n for n, c in n_blocks_reading.items()
             if c > (1 if n in reads else 0)}
            for reads in block_reads]


@register_pass("prune_dead_ops")
def prune_dead_ops(program, targets: Optional[Sequence[str]] = None):
    """Fetch-driven dead-op elimination as a standalone pass (the executor
    runs the same logic per compile; reference: prune in
    save_inference_model io.py:915).  `targets` is REQUIRED — guessing
    live outputs would silently delete independent branches."""
    from .executor import _CompiledStep, _runnable_ops

    if not targets:
        raise ValueError(
            "prune_dead_ops: pass the fetch targets explicitly "
            "(apply_pass(prog, 'prune_dead_ops', targets=[...]))")
    persistable = {v.name for v in program.list_vars() if v.persistable}
    block = program.global_block()
    block.ops = _CompiledStep._prune(_runnable_ops(block), list(targets), persistable)
    program._bump()
