"""The least time the chip could take for the attention products of the steps
in the trace, over the device time of the block-sparse kernel's own
instructions.  The least time is max(operations / peak FLOP/s, bytes / peak
HBM B/s) of `attention_flops` and `attention_bytes` in the model's module
(benchmark/models/sdar.py: the two products forward and the four backward over
the (query, key) pairs the mask ALLOWS, nothing for a masked pair computed
anyway and nothing for the scores the backward kernels compute again), so
blocks the kernel skips raise the share and masked pairs it computes lower it.
The instructions are those the lowering put under its `block_sparse_attention`
scope inside `fused_attention` (ops/masked_attention.py; backward the same
under `transpose(`), found by name in `compiled.as_text()`: the three kernel
calls and the queries' scaling, not the projections round them.  Nothing where
the program has no such scope or the model no such function.

The stock splash kernel's calls carry a frontend attribute that holds
newlines, so in the compiled text such an instruction runs over three lines
and its `op_name` stands on the last: `instructions_under` reads an
instruction's text up to the next instruction's first line, where
`trace_reduce._HLO_LINE` reads one line (PERF.md, section 7)."""
import re

from benchmark import program_trace

LAYER = 'ops: kernels (ops/*.py)'
UNIT = '%'
BETTER = 'higher'
SOURCE = 'device_trace'
MOVES = 'train_samples_per_s'

SCOPE = "/block_sparse_attention/"
_FIRST_LINE = re.compile(r'^\s*(?:ROOT )?%(\S+) = ')
_OP_NAME = re.compile(r'op_name="([^"]*)"')


def instructions_under(text: str, scope=SCOPE) -> set:
    """Names of the instructions of a compiled program's text whose `op_name`
    passes through `scope` (a string, or a compiled pattern to search for)."""
    found, name = set(), None
    for line in text.splitlines():
        first = _FIRST_LINE.match(line)
        if first:
            name = first.group(1)
        for op_name in _OP_NAME.findall(line) if name else ():
            if (scope in op_name) if isinstance(scope, str) else scope.search(op_name):
                found.add(name)
    return found


def least_seconds(flops: float, bytes_: float, peaks: dict) -> float:
    return max(flops / peaks["bf16_flops_per_s"], bytes_ / peaks["hbm_bytes_per_s"])


def seconds_per_run(planes, instructions: set):
    """Device seconds a run of the main module spends in `instructions`
    inside the traced window, the median device; None without them."""
    found = program_trace.phase_ms_per_step(planes, dict.fromkeys(instructions, "fwd"))
    return found["fwd"] / 1e3 if found and found["fwd"] else None


def seconds_under(ctx: dict, scope):
    """`seconds_per_run` of the instructions under `scope` in the run's
    executables; None without executables, a trace or such instructions."""
    if not ctx.get("executables"):
        return None
    planes = program_trace.traced_planes(ctx)
    if not planes:
        return None
    instructions = set()
    for e in ctx["executables"]:
        instructions |= instructions_under(e.as_text(), scope)
    return seconds_per_run(planes, instructions)


def read(ctx: dict):
    model = ctx.get("model")
    if not hasattr(model, "attention_flops"):
        return None
    spent = seconds_under(ctx, SCOPE)
    if not spent:
        return None
    cfg, job = ctx["config"], ctx["traffic"]
    least = least_seconds(model.attention_flops(cfg, job), model.attention_bytes(cfg, job), ctx["peaks"])
    return 100.0 * least / spent
