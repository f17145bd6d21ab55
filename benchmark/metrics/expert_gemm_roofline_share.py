"""The least time the chip could take for the experts' grouped products of the
steps in the trace, over the device time of the instructions that compute
them.  The least time is max(operations / peak FLOP/s, bytes / peak HBM B/s)
of `expert_gemm_flops` and `expert_gemm_bytes` in the model's module
(benchmark/models/olmoe.py: the three products of each token's 8 experts,
forward and backward, nothing for padding) times the step's runs.  The
instructions are those the lowering put under its `expert_gemm` scope inside
`moe_experts` (forward: `.../expert_gemm/...`; backward: the same under
`transpose(`), found by name in `compiled.as_text()`: the kernel calls, not the
sort, the gathers or the combine round them.  Nothing where the program has no
such scope or the model no such function."""
from benchmark import program_trace
from benchmark import trace_reduce as tr

LAYER = 'ops: kernels (ops/*.py)'
UNIT = '%'
BETTER = 'higher'
SOURCE = 'device_trace'
MOVES = 'train_samples_per_s'

SCOPE = "/expert_gemm/"


def instructions_under(text: str, scope: str = SCOPE) -> set:
    """Names of the instructions of a compiled program's text whose
    `op_name` passes through `scope`."""
    found = set()
    for line in text.splitlines():
        m = tr._HLO_LINE.match(line)
        if m and scope in m.group(2):
            found.add(m.group(1))
    return found


def least_seconds(flops: float, bytes_: float, peaks: dict) -> float:
    return max(flops / peaks["bf16_flops_per_s"], bytes_ / peaks["hbm_bytes_per_s"])


def seconds_per_run(planes, instructions: set):
    """Device seconds a run of the main module spends in `instructions`
    inside the traced window, the median device; None without them.  The
    phase reduction of program_trace.py with one bucket."""
    found = program_trace.phase_ms_per_step(planes, dict.fromkeys(instructions, "fwd"))
    return found["fwd"] / 1e3 if found and found["fwd"] else None


def read(ctx: dict):
    model, cfg, job = ctx["model"], ctx["config"], ctx["traffic"]
    if not ctx["executables"] or not hasattr(model, "expert_gemm_flops"):
        return None
    planes = program_trace.traced_planes(ctx)
    if not planes:
        return None
    instructions = set()
    for e in ctx["executables"]:
        instructions |= instructions_under(e.as_text())
    spent = seconds_per_run(planes, instructions)
    if not spent:
        return None
    tokens = job["batch_per_chip"] * job["seq_len"]
    least = least_seconds(model.expert_gemm_flops(cfg, tokens),
                          model.expert_gemm_bytes(cfg, tokens), ctx["peaks"])
    return 100.0 * least / spent
