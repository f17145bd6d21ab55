"""GB a chip moves through collectives in one run of the step, as the PROGRAM
counts them: the `bytes` of the `kind="collectives"` step record that
`paddle_tpu/parallel/collectives.py` writes when a mesh program's step is
compiled with the monitor on (one walk over the compiled module's text: every
all-reduce, all-gather, reduce-scatter, all-to-all and permute of the step,
the TPU's fused ones included, each counted once with the whole array as one
chip holds it after a gather or before a reduce: the record's one rule).  The
record is joined to the run by `module=`, the step's module as the trace's
`XLA Modules` line names it (`program=` comes back from a profiler trace as a
NUMBER when its eight characters are all digits: docs/observability.md, "One
timeline").

Exact, and the same in every run of one program: a hint lost (a matrix held
whole), a third gather of a matrix, a gradient summed in float32 where bf16
was meant show here before any clock does.  Nothing where the program wrote
no such record (one chip, or a parent without the walk)."""
from benchmark import program_trace

LAYER = 'multi-chip (parallel/*)'
UNIT = 'GB'
BETTER = 'lower'
SOURCE = 'program_counter'
MOVES = 'train_samples_per_s'

RECORD = "collectives"


def step_record(ctx: dict):
    """The program's record of the module that ran most in the traced window
    (`trace_reduce`'s `main_module`, `jit_<module>(<hash>)`); without a device
    trace (a rehearsal on the CPU) of the newest module that trains.  None
    where the program wrote none."""
    records = [r for r in program_trace.program_monitor().step_records() if r.get("kind") == RECORD]
    ran = (ctx.get("trace") or {}).get("main_module")
    if ran:
        mine = [r for r in records if ran.startswith(f"jit_{r['module']}(") or ran == f"jit_{r['module']}"]
    else:
        mine = [r for r in records if r["module"].startswith("train_")]
    return mine[-1] if mine else None


def read(ctx: dict):
    record = step_record(ctx)
    return record["bytes"] / 1e9 if record else None
