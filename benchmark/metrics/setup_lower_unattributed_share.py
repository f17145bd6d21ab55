"""% of the `executor.lower` spans' seconds on the caller's thread, from
process start to the window's first step, that lie in none of the five parts
(`setup_trace_forward_s`, `setup_trace_transpose_s`, `setup_trace_update_s`,
`setup_trace_probe_s`, `setup_to_hlo_s`) nor in a row of the span's `by_op`
table outside the five phases: the step function's own glue, JAX closing the
trace, the walk that counts the jaxpr.  How far the attribution inside
`executor.lower` can be trusted, as `idle_unattributed_share` is for the idle
time.  Prints the cell's programs' merged `by_op` table, self seconds and
calls by (phase, op type), on an `info` line `lowering_profile`.
One reading with its five siblings: `benchmark/lowering_profile.py`."""
from benchmark import lowering_profile

LAYER = 'lowering (core/lowering.py)'
UNIT = '%'
BETTER = 'lower'
SOURCE = 'program_span'
MOVES = 'setup_s'


def read(ctx: dict):
    return lowering_profile.read_metric(ctx, "unattributed_share")
