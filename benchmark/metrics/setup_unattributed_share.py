"""% of `setup_s` on the caller's thread under no program span and no observed
JAX event, from process start to the window's first step: imports, the runtime
coming up, numpy in the caller.  How far the set-up timeline can be trusted.
With `setup_program_build_s`, `setup_lower_s`, `setup_run_s`,
`setup_foreign_compile_s` and the `executor.compile` seconds it adds up to
`setup_s`.  One partition with its four siblings: `benchmark/setup_timeline.py`."""
from benchmark import setup_timeline

LAYER = 'executor (core/executor.py)'
UNIT = '%'
BETTER = 'lower'
SOURCE = 'program_span'
MOVES = 'setup_s'


def read(ctx: dict):
    return setup_timeline.read_metric(ctx, "unattributed_share")
