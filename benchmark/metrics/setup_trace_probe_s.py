"""Seconds of set-up inside the lowering's `lowering.sparse_probe` and
`lowering.plan_kept` spans on the caller's thread, from process start to the
window's first step: traces that make no code: the `eval_shape` of the whole
forward that finds an `is_sparse` table's lookups, and the pass over the
program's shapes that chooses what the recomputed segments keep.
One reading with its five siblings: `benchmark/lowering_profile.py`."""
from benchmark import lowering_profile

LAYER = 'lowering (core/lowering.py)'
UNIT = 's'
BETTER = 'lower'
SOURCE = 'program_span'
MOVES = 'setup_s'


def read(ctx: dict):
    return lowering_profile.read_metric(ctx, lowering_profile.PROBE)
