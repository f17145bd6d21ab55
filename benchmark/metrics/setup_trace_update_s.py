"""Seconds of set-up inside the lowering's `lowering.update` spans on the
caller's thread, from process start to the window's first step: the ops after
the last `backward` (the optimizer's, clipping, regularisation), interpreted
op by op.
One reading with its five siblings: `benchmark/lowering_profile.py`."""
from benchmark import lowering_profile

LAYER = 'lowering (core/lowering.py)'
UNIT = 's'
BETTER = 'lower'
SOURCE = 'program_span'
MOVES = 'setup_s'


def read(ctx: dict):
    return lowering_profile.read_metric(ctx, lowering_profile.UPDATE)
