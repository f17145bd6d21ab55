"""Seconds of set-up inside the lowering's `lowering.transpose` spans on the
caller's thread, from process start to the window's first step: JAX's
`backward_pass` over the linearised forward, which calls the program's own
`custom_vjp` backward rules (the kernels' backward bodies are traced here) and
differentiates the recomputed segments.
One reading with its five siblings: `benchmark/lowering_profile.py`."""
from benchmark import lowering_profile

LAYER = 'lowering (core/lowering.py)'
UNIT = 's'
BETTER = 'lower'
SOURCE = 'program_span'
MOVES = 'setup_s'


def read(ctx: dict):
    return lowering_profile.read_metric(ctx, lowering_profile.TRANSPOSE)
