"""Seconds of set-up inside the lowering's `lowering.forward` spans on the
caller's thread, from process start to the window's first step: the forward's
interpretation op by op (`run_ops` under `jax.vjp`, every Pallas kernel's body
traced where its op is lowered) and JAX's linearisation of it; for a program
with no `backward` op (the start-up program, a `for_test` clone) its whole
interpretation.
One reading with its five siblings: `benchmark/lowering_profile.py`."""
from benchmark import lowering_profile

LAYER = 'lowering (core/lowering.py)'
UNIT = 's'
BETTER = 'lower'
SOURCE = 'program_span'
MOVES = 'setup_s'


def read(ctx: dict):
    return lowering_profile.read_metric(ctx, lowering_profile.FORWARD)
