"""Seconds of set-up inside the executor's `lowering.to_hlo` spans on the
caller's thread, from process start to the window's first step: the traced
jaxpr's way to StableHLO (`traced.lower()`), where every Pallas call site
lowers its body to Mosaic; the span carries `jaxpr_eqns` and `pallas_calls`.
One reading with its five siblings: `benchmark/lowering_profile.py`."""
from benchmark import lowering_profile

LAYER = 'lowering (core/lowering.py)'
UNIT = 's'
BETTER = 'lower'
SOURCE = 'program_span'
MOVES = 'setup_s'


def read(ctx: dict):
    return lowering_profile.read_metric(ctx, lowering_profile.HLO)
