"""Device ms a step spends in the backward phase: the instructions whose
`op_name` holds `transpose(`, which JAX derived from `fwd` (recomputation
under `memory_optimize` included), over the main module's runs in the traced
window.  The median device."""
from benchmark import program_trace

LAYER = 'lowering (core/lowering.py)'
UNIT = 'ms'
BETTER = 'lower'
SOURCE = 'device_trace'
MOVES = 'train_samples_per_s'


def read(ctx: dict):
    return program_trace.read_phase_metric(ctx, "bwd")
