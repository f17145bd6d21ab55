"""Device ms a step spends in the forward phase: the instructions under the
lowering's `fwd` scope in `compiled.as_text()` that are no transpose, over the
main module's runs in the traced window.  The median device."""
from benchmark import program_trace

LAYER = 'lowering (core/lowering.py)'
UNIT = 'ms'
BETTER = 'lower'
SOURCE = 'device_trace'
MOVES = 'train_samples_per_s'


def read(ctx: dict):
    return program_trace.read_phase_metric(ctx, "fwd")
