"""Device ms a step spends in the update phase: the instructions under the
lowering's `update` scope (the ops after `backward`: the optimizer), over the
main module's runs in the traced window.  An undercount where XLA fuses an
optimizer op into the fusion that produces its gradient: a fusion carries
one scope.  The median device."""
from benchmark import program_trace

LAYER = 'lowering (core/lowering.py)'
UNIT = 'ms'
BETTER = 'lower'
SOURCE = 'device_trace'
MOVES = 'train_samples_per_s'


def read(ctx: dict):
    return program_trace.read_phase_metric(ctx, "update")
