"""Device ms a step spends in the gated short convolutions: the instructions
whose lowering scope (`op<idx>:<type>` in `compiled.as_text()`) is
`short_conv`, forward and backward, over the main module's runs in the traced
window.  The two gates and the K taps between a layer's in- and out-projection,
which are `mul` ops of their own; not the projections, the norm before them
nor the residual sum after.  Read from the device's events by instruction, as
`attention_ms_per_step` reads attention's.  Nothing where the program has no
such scope (a program without the op, or a parent that cannot build it)."""
import re

from benchmark.metrics import attention_roofline_share

LAYER = 'ops: kernels (ops/*.py)'
UNIT = 'ms'
BETTER = 'lower'
SOURCE = 'device_trace'
MOVES = 'train_samples_per_s'

SCOPE = re.compile(r"/op\d+:short_conv(/|$)")


def read(ctx: dict):
    spent = attention_roofline_share.seconds_under(ctx, SCOPE)
    return 1e3 * spent if spent else None
