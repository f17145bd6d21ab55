"""Device ms a step spends in attention: the instructions whose lowering scope
(`op<idx>:<type>` in `compiled.as_text()`) is `fused_attention`, forward and
backward, over the main module's runs in the traced window.  The kernels, the
queries' scaling and whatever the lowering puts at their edges (a repeat of
grouped key/value heads, casts); not the projections, the per-head norms or
the rotations round them, which are ops of their own.  Read from the device's
events by instruction, as `attention_roofline_share` reads the kernels' (the
reducer's `by_label` does not see an instruction whose text runs over several
lines, which the stock splash kernel's calls do).  Nothing where the program
has no such scope."""
import re

from benchmark.metrics import attention_roofline_share

LAYER = 'ops: kernels (ops/*.py)'
UNIT = 'ms'
BETTER = 'lower'
SOURCE = 'device_trace'
MOVES = 'train_samples_per_s'

SCOPE = re.compile(r"/op\d+:fused_attention(/|$)")


def read(ctx: dict):
    spent = attention_roofline_share.seconds_under(ctx, SCOPE)
    return 1e3 * spent if spent else None
