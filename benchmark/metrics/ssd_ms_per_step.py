"""Device ms a step spends in the scalar-decay state-space scans: the
instructions whose lowering scope is the op `ssd_scan` (`op<idx>:ssd_scan` in
`compiled.as_text()`: `ops/ssd_ops.py`, the chunks' products, the carried
state's `lax.scan`, the decays and the skip), forward, backward and what backward
computes again, a run of the step.  Each event's OWN time
(`recompute_ms_per_step.own_times`): the carried state goes through a
`lax.scan`, whose `while` event encloses its body's on the trace's `XLA Ops`
line.  The mixer's projections, convolution and gated norm round the op are not
in it.  Nothing where the program has no such op (a parent that cannot build
it)."""
import re

from benchmark.metrics import ssm_ms_per_step

LAYER = 'ops: kernels (ops/*.py)'
UNIT = 'ms'
BETTER = 'lower'
SOURCE = 'device_trace'
MOVES = 'train_samples_per_s'

SCOPE = re.compile(r"/op\d+:ssd_scan(/|$)")


def read(ctx: dict):
    if not ctx.get("executables"):
        return None
    return ssm_ms_per_step.own_ms_under(ctx, SCOPE)   # the trace's own times, made once a run and kept in `ctx`
