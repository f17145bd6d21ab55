"""Device ms a step spends in the Gated Memory Units: the instructions under
the scope `gmu`, which `paddle_tpu.models.transformer.gated_memory_unit` opens
with `fluid.name_scope` round the whole operator (its in projection, the op
`memory_gate`, which reads the scan output another layer kept, and the out
projection), forward, backward and what backward computes again, a run of the
step.  Each event's OWN time, from the table the state-space readers share
(`ssm_ms_per_step.own_ms_under`).  Nothing where the program has no such scope
(a program without the operator, or a parent that cannot build it)."""
import re

from benchmark.metrics import ssm_ms_per_step

LAYER = 'ops: kernels (ops/*.py)'
UNIT = 'ms'
BETTER = 'lower'
SOURCE = 'device_trace'
MOVES = 'train_samples_per_s'
#: sibling `name_scope`s of one name are numbered: gmu, gmu_1, ... (a layer each)
SCOPE = re.compile(r"/gmu(_\d+)?/")


def read(ctx: dict):
    return ssm_ms_per_step.own_ms_under(ctx, SCOPE)
