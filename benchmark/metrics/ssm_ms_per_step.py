"""Device ms a step spends in the Mamba mixers: the instructions under the
scope `mamba`, which `paddle_tpu.models.transformer.mamba_mixer` opens with
`fluid.name_scope` round the whole operator (its projections, the convolution,
the three inner norms, the op `selective_scan` and the gate), forward, backward
and what backward computes again, a run of the step.  Each event's OWN time
(`recompute_ms_per_step.own_times`): the scan's chunks go through a `lax.scan`,
whose `while` event encloses its body's on the trace's `XLA Ops` line.  Nothing
where the program has no such scope (a program without the operator, or a
parent that cannot build it)."""
import re

from benchmark.metrics import recompute_ms_per_step

LAYER = 'ops: kernels (ops/*.py)'
UNIT = 'ms'
BETTER = 'lower'
SOURCE = 'device_trace'
MOVES = 'train_samples_per_s'

#: sibling `name_scope`s of one name are numbered: mamba, mamba_1, ... (a layer each)
SCOPE = re.compile(r"/mamba(_\d+)?/")


def own_ms_under(ctx: dict, scope):
    """`kda_ms_per_step.own_ms_under`, with the trace's own times by
    instruction kept in the readers' shared `ctx`: this cell's trace holds
    millions of events (1024 chunks a `while`, 39 of them a step, four devices)
    and `ssm_scan_roofline_share` reads the same table."""
    if "ssm_own_ms" not in ctx:
        ctx["ssm_own_ms"] = recompute_ms_per_step.own_ms(ctx)
    if ctx["ssm_own_ms"] is None:
        return None
    spent, names = ctx["ssm_own_ms"]
    mine = [ms for instruction, ms in spent.items() if scope.search(names.get(instruction, ""))]
    return sum(mine) if mine else None


def read(ctx: dict):
    return own_ms_under(ctx, SCOPE)
