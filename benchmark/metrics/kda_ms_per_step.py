"""Device ms a step spends in the Kimi-Delta-Attention operators: the
instructions under the scope `kda`, which
`paddle_tpu.models.transformer.kimi_delta_attention` opens with
`fluid.name_scope` round the whole operator (its projections, the three
convolutions, the norms, the decay and the gates, and the op `kda` itself),
forward and backward, a run of the step.  Each event's OWN time
(`recompute_ms_per_step.own_times`): the op's state goes through `lax.scan`s,
whose `while` events enclose their bodies' on the trace's `XLA Ops` line.
Nothing where the program has no such scope (a program without the operator,
or a parent that cannot build it)."""
import re

from benchmark.metrics import recompute_ms_per_step

LAYER = 'ops: kernels (ops/*.py)'
UNIT = 'ms'
BETTER = 'lower'
SOURCE = 'device_trace'
MOVES = 'train_samples_per_s'

#: sibling `name_scope`s of one name are numbered: kda, kda_1, ... (a layer each)
SCOPE = re.compile(r"/kda(_\d+)?/")


def own_ms_under(ctx: dict, scope):
    """Own device ms a run of the instructions whose `op_name` passes through
    `scope` (a compiled pattern); None without a trace, executables or such
    instructions."""
    found = recompute_ms_per_step.own_ms(ctx)
    if found is None:
        return None
    spent, names = found
    mine = [ms for instruction, ms in spent.items() if scope.search(names.get(instruction, ""))]
    return sum(mine) if mine else None


def read(ctx: dict):
    return own_ms_under(ctx, SCOPE)
