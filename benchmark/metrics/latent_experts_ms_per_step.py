"""Device ms a step spends in the latent experts: the instructions under the
scope `latent_experts`, which `fluid.layers.moe(latent_size=)` opens with
`fluid.name_scope` round the projection into the latent, the router, the op
`moe_experts` (the sort by held expert, the row gathers, the two grouped
products an expert, the way back to token order) and the projection out of the
latent, forward, backward and what backward computes again, a run of the step.
The shared expert beside them is not in it (its scope is `shared_expert`).
Each event's OWN time (`recompute_ms_per_step.own_times`): the held path's
passes are branches of `conditional`s, whose events enclose their bodies' on the
trace's `XLA Ops` line.  Nothing where the program has no such scope."""
import re

from benchmark.metrics import ssm_ms_per_step

LAYER = 'ops: kernels (ops/*.py)'
UNIT = 'ms'
BETTER = 'lower'
SOURCE = 'device_trace'
MOVES = 'train_samples_per_s'

#: sibling `name_scope`s of one name are numbered: latent_experts, latent_experts_1, ... (a layer each)
SCOPE = re.compile(r"/latent_experts(_\d+)?/")


def read(ctx: dict):
    if not ctx.get("executables"):
        return None
    return ssm_ms_per_step.own_ms_under(ctx, SCOPE)
