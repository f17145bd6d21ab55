"""Device OWN ms a step spends in the routers that stand AHEAD of their layer's
attention (`layers.moe(router_input=)`: the router reads the layer's input, and
its op `moe_router` stands before the attention's ops): the instructions whose
lowering scope (`op<idx>:<type>` in `compiled.as_text()`) is `moe_router`,
`held_experts_ms_per_step`'s regex narrowed to the router, forward, made again
and backward: the float32 logits' product at six passes, the softmax, the
top-k, the experts' counts.  Each event's own time, from the table the
state-space readers share.  Nothing where no router stands ahead
(`lowering.routers_before_attention` is 0 or absent: a parent, a program whose
routers stand beside their experts) or the program has no such scope."""
import re

from benchmark import program_trace
from benchmark.metrics import ssm_ms_per_step

LAYER = 'ops: kernels (ops/*.py)'
UNIT = 'ms'
BETTER = 'lower'
SOURCE = 'device_trace'
MOVES = 'train_samples_per_s'
SCOPE = re.compile(r"/op\d+:moe_router(/|$)")


def read(ctx: dict):
    if "traffic" not in ctx or not program_trace.program_monitor().counter_values().get("lowering.routers_before_attention"):
        return None
    return ssm_ms_per_step.own_ms_under(ctx, SCOPE)
