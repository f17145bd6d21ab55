"""Device OWN ms a step spends in the attention layers' head gates
(`multi_head_attention(head_gate=)`: g = sigmoid(a Wg), one number a head a
token, and the product of the (B, L, H, dh) attention output with it): the
instructions the lowering put under a name scope `attention_gate` (the gate's
float32 projection, its sigmoid, the product and its one rounding), forward,
made again by the layer's `recompute_scope`, and backward (the product's two
gradients, the sigmoid's, the projection's two products).  A fusion that also
holds a neighbour's work (the out projection's operand, the transposes round the
attention) is counted where XLA put it.  Each event's own time, from the table
the state-space readers share.  Nothing where no layer is gated
(`lowering.gated_attention_layers` is 0 or absent: a parent, a program without
`attention_gate=`) or the program has no such scope."""
import re

from benchmark import program_trace
from benchmark.metrics import ssm_ms_per_step

LAYER = 'ops: kernels (ops/*.py)'
UNIT = 'ms'
BETTER = 'lower'
SOURCE = 'device_trace'
MOVES = 'train_samples_per_s'
SCOPE = re.compile(r"/attention_gate(_\d+)?/")


def read(ctx: dict):
    if "traffic" not in ctx or not program_trace.program_monitor().counter_values().get("lowering.gated_attention_layers"):
        return None
    return ssm_ms_per_step.own_ms_under(ctx, SCOPE)
