"""Device ms a step spends in the attention layers that read ANOTHER layer's
keys and values: the instructions under the scope `cross_attention`, which
`paddle_tpu.models.transformer.encoder_layer` opens with `fluid.name_scope`
round such a layer's query projection, its attention on the kept K and V (the
splash kernels under the causal rule at the cell's length) and its out
projection, forward, backward and what backward computes again, a run of the
step.  Each event's OWN time, from the table the state-space readers share
(`ssm_ms_per_step.own_ms_under`).  The gradients' sum over the kept tensors'
readers is the keeping layer's, not this scope's.  Nothing where the program
has no such scope."""
import re

from benchmark.metrics import ssm_ms_per_step

LAYER = 'ops: kernels (ops/*.py)'
UNIT = 'ms'
BETTER = 'lower'
SOURCE = 'device_trace'
MOVES = 'train_samples_per_s'
#: sibling `name_scope`s of one name are numbered: cross_attention, cross_attention_1, ...
SCOPE = re.compile(r"/cross_attention(_\d+)?/")


def read(ctx: dict):
    return ssm_ms_per_step.own_ms_under(ctx, SCOPE)
