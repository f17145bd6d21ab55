"""Device ms a step spends in the Gated DeltaNet operators: the instructions
under the scope `gated_delta_net`, which
`paddle_tpu.models.transformer.gated_delta_net` opens with `fluid.name_scope`
round the whole operator (the [q | k | v | z] and [b | alpha] projections, the
convolution, the L2 norms, the decay, the op `kda` with its `kda_chunk_scan`
scope, the gated norm and the out projection), forward, made again by the
layer's `recompute_scope` and backward, a run of the step.  Each event's OWN
time (`kda_ms_per_step.own_ms_under`: the table the scoped readers share).
`kda_ms_per_step` is Kimi Linear's operator's scope and reads nothing here.
Nothing where the program has no such scope (a program without the operator, or
a parent that cannot build it)."""
import re

from benchmark.metrics import kda_ms_per_step

LAYER = 'ops: kernels (ops/*.py)'
UNIT = 'ms'
BETTER = 'lower'
SOURCE = 'device_trace'
MOVES = 'train_samples_per_s'
#: sibling `name_scope`s of one name are numbered: gated_delta_net, gated_delta_net_1, ... (a layer each)
SCOPE = re.compile(r"/gated_delta_net(_\d+)?/")


def read(ctx: dict):
    return kda_ms_per_step.own_ms_under(ctx, SCOPE)
