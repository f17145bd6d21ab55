"""Device ms a step spends in latent attention: the instructions under the
scope `latent_attention`, which
`paddle_tpu.models.transformer.latent_attention` opens with `fluid.name_scope`
round the whole operator (the query's and the latent's projections, the
latent's norm, the up-projection, the assembly of the 192-wide keys from a
head's own part and the part all heads share, the attention's kernels and the
output projection), forward and backward, a run of the step, each event's own
time.  A scope WIDER than the op `fused_attention`: no second reader of that
scope (PERF.md section 7, defect 13a).  Nothing where the program has no such
scope."""
import re

from benchmark.metrics import kda_ms_per_step

LAYER = 'ops: kernels (ops/*.py)'
UNIT = 'ms'
BETTER = 'lower'
SOURCE = 'device_trace'
MOVES = 'train_samples_per_s'

SCOPE = re.compile(r"/latent_attention(_\d+)?/")


def read(ctx: dict):
    return kda_ms_per_step.own_ms_under(ctx, SCOPE)
