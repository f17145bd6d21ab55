"""Device ms a step spends on the learned choice of keys: the instructions
under the scope `sparse_index`, which
`paddle_tpu.models.transformer.multi_head_attention(sparse_index=)` opens round
the indexer (the detached input, its three projections, the key's LayerNorm,
the two rotations), the op `sparse_index` (the index scores by query chunk, the
top-k, the picks as bits) and the op `index_alignment` (the index scores once
more with their gradients, the alignment target and the divergence).  Forward,
backward and what a `recompute_scope` makes again, each event's own time
(`recompute_ms_per_step.own_times`: the ops loop over chunks, whose `while`
events enclose their bodies').  Not the selected attention's kernels, which are
`fused_attention`'s.  Nothing where the program has no such scope (a program
without an indexer, a parent that cannot build it)."""
import re

from benchmark.metrics import kda_ms_per_step

LAYER = 'ops: kernels (ops/*.py)'
UNIT = 'ms'
BETTER = 'lower'
SOURCE = 'device_trace'
MOVES = 'train_samples_per_s'

#: sibling `name_scope`s of one name are numbered: sparse_index, sparse_index_1, ... (two a layer)
SCOPE = re.compile(r"/sparse_index(_\d+)?/")


def read(ctx: dict):
    return kda_ms_per_step.own_ms_under(ctx, SCOPE)
