"""The least time the chip could take for a step's choosing, over the device
OWN time of the instructions that score and choose.  The least time is
max(operations / peak FLOP/s, bytes / peak HBM B/s) of `index_flops` and
`index_bytes` in the model's module (benchmark/models/keye.py: the index scores
of every pair of the causal triangle, 16 heads of 64; qI, kI and w read once
and the picks written once as bits: the same work whatever implements it;
nothing for the ReLU, the weights, the sum over the heads or the top-k), so it
cannot pass 100: the pairs above the diagonal that a band computes anyway, the
float32 scores' way through HBM and the choosing all lower it.  The
instructions are those under the scope `index_select` that the op
`sparse_index` opens round its chunks; the op runs in the forward pass alone (a
`recompute_scope` keeps the choice).  Nothing where the program has no such
scope or the model no such function (a parent that cannot build the layer)."""
import re

from benchmark.metrics import attention_roofline_share, kda_ms_per_step

LAYER = 'ops: kernels (ops/*.py)'
UNIT = '%'
BETTER = 'higher'
SOURCE = 'device_trace'
MOVES = 'train_samples_per_s'

SCOPE = re.compile(r"/index_select/")


def read(ctx: dict):
    model = ctx.get("model")
    if not hasattr(model, "index_flops"):
        return None
    spent = kda_ms_per_step.own_ms_under(ctx, SCOPE)
    if not spent:
        return None
    cfg, job = ctx["config"], ctx["traffic"]
    least = attention_roofline_share.least_seconds(model.index_flops(cfg, job), model.index_bytes(cfg, job), ctx["peaks"])
    return 100.0 * least / (spent / 1e3)
