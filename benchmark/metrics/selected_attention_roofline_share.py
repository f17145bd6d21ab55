"""The least time the chip could take for a step's attention over the CHOSEN
pairs, over the device OWN time of the instructions under the scope
`selected_attention`.  The least time is max(operations / peak FLOP/s, bytes /
peak HBM B/s) of `selected_attention_flops` and `selected_attention_bytes` in
the model's module (benchmark/models/keye.py: the two products forward and the
four backward over the pairs the queries HOLD, 31.46 M a sequence at 16384
tokens and 2048 picks, and the alignment target's scores over the same pairs;
nothing for a pair outside the picks that a kernel computes anyway, nothing for
the scores a backward kernel computes again and nothing for a forward that a
`recompute_scope` makes a second time), so it cannot pass 100.  The scope is
opened twice: by `fused_attention`'s lowering round the splash kernels under
the stored mask (`ops/masked_attention.py: selected_attention`: the block maps
made from the picks, the three kernels, the queries' scaling) and by
`index_alignment` round the target (that attention's scores once more).  A
form that computes every pair under the diagonal and masks reads at most about
a quarter of what one that visits the chosen pairs alone would: that is the
finding the number is there for, not a fault.  Nothing where the program has no
such scope or the model no such function (a parent that cannot build the
layer)."""
import re

from benchmark.metrics import attention_roofline_share, kda_ms_per_step

LAYER = 'ops: kernels (ops/*.py)'
UNIT = '%'
BETTER = 'higher'
SOURCE = 'device_trace'
MOVES = 'train_samples_per_s'

SCOPE = re.compile(r"/selected_attention/")


def read(ctx: dict):
    model = ctx.get("model")
    if not hasattr(model, "selected_attention_flops"):
        return None
    spent = kda_ms_per_step.own_ms_under(ctx, SCOPE)
    if not spent:
        return None
    cfg, job = ctx["config"], ctx["traffic"]
    least = attention_roofline_share.least_seconds(
        model.selected_attention_flops(cfg, job), model.selected_attention_bytes(cfg, job), ctx["peaks"])
    return 100.0 * least / (spent / 1e3)
