"""The least time the chip could take for the latent attentions' products of a
step, over the device OWN time of the attention kernels inside the latent
layers.  The least time is max(operations / peak FLOP/s, bytes / peak HBM B/s)
of `latent_attention_flops` and `latent_attention_bytes` in the model's module
(benchmark/models/kanana.py: q k^T over 192 and p v over 128 forward, the four
products backward, over the (query, key) pairs the causal mask ALLOWS; q, k, v,
the output and their gradients once in bf16; nothing for a masked pair a kernel
computes anyway, nothing for the scores a backward kernel computes again and
nothing for a forward that the layer's `recompute_scope` makes a second time),
so it cannot pass 100: the diagonal's blocks, the backward kernel's second look
at the scores and a recomputed forward all lower it.  The instructions are
those the lowering put under the splash kernels' scope
(`block_sparse_attention`: the kernels' calls and the queries' scaling) INSIDE
a `latent_attention` scope, found by name in `compiled.as_text()` as
`attention_roofline_share` finds SDAR's: not the projections, the rotation or
the keys' assembly round them.  Forward, backward and recomputed, each event's
own time.  Nothing where the program has no such scope or the model no such
function (a parent that cannot build the layer)."""
import re

from benchmark.metrics import attention_roofline_share, kda_ms_per_step

LAYER = 'ops: kernels (ops/*.py)'
UNIT = '%'
BETTER = 'higher'
SOURCE = 'device_trace'
MOVES = 'train_samples_per_s'

#: sibling `name_scope`s of one name are numbered: latent_attention, latent_attention_1, ... (a layer each)
SCOPE = re.compile(r"/latent_attention(_\d+)?/(?:[^\"]*/)?block_sparse_attention/")


def read(ctx: dict):
    model = ctx.get("model")
    if not hasattr(model, "latent_attention_flops"):
        return None
    spent = kda_ms_per_step.own_ms_under(ctx, SCOPE)
    if not spent:
        return None
    cfg, job = ctx["config"], ctx["traffic"]
    least = attention_roofline_share.least_seconds(
        model.latent_attention_flops(cfg, job), model.latent_attention_bytes(cfg, job), ctx["peaks"])
    return 100.0 * least / (spent / 1e3)
