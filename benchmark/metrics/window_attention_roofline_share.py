"""The least time the chip could take for the sliding-window attentions of a
step, over the device OWN time of the window rule's kernels.  The least time is
max(operations / peak FLOP/s, bytes / peak HBM B/s) of `window_attention_flops`
and `window_attention_bytes` in the model's module
(benchmark/models/phi4flash.py: the two products forward and the four backward
over the (query, key) pairs the window ALLOWS, q, k, v, the output and their
gradients once in bf16; nothing for a masked pair a kernel computes anyway,
nothing for the scores backward computes again and nothing for what the
layer's `recompute_scope` makes a second time), so it cannot pass 100: blocks
wider than the band and the recomputed forward lower it.  The instructions are
those the lowering put under its `window_attention` scope inside
`fused_attention` (ops/masked_attention.py: the kernels' calls, the queries'
scaling and the sum of the fused backward's partial dq; not the projections
round them), forward, backward and recomputed, each event's OWN time from the
table the state-space readers share.  Nothing where the program has no such
scope or the model no such function."""
import re

from benchmark.metrics import attention_roofline_share, ssm_ms_per_step

LAYER = 'ops: kernels (ops/*.py)'
UNIT = '%'
BETTER = 'higher'
SOURCE = 'device_trace'
MOVES = 'train_samples_per_s'
SCOPE = re.compile(r"/window_attention/")


def read(ctx: dict):
    model = ctx.get("model")
    if not hasattr(model, "window_attention_flops"):
        return None
    spent = ssm_ms_per_step.own_ms_under(ctx, SCOPE)
    if not spent:
        return None
    cfg, job = ctx["config"], ctx["traffic"]
    least = attention_roofline_share.least_seconds(
        model.window_attention_flops(cfg, job), model.window_attention_bytes(cfg, job), ctx["peaks"])
    return 100.0 * least / (spent / 1e3)
