"""The least time the chip could take for the FULL layers' attention of a step
(the causal rule, no window), over the device OWN time of those kernels.  The
least time is max(operations / peak FLOP/s, bytes / peak HBM B/s) of
`causal_attention_flops` and `causal_attention_bytes` in the model's module
(benchmark/models/smallthinker.py: the two products forward and the four
backward over the causal triangle's allowed pairs, q, k, v, the output and
their gradients once in bf16; nothing for a masked pair a kernel computes
anyway, nothing for the scores backward computes again and nothing for what
the layer's `recompute_scope` makes a second time), so it cannot pass 100: cut
blocks' masked halves and the recomputed forward lower it.  The instructions
are those under a `fused_attention` op's lowering scope that are NOT under
`/window_attention/` (the kernels' calls, the queries' scaling; not the
projections round them), forward, backward and recomputed, each event's OWN
time from the table the state-space readers share: no scope of its own in code
that other cells lower.  Nothing where the program has no such instruction or
the model no such function."""
import re

from benchmark.metrics import attention_roofline_share, ssm_ms_per_step

LAYER = 'ops: kernels (ops/*.py)'
UNIT = '%'
BETTER = 'higher'
SOURCE = 'device_trace'
MOVES = 'train_samples_per_s'
SCOPE = re.compile(r"/op\d+:fused_attention/(?!(?:[^/]+/)*window_attention/)")


def read(ctx: dict):
    model = ctx.get("model")
    if not hasattr(model, "causal_attention_flops"):
        return None
    spent = ssm_ms_per_step.own_ms_under(ctx, SCOPE)
    if not spent:
        return None
    cfg, job = ctx["config"], ctx["traffic"]
    least = attention_roofline_share.least_seconds(
        model.causal_attention_flops(cfg, job), model.causal_attention_bytes(cfg, job), ctx["peaks"])
    return 100.0 * least / (spent / 1e3)
