"""The least time the chip could take for the gated short convolutions of the
steps in the trace, over the device time of the op's own instructions.  The
least time is max(operations / peak FLOP/s, bytes / peak HBM B/s) of
`short_conv_flops` and `short_conv_bytes` in the model's module
(benchmark/models/lfm2.py: forward reads [tokens, 3d] and writes [tokens, d];
backward reads the incoming gradient and [tokens, 3d] and writes [tokens, 3d];
bf16; nothing for what the backward pass computes again), which is the bytes'
by a wide margin: the op is a pass over memory.  The instructions are those
the lowering put under its `gated_short_conv` scope inside `short_conv`
(ops/moe_ops.py; backward the same under `transpose(`), found by name in
`compiled.as_text()` as `attention_roofline_share.instructions_under` finds
attention's.  Nothing where the program has no such scope or the model no
such function."""
from benchmark.metrics import attention_roofline_share

LAYER = 'ops: kernels (ops/*.py)'
UNIT = '%'
BETTER = 'higher'
SOURCE = 'device_trace'
MOVES = 'train_samples_per_s'

SCOPE = "/gated_short_conv/"


def read(ctx: dict):
    model = ctx.get("model")
    if not hasattr(model, "short_conv_flops"):
        return None
    spent = attention_roofline_share.seconds_under(ctx, SCOPE)
    if not spent:
        return None
    cfg, job = ctx["config"], ctx["traffic"]
    least = attention_roofline_share.least_seconds(
        model.short_conv_flops(cfg, job), model.short_conv_bytes(cfg, job), ctx["peaks"])
    return 100.0 * least / spent
