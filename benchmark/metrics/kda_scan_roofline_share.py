"""The least time the chip could take for the chunked KDA recurrences of a
step, over the device OWN time of the op's instructions.  The least time is
max(operations / peak FLOP/s, bytes / peak HBM B/s) of `kda_scan_flops` and
`kda_scan_bytes` in the model's module (benchmark/models/kimi_linear.py: the
products the chunked recurrence needs, forward and the hand-written backward,
nothing for the chunks' terms and states that backward makes again; the op's
five inputs, its output and their gradients once).  The instructions are those
the lowering put under its `kda_chunk_scan` scope inside the op `kda`
(ops/linear_attention_ops.py; forward and backward alike, the `while`s of the
state's scans by their own time).  Plain `jax.numpy` that XLA fuses, no kernel:
the share says how far that is from what the recurrence needs.  Nothing where
the program has no such scope or the model no such function."""
import re

from benchmark.metrics import attention_roofline_share, kda_ms_per_step

LAYER = 'ops: kernels (ops/*.py)'
UNIT = '%'
BETTER = 'higher'
SOURCE = 'device_trace'
MOVES = 'train_samples_per_s'

SCOPE = re.compile(r"/kda_chunk_scan/")


def read(ctx: dict):
    model = ctx.get("model")
    if not hasattr(model, "kda_scan_flops"):
        return None
    spent = kda_ms_per_step.own_ms_under(ctx, SCOPE)
    if not spent:
        return None
    cfg, job = ctx["config"], ctx["traffic"]
    least = attention_roofline_share.least_seconds(
        model.kda_scan_flops(cfg, job), model.kda_scan_bytes(cfg, job), ctx["peaks"])
    return 100.0 * least / (spent / 1e3)
