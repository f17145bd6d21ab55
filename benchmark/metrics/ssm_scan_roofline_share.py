"""The least time the chip could take for the selective scans of a step, over
the device OWN time of the op's instructions.  The least time is
max(operations / peak FLOP/s, bytes / peak HBM B/s) of `selective_scan_flops`
and `selective_scan_bytes` in the model's module (benchmark/models/jamba.py:
the recurrence's elementwise operations, forward and backward, nothing for what
backward makes again; the op's inputs, its output and their gradients once).
The bytes decide: the operations are the vector unit's, which the table of
peaks does not price (it has the matrix unit's rate, 197 TFLOP/s, far above
what elementwise work can reach), and one `exp` a state element is counted as
ONE operation.  The instructions are those under the `selective_scan` scope
the lowering opens inside the op (ops/ssm_ops.py; forward, backward and
recomputed alike, the chunks' `while` by its own time).  Plain `jax.numpy` that
XLA fuses, no kernel: the share says how far that is from what the recurrence
needs, and is what a kernel starts from.  Nothing where the program has no such
scope or the model no such function."""
import re

from benchmark.metrics import attention_roofline_share, ssm_ms_per_step

LAYER = 'ops: kernels (ops/*.py)'
UNIT = '%'
BETTER = 'higher'
SOURCE = 'device_trace'
MOVES = 'train_samples_per_s'

#: the `jax.named_scope` inside the op (under `op<idx>:selective_scan`), not the
#: program's `name_scope` of the same name round the op, which also holds it
SCOPE = re.compile(r"op\d+:selective_scan/(.*/)?selective_scan/")


def read(ctx: dict):
    model = ctx.get("model")
    if not hasattr(model, "selective_scan_flops"):
        return None
    spent = ssm_ms_per_step.own_ms_under(ctx, SCOPE)
    if not spent:
        return None
    cfg, job = ctx["config"], ctx["traffic"]
    least = attention_roofline_share.least_seconds(
        model.selective_scan_flops(cfg, job), model.selective_scan_bytes(cfg, job), ctx["peaks"])
    return 100.0 * least / (spent / 1e3)
