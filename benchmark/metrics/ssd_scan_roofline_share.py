"""The least time the chip could take for the RECURRENCE of a step's
scalar-decay scans, over the device OWN time of the op's instructions
(`ssd_ms_per_step`).  The least time is max(operations / peak FLOP/s, bytes /
peak HBM B/s) of `ssd_recurrence_flops` and `ssd_recurrence_bytes` in the
model's module (benchmark/models/nemotron_h.py), which count the MATHEMATICS
from tokens, heads, head width, state and groups, the same whatever implements
the op: a token and head, the state's update and decay (2 P N) and its read
(2 P N), forward and backward; xs, the output, B, C and the step once forward,
their gradients and the inputs again backward.  What the chunked form
(`ops/ssd_ops.py`) ADDS to that work is not counted, so that a later kernel
cannot move the yardstick: a chunk of Q = 128 tokens also computes C B^T (Q N a
token and GROUP) and the decayed scores by x (Q P a token and head), +56% of the
recurrence's operations at the published widths, all at the matrix unit's
float32 (six bf16 passes), and backward makes the forward again inside the
layer's recomputed segment; a share of a few percent says how far the plain
`jax.numpy` form is from what the recurrence needs, and is what a kernel starts
from.  At the published widths the bytes decide (5.6 ms a step for 2.6).
Nothing where the program has no such op or the model no such function."""
from benchmark.metrics import attention_roofline_share, ssd_ms_per_step

LAYER = 'ops: kernels (ops/*.py)'
UNIT = '%'
BETTER = 'higher'
SOURCE = 'device_trace'
MOVES = 'train_samples_per_s'


def read(ctx: dict):
    model = ctx.get("model")
    if not hasattr(model, "ssd_recurrence_flops"):
        return None
    spent = ssd_ms_per_step.read(ctx)
    if not spent:
        return None
    cfg, job = ctx["config"], ctx["traffic"]
    least = attention_roofline_share.least_seconds(
        model.ssd_recurrence_flops(cfg, job), model.ssd_recurrence_bytes(cfg, job), ctx["peaks"])
    return 100.0 * least / (spent / 1e3)
