"""The selective scan of a Mamba-1 mixer (Gu & Dao 2023, arXiv:2312.00752): a
diagonal state-space recurrence whose step, input matrix and output matrix are
functions of the INPUT, computed chunk by chunk.

A channel c of `d` keeps a float32 state h[c, :] in R^N (N = 16), h_0 = 0:

    dt_t   = softplus(Dt_t + DtBias)                         [d]     the step a channel, float32
    h_t    = exp(dt_t[:, None] * A) * h_{t-1} + (dt_t * x_t)[:, None] * B_t[None, :],    A = -exp(ALog)  [d, N]
    y_t    = h_t C_t + D * x_t                               [d]

`selective_scan` takes x and the step's projection Dt [b, T, d], B and C
[b, T, N], the float32 parameters ALog [d, N], D [d] and DtBias [d]; the
convolution, the projections, the three inner norms and the output gate round it
are ops of the program.  The bias and the softplus are the op's (the published
kernel's `delta_bias` / `delta_softplus`), so that they and everything after
them are float32 whatever the activations' dtype.

Two paths, one rule (`_scan_path`, from what the lowering observes: the
platform, the mesh, the shapes; nothing a process or a program can set).

"kernels", on the TPU where the channels are whole registers a state index:
the two Pallas kernels of `ops/ssm_kernels.py`, which run the recurrence above
token by token on a block of channels' state held in VMEM, forward and, under a
`jax.custom_vjp` (`kernel_selective_scan`), transposed: backward keeps the
state every chunk of 64 tokens STARTS from ([T / 64, b, N, d] float32: 42 MB a
row of 8192 tokens of 5120 channels) and the transposed kernel makes a chunk's
states again in VMEM; nothing of [T, N, d] goes to HBM.

"xla", everywhere else (the CPU, odd shapes, a mesh that splits more than the
batch; what the tests hold the kernels to): ONE `lax.scan` over chunks of
`chunk` tokens that carries the state [b, N, d]; inside a chunk the recurrence
is a `jax.lax.associative_scan` over the pairs (a_t, u_t) = (exp(dt_t A), dt_t
x_t B_t) under (a, u) . (a', u') = (a a', a' u + u'), which is stable however
strong the decay (no exponent is positive), and the chunk's start state enters
through the cumulative products the same scan returns.  Every chunk is a
`jax.checkpoint`: backward keeps the chunks' inputs and start states
([chunks, b, N, d] float32: 335 MB a layer at 8192 tokens of 5120 channels in
chunks of 8, an eighth of the states) and makes a chunk's [chunk, N, d] arrays
again; nothing of [T, d, N] (1.34 GB a sequence of 4096 a layer) outlives a
chunk.  The state lies [N, d], channels
last: an array that ends in 16 is tiled to 128 lanes and wastes seven eighths
of them (PERF.md, PR 42).  In both paths T need not be a whole number of chunks:
the tail is padded with steps of zero (dt = 0: a = 1, u = 0), which leave the
state alone.

Under a mesh whose batch axis splits the rows and nothing else the whole op
runs in a `shard_map` over that axis (`ops.common.over_batch_shards`): a chip
scans its own rows by either path (a `pallas_call` cannot be partitioned), and
GSPMD is not asked how to split a loop over the sequence.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name

from ..core import analysis as _A
from ..core import resource_plan as _RP
from ..core.registry import register_op, set_kept, set_step_stats
from ..monitor import MONITOR as _MON
from . import ssm_kernels
from .common import batch_shards, counted_rules, first, kept_residuals, operand_of, over_batch_shards, residuals_name

#: Tokens a chunk of the XLA form (`_scan_path`: the CPU's and the odd shapes';
#: the kernels' chunk is `ssm_kernels.CHUNK`, and their state is `_carried`
#: after as many tokens as here).  A chunk's arrays are
#: [b, chunk, N, d] float32 and the associative scan makes log2(chunk) levels of
#: them, which XLA keeps in one fusion only while they are small.  Measured on
#: a v5e at (1, 8192, 5120) x 16, ms forward | forward and backward
#: (tools/chip_jamba_scan.py; PERF.md section 6, PR 47): 8 tokens 18.4 | 42.8,
#: 16: 20.5 | 45.8, 32: 19.7 | 110.2, 64: 18.6 | 160.4, 128: 125.7 | 331.4.
#: Shorter chunks keep more start states for backward (2 tokens: 9.2 | 30.0, and
#: half of [T, N, d]).
_SSM_CHUNK = 8
#: What the padded tail's step projection holds: softplus of it is exactly 0 in
#: float32 and so is its slope.
_NO_STEP = -1e4


def _combine(left, right):
    """(a, u) then (a', u'): h -> a' (a h + u) + u'."""
    return left[0] * right[0], right[0] * left[1] + right[1]


def _step_of(dt, dt_bias):
    """The float32 step softplus(Dt + DtBias) (a function of its own so that a
    control can round it: tests/test_jamba.py, tools/chip_jamba_controls.py)."""
    return jax.nn.softplus(dt.astype(jnp.float32) + dt_bias)


def _carried(h):
    """The state handed from a chunk to the next: float32 (a seam for the
    controls, as `_step_of`)."""
    return h


def _chunk(h0, x, dt, b_t, c_t, a_t, dt_bias):
    """One chunk from the state h0 [b, N, d]: (the state after its last token,
    y without the skip term [b, C, d] float32, the sum of its decays) of x, dt
    [b, C, d] and B, C [b, C, N]; `a_t` = A transposed, [N, d]."""
    step = _step_of(dt, dt_bias)
    decay = jnp.exp(step[:, :, None, :] * a_t)                                   # [b, C, N, d]
    enters = (step * x.astype(jnp.float32))[:, :, None, :] * b_t.astype(jnp.float32)[:, :, :, None]
    through, own = jax.lax.associative_scan(_combine, (decay, enters), axis=1)
    h = own + through * h0[:, None]
    y = jnp.sum(h * c_t.astype(jnp.float32)[:, :, :, None], axis=2)
    return _carried(h[:, -1]), y, (jnp.sum(decay), jnp.sum(step))


def chunked_selective_scan(x, dt, a_log, b_t, c_t, d_skip, dt_bias, chunk=_SSM_CHUNK):
    """(y [b, T, d] in x's dtype, the state after the last token [b, N, d]
    float32, (the mean decay, the mean step)) of the recurrence in the module's
    docstring, `chunk` tokens at a time."""
    batch, T, d = x.shape
    chunk = min(int(chunk), T)
    n = -(-T // chunk)
    pad = n * chunk - T
    a_t = -jnp.exp(a_log.astype(jnp.float32)).T                                  # [N, d]
    dt_bias = dt_bias.astype(jnp.float32)

    def chunks(t, fill=0.0):   # [b, T, .] -> [n, b, C, .]
        if pad:
            t = jnp.pad(t, ((0, 0), (0, pad), (0, 0)), constant_values=fill)
        return t.reshape(batch, n, chunk, t.shape[-1]).swapaxes(0, 1)

    @jax.checkpoint
    def body(h, part):
        h, y, sums = _chunk(h, *part, a_t, dt_bias)
        return h, (y, sums)

    with jax.named_scope("selective_scan"):
        h0 = jnp.zeros((batch, a_t.shape[0], d), jnp.float32)
        final, (y, (decays, steps)) = jax.lax.scan(body, h0, (chunks(x), chunks(dt, _NO_STEP), chunks(b_t), chunks(c_t)))
        y = y.swapaxes(0, 1).reshape(batch, n * chunk, d)[:, :T]
        y = (y + d_skip.astype(jnp.float32) * x.astype(jnp.float32)).astype(x.dtype)
        real = float(batch * T * d)
        # the padded steps decay by exactly 1 and step by exactly 0
        means = (jnp.sum(decays) - float(batch * pad * d * a_t.shape[0])) / (real * a_t.shape[0]), jnp.sum(steps) / real
    return y, final, means


def _kernel_seams():
    """The kernels' own two seams, `ssm_kernels.step_of` and `carried`, where the
    `jax.numpy` form reads this module's `_step_of` and `_carried` (static
    arguments of the kernels' `jax.jit`s, so a control that patches one is
    traced anew: tools/chip_jamba_controls.py patches both pairs)."""
    return ssm_kernels.step_of, ssm_kernels.carried


def _scan_path(platform, mesh, x, a_log, batch_axis=None):
    """How the op is lowered: "kernels" (`ops/ssm_kernels.py`: a block of
    channels' state in VMEM, token by token, forward and transposed) on the TPU
    where the channels are a whole number of the kernels' `UNIT` (whole
    registers a state index), the state a whole number of sublane tiles, and
    `batch_shards` is not 0: no mesh, one device, or a mesh that splits the
    batch alone, where the kernels run on a chip's rows inside the `shard_map`
    `over_batch_shards` opens (a `pallas_call` cannot be partitioned); else
    "xla", `chunked_selective_scan`: the CPU's path, the odd shapes', any mesh's
    that splits more than the rows (GSPMD partitions the plain form by itself),
    and what the tests hold the kernels to.  TPU v5e, (1, 8192, 5120) x 16, forward |
    forward + backward of the op alone: PERF.md, PR 48."""
    whole = x.shape[-1] % ssm_kernels.UNIT == 0 and a_log.shape[-1] % ssm_kernels.GROUP == 0
    return "kernels" if platform == "tpu" and whole and batch_shards(mesh, batch_axis, x.shape[0]) else "xla"


def _kernel_chunk(tokens, chunk=ssm_kernels.CHUNK):
    """Tokens a grid step of the kernels: `chunk`, or a short row rounded up to whole sublane tiles."""
    return min(int(chunk), -(-tokens // 16) * 16)


def _kernel_operands(x, dt, a_log, b_t, c_t, d_skip, dt_bias, chunk, block):
    """(the kernels' seven operands: x, dt, B, C padded to a whole number of
    chunks, the tail stepping by exactly 0 (`_NO_STEP`), A transposed [N, d], D
    and DtBias float32; their static arguments: the chunk, the block, the seams;
    the padded tokens)."""
    T, d = x.shape[1:]
    chunk = _kernel_chunk(T, chunk)
    pad = -T % chunk
    if pad:
        x, b_t, c_t = (jnp.pad(t, ((0, 0), (0, pad), (0, 0))) for t in (x, b_t, c_t))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)), constant_values=_NO_STEP)
    a_t = -jnp.exp(a_log.astype(jnp.float32)).T
    return ((x, dt, b_t, c_t, a_t, d_skip.astype(jnp.float32), dt_bias.astype(jnp.float32)),
            (chunk, block or ssm_kernels.block_of(d), _kernel_seams()), pad)


def _kernel_scan(x, dt, a_log, b_t, c_t, d_skip, dt_bias, kernels, chunk, block, keep):
    """(`chunked_selective_scan`'s three results, what backward reads: the
    chunks' start states where they are kept) of the kernels."""
    batch, T, d = x.shape
    with jax.named_scope("selective_scan"):
        operands, static, pad = _kernel_operands(x, dt, a_log, b_t, c_t, d_skip, dt_bias, chunk, block)
        y, final, decays, steps, *kept = ssm_kernels.scan(*operands, *static, keep, kernels == "interpret")
        real, state = float(batch * T * d), a_log.shape[1]
        means = (jnp.sum(decays) - float(batch * pad * d * state)) / (real * state), jnp.sum(steps) / real
    return (y[:, :T], ssm_kernels.channels_last(final), means), tuple(kept)


@functools.partial(jax.custom_vjp, nondiff_argnums=(7, 8, 9, 10))
def kernel_selective_scan(x, dt, a_log, b_t, c_t, d_skip, dt_bias, kernels="tpu", chunk=ssm_kernels.CHUNK, block=None,
                          keep=None):
    """`chunked_selective_scan`'s results from the Pallas kernels of
    `ops/ssm_kernels.py`, `chunk` tokens and `block` channels a grid step
    (`ssm_kernels.block_of` the row's unless given; `_scan_path` says when
    the op comes here); `kernels`: "tpu", or "interpret" for those
    interpreted (the tests').  The final state and the means are for
    statistics: backward takes no cotangent for them."""
    return _kernel_scan(x, dt, a_log, b_t, c_t, d_skip, dt_bias, kernels, chunk, block, False)[0]


def _kernel_scan_fwd(x, dt, a_log, b_t, c_t, d_skip, dt_bias, kernels, chunk, block, keep):
    """The op where it is differentiated: forward keeps the chunks' start states beside the seven inputs.  `keep`
    names the two values that only the forward kernel makes and backward reads, the output (the mixer's gate reads
    it) and the start states: a `jax.checkpoint` round the op whose policy saves the name (`core/lowering.py:
    plan_kept`) then runs no second forward."""
    _MON.counter("lowering.selective_scan_starts_kept").inc()
    (y, final, means), (starts,) = _kernel_scan(x, dt, a_log, b_t, c_t, d_skip, dt_bias, kernels, chunk, block, True)
    if keep:
        y, starts = checkpoint_name(y, keep), checkpoint_name(starts, keep)
    return (y, final, means), ((x, dt, a_log, b_t, c_t, d_skip, dt_bias), (starts,))


def _kernel_scan_bwd(kernels, chunk, block, keep, residuals, cotangents):
    (x, dt, a_log, b_t, c_t, d_skip, dt_bias), (starts,) = residuals
    _MON.counter("lowering.selective_scan_kernel_transposed_calls").inc()
    T = x.shape[1]
    with jax.named_scope("selective_scan"):
        operands, static, pad = _kernel_operands(x, dt, a_log, b_t, c_t, d_skip, dt_bias, chunk, block)
        d_y = jnp.pad(cotangents[0], ((0, 0), (0, pad), (0, 0))) if pad else cotangents[0]
        dx, ddt, db, dc, da_t, dskip, dbias = ssm_kernels.scan_transposed(*operands, d_y, starts, *static, kernels == "interpret")
        d_a_log = (da_t * operands[4]).T                                           # A = -exp(ALog): dA / dALog = A
    return (dx[:, :T], ddt[:, :T], d_a_log.astype(a_log.dtype), db[:, :T].astype(b_t.dtype), dc[:, :T].astype(c_t.dtype),
            dskip.astype(d_skip.dtype), dbias.astype(dt_bias.dtype))


kernel_selective_scan.defvjp(*counted_rules("selective_scan", _kernel_scan_fwd, _kernel_scan_bwd))


@register_op("selective_scan")
def _selective_scan(ctx, op, ins):
    """The chunked recurrence over X, Dt [b, T, d], B, C [b, T, N] with ALog
    [d, N], D and DtBias [d] (float32).  `Stats` [3] is the step's health, read
    on logged steps: the mean decay exp(dt A), the mean step dt and the largest
    |h| of the state after the last token."""
    x, dt, a_log, b_t, c_t, d_skip, dt_bias = (first(ins, s) for s in ("X", "Dt", "ALog", "B", "C", "D", "DtBias"))
    _MON.counter("lowering.selective_scan_ops").inc()
    _MON.counter("lowering.selective_scan_chunks").inc(-(-x.shape[1] // min(_SSM_CHUNK, x.shape[1])))
    shards = batch_shards(ctx.mesh, ctx.batch_axis, x.shape[0])
    # "interpret" is the tests': the kernels interpreted where no chip is
    kernels = {"kernels": "tpu", "interpret": "interpret"}.get(_scan_path(ctx.platform, ctx.mesh, x, a_log, ctx.batch_axis))
    _MON.counter("lowering.selective_scan_kernel_calls").inc(1 if kernels else 0)
    keep = kept_residuals(ctx, op)

    def scan(x, dt, b_t, c_t, a_log, d_skip, dt_bias):
        if kernels:
            y, final, (decay, step) = kernel_selective_scan(x, dt, a_log, b_t, c_t, d_skip, dt_bias, kernels, keep=keep)
        else:
            y, final, (decay, step) = chunked_selective_scan(x, dt, a_log, b_t, c_t, d_skip, dt_bias)
        decay, step, largest = jax.lax.stop_gradient((decay, step, jnp.max(jnp.abs(final))))
        if shards > 1:   # a chip's rows: the means of equal shares, the largest of all
            decay, step = (jax.lax.pmean(t, ctx.batch_axis) for t in (decay, step))
            largest = jax.lax.pmax(largest, ctx.batch_axis)
        return y, jnp.broadcast_to(jnp.stack([decay, step, largest]), (x.shape[0], 3))

    # The scan reads the VARIABLES.  Without the barrier XLA fuses the operands' producers into the chunks' layout and
    # the scan reads xs before its rounding to bf16 (excess precision): sound arithmetic that a stage check on the
    # FETCHED xs, dt, B, C cannot tell from a fault (the cell's first run read 2.78e-3 against the recurrence where
    # the op alone on such arrays reads 2e-5: PERF.md section 6, PR 47, and defect 19 for `kda`'s kernels)
    x, dt, b_t, c_t = jax.lax.optimization_barrier((x, dt, b_t, c_t))
    batched, whole = (x, dt, b_t, c_t), (a_log, d_skip, dt_bias)
    y, stats = over_batch_shards(ctx, scan, batched, whole) if shards > 1 else scan(*batched, *whole)
    return {"Out": y, "Stats": stats[0]}


def _publish_ssm_state(step, values):
    """One logged step's `ssm_state` record: per layer the mean decay exp(dt A),
    the mean step dt and the largest |h| after the last token; the layers' mean
    decay and the worst layer's state as gauges.  A health check (a decay at 1
    forgets nothing and the state grows with the sequence; at 0 the layer reads
    one token): no lever on the step's time."""
    stats = np.stack([np.asarray(s, "f8").reshape(3) for s in values["Stats"]])
    record = {"kind": "ssm_state", "pipeline_step": step, "decay_mean": stats[:, 0].tolist(),
              "dt_mean": stats[:, 1].tolist(), "state_abs_max": stats[:, 2].tolist(),
              "worst_layer": int(np.argmax(stats[:, 2]))}
    _MON.gauge("ssm.decay_mean").set(float(stats[:, 0].mean()))
    _MON.gauge("ssm.state_abs_max").set(float(stats[:, 2].max()))
    _MON.record_step(record)


set_step_stats("selective_scan", ("Stats",), _publish_ssm_state)


def _infer_selective_scan(ctx):
    x, dt, a_log, b_t, c_t = (ctx.in_shape(s) for s in ("X", "Dt", "ALog", "B", "C"))
    if x is None or a_log is None:
        return
    if len(x) != 3 or len(a_log) != 2 or a_log[0] != x[-1]:
        ctx.fail(f"X must be (b, T, d) and ALog (d, N), got {x} and {a_log}")
    if dt is not None and tuple(dt) != tuple(x):
        ctx.fail(f"Dt holds one step for each of X's {tuple(x)} channels, got {dt}")
    for name, t in (("B", b_t), ("C", c_t)):
        if t is not None and tuple(t) != tuple(x[:2]) + (a_log[1],):
            ctx.fail(f"{name} must be (b, T, N) = {tuple(x[:2]) + (a_log[1],)}, got {t}")
    for name in ("D", "DtBias"):
        t = ctx.in_shape(name)
        if t is not None and tuple(t) != (x[-1],):
            ctx.fail(f"{name} must be (d,) = ({x[-1]},), got {t}")
    ctx.set_out("Out", x, ctx.in_dtype("X"))
    ctx.set_out("Stats", (3,), "float32")


_A.register_rule(["selective_scan"], _infer_selective_scan)

#: Elementwise operations a state element a token, forward: the decay's product
#: and exp (counted as one each), the input's product, the recurrence's multiply
#: and add, the output's multiply and add.
SCAN_FLOPS_PER_STATE_ELEMENT = 7.0


def selective_scan_flops(tokens, channels, state):
    """Elementwise operations of the recurrence's forward over `tokens`
    positions, `SCAN_FLOPS_PER_STATE_ELEMENT` a state element (an exp counted
    as one), and per channel the softplus, the step's product and the skip."""
    return float(tokens) * channels * (SCAN_FLOPS_PER_STATE_ELEMENT * state + 6.0)


def _cost_selective_scan(ctx):
    x, a_log = ctx.in_shape("X"), ctx.in_shape("ALog")
    if x is None or a_log is None or len(x) != 3:
        return float(ctx.out_elems_total()), ctx.io_bytes()
    return selective_scan_flops(x[0] * x[1], x[2], a_log[1]), ctx.io_bytes()


_RP.register_cost(["selective_scan"], _cost_selective_scan)


def _kept_scan(ctx, op, shapes):
    """Where the op takes the kernels: its output and the float32 state every
    chunk starts from, [chunks, b, N, d]."""
    x, a_log = operand_of(shapes, op.input("X")[0]), operand_of(shapes, op.input("ALog")[0])
    if _scan_path(ctx.platform, ctx.mesh, x, a_log, ctx.batch_axis) == "xla":
        return None
    (batch, tokens, channels), state = x.shape, a_log.shape[1]
    starts_bytes = 4 * -(-tokens // _kernel_chunk(tokens)) * batch * state * channels
    return residuals_name(op), shapes.nbytes(op.output("Out")[0]) + starts_bytes


set_kept("selective_scan", _kept_scan)


@register_op("memory_gate")
def _memory_gate(ctx, op, ins):
    """A Gated Memory Unit's gate (SambaY, Ren et al. 2025, arXiv:2507.06607):
    Out = silu(Gate) * Memory over (b, T, d), Gate this layer's projection of
    its own input and Memory a tensor ANOTHER layer kept (a state-space scan's
    output, before that layer's own gate and out-projection); the SiLU in
    float32, the product rounded once to Memory's dtype.  `Stats` [3], read on
    logged steps: the mean |Memory|, the mean gate silu(Gate), and 1 where
    every value of both is finite."""
    gate, memory = first(ins, "Gate"), first(ins, "Memory")
    _MON.counter("lowering.kept_tensor_readers").inc()
    # the gate reads the VARIABLES, as the scan does: fused with its projection it would read the product before its
    # rounding to bf16, which a stage check on the fetched operands cannot tell from a fault
    gate, memory = jax.lax.optimization_barrier((gate, memory))
    opened = jax.nn.silu(gate.astype(jnp.float32))
    kept = memory.astype(jnp.float32)
    stats = jax.lax.stop_gradient(jnp.stack([
        jnp.mean(jnp.abs(kept)), jnp.mean(opened),
        (jnp.all(jnp.isfinite(kept)) & jnp.all(jnp.isfinite(opened))).astype(jnp.float32)]))
    return {"Out": (opened * kept).astype(memory.dtype), "Stats": stats}


def _publish_gmu_memory(step, values):
    """One logged step's `gmu_memory` record: per Gated Memory Unit the mean
    |m| of the kept scan output it read, the mean of its gate silu(x W1) and
    whether every value was finite.  A health check as `ssm_state` is: a memory
    that has died (|m| near 0) or a gate that has closed leaves the layer its
    feed-forward part alone."""
    stats = np.stack([np.asarray(s, "f8").reshape(3) for s in values["Stats"]])
    _MON.gauge("gmu.memory_abs_mean").set(float(stats[:, 0].mean()))
    _MON.record_step({"kind": "gmu_memory", "pipeline_step": step, "memory_abs_mean": stats[:, 0].tolist(),
                      "gate_mean": stats[:, 1].tolist(), "finite": bool(np.all(stats[:, 2] == 1.0))})


set_step_stats("memory_gate", ("Stats",), _publish_gmu_memory)


def _infer_memory_gate(ctx):
    gate, memory = ctx.in_shape("Gate"), ctx.in_shape("Memory")
    if gate is not None and memory is not None and tuple(gate) != tuple(memory):
        ctx.fail(f"Gate {gate} and Memory {memory} must have one shape")
    ctx.set_out("Out", memory if memory is not None else gate, ctx.in_dtype("Memory"))
    ctx.set_out("Stats", (3,), "float32")


_A.register_rule(["memory_gate"], _infer_memory_gate)
_RP.register_cost(["memory_gate"], lambda ctx: (6.0 * ctx.out_elems_total(), ctx.io_bytes()))
