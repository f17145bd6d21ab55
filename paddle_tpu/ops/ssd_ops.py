"""The scalar-decay state-space scan of a Mamba-2 mixer (Dao & Gu 2024,
arXiv:2405.21060, "state-space duality"): a recurrence whose decay is ONE scalar
a head and token, computed chunk by chunk as matrix products.

A head h of `H` (each `P` channels wide) keeps a float32 state h[P, N], zero at
the start of every row; its input and output matrices B_t, C_t in R^N are those
of its GROUP (`G` groups of H / G heads each: head h reads group h // (H / G)):

    dt_t = softplus(Dt_t + DtBias)                           [H]     the step a head, float32
    h_t  = exp(dt_t A) h_{t-1} + dt_t x_t (x) B_t,           A = -exp(ALog)  [H]: one scalar a head
    y_t  = h_t C_t + D x_t                                   [H, P]

`ssd_scan` takes X [b, T, H P], the step's projection Dt [b, T, H], B and C
[b, T, G N] and the float32 parameters ALog, D, DtBias [H]; the in-projection,
the convolution over x, B and C, the gated norm and the out-projection round it
are ops of the program (`models/transformer.py: mamba2_mixer`).  `selective_scan`
(`ops/ssm_ops.py`) cannot compute it: its state is [channels, 16] with A a matrix
and its kernels are elementwise by design; here the state is [64, 128] a head,
the decay a scalar, and every pass over the state is a matrix product.

The chunked form, `chunk` tokens (Q) at a time, with cum_i the decay's logarithm
summed from the chunk's start to token i (inclusive, float32, never positive):

    intra   y_i += sum_{j <= i} (C_i . B_j) exp(cum_i - cum_j) dt_j x_j       [Q, Q] products a head and chunk
    state   S   = sum_j exp(cum_Q - cum_j) dt_j x_j (x) B_j                   what the chunk adds to the state
    carry   h'  = exp(cum_Q) h + S                                           chunk to chunk, the only sequential part
    inter   y_i += exp(cum_i) C_i . h                                        the state the chunk started from

No exponent is positive (the mask is put on the exponent's argument, not on its
result, so that neither the value nor its slope ever meets an overflow).  The
intra-chunk work of ALL chunks is four batched products; the carried state goes
through one `lax.scan` of T / Q short steps (64 at 8192 tokens: an elementwise
multiply-add of the [H, P, N] state each).  C . B meets the operands as they are
(bf16 operands multiply exactly into the float32 accumulator); the three
products one of whose operands is float32 (the decayed scores by x, x by B, C by
the state) run at the matrix unit's float32 (`_float32_product`): at its default
a float32 operand is rounded to bf16 first, eight bits of a decay or of the
state.  T need not be a whole number of chunks: the tail is padded with steps
of zero (dt = 0: decay 1, nothing enters), which leave the state alone.

Two paths, one rule (`_scan_path`, from what the lowering observes: the
platform, the mesh, the shapes; nothing a process or a program can set).

"kernels", on the TPU where the chunk and the state are whole lane tiles and
a group's heads whole slabs of 128 channels (Nemotron-3-Super's: chunks of 128,
N 128, sixteen heads of 64 a group): the two Pallas kernels of
`ops/ssd_kernels.py` under a `jax.custom_vjp` (`kernel_ssd_scan`).  A grid
step is a (row, GROUP, chunk): C . B is made once and shared by the group's
heads, a head's [Q, Q] decays and decayed scores live and die in VMEM, the
states are carried in VMEM scratch from chunk to chunk, and x, B, C and y are
read and written as the program lays them out.  Of the three products with a
float32 operand the kernels know which operand is bf16 EXACTLY (x, B, C,
backward's d y) and send the float32 one to the matrix unit as its three bf16
pieces: `_float32_product`'s six passes less the three that multiply zeros.
What the kernels read in place of Dt, ALog and DtBias is the step and the
cumulative decay, [b, T, H] float32, made by XLA with this module's own lines
(`_decays`), and backward hands XLA their cotangents to transpose.  Forward
where it is differentiated KEEPS the seven inputs and the float32 state every
chunk starts from ([chunks, b, H, P, N]: 268 MB a row of 8192 tokens of 128
heads), and the transposed kernel makes a chunk's scores and decays again from
them, the chunks in reverse, the state's cotangent in VMEM.  A
`recompute_scope` round the layer is offered the output and those start states
under one name (`_kept_ssd`, as `selective_scan`'s): where `plan_kept`'s budget
holds them the segment runs no second forward.

"xla", everywhere else (the CPU, odd shapes, a mesh that splits more than the
rows; what the tests and `tools/chip_nemotron_controls.py`'s copy hold the
kernels to): `chunked_ssd_scan`, the chunked form above in plain `jax.numpy`.
Backward is `jax.vjp` over it like every other op's (core/lowering.py): each
product's two transposes are products of the same shapes, and what backward
reads of a chunk ([chunks, H, Q, Q] float32: the decayed scores) is dearer to
hold than to make, so a `recompute_scope` round the layer keeps nothing of it.

Under a mesh whose batch axis splits the rows and nothing else the whole op runs
in a `shard_map` over that axis (`ops.common.over_batch_shards`), as
`selective_scan` does: a chip scans its own rows by either path (a
`pallas_call` cannot be partitioned), and GSPMD is not asked how to split a
loop over the sequence.
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
from . import ssd_kernels
from .common import batch_shards, counted_rules, first, kept_residuals, operand_of, over_batch_shards, residuals_name

#: Tokens a chunk where the op's attribute gives none (Mamba-2's `chunk_size`).
CHUNK = 128
#: What the padded tail's step projection holds: softplus of it is exactly 0 in
#: float32 and so is its slope (`ssm_ops._NO_STEP`).
_NO_STEP = -1e4


def _float32_product(spec, left, right):
    """An einsum one of whose operands is float32, at the matrix unit's float32."""
    return jnp.einsum(spec, left, right, precision=jax.lax.Precision.HIGHEST, preferred_element_type=jnp.float32)


def chunked_ssd_scan(x, dt, a_log, b_t, c_t, d_skip, dt_bias, groups, chunk=CHUNK):
    """(y [b, T, H P] in x's dtype, the state after the last token [b, H, P, N]
    float32, (the mean decay exp(dt A) a token and head, the mean step)) of the
    recurrence in the module's docstring over x [b, T, H P], dt [b, T, H], B and
    C [b, T, G N], `chunk` tokens at a time."""
    batch, T, width = x.shape
    heads, G = a_log.shape[0], int(groups)
    P, N, per = width // heads, b_t.shape[-1] // G, heads // G
    Q = min(int(chunk), T)
    n = -(-T // Q)
    pad = n * Q - T

    def chunks(t, fill=0.0):   # [b, T, .] -> [b, n, Q, .]
        if pad:
            t = jnp.pad(t, ((0, 0), (0, pad), (0, 0)), constant_values=fill)
        return t.reshape(batch, n, Q, t.shape[-1])

    with jax.named_scope("ssd_scan"):
        A = -jnp.exp(a_log.astype(jnp.float32))                                      # [H]
        step = jax.nn.softplus(chunks(dt, _NO_STEP).astype(jnp.float32) + dt_bias.astype(jnp.float32))   # [b, n, Q, H]
        log_decay = step * A
        cum = jnp.cumsum(log_decay, axis=2)                                          # [b, n, Q, H], <= 0
        x_c = chunks(x).reshape(batch, n, Q, G, per, P)
        b_c = chunks(b_t).reshape(batch, n, Q, G, N)
        c_c = chunks(c_t).reshape(batch, n, Q, G, N)

        # intra-chunk: (C_i . B_j) exp(cum_i - cum_j) dt_j for j <= i, then by x
        by_head = cum.transpose(0, 1, 3, 2).reshape(batch, n, G, per, Q)             # [b, n, G, H/G, Q]
        below = jnp.tril(jnp.ones((Q, Q), bool))
        apart = jnp.where(below, by_head[..., :, None] - by_head[..., None, :], -jnp.inf)
        scores = jnp.einsum("bnigs,bnjgs->bngij", c_c, b_c, preferred_element_type=jnp.float32)
        enters = step.transpose(0, 1, 3, 2).reshape(batch, n, G, per, 1, Q)          # dt_j, along the keys
        mixed = scores[:, :, :, None] * jnp.exp(apart) * enters                      # [b, n, G, H/G, Q, Q]
        y = _float32_product("bnghij,bnjghp->bnighp", mixed, x_c.astype(jnp.float32))

        # what each chunk adds to the state, and the state each chunk starts from
        last = cum[:, :, -1:, :]                                                     # [b, n, 1, H]
        weight = (jnp.exp(last - cum) * step).reshape(batch, n, Q, G, per)
        added = _float32_product("bnjghp,bnjgs->bnghps", x_c.astype(jnp.float32) * weight[..., None],
                                 b_c.astype(jnp.float32))
        through = jnp.exp(last[:, :, 0]).reshape(batch, n, G, per)                   # the chunk's whole decay

        def carry(h, part):
            decay, more = part
            return decay[..., None, None] * h + more, h

        h0 = jnp.zeros((batch, G, per, P, N), jnp.float32)
        final, starts = jax.lax.scan(carry, h0, (through.swapaxes(0, 1), added.swapaxes(0, 1)))
        from_start = _float32_product("bnigs,bnghps->bnighp", c_c.astype(jnp.float32), starts.swapaxes(0, 1))
        y = y + from_start * jnp.exp(cum).reshape(batch, n, Q, G, per)[..., None]

        y = y.reshape(batch, n * Q, width)[:, :T]
        skip = jnp.repeat(d_skip.astype(jnp.float32), P)
        y = (y + skip * x.astype(jnp.float32)).astype(x.dtype)
        real = float(batch * T * heads)
        # the padded steps decay by exactly 1 and step by exactly 0
        means = ((jnp.sum(jnp.exp(log_decay)) - float(batch * pad * heads)) / real, jnp.sum(step) / real)
    return y, final.reshape(batch, heads, P, N), means


def _scan_path(platform, mesh, x, a_log, b_t, groups, chunk, batch_axis=None):
    """How the op is lowered: "kernels" (`ops/ssd_kernels.py`: a group's scores
    and its heads' states in VMEM, forward and transposed) on the TPU where the
    chunk (the row's own length where that is shorter) and the state N are whole
    lane tiles, the heads a whole number of groups and a group's heads a whole
    number of the slabs whose channels fill a lane tile
    (`ssd_kernels.heads_a_slab`: P a multiple of the sublane tile that divides
    128, or a multiple of 128), and `batch_shards` is not 0: no mesh, one
    device, or a mesh that splits the rows alone, where the kernels run on a
    chip's rows inside the `shard_map` `over_batch_shards` opens (a
    `pallas_call` cannot be partitioned); else "xla", `chunked_ssd_scan`: the
    CPU's path, the odd shapes', any mesh's that splits more than the rows
    (GSPMD partitions the plain form by itself), and what the tests and
    `tools/chip_nemotron_controls.py`'s copy hold the kernels to.  From what the
    lowering observes alone: nothing a process or a program can set."""
    heads, G = a_log.shape[0], int(groups)
    if platform != "tpu" or heads % G or x.shape[-1] % heads or b_t.shape[-1] % G:
        return "xla"
    P, N, Q = x.shape[-1] // heads, b_t.shape[-1] // G, min(int(chunk), x.shape[1])
    whole = Q % ssd_kernels.LANES == 0 and N % ssd_kernels.LANES == 0 and P % 8 == 0 and ssd_kernels.heads_a_slab(heads // G, P)
    return "kernels" if whole and batch_shards(mesh, batch_axis, x.shape[0]) else "xla"


def _decays(dt, a_log, dt_bias, pad, chunk):
    """(the float32 step softplus(Dt + DtBias) and the decay's logarithm summed
    along each chunk, [b, T + pad, H]; the decay's logarithm a token) as
    `chunked_ssd_scan` makes them, the padded tail stepping by exactly 0: what
    the kernels read in place of Dt, ALog and DtBias."""
    batch, T, heads = dt.shape
    if pad:
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)), constant_values=_NO_STEP)
    step = jax.nn.softplus(dt.astype(jnp.float32) + dt_bias.astype(jnp.float32))
    log_decay = step * -jnp.exp(a_log.astype(jnp.float32))
    cum = jnp.cumsum(log_decay.reshape(batch, -1, chunk, heads), axis=2).reshape(step.shape)
    return step, cum, log_decay


def _kernel_operands(x, b_t, c_t, chunk):
    """(x, B, C padded to a whole number of chunks; `_decays` as a function of
    Dt, ALog and DtBias; the chunk; the padded tokens)."""
    T = x.shape[1]
    Q = min(int(chunk), T)
    pad = -T % Q
    if pad:
        x, b_t, c_t = (jnp.pad(t, ((0, 0), (0, pad), (0, 0))) for t in (x, b_t, c_t))
    return (x, b_t, c_t), functools.partial(_decays, pad=pad, chunk=Q), Q, pad


def _kernel_scan(x, dt, a_log, b_t, c_t, d_skip, dt_bias, groups, chunk, kernels, keep):
    """(`chunked_ssd_scan`'s three results, what backward reads: the chunks'
    start states where they are kept) of the kernels."""
    batch, T, _ = x.shape
    heads = a_log.shape[0]
    with jax.named_scope("ssd_scan"):
        (xs, bs, cs), decays, Q, pad = _kernel_operands(x, b_t, c_t, chunk)
        step, cum, log_decay = decays(dt, a_log, dt_bias)
        y, final, *kept = ssd_kernels.scan(xs, bs, cs, step, cum, d_skip.astype(jnp.float32), Q, int(groups), keep, kernels == "interpret")
        real = float(batch * T * heads)
        # the padded steps decay by exactly 1 and step by exactly 0
        means = ((jnp.sum(jnp.exp(log_decay)) - float(batch * pad * heads)) / real, jnp.sum(step) / real)
    return (y[:, :T], ssd_kernels.heads_first(final, x.shape[-1] // heads), means), tuple(kept)


@functools.partial(jax.custom_vjp, nondiff_argnums=(7, 8, 9, 10))
def kernel_ssd_scan(x, dt, a_log, b_t, c_t, d_skip, dt_bias, groups, chunk=CHUNK, kernels="tpu", keep=None):
    """`chunked_ssd_scan`'s results from the Pallas kernels of
    `ops/ssd_kernels.py` (`_scan_path` says when the op comes here); `kernels`:
    "tpu", or "interpret" for those interpreted (the tests').  The final state
    and the means are for statistics: backward takes no cotangent for them."""
    return _kernel_scan(x, dt, a_log, b_t, c_t, d_skip, dt_bias, groups, chunk, kernels, False)[0]


def _kernel_scan_fwd(x, dt, a_log, b_t, c_t, d_skip, dt_bias, groups, chunk, kernels, keep):
    """The op where it is differentiated: forward keeps the chunks' start states ([chunks, b, H, P, N] float32 in the
    kernels' own tiles) beside the seven inputs.  `keep` names the two values that only the forward kernel makes and
    backward reads, the output (the mixer's gate reads it) and the start states: a `jax.checkpoint` round the op whose
    policy saves the name (`core/lowering.py: plan_kept`) then runs no second forward."""
    _MON.counter("lowering.ssd_scan_starts_kept").inc()
    (y, final, means), (starts,) = _kernel_scan(x, dt, a_log, b_t, c_t, d_skip, dt_bias, groups, chunk, kernels, True)
    if keep:
        y, starts = checkpoint_name(y, keep), checkpoint_name(starts, keep)
    return (y, final, means), ((x, dt, a_log, b_t, c_t, d_skip, dt_bias), (starts,))


def _kernel_scan_bwd(groups, chunk, kernels, keep, residuals, cotangents):
    (x, dt, a_log, b_t, c_t, d_skip, dt_bias), (starts,) = residuals
    _MON.counter("lowering.ssd_scan_kernel_transposed_calls").inc()
    T = x.shape[1]
    with jax.named_scope("ssd_scan"):
        (xs, bs, cs), decays, Q, pad = _kernel_operands(x, b_t, c_t, chunk)
        (step, cum, _), transpose = jax.vjp(decays, dt, a_log, dt_bias)
        d_y = jnp.pad(cotangents[0], ((0, 0), (0, pad), (0, 0))) if pad else cotangents[0]
        dx, db, dc, dstep, dcum, dskip = ssd_kernels.scan_transposed(
            xs, bs, cs, step, cum, d_skip.astype(jnp.float32), d_y, starts, Q, int(groups), kernels == "interpret")
        # the step's softplus, the chunk's sum and A = -exp(ALog) are plain lines of 4 MB: XLA transposes them
        ddt, d_a_log, dbias = transpose((dstep, dcum, jnp.zeros_like(step)))
    return dx[:, :T], ddt, d_a_log, db[:, :T], dc[:, :T], dskip.astype(d_skip.dtype), dbias


kernel_ssd_scan.defvjp(*counted_rules("ssd_scan", _kernel_scan_fwd, _kernel_scan_bwd))


@register_op("ssd_scan")
def _ssd_scan(ctx, op, ins):
    """The chunked recurrence over X [b, T, H P], Dt [b, T, H], B, C [b, T, G N]
    with ALog, D and DtBias [H] (float32); attributes `groups` (G) and `chunk`.
    `State` [b, H, P, N] is the float32 state after a row's last token (what a
    decoder would go on from).
    `Stats` [3] is the step's health, read on logged steps: the mean decay
    exp(dt A), the mean step dt and the largest |h| of the state after the last
    token."""
    x, dt, a_log, b_t, c_t, d_skip, dt_bias = (first(ins, s) for s in ("X", "Dt", "ALog", "B", "C", "D", "DtBias"))
    groups, chunk = op.attr("groups", 1), op.attr("chunk", CHUNK)
    _MON.counter("lowering.ssd_scan_ops").inc()
    _MON.counter("lowering.ssd_scan_chunks").inc(-(-x.shape[1] // min(chunk, x.shape[1])))
    shards = batch_shards(ctx.mesh, ctx.batch_axis, x.shape[0])
    # "interpret" is the tests': the kernels interpreted where no chip is
    kernels = {"kernels": "tpu", "interpret": "interpret"}.get(
        _scan_path(ctx.platform, ctx.mesh, x, a_log, b_t, groups, chunk, ctx.batch_axis))
    _MON.counter("lowering.ssd_scan_kernel_calls").inc(1 if kernels else 0)
    keep = kept_residuals(ctx, op)

    def scan(x, dt, b_t, c_t, a_log, d_skip, dt_bias):
        if kernels:
            y, final, (decay, step) = kernel_ssd_scan(x, dt, a_log, b_t, c_t, d_skip, dt_bias, groups, chunk, kernels, keep)
        else:
            y, final, (decay, step) = chunked_ssd_scan(x, dt, a_log, b_t, c_t, d_skip, dt_bias, groups, chunk)
        decay, step, largest = jax.lax.stop_gradient((decay, step, jnp.max(jnp.abs(final))))
        if shards > 1:   # a chip's rows: the means of equal shares, the largest of all
            decay, step = (jax.lax.pmean(t, ctx.batch_axis) for t in (decay, step))
            largest = jax.lax.pmax(largest, ctx.batch_axis)
        return y, final, jnp.broadcast_to(jnp.stack([decay, step, largest]), (x.shape[0], 3))

    # The scan reads the VARIABLES, as `selective_scan` does: fused with their producers it would read xs, B and C
    # before their rounding to bf16, and what the program fetches of them would not be what the scan read
    x, dt, b_t, c_t = jax.lax.optimization_barrier((x, dt, b_t, c_t))
    batched, whole = (x, dt, b_t, c_t), (a_log, d_skip, dt_bias)
    y, final, stats = over_batch_shards(ctx, scan, batched, whole) if shards > 1 else scan(*batched, *whole)
    return {"Out": y, "State": final, "Stats": stats[0]}


def _publish_ssd_state(step, values):
    """One logged step's `ssd_state` record: per layer the mean decay exp(dt A)
    a token and head, the mean step dt and the largest |h| after the last token;
    the layers' mean decay and the worst layer's state as gauges.  A health
    check, as `ssm_state` is: a decay at 1 forgets nothing and the state grows
    with the sequence; at 0 the layer reads one token, and neither is a test of
    a scan."""
    stats = np.stack([np.asarray(s, "f8").reshape(3) for s in values["Stats"]])
    _MON.gauge("ssd.decay_mean").set(float(stats[:, 0].mean()))
    _MON.gauge("ssd.state_abs_max").set(float(stats[:, 2].max()))
    _MON.record_step({"kind": "ssd_state", "pipeline_step": step, "decay_mean": stats[:, 0].tolist(),
                      "dt_mean": stats[:, 1].tolist(), "state_abs_max": stats[:, 2].tolist(),
                      "worst_layer": int(np.argmax(stats[:, 2]))})


set_step_stats("ssd_scan", ("Stats",), _publish_ssd_state)


def _infer_ssd_scan(ctx):
    x, dt, a_log, b_t, c_t = (ctx.in_shape(s) for s in ("X", "Dt", "ALog", "B", "C"))
    if x is None or a_log is None:
        return
    groups = ctx.op.attr("groups", 1)
    if len(x) != 3 or len(a_log) != 1 or x[-1] % a_log[0]:
        ctx.fail(f"X must be (b, T, H P) and ALog (H,), got {x} and {a_log}")
    if groups < 1 or a_log[0] % groups:
        ctx.fail(f"groups {groups} does not divide the {a_log[0]} heads")
    if ctx.op.attr("chunk", CHUNK) < 1:
        ctx.fail(f"chunk {ctx.op.attr('chunk')} tokens")
    if dt is not None and tuple(dt) != tuple(x[:2]) + (a_log[0],):
        ctx.fail(f"Dt holds one step for each of the {a_log[0]} heads, (b, T, H) = {tuple(x[:2]) + (a_log[0],)}, got {dt}")
    for name, t in (("B", b_t), ("C", c_t)):
        if t is not None and (tuple(t[:2]) != tuple(x[:2]) or t[-1] % groups):
            ctx.fail(f"{name} must be (b, T, G N) with G = {groups}, got {t}")
    if b_t is not None and c_t is not None and tuple(b_t) != tuple(c_t):
        ctx.fail(f"B {b_t} and C {c_t} must have one shape")
    for name in ("D", "DtBias"):
        t = ctx.in_shape(name)
        if t is not None and tuple(t) != (a_log[0],):
            ctx.fail(f"{name} must be (H,) = ({a_log[0]},), got {t}")
    ctx.set_out("Out", x, ctx.in_dtype("X"))
    if b_t is not None:
        ctx.set_out("State", (x[0], a_log[0], x[-1] // a_log[0], b_t[-1] // groups), "float32")
    ctx.set_out("Stats", (3,), "float32")


_A.register_rule(["ssd_scan"], _infer_ssd_scan)


def ssd_scan_flops(tokens, heads, head_dim, state, groups, chunk=CHUNK):
    """Multiply-adds x 2 of the chunked form's forward over `tokens` positions:
    a token's C . B against the chunk's keys once a GROUP (Q N), the decayed
    scores by x (Q P a head), x by B into the state and C by the state (P N a
    head each)."""
    return 2.0 * tokens * (groups * chunk * state + heads * (chunk * head_dim + 2 * head_dim * state))


def _cost_ssd_scan(ctx):
    x, a_log, b_t = ctx.in_shape("X"), ctx.in_shape("ALog"), ctx.in_shape("B")
    if x is None or a_log is None or b_t is None or len(x) != 3:
        return float(ctx.out_elems_total()), ctx.io_bytes()
    groups = ctx.op.attr("groups", 1)
    chunk = min(ctx.op.attr("chunk", CHUNK), max(x[1], 1))
    return ssd_scan_flops(x[0] * x[1], a_log[0], x[2] // a_log[0], b_t[-1] // groups, groups, chunk), ctx.io_bytes()


_RP.register_cost(["ssd_scan"], _cost_ssd_scan)


def _kept_ssd(ctx, op, shapes):
    """Where the op takes the kernels: its output and the float32 state every
    chunk starts from, [chunks, b, H, P, N] (what only the forward kernel makes
    and the transposed one reads; the chunk's scores and decays are made again
    in VMEM from the op's inputs).  The plain form offers nothing: backward
    reads its chunks' decayed scores ([b, chunks, H, Q, Q] float32, 0.5 GB a row
    of 8192 tokens of 128 heads), which are dearer to hold than to make."""
    x, a_log, b_t = (operand_of(shapes, op.input(slot)[0]) for slot in ("X", "ALog", "B"))
    groups, chunk = op.attr("groups", 1), op.attr("chunk", CHUNK)
    if _scan_path(ctx.platform, ctx.mesh, x, a_log, b_t, groups, chunk, ctx.batch_axis) == "xla":
        return None
    batch, tokens, width = x.shape
    starts_bytes = 4 * -(-tokens // min(chunk, tokens)) * batch * width * (b_t.shape[-1] // groups)
    return residuals_name(op), shapes.nbytes(op.output("Out")[0]) + starts_bytes


set_kept("ssd_scan", _kept_ssd)
