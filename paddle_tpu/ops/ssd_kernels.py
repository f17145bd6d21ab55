"""Pallas TPU kernels for the scalar-decay scan (`ops/ssd_ops.py` has the
recurrence and the chunked form's equations; `_scan_path` there sends the op
here).

A grid step is a (row, GROUP of heads, chunk of Q tokens), the chunks in order
and the chunk axis "arbitrary": the group's float32 states are carried in VMEM
scratch from chunk to chunk, and a chunk's scores, decays and decayed scores
([Q, Q] float32 a head) never leave the chip.  The step reads x and y as the
program lays them out, the group's heads as they stand in `[b, T, H P]` (a
block of Q tokens by H / G . P lanes) and the group's B and C `[Q, N]` of
`[b, T, G N]`: no copy at the call's edges.  C . B is made once a chunk and
shared by the group's heads.

Inside a grid step a loop runs over the group's SLABS: the `heads_a_slab`
neighbouring heads whose channels fill a lane tile (two heads of 64 channels),
so that every array a slab touches is [., 128] wide and a state-sized product
fills the matrix unit's columns.  A slab's states lie TRANSPOSED, [N, heads P]:
C by the state, B's transpose by the weighted x and their four transposes are
then products of plain [rows, inner] by [inner, lanes] operands, and what is one
head's (the [Q, Q] decays) is made a head at a time and laid into the head's
lanes.

The step softplus(Dt + DtBias) and the decay's logarithm summed along a chunk
are [b, T, H] float32, made by XLA outside the kernels (`ssd_ops._decays`: the
plain form's own lines, 4 MB) and handed in BOTH orientations: tokens down the
sublanes ([b, T, H]: a head's column comes out by a lane rotation) and tokens
along the lanes ([b, n, G, H / G, Q]: a head's row is a sublane); the transposed
kernel hands their cotangents back along the lanes and XLA transposes the
plain lines (softplus, cumsum, A = -exp(ALog)).

  * `scan`: y in x's dtype and the states after the last token; with `keep`
    (the op where it is differentiated) the states every chunk STARTS from,
    [n, b, slabs, N, heads P] float32 as the scratch lies;
  * `scan_transposed`: the chunks in REVERSE order, the state's cotangent
    carried in scratch as the state is forward; a chunk's scores and decays are
    made again (transposed, [keys, queries]: every product then meets its
    operands as they lie) from x, B, C, the step and the kept start state; dX,
    dB and dC (summed over the group's heads in float32 scratch, rounded once),
    the step's and the cumulative decay's cotangents and D's partial sums out.

Precision is the op's: the state, the cumulative decay, every accumulator and
`exp` are float32 and no exponent is positive (the mask is on the exponent's
argument).  A product's float32 operand goes to the matrix unit as the three
bf16 pieces that add up to it and a bf16 operand as it is (`_dot`): of the six
one-pass products that `Precision.HIGHEST` makes of two float32 operands, the
three that multiply an exact operand's empty pieces are left out, the others
are HIGHEST's own.  A padded token steps by exactly 0 and leaves the state
alone.  Each call is a `jax.jit` of its own so that a model's layers, the step
and its `for_test` clone share one lowering.

TPU v5e, a row of 8192 tokens of 128 heads of 64, N 128, 8 groups, chunks of
128, bf16 (own device ms, my chip run, PR 61, call 1; `ONLY=profile python3
tools/chip_nemotron_controls.py`): forward 2.88 (the plain form 8.80), the
forward that keeps the start states the same kernel, transposed 4.23 (the plain form's `jax.vjp` with its forward 19.01).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

F32, BF16 = jnp.float32, jnp.bfloat16
_NN = (((1,), (0,)), ((), ()))
_NT = (((1,), (1,)), ((), ()))
LANES = 128

_SEMANTICS = pltpu.CompilerParams(dimension_semantics=("parallel", "parallel", "arbitrary"), vmem_limit_bytes=48 * 2 ** 20)


def heads_a_slab(per, P):
    """Heads of a group (of `per`) whose `P` channels fill a lane tile: a slab
    of the kernels' loop; 0 where the channels fit no whole tiles."""
    if P % LANES == 0:
        return 1
    heads = LANES // P
    return heads if LANES % P == 0 and per % heads == 0 else 0


def heads_first(t, P):
    """The kernels' states [..., slabs, N, heads P] as the op's [..., H, P, N]."""
    *lead, slabs, N, width = t.shape
    t = jnp.moveaxis(t.reshape(*lead, slabs, N, width // P, P), -3, -1)
    return t.reshape(*lead, slabs * (width // P), P, N)


def _iota(shape, axis):
    return jax.lax.broadcasted_iota(jnp.int32, shape, axis)


def _pieces(a):
    """A product's operand as the bf16 arrays that add up to it: itself where it
    is bf16, else its three pieces (what is left of float32 after them is under
    2^-24 of it)."""
    if isinstance(a, tuple):      # split already
        return a
    if a.dtype == BF16:
        return (a,)
    a = a.astype(F32)
    hi = a.astype(BF16)
    rest = a - hi.astype(F32)
    mid = rest.astype(BF16)
    return hi, mid, (rest - mid.astype(F32)).astype(BF16)


def _dot(a, b, dims=_NN, widen=False):
    """`a` by `b` to float32's bits: the one-pass products of their pieces whose
    orders add up to at most two (`Precision.HIGHEST`'s six where both are
    float32, three where one is bf16, one where both are), the smallest first.
    `widen` (the interpreter's: the CPU has no bf16 product) multiplies the same
    pieces as float32, which is what a pass of the matrix unit gives of them."""
    a, b = _pieces(a), _pieces(b)
    if widen:
        a, b = (tuple(piece.astype(F32) for piece in pieces) for pieces in (a, b))
    pairs = sorted(((i, j) for i in range(len(a)) for j in range(len(b)) if i + j < 3), key=lambda p: -(p[0] + p[1]))
    return sum(jax.lax.dot_general(a[i], b[j], dims, precision=jax.lax.Precision.HIGHEST if widen else None,
                                   preferred_element_type=F32) for i, j in pairs)


class _Slab:
    """What a slab's heads read of the step and the cumulative decay, a chunk:
    `col[k]` [Q, 1] and `row[k]` [1, Q] of head k, and a column spread over its
    head's lanes [Q, heads P] (`spread`)."""

    def __init__(self, s, group, heads, P, per, step_cols, cum_cols, step_rows, cum_rows):
        self.heads, self.P = heads, P
        self.first = s * heads                                        # the slab's first head, of the group's
        padded = step_cols.shape[-1]
        shift = (padded - (group * per + self.first)) % padded
        step_n, cum_n = (pltpu.roll(ref[0], shift, 1) for ref in (step_cols, cum_cols))   # the slab's heads to lanes 0 ..
        self.step_col = [step_n[:, k:k + 1] for k in range(heads)]
        self.cum_col = [cum_n[:, k:k + 1] for k in range(heads)]
        self.step_row = [step_rows[0, 0, 0, pl.ds(self.first + k, 1), :] for k in range(heads)]
        self.cum_row = [cum_rows[0, 0, 0, pl.ds(self.first + k, 1), :] for k in range(heads)]
        self.lane_head = _iota((1, heads * P), 1) // P
        self.step, self.cum = self.spread(self.step_col), self.spread(self.cum_col)
        self.last = self.cum[-1:]                                      # [1, heads P]: the chunk's whole decay, its logarithm

    def spread(self, cols):
        out = cols[-1]
        for k in reversed(range(self.heads - 1)):
            out = jnp.where(self.lane_head == k, cols[k], out)
        return jnp.broadcast_to(out, (out.shape[0], self.heads * self.P))

    def own(self, k, t, other=0.0):
        """`t` in head k's lanes, `other` elsewhere."""
        return t if self.heads == 1 else jnp.where(self.lane_head == k, t, other)

    def sums(self, t):
        """[Q, heads P] summed over each head's lanes: a list of [Q, 1]."""
        return [jnp.sum(self.own(k, t), axis=1, keepdims=True) for k in range(self.heads)]


def _scan_kernel(heads, P, dot, x_ref, b_ref, c_ref, step_cols, cum_cols, step_rows, cum_rows, skip_ref, y_ref, final_ref, *rest):
    """`dot`: `_dot`, widened where the kernel is interpreted.  `rest`: the block of the chunk's start states where they
    are kept, then the states' scratch [slabs, N, heads P]."""
    *kept, state = rest
    Q, W, per, group = x_ref.shape[1], heads * P, step_rows.shape[3], pl.program_id(1)

    @pl.when(pl.program_id(2) == 0)
    def _():
        state[...] = jnp.zeros_like(state)

    if kept:
        kept[0][0, 0] = state[...]
    B, C = b_ref[0], c_ref[0]
    scores = dot(C, B, _NT)                                           # [queries, keys], the group's
    b_transposed = B.astype(F32).T.astype(B.dtype)
    below = _iota((Q, Q), 0) >= _iota((Q, Q), 1)
    c_pieces = _pieces(C)

    def slab(s, _):
        lanes = pl.ds(pl.multiple_of(s * W, W), W)
        of = _Slab(s, group, heads, P, per, step_cols, cum_cols, step_rows, cum_rows)
        x = x_ref[0, :, lanes]
        y = None
        for k in range(heads):
            apart = jnp.where(below, of.cum_col[k] - of.cum_row[k], -jnp.inf)
            own = dot(scores * jnp.exp(apart) * of.step_row[k], x)
            y = own if y is None else of.own(k, own, y)
        h = state[s]
        x32 = x.astype(F32)
        y = y + jnp.exp(of.cum) * dot(c_pieces, h) + skip_ref[:, lanes] * x32
        y_ref[0, :, lanes] = y.astype(y_ref.dtype)
        weight = jnp.exp(of.last - of.cum) * of.step
        state[s] = jnp.exp(of.last) * h + dot(b_transposed, x32 * weight)
        return 0

    jax.lax.fori_loop(0, state.shape[0], slab, 0)

    @pl.when(pl.program_id(2) == pl.num_programs(2) - 1)
    def _():
        final_ref[0] = state[...]


def _transposed_kernel(heads, P, dot, x_ref, b_ref, c_ref, step_cols, cum_cols, step_rows, cum_rows, skip_ref, dy_ref, starts_ref,
                       dx_ref, db_ref, dc_ref, dstep_ref, dcum_ref, dlast_ref, dskip_ref,
                       after, d_scores, d_b, d_c, to_step, to_cum, to_cum_rows):
    """Scratch: the cotangent of the states the chunk ENDS in [slabs, N, heads P]; the group's sums over its heads, the
    scores' cotangent [keys, queries] and dB's and dC's state parts [Q, N]; the step's and the cumulative decay's
    cotangents a head, columns [Q, lanes >= H / G] and (the decay's other part) rows [H / G, Q]."""
    Q, W, per, group = x_ref.shape[1], heads * P, step_rows.shape[3], pl.program_id(1)

    @pl.when(pl.program_id(2) == 0)
    def _():
        after[...] = jnp.zeros_like(after)
        dskip_ref[...] = jnp.zeros_like(dskip_ref)

    for ref in (d_scores, d_b, d_c, to_step, to_cum):
        ref[...] = jnp.zeros_like(ref)
    B, C = b_ref[0], c_ref[0]
    b_pieces, c_pieces = _pieces(B), _pieces(C)
    c_transposed = C.astype(F32).T.astype(C.dtype)
    scores = dot(b_pieces, c_transposed)                              # [keys, queries]
    above = _iota((Q, Q), 1) >= _iota((Q, Q), 0)                       # the query at or after the key
    head_lane = _iota((1, to_step.shape[1]), 1)

    def slab(s, _):
        lanes = pl.ds(pl.multiple_of(s * W, W), W)
        of = _Slab(s, group, heads, P, per, step_cols, cum_cols, step_rows, cum_rows)
        x, dy = x_ref[0, :, lanes], dy_ref[0, :, lanes]
        x32, dy32 = x.astype(F32), dy.astype(F32)
        start, ends = _pieces(starts_ref[0, 0, s]), after[s]           # the state the chunk starts from; d of the one it ends in
        end_pieces = _pieces(ends)
        to_end = jnp.exp(of.last - of.cum)
        through = dot(b_pieces, end_pieces)                           # [keys, heads P]: d (weight x), the state's part
        moved = to_end * through                                       # d x / step: the state's part, then the chunk's own
        for k in range(heads):
            decay = jnp.exp(jnp.where(above, of.cum_row[k] - of.cum_col[k], -jnp.inf))      # [keys, queries]
            d_mixed = dot(of.own(k, x32).astype(x.dtype), dy, _NT)
            d_own = d_mixed * decay * of.step_col[k]
            d_scores[...] += d_own
            to_cum_rows[pl.ds(of.first + k, 1), :] = jnp.sum(d_own * scores, axis=0, keepdims=True)
            moved = moved + of.own(k, dot(scores * decay, dy))
        dx_ref[0, :, lanes] = (of.step * moved + skip_ref[:, lanes] * dy32).astype(dx_ref.dtype)
        dskip_ref[0, :, lanes] += jnp.sum(dy32 * x32, axis=0, keepdims=True)
        weighted = x32 * (to_end * of.step)
        whole = jnp.exp(of.last)
        dlast_ref[0, 0, :, lanes] = (jnp.sum(ends * whole * starts_ref[0, 0, s], axis=0, keepdims=True)
                                     + jnp.sum(weighted * through, axis=0, keepdims=True))
        from_start = jnp.exp(of.cum)
        read = dot(c_pieces, start)                                   # [queries, heads P]: C . h
        to_steps, to_starts = of.sums(x32 * moved), of.sums(dy32 * read)
        for k in range(heads):
            here = head_lane == of.first + k
            to_step[...] = jnp.where(here, to_steps[k], to_step[...])
            to_cum[...] = jnp.where(here, jnp.exp(of.cum_col[k]) * to_starts[k] - of.step_col[k] * to_steps[k], to_cum[...])
        reads = _pieces(from_start * dy32)
        after[s] = whole * ends + dot(c_transposed, reads)
        d_c[...] += dot(reads, start, _NT)
        d_b[...] += dot(weighted, end_pieces, _NT)
        return 0

    jax.lax.fori_loop(0, after.shape[0], slab, 0)
    summed = d_scores[...]
    db_ref[0] = (d_b[...] + dot(summed, c_pieces)).astype(db_ref.dtype)
    dc_ref[0] = (d_c[...] + dot(summed.T, b_pieces)).astype(dc_ref.dtype)
    dstep_ref[0, 0, 0] = to_step[...].T[:per]
    dcum_ref[0, 0, 0] = to_cum_rows[...][:per] + to_cum[...].T[:per]


def _cost(b, T, H, P, N, G, chunk, passes, bytes_a_token, state_bytes):
    a_token = G * chunk * N + H * (chunk * P + 2 * P * N)
    return pl.CostEstimate(flops=int(2 * passes * b * T * a_token), transcendentals=int(b * T * H * (chunk + 4 * P)),
                           bytes_accessed=int(b * T * bytes_a_token + state_bytes))


def _operands(x, step, cum, d_skip, chunk, groups):
    """(the sizes: rows, tokens, heads, channels a head, chunks, heads a group
    and a slab (a whole group where its channels fill no lane tile: the
    interpreted tests' small shapes); the kernels' operands after x, B, C: the
    step and the cumulative decay with the tokens down [b, T, heads up to whole
    lane tiles] and along [b, n, G, H / G, Q], D a channel [1, H P])."""
    (b, T, width), H = x.shape, step.shape[-1]
    P, n, per = width // H, T // chunk, H // groups
    more = -H % LANES
    cols = [jnp.pad(t, ((0, 0), (0, 0), (0, more))) if more else t for t in (step, cum)]
    rows = [t.reshape(b, n, chunk, groups, per).transpose(0, 1, 3, 4, 2) for t in (step, cum)]
    return (b, T, H, P, n, per, heads_a_slab(per, P) or per), (*cols, *rows, jnp.repeat(d_skip.astype(F32), P)[None])


@functools.partial(jax.jit, static_argnums=(6, 7, 8, 9))
def scan(x, b_t, c_t, step, cum, d_skip, chunk, groups, keep, interpret):
    """(y [b, T, H P] in x's dtype, the states after the last token [b, H / heads,
    N, heads P] float32: `heads_first` lays them [b, H, P, N]) of x [b, T, H P],
    B, C [b, T, G N], the step and the decay's logarithm summed along each chunk
    [b, T, H] float32 and D [H]; T a whole number of `chunk`s.  With `keep` the
    states every chunk starts from [T / chunk, b, H / heads, N, heads P] after
    them."""
    (b, T, H, P, n, per, heads), small = _operands(x, step, cum, d_skip, chunk, groups)
    N, slabs, W = b_t.shape[-1] // groups, per // heads, heads * P
    tokens = lambda width: pl.BlockSpec((1, chunk, width), lambda i, g, c: (i, c, g))                    # noqa: E731
    cols = pl.BlockSpec((1, chunk, small[0].shape[-1]), lambda i, g, c: (i, c, 0))
    rows = pl.BlockSpec((1, 1, 1, per, chunk), lambda i, g, c: (i, c, g, 0, 0))
    out_specs = [tokens(per * P), pl.BlockSpec((1, slabs, N, W), lambda i, g, c: (i, g, 0, 0))]
    out_shape = [jax.ShapeDtypeStruct(x.shape, x.dtype), jax.ShapeDtypeStruct((b, H // heads, N, W), F32)]
    if keep:
        out_specs.append(pl.BlockSpec((1, 1, slabs, N, W), lambda i, g, c: (c, i, g, 0, 0)))
        out_shape.append(jax.ShapeDtypeStruct((n, b, H // heads, N, W), F32))
    return pl.pallas_call(
        functools.partial(_scan_kernel, heads, P, functools.partial(_dot, widen=interpret)), grid=(b, groups, n),
        in_specs=[tokens(per * P), tokens(N), tokens(N), cols, cols, rows, rows, pl.BlockSpec((1, per * P), lambda i, g, c: (0, g))],
        out_specs=out_specs, out_shape=out_shape, scratch_shapes=[pltpu.VMEM((slabs, N, W), F32)], compiler_params=_SEMANTICS,
        cost_estimate=_cost(b, T, H, P, N, groups, chunk, 3, 2 * x.dtype.itemsize * H * P, 4 * b * H * P * N * (1 + n * keep)),
        name="ssd_scan", interpret=interpret,
    )(x, b_t, c_t, *small)


@functools.partial(jax.jit, static_argnums=(8, 9, 10))
def scan_transposed(x, b_t, c_t, step, cum, d_skip, d_y, starts, chunk, groups, interpret):
    """(dX, dB, dC in their inputs' dtypes; the cotangents of the step and of
    the cumulative decay [b, T, H] and dD [H], float32) of d y [b, T, H P] and
    the chunks' start states that `scan(keep=True)` kept, beside the inputs."""
    (b, T, H, P, n, per, heads), small = _operands(x, step, cum, d_skip, chunk, groups)
    N, slabs, W, wide = b_t.shape[-1] // groups, per // heads, heads * P, -(-per // LANES) * LANES
    tokens = lambda width: pl.BlockSpec((1, chunk, width), lambda i, g, c: (i, n - 1 - c, g))            # noqa: E731  in reverse
    cols = pl.BlockSpec((1, chunk, small[0].shape[-1]), lambda i, g, c: (i, n - 1 - c, 0))
    rows = pl.BlockSpec((1, 1, 1, per, chunk), lambda i, g, c: (i, n - 1 - c, g, 0, 0))
    channels = pl.BlockSpec((1, per * P), lambda i, g, c: (0, g))
    dx, db, dc, dstep, dcum, dlast, dskip = pl.pallas_call(
        functools.partial(_transposed_kernel, heads, P, functools.partial(_dot, widen=interpret)), grid=(b, groups, n),
        in_specs=[tokens(per * P), tokens(N), tokens(N), cols, cols, rows, rows, channels, tokens(per * P),
                  pl.BlockSpec((1, 1, slabs, N, W), lambda i, g, c: (n - 1 - c, i, g, 0, 0))],
        out_specs=[tokens(per * P), tokens(N), tokens(N), rows, rows,
                   pl.BlockSpec((1, 1, 1, per * P), lambda i, g, c: (i, n - 1 - c, 0, g)), pl.BlockSpec((1, 1, per * P), lambda i, g, c: (i, 0, g))],
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype), jax.ShapeDtypeStruct(b_t.shape, b_t.dtype), jax.ShapeDtypeStruct(c_t.shape, c_t.dtype)]
        + [jax.ShapeDtypeStruct((b, n, groups, per, chunk), F32)] * 2
        + [jax.ShapeDtypeStruct((b, n, 1, H * P), F32), jax.ShapeDtypeStruct((b, 1, H * P), F32)],
        scratch_shapes=[pltpu.VMEM((slabs, N, W), F32), pltpu.VMEM((chunk, chunk), F32), pltpu.VMEM((chunk, N), F32), pltpu.VMEM((chunk, N), F32),
                        pltpu.VMEM((chunk, wide), F32), pltpu.VMEM((chunk, wide), F32), pltpu.VMEM((-(-per // 8) * 8, chunk), F32)],
        compiler_params=_SEMANTICS,
        cost_estimate=_cost(b, T, H, P, N, groups, chunk, 8, 3 * x.dtype.itemsize * H * P, 4 * b * H * P * N * n),
        name="ssd_scan_transposed", interpret=interpret,
    )(x, b_t, c_t, *small, d_y, starts)
    by_token = lambda t: t.transpose(0, 1, 4, 2, 3).reshape(b, T, H)                                      # noqa: E731
    # what the chunk's whole decay moved (the state's decay and every token's weight) is its last token's cumulative decay's
    at_last = jnp.pad(dlast.reshape(b, n, 1, H, P).sum(-1), ((0, 0), (0, 0), (chunk - 1, 0), (0, 0))).reshape(b, T, H)
    return dx, db, dc, by_token(dstep), by_token(dcum) + at_last, dskip.reshape(b, H, P).sum((0, 2))
