"""Pallas TPU kernels for the selective scan (`ops/ssm_ops.py` has the
recurrence; `_scan_path` there sends the op here).

A grid step is a (row, block of channels, chunk of tokens), the chunks in order
and the chunk axis "arbitrary": the block's float32 state is carried in VMEM
scratch from chunk to chunk and from token to token, so nothing of [T, N, d]
and no level of an associative scan ever goes to HBM.

The layout.  B_t[n] and C_t[n] are one number a (token, state index), shared by
every channel, and y_t sums over n.  So the state of a block of channels lies as
N TILES of [8, block / 8]: a state index is whole vector registers, its channels
over their sublanes and lanes; B_t[n] and C_t[n] are SCALARS read from SMEM
(the chunk's [C, N] float32, flat); h_n <- exp(dt A_n) h_n + (dt x) B_t[n] is
two products and a sum of whole registers and y_t = sum_n h_n C_t[n] adds N of
them, no reduction over sublanes or lanes anywhere in the recurrence.  What
that costs is a token's row [1, block] of `X` / `Dt` / `Y` (a token is ONE
sublane of the op's own [b, T, d] arrays) brought to [8, block / 8]: eight tokens
at a time, the eight [8 tokens, block / 8] lane slices stacked and their two
leading axes swapped (`_token_major`; Mosaic's own relayout), three of them a
group of eight tokens forward against 128 register-steps of the recurrence.
PERF.md, PR 48, has the op-alone times of this and of what else was tried.

  * `scan`: y in x's dtype, the state after the last token, the sums of the
    decays and of the steps (`Stats`); with `keep` (the op where it is
    differentiated) the state every chunk STARTS from, [T / C, b, d / block, N,
    8, block / 8] float32, as the scratch lies;
  * `scan_transposed`: the chunks in REVERSE order; a grid step makes its
    chunk's states again in VMEM scratch [C + 1, N, 8, block / 8] from the kept
    start state, carries the state's cotangent in scratch, and writes dX and
    dDt (through the softplus' slope), a block's partial of dB and dC a chunk
    (the products' eight sublanes summed by the same swap, their lanes once a
    chunk), and dA, dD and dDtBias accumulated over the chunks in their output
    blocks.

Precision is the op's: everything after the step's bias is float32, every
exponent is <= 0.  A padded token (`ssm_ops._NO_STEP`) steps by exactly 0 and
leaves the state alone.  Each call is a `jax.jit` of its own so that a model's
layers, the step and its `for_test` clone share one lowering.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

F32 = jnp.float32

#: Sublanes of a float32 register: tokens a relayout, and the tokens after
#: which the state is `carried` (the XLA form's chunk, `ssm_ops._SSM_CHUNK`).
GROUP = 8

#: Channels come in whole registers a state index: 8 sublanes of 128 lanes.
UNIT = GROUP * 128
#: Tokens a grid step, and the most channels: a row's own channels where they
#: are no more (`block_of`).  TPU v5e, (1, 8192, 5120) x 16 bf16, ms forward |
#: forward + backward of the op alone (seven gradients; the XLA form 18.4 | 52.2)
#: at tokens:channels 64:1024 2.18 | 9.12, 128:1024 2.11 | 9.06, 256:1024 2.15 |
#: 9.02, 64:5120 1.95 | 7.24, 32:5120 1.89 | 7.29 (my chip run, PR 48, call 1;
#: `KERNELS=64:1024,... python3 tools/chip_jamba_scan.py`): five registers a
#: state index give the transposed kernel four ADDS before each product's sum
#: over its sublanes, and a fifth of the grid's steps; the chunk moves nothing,
#: so it is the one that keeps an eighth of the XLA form's start states.
CHUNK = 64
BLOCK = 5120


def block_of(d):
    """Channels a grid step for rows of `d` (a whole number of `UNIT`s)."""
    return d if d <= BLOCK else UNIT


_SEMANTICS = pltpu.CompilerParams(dimension_semantics=("parallel", "parallel", "arbitrary"), vmem_limit_bytes=64 * 2 ** 20)


def step_of(dt, dt_bias):
    """The float32 step softplus(Dt + DtBias) of a chunk's [C, block] (a seam of
    its own, as `ssm_ops._step_of` is, so that the controls can round it:
    tools/chip_jamba_controls.py)."""
    return jax.nn.softplus(dt.astype(F32) + dt_bias)


def carried(h):
    """A state index's [8, block / 8] as one group of eight tokens hands it to
    the next: float32 (the other seam the controls round; `ssm_ops._carried`)."""
    return h


def _token_major(v):
    """[8 tokens, block] as [8 tokens, 8, block / 8]: a token's channels over a
    register's sublanes and lanes."""
    lanes = v.shape[1] // GROUP
    return jnp.swapaxes(jnp.stack([v[:, s * lanes:(s + 1) * lanes] for s in range(GROUP)], axis=0), 0, 1)


def _channel_major(w):
    """`_token_major`'s inverse."""
    v = jnp.swapaxes(w, 0, 1)
    return jnp.concatenate([v[s] for s in range(GROUP)], axis=1)


def tiles(t, block):
    """[..., d] as [..., d / block, 8, block / 8]: the channels as the kernels
    hold a state index's (the same order: a reshape)."""
    return t.reshape(*t.shape[:-1], t.shape[-1] // block, GROUP, block // GROUP)


def channels_last(t):
    """[..., d / block, N, 8, block / 8] (the kernels' states) as the op's [..., N, d]."""
    t = jnp.moveaxis(t, -3, -4)
    return t.reshape(*t.shape[:-3], -1)


def _relaid(C, layout, pairs):
    """Each `(from, to)` pair of refs of a chunk's C tokens, `to` = `layout`
    (`_token_major` or `_channel_major`) of `from`, a group of eight tokens at a time."""
    def group(g, _):
        rows = pl.ds(pl.multiple_of(g * GROUP, GROUP), GROUP)
        for source, target in pairs:
            target[rows] = layout(source[rows])
        return 0
    jax.lax.fori_loop(0, C // GROUP, group, 0)


def _scan_kernel(seams, x_ref, dt_ref, b_ref, c_ref, a_ref, skip_ref, bias_ref, y_ref, final_ref, decays_ref, steps_ref, *rest):
    """`rest`: the block of the chunk's start state where it is kept, then the scratch: the state [N, 8, L], two natural
    [C, block] and three token-major [C, 8, L] float32."""
    step_of, carried = seams
    *kept, state, natural, step_s, enters_s, y_s = rest
    C, N = x_ref.shape[1], a_ref.shape[1]

    @pl.when(pl.program_id(2) == 0)
    def _():
        state[...] = jnp.zeros_like(state)
        decays_ref[...] = jnp.zeros_like(decays_ref)
        steps_ref[...] = jnp.zeros_like(steps_ref)

    if kept:
        kept[0][0, 0, 0] = state[...]
    step = step_of(dt_ref[0], bias_ref[...])                                     # [C, block] float32
    steps_ref[0] += jnp.sum(step, axis=0, keepdims=True)
    natural[0] = step
    natural[1] = step * x_ref[0].astype(F32)
    _relaid(C, _token_major, ((natural.at[0], step_s), (natural.at[1], enters_s)))

    def tokens(g, carry):
        h, decays = list(carry[:N]), carry[N]
        for k in range(GROUP):
            t = g * GROUP + k
            s_t, u_t, y = step_s[t], enters_s[t], None
            for n in range(N):
                decay = jnp.exp(s_t * a_ref[0, n])
                h[n] = decay * h[n] + u_t * b_ref[0, 0, 0, t * N + n]
                term = h[n] * c_ref[0, 0, 0, t * N + n]
                y = term if y is None else y + term
                decays = decays + decay
            y_s[t] = y
        return (*(carried(v) for v in h), decays)

    *h, decays = jax.lax.fori_loop(0, C // GROUP, tokens, (*(state[n] for n in range(N)), jnp.zeros(state.shape[1:], F32)))
    for n in range(N):
        state[n] = h[n]
    decays_ref[0, 0] += decays
    _relaid(C, _channel_major, ((y_s, natural.at[0]),))
    y_ref[0] = (natural[0] + skip_ref[...] * x_ref[0].astype(F32)).astype(y_ref.dtype)

    @pl.when(pl.program_id(2) == pl.num_programs(2) - 1)
    def _():
        final_ref[0, 0] = state[...]


def _over_sublanes(products):
    """N products [8, L] of one token, each summed over its sublanes and folded
    to one lane tile: [N, min(L, 128)].  Eight of them stacked and their leading
    axes swapped are eight registers to ADD, where a register's own sublanes
    would each be a reduction; the lanes are summed once a chunk."""
    L = products[0].shape[1]
    if L > 128:
        products = [sum(p[:, j * 128:(j + 1) * 128] for j in range(L // 128)) for p in products]
    N = len(products)
    if N % GROUP:
        return jnp.sum(jnp.stack(products, axis=0), axis=1)
    return jnp.concatenate([jnp.sum(jnp.swapaxes(jnp.stack(products[i:i + GROUP], axis=0), 0, 1), axis=0)
                            for i in range(0, N, GROUP)], axis=0)


def _transposed_kernel(seams, x_ref, dt_ref, b_ref, c_ref, a_ref, skip_ref, bias_ref, dy_ref, starts_ref,
                       dx_ref, ddt_ref, db_ref, dc_ref, da_ref, dskip_ref, dbias_ref,
                       after, states, natural, step_s, enters_s, dy_s, dstep_s, denters_s, db_s, dc_s):
    """Scratch: the cotangent of the state the chunk ENDS in [N, 8, L]; the chunk's states [C + 1, N, 8, L], the start
    state first; three natural [C, block]; five token-major [C, 8, L]; dB's and dC's lanes [C, N, min(L, 128)]."""
    step_of, carried = seams
    C, N = x_ref.shape[1], a_ref.shape[1]

    @pl.when(pl.program_id(2) == 0)
    def _():
        after[...] = jnp.zeros_like(after)
        for ref in (da_ref, dskip_ref, dbias_ref):
            ref[...] = jnp.zeros_like(ref)

    step = step_of(dt_ref[0], bias_ref[...])
    natural[0] = step
    natural[1] = step * x_ref[0].astype(F32)
    natural[2] = dy_ref[0].astype(F32)
    _relaid(C, _token_major, ((natural.at[0], step_s), (natural.at[1], enters_s), (natural.at[2], dy_s)))

    # the chunk's states again: states[t + 1] after token t
    states[0] = starts_ref[0, 0, 0]

    def again(g, h):
        h = list(h)
        for k in range(GROUP):
            t = g * GROUP + k
            s_t, u_t = step_s[t], enters_s[t]
            for n in range(N):
                h[n] = jnp.exp(s_t * a_ref[0, n]) * h[n] + u_t * b_ref[0, 0, 0, t * N + n]
                states[t + 1, n] = h[n]
        return tuple(carried(v) for v in h)

    jax.lax.fori_loop(0, C // GROUP, again, tuple(states[0, n] for n in range(N)))

    def back(i, g):
        g = list(g)
        for k in reversed(range(GROUP)):
            t = (C // GROUP - 1 - i) * GROUP + k
            s_t, u_t, dy_t = step_s[t], enters_s[t], dy_s[t]
            d_step = d_enters = None
            to_b, to_c = [], []
            for n in range(N):
                a_n = a_ref[0, n]
                g_n = g[n] + dy_t * c_ref[0, 0, 0, t * N + n]
                to_c.append(dy_t * states[t + 1, n])
                to_b.append(g_n * u_t)
                term = g_n * b_ref[0, 0, 0, t * N + n]
                d_enters = term if d_enters is None else d_enters + term
                decay = jnp.exp(s_t * a_n)
                d_exponent = g_n * states[t, n] * decay
                term = d_exponent * a_n
                d_step = term if d_step is None else d_step + term
                da_ref[0, 0, n] += d_exponent * s_t
                g[n] = g_n * decay
            dstep_s[t], denters_s[t] = d_step, d_enters
            db_s[t], dc_s[t] = _over_sublanes(to_b), _over_sublanes(to_c)
        return tuple(g)

    g = jax.lax.fori_loop(0, C // GROUP, back, tuple(after[n] for n in range(N)))
    for n in range(N):
        after[n] = g[n]
    db_ref[0, 0, 0] = jnp.sum(db_s[...], axis=-1)
    dc_ref[0, 0, 0] = jnp.sum(dc_s[...], axis=-1)
    _relaid(C, _channel_major, ((dstep_s, natural.at[0]), (denters_s, natural.at[1])))
    x, dy = x_ref[0].astype(F32), dy_ref[0].astype(F32)
    raw, step = dt_ref[0].astype(F32) + bias_ref[...], step_of(dt_ref[0], bias_ref[...])
    # the softplus' slope 1 / (1 + exp(-raw)) as exp(raw - softplus(raw)): no division (Mosaic's reads 5e-6 off), no
    # positive exponent, exactly 0 on a padded token
    d_raw = (natural[0] + natural[1] * x) * jnp.exp(raw - step)
    dx_ref[0] = (natural[1] * step + dy * skip_ref[...]).astype(dx_ref.dtype)
    ddt_ref[0] = d_raw.astype(ddt_ref.dtype)
    dbias_ref[0] += jnp.sum(d_raw, axis=0, keepdims=True)
    dskip_ref[0] += jnp.sum(dy * x, axis=0, keepdims=True)


def _cost(b, T, d, N, per_element, exps, bytes_a_token_channel, extra_bytes):
    return pl.CostEstimate(flops=int(per_element * b * T * d * N), transcendentals=int(exps * b * T * d * N),
                           bytes_accessed=int(b * T * d * bytes_a_token_channel + extra_bytes))


def _flat(t, chunk):
    """B or C [b, T, N] as the float32 [b, T / chunk, 1, chunk . N] whose (1, 1, 1, chunk . N) blocks lie in SMEM."""
    b, T, N = t.shape
    return t.astype(F32).reshape(b, T // chunk, 1, chunk * N)


@functools.partial(jax.jit, static_argnums=(7, 8, 9, 10, 11))
def scan(x, dt, b_t, c_t, a_t, d_skip, dt_bias, chunk, block, seams, keep, interpret):
    """(y [b, T, d] in x's dtype, the state after the last token [b, d / block,
    N, 8, block / 8] float32 (`channels_last` lays it [b, N, d]), the decays'
    sums [b, d / block, 8, block / 8] and the steps' [b, 1, d]) of x, dt [b, T,
    d], B, C [b, T, N], `a_t` = A transposed [N, d], D and DtBias [d], float32;
    T a whole number of `chunk`s, d of `block`s; `seams` = (the step's function,
    the carried state's: `step_of` and `carried` here, through `ssm_ops._kernel_seams`).  With `keep` the state every
    chunk starts from [T / chunk, b, d / block, N, 8, block / 8] after them."""
    (b, T, d), N = x.shape, a_t.shape[0]
    n, J, L = T // chunk, d // block, block // GROUP
    tokens = pl.BlockSpec((1, chunk, block), lambda i, j, c: (i, c, j))
    scalars = pl.BlockSpec((1, 1, 1, chunk * N), lambda i, j, c: (i, c, 0, 0), memory_space=pltpu.SMEM)
    channels = pl.BlockSpec((1, block), lambda i, j, c: (0, j))
    out_specs = [tokens, pl.BlockSpec((1, 1, N, GROUP, L), lambda i, j, c: (i, j, 0, 0, 0)),
                 pl.BlockSpec((1, 1, GROUP, L), lambda i, j, c: (i, j, 0, 0)), pl.BlockSpec((1, 1, block), lambda i, j, c: (i, 0, j))]
    out_shape = [jax.ShapeDtypeStruct((b, T, d), x.dtype), jax.ShapeDtypeStruct((b, J, N, GROUP, L), F32),
                 jax.ShapeDtypeStruct((b, J, GROUP, L), F32), jax.ShapeDtypeStruct((b, 1, d), F32)]
    if keep:
        out_specs.append(pl.BlockSpec((1, 1, 1, N, GROUP, L), lambda i, j, c: (c, i, j, 0, 0, 0)))
        out_shape.append(jax.ShapeDtypeStruct((n, b, J, N, GROUP, L), F32))
    return pl.pallas_call(
        functools.partial(_scan_kernel, seams), grid=(b, J, n),
        in_specs=[tokens, tokens, scalars, scalars, pl.BlockSpec((1, N, GROUP, L), lambda i, j, c: (j, 0, 0, 0)), channels, channels],
        out_specs=out_specs, out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((N, GROUP, L), F32), pltpu.VMEM((2, chunk, block), F32)] + [pltpu.VMEM((chunk, GROUP, L), F32)] * 3,
        compiler_params=_SEMANTICS, cost_estimate=_cost(b, T, d, N, 8, 1, 3 * x.dtype.itemsize, 4 * b * d * N * (1 + n * keep)),
        name="selective_scan", interpret=interpret,
    )(x, dt, _flat(b_t, chunk), _flat(c_t, chunk), jnp.moveaxis(tiles(a_t, block), 0, 1), d_skip[None], dt_bias[None])


@functools.partial(jax.jit, static_argnums=(9, 10, 11, 12))
def scan_transposed(x, dt, b_t, c_t, a_t, d_skip, dt_bias, d_y, starts, chunk, block, seams, interpret):
    """(dX, dDt in their inputs' dtypes; dB, dC [b, T, N], dA transposed [N, d],
    dD and dDtBias [d], float32) of d y [b, T, d] and the chunks' start states
    that `scan(keep=True)` kept, beside the inputs."""
    (b, T, d), N = x.shape, a_t.shape[0]
    n, J, L = T // chunk, d // block, block // GROUP
    tokens = pl.BlockSpec((1, chunk, block), lambda i, j, c: (i, n - 1 - c, j))           # the chunks in reverse order
    scalars = pl.BlockSpec((1, 1, 1, chunk * N), lambda i, j, c: (i, n - 1 - c, 0, 0), memory_space=pltpu.SMEM)
    channels = pl.BlockSpec((1, block), lambda i, j, c: (0, j))
    partial_ = pl.BlockSpec((1, 1, 1, chunk, N), lambda i, j, c: (i, j, n - 1 - c, 0, 0))
    summed = pl.BlockSpec((1, 1, block), lambda i, j, c: (i, 0, j))
    dx, ddt, db, dc, da, dskip, dbias = pl.pallas_call(
        functools.partial(_transposed_kernel, seams), grid=(b, J, n),
        in_specs=[tokens, tokens, scalars, scalars, pl.BlockSpec((1, N, GROUP, L), lambda i, j, c: (j, 0, 0, 0)), channels, channels,
                  tokens, pl.BlockSpec((1, 1, 1, N, GROUP, L), lambda i, j, c: (n - 1 - c, i, j, 0, 0, 0))],
        out_specs=[tokens, tokens, partial_, partial_, pl.BlockSpec((1, 1, N, GROUP, L), lambda i, j, c: (i, j, 0, 0, 0)), summed, summed],
        out_shape=[jax.ShapeDtypeStruct((b, T, d), x.dtype), jax.ShapeDtypeStruct((b, T, d), dt.dtype)]
        + [jax.ShapeDtypeStruct((b, J, n, chunk, N), F32)] * 2
        + [jax.ShapeDtypeStruct((b, J, N, GROUP, L), F32)] + [jax.ShapeDtypeStruct((b, 1, d), F32)] * 2,
        scratch_shapes=[pltpu.VMEM((N, GROUP, L), F32), pltpu.VMEM((chunk + 1, N, GROUP, L), F32), pltpu.VMEM((3, chunk, block), F32)]
        + [pltpu.VMEM((chunk, GROUP, L), F32)] * 5 + [pltpu.VMEM((chunk, N, min(L, 128)), F32)] * 2,
        compiler_params=_SEMANTICS,
        cost_estimate=_cost(b, T, d, N, 26, 2, 3 * x.dtype.itemsize + 2 * dt.dtype.itemsize, 4 * b * d * N * (2 + n) + 8 * b * J * T * N),
        name="selective_scan_transposed", interpret=interpret,
    )(x, dt, _flat(b_t, chunk), _flat(c_t, chunk), jnp.moveaxis(tiles(a_t, block), 0, 1), d_skip[None], dt_bias[None], d_y, starts)
    db, dc = (jnp.sum(t, axis=1).reshape(b, T, N) for t in (db, dc))
    return dx, ddt, db, dc, channels_last(jnp.sum(da, axis=0)), jnp.sum(dskip, axis=(0, 1)), jnp.sum(dbias, axis=(0, 1))
