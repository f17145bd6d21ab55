"""Pallas TPU kernel for the routed experts' way back to token order
(`ops/moe_ops.py` has the layer; `_token_sum_path` there sends `_sum_by_token`
here).

`token_sum(rows [R, d], index [T, k], group [T, k]) -> [T, d]`: out[t] = sum_j
rows[index[t, j]], summed in float32 and rounded once to the rows' dtype.  XLA's
form is a row gather that writes [T, k, d] to HBM and a sum that reads it again.
Here nothing of [T, k, d] exists: a grid step is a block of `TOKENS` tokens, its
rows come from HBM into VMEM and are summed there, and only [T, d] is written.

What the kernel rests on: `index` is the place of each (token, slot) assignment
in a STABLE sort by `group` (its expert).  Inside a group the rows then lie in
token order, so the rows of a block of tokens are at most one contiguous RUN a
group.  A copy between HBM and VMEM moves whole tiles and not rows (Mosaic
refuses a slice of fewer than a tile's eight rows of an array in HBM, so there
is no copy of one row: PERF.md, PR 49), so a run is brought as the `GRANULE`-row
tiles that cover it, one copy a tile, into the next free tiles of the block's
buffer; `plan` (plain `jax.numpy`, a few passes over [T k, groups] booleans)
gives each (block, group) its first tile and how many, and each assignment its
row's place in the buffer.  The rows are then brought to token order and summed
in one step by a 0/1 product on the matrix unit, `[TOKENS, buffer rows] @
[buffer rows, d]` over the buffer's used chunks: the products are exact and
accumulate in float32, which is the sum over k.  The NEXT block's copies are
started before this block's product (two buffers).

A buffer row that no token of the block owns (the rest of a run's first and last
tile, the rest of the last chunk) meets a 0: the buffers start as zeros and hold
only rows of `rows` afterwards.  So every row of a tile that a run touches must
be FINITE, whoever owns it: 0 x NaN is a NaN for the block's 128 tokens.

A slot may own NO row (a layer that holds a share of its experts: the slot's
expert is another chip's): its `group` is `groups` and its `index` negative.  It
is then in no run's count and no run's first, no run's shift is added to its
place, which stays negative, and no buffer column equals it: its line of the 0/1
matrix is zeros.  `rows` may then be fewer than T k, and the kernel copies the
tiles of the owned rows only, however many it is given.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

F32 = jnp.float32

#: Rows of one copy: a tile of an array in HBM (eight rows of 32-bit or of
#: 16-bit values, the latter two to a sublane).
GRANULE = 8

#: Tokens a grid step, and the buffer rows of one product.  TPU v5e, (131072,
#: 2048) bf16 rows, k = 8, 64 experts, ms a call (my chip runs, PR 49, calls 1 to
#: 4; tools/chip_token_sum.py; a uniform router, and within 0.01 of it the
#: cell's skew of 4.4 x the mean on one expert): XLA's gather and sum 5.40; the
#: kernel at tokens:chunk 64:256 1.81, 128:256 1.69, **128:512 1.56**, 256:512
#: 2.01.  More tokens a step read fewer tiles for nothing (1.9x the rows' bytes
#: at 64, 1.45x at 128, 1.2x at 256) and multiply more (the product grows with
#: tokens x buffer rows).  Apart at 128:512: the copies alone 1.22 (0.78 GB at
#: 640 GB/s, about what the HBM gives), the products alone 0.90, and ~0.34 is the
#: core starting ~190 copies a block, 13 cycles each, which nothing hides.  Not
#: to be asked again: a wait a copy (1.85: as dear as the starts), the next
#: block's copies started a few groups at a time between the products (1.88 for
#: 1.85: it is not the queue), a run's copies as powers of two tiles (1.67 for
#: 1.56: the branches cost more than the starts they save; 1.73 for 2.01 at
#: 256:512), the product transposed so that the 0/1 matrix is latched and the
#: buffer streams (1.88 for 1.85), a copy a row (Mosaic refuses it).
TOKENS = 128
CHUNK = 512

#: The two buffers' room: with the accumulator and the output's blocks under the
#: kernel's `vmem_limit_bytes`.
_BUFFER_BYTES = 40 * 2 ** 20
_VMEM_LIMIT = 64 * 2 ** 20


def buffer_rows(k, groups, tokens=TOKENS, chunk=CHUNK):
    """Rows of a block's buffer: a run of c rows lies in fewer than c / GRANULE +
    2 tiles, and a block has `tokens` k rows in at most min(groups, tokens k)
    runs; whole chunks."""
    rows = tokens * k + 2 * GRANULE * min(groups, tokens * k)
    return -(-rows // chunk) * chunk


def fits(tokens, d, k, dtype, groups):
    """Whether `token_sum` takes rows [tokens k, d] of `dtype` in `groups`
    groups: whole lane tiles a row, whole blocks of tokens, float32 or bf16
    rows, and two buffers that fit."""
    dtype = jnp.dtype(dtype)
    return (d % 128 == 0 and tokens % TOKENS == 0 and dtype in (jnp.dtype(F32), jnp.dtype(jnp.bfloat16))
            and 2 * buffer_rows(k, groups) * d * dtype.itemsize <= _BUFFER_BYTES)


def plan(index, group, groups, tokens=TOKENS):
    """(runs [T / tokens, 1, 2 groups + 1] int32: each (block, group)'s first
    tile, then its number of tiles, then the block's tiles in all; place [T, k]
    int32: the row of the block's buffer that holds each assignment's row) of
    `index`, `group` [T, k].  A slot of group `groups` (which owns no row: its
    `index` is negative) is no run's, and its place is its `index`."""
    T, k = index.shape
    blocks = T // tokens
    index, group = (t.reshape(blocks, tokens * k, 1) for t in (index, group))
    mine = group == jnp.arange(groups, dtype=group.dtype)                        # [blocks, tokens k, groups]
    count = jnp.sum(mine, axis=1, dtype=jnp.int32)
    first = jnp.min(jnp.where(mine, index, jnp.iinfo(jnp.int32).max), axis=1)
    tile = jnp.where(count > 0, first // GRANULE, 0)
    tiles = jnp.where(count > 0, (first + count + GRANULE - 1) // GRANULE - tile, 0)
    base = jnp.cumsum(tiles, axis=1) - tiles                                     # the group's first tile in the buffer
    shift = (base - tile) * GRANULE
    place = index[..., 0] + jnp.sum(jnp.where(mine, shift[:, None, :], 0), axis=2)
    return jnp.concatenate([tile, tiles, jnp.sum(tiles, axis=1, keepdims=True)], axis=1)[:, None, :], place.reshape(T, k)


def _kernel(k, groups, chunk_rows, runs_ref, next_ref, place_ref, rows_ref, out_ref, buffer, total, arrived):
    """`runs_ref`, `next_ref`: this block's and the next one's runs in SMEM;
    `place_ref` this block's [TOKENS, k]; `rows_ref` all rows, in HBM.  Scratch:
    two buffers [rows, d], the float32 sum [TOKENS, d], a copy semaphore a buffer."""
    i, n = pl.program_id(0), pl.num_programs(0)
    slot = i % 2

    def start(runs, slot):
        """Start every tile copy of a block into buffer `slot`, run after run."""
        def run(e, at):
            first, tiles = runs[0, 0, e], runs[0, 0, groups + e]

            def tile(g, _):
                source = rows_ref.at[pl.ds(pl.multiple_of((first + g) * GRANULE, GRANULE), GRANULE)]
                target = buffer.at[slot, pl.ds(pl.multiple_of((at + g) * GRANULE, GRANULE), GRANULE)]
                pltpu.make_async_copy(source, target, arrived.at[slot]).start()
                return 0

            jax.lax.fori_loop(0, tiles, tile, 0)
            return at + tiles
        jax.lax.fori_loop(0, groups, run, 0)

    @pl.when(i == 0)
    def _():
        buffer[...] = jnp.zeros_like(buffer)
        start(runs_ref, slot)

    @pl.when(i + 1 < n)
    def _():
        start(next_ref, 1 - slot)

    # A copy's semaphore counts bytes, whoever sent them: the block's `used` tiles are awaited as the powers of two
    # that make the number, a handful of waits where a wait a copy costs the core as much as the product (PERF.md, PR 49)
    used = runs_ref[0, 0, 2 * groups]
    rows = GRANULE
    while rows <= buffer.shape[1]:
        @pl.when((used * GRANULE) & rows != 0)
        def _(rows=rows):
            arriving = buffer.at[slot, pl.ds(0, rows)]      # a wait reads its descriptor's size and semaphore, not its source
            pltpu.make_async_copy(arriving, arriving, arrived.at[slot]).wait()
        rows *= 2

    total[...] = jnp.zeros_like(total)
    exact = jax.lax.Precision.HIGHEST if buffer.dtype == F32 else None           # 1.0 x a float32 in whole

    def chunk(c, _):
        at = pl.multiple_of(c * chunk_rows, chunk_rows)
        columns = at + jax.lax.broadcasted_iota(jnp.int32, (1, chunk_rows), 1)
        owns = place_ref[:, 0:1] == columns
        for j in range(1, k):
            owns = owns | (place_ref[:, j:j + 1] == columns)
        total[...] += jnp.dot(owns.astype(buffer.dtype), buffer[slot, pl.ds(at, chunk_rows)],
                              preferred_element_type=F32, precision=exact)
        return 0

    jax.lax.fori_loop(0, (used * GRANULE + chunk_rows - 1) // chunk_rows, chunk, 0)
    out_ref[...] = total[...].astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnums=(3, 4, 5, 6))
def token_sum(rows, index, group, groups, interpret=False, tokens=TOKENS, chunk=CHUNK):
    """out [T, d] in the rows' dtype: out[t] = sum_j rows[index[t, j]] in float32,
    of rows [R, d] and `index` [T, k] int32, the place of each assignment in a
    stable sort by `group` [T, k] (values in [0, groups)); `fits(T, d, k, dtype,
    groups)`.  A slot with `group == groups` and a negative `index` owns no row
    and adds nothing; R is then whatever the owned slots need, and every row of
    an 8-row tile that holds an owned row must be finite.  `tokens` a grid step
    and `chunk` rows a product are the module's unless given
    (tools/chip_token_sum.py prices others)."""
    (T, k), d = index.shape, rows.shape[1]
    blocks = T // tokens
    runs, place = plan(index, group, groups, tokens)
    a_block = functools.partial(pl.BlockSpec, (1, 1, 2 * groups + 1), memory_space=pltpu.SMEM)
    return pl.pallas_call(
        functools.partial(_kernel, k, groups, chunk), grid=(blocks,),
        in_specs=[a_block(lambda i: (i, 0, 0)), a_block(lambda i: (jnp.minimum(i + 1, blocks - 1), 0, 0)),
                  pl.BlockSpec((tokens, k), lambda i: (i, 0)), pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((tokens, d), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((T, d), rows.dtype),
        scratch_shapes=[pltpu.VMEM((2, buffer_rows(k, groups, tokens, chunk), d), rows.dtype), pltpu.VMEM((tokens, d), F32),
                        pltpu.SemaphoreType.DMA((2,))],
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary",), vmem_limit_bytes=_VMEM_LIMIT),
        # what the sum needs: every row it is given read once (T k of them where every slot owns one), the tokens written
        # once; the product is the kernel's way, not work
        cost_estimate=pl.CostEstimate(flops=0, transcendentals=0, bytes_accessed=int((rows.shape[0] + T) * d * rows.dtype.itemsize)),
        name="token_sum", interpret=interpret,
    )(runs, runs, place, rows)
