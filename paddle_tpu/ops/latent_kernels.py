"""Pallas TPU kernels for the latent attention's edge (`ops/latent_operands.py`
has the unit and sends its passes here on the chip): four passes that each read
what a projection or an attention kernel wrote and write what the other reads,
once, with nothing else of that size in HBM.

    queries       q (B, L, H (nope + rope))  ->  q_hm (B, H, L, nope + rope), rotated, scaled
    keys_values   up (B, L, H (nope + v)), the rotated shared part (B, L, rope)
                                             ->  k_hm (B, H, L, nope + rope), v_hm (B, H, L, v)
    queries_back  dq_hm                      ->  dq (B, L, H (nope + rope)), scaled, turned back
    up_back       dk_hm, dv_hm               ->  d_up (B, L, H (nope + v)), and the float32 sum over
                                                 the heads of dk_hm[..., nope:] (B, L, rope)

Plain `jax.numpy` cannot say this to XLA: a rotation of the lanes is a slice
and a concatenate there, and the compiled step held every slice of it as a
float32 array of its own (one layer compiled for the described v5e: 804 MB
written at the edge for the op-by-op lowering's 478; PERF.md, section 6, PR 55).

Widths: `nope` and `v` whole 128-lane tiles, `rope` 64 (half a tile), an even
number of heads, so that two heads of a projection's row are a whole number of
tiles, (2 nope + 128) lanes: the first head's own part and its rotated part
begin a tile, the second's lie half a tile on.  The second head's tiles are
made of its neighbours' halves by a rotation of the lanes by 64 and a select;
a pair's other member by a rotation by one lane either way (by 32 for
rotate-half) and a select on the lane; all on whole (rows, 128) float32 tiles.
The angles come as two float32 tables (B, L, 128), cos and sin a position,
made by XLA from the positions (`latent_operands._tables`: the 64 lanes of a
rotated part twice, so that the part turns in whichever half of a tile it
lies).  One rounding, at the store.

The shared part of a key is ONE 64-wide row a token: its rotation, and its
gradient's way back, are XLA's (2 MB arrays); `up_back` only sums the heads.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

F32 = jnp.float32
LANES = 128
ROPE = 64

#: Positions a grid step and heads a grid step: blocks of 1 to 2.5 MB each way, 256 steps a pass at 16384 positions
#: and 32 heads.  The passes are bound by the bytes they move and the block does not matter: alone on the v5e (1, 16384,
#: 32, 192 / 128), `keys_values` read 1.75 to 1.81 ms and `queries` 1.45 to 1.56 at every block from 128 x 32 heads to
#: 2048 x 2 (1.74 at 64 rows), the same as a kernel of the same blocks that moves nothing in VMEM (1.77); in the cell's
#: step the four take 0.67 to 1.07 ms a call, 600 to 700 GB/s (my chip runs, PR 55: PERF.md, section 6).
ROWS = 256
HEADS = 8


def fits(heads: int, positions: int, nope: int, rope: int, v: int, dtype) -> bool:
    """Whether the kernels take these widths (the module's docstring)."""
    return (rope == ROPE and nope > 0 and nope % LANES == 0 and v > 0 and v % LANES == 0 and heads % 2 == 0
            and positions % 16 == 0 and dtype == jnp.bfloat16)


def _rows(positions: int) -> int:
    return next(r for r in (ROWS, 128, 64, 32, 16) if positions % r == 0)


def _heads(heads: int) -> int:
    return next(h for h in (HEADS, 4, 2) if heads % h == 0)


def _turn(r, cos, sin, shift: int):
    """A (rows, 128) float32 tile turned by the tables' angles: r cos + other
    sin, `other` the pair's other member, signed.  Right in the 64 lanes that
    hold a rotated part, whichever half; the other half is not read after."""
    lane = jax.lax.broadcasted_iota(jnp.int32, r.shape, 1)
    other = jnp.where(lane % (2 * shift) < shift, -pltpu.roll(r, LANES - shift, 1), pltpu.roll(r, shift, 1))
    return r * cos + other * sin


def _halves(a, b):
    """The tile that begins half a tile into `a` and ends half a tile into `b`."""
    lane = jax.lax.broadcasted_iota(jnp.int32, a.shape, 1)
    return jnp.where(lane < ROPE, pltpu.roll(a, ROPE, 1), pltpu.roll(b, ROPE, 1))


def _queries_kernel(*refs, nope, scale, shift, pairs):
    q_ref, out_ref = refs[0], refs[-1]
    tables = None if shift is None else (refs[1][...], refs[2][...])
    n = nope // LANES
    for pair in range(pairs):
        at = pair * (2 * n + 1) * LANES
        tile = [q_ref[:, at + j * LANES:at + (j + 1) * LANES].astype(F32) for j in range(2 * n + 1)]
        for j in range(n):     # the first head's own part as it lies, the second's from its neighbours' halves
            out_ref[2 * pair, :, j * LANES:(j + 1) * LANES] = (tile[j] * scale).astype(out_ref.dtype)
            out_ref[2 * pair + 1, :, j * LANES:(j + 1) * LANES] = (_halves(tile[n + j], tile[n + j + 1]) * scale).astype(out_ref.dtype)
        first, second = tile[n], tile[2 * n]      # the rotated parts: the first half of the one, the second of the other
        if tables is not None:
            first, second = _turn(first, *tables, shift), _turn(second, *tables, shift)
        out_ref[2 * pair, :, nope:] = (first * scale)[:, :ROPE].astype(out_ref.dtype)
        out_ref[2 * pair + 1, :, nope:] = (pltpu.roll(second, ROPE, 1) * scale)[:, :ROPE].astype(out_ref.dtype)


def _queries_back_kernel(*refs, nope, scale, shift, pairs):
    g_ref, out_ref, wide = refs[0], refs[-2], refs[-1]
    tables = None if shift is None else (refs[1][...], refs[2][...])      # of the angles turned back: cos, -sin
    n = nope // LANES

    def part(head):     # a head's 64 rotated lanes in the first half of a whole tile (through VMEM: no roll of half a tile)
        wide[:, :ROPE] = g_ref[head, :, nope:].astype(F32)
        r = wide[...]
        return r if tables is None else _turn(r, *tables, shift)

    lane = jax.lax.broadcasted_iota(jnp.int32, (g_ref.shape[1], LANES), 1)
    for pair in range(pairs):
        at = pair * (2 * n + 1) * LANES
        own = [[g_ref[2 * pair + h, :, j * LANES:(j + 1) * LANES].astype(F32) for j in range(n)] for h in (0, 1)]
        turned = [pltpu.roll(t, ROPE, 1) for t in own[1]]       # the second head's tiles, their halves swapped
        tile = list(own[0])
        tile.append(jnp.where(lane < ROPE, part(2 * pair), turned[0]))
        tile += [jnp.where(lane < ROPE, turned[j - 1], turned[j]) for j in range(1, n)]
        tile.append(jnp.where(lane < ROPE, turned[n - 1], pltpu.roll(part(2 * pair + 1), ROPE, 1)))
        for j, t in enumerate(tile):
            out_ref[:, at + j * LANES:at + (j + 1) * LANES] = (t * scale).astype(out_ref.dtype)


def _tables_specs(rows, shift):
    return [] if shift is None else [pl.BlockSpec((None, rows, LANES), lambda b, i, h: (b, i, 0))] * 2


@functools.partial(jax.jit, static_argnames=("heads", "nope", "scale", "shift", "interpret"))
def queries(q, cos, sin, *, heads, nope, scale, shift, interpret=False):
    """q_hm (B, H, L, nope + 64) from q (B, L, H (nope + 64)): a head's own
    part as projected, its last 64 lanes turned by the tables (`shift` 1:
    feature 2i with 2i + 1; 32: i with i + 32; None: not turned, no tables),
    all times `scale` in float32, rounded once."""
    batch, positions, _ = q.shape
    width, rows, group = nope + ROPE, _rows(positions), _heads(heads)
    return pl.pallas_call(
        functools.partial(_queries_kernel, nope=nope, scale=scale, shift=shift, pairs=group // 2),
        grid=(batch, positions // rows, heads // group),
        in_specs=[pl.BlockSpec((None, rows, group * width), lambda b, i, h: (b, i, h))] + _tables_specs(rows, shift),
        out_specs=pl.BlockSpec((None, group, rows, width), lambda b, i, h: (b, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((batch, heads, positions, width), q.dtype),
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel",) * 3),
        interpret=interpret, name="latent_queries",
    )(q, *(() if shift is None else (cos, sin)))


@functools.partial(jax.jit, static_argnames=("heads", "nope", "scale", "shift", "interpret"))
def queries_back(g, cos, sin, *, heads, nope, scale, shift, interpret=False):
    """`queries`' transpose: dq (B, L, H (nope + 64)) from dq_hm (B, H, L, nope + 64);
    the tables are those of the angles turned back (cos, -sin: a rotation's
    transpose is its inverse)."""
    batch, _, positions, width = g.shape
    rows, group = _rows(positions), _heads(heads)
    return pl.pallas_call(
        functools.partial(_queries_back_kernel, nope=nope, scale=scale, shift=shift, pairs=group // 2),
        grid=(batch, positions // rows, heads // group),
        in_specs=[pl.BlockSpec((None, group, rows, width), lambda b, i, h: (b, h, i, 0))] + _tables_specs(rows, shift),
        out_specs=pl.BlockSpec((None, rows, group * width), lambda b, i, h: (b, i, h)),
        out_shape=jax.ShapeDtypeStruct((batch, positions, heads * width), g.dtype),
        scratch_shapes=[pltpu.VMEM((rows, LANES), F32)],
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel",) * 3),
        interpret=interpret, name="latent_queries_back",
    )(g, *(() if shift is None else (cos, sin)))


def _keys_values_kernel(up_ref, part_ref, k_ref, v_ref, *, nope, v, group):
    for h in range(group):
        at = h * (nope + v)
        k_ref[h, :, :nope] = up_ref[:, at:at + nope]
        k_ref[h, :, nope:] = part_ref[...]
        v_ref[h] = up_ref[:, at + nope:at + nope + v]


def _up_back_kernel(dk_ref, dv_ref, dup_ref, sum_ref, *, nope, v, group):
    @pl.when(pl.program_id(2) == 0)
    def _():
        sum_ref[...] = jnp.zeros_like(sum_ref)

    acc = sum_ref[...]
    for h in range(group):
        at = h * (nope + v)
        dup_ref[:, at:at + nope] = dk_ref[h, :, :nope]
        dup_ref[:, at + nope:at + nope + v] = dv_ref[h]
        acc += dk_ref[h, :, nope:].astype(F32)
    sum_ref[...] = acc


@functools.partial(jax.jit, static_argnames=("heads", "nope", "interpret"))
def keys_values(up, part, *, heads, nope, interpret=False):
    """k_hm (B, H, L, nope + 64) = [a head's own part of `up` ; `part` for
    every head] and v_hm (B, H, L, v) from up (B, L, H (nope + v)) and the
    shared part (B, L, 64), rotated already: one read of `up`."""
    batch, positions, total = up.shape
    v, rows, group = total // heads - nope, _rows(positions), _heads(heads)
    hm = lambda width: pl.BlockSpec((None, group, rows, width), lambda b, i, h: (b, h, i, 0))
    return pl.pallas_call(
        functools.partial(_keys_values_kernel, nope=nope, v=v, group=group),
        grid=(batch, positions // rows, heads // group),
        in_specs=[pl.BlockSpec((None, rows, group * (nope + v)), lambda b, i, h: (b, i, h)),
                  pl.BlockSpec((None, rows, ROPE), lambda b, i, h: (b, i, 0))],
        out_specs=[hm(nope + ROPE), hm(v)],
        out_shape=[jax.ShapeDtypeStruct((batch, heads, positions, nope + ROPE), up.dtype),
                   jax.ShapeDtypeStruct((batch, heads, positions, v), up.dtype)],
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel",) * 3),
        interpret=interpret, name="latent_keys_values",
    )(up, part)


@functools.partial(jax.jit, static_argnames=("interpret"))
def up_back(dk, dv, *, interpret=False):
    """`keys_values`' transpose: d_up (B, L, H (nope + v)) from dk_hm[..., :nope]
    and dv_hm, and the float32 sum over the heads of dk_hm[..., nope:] (B, L, 64),
    which the heads' groups (the grid's last axis) add up in place."""
    batch, heads, positions, width = dk.shape
    nope, v, rows, group = width - ROPE, dv.shape[-1], _rows(positions), _heads(heads)
    hm = lambda w: pl.BlockSpec((None, group, rows, w), lambda b, i, h: (b, h, i, 0))
    return pl.pallas_call(
        functools.partial(_up_back_kernel, nope=nope, v=v, group=group),
        grid=(batch, positions // rows, heads // group),
        in_specs=[hm(width), hm(v)],
        out_specs=[pl.BlockSpec((None, rows, group * (nope + v)), lambda b, i, h: (b, i, h)),
                   pl.BlockSpec((None, rows, ROPE), lambda b, i, h: (b, i, 0))],
        out_shape=[jax.ShapeDtypeStruct((batch, positions, heads * (nope + v)), dk.dtype),
                   jax.ShapeDtypeStruct((batch, positions, ROPE), F32)],
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret, name="latent_up_back",
    )(dk, dv)
