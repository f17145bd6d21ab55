"""The indexer's threshold, found by COUNTING (`ops/sparse_index_ops.py: choose`
reads it): of each row of float32 scores the `topk`-th largest value and how far
among its equals the choice reaches, with no sort.

    (masked [C, K] float32, topk) -> (kth [C, 1] float32, last [C, 1] int32)

what the last column of a stable descending sort would hold.  A row's scores
become whole numbers in the scores' order (`ordered`): the bit pattern, either
zero's +0.0's (-0.0 and +0.0 are ONE value to `>` and `==`, so one key; a
select says so, for XLA takes `x + 0.0` for `x`), the lower 31 bits turned over
where the sign is set; -inf is the smallest key of all, NaN has no order and is
not the caller's.  The `topk`-th largest key is then built a bit at a time, the
highest first: a bit stays where `topk` keys at the least are not under the
prefix with it (32 passes that compare and count).  Of the keys EQUAL to it
`topk - count(key > kth)` are held, the lowest index first, so `last` is the
smallest index with that many equals up to it: where every equal of every row
is held (no scores tie at a threshold) the largest equal's index, one pass;
else a search over the index's bits (`K`'s bit length more passes).  Rows are
independent.

Two forms of the ONE function `_search`, chosen by the platform and the shape
(`sparse_index_ops._sparse_index`), never by a flag:

  * `select`, on the TPU where `fits`: a Pallas kernel that holds a block of
    rows' keys in VMEM and makes every pass there: the scores leave HBM once;
  * `kth_and_last`, anywhere else (the CPU, a chunk that is not whole (8, 128)
    tiles): plain `jax.numpy`.

On the v5e, ms a call of [512, keys] alone, `lax.top_k`'s last column | `select`
| `kth_and_last` (XLA holds the keys in VMEM through its loops too): 4096 keys
0.70 | 0.18 | 0.20, 8192: 1.45 | 0.25 | 0.29, 16384: 4.80 | 0.43 | 0.43; in
Keye-VL-2.0's step the two forms' selects read 20 and 19 ms for the sort's 399,
and the kernel is kept for the program's SET-UP: a `pallas_call` is one equation
to `jax.checkpoint`'s partial evaluation and one call to XLA, where the plain
form's 56 loops a program cost the cell's `setup_s` ~50 s (442 to 452 s for the
kernel's 395 to 396 and the sort's 403 to 412; ~9 of them lowering) (my chip
runs, PR 57: PERF.md, section 6).  Blocks of 0.5 | 1 | 2 | 4 MB read 0.75 | 0.48
| 0.43 | 0.37 ms at 16384 keys: a pass ends in a reduce over the lanes that the
next pass waits for, so fewer, larger blocks wait less; 2 MB leaves the scoped
VMEM room inside the whole step.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

I32 = jnp.int32
LANES = 128
#: The bytes of a block's keys: the scores' block twice (the pipeline's two buffers) and the keys once stand in VMEM
#: beside each other, 6 MB of the 16 MB a kernel may use.  Rows a block follow from it (`_rows`): 32 at 16384 keys.
BLOCK_BYTES = 2 << 20
_LOWEST = -2 ** 31


def _turned(bits):
    """Float32 bit patterns to keys, and keys back."""
    return bits ^ ((bits >> 31) & I32(0x7FFFFFFF))


def ordered(x):
    """int32 keys whose order is the float32 values' (the module's docstring)."""
    return _turned(jax.lax.bitcast_convert_type(jnp.where(x == 0.0, 0.0, x), I32))


def score_of(keys):
    """The float32 value a key stands for."""
    return jax.lax.bitcast_convert_type(_turned(keys), jnp.float32)


def _count(held):
    return jnp.sum(held.astype(I32), axis=1, keepdims=True)


def _search(keys, rows: int, width: int, topk: int):
    """(the `topk`-th largest key [rows, 1], `last` [rows, 1]) of `keys()`, int32 [rows, width] in `ordered`'s order;
    `keys` is called once a pass: a kernel reads its block anew, XLA fuses the read into the pass."""
    def bit_of_kth(step, prefix):
        # the prefix is an UNSIGNED number's bits; the signed keys compare with it once its top bit is turned
        with_bit = prefix | (I32(1) << (I32(31) - step))
        return jnp.where(_count(keys() >= (with_bit ^ I32(_LOWEST))) >= topk, with_bit, prefix)

    kth = jax.lax.fori_loop(0, 32, bit_of_kth, jnp.zeros((rows, 1), I32)) ^ I32(_LOWEST)
    index = jax.lax.broadcasted_iota(I32, (rows, width), 1)
    equal = keys() == kth
    held = topk - _count(keys() > kth)                 # of the equals, the lowest indices: one at the least
    last = jnp.max(jnp.where(equal, index, -1), axis=1, keepdims=True)       # right where every equal is held

    def lowest_equals(_):
        """The smallest index with `held` equals up to it: the largest `before` with fewer than `held` under it."""
        bits = max((width - 1).bit_length(), 1)

        def bit_of_last(step, before):
            with_bit = before | (I32(1) << (I32(bits - 1) - step))
            return jnp.where(_count((keys() == kth) & (index < with_bit)) < held, with_bit, before)

        return jax.lax.fori_loop(0, bits, bit_of_last, jnp.zeros((rows, 1), I32))

    return kth, jax.lax.cond(jnp.any(_count(equal) != held), lowest_equals, lambda _: last, None)


def kth_and_last(masked, topk: int):
    """The plain form: see the module's docstring."""
    keys = ordered(masked)
    kth, last = _search(lambda: keys, *masked.shape, topk)
    return score_of(kth), last


def fits(rows: int, width: int) -> bool:
    """Whether `select` takes [rows, width] scores: whole (8, 128) tiles, and eight rows' keys within a block."""
    return rows % 8 == 0 and width % LANES == 0 and 8 * width * 4 <= BLOCK_BYTES


def _rows(rows: int, width: int) -> int:
    """Rows a grid step: the most of 8, 16, .. 512 that divide `rows` and keep a block's keys within `BLOCK_BYTES`."""
    return max(r for r in (8, 16, 32, 64, 128, 256, 512) if rows % r == 0 and r * width * 4 <= BLOCK_BYTES)


def _select_kernel(scores_ref, kth_ref, last_ref, keys_ref, *, topk):
    keys_ref[...] = ordered(scores_ref[...])
    kth, last = _search(lambda: keys_ref[...], *keys_ref.shape, topk)
    kth_ref[...] = score_of(kth)
    last_ref[...] = last


@functools.partial(jax.jit, static_argnames=("topk", "interpret"))
def select(masked, topk: int, interpret: bool = False):
    """The kernel's form: see the module's docstring.  `interpret` is the tests'."""
    rows, width = masked.shape
    block = _rows(rows, width)
    return pl.pallas_call(
        functools.partial(_select_kernel, topk=topk),
        grid=(rows // block,),
        in_specs=[pl.BlockSpec((block, width), lambda i: (i, 0))],
        out_specs=[pl.BlockSpec((block, 1), lambda i: (i, 0))] * 2,
        out_shape=[jax.ShapeDtypeStruct((rows, 1), jnp.float32), jax.ShapeDtypeStruct((rows, 1), I32)],
        scratch_shapes=[pltpu.VMEM((block, width), I32)],
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel",)),
        name="kth_by_counting",
        interpret=interpret,
    )(masked)
