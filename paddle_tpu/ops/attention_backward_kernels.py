"""Attention's backward pass as ONE Pallas TPU kernel under a rule over
positions (`ops/masked_attention.py`: the causal rule and the sliding window):
dq, dk and dv of a (key block, query block) pair from one pass over its scores,
with dq kept on the chip.

The stock splash kernels offer two backward forms.  The pair (`dq`, `dkv`)
computes every pair's scores and `dp` twice, seven products where five do.  The
fused one computes them once and writes dq as a PARTIAL a block of keys, `[L /
block, Hq, L, dh]` rounded to the operands' dtype, which XLA then sums (1.9 GB
a layer at 28 heads of 16384 x 128), over a grid that cannot be shrunk to the
blocks the rule leaves: its query step has to BE the query block.  Here

  * the grid is (row, query head, STEP), a step a (key block, query block)
    pair that the rule leaves, key blocks in order and a key block's query
    blocks in order, read from scalar memory (`steps_of` makes the three tables
    from the stock dkv block map).  A pair the rule empties is no step: the
    causal rule at 16 blocks has 136 steps a head, not 256, a window of four
    blocks 70;
  * a float32 VMEM accumulator holds the head's whole dq, `[L, dh]` (8 MB at
    16384 x 128): zeroed at the head's first step, added to at the step's query
    block, written ONCE in the operands' dtype at the head's last step.  Nothing
    is rounded before the sum, and no partial reaches HBM;
  * dk and dv are float32 accumulators too.  Where every query head has its own
    key/value head they hold one key block, zeroed at its first step and written
    at its last.  Under GROUPED key/value heads they hold the key/value head's
    whole rows, `[L, dh]` each, zeroed at the group's first head and written at
    its last (`kv_rows`: the query heads of a group are neighbours in the grid),
    so nothing is summed outside;
  * every block's mask is computed from the positions by the rule's own function
    (`causal_allowed`, `window_allowed`: two compares a pair at the most).  A
    whole block could skip it and does not: with the step bound by the matrix
    unit the second body read the same to 0.1 ms at every shape priced, 64-wide
    heads among them, and cost its lowering again at every call.

A step's arithmetic is the stock `_flash_attention_dkv_kernel`'s over the
block's keys in ONE pass (the stock kernel's standing optimum was 512 keys a
pass inside its 16 MB of scoped VMEM; with 64 MiB of our own a whole block of
1024 is 0.5 to 2% faster at every shape priced): scores TRANSPOSED `[keys,
queries]` so that a query's log-sum-exp and `di` are rows, p = exp(s - lse), dv
+= p do, dp = v do^T, ds = p (dp - di), dk += ds q, dq += ds^T k; bf16 operands
on the matrix unit, everything else float32.

TPU v5e, forward + backward of a layer alone with the stock forward kernel, ms
(my chip runs, PR 64, calls 1 and 2; `tools/chip_block_attention.py`; the tables by
block are `masked_attention._BLOCKS`' and `_WINDOW_BLOCKS`'):

                                          ours    stock fused   stock pair
  (1, 28 on 4, 16384, 128), window 4096   23.67   29.34         30.87
  (1, 28 on 4, 16384, 128), causal        44.34   49.15
  (1, 32, 16384, 192 | 128), causal       79.76   91.31         102.57
  (2, 32 on 8, 8192, 64), causal          29.46   32.06         38.70
  (4, 16, 4096, 128), causal               9.34    9.76         11.96
  (1, 40 on 20, 8192, 64), window 512      7.20   11.71          8.42
  (1, 64 on 8, 16384, 128), window 512    18.39   50.24         22.61      (my chip run, PR 65, call 1)
  (1, 48 on 8, 16384, 128), causal        74.06   82.95                    (groups of SIX: `rem(head, 6)`)

A group's dk and dv summed in VMEM (`kv_rows`) against a query head's written in
float32 and summed by XLA: 24.23 | 25.04 at 28 on 4 x 16384 (0.94 GB a layer
written and read back), 29.74 | 30.56 at 32 on 8 x 8192, 7.20 | 7.35 at 40 on
20, 18.39 | 18.33 at 64 on 8 x 16384 under a window of 512 and 74.06 | 74.03 at
48 on 8 under the causal rule (PR 65: level, where a band 512 wide or groups of six
leave the sum outside little to move): in VMEM wherever the rows fit.  The call is a `jax.jit` of its own so that a
model's layers share one lowering (`setup_s` is end to end).
"""
from __future__ import annotations

import functools
from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NT = (((1,), (1,)), ((), ()))  # a b^T
_TN = (((0,), (0,)), ((), ()))  # a^T b
MASK_VALUE = -0.7 * float(np.finfo(np.float32).max)   # the stock kernels' `DEFAULT_MASK_VALUE`
LANES = 128
_FIRST, _LAST = 1, 2

#: What the kernel may hold in VMEM.  The chip's compiler refused 100 MiB (PR 62); `ops/ssm_kernels.py` runs at 64.
VMEM_LIMIT = 64 * 2 ** 20
#: Beside the accumulators and the whole-row output blocks: the operands' blocks twice and a step's [block, block]
#: float32 scores, p, dp and ds as far as they live together.  At 16384 x 128 under grouped heads, 48 MB of
#: accumulators and output blocks, the kernel compiles and runs inside the limit (tests/test_chip_compile.py).
_VMEM_BESIDE = 14 * 2 ** 20


class Steps(NamedTuple):
    """The grid's last axis: the query block and the key block of every step,
    and its marks (`_FIRST`, `_LAST`: of its key block's steps)."""
    q_block: np.ndarray
    kv_block: np.ndarray
    marks: np.ndarray


def steps_of(dkv_map) -> Steps:
    """The steps from the stock dkv block map (`info_lib.process_mask_dkv`,
    shrunk or not): every entry with a `block_mask`, its query block the map's
    `data_next`, a key block (the map's column) after the other."""
    assert dkv_map.block_mask.shape[0] == 1, "one mask for every head"
    state, queries = np.asarray(dkv_map.block_mask[0]), np.asarray(dkv_map.data_next[0])
    q_block, kv_block, marks = [], [], []
    for column in range(state.shape[1]):
        rows = np.nonzero(state[:, column])[0]
        assert rows.size, "a key block that no query sees would never be written"
        for n, row in enumerate(rows):
            q_block.append(int(queries[row, column]))
            kv_block.append(column)
            marks.append(_FIRST * (n == 0) + _LAST * (n == rows.size - 1))
    return Steps(*(np.asarray(t, np.int32) for t in (q_block, kv_block, marks)))


def _padded(width: int) -> int:
    return -(-width // LANES) * LANES


def vmem_bytes(length: int, widths, kv_rows: bool) -> int:
    """What the kernel holds in VMEM at `length` positions of `widths` (queries
    and keys, values): dq's float32 accumulator and its bf16 output block (a
    head's whole rows, twice: Pallas buffers an output), and with `kv_rows` the
    same for dk and dv."""
    lanes = _padded(widths[0]) + (_padded(widths[0]) + _padded(widths[1]) if kv_rows else 0)
    return length * lanes * (4 + 2 * 2) + _VMEM_BESIDE


def kv_rows_fit(length: int, widths, group: int) -> bool:
    """Are dk and dv accumulated over a group's query heads on the chip?  Where
    key/value heads are grouped and their whole rows fit beside dq's."""
    return group > 1 and vmem_bytes(length, widths, True) <= VMEM_LIMIT


def _kernel(q_of, kv_of, marks_of, q_ref, k_ref, v_ref, do_ref, lse_ref, di_ref, dq_ref, dk_ref, dv_ref,
            dq_acc, dk_acc, dv_acc, *, allowed: Callable, block: int, group: int, kv_rows: bool):
    head, step = pl.program_id(1), pl.program_id(2)
    q_block, kv_block, marks = q_of[step], kv_of[step], marks_of[step]
    last_step = step == pl.num_programs(2) - 1
    in_group = jax.lax.rem(head, group)

    def each_block(ref, fn):
        """`fn(rows)` over `ref`'s rows a block at a time: a loop, not `ref.shape[0] / 8` unrolled stores."""
        jax.lax.fori_loop(0, ref.shape[0] // block, lambda i, _: fn(pl.ds(pl.multiple_of(i * block, block), block)), None)

    def zero(acc):
        def rows_of(rows):
            acc[rows, :] = jnp.zeros((block, acc.shape[1]), acc.dtype)
        each_block(acc, rows_of)

    def write(ref, acc):
        def rows_of(rows):
            ref[rows, :] = acc[rows, :].astype(ref.dtype)
        each_block(acc, rows_of)

    first_of_kv = (step == 0) & (in_group == 0) if kv_rows else (marks & _FIRST) != 0
    last_of_kv = last_step & (in_group == group - 1) if kv_rows else (marks & _LAST) != 0

    @pl.when(step == 0)
    def _():
        zero(dq_acc)

    @pl.when(first_of_kv)
    def _():
        zero(dk_acc)
        zero(dv_acc)

    q_rows = pl.ds(pl.multiple_of(q_block * block, block), block)
    into = pl.ds(pl.multiple_of(kv_block * block, block), block) if kv_rows else slice(None)

    q, do, k, v = q_ref[...], do_ref[...], k_ref[...], v_ref[...]
    s = jax.lax.dot_general(k, q, _NT, preferred_element_type=jnp.float32)          # [keys, queries]
    q_ids = q_block * block + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    kv_ids = kv_block * block + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
    p = jnp.exp(jnp.where(allowed(q_ids, kv_ids), s, MASK_VALUE) - lse_ref[...])
    dv_acc[into, :] += jnp.dot(p.astype(do.dtype), do, preferred_element_type=jnp.float32)
    dp = jax.lax.dot_general(v, do, _NT, preferred_element_type=jnp.float32)
    ds = (p * (dp - di_ref[...])).astype(q.dtype)
    dk_acc[into, :] += jnp.dot(ds, q, preferred_element_type=jnp.float32)
    dq_acc[q_rows, :] += jax.lax.dot_general(ds, k, _TN, preferred_element_type=jnp.float32)

    @pl.when(last_step)
    def _():
        write(dq_ref, dq_acc)

    @pl.when(last_of_kv)
    def _():
        write(dk_ref, dk_acc)
        write(dv_ref, dv_acc)


def _cost(steps: int, block: int, q_shape, v_width: int, kv_heads: int, itemsize: int) -> pl.CostEstimate:
    """The five products over the pairs of the steps' blocks, an exponential a
    pair, and each operand and result once."""
    batch, heads, length, width = q_shape
    pairs = batch * heads * steps * block * block
    of_queries = batch * heads * length * (2 * width + v_width)          # q, dq, do
    of_keys = 2 * batch * kv_heads * length * (width + v_width)           # k, v, dk, dv
    return pl.CostEstimate(flops=int(2 * pairs * (3 * width + 2 * v_width)), transcendentals=int(pairs),
                           bytes_accessed=int(itemsize * (of_queries + of_keys) + 8 * batch * heads * length))


@functools.partial(jax.jit, static_argnames=("allowed", "block", "interpret"))
def backward(q, k, v, lse, do, di, steps: Steps, allowed: Callable, block: int, interpret: bool = False):
    """dq, dk, dv of softmax(q k^T under `allowed`) v given the rows'
    log-sum-exp `lse` and `di` = rowsum(do . out), both (B, Hq, L) float32; q,
    do (B, Hq, L, .), k, v (B, Hkv, L, .), Hkv a divisor of Hq, the queries
    carrying the scale.  `steps` names the blocks of `block` positions that the
    rule leaves, `allowed(q_ids, kv_ids)` is the rule (the one function a rule:
    the call is traced once for each)."""
    batch, heads, length, width = q.shape
    kv_heads, v_width = k.shape[1], v.shape[-1]
    group = heads // kv_heads
    kv_rows = kv_rows_fit(length, (width, v_width), group)

    # a step's block of queries, of keys (of the `heads_a`-th part of the query heads: a key/value head's, or a query
    # head's own), its row of a per-query statistic, and a head's whole rows
    of_q = lambda w: pl.BlockSpec((None, None, block, w), lambda b, h, s, q_of, kv_of, marks: (b, h, q_of[s], 0))   # noqa: E731
    of_kv = lambda w, heads_a: pl.BlockSpec((None, None, block, w), lambda b, h, s, q_of, kv_of, marks: (b, h // heads_a, kv_of[s], 0))   # noqa: E731
    of_row = pl.BlockSpec((None, None, 1, block), lambda b, h, s, q_of, kv_of, marks: (b, h, 0, q_of[s]))
    whole = lambda w, heads_a: pl.BlockSpec((None, None, length, w), lambda b, h, s, *_: (b, h // heads_a, 0, 0))   # noqa: E731
    if kv_rows:      # a key/value head's whole rows, written at its group's last head
        kv_out = [whole(width, group), whole(v_width, group)]
        kv_shape = [jax.ShapeDtypeStruct(k.shape, k.dtype), jax.ShapeDtypeStruct(v.shape, v.dtype)]
        kv_acc = [pltpu.VMEM((length, width), jnp.float32), pltpu.VMEM((length, v_width), jnp.float32)]
    else:            # a key block of a QUERY head: float32 where a group's are summed outside
        kv_out = [of_kv(width, 1), of_kv(v_width, 1)]
        kv_shape = [jax.ShapeDtypeStruct((batch, heads, length, w), t.dtype if group == 1 else jnp.float32)
                    for w, t in ((width, k), (v_width, v))]
        kv_acc = [pltpu.VMEM((block, width), jnp.float32), pltpu.VMEM((block, v_width), jnp.float32)]
    dq, dk, dv = pl.pallas_call(
        functools.partial(_kernel, allowed=allowed, block=block, group=group, kv_rows=kv_rows),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(batch, heads, steps.q_block.size),
            in_specs=[of_q(width), of_kv(width, group), of_kv(v_width, group), of_q(v_width), of_row, of_row],
            out_specs=[whole(width, 1), *kv_out],
            scratch_shapes=[pltpu.VMEM((length, width), jnp.float32), *kv_acc]),
        out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype), *kv_shape],
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel", "arbitrary", "arbitrary"),
                                             vmem_limit_bytes=VMEM_LIMIT),
        cost_estimate=_cost(steps.q_block.size, block, q.shape, v_width, kv_heads, q.dtype.itemsize),
        interpret=interpret, name="attention_dq_dk_dv",
    )(*(jnp.asarray(t) for t in steps), q, k, v, do, lse[:, :, None], di[:, :, None])
    if not kv_rows and group > 1:
        dk, dv = (t.reshape(batch, kv_heads, group, length, -1).sum(2).astype(like.dtype) for t, like in ((dk, k), (dv, v)))
    return dq, dk, dv
