"""Attention's backward pass as ONE Pallas TPU kernel under every rule of
`ops/masked_attention.py`, a rule over positions (the causal rule, the sliding
window) or a mask that is STORED (block diffusion's few distinct cut blocks,
the selected rule's picks of the step): dq, dk and dv of a (key block, query
block) pair from one pass over its scores, with dq kept on the chip.

The stock splash kernels offer two backward forms.  The pair (`dq`, `dkv`)
computes every pair's scores and `dp` twice, seven products where five do.  The
fused one computes them once and writes dq as a PARTIAL a block of keys, `[L /
block, Hq, L, dh]` rounded to the operands' dtype, which XLA then sums (1.9 GB
a layer at 28 heads of 16384 x 128), over a grid that cannot be shrunk to the
blocks the rule leaves: its query step has to BE the query block.  Here

  * the grid is (row, query head, STEP), a step a (key block, query block)
    pair that the rule leaves, key blocks in order and a key block's query
    blocks in order, read from scalar memory (`steps_of` makes the three tables
    from the stock dkv block map, `steps_over` for a mask that is data).  A
    pair the rule empties is no step: the causal rule at 16 blocks has 136
    steps a head, not 256, a window of four blocks 70, block diffusion's 8192
    queries against 4096 keys 20 of 32;
  * a float32 VMEM accumulator holds the head's whole dq, `[Lq, dh]` (8 MB at
    16384 x 128): zeroed at the head's first step, added to at the step's query
    block, written ONCE in the operands' dtype at the head's last step.  Nothing
    is rounded before the sum, and no partial reaches HBM;
  * dk and dv are float32 accumulators too.  Where every query head has its own
    key/value head they hold one key block, zeroed at its first step and written
    at its last.  Under GROUPED key/value heads they hold the key/value head's
    whole rows, `[Lk, dh]` each, zeroed at the group's first head and written at
    its last (`kv_rows`: the query heads of a group are neighbours in the grid),
    so nothing is summed outside.  Queries and keys may differ in length (block
    diffusion's far term: 2L queries against the L clean keys);
  * under a rule over positions every block's mask is computed by the rule's own
    function (`causal_allowed`, `window_allowed`: two compares a pair at the
    most).  A whole block could skip it and does not: with the step bound by the
    matrix unit the second body read the same to 0.1 ms at every shape priced,
    64-wide heads among them, and cost its lowering again at every call;
  * under a STORED mask (PR 68) the step's `[keys, queries]` block of it, one
    byte a pair, is one more operand, brought by a `BlockSpec` whose index comes
    from the step tables, and `p = exp(where(block, s, MASK_VALUE) - lse)` is the
    line it was.  A RULE's stored blocks are its few distinct cut ones and one of
    ones, `[n + 1, block, block]`, named by a fourth table `mask_of[step]`
    (`stored_blocks`; at SDAR's shape 8 of a head's 20 steps read one of two cut
    blocks, 12 the ones: one body).  A mask that is DATA is each row's own,
    `[B, Lk, Lq]`, indexed (row, key block, query block) over a STATIC grid, the
    causal triangle or the whole square; what a row's picks leave of a pair is
    the fourth table's state `[B, steps]`, and a pair none of whose queries
    holds a key of it computes nothing (`pl.when`).  Every row rides the grid's
    first axis: no loop over rows.  A call under a rule over positions has
    neither operand nor table and traces to what it traced to before.

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
  (1, 16 on 2, 16384, 256), causal        48.37   52.92         61.88      (my chip run, PR 69: 256-wide heads, groups of EIGHT)

Under a STORED mask (my chip run, PR 68, call 2; `STORED=1 python3
tools/chip_block_attention.py`; ours, dk and dv of a group summed in VMEM |
outside in float32 | an int32 a pair of the mask in place of a byte | the stock
pair; the selected rule's picks ~2048 a query under the causal rule, every one
of its 136 blocks cut):

  grid block                                          1024                              512
  (2, 32 on 4, 8192 on 4096, 128), block diffusion    18.34 | 18.76 | 18.50 | 24.62     20.17 | 20.39 | 20.39 | 26.81
  (1, 32 on 4, 16384, 128), selected                  78.96 | 80.28 | 81.93 | 109.06    83.34 | 84.75 | 86.03 | 118.53

so 1024-blocks, a byte a pair (an int32 block twice is 8 MiB, and at the
second shape pushes the group's rows out of the VMEM) and the group's dk and dv
on the chip at both: 25.5% and 27.6% under the pair, whose dq and dkv agree
with ours to bf16's rounding (dq equal, dk 1.7e-3 and dv 7.1e-4 of the largest at
the most).

A group's dk and dv summed in VMEM (`kv_rows`) against a query head's written in
float32 and summed by XLA: 24.23 | 25.04 at 28 on 4 x 16384 (0.94 GB a layer
written and read back), 29.74 | 30.56 at 32 on 8 x 8192, 7.20 | 7.35 at 40 on
20, 18.39 | 18.33 at 64 on 8 x 16384 under a window of 512 and 74.06 | 74.03 at
48 on 8 under the causal rule (PR 65: level, where a band 512 wide or groups of six
leave the sum outside little to move): in VMEM wherever the rows fit.  At 256-wide heads and 16384 keys they do
NOT: a head's dq and its output block hold 32 MiB (`vmem_bytes` 46 with the blocks beside them), a key/value head's
dk and dv rows would hold 64 more (110 MiB: the chip's compiler refuses it at every grid block, `RESOURCE_EXHAUSTED`,
my chip run, PR 69), so dk and dv of a group of eight go out in float32 a query head, 0.54 GB a layer, and are summed
outside: 48.37 ms forward and backward at 1024-blocks (15.38 of it forward), 51.51 at 512, 66.95 at 256, against the
stock fused backward's 52.92 and the stock pair's 61.88 at 1024 (`WIDE=256 python3 tools/chip_block_attention.py`);
a kernel that holds a key/value head's rows and a query BLOCK's dq, or half a head's rows, is not written
(ROADMAP.md, R).  The call is a `jax.jit` of its own so that a
model's layers share one lowering (`setup_s` is end to end).
"""
from __future__ import annotations

import functools
from typing import Callable, NamedTuple, Optional

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
#: A pair of a STORED mask: one byte (`tools/chip_block_attention.py` prices a wider one: the module's table).
STORED_DTYPE = jnp.int8


class Steps(NamedTuple):
    """The grid's last axis: the query block and the key block of every step,
    and its marks (`_FIRST`, `_LAST`: of its key block's steps)."""
    q_block: np.ndarray
    kv_block: np.ndarray
    marks: np.ndarray


def _steps(columns) -> Steps:
    """The steps from each key block's query blocks, a key block after the other."""
    q_block, kv_block, marks = [], [], []
    for column, queries in enumerate(columns):
        assert len(queries), "a key block that no query sees would never be written"
        for n, query in enumerate(queries):
            q_block.append(int(query))
            kv_block.append(column)
            marks.append(_FIRST * (n == 0) + _LAST * (n == len(queries) - 1))
    return Steps(*(np.asarray(t, np.int32) for t in (q_block, kv_block, marks)))


def steps_of(dkv_map) -> Steps:
    """The steps from the stock dkv block map (`info_lib.process_mask_dkv`,
    shrunk or not): every entry with a `block_mask`, its query block the map's
    `data_next`, a key block (the map's column) after the other."""
    assert dkv_map.block_mask.shape[0] == 1, "one mask for every head"
    state, queries = np.asarray(dkv_map.block_mask[0]), np.asarray(dkv_map.data_next[0])
    return _steps(queries[np.nonzero(state[:, column])[0], column] for column in range(state.shape[1]))


def steps_over(blocks: int, causal: bool) -> Steps:
    """The steps of a mask that is DATA over `blocks` blocks of queries and as
    many of keys: the grid is static, the pairs under the diagonal where the
    causal rule is laid over the picks and the whole square otherwise, in
    `steps_of`'s order; what a row's picks leave of a pair is the step's state."""
    return _steps(range(column if causal else 0, blocks) for column in range(blocks))


def stored_blocks(dkv_map, block: int):
    """`backward`'s `stored` (the blocks `[n + 1, keys, queries]` one byte a pair,
    `mask_of[step]`) of a stock dkv block map whose cut blocks are STORED: a cut step names its
    block among the map's few distinct ones (none where the rule's blocks are
    the grid's), a whole step the block of ones appended to them (one body for
    both: PR 64 priced a second body level with the select it saves)."""
    state = np.asarray(dkv_map.block_mask[0])
    cut = np.zeros((0, block, block), bool) if dkv_map.partial_mask_blocks is None else np.asarray(dkv_map.partial_mask_blocks)
    cut = cut.reshape(-1, block, block)
    mask_of = [int(dkv_map.mask_next[0][row, column]) if state[row, column] == 1 else cut.shape[0]
               for column in range(state.shape[1]) for row in np.nonzero(state[:, column])[0]]
    return np.concatenate([cut, np.ones((1, block, block), bool)]).astype(STORED_DTYPE), np.asarray(mask_of, np.int32)


def _padded(width: int) -> int:
    return -(-width // LANES) * LANES


def vmem_bytes(lengths, widths, kv_rows: bool, stored: int = 0) -> int:
    """What the kernel holds in VMEM at `lengths` positions (of queries, of
    keys) of `widths` (queries and keys, values): dq's float32 accumulator and
    its bf16 output block (a head's whole rows, twice: Pallas buffers an
    output), with `kv_rows` the same for dk and dv over the keys, and under a
    STORED mask a step's `[stored, stored]` block of it, twice (Pallas buffers an input)."""
    q_len, kv_len = lengths
    lanes = q_len * _padded(widths[0]) + (kv_len * (_padded(widths[0]) + _padded(widths[1])) if kv_rows else 0)
    return lanes * (4 + 2 * 2) + _VMEM_BESIDE + 2 * jnp.dtype(STORED_DTYPE).itemsize * stored * stored


def kv_rows_fit(lengths, widths, group: int, stored: int = 0) -> bool:
    """Are dk and dv accumulated over a group's query heads on the chip?  Where
    key/value heads are grouped and their whole rows fit beside dq's."""
    return group > 1 and vmem_bytes(lengths, widths, True, stored) <= VMEM_LIMIT


def _kernel(q_of, kv_of, marks_of, q_ref, k_ref, v_ref, do_ref, lse_ref, di_ref, dq_ref, dk_ref, dv_ref,
            dq_acc, dk_acc, dv_acc, *, allowed: Optional[Callable], block: int, group: int, kv_rows: bool,
            mask_ref=None, state_of=None):
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

    def pair():
        q, do, k, v = q_ref[...], do_ref[...], k_ref[...], v_ref[...]
        s = jax.lax.dot_general(k, q, _NT, preferred_element_type=jnp.float32)          # [keys, queries]
        if mask_ref is None:
            q_ids = q_block * block + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            kv_ids = kv_block * block + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
            keep = allowed(q_ids, kv_ids)
        else:
            keep = mask_ref[...] != 0
        p = jnp.exp(jnp.where(keep, s, MASK_VALUE) - lse_ref[...])
        dv_acc[into, :] += jnp.dot(p.astype(do.dtype), do, preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(v, do, _NT, preferred_element_type=jnp.float32)
        ds = (p * (dp - di_ref[...])).astype(q.dtype)
        dk_acc[into, :] += jnp.dot(ds, q, preferred_element_type=jnp.float32)
        dq_acc[q_rows, :] += jax.lax.dot_general(ds, k, _TN, preferred_element_type=jnp.float32)

    if state_of is None:
        pair()
    else:     # a mask that is data: a pair none of whose queries chose a key of it computes nothing
        pl.when(state_of[pl.program_id(0), step] != 0)(pair)

    @pl.when(last_step)
    def _():
        write(dq_ref, dq_acc)

    @pl.when(last_of_kv)
    def _():
        write(dk_ref, dk_acc)
        write(dv_ref, dv_acc)


def _stored_kernel(q_of, kv_of, marks_of, table, q_ref, k_ref, v_ref, do_ref, lse_ref, di_ref, mask_ref, *refs, by_row: bool, **how):
    """`_kernel` under a STORED mask: one scalar table and one operand more."""
    _kernel(q_of, kv_of, marks_of, q_ref, k_ref, v_ref, do_ref, lse_ref, di_ref, *refs, allowed=None, mask_ref=mask_ref,
            state_of=table if by_row else None, **how)


def _cost(steps: int, block: int, q_shape, kv_shape, v_width: int, itemsize: int, stored_bytes: int = 0) -> pl.CostEstimate:
    """The five products over the pairs of the steps' blocks, an exponential a
    pair, each operand and result once, and a stored mask's blocks as often as
    they are read."""
    batch, heads, length, width = q_shape
    pairs = batch * heads * steps * block * block
    of_queries = batch * heads * length * (2 * width + v_width)          # q, dq, do
    of_keys = 2 * batch * kv_shape[1] * kv_shape[2] * (width + v_width)           # k, v, dk, dv
    return pl.CostEstimate(flops=int(2 * pairs * (3 * width + 2 * v_width)), transcendentals=int(pairs),
                           bytes_accessed=int(itemsize * (of_queries + of_keys) + 8 * batch * heads * length + stored_bytes))


@functools.partial(jax.jit, static_argnames=("allowed", "block", "interpret"))
def backward(q, k, v, lse, do, di, steps: Steps, allowed: Optional[Callable], block: int, interpret: bool = False, stored=None):
    """dq, dk, dv of softmax(q k^T under the rule) v given the rows'
    log-sum-exp `lse` and `di` = rowsum(do . out), both (B, Hq, Lq) float32; q,
    do (B, Hq, Lq, .), k, v (B, Hkv, Lk, .), Hkv a divisor of Hq, the queries
    carrying the scale.  `steps` names the blocks of `block` positions that the
    rule leaves.  A rule over positions is `allowed(q_ids, kv_ids)` (the one
    function a rule: the call is traced once for each) and `stored` None.  A
    STORED mask is `allowed` None and `stored` its two arrays, one byte a pair
    and laid `[keys, queries]` as the scores are: either (a rule's few distinct
    blocks `[n, block, block]`, int32 `mask_of[step]`: `stored_blocks`), or (each
    row's own mask `[B, Lk, Lq]`, int32 state `[B, steps]`: 0 where none of a
    step's pairs is allowed, and the step computes nothing)."""
    batch, heads, q_len, width = q.shape
    kv_heads, kv_len, v_width = k.shape[1], k.shape[2], v.shape[-1]
    group = heads // kv_heads
    kv_rows = kv_rows_fit((q_len, kv_len), (width, v_width), group, block if stored else 0)
    tables = [jnp.asarray(t) for t in steps]

    # a step's block of queries, of keys (of the `heads_a`-th part of the query heads: a key/value head's, or a query
    # head's own), its row of a per-query statistic, and a head's whole rows
    of_q = lambda w: pl.BlockSpec((None, None, block, w), lambda b, h, s, q_of, kv_of, *_: (b, h, q_of[s], 0))   # noqa: E731
    of_kv = lambda w, heads_a: pl.BlockSpec((None, None, block, w), lambda b, h, s, q_of, kv_of, *_: (b, h // heads_a, kv_of[s], 0))   # noqa: E731
    of_row = pl.BlockSpec((None, None, 1, block), lambda b, h, s, q_of, *_: (b, h, 0, q_of[s]))
    whole = lambda length, w, heads_a: pl.BlockSpec((None, None, length, w), lambda b, h, s, *_: (b, h // heads_a, 0, 0))   # noqa: E731
    if kv_rows:      # a key/value head's whole rows, written at its group's last head
        kv_out = [whole(kv_len, width, group), whole(kv_len, v_width, group)]
        kv_shape = [jax.ShapeDtypeStruct(k.shape, k.dtype), jax.ShapeDtypeStruct(v.shape, v.dtype)]
        kv_acc = [pltpu.VMEM((kv_len, width), jnp.float32), pltpu.VMEM((kv_len, v_width), jnp.float32)]
    else:            # a key block of a QUERY head: float32 where a group's are summed outside
        kv_out = [of_kv(width, 1), of_kv(v_width, 1)]
        kv_shape = [jax.ShapeDtypeStruct((batch, heads, kv_len, w), t.dtype if group == 1 else jnp.float32)
                    for w, t in ((width, k), (v_width, v))]
        kv_acc = [pltpu.VMEM((block, width), jnp.float32), pltpu.VMEM((block, v_width), jnp.float32)]
    how = dict(block=block, group=group, kv_rows=kv_rows)
    operands = [q, k, v, do, lse[:, :, None], di[:, :, None]]
    in_specs = [of_q(width), of_kv(width, group), of_kv(v_width, group), of_q(v_width), of_row, of_row]
    if stored is None:
        kernel, stored_bytes = functools.partial(_kernel, allowed=allowed, **how), 0
    else:
        mask, table = stored
        by_row = table.ndim == 2
        kernel = functools.partial(_stored_kernel, by_row=by_row, **how)
        tables.append(table)
        operands.append(mask)
        in_specs.append(pl.BlockSpec((None, block, block), (lambda b, h, s, q_of, kv_of, *_: (b, kv_of[s], q_of[s])) if by_row
                                     else (lambda b, h, s, q_of, kv_of, marks, mask_of: (mask_of[s], 0, 0))))
        stored_bytes = batch * heads * steps.q_block.size * block * block * mask.dtype.itemsize
    dq, dk, dv = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(tables), grid=(batch, heads, steps.q_block.size),
            in_specs=in_specs, out_specs=[whole(q_len, width, 1), *kv_out],
            scratch_shapes=[pltpu.VMEM((q_len, width), jnp.float32), *kv_acc]),
        out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype), *kv_shape],
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel", "arbitrary", "arbitrary"),
                                             vmem_limit_bytes=VMEM_LIMIT),
        cost_estimate=_cost(steps.q_block.size, block, q.shape, k.shape, v_width, q.dtype.itemsize, stored_bytes),
        interpret=interpret, name="attention_dq_dk_dv",
    )(*tables, *operands)
    if not kv_rows and group > 1:
        dk, dv = (t.reshape(batch, kv_heads, group, kv_len, -1).sum(2).astype(like.dtype) for t, like in ((dk, k), (dv, v)))
    return dq, dk, dv
