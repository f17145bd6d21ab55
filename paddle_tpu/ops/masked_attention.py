"""Attention under a mask that is a rule over positions, not a tensor, and,
at the module's end (`selected_attention`), under one that IS a tensor of the
step: each query's own chosen keys, through the same forward kernel and the same one backward kernel.

Three rules.  The SLIDING-WINDOW one (`window_allowed`: key j for query i where
i - window < j <= i; `fused_attention`'s `mask="sliding_window"`, `window_plan`)
is the causal rule's kernels under the stock local mask: the block maps hold
only the blocks the band touches (at 8192 keys and a window of 512, 31 of the
256 512-blocks, every one of them cut), the cut blocks' mask is computed in the
kernel from the positions (two compares a pair), and a window that reaches the
sequence's start from every query IS the causal rule and takes its plan.  The
CAUSAL one (`causal_allowed`: key j <= query i over equal
lengths; `fused_attention`'s `causal` at long keys, `causal_plan`) is the stock
splash forward kernel under the stock causal mask and nothing round it but the
queries' scaling.  BACKWARD under every rule of this module is ONE kernel of our own
(`ops/attention_backward_kernels.py`, `Plan.backward`): dq, dk and dv from one
pass over the scores of the (key block, query block) pairs the rule leaves, dq
summed in VMEM, the cut blocks' mask computed in the kernel under these two
rules and read from a stored block under the other two (PR 68).  At 4096 keys 10 of
the square's 16 1024-blocks are visited and 4 of them cut, at 8192 36 of 64 and
8.  What is said below of the far term's forward kernel, its block maps and
grouped key/value heads holds for it; nothing of the own-block term does.  The other rule is block-diffusion training's
(BD3-LM, Arriola et al.
2025, arXiv:2503.09573; SDAR trains this way).  L tokens of data are 2L
positions: i < L the noised copy x_t, i >= L the clean copy x_0, in blocks
of B, blk(i) = (i mod L) div B.  Query i may see key j where

    i <  L, j <  L, blk(j) == blk(i)     a noised block sees itself, both ways
    i <  L, j >= L, blk(j) <  blk(i)     ... and the clean blocks strictly before it
    i >= L, j >= L, blk(j) <= blk(i)     a clean block sees the clean blocks up to itself

and nothing else: a clean query never sees a noised key.  A quarter of the
(query, key) square is allowed (exactly 1/4 + 1/(4 . L/B) of it).

`block_diffusion_allowed` is the rule, over numpy or jax integers: the XLA
attention of `fused_attention` builds the dense mask from it (tiny sizes: the
CPU tests and goldens), and `block_sparse_attention` splits it into the term
that has block structure and the one that has none:

* the FAR term, all 2L queries against the L clean keys, through the stock
  splash-attention forward kernel (jax.experimental.pallas.ops.tpu.splash_attention)
  and the one backward kernel, under the rule's `[2L, L]` rectangle as a mask they can
  ask for any block of.  The block maps are made from the rule at trace time
  (numpy, a block of the grid at a time): blocks the rule empties are never
  visited nor their keys fetched, blocks it fills skip the mask, and the
  blocks it cuts read theirs from the few DISTINCT cut blocks, which are all
  of the mask that is kept on the device.  At SDAR's cell 20 of the
  rectangle's 32 1024-blocks are visited, 12 of them whole and 8 cut (two
  distinct ones); the noised half's keys and values enter no stock kernel.
* the NEAR term, every noised query against the B noised keys of its own
  block.  In the stock kernels' grid it is the diagonal of the noised
  quadrant: a sixth of every kernel's visited blocks, each B / 1024 full,
  for 0.1% of the allowed pairs.  No product of B x dh by dh x B fills the
  MXU and plain jax writes 128-wide float32 score tiles to HBM for the 4 in
  128 it needs (5.4 ms a layer, which gave back the 4.9 the far term won: my
  chip runs, PR 33), so two kernels of this module compute it a 128-tile of
  the diagonal at a time in VMEM (`_join_kernel`, `_own_block_backward_kernel`).
* JOINED exactly by the log-sum-exp: lse = logaddexp(lse_f, lse_n), out =
  exp(lse_f - lse) out_f + exp(lse_n - lse) out_n in float32, in place over
  the far term's output (the clean rows pass through).  The first noised
  block's queries have no far key: the stock kernel leaves them a log-sum-exp
  of its `mask_value` -2.38e38 under a finite output, so their far weight is
  exactly 0.  The far term's output leaves its kernel rounded to the
  operands' dtype, so a noised row carries that rounding and the join's.
* BACKWARD as one `custom_vjp` over the whole op: di = rowsum(do . out) from
  the joined output, the far term's dq, dk, dv from the one backward kernel
  given the joined `lse` and `di` (2L queries against L keys; a step's block of
  the mask one of the two distinct cut blocks or the block of ones, a byte a
  pair: a row with no far key reads p = exp(mask_value - lse) = 0 under its
  finite joined log-sum-exp), the near term's by the same formulas, added to
  the noised rows of dq in place.

The split is taken where the rule's block is smaller than the kernels' and
whole blocks of the rule make up a lane tile (`plan_of`: every block length
BD3-LM or SDAR uses); a rule's block as large as the kernels' keeps one call
over the whole square, which is the far term over all keys and no near term.
No [2L, 2L] array exists on the device, forward or backward, nor on the host,
and no float32 [2L, L] one.  Computing the cut blocks' mask from the positions
inside the kernel instead (the kernel's "computable" masks) was priced and
lost by 19 ms a layer (`_BLOCKS`' table).  Key/value heads may be fewer than
query heads: the index maps read key/value head j div (Hq / Hkv), nothing is
repeated.
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..monitor import MONITOR as _MON
from .common import counted_rules

#: The rules an op's `mask` attribute may name (`causal` is an attribute of its own).
MASKS = ("block_diffusion", "sliding_window")

#: Queries and keys a block of the kernels' grids, the largest that divides the
#: length, forward and both backward kernels, and the keys a step inside a
#: block (`_KV_COMPUTE`).  TPU v5e, (2, 32, 8192, 128) queries over
#: (2, 4, 8192, 128) keys and values, bf16, forward + backward of one layer, ms
#: (my chip runs; tools/chip_block_attention.py).  The whole square against
#: the clean keys with the own-block term joined, by the rule's block (PR 33):
#:
#:   rule's block           4       16      128     256
#:   whole square           28.17   28.39   28.22   28.30   (24 of 64 1024-blocks, 12 cut at 4)
#:   far + near             24.60   24.70   24.64   24.75   (20 of 32, 8 cut; the two forms 6e-3 apart
#:                                                           at the most, output and gradients)
#:
#: no crossing below the kernels' block, so `plan_of` holds no constant.  The
#: whole square at a rule's block of 4, by the kernels' block (PR 32):
#:
#:   block                  256     512     1024    1024, 512 keys a step   2048 with 1024
#:   two backward kernels   60.99   30.51   28.29   27.44                   scoped VMEM overrun
#:   fused backward         -       28.08   24.36
#:   mask computed in       74.85   49.57   49.48   (the kernel's "computable" mask: the rule
#:   the kernel                                      evaluated on the positions of every cut block)
#:
#: At 512 the rule leaves 80 of 256 blocks (24 cut), at 1024 24 of 64 (12 cut):
#: fewer, larger blocks win although more of what they hold is masked.  The
#: fused backward (dq, dk and dv from one pass over the scores) is the fastest
#: alone and is NOT taken: it writes dq once per block of keys, 1.07 GB a layer
#: at 1024 and 2.15 GB at 512 (half that over the clean keys alone), and inside
#: the cell's step its 1024-block kernel overruns the scoped VMEM (19.1 of 16
#: MB) that it fits alone.  So does the dq kernel at 1024 x 1024 (16.47 MB:
#: compiled here for the described v5e, the whole step; alone it fits), which
#: therefore takes `_KV_COMPUTE` queries a block against 1024 keys; and the
#: forward kernel over the whole square where its queries are a parameter of
#: the program and not the scaling's result (16.77 MB: PR 33).
#:
#: Under the CAUSAL rule (PR 37; forward + backward of a layer alone, ms; the
#: stock flash kernel as `fused_attention` called it 15.06 and 48.22):
#:
#:   (4, 16, 4096, 128)            1024, 1024 keys a step   1024, 512   512     2048
#:   dq, dkv apart; cut stored     12.67                    12.35       14.09   VMEM overrun
#:   dq, dkv apart; cut computed   12.43                    11.85       13.87
#:   fused backward; stored        10.34                     9.96       11.52
#:   fused backward; computed       9.99                     9.87       11.31
#:   (2, 32 on 8, 8192, 64)
#:   dq, dkv apart; stored | computed    40.63 | 39.62      39.49 | 38.51   47.12 | 46.64
#:   fused backward; stored | computed   33.51 | 32.79      32.84 | 32.10   39.55 | 39.02
#:
#: so 1024-blocks with 512 keys a step again, the cut blocks COMPUTED (one
#: compare a pair; with them stored the fused kernel's [keys, queries] block of
#: the mask overran the scoped VMEM inside OLMoE's and LFM2's steps, 16.64 and
#: 17.14 of 16 MB, which it fits alone) and the backward FUSED: in OLMoE's step
#: 32.627 samples/s against 31.948 with dq and dkv apart and 31.207 on the
#: flash kernel.  Its dq is a partial a block of keys, rounded to the operands'
#: dtype before XLA sums them (4 or 8 of them: 0.27 and 1.07 GB written; dq
#: against float32 reads 3.39e-3 for 3.33e-3 at 128-wide heads and 2.59e-3 for
#: 2.50e-3 at 64-wide, tools/chip_attention_errors.py).
#:
#: Since PR 64 backward under the causal rule is ONE kernel of our own that sums
#: dq in VMEM (`Plan.backward`, `ops/attention_backward_kernels.py`), its grid the
#: (key block, query block) pairs under the diagonal.  Forward + backward of a
#: layer alone, ms (my chip run, PR 64, call 1; the stock rows read again in the
#: same call, cut blocks computed):
#:
#:   block (keys a pass)                  1024 (1024)     1024 (512)      512 (512)
#:   (4, 16, 4096, 128), OLMoE's
#:   fused, dq on the chip                 9.34            9.47           10.63
#:   stock fused backward                 10.05            9.76           11.23
#:   (2, 32 on 8, 8192, 64), LFM2's       dk and dv of a group summed in VMEM | outside in float32
#:   fused, dq on the chip                29.46 | 30.28   29.74 | 30.56   33.19 | 34.18
#:   stock fused backward                 32.82           32.06           38.84
#:   (1, 32, 16384, 192 | 128), Kanana-2's latent attention
#:   fused, dq on the chip                79.76           80.30           88.75
#:   stock fused backward | the pair                      91.31 | 102.57
#:   (1, 28 on 4, 16384, 128), SmallThinker's full layer
#:   fused, dq on the chip                                44.82 | 45.73
#:   stock fused backward                                 49.15
#:   (1, 16 on 2, 16384, 256), Qwen3-Next's full layer (my chip run, PR 69: dk and dv outside; in VMEM they do not fit)
#:   fused, dq on the chip                                48.37           51.51           (66.95 at 256-blocks)
#:   stock fused backward | the pair                      52.92 | 61.88   60.30 | 67.64
#:
#: so ours at every shape, 1024-blocks, a block's keys ONE pass (the kernel has
#: no inner loop: 0.5 to 2% over 512 keys a pass now that the VMEM is ours), a
#: group's dk and dv summed in VMEM where a key/value head's rows fit.  dq is
#: rounded once: against float32 3.34e-3 for the stock fused kernel's 3.49e-3 at
#: (1, 4, 8192, 128), 8 partials; 2.50e-3 for 2.59e-3 at 64-wide heads; dk and dv
#: to the sixth digit the stock kernel's (tools/chip_attention_errors.py).
#:
#: Since PR 68 backward under BLOCK DIFFUSION's rule is that kernel too, a step's block of the mask read from the
#: rule's distinct cut blocks (a byte a pair, [3, 1024, 1024] at SDAR's shape with the block of ones that the whole
#: steps read).  (2, 32 on 4, 8192 queries on 4096 clean keys, 128), far + near, forward + backward of a layer alone,
#: ms (my chip run, PR 68, call 2; `STORED=1 python3 tools/chip_block_attention.py`):
#:
#:   block                                    1024 (20 steps a head)   512 (72)
#:   ours, dk and dv of a group in VMEM       18.34                    20.17
#:   ours, summed outside in float32          18.76                    20.39
#:   ours, an int32 a pair of the mask        18.50                    20.39
#:   the stock dq and dkv pair                24.62                    26.81
#:
#: so 1024-blocks and a byte a pair, 25.5% under the pair; dq is the pair's to the last bit, dk 1.1e-3 of the largest.
_BLOCKS = (1024, 512, 128)
_KV_COMPUTE = 512


def block_diffusion_allowed(q_ids, kv_ids, seq: int, block: int):
    """May position `q_ids` see position `kv_ids`?  Broadcasts; numpy in,
    numpy out (the kernel's block map, the tests), jax in, jax out (inside the
    kernel, and XLA's dense mask)."""
    q_clean, kv_clean = q_ids >= seq, kv_ids >= seq
    q_blk = (q_ids - seq * q_clean) // block
    kv_blk = (kv_ids - seq * kv_clean) // block
    return ((~q_clean & ~kv_clean & (kv_blk == q_blk))
            | (~q_clean & kv_clean & (kv_blk < q_blk))
            | (q_clean & kv_clean & (kv_blk <= q_blk)))


def causal_allowed(q_ids, kv_ids):
    """May query `q_ids` see key `kv_ids` under the causal rule?  Broadcasts,
    numpy or jax, as `block_diffusion_allowed` does."""
    return kv_ids <= q_ids


def window_allowed(q_ids, kv_ids, window: int):
    """May query `q_ids` see key `kv_ids` under a sliding window of `window`
    keys, the query's own position the last of them?  Broadcasts, numpy or
    jax, as `block_diffusion_allowed` does."""
    return (kv_ids <= q_ids) & (kv_ids > q_ids - window)


def window_pairs(length: int, window: int) -> int:
    """(query, key) pairs the window rule allows among `length` positions: the
    causal triangle where the window reaches the start from every query."""
    w = min(window, length)
    return w * (w + 1) // 2 + (length - w) * w


def allowed_pairs(positions: int, block: int) -> int:
    """(query, key) pairs the rule allows among `positions` = 2L positions."""
    seq = positions // 2
    n = seq // block
    return block * block * (n + n * (n - 1) // 2 + n * (n + 1) // 2)


def kernel_block(q_len: int):
    """The grid's block for `q_len` positions, None where the kernel is not
    taken: lengths that are no whole number of blocks."""
    return next((b for b in _BLOCKS if q_len % b == 0), None)


def _rule_mask(positions: int, first_key: int, block: int):
    from jax.experimental.pallas.ops.tpu.splash_attention import splash_attention_mask as mask_lib

    class BlockDiffusionMask(mask_lib.Mask):
        """The rule over all 2L queries and the keys from `first_key` on, as a
        mask the kernel's block map can slice: any [queries, keys] window of
        it, computed when asked for."""

        shape = (positions, positions - first_key)

        def __getitem__(self, idx):
            q, kv = (np.arange(s.start or 0, n if s.stop is None else s.stop) for s, n in zip(idx, self.shape))
            return block_diffusion_allowed(q[:, None], first_key + kv[None, :], positions // 2, block)

        def __eq__(self, other):
            return isinstance(other, type(self)) and self.shape == other.shape

        def __hash__(self):
            return hash((type(self).__name__, positions, first_key, block))

    return BlockDiffusionMask()


class Plan(NamedTuple):
    """What one attention's kernels are built from, all of it read off the
    shapes and the rule's block."""
    positions: int
    heads: int
    mask_block: int
    block: int       # of the kernels' grids
    first_key: int   # L where the own-block term is split off the kernels, else 0
    interpret: bool
    rule: str = "block_diffusion"
    causal: bool = False   # under the rule "selected": the causal rule laid over the picks too
    widths: tuple = (128, 128)   # of a head's queries and keys, and of its values

    @property
    def tile(self) -> int:
        """Noised positions a tile of the own-block term: a lane tile of
        scores, or the rule's block where that is larger."""
        return max(self.mask_block, 128)

    @property
    def stored(self) -> bool:
        """Are the rule's cut blocks STORED (block diffusion's few distinct
        ones, the selected rule's whole mask) and not computed from positions?"""
        return self.rule in ("block_diffusion", "selected")

    @property
    def backward(self) -> str:
        """Which backward form the plan takes, read off the rule, the widths and
        the lengths alone.  `"onchip_dq"`: the one kernel of
        `ops/attention_backward_kernels.py`, dq, dk and dv from one pass over the
        scores of the blocks the rule leaves, dq summed in VMEM; under every
        rule, the cut blocks' mask computed from positions (`causal`,
        `sliding_window`) or a STORED block a step (`block_diffusion`,
        `selected`), wherever a head's whole dq and that block fit the kernel's
        VMEM.  `"stock_pair"`: the stock dq and dkv kernels, each computing its
        scores again; where they do not fit.  The stock
        FUSED kernel, which the causal rule took until PR 64, is no form any
        more: it writes dq as one partial a block of keys, [L / block, Hq, L, dh]
        rounded to the operands' dtype for XLA to sum, over a dkv grid that is
        not shrunk to the rule's blocks, and lost to the kernel of our own at
        every shape priced (`_BLOCKS`' and `_WINDOW_BLOCKS`' tables)."""
        from . import attention_backward_kernels as onchip

        held = onchip.vmem_bytes((self.positions, self.positions - self.first_key), self.widths, False,
                                 self.block if self.stored else 0)
        return "onchip_dq" if held <= onchip.VMEM_LIMIT else "stock_pair"

    @property
    def sizes(self):
        from jax.experimental.pallas.ops.tpu.splash_attention import splash_attention_kernel as splash

        b, inner = self.block, min(self.block, _KV_COMPUTE)
        if self.rule == "selected":   # a stored block of the mask a grid step: [512, b] bytes, never [b, b]
            return splash.BlockSizes(block_q=inner, block_kv=b, block_kv_compute=inner, block_q_dkv=inner, block_kv_dkv=b,
                                     block_kv_dkv_compute=inner, block_q_dq=inner, block_kv_dq=b)
        dq = {} if self.backward == "onchip_dq" else dict(block_q_dq=inner, block_kv_dq=b)
        return splash.BlockSizes(block_q=b, block_kv=b, block_kv_compute=inner, block_q_dkv=b, block_kv_dkv=b,
                                 block_kv_dkv_compute=inner, **dq)


def plan_of(positions: int, heads: int, mask_block: int, interpret: bool = False, widths=(128, 128)) -> Plan:
    """The own-block term leaves the stock kernels where the rule's block is
    smaller than theirs (the noised quadrant's diagonal blocks are then cut
    blocks mask_block / block full) and whole blocks of the rule make up the
    own-block kernels' tile and that the stock kernels' block, which then is
    the largest that divides L, the far keys."""
    far, tile = kernel_block(positions // 2), max(mask_block, 128)
    if far is not None and mask_block < far and tile % mask_block == 0 and far % tile == 0:
        return Plan(positions, heads, mask_block, far, positions // 2, interpret, widths=tuple(widths))
    return Plan(positions, heads, mask_block, kernel_block(positions), 0, interpret, widths=tuple(widths))


def causal_plan(length: int, heads: int, interpret: bool = False, widths=(128, 128)) -> Plan:
    """The causal rule over `length` queries and as many keys: the kernels
    over the whole square (the rule's block is one position, and no term of it
    lacks block structure), their block the largest that divides the length."""
    return Plan(length, heads, 1, kernel_block(length), 0, interpret, "causal", widths=tuple(widths))


#: The kernels' block under the window rule: the smallest block of `_WINDOW_BLOCKS` that holds a whole window.  A
#: block of b visits 1 + ceil((window - 1) / b) blocks a block of queries, so about (b + window) / window times the
#: pairs the rule allows: smaller blocks compute fewer masked pairs and pay for it in grid steps, larger ones the
#: other way.  TPU v5e, (1, 40 on 20, 8192, 64) bf16, window 512, forward + backward of a layer alone, ms (my chip
#: run, PR 50; `WINDOW=1 python3 tools/chip_block_attention.py`; the causal rule over the same operands 20.61):
#:
#:   block (keys a step)           128      256      512 (512)   512 (256)   1024 (512)   1024 (1024)
#:   pairs visited / allowed       1.29     1.55     2.06        2.06        4.13         4.13
#:   dq and dkv apart              22.82    11.37     8.17        8.66       11.46        11.63
#:   fused backward                -        22.71    11.72        -          11.56
#:
#: so a block of the window's own length, `_KV_COMPUTE` keys a step, dq a kernel of its own (`window_attention` as the
#: op called it: 8.36).  The output and gradients against dense float32 at (1, 8 on 4, 2048, 64): 3.5e-3, 4.4e-3,
#: 2.7e-3, 3.8e-3 of the largest, the causal rule's readings.  Since PR 64 backward is the ONE kernel that sums dq in
#: VMEM over the band's blocks alone (my chip run, PR 64, call 1; the pair read again in the same call; dk and dv of a
#: group summed in VMEM | outside in float32):
#:
#:   block (keys a pass)           256 (256)       512 (512)       512 (256)       1024 (512)
#:   fused, dq on the chip         9.82 | 10.23    7.20 | 7.35     7.51 | 7.91     9.48 | 9.80
#:   dq and dkv apart              11.45           8.42            8.70            11.44
#:
#: the same block, 14% under the pair (`window_attention` as the op calls it: 6.94).
#:
#: The same window on 128-wide heads in groups of eight at twice the keys: TPU v5e, (1, 64 on 8, 16384, 128) bf16, window 512
#: (Laguna-XS.2's window layer), forward + backward of a layer alone, ms (my chip run, PR 65, call 1; `WINDOW=512x128
#: python3 tools/chip_block_attention.py`; the causal rule over the same operands 98.49, the band 6.15% of its pairs; dk and
#: dv of a group summed in VMEM | outside in float32):
#:
#:   block (keys a pass)           256 (256)        512 (512)        1024 (512)
#:   pairs visited / allowed       1.52             2.03             4.06
#:   fused, dq on the chip         26.13 | 26.13    18.39 | 18.33    26.69 | 26.67
#:   stock: dq and dkv apart       31.13            22.61            33.63
#:   stock: fused backward         -                50.24
#:   as the op calls it            26.39            18.23            26.70
#:
#: so the block of the window's own length again, which `window_block` already took: 63 steps a head where 256 keys a step
#: are 189 and lose 42% to the grid, and 1024 compute twice the masked pairs; a group's eight heads' dk and dv fit the VMEM
#: beside a head's dq (`kv_rows_fit`) and cost the same summed outside.  Against dense float32 at (1, 16 on 2, 2048, 128):
#: 3.4e-3, 6.9e-3, 3.8e-3, 2.2e-3.
#:
#: A window LONGER than the largest block takes that block, 1024.  TPU v5e, (1, 28 on 4, 16384, 128) bf16, window 4096
#: (SmallThinker's window layer), forward + backward of a layer alone, ms (my chip run, PR 63; `WINDOW=4096 python3
#: tools/chip_block_attention.py`; the causal rule over the same operands 49.59, the band 43.7% of its pairs):
#:
#:   block (keys a step)           512 (512)   1024 (512)   1024 (1024)   2048 (512)
#:   pairs visited / allowed       1.12        1.25         1.25          overruns the scoped VMEM
#:   dq and dkv apart              34.26       30.96        31.87
#:   fused backward                39.62       29.50
#:   as the op calls it            34.28       31.60
#:
#: so 1024, which `window_block` already took: a block of 512 visits 10% fewer pairs and loses 8% to its grid steps.
#: The stock fused backward was 1.5 ms a layer ahead alone at 1024 and was NOT taken: its partial dq, [16, 28, 16384,
#: 128] a layer, is 1.9 GB of the step's memory.  Since PR 64 (my chip run, PR 64, call 1; the stock rows read again in
#: the same call: 34.25, 30.87, 31.78 apart and 39.44, 29.34 fused; dk and dv of a group summed in VMEM | outside):
#:
#:   block (keys a pass)           512 (512)       1024 (512)      1024 (1024)
#:   fused, dq on the chip         25.34 | 26.35   24.23 | 25.04   23.67 | 24.85
#:
#: 70 steps a head for the pair's 80 + 80 and the stock fused kernel's 256: 23% under the pair.  Against dense float32
#: at (1, 7 on 1, 6144, 128): 2.4e-3, 4.0e-3, 5.2e-3, 3.8e-3.
_WINDOW_BLOCKS = (128, 256, 512, 1024)


def window_block(length: int, window: int):
    """The grid's block for the window rule over `length` positions, None where
    no block of `_WINDOW_BLOCKS` divides the length."""
    fitting = [b for b in _WINDOW_BLOCKS if length % b == 0]
    return next((b for b in fitting if b >= window), fitting[-1] if fitting else None)


def window_plan(length: int, heads: int, window: int, interpret: bool = False, widths=(128, 128)) -> Plan:
    """The window rule over `length` queries and as many keys.  A window that
    reaches the sequence's start from every query allows what the causal rule
    allows: that plan is the causal one, block maps and all."""
    if window >= length:
        return causal_plan(length, heads, interpret, widths)
    return Plan(length, heads, window, window_block(length, window), 0, interpret, "sliding_window", widths=tuple(widths))


@functools.lru_cache(maxsize=8)
def _window_rule(window: int):
    """`window_allowed` at one window, the one function a window: the kernels
    keep what they traced by the function they were handed."""
    return functools.partial(window_allowed, window=window)


@functools.lru_cache(maxsize=32)
def block_maps(plan: Plan):
    """The kernels' block maps for `plan`, forward, dq (None where one kernel
    computes dq, dk and dv: it walks the dkv map, `data_next` the query block of
    a step whether the map was shrunk to the rule's blocks, as the window's
    is, or not) and dkv, in numpy: made from the rule once a shape, a block
    of the grid at a time."""
    from jax.experimental.pallas.ops.tpu.splash_attention import splash_attention_mask as mask_lib
    from jax.experimental.pallas.ops.tpu.splash_attention import splash_attention_mask_info as info_lib

    sizes = plan.sizes
    if plan.rule == "causal":
        # The stock mask of the kind the kernels COMPUTE on a cut block (`_stock_options` hands them the rule): one
        # compare a pair, where block-diffusion's divisions lost by 19 ms (`_BLOCKS`' table).
        rule = mask_lib.CausalMask((plan.positions, plan.positions))
    elif plan.rule == "sliding_window":
        # the stock local mask, `mask_block` keys wide with the query's own the last: computed on a cut block as well
        rule = mask_lib.LocalMask((plan.positions, plan.positions), (plan.mask_block - 1, 0), 0)
    else:
        rule = _rule_mask(plan.positions, plan.first_key, plan.mask_block)
    mask = mask_lib.MultiHeadMask([rule] * plan.heads)
    shards = dict(downcast_smem_data=True, head_shards=1, q_seq_shards=1)
    return (info_lib.process_mask(mask, (sizes.block_q, sizes.block_kv), **shards)[0],
            None if sizes.block_q_dq is None else info_lib.process_mask(mask, (sizes.block_q_dq, sizes.block_kv_dq), **shards)[0],
            info_lib.process_mask_dkv(mask, (sizes.block_q_dkv, sizes.block_kv_dkv), **shards)[0])


def _block_map(plan: Plan, which: int):
    """The stock kernels' block map `which` (forward, dq, dkv) on the device."""
    return jax.tree.map(jnp.asarray, block_maps(plan)[which])


@functools.lru_cache(maxsize=32)
def _steps(plan: Plan):
    """The (key block, query block) pairs the one backward kernel steps through:
    those the rule leaves; under the selected rule, whose mask is the step's
    data, those the causal rule leaves or the whole square."""
    from . import attention_backward_kernels as onchip

    if plan.rule == "selected":
        return onchip.steps_over(plan.positions // plan.block, plan.causal)
    return onchip.steps_of(block_maps(plan)[2])


@functools.lru_cache(maxsize=32)
def _stored_blocks(plan: Plan):
    """(The distinct cut blocks and one of ones, `mask_of[step]`) of block diffusion's rule."""
    from . import attention_backward_kernels as onchip

    return onchip.stored_blocks(block_maps(plan)[2], plan.block)


def _stock_options(plan: Plan) -> dict:
    """What the stock kernels' three calls share."""
    from jax.experimental.pallas.ops.tpu.splash_attention import splash_attention_kernel as splash

    computed = (causal_allowed if plan.rule == "causal"
                else _window_rule(plan.mask_block) if plan.rule == "sliding_window" else None)   # stored: the other rules
    return dict(mask_value=splash.DEFAULT_MASK_VALUE, is_mqa=False, attn_logits_soft_cap=None,
                mask_function=computed, interpret=plan.interpret)


def _far_forward(q, k, v, plan: Plan, residuals: bool = True):
    """The kernels' term: output in the operands' dtype and float32
    log-sum-exp (B, Hq, 2L).  A row the rule leaves no far key (the first
    noised block's) reads the mean of a block's values, finite, under a
    log-sum-exp of `mask_value` -2.38e38: weight exactly 0 in the join.
    `residuals` False: the output alone, None for the log-sum-exp (the kernel
    writes it 128 lanes wide in float32, twice the output's bytes at 128-wide
    heads: a forward that nothing differentiates and nothing joins leaves it)."""
    from jax.experimental.pallas.ops.tpu.splash_attention import splash_attention_kernel as splash

    info = _block_map(plan, 0)
    made = jax.vmap(lambda q, k, v: splash._splash_attention_forward(
        info, q, k, v, None, None, block_sizes=plan.sizes, residual_checkpoint_name=None, save_residuals=residuals,
        **_stock_options(plan)))(q, k, v)
    return (made[0], made[1][0]) if residuals else (made, None)


def _far_backward(q, k, v, lse, do, di, plan: Plan):
    """dq, dk, dv of the kernels' term given the log-sum-exp and rowsum(do .
    out) of the WHOLE row (joined, where the own-block term was split off): from
    the one kernel that keeps dq on the chip, its cut blocks computed from
    positions or (block diffusion's) read from the rule's stored ones, or from
    the stock dq and dkv kernels (`Plan.backward`)."""
    if plan.backward == "onchip_dq":
        from . import attention_backward_kernels as onchip

        if plan.stored:
            return onchip.backward(q, k, v, lse, do, di, _steps(plan), None, plan.block, plan.interpret, stored=_stored_blocks(plan))
        return onchip.backward(q, k, v, lse, do, di, _steps(plan), _stock_options(plan)["mask_function"], plan.block, plan.interpret)
    from jax.experimental.pallas.ops.tpu.splash_attention import splash_attention_kernel as splash

    sizes, dq_info, dkv_info = plan.sizes, _block_map(plan, 1), _block_map(plan, 2)
    options = dict(_stock_options(plan), q_layout=sizes.q_layout, k_layout=sizes.k_layout, v_layout=sizes.v_layout)
    _, dk, dv = jax.vmap(lambda *a: splash._splash_attention_bwd_dkv(
        *a[:3], None, None, *a[3:], bq=sizes.block_q_dkv, bkv=sizes.block_kv_dkv, bkv_compute=sizes.block_kv_dkv_compute,
        mask_info=dkv_info, use_fused_bwd_kernel=False, **options))(q, k, v, lse, do, di)
    dq = jax.vmap(lambda *a: splash._splash_attention_bwd_dq(
        *a[:3], None, None, *a[3:], bq=sizes.block_q_dq, bkv=sizes.block_kv_dq, mask_info=dq_info,
        **options))(q, k, v, lse, do, di)
    return dq, dk, dv


_NT = (((1,), (1,)), ((), ()))  # a b^T
_TN = (((0,), (0,)), ((), ()))  # a^T b


def _own_block_scores(q, k, plan: Plan):
    """Float32 scores [keys, queries] of a tile of the noised half's diagonal,
    keys down the sublanes so that a query's statistics are rows [1, queries]
    as the log-sum-exp is stored; pairs of different blocks of the rule masked."""
    from jax.experimental.pallas.ops.tpu.splash_attention import splash_attention_kernel as splash

    s = jax.lax.dot_general(k, q, _NT, preferred_element_type=jnp.float32)
    if plan.tile == plan.mask_block:
        return s, None
    own = (jax.lax.broadcasted_iota(jnp.int32, s.shape, 0) // plan.mask_block
           == jax.lax.broadcasted_iota(jnp.int32, s.shape, 1) // plan.mask_block)
    return jnp.where(own, s, splash.DEFAULT_MASK_VALUE), own


def _join_kernel(q_ref, k_ref, v_ref, out_f_ref, lse_f_ref, out_ref, lse_ref, *, plan: Plan):
    """A block of noised rows: their own block's keys joined to the far term.
    lse = logaddexp(lse_f, lse_n), out = exp(lse_f - lse) out_f + p v with
    p = exp(s - lse) rounded to the operands' dtype, all else float32."""
    for t in range(q_ref.shape[0] // plan.tile):
        at = pl.ds(t * plan.tile, plan.tile)
        s, _ = _own_block_scores(q_ref[at, :], k_ref[at, :], plan)
        m = s.max(axis=0, keepdims=True)
        e = jnp.exp(s - m)
        lse_f, lse_n = lse_f_ref[:, at], m + jnp.log(e.sum(axis=0, keepdims=True))
        top = jnp.maximum(lse_f, lse_n)
        lse = top + jnp.log(jnp.exp(lse_f - top) + jnp.exp(lse_n - top))
        lse_ref[:, at] = lse
        p = (e * jnp.exp(m - lse)).astype(v_ref.dtype)
        near = jax.lax.dot_general(p, v_ref[at, :], _TN, preferred_element_type=jnp.float32)
        # The far term's weight a query, turned from along the lanes to down the sublanes by a transpose in
        # registers.  NOT by reading `lse_ref` back: its array is aliased to the input's, and on the chip (not
        # interpreted) such a read returned the input's values (my chip run, PR 33).
        far = jnp.broadcast_to(jnp.exp(lse_f - lse), s.shape).T
        far = jnp.tile(far, (1, -(-out_ref.shape[1] // plan.tile)))[:, :out_ref.shape[1]]
        out_ref[at, :] = (far * out_f_ref[at, :].astype(jnp.float32) + near).astype(out_ref.dtype)


def _own_block_backward_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, di_ref, dq_f_ref, dq_ref, dk_ref, dv_ref,
                               dk_acc, dv_acc, *, plan: Plan, group: int):
    """The own-block term's gradients by the stock kernels' formulas: p =
    exp(s - lse), dv = p^T do, ds = p (do v^T - di), dq = ds k (added to the
    far term's), dk = ds^T q; dk and dv summed over the query heads of a
    key/value head, the grid's last axis."""
    g = pl.program_id(3)

    @pl.when(g == 0)
    def _():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    for t in range(q_ref.shape[0] // plan.tile):
        at = pl.ds(t * plan.tile, plan.tile)
        q, k, do = q_ref[at, :], k_ref[at, :], do_ref[at, :]
        s, own = _own_block_scores(q, k, plan)
        p = jnp.exp(s - lse_ref[:, at])
        p = p if own is None else jnp.where(own, p, 0.0)
        dv_acc[at, :] += jnp.dot(p.astype(do.dtype), do, preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(v_ref[at, :], do, _NT, preferred_element_type=jnp.float32)
        ds = (p * (dp - di_ref[:, at])).astype(q.dtype)
        dk_acc[at, :] += jnp.dot(ds, q, preferred_element_type=jnp.float32)
        dq = jax.lax.dot_general(ds, k, _TN, preferred_element_type=jnp.float32)
        dq_ref[at, :] = (dq_f_ref[at, :].astype(jnp.float32) + dq).astype(dq_ref.dtype)

    @pl.when(g == group - 1)
    def _():
        dk_ref[...] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[...] = dv_acc[...].astype(dv_ref.dtype)


def _join_own_block(q, k, v, out, lse, plan: Plan):
    """`out` (B, Hq, 2L, dh) and `lse` (B, Hq, 2L) of the far term with the
    noised rows' own blocks joined in, in place: the clean rows pass through."""
    batch, heads, positions, width = q.shape
    group, rows = heads // k.shape[1], plan.block
    of_q = pl.BlockSpec((None, None, rows, width), lambda b, h, i: (b, h, i, 0))
    of_kv = pl.BlockSpec((None, None, rows, width), lambda b, h, i: (b, h // group, i, 0))
    of_lse = pl.BlockSpec((None, None, 1, rows), lambda b, h, i: (b, h, 0, i))
    out, lse = pl.pallas_call(
        functools.partial(_join_kernel, plan=plan), grid=(batch, heads, plan.first_key // rows),
        in_specs=[of_q, of_kv, of_kv, of_q, of_lse], out_specs=[of_q, of_lse],
        out_shape=[jax.ShapeDtypeStruct(out.shape, out.dtype), jax.ShapeDtypeStruct((batch, heads, 1, positions), lse.dtype)],
        input_output_aliases={3: 0, 4: 1}, interpret=plan.interpret, name="own_block_join",
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel",) * 3),
    )(q, k, v, out, lse[:, :, None])
    return out, lse[:, :, 0]


def _own_block_backward(q, k, v, lse, do, di, dq, plan: Plan):
    """`dq` (B, Hq, 2L, dh) of the far term with the own-block term's added to
    the noised rows in place, and that term's dk, dv (B, Hkv, L, dh)."""
    batch, heads, _, width = q.shape
    kv_heads, rows = k.shape[1], plan.block
    group = heads // kv_heads
    of_q = pl.BlockSpec((None, None, rows, width), lambda b, h, i, g: (b, h * group + g, i, 0))
    of_kv = pl.BlockSpec((None, None, rows, width), lambda b, h, i, g: (b, h, i, 0))
    of_lse = pl.BlockSpec((None, None, 1, rows), lambda b, h, i, g: (b, h * group + g, 0, i))
    near_kv = jax.ShapeDtypeStruct((batch, kv_heads, plan.first_key, width), k.dtype)
    return pl.pallas_call(
        functools.partial(_own_block_backward_kernel, plan=plan, group=group),
        grid=(batch, kv_heads, plan.first_key // rows, group),
        in_specs=[of_q, of_kv, of_kv, of_q, of_lse, of_lse, of_q], out_specs=[of_q, of_kv, of_kv],
        out_shape=[jax.ShapeDtypeStruct(dq.shape, dq.dtype), near_kv, near_kv],
        scratch_shapes=[pltpu.VMEM((rows, width), jnp.float32)] * 2,
        input_output_aliases={6: 0}, interpret=plan.interpret, name="own_block_backward",
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel", "parallel", "parallel", "arbitrary")),
    )(q, k, v, do, lse[:, :, None], di[:, :, None], dq)


def _forward(q, k, v, plan: Plan, residuals: bool = True):
    seq = plan.first_key
    out, lse = _far_forward(q, k[:, :, seq:], v[:, :, seq:], plan, residuals or bool(seq))   # the join reads the log-sum-exp
    return _join_own_block(q, k, v, out, lse, plan) if seq else (out, lse)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _attention(q, k, v, plan: Plan, keep=None):
    return _forward(q, k, v, plan, residuals=False)[0]     # nothing differentiates this call: a `for_test` clone's


def _attention_fwd(q, k, v, plan: Plan, keep):
    """`keep` names the two residuals that only the forward kernels make: a
    `jax.checkpoint` round the op whose policy saves the name
    (`core/lowering.py: plan_kept`) then runs no second forward."""
    out, lse = _forward(q, k, v, plan)
    if keep:
        out, lse = checkpoint_name(out, keep), checkpoint_name(lse, keep)
    return out, (q, k, v, out, lse)


def _attention_bwd(plan: Plan, keep, residuals, do):
    q, k, v, out, lse = residuals
    seq = plan.first_key
    di = jnp.einsum("bhsd,bhsd->bhs", out.astype(jnp.float32), do.astype(jnp.float32))
    dq, dk, dv = _far_backward(q, k[:, :, seq:], v[:, :, seq:], lse, do, di, plan)
    if seq:
        dq, dk_n, dv_n = _own_block_backward(q, k, v, lse, do, di, dq, plan)
        dk, dv = jnp.concatenate([dk_n, dk], axis=2), jnp.concatenate([dv_n, dv], axis=2)
    return dq, dk, dv


_attention.defvjp(*counted_rules("fused_attention", _attention_fwd, _attention_bwd))


def attention_under(plan: Plan, q, k, v, scale: Optional[float], keep=None):
    """softmax(q k^T . scale under the plan's rule) v over (B, Hq, positions,
    dh) queries and (B, Hkv, positions, dh) keys and values, Hkv a divisor of
    Hq.  The kernels have no scale of their own: the queries carry it, rounded
    once more to their dtype (`scale` None: they carry it already, as the
    latent attention's assembled queries do).  That costs nothing where the scale is a power
    of two (64-wide heads); at 128-wide heads the output is 2.51e-3 from
    float32 where the flash kernel, which scales the float32 scores, is
    2.03e-3, most of either the output's own rounding
    (tools/chip_attention_errors.py, PR 37).  `keep`: the name under which a
    recomputed segment keeps the output and the log-sum-exp for backward."""
    blocks = block_maps(plan)[0].block_mask
    _MON.counter("lowering.attention_blocks_visited").inc(int(np.count_nonzero(blocks)))
    _MON.counter("lowering.attention_blocks_cut").inc(int(np.count_nonzero(blocks == 1)))
    _MON.counter("lowering.attention_own_block_terms").inc(int(plan.first_key > 0))
    _MON.counter("lowering.attention_backward_onchip_dq").inc(int(plan.backward == "onchip_dq"))
    if plan.stored and plan.backward == "onchip_dq":      # the steps a head whose block of the mask is read, not computed
        _MON.counter("lowering.attention_backward_stored_steps").inc(int(np.count_nonzero(block_maps(plan)[2].block_mask == 1)))
    with jax.named_scope("block_sparse_attention"):
        if scale is not None:
            q = (q.astype(jnp.float32) * scale).astype(q.dtype)
        return _attention(q, k, v, plan, keep)


def block_sparse_attention(q, k, v, mask_block: int, scale: float, interpret: bool = False, keep=None):
    """`attention_under` the block-diffusion rule over 2L positions in blocks
    of `mask_block`."""
    return attention_under(plan_of(q.shape[2], q.shape[1], mask_block, interpret, (q.shape[-1], v.shape[-1])), q, k, v, scale, keep)


def window_attention(q, k, v, window: int, scale: float, interpret: bool = False, keep=None):
    """`attention_under` the sliding-window rule over equal lengths of queries
    and keys.  Counted at trace time: the op, the pairs inside the blocks its
    forward block map visits and the pairs the rule allows, over rows and heads."""
    plan = window_plan(q.shape[2], q.shape[1], window, interpret, (q.shape[-1], v.shape[-1]))
    blocks = block_maps(plan)[0].block_mask      # [heads, or 1 where every head has the one mask; query blocks; key blocks]
    visited = int(np.count_nonzero(blocks)) * (q.shape[1] // blocks.shape[0]) * plan.block * plan.block
    _MON.counter("lowering.window_attention_ops").inc()
    _MON.counter("lowering.window_pairs_visited").inc(q.shape[0] * visited)
    _MON.counter("lowering.window_pairs_allowed").inc(q.shape[0] * q.shape[1] * window_pairs(q.shape[2], window))
    with jax.named_scope("window_attention"):
        return attention_under(plan, q, k, v, scale, keep)


def causal_attention(q, k, v, scale: float, interpret: bool = False, keep=None):
    """`attention_under` the causal rule over equal lengths of queries and keys."""
    return attention_under(causal_plan(q.shape[2], q.shape[1], interpret, (q.shape[-1], v.shape[-1])), q, k, v, scale, keep)


# -- a mask that is DATA: each query's own chosen keys ------------------------------------------------------------------
#
# FORWARD the stock kernel once more, its block map made on the device from the step's own mask (the stock
# `process_dynamic_mask`): every block of the grid brings its [queries, keys] block of the mask from HBM, a
# block no query of which chose a key is skipped (`block_mask` 0: its keys are not fetched), and nothing is
# computed from positions.  The rows are taken one at a time (`lax.map`): a row's mask is a row's own block map, which
# the kernel reads from scalar memory.  BACKWARD the one kernel of `ops/attention_backward_kernels.py` (PR 68), every row
# in one call over a static grid: a row's mask transposed, [keys, queries] one byte a pair, and the state of each of
# its steps (`_selected_bwd`).

#: The selected attention's grid block: the largest of these that divides the length.  At 1024 the STOCK fused backward
#: kernel's [keys, queries] block of the stored mask overran the scoped VMEM inside two cells' steps (`_BLOCKS`' table),
#: so the stock forward kernel takes 512 queries a step against 1024 keys (`Plan.sizes`); the one backward kernel, with
#: 64 MiB of its own, takes the whole [1024, 1024] byte block.  (1, 32 on 4, 16384, 128) under the causal rule, ~2048
#: picks a query (all 136 blocks cut), forward + backward of a layer alone, ms (my chip run, PR 68, call 2; `STORED=1
#: python3 tools/chip_block_attention.py`):
#:
#:   block                                    1024 (136 steps a head)   512 (528)
#:   ours, dk and dv of a group in VMEM       78.96                     83.34
#:   ours, summed outside in float32          80.28                     84.75
#:   ours, an int32 a pair of the mask        81.93                     86.03    (8 MiB of blocks: the group's rows no longer fit)
#:   the stock dq and dkv pair, a row a loop  109.06                    118.53
#:
#: so 1024 and a byte a pair, 27.6% under the pair; dq is the pair's to the last bit, dk 1.7e-3 and dv 7.1e-4 of the largest.
_SELECTED_BLOCKS = (1024, 512, 256, 128)


def selected_block(length: int):
    """The grid's block for `length` positions under a mask that is data, None
    where the kernels are not taken."""
    return next((b for b in _SELECTED_BLOCKS if length % b == 0), None)


def selected_plan(length: int, heads: int, causal: bool = False, interpret: bool = False, widths=(128, 128)) -> Plan:
    return Plan(length, heads, 1, selected_block(length), 0, interpret, "selected", causal, tuple(widths))


def _row_mask(picks, plan: Plan):
    """bool [L, L] of one row's picks [L, L / 32]."""
    from .sparse_index_ops import unpack_bits

    allowed = unpack_bits(picks, plan.positions)
    if plan.causal:
        allowed &= causal_allowed(jax.lax.broadcasted_iota(jnp.int32, allowed.shape, 0),
                                  jax.lax.broadcasted_iota(jnp.int32, allowed.shape, 1))
    return allowed


def _selected_map(picks, plan: Plan, queries: int, keys: int, dkv: bool = False):
    """One kernel's block map, (`queries`, `keys`) a block, of ONE row's picks
    [L, L / 32], as arrays of the step (`dkv`: the dkv kernel's, its stored
    blocks [keys, queries])."""
    from jax.experimental.pallas.ops.tpu.splash_attention import splash_attention_mask_info as info_lib

    process = info_lib.process_dynamic_mask_dkv if dkv else info_lib.process_dynamic_mask
    info = process(_row_mask(picks, plan)[None], (queries, keys))[0]
    # the stock kernels read a dynamic mask's blocks by one index
    return info._replace(partial_mask_blocks=info.partial_mask_blocks.reshape((-1,) + info.partial_mask_blocks.shape[-2:]))


def _selected_forward(q, k, v, picks, plan: Plan):
    from jax.experimental.pallas.ops.tpu.splash_attention import splash_attention_kernel as splash

    def row(operands):
        q, k, v, picks = operands
        info = _selected_map(picks, plan, plan.sizes.block_q, plan.sizes.block_kv)
        out, (lse,) = splash._splash_attention_forward(
            info, q, k, v, None, None, block_sizes=plan.sizes, residual_checkpoint_name=None, save_residuals=True,
            **_stock_options(plan))
        return out, lse

    return jax.lax.map(row, (q, k, v, picks))


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def _selected(q, k, v, picks, plan: Plan, keep=None):
    return _selected_forward(q, k, v, picks, plan)


def _selected_fwd(q, k, v, picks, plan: Plan, keep):
    out, lse = _selected_forward(q, k, v, picks, plan)
    if keep:
        out, lse = checkpoint_name(out, keep), checkpoint_name(lse, keep)
    return (out, lse), (q, k, v, picks, out, lse)


def _selected_bwd(plan: Plan, keep, residuals, cotangents):
    """The picks are whole numbers and take no gradient.  The log-sum-exp is an
    OUTPUT here (the alignment term reads it): ds = p (dp - di) + p dlse, so its
    cotangent goes in as di - dlse.  dq, dk and dv from the ONE kernel that keeps
    dq on the chip (`Plan.backward`), every row in one call: a row's mask
    transposed, [keys, queries] one byte a pair as the kernel lays its scores, is
    the only layout of it made for backward, and the state of a row's step (does
    any query of the block hold a key of the block?) one reduce over it.  Where
    a head's dq does not fit the kernel's VMEM, the stock dq and dkv kernels a
    row, each on its own block maps of the row's mask."""
    from jax.experimental.pallas.ops.tpu.splash_attention import splash_attention_kernel as splash

    q, k, v, picks, out, lse = residuals
    do, dlse = cotangents
    di = jnp.einsum("bhsd,bhsd->bhs", out.astype(jnp.float32), do.astype(jnp.float32)) - dlse.astype(jnp.float32)
    if plan.backward == "onchip_dq":
        from . import attention_backward_kernels as onchip

        steps, blocks = _steps(plan), plan.positions // plan.block
        mask = jax.vmap(lambda picks: _row_mask(picks, plan).T)(picks)
        state = mask.reshape(-1, blocks, plan.block, blocks, plan.block).any((2, 4))[:, steps.kv_block, steps.q_block]
        stored = (mask.astype(onchip.STORED_DTYPE), state.astype(jnp.int32))
        return (*onchip.backward(q, k, v, lse, do, di, steps, None, plan.block, plan.interpret, stored=stored), None)
    sizes = plan.sizes
    options = dict(_stock_options(plan), q_layout=sizes.q_layout, k_layout=sizes.k_layout, v_layout=sizes.v_layout)

    def row(operands):
        q, k, v, picks, lse, do, di = operands
        _, dk, dv = splash._splash_attention_bwd_dkv(
            q, k, v, None, None, lse, do, di, bq=sizes.block_q_dkv, bkv=sizes.block_kv_dkv,
            bkv_compute=sizes.block_kv_dkv_compute, use_fused_bwd_kernel=False,
            mask_info=_selected_map(picks, plan, sizes.block_q_dkv, sizes.block_kv_dkv, dkv=True), **options)
        dq = splash._splash_attention_bwd_dq(
            q, k, v, None, None, lse, do, di, bq=sizes.block_q_dq, bkv=sizes.block_kv_dq,
            mask_info=_selected_map(picks, plan, sizes.block_q_dq, sizes.block_kv_dq), **options)
        return dq, dk, dv

    return (*jax.lax.map(row, (q, k, v, picks, lse, do, di)), None)


_selected.defvjp(*counted_rules("fused_attention", _selected_fwd, _selected_bwd))


def selected_attention(q, k, v, picks, scale: Optional[float], causal: bool = False, interpret: bool = False, keep=None):
    """(out, the float32 log-sum-exp of each query's scores over its keys (B,
    Hq, L)) of softmax(q k^T . scale over the keys `picks` holds for each query) v
    over (B, Hq, L, dh) queries and (B, Hkv, L, dh) keys and values: `picks` int32 (B, L, L / 32), bit j
    of word w of query t set where t holds key 32 w + j (`ops/sparse_index_ops.py: pack_bits`).  No
    pair outside the picks has weight: the kernels mask every block from the
    stored mask, whatever the positions.  A query that holds no key reads the
    stock kernels' finite output under a log-sum-exp of `mask_value`; the
    indexer's choice always holds the query's own position."""
    plan = selected_plan(q.shape[2], q.shape[1], causal, interpret, (q.shape[-1], v.shape[-1]))
    onchip = plan.backward == "onchip_dq"
    _MON.counter("lowering.attention_backward_onchip_dq").inc(int(onchip))
    _MON.counter("lowering.attention_backward_stored_steps").inc(_steps(plan).q_block.size if onchip else 0)
    with jax.named_scope("selected_attention"):
        if scale is not None:
            q = (q.astype(jnp.float32) * scale).astype(q.dtype)
        return _selected(q, k, v, picks, plan, keep)
