"""Attention under a mask that is a rule over positions, not a tensor.

The one rule so far is block-diffusion training's (BD3-LM, Arriola et al.
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
CPU tests and goldens), and `block_sparse_attention` hands it to the stock
splash-attention kernel (jax.experimental.pallas.ops.tpu.splash_attention) as
a mask it can ask for any block of: the kernel's block map is made from the
rule at trace time (numpy, a block of the grid at a time), blocks the rule
empties are never visited nor their keys fetched, blocks it fills skip the
mask, and the blocks it cuts read theirs from the few DISTINCT cut blocks,
which are all of the mask that is kept on the device (three at SDAR's cell: one
a quadrant's diagonal, 3 MB as int8).  No [2L, 2L] array exists on the device,
forward or backward, nor on the host.  Computing the cut blocks' mask from the
positions inside the kernel instead (the kernel's "computable" masks) was
priced and lost by 19 ms a layer (`_BLOCKS`' table).  Key/value heads may be
fewer than query heads: the kernel's index maps read key/value head
j div (Hq / Hkv), nothing is repeated.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

MASKS = ("block_diffusion",)

#: Queries and keys a block of the kernels' grids, the largest that divides the
#: length, forward and both backward kernels, and the keys a step inside a
#: block (`_KV_COMPUTE`).  TPU v5e, (2, 32, 8192, 128) queries over
#: (2, 4, 8192, 128) keys and values, bf16, block length 4, forward + backward
#: of one layer, ms (my chip runs, PR 32; tools/chip_block_attention.py):
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
#: at 1024 and 2.15 GB at 512, and inside the cell's step its 1024-block kernel
#: overruns the scoped VMEM (19.1 of 16 MB) that it fits alone.  So does the dq
#: kernel at 1024 x 1024 (16.47 MB: compiled here for the described v5e, the
#: whole step; alone it fits), which therefore takes `_KV_COMPUTE` queries a
#: block against 1024 keys.
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


def allowed_pairs(positions: int, block: int) -> int:
    """(query, key) pairs the rule allows among `positions` = 2L positions."""
    seq = positions // 2
    n = seq // block
    return block * block * (n + n * (n - 1) // 2 + n * (n + 1) // 2)


def kernel_block(q_len: int):
    """The grid's block for `q_len` positions, None where the kernel is not
    taken: lengths that are no whole number of blocks."""
    return next((b for b in _BLOCKS if q_len % b == 0), None)


def _mask(positions: int, block: int):
    from jax.experimental.pallas.ops.tpu.splash_attention import splash_attention_mask as mask_lib

    class BlockDiffusionMask(mask_lib.Mask):
        """The rule as a mask the kernel's block map can slice: any
        [queries, keys] window of it, computed when asked for."""

        shape = (positions, positions)

        def __getitem__(self, idx):
            q, kv = (np.arange(s.start or 0, positions if s.stop is None else s.stop) for s in idx)
            return block_diffusion_allowed(q[:, None], kv[None, :], positions // 2, block)

        def __eq__(self, other):
            return isinstance(other, type(self)) and self.shape == other.shape

        def __hash__(self):
            return hash((type(self).__name__, positions, block))

    return BlockDiffusionMask()


def block_sparse_attention(q, k, v, mask_block: int, scale: float, interpret: bool = False):
    """softmax(q k^T . scale under the block-diffusion rule) v over
    (B, Hq, 2L, dh) queries and (B, Hkv, 2L, dh) keys and values, Hkv a
    divisor of Hq: the stock splash-attention kernel, forward, dq and dkv.
    The kernel has no scale of its own: the queries carry it."""
    from jax.experimental.pallas.ops.tpu.splash_attention import splash_attention_kernel as splash
    from jax.experimental.pallas.ops.tpu.splash_attention import splash_attention_mask as mask_lib

    positions, heads = q.shape[2], q.shape[1]
    b = kernel_block(positions)
    inner = min(b, _KV_COMPUTE)
    sizes = splash.BlockSizes(block_q=b, block_kv=b, block_kv_compute=inner, block_q_dkv=b, block_kv_dkv=b,
                              block_kv_dkv_compute=inner, block_q_dq=inner, block_kv_dq=b)
    one = _mask(positions, mask_block)
    kernel = splash.make_splash_mha(mask_lib.MultiHeadMask([one] * heads), block_sizes=sizes,
                                    head_shards=1, q_seq_shards=1, interpret=interpret)
    with jax.named_scope("block_sparse_attention"):
        q = (q.astype(jnp.float32) * scale).astype(q.dtype)
        return jax.vmap(kernel)(q, k, v).astype(q.dtype)
