"""The indexer's three gradients of the alignment loss, made from dI with the
per-head products made AGAIN where they are used (`ops/sparse_index_ops.py:
_alignment_row` calls it a chunk):

    (qI [C, Hi, Di], kI [K, Di], w [C, Hi] float32, dI [C, K] float32)
        -> (d_qI [C, Hi, Di], d_kI [K, Di], d_w [C, Hi]), all float32

    P_h        = qI[:, h] kI^T                    the operands' dtype into float32
    M_h        = dI where P_h > 0, else 0
    d_w[c, h]  = sum_k P_h[c, k] M_h[c, k]        float32
    G_h        = w[:, h] M_h                      float32, then the operands' dtype
    d_qI[:, h] = G_h kI                           into float32
    d_kI       = sum_h G_h^T qI[:, h]             into float32

dI [C, K] is the loss's gradient to the index scores I = sum_h w[:, h] relu(P_h)
(zero where a key is not allowed).  No [Hi, C, K] array is asked of the forward
pass: `jax.vjp` of `index_scores` kept the products for the ReLU's mask and the
weights' gradient and handed a float32 d_products [Hi, C, K] to two more
einsums, four or five passes of 37.6 GB a step through HBM in Keye-VL-2.0's
cell (PERF.md, section 6, PR 59).  G_h reaches the matrix unit in the operands'
dtype, which is what the chip's default precision made of the float32
d_products; every sum, weight and comparison is float32.

Two forms of the one function, chosen by the platform and the shape
(`sparse_index_ops._index_alignment`), never by a flag:

  * `gradients`, on the TPU where `fits`: ONE `pallas_call` a chunk, its grid
    over blocks of `_block(K)` keys.  qI's chunk [C, Hi Di] and w stay in VMEM,
    kI's block and dI's block stream; a head's P_h, M_h and G_h live for a block
    of keys and never leave VMEM; d_qI and d_w accumulate over the key
    blocks (the grid's axis is `arbitrary`), d_kI's block is written once.  The
    heads that share 128 lanes of qI (two at 64 wide) are told apart by kI laid
    at each head's lanes with zeros beside it (`_at_each_heads_lanes`): every
    operand of the matrix unit is whole tiles, and the zeros cost it nothing it
    would not idle through at a contraction of 64.
  * `gradients_plain`, anywhere else (the CPU, a chunk that is not whole
    tiles): the five lines above in `jax.numpy`.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

F32 = jnp.float32
LANES = 128
#: The most queries a call takes and the most keys a grid step: a head's products, M_h and G_h are [ROWS, BLOCK] tiles
#: in VMEM, 2 MB each in float32, beside dI's block twice (the pipeline's two buffers), qI and d_qI: under `_VMEM_LIMIT`.
#: The whole chunk a pass is the fastest of seven tilings: alone at [512, 8192], ms a call by (rows a pass, keys a step),
#: (128, 1024) 0.574, (256, 1024) 0.538, (128, 2048) 0.518, (512, 512) 0.520, (256, 2048) 0.517, (512, 1024) 0.484; in
#: the cell's step 57.2 ms at (128, 1024) and 40.9 at (512, 1024) (my chip runs, PR 59, calls 2 and 3).
ROWS = 512
BLOCK = 1024
_VMEM_LIMIT = 32 * 2 ** 20


def _held(products, d_scores):
    """M_h: dI where the ReLU passes a product.  (`tools/chip_index_alignment.py: without_relu_mask` is the control that
    takes it out of both forms.)"""
    return jnp.where(products > 0, d_scores, 0.0)


def gradients_plain(qi, ki, w, d_scores):
    """The plain form: see the module's docstring."""
    products = jnp.einsum("chd,kd->hck", qi, ki, preferred_element_type=F32)
    held = _held(products, d_scores[None])
    d_w = jnp.transpose(jnp.sum(products * held, axis=-1))
    g = held * jnp.transpose(w)[:, :, None]            # float32 beside the operands: the matrix unit's precision rounds it
    return (jnp.einsum("hck,kd->chd", g, ki, preferred_element_type=F32),
            jnp.einsum("hck,chd->kd", g, qi, preferred_element_type=F32), d_w)


def _block(keys: int) -> int:
    """Keys a grid step: the most of 128, 256, 512 and `BLOCK` that divide `keys`."""
    return max(b for b in (128, 256, 512, BLOCK) if keys % b == 0)


def fits(chunk: int, keys: int, heads: int, width: int) -> bool:
    """Whether `gradients` takes a chunk of `chunk` queries of `heads` heads
    `width` wide against `keys` keys: whole tiles of rows (16 of bf16) and of
    keys, no more rows than a tile in VMEM holds, and whole heads in every 128
    lanes of qI."""
    return chunk % 16 == 0 and chunk <= ROWS and keys % LANES == 0 and LANES % width == 0 and (heads * width) % LANES == 0


def _at_each_heads_lanes(ki, width: int):
    """[128 / width, K, 128]: kI at the lanes of the s-th head of a group of
    128 lanes, zeros at the others'."""
    group = LANES // width
    return jnp.stack([jnp.pad(ki, ((0, 0), (s * width, LANES - (s + 1) * width))) for s in range(group)])


def _gradients_kernel(q_ref, k_ref, w_ref, d_ref, dq_ref, dk_ref, dw_ref, acc_ref, *, width: int):
    """q_ref, dq_ref [Hi Di / 128, C, 128]: the heads of 128 lanes at a time, a loop's step each (the body is a
    group's heads unrolled, so a program's eight key widths compile in a second each)."""
    group = LANES // width

    @pl.when(pl.program_id(0) == 0)
    def _():
        dq_ref[...] = jnp.zeros_like(dq_ref)
        dw_ref[...] = jnp.zeros_like(dw_ref)

    acc_ref[...] = jnp.zeros_like(acc_ref)
    d_scores = d_ref[...]
    weights = w_ref[...]
    head_of = jax.lax.broadcasted_iota(jnp.int32, weights.shape, 1)

    def of_lanes(lanes, d_w):
        q = q_ref[lanes]
        d_q = jnp.zeros(q.shape, F32)
        for s in range(group):
            mine = head_of == lanes * group + s
            k = k_ref[s]
            products = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())), preferred_element_type=F32)
            held = _held(products, d_scores)
            d_w = jnp.where(mine, jnp.sum(products * held, axis=1, keepdims=True), d_w)
            g = (held * jnp.sum(jnp.where(mine, weights, 0.0), axis=1, keepdims=True)).astype(q.dtype)
            d_q = d_q + jnp.dot(g, k, preferred_element_type=F32)
            acc_ref[s] += jax.lax.dot_general(g, q, (((0,), (0,)), ((), ())), preferred_element_type=F32)
        dq_ref[lanes] += d_q
        return d_w

    dw_ref[...] += jax.lax.fori_loop(0, q_ref.shape[0], of_lanes, jnp.zeros_like(weights))
    d_k = acc_ref[0]
    for s in range(1, group):      # the s-th head of each group summed at its own lanes: back to the first `width`
        d_k = d_k + pltpu.roll(acc_ref[s], LANES - s * width, 1)
    dk_ref[...] = d_k[:, :width]


@functools.partial(jax.jit, static_argnames=("interpret",))
def gradients(qi, ki, w, d_scores, interpret: bool = False):
    """The kernel's form: see the module's docstring.  `interpret` is the tests'."""
    chunk, heads, width = qi.shape
    keys = ki.shape[0]
    block = _block(keys)
    group = LANES // width
    by_lanes = (heads // group, chunk, LANES)
    d_q, d_k, d_w = pl.pallas_call(
        functools.partial(_gradients_kernel, width=width),
        grid=(keys // block,),
        in_specs=[pl.BlockSpec(by_lanes, lambda i: (0, 0, 0)),
                  pl.BlockSpec((group, block, LANES), lambda i: (0, i, 0)),
                  pl.BlockSpec((chunk, heads), lambda i: (0, 0)),
                  pl.BlockSpec((chunk, block), lambda i: (0, i))],
        out_specs=[pl.BlockSpec(by_lanes, lambda i: (0, 0, 0)),
                   pl.BlockSpec((block, width), lambda i: (i, 0)),
                   pl.BlockSpec((chunk, heads), lambda i: (0, 0))],
        out_shape=[jax.ShapeDtypeStruct(by_lanes, F32), jax.ShapeDtypeStruct((keys, width), F32),
                   jax.ShapeDtypeStruct((chunk, heads), F32)],
        scratch_shapes=[pltpu.VMEM((group, block, LANES), F32)],
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary",), vmem_limit_bytes=_VMEM_LIMIT),
        name="index_alignment_gradients",
        interpret=interpret,
    )(jnp.swapaxes(qi.reshape(chunk, heads // group, LANES), 0, 1), _at_each_heads_lanes(ki, width), w, d_scores)
    return jnp.swapaxes(d_q, 0, 1).reshape(chunk, heads, width), d_k, d_w
