"""Whole-row scaled-dot-product attention Pallas kernels (moderate sequence).

Reference role: operators/fused/fused_attention ambitions + the unfused
matmul/softmax/matmul stack in layers/nn.py multi-head attention.  XLA's
attention is bandwidth-bound on the [B,H,L,L] f32 score tensor, which it
writes to HBM and reads back, forward and backward.  For L <= 512 the
ENTIRE score row block fits VMEM, so no online-softmax streaming is needed:
each grid step loads NB (batch*head) pairs of Q/K/V tiles, computes
S = QK^T (f32 on the MXU), full-row softmax in VMEM, and O = PV: scores
never touch HBM, forward or backward (the backward kernel recomputes S/P
from Q/K the flash-attention way rather than saving them).

`ops/nn_ops.py:_attention_path` sends `fused_attention` here by shape: bf16,
64-wide heads, no mesh, 256 to 512 queries and keys, in either layout
(below).  TPU v5e, BERT-base, 32 x 512 tokens a
step: 212.54 samples/s against 181.36 with XLA's attention (`fused_attention`
54.4 -> 18.2 ms of the step, `peak_hbm_gb` 11.18 -> 7.24); at 256 x 128 it
lost, 1003.2 against 1132.9, and is not taken (PERF.md, PRs 29 and 30).

Two layouts, one kernel body a direction (PR 39).  The op's `layout` says
where the heads lie in what it was handed: "bhld" is (B, H, L, dh), read as
[B*H, L, dh] in blocks of `g` whole heads; "blhd" is (B, L, H, dh), what a
projection's output reshapes to for nothing, read and written as [B, L, H*dh]
in blocks of [L, g*dh], `g` heads side by side on the lanes, each head's
[L, dh] tile a static lane slice of the block.  A head's arithmetic is
`_sdpa_tile` / `_sdpa_tile_bwd` either way; only the BlockSpecs (`_specs`)
and the tile's index in its block (`_head_at`) differ, and on the chip the two
give the same bits (output and dq, dk, dv: `tools/chip_row_attention.py`).
BERT's program hands over "blhd" and holds no transpose round the op: the same
cell 212.54 -> 239.15 samples/s (+12.5%), `peak_hbm_gb` 7.24 -> 6.02, the
kernel itself 17.0 -> 15.0 ms a step (lane-dense [L, g*64] blocks where a
64-wide head's tile fills half of each 128-lane row in VMEM, and more heads a
grid step, `_pick_heads`; PERF.md, PR 39).

Same mathematics as the XLA path: operands in their own dtype on the MXU,
float32 accumulation, float32 scores and softmax, the probabilities rounded
to the operands' dtype for PV.  The backward rounds dS to the operands'
dtype before the dQ and dK products, as XLA's transposed einsums and the
stock flash kernel's backward do (tests/test_pallas_attention.py compares
the three).

Contracts:
  * q/k/v: [B, H, L, dh] (`layout="bhld"`) or [B, L, H, dh] ("blhd"), all the
    same dtype (bf16 or f32); out matches q, in its layout.  Under "blhd" the
    heads go `g` a block with g*dh a whole number of 128-lane tiles, or all H.
  * bias: optional additive pre-softmax bias [B, 1|H, Lq, Lk], treated as
    NON-differentiable (it derives from lengths/causality in every caller,
    layers.attention_bias, so its cotangent is structurally zero; the op
    lowering stop_gradients it).
  * causal masking applied inside the kernel (no bias materialization).
  * long-L guard: this module asserts L <= 1024; from _FLASH_MIN_SEQ keys
    on the lowering takes a streaming stock kernel instead.
  * both calls carry a `cost_estimate` of their matrix products and
    exponentials, so the step's `cost_analysis()` still counts attention's
    arithmetic; not of their bytes (`_cost` says why).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .common import counted_rules

# The working set a grid step may hold, which sets the heads a step
# (`_pick_heads`).  Re-chosen on the chip in PR 30 for the heads-major call and
# left alone: (32, 12, 512, 64) bf16, the backward kernel alone, 1.458 ms at
# 2 MB (one pair a step), 1.425 at 8 MB, 1.407 at 16 MB (6 and 4 pairs), 1.404
# at 24 MB: 0.02 ms a layer between 8 and 24, a fifth of a millisecond of a
# 150 ms step.  The divide by the row sum likewise: one reciprocal a row and a
# multiply a score instead reads 1.431 at 8 MB (PERF.md, PR 30).
_VMEM_BUDGET = 8 * 1024 * 1024
_LANES = 128


def _pick_heads(H, L, dh, itemsize, n_bufs, layout, head_bias=0):
    """Heads a grid step: the largest divisor of H whose working set fits the
    VMEM budget and whose block the layout can tile.

    n_bufs: tile count estimate (qkv/o tiles + f32 score/prob buffers): fwd ~
    (4 small + 2 big), bwd ~ (7 small + 3 big).  Heads-major ("bhld") a block
    is `g` whole [L, dh] tiles, any divisor will do, and the estimate counts
    the score buffers a head, as PR 30 priced it: 3 forward and 2 backward at
    512 keys, 4 and 3 at 384.  In the projections' layout ("blhd") a block is
    [L, g*dh] of a row of H*dh, `g` heads side by side on the lanes, so g*dh
    is a whole number of 128-lane tiles (or the whole row: 64-wide heads go
    in twos), and the score buffers count once (a head's are the next one's):
    12 forward and 6 backward at 512 keys, 12 and 12 from 384 down; a bias of
    its own a head (`head_bias` bytes a head's tile, both of its buffers)
    counts with the head's tiles.  TPU v5e, bf16, twelve 64-wide heads,
    ~16k tokens, forward + backward of a layer alone, ms
    (`tools/chip_row_attention.py`; PERF.md, PR 39):

        keys    heads-major     2 heads     4       6       12      the rule
        128     1.766           1.813       1.649   1.586   1.639   1.639
        256     1.773           1.668       1.484   1.495   1.476   1.471
        384     2.231           1.988       1.863   1.830   1.790   1.788
        512     1.923           1.693       1.649   1.649   1.639   1.635

    The backward kernel alone does not care (1.314, 1.309, 1.315, 1.316 at 512
    keys for 1.440 heads-major: a DMA's contiguous run is 256 B a row at g = 2
    and 1536 B at 12, and it is the arithmetic's either way); the forward
    kernel, a third of its work a grid step, gains from 2 to 12 (96 grid
    steps a layer's 32 sequences instead of 192, ~0.35 us each).  Either way
    the lane-dense blocks beat heads-major, whose 64-wide tiles fill half of
    each 128-lane row in VMEM.  In `bert-base.pretrain-s512`'s step: 236.990,
    236.991 samples/s at 2 heads a step, both directions, and 239.540, 239.536
    under this rule (+1.08%; the step 135.03 -> 133.60 ms).  What the body
    writes out `g` times it pays for at set-up, once a program: `_fwd_call`."""
    small = L * dh * itemsize
    big = L * L * 4
    if layout == "bhld":
        fits = _VMEM_BUDGET // (n_bufs[0] * small + n_bufs[1] * big)
    else:
        fits = (_VMEM_BUDGET - n_bufs[1] * big) // (n_bufs[0] * small + 2 * head_bias)
    tiles = [g for g in range(1, H + 1) if H % g == 0
             and (layout == "bhld" or g == H or (g * dh) % _LANES == 0)]
    return max([g for g in tiles if g <= fits] or tiles[:1])


def _apply_causal(s):
    # iota-built mask (Pallas kernels cannot capture host array constants);
    # Lk - Lq offset keeps self-attention semantics when the query block is
    # the tail of the kv sequence (standard convention)
    Lq, Lk = s.shape[-2], s.shape[-1]
    rows = jax.lax.broadcasted_iota(jnp.int32, s.shape, s.ndim - 2)
    cols = jax.lax.broadcasted_iota(jnp.int32, s.shape, s.ndim - 1)
    return jnp.where(cols <= rows + (Lk - Lq), s, -1e30)


def _head_at(layout, dh):
    """Where head j's [L, dh] tile lies in a block: a whole leading index
    heads-major, a static slice of the lanes in the projections' layout."""
    if layout == "bhld":
        return lambda j: (j,)
    return lambda j: (0, slice(None), slice(j * dh, (j + 1) * dh))


def _bias_tile(b_ref, bias_mode, j):
    """bias_mode: None | 'bcast' (B,1,L,L) | 'per_head' (B,H,L,L)."""
    if bias_mode is None:
        return None
    return b_ref[0, 0] if bias_mode == "bcast" else b_ref[0, j]


def _make_fwd_kernel(scale, causal, g, bias_mode, at):
    def kern(q_ref, k_ref, v_ref, *rest):
        b_ref, o_ref = rest if bias_mode else (None,) + rest
        for j in range(g):
            _sdpa_tile(q_ref[at(j)], k_ref[at(j)], v_ref[at(j)], _bias_tile(b_ref, bias_mode, j), scale, causal,
                       o_ref, at(j))
    return kern


def _sdpa_tile(q, k, v, bias, scale, causal, o_ref, at):
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    if bias is not None:
        s = s + bias.astype(jnp.float32)
    if causal:
        s = _apply_causal(s)
    m = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.exp(s - m)
    p = p / jnp.sum(p, axis=-1, keepdims=True)
    o = jax.lax.dot_general(p.astype(q.dtype), v, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)
    o_ref[at] = o.astype(o_ref.dtype)


def _sdpa_tile_bwd(q, k, v, do, bias, scale, causal, dq_ref, dk_ref, dv_ref, at):
    # recompute forward probs (flash-style: cheaper than saving [L,L] to HBM)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    if bias is not None:
        s = s + bias.astype(jnp.float32)
    if causal:
        s = _apply_causal(s)
    m = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.exp(s - m)
    p = p / jnp.sum(p, axis=-1, keepdims=True)

    pb = p.astype(q.dtype)
    # dV = P^T dO
    dv = jax.lax.dot_general(pb, do, (((0,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32)
    # dP = dO V^T
    dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
    row = jnp.sum(dp * p, axis=-1, keepdims=True)
    ds = (p * (dp - row) * scale).astype(q.dtype)
    # dQ = dS K ; dK = dS^T Q
    dq = jax.lax.dot_general(ds, k, (((1,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32)
    dk = jax.lax.dot_general(ds, q, (((0,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32)
    dq_ref[at] = dq.astype(dq_ref.dtype)
    dk_ref[at] = dk.astype(dk_ref.dtype)
    dv_ref[at] = dv.astype(dv_ref.dtype)


def _make_bwd_kernel(scale, causal, g, bias_mode, at):
    def kern(q_ref, k_ref, v_ref, *rest):
        b_ref, do_ref, dq_ref, dk_ref, dv_ref = rest if bias_mode else (None,) + rest
        for j in range(g):
            _sdpa_tile_bwd(q_ref[at(j)], k_ref[at(j)], v_ref[at(j)], do_ref[at(j)], _bias_tile(b_ref, bias_mode, j),
                           scale, causal, dq_ref, dk_ref, dv_ref, at(j))
    return kern


def _bias_mode(bias):
    if bias is None:
        return None
    return "bcast" if bias.shape[1] == 1 else "per_head"


def _head_bias(bias, bias_mode):
    """Bytes of one head's bias tile where each head has its own, else 0."""
    return bias.shape[2] * bias.shape[3] * bias.dtype.itemsize if bias_mode == "per_head" else 0


def _dims(q, k, layout):
    """(B, H, L, Lk, dh) of 4-D operands in `layout`."""
    if layout == "bhld":
        B, H, L, dh = q.shape
        return B, H, L, k.shape[2], dh
    B, L, H, dh = q.shape
    return B, H, L, k.shape[1], dh


def _flat(t, layout):
    """The operand as the kernel's BlockSpecs read it, a free reshape either
    way: [B*H, L, dh] heads-major, [B, L, H*dh] in the projections' layout."""
    if layout == "bhld":
        return t.reshape((-1,) + t.shape[2:])
    return t.reshape(t.shape[:2] + (-1,))


def _specs(dims, g, bias_mode, layout):
    """(the grid, tile(length) -> the BlockSpec of a q/k/v/o/cotangent block
    of `g` heads, [the bias's BlockSpec]).  The two layouts differ here and in
    `_head_at` alone.  Heads-major the grid is the B * H / g blocks of the
    flattened pairs; in the projections' layout it is (B, H / g), the heads'
    blocks the fast axis, so that no index map divides (a `//` and a `%` an
    index map cost the step's lowering 7 s on the chip's host: PERF.md, PR 39)."""
    B, H, L, Lk, dh = dims
    hpg = H // g
    if layout == "bhld":
        def tile(n):
            return pl.BlockSpec((g, n, dh), lambda i: (i, 0, 0))
        bias = {"bcast": pl.BlockSpec((1, 1, L, Lk), lambda i: (i // hpg, 0, 0, 0)),
                "per_head": pl.BlockSpec((1, g, L, Lk), lambda i: (i // hpg, i % hpg, 0, 0))}
        return (B * hpg,), tile, [bias[bias_mode]] if bias_mode else []

    def tile(n):
        return pl.BlockSpec((1, n, g * dh), lambda b, h: (b, 0, h))
    bias = {"bcast": pl.BlockSpec((1, 1, L, Lk), lambda b, h: (b, 0, 0, 0)),
            "per_head": pl.BlockSpec((1, g, L, Lk), lambda b, h: (b, h, 0, 0))}
    return (B, hpg), tile, [bias[bias_mode]] if bias_mode else []


def _cost(dims, products):
    """What a call computes, for the step's `cost_analysis()`: `products`
    [L, Lk, dh] matrix products a (batch, head) pair and one exponential a
    score.  `bytes_accessed` is left 0 on purpose.  Told the bytes (each
    operand once, 101 MB forward and 176 MB backward at (32, 12, 512, 64)),
    XLA's memory-space assignment prefetches across the call and stops
    keeping the dropout fusion's output behind it in fast memory: TPU v5e,
    `bert-base.pretrain-s512`, samples/s: no estimate 212.596, 212.603;
    operations alone 212.546, 212.546; bytes alone 207.979, 207.977; both
    209.028, 209.030 (PERF.md, PR 30)."""
    B, H, L, Lk, dh = dims
    pairs = B * H
    return pl.CostEstimate(flops=2 * products * pairs * L * Lk * dh,
                           transcendentals=pairs * L * Lk, bytes_accessed=0)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7))
def fused_sdpa(q, k, v, bias, causal, scale, interpret=False, layout="bhld"):
    """Fused attention over [B,H,L,dh] (`layout="bhld"`) or [B,L,H,dh]
    ("blhd": the projections' own layout, read and written as [B,L,H*dh]);
    the output in the operands' layout; bias non-differentiable."""
    out, _ = _fused_sdpa_fwd(q, k, v, bias, causal, scale, interpret, layout)
    return out


def _heads_a_step(q, k, bias, layout, n_bufs):
    _, H, L, Lk, dh = _dims(q, k, layout)
    return _pick_heads(H, max(L, Lk), dh, q.dtype.itemsize, n_bufs, layout, _head_bias(bias, _bias_mode(bias)))


def _fused_sdpa_fwd(q, k, v, bias, causal, scale, interpret, layout):
    g = _heads_a_step(q, k, bias, layout, (6, 2))
    return _fwd_call(q, k, v, bias, causal, scale, interpret, layout, g), (q, k, v, bias)


def _fused_sdpa_bwd(causal, scale, interpret, layout, res, g_out):
    q, k, v, bias = res
    g = _heads_a_step(q, k, bias, layout, (10, 3))
    dbias = None if bias is None else jnp.zeros_like(bias)
    return _bwd_call(q, k, v, bias, g_out, causal, scale, interpret, layout, g) + (dbias,)


fused_sdpa.defvjp(*counted_rules("fused_attention", _fused_sdpa_fwd, _fused_sdpa_bwd))


# The two calls are `jax.jit`s of their own inside the step's: a model's layers
# call them with one signature, so JAX traces each kernel once a program and
# lowers it to ONE function that every layer calls (XLA inlines the calls: the
# compiled step holds the same 24 custom calls, each under its own op's scope),
# where a bare `pallas_call` is traced and lowered to a Mosaic module layer by
# layer, in Python, by its body's size.  `bert-base.pretrain-s512`, twelve
# layers, `setup_lower_s` | warm `setup_s`: the parent (3 and 2 heads a step)
# 11.4 | 38.8-41.8; bare calls at 2 and 2 heads 17.9 | 47.3-49.2 and at 12 and 6
# 25.4 | 54.7-58.5 (a third of it the index maps' `//` and `%`, hence the 2-D
# grid of `_specs`); shared 10.2 | 37.4-38.1, the step's own lowering 6.0 ->
# 4.7 s.  The rate is the bare calls' to 0.2% (239.54 | 239.15: XLA prefetches
# 20 of 1288 operands differently; PERF.md, PR 39).
@functools.partial(jax.jit, static_argnums=(4, 5, 6, 7, 8))
def _fwd_call(q, k, v, bias, causal, scale, interpret, layout, g):
    dims = B, H, L, Lk, dh = _dims(q, k, layout)
    assert max(L, Lk) <= 1024, "use the streaming flash kernel beyond 1024"
    bias_mode = _bias_mode(bias)
    grid, tile, bias_spec = _specs(dims, g, bias_mode, layout)
    args = tuple(_flat(t, layout) for t in (q, k, v)) + ((bias,) if bias is not None else ())
    out = pl.pallas_call(
        _make_fwd_kernel(scale, causal, g, bias_mode, _head_at(layout, dh)),
        grid=grid,
        in_specs=[tile(L), tile(Lk), tile(Lk)] + bias_spec,
        out_specs=tile(L),
        out_shape=jax.ShapeDtypeStruct(args[0].shape, q.dtype),
        cost_estimate=_cost(dims, 2),
        name="fused_sdpa_fwd",
        interpret=interpret,
    )(*args)
    return out.reshape(q.shape)


@functools.partial(jax.jit, static_argnums=(5, 6, 7, 8, 9))
def _bwd_call(q, k, v, bias, g_out, causal, scale, interpret, layout, g):
    dims = B, H, L, Lk, dh = _dims(q, k, layout)
    bias_mode = _bias_mode(bias)
    grid, tile, bias_spec = _specs(dims, g, bias_mode, layout)
    args = tuple(_flat(t, layout) for t in (q, k, v)) + ((bias,) if bias is not None else ()) + (_flat(g_out, layout),)
    dq, dk, dv = pl.pallas_call(
        _make_bwd_kernel(scale, causal, g, bias_mode, _head_at(layout, dh)),
        grid=grid,
        in_specs=[tile(L), tile(Lk), tile(Lk)] + bias_spec + [tile(L)],
        out_specs=[tile(L), tile(Lk), tile(Lk)],
        out_shape=[jax.ShapeDtypeStruct(a.shape, a.dtype) for a in args[:3]],
        cost_estimate=_cost(dims, 5),
        name="fused_sdpa_bwd",
        interpret=interpret,
    )(*args)
    return dq.reshape(q.shape), dk.reshape(k.shape), dv.reshape(v.shape)
