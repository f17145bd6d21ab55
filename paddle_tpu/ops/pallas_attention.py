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
64-wide heads, 384 to 512 queries and keys, no mesh.  TPU v5e, BERT-base,
32 x 512 tokens a step: 212.54 samples/s against 181.36 with XLA's attention
(`fused_attention` 54.4 -> 18.2 ms of the step, `peak_hbm_gb` 11.18 -> 7.24);
at 256 x 128 it loses, 1003.2 against 1132.9, and is not taken (PERF.md,
PRs 29 and 30).

Same mathematics as the XLA path: operands in their own dtype on the MXU,
float32 accumulation, float32 scores and softmax, the probabilities rounded
to the operands' dtype for PV.  The backward rounds dS to the operands'
dtype before the dQ and dK products, as XLA's transposed einsums and the
stock flash kernel's backward do (tests/test_pallas_attention.py compares
the three).

Contracts:
  * q/k/v: [B, H, L, dh] all same dtype (bf16 or f32); out matches.
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

# The working set a grid step may hold, which sets the (batch, head) pairs a
# step (`_pick_nb`): 3 forward and 2 backward at 512 keys, 4 and 3 at 384.
# Re-chosen on the chip in PR 30 and left alone: (32, 12, 512, 64) bf16, the
# backward kernel alone, 1.458 ms at 2 MB (one pair a step), 1.425 at 8 MB,
# 1.407 at 16 MB (6 and 4 pairs), 1.404 at 24 MB: 0.02 ms a layer between 8
# and 24, a fifth of a millisecond of a 150 ms step.  The divide by the row
# sum likewise: one reciprocal a row and a multiply a score instead reads
# 1.431 at 8 MB (PERF.md, PR 30).
_VMEM_BUDGET = 8 * 1024 * 1024


def _pick_nb(H, L, dh, itemsize, n_bufs):
    """Largest divisor of H whose working set fits the VMEM budget.

    n_bufs: per-pair tile count estimate (qkv/o tiles + f32 score/prob
    buffers) — fwd ~ (4 small + 2 big), bwd ~ (7 small + 3 big)."""
    small = L * dh * itemsize
    big = L * L * 4
    per_pair = n_bufs[0] * small + n_bufs[1] * big
    nb = max(1, int(_VMEM_BUDGET // max(per_pair, 1)))
    nb = min(nb, H)
    while H % nb:
        nb -= 1
    return nb


def _apply_causal(s):
    # iota-built mask (Pallas kernels cannot capture host array constants);
    # Lk - Lq offset keeps self-attention semantics when the query block is
    # the tail of the kv sequence (standard convention)
    Lq, Lk = s.shape[-2], s.shape[-1]
    rows = jax.lax.broadcasted_iota(jnp.int32, s.shape, s.ndim - 2)
    cols = jax.lax.broadcasted_iota(jnp.int32, s.shape, s.ndim - 1)
    return jnp.where(cols <= rows + (Lk - Lq), s, -1e30)


def _make_fwd_kernel(scale, causal, nb, bias_mode):
    """bias_mode: None | 'bcast' (B,1,L,L) | 'per_head' (B,H,L,L)."""

    if bias_mode is None:
        def kern(q_ref, k_ref, v_ref, o_ref):
            for j in range(nb):
                _sdpa_tile(q_ref[j], k_ref[j], v_ref[j], None, scale, causal,
                           o_ref, j)
        return kern

    def kern(q_ref, k_ref, v_ref, b_ref, o_ref):
        for j in range(nb):
            b = b_ref[0, 0] if bias_mode == "bcast" else b_ref[0, j]
            _sdpa_tile(q_ref[j], k_ref[j], v_ref[j], b, scale, causal,
                       o_ref, j)
    return kern


def _sdpa_tile(q, k, v, bias, scale, causal, o_ref, j):
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
    o_ref[j] = o.astype(o_ref.dtype)


def _sdpa_tile_bwd(q, k, v, do, bias, scale, causal, dq_ref, dk_ref, dv_ref, j):
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
    dq_ref[j] = dq.astype(dq_ref.dtype)
    dk_ref[j] = dk.astype(dk_ref.dtype)
    dv_ref[j] = dv.astype(dv_ref.dtype)


def _make_bwd_kernel(scale, causal, nb, bias_mode):
    if bias_mode is None:
        def kern(q_ref, k_ref, v_ref, do_ref, dq_ref, dk_ref, dv_ref):
            for j in range(nb):
                _sdpa_tile_bwd(q_ref[j], k_ref[j], v_ref[j], do_ref[j], None,
                               scale, causal, dq_ref, dk_ref, dv_ref, j)
        return kern

    def kern(q_ref, k_ref, v_ref, b_ref, do_ref, dq_ref, dk_ref, dv_ref):
        for j in range(nb):
            b = b_ref[0, 0] if bias_mode == "bcast" else b_ref[0, j]
            _sdpa_tile_bwd(q_ref[j], k_ref[j], v_ref[j], do_ref[j], b,
                           scale, causal, dq_ref, dk_ref, dv_ref, j)
    return kern


def _bias_mode(bias, H):
    if bias is None:
        return None
    return "bcast" if bias.shape[1] == 1 else "per_head"


def _specs(B, H, L, Lk, dh, nb, bias_mode, n_io):
    """BlockSpecs for [BH,L,dh]-flattened q/k/v(/bias)(/cotangent)."""
    def _fix(spec_shape, imap):
        return pl.BlockSpec(spec_shape, imap)

    hpnb = H // nb
    specs = [
        _fix((nb, L, dh), lambda i: (i, 0, 0)),
        _fix((nb, Lk, dh), lambda i: (i, 0, 0)),
        _fix((nb, Lk, dh), lambda i: (i, 0, 0)),
    ]
    if bias_mode == "bcast":
        specs.append(_fix((1, 1, L, Lk), lambda i: (i // hpnb, 0, 0, 0)))
    elif bias_mode == "per_head":
        specs.append(_fix((1, nb, L, Lk), lambda i: (i // hpnb, i % hpnb, 0, 0)))
    for _ in range(n_io):
        specs.append(_fix((nb, L, dh), lambda i: (i, 0, 0)))
    return specs


def _cost(B, H, L, Lk, dh, products):
    """What a call computes, for the step's `cost_analysis()`: `products`
    [L, Lk, dh] matrix products a (batch, head) pair and one exponential a
    score.  `bytes_accessed` is left 0 on purpose.  Told the bytes (each
    operand once, 101 MB forward and 176 MB backward at (32, 12, 512, 64)),
    XLA's memory-space assignment prefetches across the call and stops
    keeping the dropout fusion's output behind it in fast memory: TPU v5e,
    `bert-base.pretrain-s512`, samples/s: no estimate 212.596, 212.603;
    operations alone 212.546, 212.546; bytes alone 207.979, 207.977; both
    209.028, 209.030 (PERF.md, PR 30)."""
    pairs = B * H
    return pl.CostEstimate(flops=2 * products * pairs * L * Lk * dh,
                           transcendentals=pairs * L * Lk, bytes_accessed=0)


def _flatten(q, k, v):
    B, H, L, dh = q.shape
    Lk = k.shape[2]
    return (q.reshape(B * H, L, dh), k.reshape(B * H, Lk, dh),
            v.reshape(B * H, Lk, dh))


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def fused_sdpa(q, k, v, bias, causal, scale, interpret=False):
    """Fused attention over [B,H,L,dh]; bias non-differentiable."""
    out, _ = _fused_sdpa_fwd(q, k, v, bias, causal, scale, interpret)
    return out


def _fused_sdpa_fwd(q, k, v, bias, causal, scale, interpret):
    B, H, L, dh = q.shape
    Lk = k.shape[2]
    assert max(L, Lk) <= 1024, "use the streaming flash kernel beyond 1024"
    bias_mode = _bias_mode(bias, H)
    nb = _pick_nb(H, max(L, Lk), dh, q.dtype.itemsize, (6, 2))
    qf, kf, vf = _flatten(q, k, v)
    in_specs = _specs(B, H, L, Lk, dh, nb, bias_mode, 0)
    out_spec = pl.BlockSpec((nb, L, dh), lambda i: (i, 0, 0))
    kern = _make_fwd_kernel(scale, causal, nb, bias_mode)
    args = (qf, kf, vf) + ((bias,) if bias is not None else ())
    out = pl.pallas_call(
        kern,
        grid=(B * H // nb,),
        in_specs=in_specs,
        out_specs=out_spec,
        out_shape=jax.ShapeDtypeStruct((B * H, L, dh), q.dtype),
        cost_estimate=_cost(B, H, L, Lk, dh, 2),
        name="fused_sdpa_fwd",
        interpret=interpret,
    )(*args)
    out = out.reshape(B, H, L, dh)
    return out, (q, k, v, bias)


def _fused_sdpa_bwd(causal, scale, interpret, res, g):
    q, k, v, bias = res
    B, H, L, dh = q.shape
    Lk = k.shape[2]
    bias_mode = _bias_mode(bias, H)
    nb = _pick_nb(H, max(L, Lk), dh, q.dtype.itemsize, (10, 3))
    qf, kf, vf = _flatten(q, k, v)
    gf = g.reshape(B * H, L, dh)
    in_specs = _specs(B, H, L, Lk, dh, nb, bias_mode, 1)
    out_specs = [
        pl.BlockSpec((nb, L, dh), lambda i: (i, 0, 0)),
        pl.BlockSpec((nb, Lk, dh), lambda i: (i, 0, 0)),
        pl.BlockSpec((nb, Lk, dh), lambda i: (i, 0, 0)),
    ]
    kern = _make_bwd_kernel(scale, causal, nb, bias_mode)
    args = (qf, kf, vf) + ((bias,) if bias is not None else ()) + (gf,)
    dq, dk, dv = pl.pallas_call(
        kern,
        grid=(B * H // nb,),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=[
            jax.ShapeDtypeStruct((B * H, L, dh), q.dtype),
            jax.ShapeDtypeStruct((B * H, Lk, dh), k.dtype),
            jax.ShapeDtypeStruct((B * H, Lk, dh), v.dtype),
        ],
        cost_estimate=_cost(B, H, L, Lk, dh, 5),
        name="fused_sdpa_bwd",
        interpret=interpret,
    )(*args)
    dbias = None if bias is None else jnp.zeros_like(bias)
    return (dq.reshape(B, H, L, dh), dk.reshape(B, H, Lk, dh),
            dv.reshape(B, H, Lk, dh), dbias)


fused_sdpa.defvjp(_fused_sdpa_fwd, _fused_sdpa_bwd)
