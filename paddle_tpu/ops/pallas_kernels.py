"""Fused Pallas TPU kernels beyond SDPA: LayerNorm+residual, BN epilogue
(scale/shift/relu), and the row-slab Adam update.

Reference role: the hand-fused device kernels of operators/fused/
(fused_layernorm_residual_dropout_bias, conv_fusion, fused adam) — the
reference's answer to per-op dispatch overhead across its 169k-LoC operator
tree.  Here the XLA seam already fuses most elementwise chains, so each
kernel below targets a case the r5 step-time profile showed XLA handling
badly (see docs/performance.md):

  * `fused_ln_residual` — residual add + LayerNorm over the last axis in one
    VMEM pass: the [B,L,D] activation is read once forward (XLA's two-pass
    mean/var formulation reads it twice, and the residual add materializes a
    third stream) and once backward (stats recomputed flash-style).
  * `fused_scale_shift_relu` — the BN inference/apply epilogue y =
    max(x*mul + add, 0) with per-channel mul/add, applied AFTER the batch
    stats are computed: keeps the conv's producer fusion clean (the r5
    profile showed BN reductions fused INTO convs wrecking MXU tiling) while
    the epilogue runs at roofline bandwidth.
  * `fused_adam` — m/v/param in ONE pass over row slabs instead of the 5+
    HBM round-trips of the composite (m, v, sqrt, div, sub chains), with
    `input_output_aliases` pinning the update in place.
  * `fused_softmax_xent` — hard-label softmax-cross-entropy (max, logsumexp
    and the picked logit in one VMEM pass; backward recomputes the softmax
    flash-style).  The composite is pure HBM traffic; PERF.md section 5
    has its measured share of a step (the ledger's `breakdown`).
  * `fused_bias_act` — y = act(x + bias[D]) for relu/gelu, the FFN bias
    epilogue (core/passes.py fuse_bias_act folds the add->act pair); the
    composite's intermediate never round-trips through HBM.

Every kernel is an OPT-IN lowering alternative behind `FLAGS_use_pallas`
(ops/nn_ops.py, ops/math_ops.py, ops/optimizer_ops.py).  A call site keeps
the XLA composite when the flag is off, when the platform is not a TPU, or
when the kernel's `*_shape_ok` predicate refuses the shape; which of the two
a compiled step holds is read off the step itself — each `pallas_call` is
named, and `chip_smoke.py` prints the kernels it finds
(`tpu_custom_call` in the compiled text).  Each kernel matches its composite
to per-dtype tolerance: tests/test_pallas_kernels.py runs the parity matrix
in interpret mode, tests/test_chip_compile.py compiles every kernel for a
described v5e at BERT-base / ResNet-50 widths, chip_smoke.py runs them
compiled; the interleaved device A/B lives in tools/opbench.py --fused.

Kernel-shape contract: the last axis is the vector (lane) axis; leading
axes flatten to rows.  Row slabs are whole Mosaic tiles — a multiple of 8
rows (16 for bf16) or the whole array — chosen so one grid step's rows fit
the VMEM budget; per-row operands are carried as [R, 1], never as a blocked
rank-1 array.  Row counts no such slab divides keep the composite rather
than pad (padding would re-introduce the HBM copy the kernel exists to
remove); the elementwise Adam update alone lets its last slab overhang.
"""
from __future__ import annotations

import functools
from typing import Callable, Dict

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from .common import counted_rules

# Half of the chip's 16 MB scoped VMEM: each kernel counts the f32 rows one
# grid step keeps live, Mosaic's double buffers of them take the other half.
_VMEM_BUDGET = 8 * 1024 * 1024


def _sublane(*dtypes) -> int:
    """Rows in one Mosaic tile of the narrowest operand: 8 for 4-byte
    dtypes, 16 for bf16."""
    return max(32 // jnp.dtype(d).itemsize for d in dtypes)


def _lane_pad(width: int) -> int:
    """VMEM holds the last axis in whole 128-lane tiles."""
    return -(-width // 128) * 128


def _n_rows(shape) -> int:
    """Rows of the [R, last] view the kernels work on."""
    return int(np.prod(shape[:-1])) if len(shape) > 1 else 1


def _pick_slab(n_rows: int, row_bytes: int, sublane: int = 8):
    """Largest row slab that divides n_rows, fits the VMEM budget and that
    Mosaic can tile: the whole array, or a multiple of the sublane tile.
    None when there is no such slab — the kernel cannot take the shape."""
    cap = max(1, _VMEM_BUDGET // max(row_bytes, 1))
    if n_rows <= cap:
        return n_rows
    for slab in range(cap - cap % sublane, 0, -sublane):
        if n_rows % slab == 0:
            return slab
    return None


def pallas_supported(platform) -> bool:
    """True when the opt-in kernels can lower on this backend."""
    return platform == "tpu"


def use_pallas(ctx) -> bool:
    """The routing predicate every lowering alternative shares: the flag is
    the opt-in, the platform is the capability."""
    from ..flags import flag

    return bool(flag("FLAGS_use_pallas")) and pallas_supported(
        getattr(ctx, "platform", None))


# --------------------------------------------------------------------------
# fused LayerNorm + residual
# --------------------------------------------------------------------------


def _ln_rows(x):
    """[.., D] -> ([R, D], unflatten)."""
    D = x.shape[-1]
    lead = x.shape[:-1]
    return x.reshape(_n_rows(x.shape), D), lambda y: y.reshape(*lead, D)


def _ln_fwd_kernel(eps, has_res):
    def kern(*refs):
        if has_res:
            x_ref, r_ref, s_ref, b_ref, o_ref = refs
            r = x_ref[...].astype(jnp.float32) + r_ref[...].astype(jnp.float32)
        else:
            x_ref, s_ref, b_ref, o_ref = refs
            r = x_ref[...].astype(jnp.float32)
        mean = jnp.mean(r, axis=-1, keepdims=True)
        c = r - mean
        var = jnp.mean(c * c, axis=-1, keepdims=True)
        y = c * jax.lax.rsqrt(var + eps)
        y = (y * s_ref[...].astype(jnp.float32)
             + b_ref[...].astype(jnp.float32))  # (1, D) broadcasts over rows
        o_ref[...] = y.astype(o_ref.dtype)

    return kern


def _ln_bwd_kernel(eps, has_res, out_dtype):
    """Recompute stats from x(+res), emit d(input) slab and ACCUMULATE
    dscale/dbias across sequential grid steps (all steps map to the same
    f32 accumulator block; TPU grids execute in order on one core)."""

    def kern(*refs):
        if has_res:
            x_ref, r_ref, s_ref, g_ref, dx_ref, ds_ref, db_ref = refs
            r = x_ref[...].astype(jnp.float32) + r_ref[...].astype(jnp.float32)
        else:
            x_ref, s_ref, g_ref, dx_ref, ds_ref, db_ref = refs
            r = x_ref[...].astype(jnp.float32)
        i = pl.program_id(0)

        mean = jnp.mean(r, axis=-1, keepdims=True)
        c = r - mean
        var = jnp.mean(c * c, axis=-1, keepdims=True)
        inv = jax.lax.rsqrt(var + eps)
        xhat = c * inv
        g = g_ref[...].astype(jnp.float32)
        gs = g * s_ref[...].astype(jnp.float32)
        m1 = jnp.mean(gs, axis=-1, keepdims=True)
        m2 = jnp.mean(gs * xhat, axis=-1, keepdims=True)
        dx = inv * (gs - m1 - xhat * m2)
        dx_ref[...] = dx.astype(out_dtype)
        ds = jnp.sum(g * xhat, axis=0)
        db = jnp.sum(g, axis=0)

        @pl.when(i == 0)
        def _init():
            ds_ref[...] = ds
            db_ref[...] = db

        @pl.when(i != 0)
        def _acc():
            ds_ref[...] += ds
            db_ref[...] += db

    return kern


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def fused_ln_residual(x, res, scale, bias, eps, interpret=False):
    """y = LayerNorm(x + res) * scale + bias over the LAST axis.

    res may be None (plain LN).  scale/bias are [D]; stats in f32; output
    matches x.dtype.  bwd recomputes stats (nothing but x/res saved)."""
    out, _ = _ln_fwd(x, res, scale, bias, eps, interpret)
    return out


def _ln_slab(R, D, dtype, has_res, bwd):
    n_f32 = (5 if bwd else 3) + bool(has_res)
    return _pick_slab(R, _lane_pad(D) * 4 * n_f32, _sublane(dtype))


def ln_shape_ok(shape, dtype, has_res) -> bool:
    """The no-padding contract: the rows must split into whole slabs the
    backward (the tighter budget) can tile, else the lowering keeps the
    composite."""
    return _ln_slab(_n_rows(shape), shape[-1], dtype, has_res,
                    bwd=True) is not None


def _ln_call(x2, res2, scale, bias, eps, interpret):
    R, D = x2.shape
    slab = _ln_slab(R, D, x2.dtype, res2 is not None, bwd=False)
    row_spec = pl.BlockSpec((slab, D), lambda i: (i, 0))
    vec_spec = pl.BlockSpec((D,), lambda i: (0,))
    args = (x2,) + ((res2,) if res2 is not None else ()) + (scale, bias)
    in_specs = [row_spec] * (2 if res2 is not None else 1) + [vec_spec] * 2
    return pl.pallas_call(
        _ln_fwd_kernel(eps, res2 is not None),
        grid=(R // slab,),
        in_specs=in_specs,
        out_specs=row_spec,
        out_shape=jax.ShapeDtypeStruct((R, D), x2.dtype),
        interpret=interpret,
        name="ln_residual_fwd",
    )(*args)


def _ln_fwd(x, res, scale, bias, eps, interpret):
    x2, unflat = _ln_rows(x)
    res2 = None if res is None else _ln_rows(res)[0]
    out = _ln_call(x2, res2, scale, bias, eps, interpret)
    return unflat(out), (x, res, scale, bias)


def _ln_bwd(eps, interpret, saved, g):
    x, res, scale, bias = saved
    x2, unflat = _ln_rows(x)
    res2 = None if res is None else _ln_rows(res)[0]
    g2 = _ln_rows(g)[0]
    R, D = x2.shape
    slab = _ln_slab(R, D, x2.dtype, res2 is not None, bwd=True)
    row_spec = pl.BlockSpec((slab, D), lambda i: (i, 0))
    vec_spec = pl.BlockSpec((D,), lambda i: (0,))
    acc_spec = pl.BlockSpec((D,), lambda i: (0,))
    args = (x2,) + ((res2,) if res2 is not None else ()) + (scale, g2)
    in_specs = ([row_spec] * (2 if res2 is not None else 1)
                + [vec_spec, row_spec])
    dx2, ds, db = pl.pallas_call(
        _ln_bwd_kernel(eps, res2 is not None, x2.dtype),
        grid=(R // slab,),
        in_specs=in_specs,
        out_specs=[row_spec, acc_spec, acc_spec],
        out_shape=[
            jax.ShapeDtypeStruct((R, D), x2.dtype),
            jax.ShapeDtypeStruct((D,), jnp.float32),
            jax.ShapeDtypeStruct((D,), jnp.float32),
        ],
        interpret=interpret,
        name="ln_residual_bwd",
    )(*args)
    dx = unflat(dx2)
    dres = None if res is None else dx.astype(res.dtype)
    return (dx, dres, ds.astype(scale.dtype), db.astype(bias.dtype))


fused_ln_residual.defvjp(*counted_rules("layer_norm", _ln_fwd, _ln_bwd))


# --------------------------------------------------------------------------
# fused BN epilogue: per-channel scale/shift (+ relu)
# --------------------------------------------------------------------------


def _epilogue_fwd_kernel(relu):
    def kern(x_ref, m_ref, a_ref, o_ref):
        # m/a are (slab, 1) blocks: one multiplier per row, lane-broadcast
        y = x_ref[...].astype(jnp.float32) * m_ref[...] + a_ref[...]
        if relu:
            y = jnp.maximum(y, 0.0)
        o_ref[...] = y.astype(o_ref.dtype)

    return kern


def _epilogue_bwd_kernel(relu, out_dtype):
    def kern(x_ref, m_ref, a_ref, g_ref, dx_ref, dm_ref, da_ref):
        x = x_ref[...].astype(jnp.float32)
        mul = m_ref[...]
        g = g_ref[...].astype(jnp.float32)
        if relu:
            live = (x * mul + a_ref[...]) > 0.0
            g = jnp.where(live, g, 0.0)
        dx_ref[...] = (g * mul).astype(out_dtype)
        # dm/da are PER-ROW and each grid step owns a disjoint row slab
        # (BlockSpec i -> (i, 0)), so a plain store is complete — unlike
        # _ln_bwd_kernel, whose dscale/dbias block is shared across steps
        # (i -> (0,)) and genuinely accumulates.
        dm_ref[...] = jnp.sum(g * x, axis=-1, keepdims=True)
        da_ref[...] = jnp.sum(g, axis=-1, keepdims=True)

    return kern


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def fused_scale_shift_relu(x, mul, add, relu=True, interpret=False):
    """y = max(x * mul + add, 0) with PER-ROW mul/add [R, 1] over x:[R, W].

    The BN-epilogue shape: callers flatten NCHW to [N*C, H*W] and tile the
    per-channel f32 multipliers to N*C rows (bn_epilogue below).  The
    per-row vectors are rank 2 because Mosaic tiles a blocked rank-1
    operand differently from the layout XLA hands it.
    Backward masks by recomputed sign, accumulates dmul/dadd per row."""
    out, _ = _epilogue_fwd(x, mul, add, relu, interpret)
    return out


def _epilogue_slab(R, W, dtype, bwd):
    # each [slab, 1] per-row block occupies a whole 128-lane tile row
    n_rows, n_vecs = (3, 4) if bwd else (2, 2)
    return _pick_slab(R, (_lane_pad(W) * n_rows + 128 * n_vecs) * 4,
                      _sublane(dtype))


def epilogue_shape_ok(shape, dtype) -> bool:
    """NC* activation -> [N*C, prod(spatial)] rows must split into whole
    slabs, else the lowering keeps the composite."""
    W = int(np.prod(shape[2:])) if len(shape) > 2 else 1
    return _epilogue_slab(shape[0] * shape[1], W, dtype, True) is not None


def _epilogue_fwd(x, mul, add, relu, interpret):
    R, W = x.shape
    slab = _epilogue_slab(R, W, x.dtype, bwd=False)
    row_spec = pl.BlockSpec((slab, W), lambda i: (i, 0))
    vec_spec = pl.BlockSpec((slab, 1), lambda i: (i, 0))
    out = pl.pallas_call(
        _epilogue_fwd_kernel(relu),
        grid=(R // slab,),
        in_specs=[row_spec, vec_spec, vec_spec],
        out_specs=row_spec,
        out_shape=jax.ShapeDtypeStruct((R, W), x.dtype),
        interpret=interpret,
        name="bn_epilogue_fwd",
    )(x, mul, add)
    return out, (x, mul, add)


def _epilogue_bwd(relu, interpret, saved, g):
    x, mul, add = saved
    R, W = x.shape
    slab = _epilogue_slab(R, W, x.dtype, bwd=True)
    row_spec = pl.BlockSpec((slab, W), lambda i: (i, 0))
    vec_spec = pl.BlockSpec((slab, 1), lambda i: (i, 0))
    dx, dm, da = pl.pallas_call(
        _epilogue_bwd_kernel(relu, x.dtype),
        grid=(R // slab,),
        in_specs=[row_spec, vec_spec, vec_spec, row_spec],
        out_specs=[row_spec, vec_spec, vec_spec],
        out_shape=[
            jax.ShapeDtypeStruct((R, W), x.dtype),
            jax.ShapeDtypeStruct((R, 1), jnp.float32),
            jax.ShapeDtypeStruct((R, 1), jnp.float32),
        ],
        interpret=interpret,
        name="bn_epilogue_bwd",
    )(x, mul, add, g)
    return dx, dm.astype(mul.dtype), da.astype(add.dtype)


fused_scale_shift_relu.defvjp(*counted_rules("batch_norm", _epilogue_fwd, _epilogue_bwd))


def bn_epilogue(x, mul, add, relu, interpret=False):
    """Apply the fused epilogue to an NCHW/NC* activation given per-channel
    f32 mul/add (channel axis 1): flatten to [N*C, prod(spatial)], tile the
    channel vectors to rows, run the kernel, restore the shape."""
    N, C = x.shape[0], x.shape[1]
    W = int(np.prod(x.shape[2:])) if x.ndim > 2 else 1
    x2 = x.reshape(N * C, W)
    mul_r = jnp.tile(mul.reshape(-1), N)[:, None]
    add_r = jnp.tile(add.reshape(-1), N)[:, None]
    y = fused_scale_shift_relu(x2, mul_r, add_r, bool(relu), interpret)
    return y.reshape(x.shape)


# --------------------------------------------------------------------------
# fused Adam row-slab update
# --------------------------------------------------------------------------

_ADAM_LANE = 256  # flatten to [R, _ADAM_LANE]; non-multiples fall back


def _adam_kernel(beta1, beta2, eps, p_dtype):
    def kern(p_ref, m_ref, v_ref, g_ref, lr_ref, po_ref, mo_ref, vo_ref):
        p = p_ref[...].astype(jnp.float32)
        g = g_ref[...].astype(jnp.float32)
        m = beta1 * m_ref[...].astype(jnp.float32) + (1.0 - beta1) * g
        v = beta2 * v_ref[...].astype(jnp.float32) + (1.0 - beta2) * (g * g)
        lr_t = lr_ref[0, 0]
        p2 = p - lr_t * m / (jnp.sqrt(v) + eps)
        po_ref[...] = p2.astype(p_dtype)
        mo_ref[...] = m.astype(mo_ref.dtype)
        vo_ref[...] = v.astype(vo_ref.dtype)

    return kern


def adam_shape_ok(shape) -> bool:
    """The no-padding contract: the element count must tile into
    [R, _ADAM_LANE] rows exactly, else the lowering keeps the composite."""
    n = int(np.prod(shape)) if len(shape) else 1
    return n % _ADAM_LANE == 0


def fused_adam(p, g, m, v, lr_t, beta1, beta2, eps, interpret=False):
    """One-pass Adam over row slabs: returns (p2, m2, v2).

    lr_t is the bias-corrected step size lr*sqrt(1-b2p)/(1-b1p), computed
    by the caller (the beta-pow advance stays outside).  p/m/v are aliased
    in place (`input_output_aliases`), so with the executor's donation this
    is a true in-HBM update — no double-buffered copies of optimizer
    state."""
    shape = p.shape
    n = int(np.prod(shape)) if len(shape) else 1
    assert n % _ADAM_LANE == 0, "caller must check adam_shape_ok first"
    R = n // _ADAM_LANE
    p2 = p.reshape(R, _ADAM_LANE)
    g2 = g.astype(jnp.float32).reshape(R, _ADAM_LANE)
    m2 = m.reshape(R, _ADAM_LANE)
    v2 = v.reshape(R, _ADAM_LANE)
    lr2 = jnp.asarray(lr_t, jnp.float32).reshape(1, 1)
    # The update is elementwise, so the slab need not divide R: the last
    # grid step overhangs the array, computes on padding and its
    # out-of-bounds rows are never written back.  (R is 2*3*3*5087 for the
    # BERT embedding — no aligned divisor exists.)
    # 7 blocks a step (p/m/v/g in, p/m/v out) plus temporaries: counted
    # as 7 rows, the BERT embedding's slab took 18.2 MB of the 16 MB scoped
    # VMEM inside the whole train step (alone it compiled); 10 leaves a
    # fifth spare
    cap = _VMEM_BUDGET // (_ADAM_LANE * 4 * 10)
    slab = R if R <= cap else cap - cap % 16  # whole tiles, f32 or bf16
    row_spec = pl.BlockSpec((slab, _ADAM_LANE), lambda i: (i, 0))
    lr_spec = pl.BlockSpec((1, 1), lambda i: (0, 0))
    po, mo, vo = pl.pallas_call(
        _adam_kernel(beta1, beta2, eps, p2.dtype),
        grid=(pl.cdiv(R, slab),),
        in_specs=[row_spec, row_spec, row_spec, row_spec, lr_spec],
        out_specs=[row_spec, row_spec, row_spec],
        out_shape=[
            jax.ShapeDtypeStruct((R, _ADAM_LANE), p2.dtype),
            jax.ShapeDtypeStruct((R, _ADAM_LANE), m2.dtype),
            jax.ShapeDtypeStruct((R, _ADAM_LANE), v2.dtype),
        ],
        input_output_aliases={0: 0, 1: 1, 2: 2},
        interpret=interpret,
        name="adam_slab",
    )(p2, m2, v2, g2, lr2)
    return po.reshape(shape), mo.reshape(shape), vo.reshape(shape)


# --------------------------------------------------------------------------
# fused softmax + cross-entropy (hard labels)
# --------------------------------------------------------------------------
# ISSUE-17 gap ranking: softmax_with_cross_entropy is 100% traffic-bound in
# every zoo program — the composite's max/exp-sum/pick chain streams the
# [N, V] logits through HBM three times (plus the Softmax slot when XLA
# fails to DCE it).  One VMEM pass computes max, logsumexp and the picked
# logit together; backward recomputes softmax flash-style (nothing but the
# logits and labels saved).


def _sxe_fwd_kernel(ignore_index):
    def kern(x_ref, l_ref, o_ref):
        x = x_ref[...].astype(jnp.float32)
        lab = l_ref[...]  # (slab, 1) int32
        m = jnp.max(x, axis=-1, keepdims=True)
        lse = jnp.log(jnp.sum(jnp.exp(x - m), axis=-1, keepdims=True)) + m
        iota = jax.lax.broadcasted_iota(jnp.int32, x.shape, x.ndim - 1)
        picked = jnp.sum(jnp.where(iota == lab, x, 0.0),
                         axis=-1, keepdims=True)
        o_ref[...] = jnp.where(lab == ignore_index, 0.0, lse - picked)

    return kern


def _sxe_bwd_kernel(ignore_index, out_dtype):
    def kern(x_ref, l_ref, g_ref, dx_ref):
        x = x_ref[...].astype(jnp.float32)
        lab = l_ref[...]  # (slab, 1) int32
        m = jnp.max(x, axis=-1, keepdims=True)
        e = jnp.exp(x - m)
        sm = e / jnp.sum(e, axis=-1, keepdims=True)
        iota = jax.lax.broadcasted_iota(jnp.int32, x.shape, x.ndim - 1)
        onehot = (iota == lab).astype(jnp.float32)
        dx = (sm - onehot) * g_ref[...]
        dx = jnp.where(lab == ignore_index, 0.0, dx)
        dx_ref[...] = dx.astype(out_dtype)

    return kern


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def fused_softmax_xent(logits, labels, ignore_index=-100, interpret=False):
    """loss[i] = logsumexp(logits[i]) - logits[i, labels[i]] in ONE pass.

    logits: [R, V]; labels: [R] integer.  Loss is f32 [R, 1] (matching the
    composite lowering's dtype); rows whose label equals ignore_index get
    zero loss and zero gradient.  The softmax is never materialized —
    callers that consume the Softmax slot keep the composite."""
    out, _ = _sxe_fwd(logits, labels, ignore_index, interpret)
    return out


def _sxe_slab(R, V, dtype, bwd):
    return _pick_slab(R, _lane_pad(V) * 4 * (4 if bwd else 3),
                      _sublane(dtype))


def sxe_shape_ok(shape, dtype) -> bool:
    """[.., V] logits: the rows must split into whole slabs, else the
    lowering keeps the composite."""
    return _sxe_slab(_n_rows(shape), shape[-1], dtype, bwd=True) is not None


def _sxe_fwd(logits, labels, ignore_index, interpret):
    # per-row operands (labels, loss, upstream grad) are [R, 1]: a blocked
    # rank-1 operand must be a multiple of 128 rows, which a [slab, V]
    # logits block at vocabulary width cannot afford
    R, V = logits.shape
    slab = _sxe_slab(R, V, logits.dtype, bwd=False)
    row_spec = pl.BlockSpec((slab, V), lambda i: (i, 0))
    lab_spec = pl.BlockSpec((slab, 1), lambda i: (i, 0))
    loss = pl.pallas_call(
        _sxe_fwd_kernel(int(ignore_index)),
        grid=(R // slab,),
        in_specs=[row_spec, lab_spec],
        out_specs=lab_spec,
        out_shape=jax.ShapeDtypeStruct((R, 1), jnp.float32),
        interpret=interpret,
        name="softmax_xent_fwd",
    )(logits, labels.astype(jnp.int32)[:, None])
    return loss, (logits, labels)


def _sxe_bwd(ignore_index, interpret, saved, g):
    logits, labels = saved
    R, V = logits.shape
    g1 = g.reshape(R, 1).astype(jnp.float32)
    slab = _sxe_slab(R, V, logits.dtype, bwd=True)
    row_spec = pl.BlockSpec((slab, V), lambda i: (i, 0))
    lab_spec = pl.BlockSpec((slab, 1), lambda i: (i, 0))
    dx = pl.pallas_call(
        _sxe_bwd_kernel(int(ignore_index), logits.dtype),
        grid=(R // slab,),
        in_specs=[row_spec, lab_spec, lab_spec],
        out_specs=row_spec,
        out_shape=jax.ShapeDtypeStruct((R, V), logits.dtype),
        interpret=interpret,
        name="softmax_xent_bwd",
    )(logits, labels.astype(jnp.int32)[:, None], g1)
    return dx, np.zeros(labels.shape, jax.dtypes.float0)


fused_softmax_xent.defvjp(*counted_rules("softmax_with_cross_entropy", _sxe_fwd, _sxe_bwd))


# --------------------------------------------------------------------------
# fused bias + activation epilogue (the FFN bias-act of BERT)
# --------------------------------------------------------------------------
# ISSUE-17 gap ranking: elementwise_add + relu/gelu are pure traffic
# (gap_frac 1.00) and together outrank every unfused compute op left in the
# zoo — the composite writes act's input to HBM only for act to read it
# straight back.  One pass applies bias and activation; backward recomputes
# the pre-activation (only x and bias saved) and accumulates dbias across
# row slabs like _ln_bwd_kernel's dscale.

_BIAS_ACTS = ("relu", "gelu")


_ERF_ALPHA = (0.00022905065861350646, 0.0034082910107109506,
              0.050955695062380861, 0.18520832239976145, 1.128379143519084)
_ERF_BETA = (-1.1791602954361697e-7, 0.000023547966471313185,
             0.0010179625278914885, 0.014070470171167667,
             0.11098505178285362, 0.49746925110067538, 1.0)


def _erf(x):
    """f32 erf from mul/add/div only: Mosaic has no lowering for the erf
    primitive.  The clamped rational x*P(x^2)/Q(x^2) XLA itself expands
    f32 erf into, so kernel and composite agree to ~4e-7."""
    x = jnp.clip(x, -3.832506856900711, 3.832506856900711)
    x2 = x * x

    def poly(coef):
        acc = coef[0]
        for c in coef[1:]:
            acc = acc * x2 + c
        return acc

    return x * poly(_ERF_ALPHA) / poly(_ERF_BETA)


def _act_fwd(z, act):
    if act == "relu":
        return jnp.maximum(z, 0.0)
    # exact gelu (jax.nn.gelu approximate=False): z * Phi(z)
    return 0.5 * z * (1.0 + _erf(z * (2.0 ** -0.5)))


def _act_grad(z, act):
    if act == "relu":
        return (z > 0.0).astype(jnp.float32)
    phi = jnp.exp(-0.5 * z * z) * 0.3989422804014327  # N(0,1) pdf
    return 0.5 * (1.0 + _erf(z * (2.0 ** -0.5))) + z * phi


def _bias_act_fwd_kernel(act):
    def kern(x_ref, b_ref, o_ref):
        z = x_ref[...].astype(jnp.float32) + b_ref[...].astype(jnp.float32)
        o_ref[...] = _act_fwd(z, act).astype(o_ref.dtype)

    return kern


def _bias_act_bwd_kernel(act, out_dtype):
    def kern(x_ref, b_ref, g_ref, dx_ref, db_ref):
        z = x_ref[...].astype(jnp.float32) + b_ref[...].astype(jnp.float32)
        dz = g_ref[...].astype(jnp.float32) * _act_grad(z, act)
        dx_ref[...] = dz.astype(out_dtype)
        i = pl.program_id(0)
        db = jnp.sum(dz, axis=0)

        @pl.when(i == 0)
        def _init():
            db_ref[...] = db

        @pl.when(i != 0)
        def _acc():
            db_ref[...] += db

    return kern


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def fused_bias_act(x, bias, act="gelu", interpret=False):
    """y = act(x + bias) with bias [D] broadcast over rows of x:[R, D].

    act in ("relu", "gelu") — gelu is the exact erf form (matches
    jax.nn.gelu(approximate=False), the lowering's composite).  Backward
    recomputes the pre-activation; dbias accumulates across row slabs in
    f32 (shared accumulator block, sequential TPU grid)."""
    out, _ = _bias_act_fwd(x, bias, act, interpret)
    return out


def _bias_act_slab(R, D, dtype):
    # 4 f32 rows per row: in/out blocks plus the gelu polynomial's live
    # temporaries (2 overran the chip's 16 MB scoped VMEM at [32768, 3072])
    return _pick_slab(R, _lane_pad(D) * 4 * 4, _sublane(dtype))


def bias_act_shape_ok(shape, dtype) -> bool:
    """[.., D] activation: the rows must split into whole slabs, else the
    lowering keeps the composite."""
    return _bias_act_slab(_n_rows(shape), shape[-1], dtype) is not None


def _bias_act_fwd(x, bias, act, interpret):
    assert act in _BIAS_ACTS, act
    R, D = x.shape
    slab = _bias_act_slab(R, D, x.dtype)
    row_spec = pl.BlockSpec((slab, D), lambda i: (i, 0))
    vec_spec = pl.BlockSpec((D,), lambda i: (0,))
    out = pl.pallas_call(
        _bias_act_fwd_kernel(act),
        grid=(R // slab,),
        in_specs=[row_spec, vec_spec],
        out_specs=row_spec,
        out_shape=jax.ShapeDtypeStruct((R, D), x.dtype),
        interpret=interpret,
        name="bias_act_fwd",
    )(x, bias)
    return out, (x, bias)


def _bias_act_bwd(act, interpret, saved, g):
    x, bias = saved
    R, D = x.shape
    slab = _bias_act_slab(R, D, x.dtype)
    row_spec = pl.BlockSpec((slab, D), lambda i: (i, 0))
    vec_spec = pl.BlockSpec((D,), lambda i: (0,))
    dx, db = pl.pallas_call(
        _bias_act_bwd_kernel(act, x.dtype),
        grid=(R // slab,),
        in_specs=[row_spec, vec_spec, row_spec],
        out_specs=[row_spec, vec_spec],
        out_shape=[
            jax.ShapeDtypeStruct((R, D), x.dtype),
            jax.ShapeDtypeStruct((D,), jnp.float32),
        ],
        interpret=interpret,
        name="bias_act_bwd",
    )(x, bias, g)
    return dx, db.astype(bias.dtype)


fused_bias_act.defvjp(*counted_rules("elementwise_add", _bias_act_fwd, _bias_act_bwd))


# --------------------------------------------------------------------------
# kernel registry (tools/opbench.py --fused, parity matrix tests, docs)
# --------------------------------------------------------------------------


def _ln_example(dtype, rows=256, d=512, residual=True, rng_seed=0):
    rng = np.random.RandomState(rng_seed)
    x = jnp.asarray(rng.randn(rows, d), dtype)
    res = jnp.asarray(rng.randn(rows, d), dtype) if residual else None
    scale = jnp.asarray(rng.rand(d) + 0.5, jnp.float32)
    bias = jnp.asarray(rng.randn(d) * 0.1, jnp.float32)
    return (x, res, scale, bias)


def _ln_reference(x, res, scale, bias, eps=1e-5):
    r = x if res is None else x + res
    rf = r.astype(jnp.float32)
    mean = jnp.mean(rf, axis=-1, keepdims=True)
    var = jnp.var(rf, axis=-1, keepdims=True)
    y = (rf - mean) * jax.lax.rsqrt(var + eps)
    return (y * scale + bias).astype(x.dtype)


def _epilogue_example(dtype, n=8, c=64, hw=196, rng_seed=0):
    rng = np.random.RandomState(rng_seed)
    x = jnp.asarray(rng.randn(n, c, hw), dtype)
    mul = jnp.asarray(rng.rand(c) + 0.5, jnp.float32)
    add = jnp.asarray(rng.randn(c) * 0.1, jnp.float32)
    return (x, mul, add)


def _epilogue_reference(x, mul, add, relu=True):
    shp = (1, -1) + (1,) * (x.ndim - 2)
    y = x.astype(jnp.float32) * mul.reshape(shp) + add.reshape(shp)
    if relu:
        y = jnp.maximum(y, 0.0)
    return y.astype(x.dtype)


def _adam_example(dtype, shape=(512, 256), rng_seed=0):
    rng = np.random.RandomState(rng_seed)
    p = jnp.asarray(rng.randn(*shape), dtype)
    g = jnp.asarray(rng.randn(*shape) * 0.01, dtype)
    m = jnp.asarray(rng.randn(*shape) * 0.001, jnp.float32)
    v = jnp.asarray(rng.rand(*shape) * 1e-4, jnp.float32)
    return (p, g, m, v)


def _adam_reference(p, g, m, v, lr_t=1e-3, beta1=0.9, beta2=0.999, eps=1e-8):
    gf = g.astype(jnp.float32)
    m2 = beta1 * m + (1.0 - beta1) * gf
    v2 = beta2 * v + (1.0 - beta2) * jnp.square(gf)
    p2 = (p.astype(jnp.float32) - lr_t * m2 / (jnp.sqrt(v2) + eps)).astype(p.dtype)
    return p2, m2, v2


def _sxe_example(dtype, rows=256, v=1024, rng_seed=0):
    rng = np.random.RandomState(rng_seed)
    logits = jnp.asarray(rng.randn(rows, v) * 2.0, dtype)
    labels = jnp.asarray(rng.randint(0, v, size=rows), jnp.int32)
    return (logits, labels)


def _sxe_reference(logits, labels, ignore_index=-100):
    """The composite lowering's fused-logsumexp formulation (nn_ops.py)."""
    m = jax.lax.stop_gradient(jnp.max(logits, axis=-1, keepdims=True))
    shifted = (logits - m).astype(jnp.float32)
    lse = (jnp.log(jnp.sum(jnp.exp(shifted), axis=-1, keepdims=True))
           + m.astype(jnp.float32))
    iota = jax.lax.broadcasted_iota(jnp.int32, logits.shape, logits.ndim - 1)
    onehot = iota == labels[:, None]
    picked = jnp.sum(jnp.where(onehot, logits, 0).astype(jnp.float32),
                     axis=-1, keepdims=True)
    loss = lse - picked
    return jnp.where(labels[:, None] == ignore_index, 0.0, loss)


def _bias_act_example(dtype, rows=512, d=1024, rng_seed=0):
    rng = np.random.RandomState(rng_seed)
    x = jnp.asarray(rng.randn(rows, d), dtype)
    b = jnp.asarray(rng.randn(d) * 0.1, jnp.float32)
    return (x, b)


def _bias_act_reference(x, b, act="gelu"):
    z = x.astype(jnp.float32) + b.astype(jnp.float32)
    if act == "relu":
        y = jnp.maximum(z, 0.0)
    else:
        y = jax.nn.gelu(z, approximate=False)
    return y.astype(x.dtype)


def _nbytes(a):
    return int(a.size) * int(a.dtype.itemsize)


def _ln_analytic(args):
    x, res, scale, bias = args
    streams = _nbytes(x) * (3 if res is not None else 2)
    return 10.0 * x.size, float(streams + _nbytes(scale) + _nbytes(bias))


def _epilogue_analytic(args):
    x, mul, add = args
    return 3.0 * x.size, float(2 * _nbytes(x) + _nbytes(mul) + _nbytes(add))


def _adam_analytic(args):
    p, g, m, v = args
    io = 2 * (_nbytes(p) + _nbytes(m) + _nbytes(v)) + _nbytes(g)
    return 10.0 * p.size, float(io)


def _sxe_analytic(args):
    logits, labels = args
    return (8.0 * logits.size,
            float(_nbytes(logits) + _nbytes(labels) + logits.shape[0] * 4))


def _bias_act_analytic(args):
    x, b = args
    return 9.0 * x.size, float(2 * _nbytes(x) + _nbytes(b))


# name -> {fused, reference, example, tol}: `fused`/`reference` take the
# example tuple; tolerances are per-dtype (bf16 carries its 8-bit mantissa).
# `analytic` maps the example args to (flops, hbm_bytes) from the same cost
# model the planner prices the op with — tools/opbench.py --fused divides
# the implied roofline time by the measured time (roofline_frac column) so
# A/B wins are stated in the units the MFU floors ratchet in.
FUSED_KERNELS: Dict[str, dict] = {
    "ln_residual": {
        "fused": lambda args, interpret=False: fused_ln_residual(
            args[0], args[1], args[2], args[3], 1e-5, interpret),
        "reference": lambda args: _ln_reference(*args),
        "example": _ln_example,
        "tol": {"float32": 2e-5, "bfloat16": 5e-2},
        "grad_argnums": (0, 1, 2, 3),
        "analytic": _ln_analytic,
    },
    "bn_scale_shift_relu": {
        "fused": lambda args, interpret=False: bn_epilogue(
            args[0], args[1], args[2], True, interpret),
        "reference": lambda args: _epilogue_reference(*args, relu=True),
        "example": _epilogue_example,
        "tol": {"float32": 2e-5, "bfloat16": 2e-2},
        "grad_argnums": (0, 1, 2),
        "analytic": _epilogue_analytic,
    },
    "adam_slab": {
        "fused": lambda args, interpret=False: fused_adam(
            args[0], args[1], args[2], args[3], 1e-3, 0.9, 0.999, 1e-8,
            interpret),
        "reference": lambda args: _adam_reference(*args),
        "example": _adam_example,
        "tol": {"float32": 2e-6, "bfloat16": 1e-2},
        "grad_argnums": (),  # state update, not a differentiable layer
        "analytic": _adam_analytic,
    },
    "softmax_xent": {
        "fused": lambda args, interpret=False: fused_softmax_xent(
            args[0], args[1], -100, interpret),
        "reference": lambda args: _sxe_reference(*args),
        "example": _sxe_example,
        "tol": {"float32": 2e-5, "bfloat16": 5e-2},
        "grad_argnums": (0,),  # labels are integral
        "analytic": _sxe_analytic,
    },
    "bias_act": {
        "fused": lambda args, interpret=False: fused_bias_act(
            args[0], args[1], "gelu", interpret),
        "reference": lambda args: _bias_act_reference(*args, act="gelu"),
        "example": _bias_act_example,
        "tol": {"float32": 2e-5, "bfloat16": 5e-2},
        "grad_argnums": (0, 1),
        "analytic": _bias_act_analytic,
    },
}


def registered_fused_kernels():
    return sorted(FUSED_KERNELS)
