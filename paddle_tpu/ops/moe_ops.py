"""The parts of a modern decoder block that no 2019 op computes: RMSNorm,
rotary positions, a layer of routed experts (a router and the experts), the
gated short convolution that stands where attention does in most layers
of a convolution-attention hybrid, and the loss of a model with an exit after
every pass of a looped stack.

No reference counterpart: the reference predates all four.  The equations
are those of OLMoE-1B-7B (Muennighoff et al. 2024, arXiv:2409.02060), which
are also Mixtral's and DeepSeek-MoE's but for the shared expert; the sigmoid
router whose bias picks and does not weigh is DeepSeek-V3's and LFM2's, the
short convolution LFM2's:

    rms_norm          y = x / sqrt(mean(x^2) + eps) * g
    rotary_embedding  y = x cos(t) + rotate_half(x) sin(t),  t = pos * theta^(-2i/dh)  (or pairs (2i, 2i+1))
    moe_router        p = softmax_f32(x Wr); (p_e, e) = top_k(p); two auxiliary losses
                      or s = sigmoid_f32(x Wr); e = top_k(s + b); p_e = s_e (the unbiased score)
    moe_experts       y = sum_{e in top_k} p_e . Wdown_e( silu(Wgate_e x) * (Wup_e x) )
                      or, `gated=False, activation="relu2"`:  p_e . Wdown_e( relu(Wup_e x)^2 ), two matrices an expert
    short_conv        y = C * conv_K(B * u),  [B, C, u] = split3(x),  conv_K causal and depthwise
    exit_loss         p_t = sigmoid(g_t) prod_{j<t} (1 - sigmoid(g_j)),  p_T the rest;
                      loss = mean( sum_t p_t CE_t - beta H(p) )           (Ouro's stage-one objective)

Backward comes from `jax.vjp` over these lowerings like every other op's
(core/lowering.py).  `moe_experts` makes no pass over a [rows, hidden],
[rows, width] or [experts, ., .] array outside its grouped kernels that the
mathematics does not need (PERF.md, PR 28).  Two row operations exist,
`_rows_by_expert` (tokens to expert order) and `_sum_by_token` (back, summing
each token's k rows), written as each other's transposes: the derived
transpose of a row gather is a scatter-add, and here the permutation's inverse
is known, so the transpose is the other gather (TPU v5e, one OLMoE layer's
experts forward and backward: 66.2 ms against 74.7 derived; PERF.md, PR 26).
`_rows_by_expert` is XLA's gather, which promises its indices (no fill value
is selected over its result) and reads and writes its 604 MB at the HBM's speed
(0.8 ms): nothing for a kernel to win.  `_sum_by_token` is a Pallas kernel on
one TPU device (`ops/moe_kernels.py`, `_token_sum_path`): as XLA's gather and
sum it wrote [tokens, k, hidden] at a third of the HBM's speed and read it
again, 5.4 ms a call, where the kernel brings a block of tokens' rows into VMEM
run by run and sums them there, 1.56 ms (PERF.md, PR 49); XLA's form stays the
CPU's and the odd shapes' path, and what the tests hold the kernel to.  Under a
mesh whose batch axis splits the rows and nothing else the whole of `moe_experts`
(the sort, the grouped products, the way back) runs on a chip's own rows inside
`over_batch_shards`, the matrices handed in whole (ZeRO-3's gather): a
`pallas_call` GSPMD cannot partition would run ALL rows on every chip.
The router's weights multiply the hidden rows, in expert order, so nothing
else passes over a [rows, hidden] array, forward or backward.  The matrices
are float32 masters: `grouped_matmul` casts each once for the forward and
hands back `tgmm`'s float32 accumulator as its gradient.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name

from ..core import analysis as _A
from ..core import resource_plan as _RP
from ..core.registry import register_op, set_kept, set_step_stats
from ..monitor import MONITOR as _MON
from . import moe_kernels
from .common import (batch_shards, counted_rules, first, kept_residuals, match_dtype, over_batch_shards, residuals_name,
                     rotary_angles)


@register_op("rms_norm")
def _rms_norm(ctx, op, ins):
    """Statistics in float32 whatever the activation's dtype, as
    `layer_norm` keeps them; the gain multiplies in the activation's dtype."""
    x = first(ins, "X")
    scale = first(ins, "Scale")
    begin = op.attr("begin_norm_axis", 1)
    axes = tuple(range(begin, x.ndim))
    xf = x.astype(jnp.float32)
    y = (xf * jax.lax.rsqrt(jnp.mean(jnp.square(xf), axis=axes, keepdims=True)
                            + op.attr("epsilon", 1e-5))).astype(x.dtype)
    if scale is not None:
        y = y * match_dtype(y, scale).reshape((1,) * begin + tuple(x.shape[begin:]))
    return {"Y": y}


@register_op("rotary_embedding")
def _rotary_embedding(ctx, op, ins):
    """Rotary positions over (B, H, L, dh), or with the attribute
    `layout="blhd"` over (B, L, H, dh) as a projection's reshape leaves the
    heads (the latent attention's layout: H is 1 for the key part its heads
    share); `Positions` is (B, L) integers, an input so that packed or offset
    sequences bring their own.  Feature i turns with feature i + dh/2
    (rotate-half), or with the attribute `interleave` feature 2i with 2i + 1
    (the pairing of the original rotary embedding, which DeepSeek-V3's family
    keeps).  With `rotary_dim` = r the leading r features turn among
    themselves and the rest pass, bit for bit; `inv_freq` (r/2 numbers) are the
    frequencies in place of theta's own; `scale` multiplies cos and sin.
    Angles, sines and the rotation are float32 whatever X's dtype: at
    position 16383 a bf16 angle is off by whole turns."""
    x = first(ins, "X")
    pos = first(ins, "Positions")
    turned, passed = op.attr("rotary_dim", x.shape[-1]), None
    if turned != x.shape[-1]:
        x, passed = x[..., :turned], x[..., turned:]
    half = x.shape[-1] // 2
    by_position = op.attr("layout", "bhld") == "blhd"
    if by_position:
        _MON.counter("lowering.latent_rotary_ops").inc()
    cos, sin = rotary_angles(pos, half, op.attr("theta", 10000.0), by_position,   # dh/2 angles a position
                             op.attr("inv_freq", None), op.attr("scale", 1.0))
    if op.attr("interleave", False):
        # A pair's other member, signed, (-x[2i+1], x[2i]), as a product with a constant matrix of 0 and +-1 (exact in
        # any dtype: one term a sum) and not as strided slices: those leave arrays whose last axis is 2, which the chip
        # tiles to (8, 128) and copies (the 8-row clone compiled for the described v5e: PERF.md, section 6, PR 54).
        swap = np.zeros((2 * half, 2 * half), np.float32)
        swap[np.arange(1, 2 * half, 2), np.arange(0, 2 * half, 2)] = -1.0
        swap[np.arange(0, 2 * half, 2), np.arange(1, 2 * half, 2)] = 1.0
        other = jnp.einsum("...d,de->...e", x, swap.astype(x.dtype), precision=jax.lax.Precision.HIGHEST,
                           preferred_element_type=jnp.float32)
        out = x.astype(jnp.float32) * jnp.repeat(cos, 2, axis=-1) + other * jnp.repeat(sin, 2, axis=-1)
    else:
        x1, x2 = x[..., :half].astype(jnp.float32), x[..., half:].astype(jnp.float32)
        out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    out = out.astype(x.dtype)
    return {"Out": out if passed is None else jnp.concatenate([out, passed], axis=-1)}


def _router_logits_name(op):
    """What a recomputed segment calls a router's float32 logits (`registry.set_kept`)."""
    return op.output("TopKProb")[0] + "@logits"


@register_op("moe_router")
def _moe_router(ctx, op, ins):
    """Logits, scores, their top-k and both auxiliary losses in float32: a
    routing decision made on rounded scores is another decision.  `Load` is
    the number of (token, slot) assignments each expert received;
    `moe_experts` takes it as its group sizes.

    `scoring` "softmax" (the default): the scores are the softmax over the
    experts.  "sigmoid": each expert's own sigmoid.  An input `Bias`
    [experts] is added to the scores FOR THE CHOICE ONLY: the chosen experts'
    weights are their unbiased scores, and `BiasMoved` counts the (token,
    slot) choices that the unbiased top-k would not have made.  With
    `norm_topk_prob` the weights are divided by (their sum + `norm_eps`);
    `routed_scaling_factor` multiplies them.

    LoadBalanceLoss = E . sum_e f_e P_e with f_e the expert's share of the
    T . k assignments (no gradient) and P_e its mean share of the token's
    scores (1 when both are uniform); ZLoss = mean_t logsumexp(logits_t)^2."""
    x = first(ins, "X")
    w = first(ins, "W")
    bias = first(ins, "Bias")
    k = op.attr("top_k")
    n_experts = w.shape[-1]
    x2 = x.reshape(-1, x.shape[-1]).astype(jnp.float32)
    logits = jnp.dot(x2, w.astype(jnp.float32), precision=jax.lax.Precision.HIGHEST)
    if ctx.keep and _router_logits_name(op) in ctx.keep:   # a recomputed segment keeps them: a float32 product at six passes
        logits = checkpoint_name(logits, _router_logits_name(op))
    lse = jax.nn.logsumexp(logits, axis=-1)
    if op.attr("scoring", "softmax") == "sigmoid":
        _MON.counter("lowering.moe_router_sigmoid").inc()
        scores = jax.nn.sigmoid(logits)
        probs = scores / jnp.sum(scores, axis=-1, keepdims=True)   # the balance loss's shares
    else:
        scores = probs = jnp.exp(logits - lse[:, None])
    outs = {}
    if bias is None:
        top_p, top_i = jax.lax.top_k(scores, k)
    else:
        _, top_i = jax.lax.top_k(scores + bias.astype(jnp.float32), k)
        top_p = jnp.take_along_axis(scores, top_i, axis=-1)
        _, unbiased = jax.lax.top_k(scores, k)
        kept = jnp.any(top_i[:, :, None] == unbiased[:, None, :], axis=-1)
        outs["BiasMoved"] = jnp.sum(~kept, dtype=jnp.int32).reshape((1,))
    # an epsilon of 0 and a factor of 1 add no operation: the 2024 router's lowered text is what it was
    if op.attr("norm_topk_prob", False):
        total = jnp.sum(top_p, axis=-1, keepdims=True)
        eps = op.attr("norm_eps", 0.0)
        top_p = top_p / (total + eps if eps else total)
    scaling = op.attr("routed_scaling_factor", 1.0)
    if scaling != 1.0:
        top_p = top_p * scaling
    load = jnp.sum(top_i[:, :, None] == jnp.arange(n_experts, dtype=top_i.dtype),
                   axis=(0, 1), dtype=jnp.int32)
    share = load.astype(jnp.float32) / float(top_i.size)
    lead = x.shape[:-1]
    return {
        "TopKProb": top_p.reshape(lead + (k,)),
        "TopKIndex": top_i.astype(jnp.int32).reshape(lead + (k,)),
        "Load": load,
        "LoadBalanceLoss": (n_experts * jnp.sum(share * jnp.mean(probs, axis=0))).reshape((1,)),
        "ZLoss": jnp.mean(jnp.square(lse)).reshape((1,)),
        **outs,
    }


def _shift_rows(t, back):
    """t[:, s - back] over [b, T, .]: later by `back` positions with zeros
    before the sequence's start, or for a negative `back` earlier, with zeros
    past its end.  One `pad` of the operand itself, so that XLA reads the
    shifted rows where it uses them (a pad of a computed product is
    materialised, in float32: three passes more a tap)."""
    if back == 0:
        return t
    return jax.lax.pad(t, jnp.zeros((), t.dtype), ((0, 0, 0), (back, -back, 0), (0, 0, 0)))


def _short_conv_taps(x, w):
    """(c = conv_K(B * u) in float32, each tap's shifted B * u) of the
    in-projection x [b, T, 3d] = [B, C, u] and the filter w [d, K]: tap j
    meets position t - (K - 1) + j."""
    d, taps = w.shape
    shifted = []
    for j in range(taps):
        rows = _shift_rows(x, taps - 1 - j)
        shifted.append(rows[..., :d].astype(jnp.float32) * rows[..., 2 * d:].astype(jnp.float32))
    return sum(z * w[:, j].astype(jnp.float32) for j, z in enumerate(shifted)), shifted


@jax.custom_vjp
def _gated_short_conv(x, w):
    """C * conv_K(B * u), computed in float32 from x's dtype and rounded once."""
    d = w.shape[0]
    with jax.named_scope("gated_short_conv"):
        return (x[..., d:2 * d].astype(jnp.float32) * _short_conv_taps(x, w)[0]).astype(x.dtype)


def _gated_short_conv_bwd(res, g):
    """The transpose written on shifted reads of x and g, as the forward is:
    dc = g C; dz[s] = sum_j w_j dc[s + (K - 1) - j]; dB = dz u, du = dz B,
    dC = g c; dw_j = sum dc . (B u)[. - (K - 1) + j].  Keeps x and the filter
    only and makes the taps again."""
    x, w = res
    d, taps = w.shape
    with jax.named_scope("gated_short_conv"):
        c, shifted = _short_conv_taps(x, w)
        gf = g.astype(jnp.float32)
        dc = gf * x[..., d:2 * d].astype(jnp.float32)
        d_w = jnp.stack([jnp.sum(dc * z, axis=(0, 1)) for z in shifted], axis=1).astype(w.dtype)
        dz = sum(_shift_rows(g, j - (taps - 1)).astype(jnp.float32)
                 * _shift_rows(x, j - (taps - 1))[..., d:2 * d].astype(jnp.float32)
                 * w[:, j].astype(jnp.float32) for j in range(taps))
        d_x = jnp.concatenate([(dz * x[..., 2 * d:].astype(jnp.float32)).astype(x.dtype),
                               (gf * c).astype(x.dtype),
                               (dz * x[..., :d].astype(jnp.float32)).astype(x.dtype)], axis=-1)
    return d_x, d_w


_gated_short_conv.defvjp(*counted_rules("short_conv", lambda x, w: (_gated_short_conv(x, w), (x, w)), _gated_short_conv_bwd))


def _plain_short_conv_taps(x, w, ahead=0, bias=None):
    """(c = conv_K(x) in float32, each tap's shifted x) of x [b, T, d] and the
    filter w [d, K], as `_short_conv_taps` has them for B * u; `ahead`: c of the
    row that many after each, zeros past the sequence's end; `bias` [d] is
    added to c where there is one."""
    taps = w.shape[1]
    shifted = [_shift_rows(x, taps - 1 - j - ahead).astype(jnp.float32) for j in range(taps)]
    c = sum(z * w[:, j].astype(jnp.float32) for j, z in enumerate(shifted))
    return (c if bias is None else c + bias.astype(jnp.float32)), shifted


def _silu_slope(c):
    s = jax.nn.sigmoid(c)
    return s * (1.0 + c * (1.0 - s))


@jax.custom_vjp
def _plain_short_conv(x, w, bias=None):
    """silu(conv_K(x) + bias), computed in float32 from x's dtype and rounded once."""
    with jax.named_scope("plain_short_conv"):
        return jax.nn.silu(_plain_short_conv_taps(x, w, bias=bias)[0]).astype(x.dtype)


def _plain_short_conv_bwd(res, g):
    """The transpose on shifted reads of x and g, in the gated form's shape:
    dc = g silu'(c); dw_j = sum dc . x[. - (K - 1) + j]; dx[s] = sum_j w_j
    dc[s + (K - 1) - j], where dc at a later row is made again from the rows of
    x it reads, so that every `pad` is of an operand.  Keeps x and the filter."""
    x, w, bias = res
    taps = w.shape[1]
    with jax.named_scope("plain_short_conv"):
        c, shifted = _plain_short_conv_taps(x, w, bias=bias)
        dc = g.astype(jnp.float32) * _silu_slope(c)
        d_w = jnp.stack([jnp.sum(dc * z, axis=(0, 1)) for z in shifted], axis=1).astype(w.dtype)

        def dc_ahead(ahead):   # dc[s + ahead], zero past the sequence's end (where g is zero, whatever the bias)
            return (_shift_rows(g, -ahead).astype(jnp.float32)
                    * _silu_slope(_plain_short_conv_taps(x, w, ahead, bias)[0]))

        d_x = sum((dc if j == taps - 1 else dc_ahead(taps - 1 - j)) * w[:, j].astype(jnp.float32)
                  for j in range(taps)).astype(x.dtype)
    return d_x, d_w, None if bias is None else jnp.sum(dc, axis=(0, 1)).astype(bias.dtype)


_plain_short_conv.defvjp(*counted_rules(
    "short_conv", lambda x, w, bias=None: (_plain_short_conv(x, w, bias), (x, w, bias)), _plain_short_conv_bwd))


@register_op("short_conv")
def _short_conv(ctx, op, ins):
    """The gated short convolution between its two projections (which are
    `mul` ops of the program): X [b, T, 3d] is the in-projection, split into
    B, C and u; Out = C * conv_K(B * u) with one causal filter of K taps a
    channel (`Filter` [d, K]; the last tap meets the current position), zeros
    before the sequence's start.  Plain jax.numpy: K shifted multiply-adds
    that XLA fuses into one pass forward, and a backward pass written the same
    way (`_gated_short_conv_bwd`: the derived one pads computed products and
    reads 2.3x the bytes; PERF.md, PR 34).  With the attributes `gated=False,
    activation="silu"` X is [b, T, d] and Out = silu(conv_K(X)): the taps alone,
    plus the optional input `Bias` [d] before the SiLU where the op has one."""
    if not op.attr("gated", True):
        # the plain mode (attributes gated=False, activation="silu"): X [b, T, d] itself passes the taps and a
        # SiLU, as a linear-attention layer's q, k and v do; `_shift_rows` and the transpose's shape are shared
        _MON.counter("lowering.short_conv_plain_layers").inc()
        bias = first(ins, "Bias")   # absent in every program that stood before the state-space mixer's
        return {"Out": _plain_short_conv(first(ins, "X"), first(ins, "Filter"), *(() if bias is None else (bias,)))}
    _MON.counter("lowering.short_conv_layers").inc()
    return {"Out": _gated_short_conv(first(ins, "X"), first(ins, "Filter"))}


def _take_rows(x, index):
    """x[index] along axis 0 for an `index` that is in bounds by
    construction (an argsort's permutation, or one integer-divided): said
    to the gather, so that it has no fill value to select in a second pass
    over its result (PERF.md, PR 28)."""
    return x.at[index].get(mode="promise_in_bounds")


# The op's two row operations, each the other's transpose.  `route` = (order,
# inverse, expert): `order` is the stable permutation that sorts the tokens x k
# (token, slot) assignments by expert, `inverse` its inverse, `expert` [T, k]
# each assignment's expert; row i of the expert-ordered side is assignment
# order[i], of token order[i] // k.  `kernel`: None for the `jax.numpy` form of
# the way back, else `_token_sum_path`'s (experts, interpreted).

def _token_sum_path(platform, mesh, x, k, experts, on_own_rows=False):
    """How `_sum_by_token` is lowered for tokens `x` [T, d] of k rows each:
    "kernel" (`ops/moe_kernels.py`: a block of tokens' rows brought into VMEM
    run by run and summed there by a 0/1 product) on the TPU, where a chip has
    its rows to itself (a `pallas_call` cannot be partitioned: `nn_ops.
    _attention_path`'s rule): on one device, or `on_own_rows`, inside the
    `shard_map` that `moe_experts` opens under a mesh which splits the rows and
    nothing else (`x` is then the chip's own tokens); and where
    `moe_kernels.fits`: a row is whole lane tiles, the tokens whole blocks, bf16
    or float32, and the two buffers fit; else "xla", the gather and the sum
    below: the CPU's path, any other mesh's (GSPMD partitions it by itself), the
    odd shapes', and what the tests hold the kernel to.  TPU v5e, (131072, 2048)
    bf16 rows, k = 8: PERF.md, PR 49."""
    to_itself = mesh is None or mesh.size == 1 or on_own_rows
    return "kernel" if platform == "tpu" and to_itself and moe_kernels.fits(*x.shape, k, x.dtype, experts) else "xla"


def _token_sum_kernel(ctx, x, k, groups, on_own_rows=False):
    """`_sum_by_token`'s and `_add_to_tokens`' `kernel` for tokens `x` of k slots
    each over `groups` groups (a layer's experts, or the ones it holds): None
    where `_token_sum_path` says XLA's form, else (groups, interpreted).
    "interpret" is the tests': the kernel interpreted where no chip is."""
    path = _token_sum_path(ctx.platform, ctx.mesh, x, k, groups, on_own_rows)
    return {"kernel": (groups, False), "interpret": (groups, True)}.get(path)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def _rows_by_expert(x, route, k, kernel=None):
    """Tokens [T, d] -> one row per assignment [T k, d], in `order`."""
    return _take_rows(x, route[0] // k)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def _sum_by_token(rows, route, k, kernel=None):
    """Rows [T k, d] in `order` -> tokens [T, d]: each token's k rows,
    summed in float32."""
    _, inverse, expert = route
    if kernel:
        _MON.counter("lowering.token_sum_kernel_calls").inc()
        return moe_kernels.token_sum(rows, inverse.reshape(-1, k), expert.reshape(-1, k), *kernel)
    rows = _take_rows(rows, inverse).reshape(-1, k, rows.shape[-1])
    return jnp.sum(rows, axis=1, dtype=jnp.float32).astype(rows.dtype)


_rows_by_expert.defvjp(*counted_rules(
    "moe_experts",
    lambda x, route, k, kernel=None: (_rows_by_expert(x, route, k, kernel), route),
    lambda k, kernel, route, g: (_sum_by_token(g, route, k, kernel), None)))
_sum_by_token.defvjp(*counted_rules(
    "moe_experts",
    lambda rows, route, k, kernel=None: (_sum_by_token(rows, route, k, kernel), route),
    lambda k, kernel, route, g: (_rows_by_expert(g, route, k, kernel), None)))


@jax.custom_vjp
def _permute_scalars(v, perm, inverse):
    """v[perm] for a vector `v` and a permutation `perm` whose inverse is
    `inverse`, as a sort of the pairs (inverse[j], v[j]) by their key: a
    gather of scalars runs element by element on the chip (1.07 ms for
    131072 floats against 0.09 for the sort; PERF.md, PR 28).  The
    transpose is the same with the two permutations exchanged."""
    return jax.lax.sort((inverse, v), num_keys=1, is_stable=False)[1]


_permute_scalars.defvjp(*counted_rules(
    "moe_experts",
    lambda v, perm, inverse: (_permute_scalars(v, perm, inverse), (perm, inverse)),
    lambda res, g: (_permute_scalars(g, res[1], res[0]), None, None)))

#: (rows, contraction, columns) tile of the megablox kernels.  TPU v5e, the
#: three products of one OLMoE layer over 131072 rows, forward and backward
#: (PERF.md, PR 26): 66.2 ms with this tile; (256, 1024, 1024) 67.7,
#: (512, 1024, 512) 69.7, (512, 512, 1024) 71.4, (512, 512, 512) 78.4,
#: (1024, 512, 512) 79.2, the kernel's default (128, 128, 128) 531.9;
#: (1024, 1024, 1024) and (512, 2048, 1024) do not fit the scoped VMEM.
_GMM_TILE = (512, 1024, 1024)
#: `tgmm`'s, whose output block is float32 for a float32 master: two such
#: blocks and the accumulator are 16 MB at `_GMM_TILE`, over the scoped VMEM.
#: The same layer with float32 gradients (PERF.md, PR 28): 57.6 ms with this
#: tile against 60.5 for bf16 gradients widened afterwards; (256, 2048, 512)
#: 58.2, (512, 1024, 512) and (512, 512, 1024) 59.1, (1024, 1024, 512) and
#: (1024, 512, 1024) 60.3, (1024, 512, 512) 62.4, (2048, 512, 512) 65.9.
_TGMM_TILE = (256, 1024, 1024)


def _tile(tile, m, k, n):
    """`tile` cut to a product of [m, k] by [k, n] over padded rows: fewer
    than a row tile of them are one tile."""
    return (tile[0] if m % tile[0] == 0 else m, min(tile[1], k), min(tile[2], n))


def _pad_rows(rows):
    """Rows padded with zeros, which belong to no group, to a multiple of
    the kernels' row tile."""
    m = rows.shape[0]
    tm = _GMM_TILE[0] if m >= _GMM_TILE[0] else 128
    return jnp.pad(rows, ((0, -m % tm), (0, 0)))


def _megablox():
    """The stock kernels `gmm` and `tgmm` themselves: the package's VJP over
    them hands back the matrices' gradient in the rows' dtype."""
    from jax.experimental.pallas.ops.tpu.megablox import ops

    return ops.backend


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _grouped_matmul(rows, master, group_sizes, interpret):
    return _grouped_matmul_fwd(rows, master, group_sizes, interpret)[0]


def _grouped_matmul_fwd(rows, master, group_sizes, interpret):
    # the one copy of the matrices the forward needs, outside the products' scope
    weights = match_dtype(rows, master)
    m, k = rows.shape
    with jax.named_scope("expert_gemm"):
        rows = _pad_rows(rows)
        out = _megablox().gmm(rows, weights, group_sizes, rows.dtype,
                              _tile(_GMM_TILE, rows.shape[0], k, weights.shape[2]), interpret=interpret)[:m]
    return out, (rows, weights, group_sizes, master)  # the master for its dtype


def _grouped_matmul_bwd(interpret, res, g):
    """The rows' gradient is `gmm` over the transposed matrices; the
    matrices' is `tgmm`'s float32 accumulator, written in the MASTER's dtype:
    for a float32 master never rounded to the rows' dtype and widened again
    (three passes over 805 MB each in an OLMoE layer; PERF.md, PR 28)."""
    rows, weights, group_sizes, master = res
    m, (padded, k), n = g.shape[0], rows.shape, weights.shape[2]
    with jax.named_scope("expert_gemm"):
        g = _pad_rows(g)
        d_rows = _megablox().gmm(g, weights, group_sizes, rows.dtype, _tile(_GMM_TILE, padded, k, n),
                                 transpose_rhs=True, interpret=interpret)[:m]
        d_master = _megablox().tgmm(rows.swapaxes(0, 1), g, group_sizes, master.dtype,
                                    _tile(_TGMM_TILE, padded, k, n), interpret=interpret)
    return d_rows, d_master, None


_grouped_matmul.defvjp(*counted_rules("moe_experts", _grouped_matmul_fwd, _grouped_matmul_bwd))


def grouped_matmul(rows, weights, group_sizes, platform=None):
    """rows [M, K] sorted by group, weights [G, K, N], group_sizes [G]
    summing to M -> [M, N]: row i is multiplied by the matrix of its own
    group.  The matrices are multiplied in the rows' dtype (a float32 master
    of bf16 rows is cast once); accumulation is float32, and the matrices'
    gradient leaves it in THEIR dtype.

    The stock Pallas grouped-matmul kernels
    (jax.experimental.pallas.ops.tpu.megablox: forward `gmm`; backward `gmm`
    with the matrices transposed for the rows' gradient and `tgmm` for the
    matrices'), compiled on a TPU and interpreted elsewhere (CPU tests,
    virtual meshes), so what the tests check is what the chip runs.  On the
    chip `jax.lax.ragged_dot` read 101.9 ms against 66.2 for the layer above,
    and applying every expert to every token (8x the arithmetic) 82.1 ms
    forward alone, its backward 30 GB (PERF.md, PR 26)."""
    return _grouped_matmul(rows, weights, group_sizes, platform != "tpu")


def _activated(products, weight, activation):
    """An expert's hidden rows in float32 from its first products' outputs: the
    activation of the first (`"silu"`, `"relu"`, or `"relu2"`: relu(.)^2), times
    the second where the expert is gated, times the row's router weight."""
    def wide(t):   # float32, each where it is first read
        return t if t.dtype == jnp.float32 else t.astype(jnp.float32)

    opened, *gated_by = products
    hidden = jax.nn.silu(wide(opened)) if activation == "silu" else jax.nn.relu(wide(opened))
    if activation == "relu2":
        hidden = jnp.square(hidden)
    for other in gated_by:
        hidden = hidden * wide(other)
    return hidden * weight


@register_op("moe_experts")
def _moe_experts(ctx, op, ins):
    """Every (token, slot) assignment is a row: the rows are sorted by
    expert and multiplied group by group (`grouped_matmul`: 1/8 of the
    arithmetic of applying all 64 experts to every token); the hidden rows
    are weighted by their router probability (sum_k p_k Wdown(h_k) =
    sum_k Wdown(p_k h_k): float32 into the one rounding of `hidden`), and
    the down product's rows are summed back per token in float32.  No
    capacity, so no dropped token, however skewed the router: `Dropped` is
    the number of rows the group sizes do not cover, 0 by construction.

    The experts' form is two attributes: `gated` (the default: inputs WGate,
    WUp, WDown, hidden = act(gate) * up) or not (WUp and WDown alone, hidden =
    act(up)), and `activation`, "silu" (the default), "relu" or "relu2",
    relu(.)^2.

    With the attribute `held` = (first, count) the matrices are those
    of `count` experts from `first` on, and what the absent experts would
    have added is left out (`_held_experts`): `Held` is then the number of
    assignments that fell on held experts, `Dropped` those of them no pass
    covered, 0 by construction.  Without it, every expert: today's layer.

    Under a mesh whose batch axis splits the rows and nothing else
    (`batch_shards`) all of this runs on a chip's own rows inside
    `over_batch_shards`: the group sizes are the chip's own counts, the matrices
    are handed in whole, and `Held` and `Dropped` are summed over the axis."""
    x = first(ins, "X")
    top_p = first(ins, "TopKProb")
    top_i = first(ins, "TopKIndex")
    load = first(ins, "Load")
    gated, activation = op.attr("gated", True), op.attr("activation", "silu")
    matrices = tuple(first(ins, s) for s in (("WGate",) if gated else ()) + ("WUp", "WDown"))
    d, k = x.shape[-1], top_i.shape[-1]
    held = op.attr("held", None)
    if op.attr("shared_experts", 0):   # the layer's builder computes them beside this op, every token, once
        _MON.counter("lowering.shared_expert_layers").inc()
    keep = kept_residuals(ctx, op)   # the name under which a recomputed segment keeps the first products' outputs
    shards = batch_shards(ctx.mesh, ctx.batch_axis, x.shape[0])
    own_rows = shards > 1

    def experts(x, top_p, top_i, load, *matrices):
        """(out, the assignments no pass covered, those that fell on held experts) of a chip's rows."""
        x2 = x.reshape(-1, d)
        tokens = x2.shape[0]
        if own_rows:   # the group sizes are this chip's own counts, not the mesh's
            load = jnp.sum(top_i.reshape(-1, k)[:, :, None] == jnp.arange(load.shape[0], dtype=top_i.dtype),
                           axis=(0, 1), dtype=jnp.int32)
        if held is not None:
            out, n_held, missed = _held_experts(x2, top_p.reshape(-1, k), top_i.reshape(-1, k), load, matrices,
                                                tuple(held), ctx.platform,
                                                _token_sum_kernel(ctx, x2, k, held[1], own_rows), keep, activation)
            return out.reshape(x.shape), missed.astype(jnp.int32).reshape((1,)), n_held.astype(jnp.int32).reshape((1,))
        *openers, w_down = matrices
        order = jnp.argsort(top_i.reshape(-1), stable=True).astype(jnp.int32)
        inverse = jnp.argsort(order).astype(jnp.int32)
        route = (order, inverse, top_i.reshape(-1, k).astype(jnp.int32))
        kernel = _token_sum_kernel(ctx, x2, k, load.shape[0], own_rows)
        rows = _rows_by_expert(x2, route, k, kernel)
        # each row's router probability, in expert order
        weight = _permute_scalars(top_p.reshape(-1).astype(jnp.float32), order, inverse)[:, None]
        products = [grouped_matmul(rows, w, load, ctx.platform) for w in openers]
        if keep:
            products = [checkpoint_name(t, keep) for t in products]
        hidden = _activated(products, weight, activation).astype(x.dtype)
        down = grouped_matmul(hidden, w_down, load, ctx.platform)
        out = _sum_by_token(down, route, k, kernel)
        return out.reshape(x.shape), (tokens * k - jnp.sum(load)).astype(jnp.int32).reshape((1,)), None

    if own_rows:
        _MON.counter("lowering.moe_experts_under_shard_map").inc()

        def on_a_chip(*operands):   # the two counts whole over the mesh, a row a row of x so that every output is split alike
            out, *counts = experts(*operands)
            return (out,) + tuple(jnp.broadcast_to(jax.lax.psum(n, ctx.batch_axis), (out.shape[0], 1))
                                  for n in counts if n is not None)

        out, *counts = over_batch_shards(ctx, on_a_chip, (x, top_p, top_i), (load,) + matrices)
        counts = [n[0] for n in counts]
    else:
        out, *counts = experts(x, top_p, top_i, load, *matrices)
    return {"Out": out, "Dropped": counts[0], **({"Held": counts[1]} if held is not None else {})}


# -- a layer that holds a share of its experts ---------------------------------
#
# `held = (first, count)`: the three stacked matrices are those of experts
# first .. first + count - 1 only (one chip's share of a layer split over
# several); the router still decides over all its outputs.  Assignments to
# absent experts are never rows: the (token, slot) assignments are sorted by
# LOCAL expert with the absent ones last, and the row operations and grouped
# products pass over the first `_held_rows_bound` of them, twice the share a
# uniform router gives this chip.  What lies past the bound is a second, rarer
# lowering in the same program (`jax.lax.cond`): the same chunk, checkpointed,
# scanned over the rest of the order `_HELD_REST_ROWS` at a time, so that no
# assignment to a held expert is ever left out however skewed the router, and
# the common step neither runs nor keeps anything of it.  The rest goes in
# small passes because the step pays for a branch it never takes: XLA plans a
# branch's temporaries into every step's heap, and `cost_analysis()` counts a
# conditional's dearer branch (PERF.md, PR 34: at one sequence of LFM2's cell
# the never-run branch is 50 of the 166 GB counted a step with a bound's rows a
# pass, 27 with 2048).

#: The bound as a multiple of the uniform share.  With weights N(0, 0.02) the
#: masked positions of a block-diffusion batch (a quarter of all positions)
#: carry one embedding and choose alike in the first layer: where two of
#: their eight experts are held this chip's share is 16% against the uniform
#: 12.5%, where five are it is 25%; a bound under the share costs the step
#: the rare path's recomputation, one over it costs gathers over empty rows.
_HELD_ROWS_SLACK = 2.0


#: Rows of one pass of the rare path: four of the grouped kernels' row tiles.
_HELD_REST_ROWS = 2048


def _held_rows_bound(assignments, count, num_experts):
    """Rows of one pass over the held assignments: `_HELD_ROWS_SLACK` x the
    uniform share, a whole number of the kernels' row tiles, at most all."""
    tile = _GMM_TILE[0] if assignments >= _GMM_TILE[0] else 128
    want = int(np.ceil(_HELD_ROWS_SLACK * assignments * count / num_experts))
    return min(-(-want // tile) * tile, -(-assignments // tile) * tile)


@jax.custom_vjp
def _sort_by_key(key, values):
    """(the stable permutation that sorts `key`, `values` in that order) from
    one sort; the transpose sorts back by the permutation, for a gather or a
    scatter of scalars runs element by element on the chip (`_permute_scalars`)."""
    index = jax.lax.iota(jnp.int32, key.shape[0])
    _, order, ordered = jax.lax.sort((key, index, values), num_keys=1, is_stable=True)
    return order, ordered


def _sort_by_key_fwd(key, values):
    out = _sort_by_key(key, values)
    return out, out[0]


_sort_by_key.defvjp(*counted_rules(
    "moe_experts",
    _sort_by_key_fwd,
    lambda order, g: (None, jax.lax.sort((order, g[1]), num_keys=1, is_stable=False)[1])))


# The held path's two row operations, each the other's transpose, as
# `_rows_by_expert` and `_sum_by_token` are.  `token` [C] is the token of each
# row of a chunk, `target` the same with the rows no held expert owns sent
# past the last token, where a scatter drops them, `live` the number of the
# chunk's rows that a held expert owns (they come first).  Both cost what the
# rows they are GIVEN cost, dropped or not (TPU v5e, 32768 rows of 2048 bf16:
# a scatter-add 2.93 ms, a gather 0.71 in LFM2's step), and the bound is twice
# what a uniform router sends: they are given the live rows' passes only.
#
# `kernel`: None for XLA's scatter-add, else `_token_sum_kernel`'s (held experts,
# interpreted): the way back is then `moe_kernels.token_sum` with the slots no
# held expert owns left out, and `target` is what THAT reads, (each slot's place
# among the chunk's rows [T, k], negative where it owns none; its local expert,
# `count` there).  A stable sort by local expert left every expert's rows in
# token order, which is what the kernel rests on.  It copies the live runs'
# tiles only, whatever the bound, so it has no switch over prefixes; the gather,
# at 20 ns a row, stays XLA's and keeps its own.  TPU v5e, ms a call alone, half
# | all of the bound live (my chip runs, PR 53, tools/chip_held_experts.py):
# SDAR's 32768 rows of 2048 bf16 behind 16384 tokens of 8 slots, 16 held: the
# scatter-add 2.00 | 3.09, the kernel 0.50 | 0.55; LFM2's (4 slots, 8 held)
# 1.99 | 3.08 and 0.43 | 0.48; Kimi Linear's 2048 rows of 2304 behind 4096
# tokens 0.54 | 0.64 and 0.22 | 0.23.  The layer forward and backward, at the
# share the cell reads: SDAR's 16.9 -> 13.2, LFM2's 21.6 -> 17.8, Kimi
# Linear's 4.4 -> 3.4; the cells +4.0 to +5.3%, +5.0 to +5.5% and +3.8%
# (PERF.md, PR 53).

#: Passes the two row operations make over a chunk at the most.  Each count of
#: passes is a branch of its own, compiled for its rows (80 bytes of code a
#: row), so it is the NUMBER that is fixed and not the rows, and it is small.
#: LFM2's cell, samples/s and warm `setup_s`, against 7.16-7.19 and 84-87 s for
#: one pass over the bound: 16 passes (2048 rows at the step's bound of 32768)
#: 7.47-7.51, but the step compiled in 312 s for 112 and the `for_test` clone
#: (eight sequences: a bound of 131072, which was 64 passes) made an executable
#: too large for the compile cache, so every run compiled it again, 355-383 s;
#: 8 passes 7.44-7.54 and 92-95 s: the gain whole, `setup_s` 10% up, at its
#: bound; 4 passes 7.33-7.42 and 86-89 s.  The layer alone reads the same with
#: 2048, 4096 and 8192 rows a pass where they cover the same rows (18.36, 18.25,
#: 18.02 ms, half the bound live); a coarser pass covers up to a pass more, at
#: 68 ns a row and scatter-add, 20 and gather (tools/chip_held_experts.py;
#: PERF.md, PR 35).
_HELD_PASSES = 4


def _pass_rows(n):
    """Rows of one pass over a chunk of n: a `_HELD_PASSES`th, in whole lane tiles."""
    return min(n, -(-n // (_HELD_PASSES * 128)) * 128)


def _over_the_live_rows(n, live, over):
    """`over(rows)` for the fewest whole passes' `rows` (`_pass_rows` a pass;
    the last ends with the chunk) that hold the `live` first of a chunk's n
    rows: one branch a count of passes, none to all.  The count is the step's
    own, which nothing could differentiate and nothing has to: the row
    operations' transposes are written.

    One instruction over a prefix and not a loop over passes: on the chip a
    pass of a loop costs twice a row what the one instruction does (LFM2's
    layer forward and backward, the whole bound live: 27.0 ms with one
    instruction, 27.1 so, 32.7 to 33.8 under a loop of 2048-row passes; half of
    it live 19.9, 18.3, 20.0 to 20.6; tools/chip_held_experts.py)."""
    a_pass = _pass_rows(n)
    counts = [0] + list(range(a_pass, n, a_pass)) + [n]
    return jax.lax.switch((live + a_pass - 1) // a_pass, [functools.partial(over, rows) for rows in counts])


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def _rows_of_tokens(x, token, target, live, tokens, kernel=None):
    """Tokens [T, d] -> the chunk's rows [C, d]; zeros past the last pass.
    XLA's gather whatever `kernel`, which is its transpose's."""
    def over(rows):
        return jnp.pad(_take_rows(x, token[:rows]), ((0, token.shape[0] - rows), (0, 0)))

    return _over_the_live_rows(token.shape[0], live, over)


def _zeros_from(rows, live):
    """`rows` with zeros from row `live` to the end of its tile of `GRANULE`.
    Rows past the live ones come out of the grouped kernels as they lay in
    memory, the kernel copies whole tiles, and its 0/1 product makes a NaN of
    0 x NaN for a whole block of tokens: the live rows' last tile is the only
    one it copies that can hold such rows, and eight rows are written in place
    (into the grouped kernel's output: XLA copies nothing) where a `where` over
    all of them is a pass over the bound: SDAR's layer forward and backward
    13.2 ms so, 13.2 with nothing zeroed, 13.7 with the `where` (my chip run,
    PR 53, tools/chip_held_experts.py); a kernel that zeroed its own buffer
    could win nothing and would change the unmasked call's text."""
    tile = moe_kernels.GRANULE
    at = jnp.minimum(live // tile * tile, rows.shape[0] - tile)
    dead = (at + jax.lax.iota(jnp.int32, tile) >= live)[:, None]
    last = jnp.where(dead, 0, jax.lax.dynamic_slice(rows, (at, 0), (tile, rows.shape[1])))
    return jax.lax.dynamic_update_slice(rows, last, (at, 0))


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def _add_to_tokens(rows, token, target, live, tokens, kernel=None):
    """The chunk's rows [C, d] -> tokens [T, d]: each token's rows summed,
    rows of no held expert dropped.  A token has one such row on average and
    eight at the most.  XLA's form sums them in the rows' dtype (a float32 copy
    of the rows for the scatter to read is 268 MB at SDAR's cell), the kernel in
    float32 with one rounding."""
    if kernel:
        _MON.counter("lowering.held_token_sum_calls").inc()
    return _add_to_tokens_as_traced(rows, token, target, live, tokens, kernel)


def _add_to_tokens_as_traced(rows, token, target, live, tokens, kernel):
    """`_add_to_tokens` itself, and its forward rule's: the common pass is a
    `jax.checkpoint`, whose body JAX traces as written and then once more through
    the rules, and a call is counted where it is written."""
    if kernel:
        return moe_kernels.token_sum(_zeros_from(rows, live), *target, *kernel)

    def over(n):
        return jnp.zeros((tokens, rows.shape[-1]), rows.dtype).at[target[:n]].add(rows[:n], mode="drop")

    return _over_the_live_rows(token.shape[0], live, over)


_rows_of_tokens.defvjp(*counted_rules(
    "moe_experts",
    lambda x, token, target, live, tokens, kernel=None: (_rows_of_tokens(x, token, target, live, tokens, kernel), (token, target, live)),
    lambda tokens, kernel, res, g: (_add_to_tokens(g, *res, tokens, kernel), None, None, None)))
_add_to_tokens.defvjp(*counted_rules(
    "moe_experts",
    lambda rows, token, target, live, tokens, kernel=None: (_add_to_tokens_as_traced(rows, token, target, live, tokens, kernel), (token, target, live)),
    lambda tokens, kernel, res, g: (_rows_of_tokens(g, *res, tokens, kernel), None, None, None)))


def _held_experts(x2, top_p, top_i, load, matrices, held, platform, kernel=None, keep=None, activation="silu"):
    """`moe_experts` over the experts `held` = (first, count): (the tokens'
    output [T, d] in x2's dtype, the assignments that fell on held experts,
    those of them no pass covered).  `kernel`: the common pass's way back to
    token order (`_add_to_tokens`); the rare path, which no step runs and every
    step's compile pays for, keeps XLA's.  `keep`: the name under which a
    `recompute_scope` round the layer keeps the common pass's first products'
    outputs (`_kept_experts`); the rare path's are made again whatever it says.
    `matrices` and `activation`: the experts' form (`_activated`): (gate, up,
    down), or (up, down) for experts with no gate."""
    first, count = held
    tokens, k = top_i.shape
    assignments = tokens * k
    bound = _held_rows_bound(assignments, count, load.shape[0])
    rest = min(bound, _HELD_REST_ROWS)
    _MON.counter("lowering.held_row_passes").inc(-(-bound // _pass_rows(bound)))   # of a row operation, at the most
    chunks = -(-(assignments - bound) // rest)   # of the rare path
    expert = top_i.reshape(-1)
    local = jnp.where((expert >= first) & (expert < first + count), expert - first, count)
    order, weight = _sort_by_key(local.astype(jnp.int32), top_p.reshape(-1).astype(jnp.float32))
    if kernel:   # each assignment's place in the order: one more sort of scalars, as `_sort_by_key`'s transpose is
        place = jax.lax.sort((order, jax.lax.iota(jnp.int32, assignments)), num_keys=1, is_stable=False)[1].reshape(tokens, k)
    pad = bound + chunks * rest - assignments   # rows past the last assignment belong to no token
    order = jnp.pad(order, (0, pad), constant_values=assignments)
    weight = jnp.pad(weight, (0, pad))
    sizes = load[first:first + count]
    ends = jnp.cumsum(sizes)
    n_held = ends[-1]

    # What the passes read of the routing, handed to them as an ARGUMENT: a `jax.custom_vjp` that closes over values
    # of the trace it stands in is lowered with them as inputs nobody passes once that trace is a `jax.checkpoint`'s
    # (a sparse layer inside a `recompute_scope`), so `passes` below closes over nothing that is traced.
    route = (order, n_held) + ((place, local) if kernel else ()) + (ends, sizes)
    # the names of the two products' outputs that the common pass keeps for its own backward pass; a `recompute_scope`
    # round the layer that keeps them too saves by `keep`, so they carry that one name: a policy by name reaches through
    # this pass's own `jax.checkpoint`, and the value this pass saves has to be the one the outer policy saves
    kept = ((keep, keep) if keep else ("expert_gate", "expert_up"))[3 - len(matrices):]
    # ... and under such a segment the rare path's carry names of their own, which no policy saves: under the segment's
    # name the outer policy would keep every one of its passes' products too, [passes, rows, width] a layer that no step
    # ever makes, and the branch that does nothing would hand backward as many zeros (0.85 GB a layer at 22 of 512 over
    # 8192 tokens: 20.6 GB planned a chip for 13.5)
    unkept = tuple(name + "@rest" for name in kept) if keep else kept

    def chunk(route, x2, weight, matrices, lo, n, kernel=None, kept=kept):
        """Rows [lo, lo + n) of the order, as tokens' sums; `kept`: the names of its first products' outputs."""
        order, n_held, *slots, ends, sizes = route
        rank = lo + jax.lax.iota(jnp.int32, n)
        mine = jax.lax.dynamic_slice(order, (lo,), (n,))
        token = jnp.minimum(mine // k, tokens - 1)
        valid = rank < n_held
        if kernel:   # a slot owns a row of this chunk where its place is one of the chunk's live rows'
            place, local = slots
            owned = (place >= lo) & (place < jnp.minimum(lo + n, n_held))
            target = jnp.where(owned, place - lo, -1), jnp.where(owned, local.reshape(tokens, k), count).astype(jnp.int32)
        else:
            target = jnp.where(valid, token, tokens)
        groups = jnp.clip(ends, lo, lo + n) - jnp.clip(ends - sizes, lo, lo + n)
        live = jnp.clip(n_held - lo, 0, n)
        *openers, w_down = matrices
        rows = _rows_of_tokens(x2, token, target, live, tokens, kernel)
        # a row no group covers comes out of the kernels as it lay in memory
        covered = valid[:, None]
        products = [checkpoint_name(grouped_matmul(rows, w, groups, platform), name) for w, name in zip(openers, kept)]
        products = [jnp.where(covered, t, 0).astype(jnp.float32) for t in products]
        w = jax.lax.dynamic_slice(weight, (lo,), (n,))[:, None]
        hidden = jnp.where(covered, _activated(products, w, activation), 0).astype(x2.dtype)
        down = grouped_matmul(hidden, w_down, groups, platform)
        return _add_to_tokens(down, token, target, live, tokens, kernel)

    # the common pass keeps the two products' outputs for the backward pass and
    # makes the rest again there (a gather, the masters' casts, one elementwise
    # pass): 335 MB a layer at SDAR's cell that no step has to hold
    common = jax.checkpoint(lambda route, x2, weight, matrices: chunk(route, x2, weight, matrices, 0, bound, kernel),
                            policy=jax.checkpoint_policies.save_only_these_names(*kept))
    if chunks == 0:
        return common(route, x2, weight, matrices), n_held, jnp.zeros_like(n_held)

    a_rest_pass = functools.partial(chunk, kept=unkept)   # ONE function, so that a second trace of the scan finds the first's

    def the_rest(x2, weight, matrices, route):
        def step(acc, lo):
            return acc + jax.checkpoint(a_rest_pass, static_argnums=(5,))(route, x2, weight, matrices, lo, rest), None
        return jax.lax.scan(step, jnp.zeros_like(x2), bound + rest * jnp.arange(chunks, dtype=jnp.int32))[0]

    # Both passes' transpose is written out so that the rare one ADDS to what
    # the common one made, inside its branch.  Derived, a conditional's two
    # branches return the same residuals and the same cotangents, so the branch
    # that does nothing returns zeros for them: a common step then writes the
    # three matrices' shapes in float32 twice a layer, keeps one set from the
    # forward pass to the backward pass and adds the other to the gradients
    # (2.2 to 3.0 GB of the planned peak: PERF.md, PR 34).
    def with_the_rest(out, x2, weight, matrices, route):
        return jax.lax.cond(route[1] > bound, lambda out: out + the_rest(x2, weight, matrices, route), lambda out: out, out)

    @jax.custom_vjp
    def passes(x2, weight, matrices, route):
        return with_the_rest(common(route, x2, weight, matrices), x2, weight, matrices, route)

    def passes_fwd(x2, weight, matrices, route):
        out, pull = jax.vjp(functools.partial(common, route), x2, weight, matrices)
        return with_the_rest(out, x2, weight, matrices, route), (pull, x2, weight, matrices, route)

    def passes_bwd(res, g):
        pull, *primals, route = res
        grads = jax.lax.cond(
            route[1] > bound,
            lambda grads: jax.tree.map(jnp.add, grads, jax.vjp(lambda *p: the_rest(*p, route), *primals)[1](g)),
            lambda grads: grads, pull(g))
        return (*grads, None)    # the routing is whole numbers: nothing flows back into it

    passes.defvjp(*counted_rules("moe_experts", passes_fwd, passes_bwd))
    # the rare path passes over every chunk there is: no assignment is left out
    return passes(x2, weight, matrices, route), n_held, jnp.zeros_like(n_held)



def _publish_routing(step, values):
    """One logged step's routing statistics: per layer the busiest and the
    idlest expert's tokens over the mean (1.0 is perfect balance), the worst
    layer's as gauges, every layer's in the step record."""
    loads = [np.asarray(v, "f8").reshape(-1) for v in values["Load"]]
    most = [float(v.max() / v.mean()) for v in loads]
    least = [float(v.min() / v.mean()) for v in loads]
    lost = int(sum(int(np.asarray(v).sum()) for v in values["Dropped"]))
    _MON.gauge("moe.load_max_over_mean").set(max(most))
    _MON.gauge("moe.load_min_over_mean").set(min(least))
    _MON.gauge("moe.dropped_tokens").set(lost)
    record = {"kind": "moe_routing", "pipeline_step": step,
              "load_max_over_mean": most, "load_min_over_mean": least,
              "dropped_tokens": lost}
    if values.get("Held"):
        # layers that hold a share of their experts: the share of the step's
        # (token, slot) assignments that landed on them, per layer
        held = [int(np.asarray(h).sum()) for h in values["Held"]]
        record["held_rows_share"] = [float(h / v.sum()) for h, v in zip(held, loads)]
        _MON.gauge("moe.held_rows_share").set(max(record["held_rows_share"]))
        # ... and the rows the row operations' passes went over, of the bound's
        bounds = [_held_rows_bound(int(v.sum()), count, v.size) for (_, count), v in zip(values["held"], loads)]
        record["held_rows_passed_share"] = [
            min(-(-h // _pass_rows(b)) * _pass_rows(b), b) / b for h, b in zip(held, bounds)]
        _MON.gauge("moe.held_rows_passed_share").set(max(record["held_rows_passed_share"]))
    if values.get("BiasMoved"):
        # routers with a bias on the choice: the share of the step's (token,
        # slot) choices that the unbiased scores' top-k would not have made
        record["bias_moved_share"] = [float(np.asarray(m).sum() / v.sum())
                                      for m, v in zip(values["BiasMoved"], loads)]
        _MON.gauge("moe.bias_moved_share").set(max(record["bias_moved_share"]))
    _MON.record_step(record)


# one record a logged step from both ops of the layer: the experts' slots and
# the router's `BiasMoved` reach the one `_publish_routing`
set_step_stats("moe_experts", ("Load", "Dropped", "Held"), _publish_routing, attrs=("held",))
set_step_stats("moe_router", ("BiasMoved",), _publish_routing)


@register_op("exit_loss")
def _exit_loss(ctx, op, ins):
    """The expected task loss under a learned exit distribution, with an
    entropy term that holds the distribution towards the uniform one.  `CE`
    and `Gate` are [T, ...]: exit t's cross entropy and exit-gate LOGIT at
    every position.  Everything here is float32 whatever the inputs are.  The
    probabilities are the products as written, each pass taking its share of
    what is LEFT (left - p_t, so they sum to 1 to float32 rounding whatever the
    device's sigmoid rounds to); the entropy's logarithms are sums of
    log-sigmoids, log p_t = log sigmoid(g_t) + sum_{j<t} log sigmoid(-g_j), so
    a saturated gate gives 0 x a large number and not 0 x inf.  `P` [T, ...] is the
    distribution; `ExitMass` [T] (the mean of p_t), `Entropy` and `ExitCE`
    [T] (the mean cross entropy of exit t) are the step's statistics and
    carry no gradient."""
    ce = first(ins, "CE").astype(jnp.float32)
    gate = first(ins, "Gate").astype(jnp.float32).reshape(ce.shape)
    left, p = jnp.ones_like(gate[0]), []
    for lam in jax.nn.sigmoid(gate[:-1]):            # T is small and static
        p.append(lam * left)
        left = left - p[-1]                           # so that the exits' probabilities sum to 1 as float32 adds
    p = jnp.stack(p + [left])
    stay = jnp.cumsum(jax.nn.log_sigmoid(-gate), axis=0)          # log prod_{j<=t} (1 - lam_j)
    before = jnp.concatenate([jnp.zeros_like(stay[:1]), stay[:-1]], axis=0)
    log_p = jnp.concatenate([jax.nn.log_sigmoid(gate[:-1]) + before[:-1], before[-1:]], axis=0)
    entropy = -jnp.sum(p * jnp.maximum(log_p, jnp.finfo(jnp.float32).min), axis=0)
    loss = jnp.mean(jnp.sum(p * ce, axis=0) - op.attr("beta", 0.0) * entropy)
    per_exit = tuple(range(1, ce.ndim))
    stats = jax.lax.stop_gradient((jnp.mean(p, axis=per_exit), jnp.mean(entropy).reshape(1),
                                   jnp.mean(ce, axis=per_exit)))
    return {"Loss": loss.reshape(1), "P": p, "ExitMass": stats[0], "Entropy": stats[1], "ExitCE": stats[2]}


def _publish_loop_exit(step, values):
    """One logged step's exit statistics (a program holds one `exit_loss`):
    the mean exit probability a pass, the mean entropy of the distribution and
    each exit's mean cross entropy, as a `kind="loop_exit"` record and the
    gauges `loop.exit_mass_last_pass` / `loop.exit_entropy`."""
    mass = [float(v) for v in np.asarray(values["ExitMass"][0], "f8").reshape(-1)]
    entropy = float(np.asarray(values["Entropy"][0], "f8").reshape(-1)[0])
    _MON.gauge("loop.exit_mass_last_pass").set(mass[-1])
    _MON.gauge("loop.exit_entropy").set(entropy)
    _MON.record_step({"kind": "loop_exit", "pipeline_step": step, "exit_mass": mass, "entropy": entropy,
                      "exit_ce": [float(v) for v in np.asarray(values["ExitCE"][0], "f8").reshape(-1)]})


set_step_stats("exit_loss", ("ExitMass", "Entropy", "ExitCE"), _publish_loop_exit)


# -- build-time shape and dtype rules -----------------------------------------

def _infer_exit_loss(ctx):
    ce, gate = ctx.in_shape("CE"), ctx.in_shape("Gate")
    if ce is None:
        return
    if gate is not None and int(np.prod([max(d, 1) for d in gate])) != int(np.prod([max(d, 1) for d in ce])):
        ctx.fail(f"Gate {gate} holds another number of logits than CE {ce} of cross entropies")
    if len(ce) < 2 or ce[0] < 2:
        ctx.fail(f"CE must be [exits, ...] with two exits at least, got {ce}")
    ctx.set_out("Loss", (1,), "float32")
    ctx.set_out("P", ce, "float32")
    ctx.set_out("ExitMass", (ce[0],), "float32")
    ctx.set_out("Entropy", (1,), "float32")
    ctx.set_out("ExitCE", (ce[0],), "float32")


def _infer_rms_norm(ctx):
    xs = ctx.in_shape("X")
    if xs is None:
        return
    begin = ctx.op.attr("begin_norm_axis", 1)
    scale = ctx.in_shape("Scale")
    if scale is not None and int(np.prod(scale)) != int(np.prod(xs[begin:])):
        ctx.fail(f"Scale holds {int(np.prod(scale))} gains for a normalised "
                 f"extent of {tuple(xs[begin:])}")
    ctx.set_out("Y", xs, ctx.in_dtype("X"))


def _infer_rotary_embedding(ctx):
    xs, ps = ctx.in_shape("X"), ctx.in_shape("Positions")
    if xs is None:
        return
    layout = ctx.op.attr("layout", "bhld")
    if layout not in ("bhld", "blhd"):
        ctx.fail(f"layout must be \"bhld\" or \"blhd\", got {layout!r}")
    at = 1 if layout == "blhd" else 2    # where X holds the positions
    if len(xs) != 4 or xs[-1] % 2:
        ctx.fail(f"X must be {'(B, L, H, dh)' if at == 1 else '(B, H, L, dh)'} with an even dh, got {xs}")
    turned = ctx.op.attr("rotary_dim", xs[-1])
    if not 0 < turned <= xs[-1] or turned % 2:
        ctx.fail(f"rotary_dim must be an even number of leading features, at most dh = {xs[-1]}, got {turned}")
    table = ctx.op.attr("inv_freq", None)
    if table is not None and len(table) != turned // 2:
        ctx.fail(f"inv_freq must hold {turned // 2} frequencies, one a pair, got {len(table)}")
    if ps is not None and (len(ps) != 2 or (ps[1] != xs[at] and _A.DYN not in (ps[1], xs[at]))):
        ctx.fail(f"Positions must be (B, L) with L = {xs[at]}, got {ps}")
    ctx.set_out("Out", xs, ctx.in_dtype("X"))


def _infer_moe_router(ctx):
    xs, ws = ctx.in_shape("X"), ctx.in_shape("W")
    if xs is None or ws is None:
        return
    k = ctx.op.attr("top_k")
    if len(ws) != 2 or ws[0] != xs[-1]:
        ctx.fail(f"W must be ({xs[-1]}, experts), got {ws}")
    if not 1 <= k <= ws[1]:
        ctx.fail(f"top_k {k} of {ws[1]} experts")
    if ctx.op.attr("scoring", "softmax") not in ("softmax", "sigmoid"):
        ctx.fail(f"scoring {ctx.op.attr('scoring')!r} is neither softmax nor sigmoid")
    bias = ctx.in_shape("Bias")
    if bias is not None:
        if tuple(bias) != (ws[1],):
            ctx.fail(f"Bias must hold one value for each of {ws[1]} experts, got {bias}")
        ctx.set_out("BiasMoved", (1,), "int32")
    ctx.set_out("TopKProb", tuple(xs[:-1]) + (k,), "float32")
    ctx.set_out("TopKIndex", tuple(xs[:-1]) + (k,), "int32")
    ctx.set_out("Load", (ws[1],), "int32")
    ctx.set_out("LoadBalanceLoss", (1,), "float32")
    ctx.set_out("ZLoss", (1,), "float32")


def _infer_moe_experts(ctx):
    xs = ctx.in_shape("X")
    gated = ctx.op.attr("gated", True)
    gate, up, down = (ctx.in_shape(s) for s in ("WGate", "WUp", "WDown"))
    if ctx.op.attr("activation", "silu") not in ("silu", "relu", "relu2"):
        ctx.fail(f"activation {ctx.op.attr('activation')!r} is none of silu, relu and relu2")
    if not gated:   # two matrices an expert: no WGate
        if gate is not None:
            ctx.fail("gated=False: experts of two matrices have no WGate")
        gate = up
    if xs is None or gate is None or up is None or down is None:
        return
    if len(gate) != 3 or gate[1] != xs[-1] or tuple(up) != tuple(gate) \
            or tuple(down) != (gate[0], gate[2], gate[1]):
        ctx.fail(f"experts must be {'WGate, ' if gated else ''}WUp (E, {xs[-1]}, F) and WDown "
                 f"(E, F, {xs[-1]}), got {gate if gated else ''}, {up}, {down}")
    load = ctx.in_shape("Load")
    held = ctx.op.attr("held", None)
    if held is not None:
        first, count = held
        if count != gate[0] or first < 0 or (load is not None and first + count > load[0]):
            ctx.fail(f"held = {tuple(held)}: the matrices hold {gate[0]} experts and the "
                     f"router decides over {None if load is None else load[0]}")
        ctx.set_out("Held", (1,), "int32")
    elif load is not None and tuple(load) != (gate[0],):
        ctx.fail(f"Load must hold one count for each of {gate[0]} experts, got {load}")
    ctx.set_out("Out", xs, ctx.in_dtype("X"))
    ctx.set_out("Dropped", (1,), "int32")


def _infer_short_conv(ctx):
    xs, ws = ctx.in_shape("X"), ctx.in_shape("Filter")
    if xs is None or ws is None:
        return
    gated, activation = ctx.op.attr("gated", True), ctx.op.attr("activation", None)
    if activation != (None if gated else "silu"):
        ctx.fail(f"gated={gated} with activation={activation!r}: the gated form has none, the plain form a silu")
    fold = 3 if gated else 1
    if len(xs) != 3 or len(ws) != 2 or ws[0] * fold != xs[-1] or ws[1] < 1:
        ctx.fail(f"X must be (b, T, {'3d' if gated else 'd'}) and Filter (d, K), got {xs} and {ws}")
    bias = ctx.in_shape("Bias")
    if bias is not None and (gated or tuple(bias) != (ws[0],)):
        ctx.fail(f"Bias is the plain form's, one value for each of Filter's {ws[0]} channels, got {bias} with gated={gated}")
    ctx.set_out("Out", tuple(xs[:-1]) + (ws[0],), ctx.in_dtype("X"))


_A.register_rule(["exit_loss"], _infer_exit_loss)
_A.register_rule(["rms_norm"], _infer_rms_norm)
_A.register_rule(["short_conv"], _infer_short_conv)
_A.register_rule(["rotary_embedding"], _infer_rotary_embedding)
_A.register_rule(["moe_router"], _infer_moe_router)
_A.register_rule(["moe_experts"], _infer_moe_experts)


# -- cost rows (core/resource_plan.py) -----------------------------------------

def _cost_moe_router(ctx):
    """The logits' product; the softmax, top-k and losses are a few passes
    over [tokens, experts]."""
    ws = ctx.in_shape("W")
    if ws is None:
        return float(ctx.out_elems_total()), ctx.io_bytes()
    tokens = ctx.in_elems("X") // max(ws[0], 1)
    return (2.0 * ws[0] + 16.0) * tokens * ws[1], ctx.io_bytes()


#: Passes of the forward lowering over its (token, slot) rows, in arrays read
#: or written.  [rows, hidden]: written by the gather, read by the gate and by
#: the up product, written by the down product, and on the way back to token
#: order read once by the kernel (`_token_sum_path`; a layer that holds a share
#: too, over its bound's rows); "hidden_xla": where the way back is XLA's, the
#: gather reads and writes them and the sum over k reads them again.  [rows,
#: width]: written by gate and up, both read and one written by SiLU x up x
#: weight, read by the down product.  tests/test_chip_compile.py counts them in
#: the compiled program.
_ROW_PASSES = {"hidden": 5, "hidden_xla": 7, "width": 6}


def _cost_moe_experts(ctx):
    """Useful arithmetic of the grouped products (three, or two without a gate) over the (token,
    slot) rows, 2 per multiply-add, whatever a kernel pads; traffic: every
    expert's three matrices once and `_ROW_PASSES` over the rows, the way back
    the kernel's where the shapes are ones it takes (the plan is the chip's).
    For a layer that holds a share the rows are the bound's: an upper bound
    since the row operations stop after the step's last live pass, which no plan
    can know."""
    gate, products = ctx.in_shape("WUp"), 3.0 if ctx.op.attr("gated", True) else 2.0
    if gate is None or ctx.in_shape("TopKIndex") is None:
        return float(ctx.out_elems_total()), ctx.io_bytes()
    rows, d, f = ctx.in_elems("TopKIndex"), gate[1], gate[2]
    held, load = ctx.op.attr("held", None), ctx.in_shape("Load")
    dtype, k = ctx.env.dtype(ctx.in_name("X")), ctx.in_shape("TopKIndex")[-1]
    # the matrices are the experts the layer computes with, all or the held ones: the kernel's groups either way
    hidden = _ROW_PASSES["hidden" if moe_kernels.fits(rows // k, d, k, dtype, gate[0]) else "hidden_xla"]
    passes = rows
    if held is not None and load is not None:
        # the passes are over the bound; the arithmetic is the uniform share's
        passes = _held_rows_bound(rows, held[1], load[0])
        rows = rows * held[1] // load[0]
    item = 2 if dtype in ("bfloat16", "float16") else 4
    moved = passes * (hidden * d + _ROW_PASSES["width"] * f) * item
    return products * 2.0 * rows * d * f, float(ctx.io_bytes() + moved)


def _cost_short_conv(ctx):
    """Per output element the two gates' multiplies and K multiply-adds; the
    traffic is the op's own: [b, T, 3d] read, [b, T, d] written."""
    ws = ctx.in_shape("Filter")
    taps = ws[1] if ws is not None else 3
    edge = 2.0 if ctx.op.attr("gated", True) else 4.0   # the two gates, or the SiLU
    return (edge + 2.0 * taps) * ctx.out_elems_total(), ctx.io_bytes()


_RP.register_cost(["short_conv"], _cost_short_conv)
_RP.register_elementwise_cost("rms_norm", flops_per_elem=6.0)
_RP.register_elementwise_cost("exit_loss", flops_per_elem=12.0)
_RP.register_elementwise_cost("rotary_embedding", flops_per_elem=6.0)
_RP.register_cost(["moe_router"], _cost_moe_router)
_RP.register_cost(["moe_experts"], _cost_moe_experts)


# -- what a `recompute_scope` round a sparse layer may keep (core/lowering.py: plan_kept) ----------

def _kept_experts(ctx, op, shapes):
    """The gate and the up product's outputs (the one first product's where the
    experts have no gate), [rows, width] each in the rows'
    dtype, which backward reads (the down product's it does not): over every
    (token, slot) row, or for a layer that holds a share over its bound's rows
    (the common pass's; the rare path makes its own again).  Priced by the op's
    cost rule, as a `mul`'s output is by its own."""
    gate, index = shapes.shape(op.input("WUp")[0]), shapes.shape(op.input("TopKIndex")[0])
    rows = int(np.prod(index))
    held = op.attr("held", None)
    if held is not None:
        rows = _held_rows_bound(rows, held[1], shapes.shape(op.input("Load")[0])[0])
    firsts = 2 if op.attr("gated", True) else 1
    return residuals_name(op), firsts * rows * gate[-1] * _RP._itemsize(shapes.dtype(op.input("X")[0]))


def _kept_router(ctx, op, shapes):
    """The float32 logits [tokens, experts]: 8 MB where the product that makes
    them runs at the highest precision, six passes of the matrix unit."""
    experts = shapes.shape(op.input("W")[0])[-1]
    tokens = int(np.prod(shapes.shape(op.output("TopKProb")[0])[:-1]))
    return _router_logits_name(op), 4 * tokens * experts


set_kept("moe_experts", _kept_experts)
set_kept("moe_router", _kept_router)
