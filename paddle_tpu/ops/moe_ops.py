"""The parts of a modern decoder block that no 2019 op computes: RMSNorm,
rotary positions, and a layer of routed experts (a router and the experts).

No reference counterpart: the reference predates all three.  The equations
are those of OLMoE-1B-7B (Muennighoff et al. 2024, arXiv:2409.02060), which
are also Mixtral's and DeepSeek-MoE's but for the shared expert:

    rms_norm          y = x / sqrt(mean(x^2) + eps) * g
    rotary_embedding  y = x cos(t) + rotate_half(x) sin(t),  t = pos * theta^(-2i/dh)
    moe_router        p = softmax_f32(x Wr); (p_e, e) = top_k(p); two auxiliary losses
    moe_experts       y = sum_{e in top_k} p_e . Wdown_e( silu(Wgate_e x) * (Wup_e x) )

Backward comes from `jax.vjp` over these lowerings like every other op's
(core/lowering.py).  Two transposes are written by hand, `_permute_rows`
and `_rows_by_expert`: the derived transpose of a row gather is a
scatter-add, and here the gather is a permutation whose inverse is known, so
the transpose is the other gather (TPU v5e, one OLMoE layer forward and
backward: 66.2 ms against 74.7 derived; PERF.md, PR 26).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from ..core import analysis as _A
from ..core import resource_plan as _RP
from ..core.registry import register_op, set_step_stats
from ..monitor import MONITOR as _MON
from .common import first, match_dtype


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
    """Rotate-half rotary positions over (B, H, L, dh); `Positions` is
    (B, L) integers, an input so that packed or offset sequences bring
    their own.  Angles, sines and the rotation are float32."""
    x = first(ins, "X")
    pos = first(ins, "Positions")
    half = x.shape[-1] // 2
    inv_freq = op.attr("theta", 10000.0) ** (-np.arange(half, dtype=np.float32) / half)
    angle = pos.astype(jnp.float32)[:, None, :, None] * inv_freq  # (B, 1, L, dh/2)
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    x1, x2 = x[..., :half].astype(jnp.float32), x[..., half:].astype(jnp.float32)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return {"Out": out.astype(x.dtype)}


@register_op("moe_router")
def _moe_router(ctx, op, ins):
    """Logits, probabilities, their top-k and both auxiliary losses in
    float32: a routing decision made on rounded probabilities is another
    decision.  `Load` is the number of (token, slot) assignments each
    expert received; `moe_experts` takes it as its group sizes.

    LoadBalanceLoss = E . sum_e f_e P_e with f_e the expert's share of the
    T . k assignments (no gradient) and P_e its mean probability (1 when
    both are uniform); ZLoss = mean_t logsumexp(logits_t)^2."""
    x = first(ins, "X")
    w = first(ins, "W")
    k = op.attr("top_k")
    n_experts = w.shape[-1]
    x2 = x.reshape(-1, x.shape[-1]).astype(jnp.float32)
    logits = jnp.dot(x2, w.astype(jnp.float32), precision=jax.lax.Precision.HIGHEST)
    lse = jax.nn.logsumexp(logits, axis=-1)
    probs = jnp.exp(logits - lse[:, None])
    top_p, top_i = jax.lax.top_k(probs, k)
    if op.attr("norm_topk_prob", False):
        top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)
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
    }


@jax.custom_vjp
def _permute_rows(x, perm, inverse):
    """x[perm] for a permutation `perm` whose inverse is `inverse`."""
    return jnp.take(x, perm, axis=0)


_permute_rows.defvjp(
    lambda x, perm, inverse: (jnp.take(x, perm, axis=0), inverse),
    lambda inverse, g: (jnp.take(g, inverse, axis=0), None, None))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _rows_by_expert(x, order, inverse, k):
    """One row per (token, slot) assignment, in the order `order` (a
    permutation of the tokens x k assignments, `inverse` its inverse): row i
    is token order[i] // k.  The transpose gathers the rows' gradients back
    into assignment order and sums each token's k, in float32."""
    return jnp.take(x, order // k, axis=0)


def _rows_by_expert_bwd(k, inverse, g):
    g = jnp.take(g, inverse, axis=0).reshape(-1, k, g.shape[-1])
    return jnp.sum(g, axis=1, dtype=jnp.float32).astype(g.dtype), None, None


_rows_by_expert.defvjp(
    lambda x, order, inverse, k: (jnp.take(x, order // k, axis=0), inverse),
    _rows_by_expert_bwd)

#: (rows, contraction, columns) tile of the megablox kernel.  TPU v5e, the
#: three products of one OLMoE layer over 131072 rows, forward and backward
#: (PERF.md, PR 26): 66.2 ms with this tile; (256, 1024, 1024) 67.7,
#: (512, 1024, 512) 69.7, (512, 512, 1024) 71.4, (512, 512, 512) 78.4,
#: (1024, 512, 512) 79.2, the kernel's default (128, 128, 128) 531.9;
#: (1024, 1024, 1024) and (512, 2048, 1024) do not fit the scoped VMEM.
_GMM_TILE = (512, 1024, 1024)


def grouped_matmul(rows, weights, group_sizes, platform=None):
    """rows [M, K] sorted by group, weights [G, K, N], group_sizes [G]
    summing to M -> [M, N]: row i is multiplied by the matrix of its own
    group.  Rows and matrices share a dtype; accumulation is float32.

    The stock Pallas grouped-matmul kernel
    (jax.experimental.pallas.ops.tpu.megablox: forward `gmm`, its custom VJP
    `gmm` with the matrices transposed for the rows' gradient and `tgmm` for
    the matrices'), compiled on a TPU and interpreted elsewhere (CPU tests,
    virtual meshes), so what the tests check is what the chip runs.  The
    kernel wants the row count a multiple of its row tile: rows are padded
    with zeros that belong to no group.  On the chip `jax.lax.ragged_dot`
    read 101.9 ms against 66.2 for the layer above, and applying every expert
    to every token (8x the arithmetic) 82.1 ms forward alone, its backward
    30 GB (PERF.md, PR 26)."""
    from jax.experimental.pallas.ops.tpu.megablox import ops as megablox

    m, k = rows.shape
    n = weights.shape[2]
    tm = _GMM_TILE[0] if m >= _GMM_TILE[0] else -(-m // 128) * 128
    padded = -(-m // tm) * tm
    if padded != m:
        rows = jnp.pad(rows, ((0, padded - m), (0, 0)))
    out = megablox.gmm(rows, weights, group_sizes, rows.dtype,
                       (tm, min(_GMM_TILE[1], k), min(_GMM_TILE[2], n)),
                       None, None, False, platform != "tpu")
    return out[:m]


@register_op("moe_experts")
def _moe_experts(ctx, op, ins):
    """Every (token, slot) assignment is a row: the rows are sorted by
    expert, multiplied group by group (`grouped_matmul`: 1/8 of the
    arithmetic of applying all 64 experts to every token), weighted by
    their router probability and summed back per token.  No capacity, so no
    dropped token, however skewed the router: `Dropped` is the number of
    rows the group sizes do not cover, 0 by construction."""
    x = first(ins, "X")
    top_p = first(ins, "TopKProb")
    top_i = first(ins, "TopKIndex")
    load = first(ins, "Load")
    # master weights follow the activations' dtype, outside the products' scope
    w_gate, w_up, w_down = (match_dtype(x, first(ins, s)) for s in ("WGate", "WUp", "WDown"))
    d, k = x.shape[-1], top_i.shape[-1]
    x2 = x.reshape(-1, d)
    tokens = x2.shape[0]
    order = jnp.argsort(top_i.reshape(-1), stable=True).astype(jnp.int32)
    inverse = jnp.argsort(order).astype(jnp.int32)
    rows = _rows_by_expert(x2, order, inverse, k)
    with jax.named_scope("expert_gemm"):
        gate = grouped_matmul(rows, w_gate, load, ctx.platform)
        up = grouped_matmul(rows, w_up, load, ctx.platform)
    hidden = (jax.nn.silu(gate.astype(jnp.float32)) * up.astype(jnp.float32)).astype(x.dtype)
    with jax.named_scope("expert_gemm"):
        down = grouped_matmul(hidden, w_down, load, ctx.platform)
    down = _permute_rows(down, inverse, order).reshape(tokens, k, d)
    out = jnp.einsum("tkd,tk->td", down.astype(jnp.float32),
                     top_p.reshape(tokens, k).astype(jnp.float32))
    return {"Out": out.astype(x.dtype).reshape(x.shape),
            "Dropped": (tokens * k - jnp.sum(load)).astype(jnp.int32).reshape((1,))}


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
    _MON.record_step({"kind": "moe_routing", "pipeline_step": step,
                      "load_max_over_mean": most, "load_min_over_mean": least,
                      "dropped_tokens": lost})


set_step_stats("moe_experts", ("Load", "Dropped"), _publish_routing)


# -- build-time shape and dtype rules -----------------------------------------

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
    if len(xs) != 4 or xs[-1] % 2:
        ctx.fail(f"X must be (B, H, L, dh) with an even dh, got {xs}")
    if ps is not None and (len(ps) != 2 or (ps[1] != xs[2] and _A.DYN not in (ps[1], xs[2]))):
        ctx.fail(f"Positions must be (B, L) with L = {xs[2]}, got {ps}")
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
    ctx.set_out("TopKProb", tuple(xs[:-1]) + (k,), "float32")
    ctx.set_out("TopKIndex", tuple(xs[:-1]) + (k,), "int32")
    ctx.set_out("Load", (ws[1],), "int32")
    ctx.set_out("LoadBalanceLoss", (1,), "float32")
    ctx.set_out("ZLoss", (1,), "float32")


def _infer_moe_experts(ctx):
    xs = ctx.in_shape("X")
    gate, up, down = (ctx.in_shape(s) for s in ("WGate", "WUp", "WDown"))
    if xs is None or gate is None or up is None or down is None:
        return
    if len(gate) != 3 or gate[1] != xs[-1] or tuple(up) != tuple(gate) \
            or tuple(down) != (gate[0], gate[2], gate[1]):
        ctx.fail(f"experts must be WGate, WUp (E, {xs[-1]}, F) and WDown "
                 f"(E, F, {xs[-1]}), got {gate}, {up}, {down}")
    load = ctx.in_shape("Load")
    if load is not None and tuple(load) != (gate[0],):
        ctx.fail(f"Load must hold one count for each of {gate[0]} experts, got {load}")
    ctx.set_out("Out", xs, ctx.in_dtype("X"))
    ctx.set_out("Dropped", (1,), "int32")


_A.register_rule(["rms_norm"], _infer_rms_norm)
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


def _cost_moe_experts(ctx):
    """Useful arithmetic of the three grouped products over the (token,
    slot) rows, 2 per multiply-add, whatever a kernel pads; traffic: every
    expert's three matrices once, the rows in and out of each product
    (sorted copy, gate, up, hidden, down) and the combine."""
    gate = ctx.in_shape("WGate")
    if gate is None or ctx.in_shape("TopKIndex") is None:
        return float(ctx.out_elems_total()), ctx.io_bytes()
    rows, d, f = ctx.in_elems("TopKIndex"), gate[1], gate[2]
    item = 2 if ctx.env.dtype(ctx.in_name("X")) in ("bfloat16", "float16") else 4
    moved = rows * (2 * d + 3 * f + 2 * d) * item
    return 3.0 * 2.0 * rows * d * f, float(ctx.io_bytes() + moved)


_RP.register_elementwise_cost("rms_norm", flops_per_elem=6.0)
_RP.register_elementwise_cost("rotary_embedding", flops_per_elem=6.0)
_RP.register_cost(["moe_router"], _cost_moe_router)
_RP.register_cost(["moe_experts"], _cost_moe_experts)
