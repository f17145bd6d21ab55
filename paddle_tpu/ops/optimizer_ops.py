"""Optimizer update op lowerings.

Reference kernels: operators/optimizers/{sgd,momentum,adam,adagrad,rmsprop,
adamax,adadelta,ftrl,lamb}_op.cc.  Each is a pure function from
(param, grad, accumulators, lr) to updated values; the executor fuses all
per-param updates into the same XLA program as the backward pass, which is
what the reference's fuse_sgd/fuse_adam build passes approximated.

Sparse (SelectedRows) gradients: sgd/momentum/adagrad/adam carry row-wise
update kernels matching the reference's SelectedRows functors (each op's
`.cc` sparse kernel + math/selected_rows_functor.cc MergeAdd): duplicates
merge first, then only touched table rows are gathered/updated/scattered —
accumulator state for untouched rows is left alone (same deliberate
semantic difference from the dense kernels the reference documents for
momentum/adam)."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from ..core.registry import register_op
from ..core.selected_rows import SelectedRows
from .common import first


_SPARSE_CAPABLE = {"sgd", "momentum", "adam", "adagrad"}


def _lr(ins):
    lr = first(ins, "LearningRate")
    return lr.reshape(()) if lr.ndim else lr


def register_opt(type: str):
    """register_op + dtype preservation: update math runs in the promoted
    (fp32) type, but each `<Slot>Out` is cast back to `<Slot>`'s dtype so
    bf16 params stay bf16 across steps (otherwise state dtype drifts and,
    e.g., a multi-step lax.scan carry mismatches)."""

    def deco(fn):
        def wrapped(ctx, op, ins):
            gslot = ins.get("Grad")
            if gslot and isinstance(gslot[0], SelectedRows) and type not in _SPARSE_CAPABLE:
                raise NotImplementedError(
                    f"{type}: no SelectedRows (sparse) update kernel; use "
                    f"sgd/momentum/adagrad/adam for is_sparse embeddings, or "
                    f"set is_sparse=False"
                )
            outs = fn(ctx, op, ins)
            found_inf = ins.get("FoundInf")  # AMP decorator predication
            skip = found_inf[0].reshape(()) if found_inf else None
            for k, v in list(outs.items()):
                src = k[:-3] if k.endswith("Out") else None
                if src and ins.get(src):
                    ref = ins[src][0]
                    if hasattr(v, "dtype") and v.dtype != ref.dtype:
                        v = v.astype(ref.dtype)
                    if skip is not None:
                        # overflow step: every state buffer keeps its old
                        # value exactly (contrib/mixed_precision/decorator.py)
                        v = jnp.where(skip, ref, v)
                    outs[k] = v
            return outs

        register_op(type)(wrapped)
        return wrapped

    return deco


def _rows_gather(state, rows):
    """Gather state rows for a merged SelectedRows (sentinel rows read
    garbage that the paired drop-scatter discards)."""
    return state.at[rows].get(mode="fill", fill_value=0)


@register_opt("sgd")
def _sgd(ctx, op, ins):
    p = first(ins, "Param")
    g = first(ins, "Grad")
    lr = _lr(ins)
    if isinstance(g, SelectedRows):
        # no MergeAdd needed: scatter-add already sums duplicate rows
        return {"ParamOut": p.at[g.rows].add((-lr * g.values).astype(p.dtype), mode="drop")}
    return {"ParamOut": p - lr * g}


@register_opt("momentum")
def _momentum(ctx, op, ins):
    p = first(ins, "Param")
    g = first(ins, "Grad")
    v = first(ins, "Velocity")
    mu = op.attr("mu", 0.9)
    lr = _lr(ins)
    if isinstance(g, SelectedRows):
        m = g.merged()
        vr = _rows_gather(v, m.rows)
        v_new_r = mu * vr + m.values
        upd = m.values + mu * v_new_r if op.attr("use_nesterov", False) else v_new_r
        return {
            "ParamOut": p.at[m.rows].add((-lr * upd).astype(p.dtype), mode="drop"),
            "VelocityOut": v.at[m.rows].set(v_new_r.astype(v.dtype), mode="drop"),
        }
    v_new = mu * v + g
    if op.attr("use_nesterov", False):
        p_new = p - lr * (g + mu * v_new)
    else:
        p_new = p - lr * v_new
    return {"ParamOut": p_new, "VelocityOut": v_new}


@register_opt("adam")
def _adam(ctx, op, ins):
    p = first(ins, "Param")
    g = first(ins, "Grad")
    m1 = first(ins, "Moment1")
    m2 = first(ins, "Moment2")
    b1p = first(ins, "Beta1Pow").reshape(())
    b2p = first(ins, "Beta2Pow").reshape(())
    beta1 = op.attr("beta1", 0.9)
    beta2 = op.attr("beta2", 0.999)
    eps = op.attr("epsilon", 1e-8)
    lr = _lr(ins)
    if isinstance(g, SelectedRows):
        m = g.merged()
        if not op.attr("lazy_mode", False):
            # reference default (adam_op.h AdamFunctor over a densified
            # grad): EVERY row decays its moments and moves, untouched rows
            # with g=0.  Scatter the slab dense and fall through to the
            # dense math — correct-by-construction; users wanting the
            # touched-rows-only fast path opt in via lazy_mode=True.
            g = jnp.zeros(p.shape, m.values.dtype).at[m.rows].add(
                m.values, mode="drop")
        else:
            # lazy_mode: row-wise moment updates on touched rows only
            # (reference SparseAdamFunctor), beta powers advance globally
            lr_t = lr * jnp.sqrt(1.0 - b2p) / (1.0 - b1p)
            m1r = beta1 * _rows_gather(m1, m.rows) + (1.0 - beta1) * m.values
            m2r = beta2 * _rows_gather(m2, m.rows) + (1.0 - beta2) * jnp.square(m.values)
            upd = lr_t * m1r / (jnp.sqrt(m2r) + eps)
            return {
                "ParamOut": p.at[m.rows].add(-upd.astype(p.dtype), mode="drop"),
                "Moment1Out": m1.at[m.rows].set(m1r.astype(m1.dtype), mode="drop"),
                "Moment2Out": m2.at[m.rows].set(m2r.astype(m2.dtype), mode="drop"),
                "Beta1PowOut": (b1p * beta1).reshape((1,)),
                "Beta2PowOut": (b2p * beta2).reshape((1,)),
            }
    m1n = beta1 * m1 + (1.0 - beta1) * g
    m2n = beta2 * m2 + (1.0 - beta2) * jnp.square(g)
    lr_t = lr * jnp.sqrt(1.0 - b2p) / (1.0 - b1p)
    p_new = p - lr_t * m1n / (jnp.sqrt(m2n) + eps)
    return {
        "ParamOut": p_new,
        "Moment1Out": m1n,
        "Moment2Out": m2n,
        "Beta1PowOut": (b1p * beta1).reshape((1,)),
        "Beta2PowOut": (b2p * beta2).reshape((1,)),
    }


@register_opt("adagrad")
def _adagrad(ctx, op, ins):
    p = first(ins, "Param")
    g = first(ins, "Grad")
    moment = first(ins, "Moment")
    eps = op.attr("epsilon", 1e-6)
    lr = _lr(ins)
    if isinstance(g, SelectedRows):
        m = g.merged()
        mr = _rows_gather(moment, m.rows) + jnp.square(m.values)
        upd = lr * m.values / (jnp.sqrt(mr) + eps)
        return {
            "ParamOut": p.at[m.rows].add(-upd.astype(p.dtype), mode="drop"),
            "MomentOut": moment.at[m.rows].set(mr.astype(moment.dtype), mode="drop"),
        }
    m_new = moment + jnp.square(g)
    p_new = p - lr * g / (jnp.sqrt(m_new) + eps)
    return {"ParamOut": p_new, "MomentOut": m_new}


@register_opt("rmsprop")
def _rmsprop(ctx, op, ins):
    p = first(ins, "Param")
    g = first(ins, "Grad")
    ms = first(ins, "MeanSquare")
    mg = first(ins, "MeanGrad")
    mom = first(ins, "Moment")
    rho = op.attr("decay", 0.95)
    eps = op.attr("epsilon", 1e-6)
    momentum = op.attr("momentum", 0.0)
    centered = op.attr("centered", False)
    lr = _lr(ins)
    ms_new = rho * ms + (1.0 - rho) * jnp.square(g)
    if centered:
        mg_new = rho * mg + (1.0 - rho) * g
        denom = jnp.sqrt(ms_new - jnp.square(mg_new) + eps)
    else:
        mg_new = mg
        denom = jnp.sqrt(ms_new + eps)
    mom_new = momentum * mom + lr * g / denom
    return {
        "ParamOut": p - mom_new,
        "MeanSquareOut": ms_new,
        "MeanGradOut": mg_new,
        "MomentOut": mom_new,
    }


@register_opt("adamax")
def _adamax(ctx, op, ins):
    p = first(ins, "Param")
    g = first(ins, "Grad")
    m = first(ins, "Moment")
    inf_norm = first(ins, "InfNorm")
    b1p = first(ins, "Beta1Pow").reshape(())
    beta1 = op.attr("beta1", 0.9)
    beta2 = op.attr("beta2", 0.999)
    eps = op.attr("epsilon", 1e-8)
    lr = _lr(ins)
    m_new = beta1 * m + (1.0 - beta1) * g
    inf_new = jnp.maximum(beta2 * inf_norm, jnp.abs(g))
    lr_t = lr / (1.0 - b1p)
    p_new = p - lr_t * m_new / (inf_new + eps)
    return {"ParamOut": p_new, "MomentOut": m_new, "InfNormOut": inf_new}


@register_opt("adadelta")
def _adadelta(ctx, op, ins):
    p = first(ins, "Param")
    g = first(ins, "Grad")
    avg_sq_grad = first(ins, "AvgSquaredGrad")
    avg_sq_upd = first(ins, "AvgSquaredUpdate")
    rho = op.attr("rho", 0.95)
    eps = op.attr("epsilon", 1e-6)
    g2 = rho * avg_sq_grad + (1.0 - rho) * jnp.square(g)
    update = -jnp.sqrt((avg_sq_upd + eps) / (g2 + eps)) * g
    u2 = rho * avg_sq_upd + (1.0 - rho) * jnp.square(update)
    return {"ParamOut": p + update, "AvgSquaredGradOut": g2, "AvgSquaredUpdateOut": u2}


@register_opt("lamb")
def _lamb(ctx, op, ins):
    p = first(ins, "Param")
    g = first(ins, "Grad")
    m1 = first(ins, "Moment1")
    m2 = first(ins, "Moment2")
    b1p = first(ins, "Beta1Pow").reshape(())
    b2p = first(ins, "Beta2Pow").reshape(())
    beta1 = op.attr("beta1", 0.9)
    beta2 = op.attr("beta2", 0.999)
    eps = op.attr("epsilon", 1e-6)
    wd = op.attr("weight_decay", 0.0)
    lr = _lr(ins)
    m1n = beta1 * m1 + (1.0 - beta1) * g
    m2n = beta2 * m2 + (1.0 - beta2) * jnp.square(g)
    mhat = m1n / (1.0 - b1p)
    vhat = m2n / (1.0 - b2p)
    r = mhat / (jnp.sqrt(vhat) + eps) + wd * p
    w_norm = jnp.sqrt(jnp.sum(jnp.square(p)))
    r_norm = jnp.sqrt(jnp.sum(jnp.square(r)))
    ratio = jnp.where((w_norm > 0) & (r_norm > 0), w_norm / r_norm, 1.0)
    return {
        "ParamOut": p - lr * ratio * r,
        "Moment1Out": m1n,
        "Moment2Out": m2n,
        "Beta1PowOut": (b1p * beta1).reshape((1,)),
        "Beta2PowOut": (b2p * beta2).reshape((1,)),
    }


@register_opt("ftrl")
def _ftrl(ctx, op, ins):
    p = first(ins, "Param")
    g = first(ins, "Grad")
    sq = first(ins, "SquaredAccumulator")
    lin = first(ins, "LinearAccumulator")
    l1 = op.attr("l1", 0.0)
    l2 = op.attr("l2", 0.0)
    lr_power = op.attr("lr_power", -0.5)
    lr = _lr(ins)
    new_sq = sq + jnp.square(g)
    sigma = (jnp.power(new_sq, -lr_power) - jnp.power(sq, -lr_power)) / lr
    new_lin = lin + g - sigma * p
    quad = jnp.power(new_sq, -lr_power) / lr + 2.0 * l2
    pre = jnp.clip(new_lin, -l1, l1) - new_lin
    p_new = jnp.where(jnp.abs(new_lin) > l1, pre / quad, jnp.zeros_like(p))
    return {"ParamOut": p_new, "SquaredAccumOut": new_sq, "LinearAccumOut": new_lin}


@register_op("update_loss_scaling")
def _update_loss_scaling(ctx, op, ins):
    """Dynamic loss-scaling state machine (reference:
    contrib/mixed_precision/decorator.py _increment/_decrement logic):
    N consecutive finite steps multiply the scale by incr_ratio; M overflow
    steps within a window multiply by decr_ratio (floored at 1.0)."""
    fi = first(ins, "FoundInf").reshape(())
    s = first(ins, "LossScaling").reshape(())
    good = first(ins, "GoodSteps").reshape(())
    bad = first(ins, "BadSteps").reshape(())
    incr_n = op.attr("incr_every_n_steps", 1000)
    decr_n = op.attr("decr_every_n_nan_or_inf", 2)
    incr_ratio = op.attr("incr_ratio", 2.0)
    decr_ratio = op.attr("decr_ratio", 0.5)
    good_new = jnp.where(fi, 0, good + 1)
    bad_new = jnp.where(fi, bad + 1, 0)
    do_incr = good_new >= incr_n
    do_decr = bad_new >= decr_n
    # keep the old scale if growth would overflow (reference
    # update_loss_scaling_op.h keeps pre-update scale when non-finite)
    grown = s * incr_ratio
    s_new = jnp.where(do_incr & jnp.isfinite(grown), grown, s)
    s_new = jnp.where(do_decr, jnp.maximum(s * decr_ratio, 1.0), s_new)
    good_new = jnp.where(do_incr, 0, good_new)
    bad_new = jnp.where(do_decr, 0, bad_new)
    return {
        "LossScalingOut": s_new.reshape((1,)),
        "GoodStepsOut": good_new.reshape((1,)).astype(good.dtype),
        "BadStepsOut": bad_new.reshape((1,)).astype(bad.dtype),
    }


@register_opt("lars_momentum")
def _lars_momentum(ctx, op, ins):
    """reference optimizers/lars_momentum_op.cc: layer-adaptive rate
    scaling — local_lr = lr * lars_coeff * ||p|| / (||g|| + wd * ||p||),
    then plain momentum with weight decay folded into the gradient."""
    p = first(ins, "Param")
    g = first(ins, "Grad")
    v = first(ins, "Velocity")
    mu = op.attr("mu", 0.9)
    lars_coeff = op.attr("lars_coeff", 0.001)
    wd = op.attr("lars_weight_decay", 0.0005)
    eps = op.attr("epsilon", 0.0)
    lr = _lr(ins)
    p_norm = jnp.sqrt(jnp.sum(jnp.square(p.astype(jnp.float32))))
    g_norm = jnp.sqrt(jnp.sum(jnp.square(g.astype(jnp.float32))))
    local_lr = jnp.where(
        (p_norm > 0) & (g_norm > 0),
        lr * lars_coeff * p_norm / (g_norm + wd * p_norm + eps),
        lr,
    )
    v_new = mu * v + local_lr * (g + wd * p)
    return {"ParamOut": p - v_new, "VelocityOut": v_new}


@register_op("model_average_accum")
def _model_average_accum(ctx, op, ins):
    """Bounded-window parameter accumulation for ModelAverage (reference
    optimizer.py:2241 rotates sum_1/sum_2/sum_3 windows; here one
    sum+count pair halves when the count reaches max_average_window, which
    bounds the effective window to ~2x max while staying O(1) state).
    Count is read pre-step (the paired model_average_count op, appended
    after every accum, owns the increment) so all params halve together."""
    s = first(ins, "Sum")
    cnt = first(ins, "Count").reshape(())
    p = first(ins, "Param")
    max_w = op.attr("max_average_window", 10000)
    s2 = s + p.astype(s.dtype)
    over = (cnt + 1.0) >= max_w
    return {"SumOut": jnp.where(over, s2 * 0.5, s2)}


@register_op("model_average_count")
def _model_average_count(ctx, op, ins):
    cnt = first(ins, "Count").reshape(())
    max_w = op.attr("max_average_window", 10000)
    c2 = cnt + 1.0
    return {"CountOut": jnp.where(c2 >= max_w, c2 * 0.5, c2).reshape((1,))}


@register_opt("dpsgd")
def _dpsgd(ctx, op, ins):
    """reference optimizers/dpsgd_op.cc: differentially-private SGD —
    per-batch gradient L2-clipped to `clip`, Gaussian noise sigma*clip
    added, then a plain SGD step."""
    p = first(ins, "Param")
    g = first(ins, "Grad")
    clip = op.attr("clip", 10.0)
    sigma = op.attr("sigma", 1.0)
    batch_size = op.attr("batch_size", 16.0)
    lr = _lr(ins)
    gf = g.astype(jnp.float32)
    norm = jnp.sqrt(jnp.sum(jnp.square(gf)))
    scale = jnp.minimum(1.0, clip / jnp.maximum(norm, 1e-12))
    # reference dpsgd_op.h: update = (clipped_grad + sigma*clip*z) / batch
    noise = sigma * clip * jax.random.normal(ctx.next_key(), g.shape, jnp.float32)
    upd = (gf * scale + noise) / batch_size
    return {"ParamOut": p - lr * upd}


@register_op("dgc")
def _dgc(ctx, op, ins):
    """Deep Gradient Compression transform (reference dgc_op.cc, appended
    by DGCMomentumOptimizer optimizer.py:786): U = m*U + G, V += U, send
    top-k of |V|, clear BOTH buffers at the sent coordinates (momentum
    factor masking).  GradOut is the dense scatter of the selected values;
    the regular momentum op consumes it downstream, as in the reference.

    TPU notes: under GSPMD the gradient arrives already summed over dp (the
    wire-compression role is subsumed by XLA's ICI all-reduce; the genuine
    multi-worker sparse exchange lives in parallel/dgc.py for DCN-spanning
    deployments), so this op preserves the part that shapes training
    dynamics — sparsified updates with error feedback — with W=1 semantics.
    The data-dependent k is handled statically: top_k at the largest ramp k,
    then a rank mask for the current step's k."""
    g = first(ins, "Grad").astype(jnp.float32)
    u = first(ins, "U").astype(jnp.float32)
    v = first(ins, "V").astype(jnp.float32)
    step = first(ins, "CurrentStep").reshape(()).astype(jnp.float32)
    m = op.attr("m", 0.9)
    rampup_begin = float(op.attr("rampup_begin_step", 0.0))
    rampup_step = float(op.attr("rampup_step", 1.0))
    sparsity = list(op.attr("sparsity", [0.999]))
    clip_norm = float(op.attr("clip_norm", 0.0))

    if clip_norm > 0:  # reference dgc_clip_by_norm on the local grad
        norm = jnp.sqrt(jnp.sum(jnp.square(g)))
        g = g * (clip_norm / jnp.maximum(norm, clip_norm))

    numel = int(np.prod(g.shape))
    k_list = [max(1, int(numel * (1.0 - s))) for s in sparsity]
    k_max = max(k_list)
    # sparsity ramp: index advances every rampup_step/len(sparsity) steps
    period = max(rampup_step / len(sparsity), 1e-9)
    idx = jnp.clip(jnp.floor((step - rampup_begin) / period),
                   0, len(sparsity) - 1).astype(jnp.int32)
    k_cur = jnp.take(jnp.asarray(k_list, jnp.int32), idx)

    u2 = m * u + g
    v2 = v + u2
    flat = v2.reshape(-1)
    _, top_idx = jax.lax.top_k(jnp.abs(flat), k_max)
    sel = jnp.arange(k_max) < k_cur  # top_k is sorted: rank < k_cur
    dense = jnp.zeros_like(flat).at[top_idx].set(
        jnp.where(sel, flat[top_idx], 0.0))
    cleared = jnp.zeros_like(flat, dtype=bool).at[top_idx].set(sel)
    u3 = jnp.where(cleared.reshape(g.shape), 0.0, u2)
    v3 = jnp.where(cleared.reshape(g.shape), 0.0, v2)

    active = step >= rampup_begin
    return {
        "GradOut": jnp.where(active, dense.reshape(g.shape), g),
        "UOut": jnp.where(active, u3, u),
        "VOut": jnp.where(active, v3, v),
    }


@register_opt("proximal_gd")
def _proximal_gd(ctx, op, ins):
    """reference proximal_gd_op.h: prox = p - lr*g;
    p' = sign(prox) * max(|prox| - lr*l1, 0) / (1 + lr*l2)."""
    p = first(ins, "Param")
    g = first(ins, "Grad")
    lr = _lr(ins)
    l1 = op.attr("l1", 0.0)
    l2 = op.attr("l2", 0.0)
    prox = p - lr * g
    p_new = (jnp.sign(prox) * jnp.maximum(jnp.abs(prox) - lr * l1, 0.0)
             / (1.0 + lr * l2))
    return {"ParamOut": p_new}


@register_opt("proximal_adagrad")
def _proximal_adagrad(ctx, op, ins):
    """reference proximal_adagrad_op.h: moment += g^2; only the gradient
    step is scaled by 1/sqrt(moment) — the l1 threshold and the (1+lr*l2)
    denominator use the RAW lr, not the effective one."""
    p = first(ins, "Param")
    g = first(ins, "Grad")
    m = first(ins, "Moment")
    lr = _lr(ins)
    l1 = op.attr("l1", 0.0)
    l2 = op.attr("l2", 0.0)
    m_new = m + jnp.square(g)
    prox = p - (lr / jnp.sqrt(m_new)) * g
    p_new = (jnp.sign(prox) * jnp.maximum(jnp.abs(prox) - lr * l1, 0.0)
             / (1.0 + lr * l2))
    return {"ParamOut": p_new, "MomentOut": m_new}


# --- build-time shape/dtype inference --------------------------------------
# Every optimizer update writes `<Slot>Out` mirroring `<Slot>`'s
# shape/dtype; Grad must match Param (reference: each optimizer op's
# InferShape asserts exactly this before the kernel runs).

from ..core import analysis as _A

_A.register_state_update_infer(
    "sgd", "momentum", "adam", "adagrad", "rmsprop", "adamax", "adadelta",
    "lamb", "ftrl", "lars_momentum", "dpsgd", "proximal_gd",
    "proximal_adagrad")

# Static cost rules (core/resource_plan.py): optimizer updates are pure
# bandwidth — every state slot reads + writes its full size per step (the
# donation audit's point: aliasing saves RESIDENCY, not traffic).

from ..core import resource_plan as _RP

_RP.register_state_update_cost(
    "sgd", "momentum", "adam", "adagrad", "rmsprop", "adamax", "adadelta",
    "lamb", "ftrl", "lars_momentum", "dpsgd", "proximal_gd",
    "proximal_adagrad")
