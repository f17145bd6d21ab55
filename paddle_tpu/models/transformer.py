"""Transformer blocks: the BERT-style masked-LM encoder and the causal
decoder LM with routed experts (OLMoE's block), from one attention builder
and one layer builder.

Reference builds transformers from the same primitive layers
(tests/unittests/dist_transformer.py; BERT-base is the BASELINE.md pod
target).  This builder emits fc/matmul/layer_norm/softmax program ops;
attention is plain batched matmul, which XLA maps onto the MXU.

What differs between the 2018 block and today's is an argument of the shared
builders, not a second library: the norm (`layer` / `rms`), where it sits
(post / pre), the positions (a learned table added to the embedding /
rotary, applied to queries and keys), q/k-norm, projection biases, and the
feed-forward part (dense GELU / routed gated-SiLU experts).  `build_bert`
and `build_causal_lm` pick them.

`tp_rules()` returns the sharding-hint ruleset for Megatron-style tensor
parallelism (QKV/FFN1 column-parallel, proj/FFN2 row-parallel) — a new
capability vs the reference (SURVEY.md §2c: TP absent in 2019).
"""
from __future__ import annotations

import contextlib
import re

import numpy as np

from .. import layers, optimizer
from ..core.initializer import (ConstantInitializer, NormalInitializer, NumpyArrayInitializer,
                                SoftplusInverseLogUniformInitializer, UniformInitializer)
from ..core.param_attr import ParamAttr
from ..core.program import Program, name_scope, program_guard, recompute_scope


def _attr(name, std=0.02, seed=0):
    """N(0, std); `seed` 0 draws from the program's own stream (its
    `random_seed`), another pins this parameter's values whatever that is."""
    return ParamAttr(name=name, initializer=NormalInitializer(0.0, std, seed))


def _attr_ones(name):
    """A norm's gain in the causal LM: 1, as its sources initialise it.
    (`build_bert` draws its layer-norm gains N(0, 0.02) like every other
    parameter: PERF.md section 7, defect 3.)"""
    return ParamAttr(name=name, initializer=ConstantInitializer(1.0))


def multi_head_attention(x, seq_len, d_model, n_heads, prefix, dropout_prob=0.1, is_test=False,
                         use_ring_attention=False, causal=False, kv=None, bias=None,
                         use_fused_attention=False, proj_bias=True,
                         qk_norm_eps=None, positions=None, rope_theta=10000.0,
                         n_kv_heads=None, head_dim=None, qk_norm_per_head=False,
                         mask=None, mask_block=None, keep=None, kept_kv=None, sparse_index=None, index_losses=None,
                         head_gate=False):
    """Self- or cross-attention over [b, T, d] (T may be dynamic: head
    split/merge uses fluid's 0-copy-dim reshape).  `kv` switches to
    cross-attention (keys/values from another sequence); `bias` is an
    additive [b, 1, Tq, Tk] pre-softmax mask (layers.attention_bias).
    Serves the fixed-length BERT builder, the ragged NMT model and the
    causal LM: `proj_bias=False` drops the four projection biases,
    `qk_norm_eps` puts an RMS norm over the whole width of the projected
    queries and of the keys (before the heads are split, as OLMoE has it) or,
    with `qk_norm_per_head`, over each head's features with one gain of the
    head's width shared by the heads (after the split, as Qwen3 has it);
    `positions` ([b, T] integers) rotates queries and keys; `rope_theta` is
    the base, or the embedding's whole description, dict(theta=, rotary_dim=,
    inv_freq=, scale=) as `layers.rotary_embedding` takes them: how many of a
    head's leading features turn, a table of frequencies where they are not
    theta's own (`yarn_frequencies`), a factor on cos and sin.

    `head_gate` (True or "head") gates each head's output before the out
    projection (the head-wise gated attention of Qiu et al. 2025,
    arXiv:2505.06708): g = sigmoid(x Wg), Wg [d_model, n_heads], ONE number a
    head a token, read from the layer's own (normed) input in float32; the
    attention's output, in the layout the attention left it, is multiplied by it
    in float32 and rounded once.  `head_gate="feature"` is the same paper's
    gate a FEATURE (Qwen3-Next's): the gate's columns ride in the query
    projection, x Wq [d_model, n_heads x 2 head_dim], a head's head_dim query
    features and then its head_dim gate features; the attention's output, heads
    merged, is multiplied by sigmoid of them in float32 and rounded once, n_heads
    x head_dim numbers a token and no matrix of its own.  Either form's sigmoid
    and product (and the form a head's projection) stand in the scope
    `attention_gate`.

    `n_kv_heads` (a divisor of `n_heads`) gives keys and values fewer heads
    than queries, and `head_dim` a head width other than d_model / n_heads:
    q projects to n_heads x head_dim, k and v to n_kv_heads x head_dim, the
    output from n_heads x head_dim back to d_model.  `mask` / `mask_block`
    are `layers.fused_attention`'s structured mask (fused attention only).

    With `use_fused_attention`, no per-head norm and no `positions` the heads
    are split by a reshape alone and the attention is handed (B, L, H, dh)
    (`layout="blhd"`): the program holds no transpose round it.  Otherwise
    the heads are transposed to the front, (B, H, L, dh), as the per-head
    norm, the rotary embedding and the other two attentions are written.

    A layer may hand its keys and values on, and another read them (SambaY's
    cross-decoder, Ren et al. 2025): `keep` is a dict into which this layer
    puts `keep["kv"]` = (k, v), each (B, L, n_kv_heads, head_dim) as projected
    (bias included); `kept_kv` is such a pair, and the layer then has NO key or
    value weights: its own queries attend to the kept tensors and its own out
    projection follows.  (`kv=` is something else: another SEQUENCE projected
    with THIS layer's weights.)  Both take the fused attention's (B, L, H, dh).

    `sparse_index` = dict(heads=, head_dim=, topk=) makes the mask DATA
    (DeepSeek Sparse Attention): an indexer of its own beside the projections
    chooses, every step, the `topk` keys each query may see, and the attention
    runs over those alone (`layers.sparse_index`, `fused_attention(picks=)`).
    The indexer reads the layer's input DETACHED (`layers.stop_gradient`): qI =
    x WqI, `heads` of `head_dim`; kI = LayerNorm(x WkI), ONE head of `head_dim`
    with a gain and a bias; w = x Ww, a float32 weight an index head; qI and kI
    carry the layer's rotary embedding over their whole width.  What trains it
    is `layers.index_alignment`, the divergence between the attention's
    head-summed probabilities over the picks and the softmax of the index scores
    over them.  `index_losses` receives a CALL that appends that op and returns
    the term [1]: the caller makes it where the term should stand
    (`build_causal_lm`: after the layer's `recompute_scope`, so that the term,
    whose forward pass makes its gradients too, is no part of what backward
    makes again) and adds it to the loss.  The indexer's ops stand in the scope
    `sparse_index`.  It takes the fused
    attention over heads-major operands (a per-head norm or `positions`)."""
    d_head = head_dim or d_model // n_heads
    n_kv_heads = n_kv_heads or n_heads
    kv_in = kv if kv is not None else x

    def project(t, name, width=d_model):
        return layers.fc(t, width, num_flatten_dims=2, param_attr=_attr(f"{prefix}.{name}.w"),
                         bias_attr=_attr(f"{prefix}.{name}.b") if proj_bias else False)

    if head_gate not in (False, True, "head", "feature"):
        raise ValueError(f"multi_head_attention: head_gate={head_gate!r}; True or \"head\" (a number a head), or \"feature\"")
    feature_gate = None
    if head_gate == "feature":   # [q | gate] a head in one projection: the query's features go on as ever
        both = layers.reshape(project(x, "q", n_heads * 2 * d_head), [0, 0, n_heads, 2 * d_head])
        q = layers.reshape(layers.slice(both, axes=[3], starts=[0], ends=[d_head]), [0, 0, n_heads * d_head])
        with name_scope("attention_gate"):
            feature_gate = layers.slice(both, axes=[3], starts=[d_head], ends=[2 * d_head])
    else:
        q = project(x, "q", n_heads * d_head)
    if kept_kv is None:
        k, v = project(kv_in, "k", n_kv_heads * d_head), project(kv_in, "v", n_kv_heads * d_head)
    if qk_norm_eps is not None and not qk_norm_per_head:
        q = layers.rms_norm(q, begin_norm_axis=2, epsilon=qk_norm_eps,
                            param_attr=_attr_ones(f"{prefix}.q_norm.w"))
        k = layers.rms_norm(k, begin_norm_axis=2, epsilon=qk_norm_eps,
                            param_attr=_attr_ones(f"{prefix}.k_norm.w"))

    # Heads-major, (B, H, L, dh), is what the per-head norm, the rotary embedding and the unfused and ring attentions
    # are written over.  Where none of them stands between the head split and a fused attention, the projections keep
    # their own layout, (B, L, H, dh), and `fused_attention(layout="blhd")` reads it: no `transpose2` in the program.
    per_head_norm = qk_norm_eps is not None and qk_norm_per_head
    heads_major = not use_fused_attention or per_head_norm or positions is not None
    if (keep is not None or kept_kv is not None) and (heads_major or qk_norm_eps is not None):
        raise ValueError("keep= / kept_kv=: keys and values are kept, and kept ones read, as the fused attention's "
                         "(B, L, H, dh): use_fused_attention=True, and no q/k-norm and no rotary positions (qk_norm_eps=, "
                         "positions=) stand between the projection and the attention")

    def split_heads(t, heads):
        t = layers.reshape(t, [0, 0, heads, d_head])
        return layers.transpose(t, [0, 2, 1, 3]) if heads_major else t

    q = split_heads(q, n_heads)
    k, v = kept_kv if kept_kv is not None else (split_heads(k, n_kv_heads), split_heads(v, n_kv_heads))
    if keep is not None:
        keep["kv"] = (k, v)
    if per_head_norm:
        q = layers.rms_norm(q, begin_norm_axis=3, epsilon=qk_norm_eps,
                            param_attr=_attr_ones(f"{prefix}.q_norm.w"))
        k = layers.rms_norm(k, begin_norm_axis=3, epsilon=qk_norm_eps,
                            param_attr=_attr_ones(f"{prefix}.k_norm.w"))
    rope = dict(rope_theta) if isinstance(rope_theta, dict) else {"theta": rope_theta}
    if positions is not None:
        q = layers.rotary_embedding(q, positions, **rope)
        k = layers.rotary_embedding(k, positions, **rope)
    if (mask is not None or n_kv_heads != n_heads) and not use_fused_attention:
        raise ValueError("mask= and n_kv_heads= (a structured mask, grouped key/value heads) are fused_attention's: "
                         "use_fused_attention=True")
    picks = None
    if sparse_index is not None:
        if not (use_fused_attention and heads_major) or kv is not None or kept_kv is not None or mask is not None:
            raise ValueError("sparse_index=: the chosen keys are fused_attention's (use_fused_attention=True) over "
                             "heads-major operands of the layer's own sequence (a per-head norm or positions=; no kv=, "
                             "kept_kv= or mask=)")
        if set(rope) != {"theta"}:
            raise ValueError("sparse_index=: the indexer's heads have a width of their own and turn whole, by theta alone "
                             "(rope_theta= a number)")
        rope_theta = rope["theta"]
        index_heads, index_dim = sparse_index["heads"], sparse_index["head_dim"]
        with name_scope("sparse_index"):
            detached = layers.stop_gradient(x)      # the indexer trains on its own loss: nothing of it reaches x
            q_index = layers.reshape(project(detached, "index.q", index_heads * index_dim), [0, 0, index_heads, index_dim])
            k_index = layers.layer_norm(
                project(detached, "index.k", index_dim), begin_norm_axis=2, epsilon=1e-6,
                param_attr=_attr_ones(f"{prefix}.index.k_norm.w"),
                bias_attr=ParamAttr(name=f"{prefix}.index.k_norm.b", initializer=ConstantInitializer(0.0)))
            k_index = layers.reshape(k_index, [0, 0, 1, index_dim])
            weights = layers.fc(layers.cast(detached, "float32"), index_heads, num_flatten_dims=2,
                                param_attr=_attr(f"{prefix}.index.w.w"), bias_attr=False)
            if positions is not None:
                q_index = layers.rotary_embedding(q_index, positions, theta=rope_theta, layout="blhd")
                k_index = layers.rotary_embedding(k_index, positions, theta=rope_theta, layout="blhd")
            picks = layers.sparse_index(q_index, k_index, weights, sparse_index["topk"])
    if use_fused_attention:
        # Pallas flash kernel: scores never hit HBM.  Attention-prob dropout
        # can't run inside the fused kernel; the equivalent regularization
        # goes on the attention output (same substitution as the ring path).
        handed = {}
        ctx = layers.fused_attention(q, k, v, bias=bias, causal=causal, mask=mask, mask_block=mask_block,
                                     layout="bhld" if heads_major else "blhd", kept_kv=kept_kv is not None,
                                     picks=picks, picks_topk=sparse_index and sparse_index["topk"], keep=handed)
        if picks is not None:
            def alignment_term():
                with name_scope("sparse_index"):
                    return layers.index_alignment(q_index, k_index, weights, picks, q, k, handed["lse"])

            index_losses.append(alignment_term)
        if dropout_prob and not is_test:
            ctx = layers.dropout(ctx, dropout_prob, is_test=is_test,
                                 dropout_implementation="upscale_in_train")
    elif use_ring_attention:
        # sequence-parallel blockwise attention (L shards over the sp axis);
        # attention-prob dropout can't be applied inside the ring, so the
        # equivalent regularization goes on the attention output instead
        ctx = layers.ring_attention(q, k, v, causal=causal)
        if dropout_prob and not is_test:
            ctx = layers.dropout(ctx, dropout_prob, is_test=is_test,
                                 dropout_implementation="upscale_in_train")
    else:
        scores = layers.matmul(q, k, transpose_y=True, alpha=1.0 / np.sqrt(d_head))
        if bias is not None:
            scores = layers.elementwise_add(scores, bias)
        attn = layers.softmax(scores)
        if dropout_prob and not is_test:
            attn = layers.dropout(attn, dropout_prob, is_test=is_test,
                                  dropout_implementation="upscale_in_train")
        ctx = layers.matmul(attn, v)  # (B, H, L, dh)
    if head_gate and feature_gate is None:   # in the layout the attention left: the one transpose that follows feeds
        with name_scope("attention_gate"):   # the out projection as before
            ctx = _head_gate(x, ctx, n_heads, f"{prefix}.gate.w", heads_major)
    if heads_major:
        ctx = layers.transpose(ctx, [0, 2, 1, 3])
    if feature_gate is not None:             # in the projections' own layout, (B, L, H, dh): the gate is never transposed
        with name_scope("attention_gate"):
            ctx = _feature_gate(ctx, feature_gate)
    ctx = layers.reshape(ctx, [0, 0, n_heads * d_head])
    return project(ctx, "out")


def _head_gate(x, ctx, n_heads, name, heads_major):
    """ctx, (B, L, H, dh) or `heads_major` (B, H, L, dh), times sigmoid(x Wg)
    (B, L, H), one number a head a token: the projection reads x in float32 at
    the highest precision, as a router's does (the chip's default rounds the
    float32 matrix to bf16: 4e-3 of a small gate, a bf16 gate's own error), the
    sigmoid and the product are float32 and the result is rounded once, to
    ctx's dtype."""
    gate = layers.sigmoid(layers.fc(layers.cast(x, "float32"), n_heads, num_flatten_dims=2, param_attr=_attr(name),
                                    bias_attr=False, precision="highest"))
    gate = (layers.reshape(layers.transpose(gate, [0, 2, 1]), [0, n_heads, 0, 1]) if heads_major
            else layers.reshape(gate, [0, 0, n_heads, 1]))
    return layers.cast(layers.elementwise_mul(layers.cast(ctx, "float32"), gate), ctx.dtype)


def _feature_gate(ctx, gate):
    """ctx (B, L, H, dh) times sigmoid(gate), a number a FEATURE a token, the
    gate as the query projection made it (B, L, H, dh): the sigmoid and the
    product in float32, rounded once to ctx's dtype, as `_head_gate`'s are."""
    gate = layers.sigmoid(layers.cast(gate, "float32"))
    return layers.cast(layers.elementwise_mul(layers.cast(ctx, "float32"), gate), ctx.dtype)


def yarn_frequencies(theta, rotary_dim, factor, original_max_position_embeddings, beta_fast=32.0, beta_slow=1.0):
    """The `rotary_dim / 2` frequencies of a rotary embedding stretched by YaRN
    (Peng et al. 2023, arXiv:2309.00071), as a tuple for
    `layers.rotary_embedding(inv_freq=)`: pair i keeps theta's own frequency
    theta^(-2i / rotary_dim) where it turns more than `beta_fast` times over the
    original context, takes a `factor`-th of it where it turns fewer than
    `beta_slow` times, and a linear blend between:

        f_i = (1 - r_i) theta^(-2i/r) + r_i theta^(-2i/r) / factor,   r_i = clip((i - low) / (high - low), 0, 1),
        low = floor(d(beta_fast)), high = ceil(d(beta_slow)),   d(n) = r ln(original / (2 pi n)) / (2 ln theta),

    both bounds held within [0, r - 1], as the public implementation of
    `rope_type` "yarn" holds them.  The factor on cos and sin that goes with it
    (0.1 ln factor + 1 by YaRN's rule) is `rotary_embedding`'s `scale`."""
    pairs = rotary_dim // 2
    own = float(theta) ** (-np.arange(pairs, dtype=np.float64) / pairs)

    def turns(n):   # the pair that makes n turns over the original context
        return rotary_dim * np.log(original_max_position_embeddings / (n * 2 * np.pi)) / (2 * np.log(float(theta)))

    low, high = max(np.floor(turns(beta_fast)), 0), min(np.ceil(turns(beta_slow)), rotary_dim - 1)
    ramp = np.clip((np.arange(pairs, dtype=np.float64) - low) / max(high - low, 1e-3), 0.0, 1.0)
    return tuple(float(f) for f in own * (1.0 - ramp) + own / factor * ramp)


def latent_attention(x, d_model, n_heads, prefix, rank, nope_dim, rope_dim, v_dim, norm_eps=1e-5,
                     positions=None, rope_theta=10000.0, rope_interleave=False):
    """Causal attention whose keys and values are up-projected from one normed
    latent a token (multi-head latent attention): q = x Wq, `n_heads` heads of
    `nope_dim + rope_dim`; [c ; k_r] = x Wkva, `rank + rope_dim` wide;
    [k_n ; v] = rms(c) Wkvb, a head `nope_dim + v_dim`; head h's key is
    [k_n[h] ; k_r], the `rope_dim` part shared by all heads; softmax attention
    at scale (nope_dim + rope_dim)^-0.5 over keys wider than the values; the
    output from n_heads x v_dim back to d_model.  No biases.  The heads are
    split by reshapes alone and the attention is handed (B, L, H, dh).

    Without `positions` neither part carries a rotary embedding (Kimi Linear's
    global layers: `mla_use_nope`).  With `positions` ([b, T] integers) the
    decoupled rotary embedding of DeepSeek-V2/V3's family stands between the
    projections and the attention, in the scope `latent_attention/rotary`: each
    head's `rope_dim`-wide q_r and the ONE k_r a token are rotated
    (`rope_theta`; `rope_interleave` pairs feature 2i with 2i + 1 and not i
    with i + rope_dim / 2; float32 angles), and k_r is spread over the heads
    AFTER its rotation, so that it is rotated once and not `n_heads` times."""
    def project(t, name, width):
        return layers.fc(t, width, num_flatten_dims=2, param_attr=_attr(f"{prefix}.{name}.w"), bias_attr=False)

    def rotated(t):
        return layers.rotary_embedding(t, positions, theta=rope_theta, layout="blhd", interleave=rope_interleave)

    with name_scope("latent_attention"):
        qk_dim = nope_dim + rope_dim
        q = layers.reshape(project(x, "q", n_heads * qk_dim), [0, 0, n_heads, qk_dim])
        down = project(x, "kv_a", rank + rope_dim)
        latent = layers.slice(down, axes=[2], starts=[0], ends=[rank])
        k_shared = layers.slice(down, axes=[2], starts=[rank], ends=[rank + rope_dim])
        latent = layers.rms_norm(latent, begin_norm_axis=2, epsilon=norm_eps,
                                 param_attr=_attr_ones(f"{prefix}.kv_norm.w"))
        up = layers.reshape(project(latent, "kv_b", n_heads * (nope_dim + v_dim)), [0, 0, n_heads, nope_dim + v_dim])
        k_own = layers.slice(up, axes=[3], starts=[0], ends=[nope_dim])
        v = layers.slice(up, axes=[3], starts=[nope_dim], ends=[nope_dim + v_dim])
        k_shared = layers.reshape(k_shared, [0, 0, 1, rope_dim])
        if positions is None:
            k_shared = layers.expand(k_shared, [1, 1, n_heads, 1])
        else:
            with name_scope("rotary"):
                q = layers.concat([layers.slice(q, axes=[3], starts=[0], ends=[nope_dim]),
                                   rotated(layers.slice(q, axes=[3], starts=[nope_dim], ends=[qk_dim]))], axis=3)
                k_shared = layers.expand(rotated(k_shared), [1, 1, n_heads, 1])
        k = layers.concat([k_own, k_shared], axis=3)
        ctx = layers.fused_attention(q, k, v, causal=True, layout="blhd")
        return project(layers.reshape(ctx, [0, 0, n_heads * v_dim]), "out", d_model)


def _delta_rule_parts(prefix, conv_kernel, head_dim):
    """What the two delta-rule operators (`kimi_delta_attention`,
    `gated_delta_net`) share round the op `kda`: `project` (a matrix without a
    bias unless asked), `mixed` (the taps and the SiLU of `short_conv`'s plain
    mode, then the heads), `unit` (the L2 norm a head times a scale) and
    `decay_attrs` (how A_log and dt_bias are drawn: a decay of 0.2 to 0.999 a
    token)."""
    def project(t, name, out, bias=False):
        return layers.fc(t, out, num_flatten_dims=2, param_attr=_attr(f"{prefix}.{name}.w"),
                         bias_attr=ParamAttr(name=f"{prefix}.{name}.b", initializer=ConstantInitializer(0.0))
                         if bias else False)

    def mixed(t, name, heads):   # taps, SiLU, heads
        t = layers.short_conv(t, conv_kernel, gated=False, activation="silu", filter_attr=_attr(f"{prefix}.{name}_conv.w"))
        return layers.reshape(t, [0, 0, heads, head_dim]) if heads else t

    def unit(t, scale):   # t / sqrt(sum t^2 + 1e-6) . scale = rms(t; 1e-6 / head_dim) . head_dim^-0.5 . scale
        t = layers.rms_norm(t, begin_norm_axis=3, epsilon=1e-6 / head_dim, param_attr=False)
        return layers.scale(t, scale=scale * head_dim ** -0.5)

    decay_attrs = dict(
        a_log_attr=ParamAttr(name=f"{prefix}.a_log", initializer=UniformInitializer(0.0, float(np.log(16.0)))),
        dt_bias_attr=ParamAttr(name=f"{prefix}.dt_bias",
                               initializer=UniformInitializer(float(np.log(1e-3)), float(np.log(1e-1)))))
    return project, mixed, unit, decay_attrs


def kimi_delta_attention(x, d_model, n_heads, head_dim, prefix, conv_kernel=4, norm_eps=1e-5):
    """The Kimi-Delta-Attention operator (Kimi Linear, arXiv:2510.26692) round
    the op `kda`: q, k and v are each a projection to n_heads x head_dim, a
    depthwise causal convolution of `conv_kernel` taps and a SiLU (`short_conv`'s
    plain mode); q and k are L2-normalised a head (an RMS norm without a gain,
    eps 1e-6 on the sum of squares) and q scaled by head_dim^-0.5; the log decay
    a channel comes through a low-rank pair of width head_dim and `kda_gate`,
    the step beta = sigmoid(x Wb) a head in float32; the recurrence's output is
    RMS-normed a head (one gain of head_dim), gated by sigmoid of a second
    low-rank pair (a bias on its second matrix) and projected back."""
    width = n_heads * head_dim
    project, mixed, unit, decay_attrs = _delta_rule_parts(prefix, conv_kernel, head_dim)

    with name_scope("kda"):
        def taken(name):
            return mixed(project(x, name, width), name, n_heads)

        q, k, v = unit(taken("q"), head_dim ** -0.5), unit(taken("k"), 1.0), taken("v")
        g = layers.kda_gate(project(project(x, "f_a", head_dim), "f_b", width), n_heads, **decay_attrs)
        beta = layers.sigmoid(layers.cast(project(x, "b", n_heads), "float32"))
        o = layers.rms_norm(layers.kda(q, k, v, g, beta), begin_norm_axis=3, epsilon=norm_eps,
                            param_attr=_attr_ones(f"{prefix}.o_norm.w"))
        gate = layers.sigmoid(project(project(x, "g_a", head_dim), "g_b", width, bias=True))
        o = layers.elementwise_mul(o, layers.reshape(gate, [0, 0, n_heads, head_dim]))
        return project(layers.reshape(o, [0, 0, width]), "out", d_model)


def gated_delta_net(x, d_model, key_heads, value_heads, head_dim, prefix, conv_kernel=4, norm_eps=1e-6):
    """The Gated DeltaNet operator (Yang et al. 2024, arXiv:2412.06464, as
    Qwen3-Next has it) round the op `kda` with a decay of ONE number a head:
    [q | k | v | z] = x Wqkvz, `key_heads` heads of `head_dim` for q and for k,
    `value_heads` (a multiple of them) for v and for z; [b | alpha] = x Wba, a
    number a value head each.  The columns of [q | k | v] pass ONE depthwise
    causal convolution of `conv_kernel` taps and a SiLU; q and k are
    L2-normalised a head (eps 1e-6 on the sum of squares) and q scaled by
    head_dim^-0.5; beta = sigmoid(b) and g = -exp(A_log[h]) . softplus(alpha +
    dt_bias[h]) in float32 (`kda_gate` at a width of one a head); value head h
    reads key head h div (value_heads / key_heads) (`layers.kda`).  The
    recurrence's output is RMS-normed a head (one gain of head_dim), multiplied
    by silu(z) in float32, rounded once and projected back.  The whole operator
    stands in the scope `gated_delta_net`; the op keeps `kda_chunk_scan` inside."""
    keys, values = key_heads * head_dim, value_heads * head_dim
    project, mixed, unit, decay_attrs = _delta_rule_parts(prefix, conv_kernel, head_dim)

    def part(t, lo, hi, heads):
        return layers.reshape(layers.slice(t, axes=[2], starts=[lo], ends=[hi]), [0, 0, heads, (hi - lo) // heads])

    with name_scope("gated_delta_net"):
        qkvz = project(x, "qkvz", 2 * keys + 2 * values)
        taken = mixed(layers.slice(qkvz, axes=[2], starts=[0], ends=[2 * keys + values]), "qkv", None)
        q, k = unit(part(taken, 0, keys, key_heads), head_dim ** -0.5), unit(part(taken, keys, 2 * keys, key_heads), 1.0)
        v = part(taken, 2 * keys, 2 * keys + values, value_heads)
        ba = project(x, "ba", 2 * value_heads)
        beta = layers.sigmoid(layers.cast(layers.slice(ba, axes=[2], starts=[0], ends=[value_heads]), "float32"))
        g = layers.kda_gate(layers.slice(ba, axes=[2], starts=[value_heads], ends=[2 * value_heads]), value_heads, **decay_attrs)
        o = layers.rms_norm(layers.kda(q, k, v, layers.reshape(g, [0, 0, value_heads]), beta), begin_norm_axis=3,
                            epsilon=norm_eps, param_attr=_attr_ones(f"{prefix}.o_norm.w"))
        # the gate's product in the projections' own layout, [b, T, H . 128]: z is never split into heads (a head's 128
        # lanes of 4096 and a [32, 128] tile are two layouts on the chip: 2.1 GB of float32 copies an 8-row clone, PR 69)
        o, z = layers.reshape(o, [0, 0, values]), layers.slice(qkvz, axes=[2], starts=[2 * keys + values], ends=[2 * keys + 2 * values])
        o = layers.cast(layers.elementwise_mul(layers.cast(o, "float32"), layers.swish(layers.cast(z, "float32"))), o.dtype)
        return project(o, "out", d_model)


def mamba_mixer(x, d_model, prefix, expand=2, state=16, dt_rank=None, conv_kernel=4, norm_eps=1e-6,
                inner_norms=True, keep=None, taps_bound=None):
    """The Mamba-1 mixer (Gu & Dao 2023) round the op `selective_scan`: [xs, z]
    = split(x W_in), each `expand` x d_model wide; xs = silu(conv(xs)), a
    depthwise causal convolution of `conv_kernel` taps with a bias a channel
    (`short_conv`'s plain mode); [dt, B, C] = split(xs W_x), `dt_rank`, `state`
    and `state` wide; the step's projection dt W_dt back to the channels (its
    bias and the softplus are the op's, float32); the recurrence; y * silu(z),
    projected back.  No other biases.  A_log[c, n] = ln(n + 1), D = 1 and the
    step's bias the inverse softplus of a log-uniform draw on [1e-3, 1e-1], as
    Mamba initialises them.

    `inner_norms` (the default, as Jamba has it: Lieber et al. 2024,
    arXiv:2403.19887) RMS-norms dt, B and C each with a gain before they are
    used; False is the plain mixer, which has no such parameters.  `taps_bound`
    = b draws the convolution's taps U(-b, b) and not N(0, 0.02) with the other
    weights: Mamba's own code leaves them at `nn.Conv1d`'s default, b =
    conv_kernel^-0.5, and WITHOUT the inner norms it is the taps' size that
    decides how much of the output the state carries (at N(0, 0.02) B and C
    are of the weights' order and h C is ~5e-4 of the skip D x).  `keep` is a
    dict into which the layer puts `keep["memory"]` = y, the scan's output
    (b, T, `expand` x d_model) BEFORE the gate silu(z) and the out projection:
    what a later layer's Gated Memory Unit reads (`gated_memory_unit`)."""
    inner = expand * d_model
    dt_rank = dt_rank or -(-d_model // 16)

    def project(t, name, out):
        return layers.fc(t, out, num_flatten_dims=2, param_attr=_attr(f"{prefix}.{name}.w"), bias_attr=False)

    def part(t, name, lo, hi):
        t = layers.slice(t, axes=[2], starts=[lo], ends=[hi])
        if not inner_norms:
            return t
        return layers.rms_norm(t, begin_norm_axis=2, epsilon=norm_eps, param_attr=_attr_ones(f"{prefix}.{name}_norm.w"))

    with name_scope("mamba"):
        both = project(x, "in", 2 * inner)
        xs = layers.slice(both, axes=[2], starts=[0], ends=[inner])
        z = layers.slice(both, axes=[2], starts=[inner], ends=[2 * inner])
        taps = _attr(f"{prefix}.conv.w") if taps_bound is None else ParamAttr(
            name=f"{prefix}.conv.w", initializer=UniformInitializer(-float(taps_bound), float(taps_bound)))
        xs = layers.short_conv(xs, conv_kernel, gated=False, activation="silu", filter_attr=taps,
                               bias_attr=ParamAttr(name=f"{prefix}.conv.b", initializer=ConstantInitializer(0.0)))
        low = project(xs, "x", dt_rank + 2 * state)
        dt = project(part(low, "dt", 0, dt_rank), "dt", inner)
        b, c = part(low, "b", dt_rank, dt_rank + state), part(low, "c", dt_rank + state, dt_rank + 2 * state)
        with name_scope("selective_scan"):
            y = layers.selective_scan(
                xs, dt, b, c,
                a_log_attr=ParamAttr(name=f"{prefix}.a_log", initializer=NumpyArrayInitializer(
                    np.tile(np.log(np.arange(1, state + 1, dtype="float32")), (inner, 1)))),
                d_attr=ParamAttr(name=f"{prefix}.d", initializer=ConstantInitializer(1.0)),
                dt_bias_attr=ParamAttr(name=f"{prefix}.dt.b", initializer=SoftplusInverseLogUniformInitializer(1e-3, 1e-1)))
        if keep is not None:
            keep["memory"] = y
        return project(layers.elementwise_mul(y, layers.swish(z)), "out", d_model)


def mamba2_mixer(x, d_model, prefix, heads, head_dim, state, groups=1, conv_kernel=4, chunk=128, norm_eps=1e-5,
                 taps_bound=None):
    """The Mamba-2 mixer (Dao & Gu 2024, arXiv:2405.21060) round the op
    `ssd_scan`: ONE in-projection [z, xBC, dt] = split(x W_in), `heads` x
    `head_dim` (the inner width), the inner width + 2 x `groups` x `state`, and
    `heads` wide; xBC = silu(conv(xBC) + b), a depthwise causal convolution of
    `conv_kernel` taps over xs, B and C TOGETHER (`short_conv`'s plain mode with
    its bias); [xs, B, C] = split(xBC); the recurrence with one scalar decay a
    head (the step's bias and its softplus are the op's, float32), `chunk`
    tokens at a time; then the GATED norm, which gates before it norms and norms
    by group: rms(y * silu(z)) over each of the `groups` groups of inner /
    groups channels, one gain a channel; projected back.  No other biases.  A =
    1 .. 16 evenly over the heads (A_log its logarithm: Mamba-2 draws A
    U(1, 16)), D = 1 and the step's bias the inverse softplus of a log-uniform
    draw on [1e-3, 1e-1], as Mamba-2 initialises them.  `taps_bound` = b draws
    the convolution's taps U(-b, b) and not N(0, 0.02) with the other weights,
    as `mamba_mixer`'s does: Mamba-2's own code leaves them at `nn.Conv1d`'s
    default, b = conv_kernel^-0.5, and it is the taps' size that decides how
    much of the output the state carries beside the skip D x."""
    inner = heads * head_dim
    wide = groups * state

    def project(t, name, out):
        return layers.fc(t, out, num_flatten_dims=2, param_attr=_attr(f"{prefix}.{name}.w"), bias_attr=False)

    def part(t, lo, hi):
        return layers.slice(t, axes=[2], starts=[lo], ends=[hi])

    with name_scope("mamba2"):
        both = project(x, "in", 2 * inner + 2 * wide + heads)
        z, dt = part(both, 0, inner), part(both, 2 * inner + 2 * wide, 2 * inner + 2 * wide + heads)
        taps = _attr(f"{prefix}.conv.w") if taps_bound is None else ParamAttr(
            name=f"{prefix}.conv.w", initializer=UniformInitializer(-float(taps_bound), float(taps_bound)))
        xbc = layers.short_conv(part(both, inner, 2 * inner + 2 * wide), conv_kernel, gated=False, activation="silu",
                                filter_attr=taps,
                                bias_attr=ParamAttr(name=f"{prefix}.conv.b", initializer=ConstantInitializer(0.0)))
        y = layers.ssd_scan(
            part(xbc, 0, inner), dt, part(xbc, inner, inner + wide), part(xbc, inner + wide, inner + 2 * wide),
            heads, groups=groups, chunk=chunk,
            a_log_attr=ParamAttr(name=f"{prefix}.a_log", initializer=NumpyArrayInitializer(
                np.log(np.linspace(1.0, 16.0, heads)).astype("float32"))),
            d_attr=ParamAttr(name=f"{prefix}.d", initializer=ConstantInitializer(1.0)),
            dt_bias_attr=ParamAttr(name=f"{prefix}.dt_bias", initializer=SoftplusInverseLogUniformInitializer(1e-3, 1e-1)))
        gated = layers.reshape(layers.elementwise_mul(y, layers.swish(z)), [0, 0, groups, inner // groups])
        normed = layers.reshape(layers.rms_norm(gated, begin_norm_axis=3, epsilon=norm_eps, param_attr=False), [0, 0, inner])
        normed = layers.elementwise_mul(normed, layers.create_parameter([inner], x.dtype, attr=_attr_ones(f"{prefix}.norm.w")))
        return project(normed, "out", d_model)


def gated_memory_unit(x, memory, d_model, prefix):
    """A Gated Memory Unit (SambaY, Ren et al. 2025, arXiv:2507.06607): out =
    (silu(x W1) * m) W2, m the scan output (b, T, width) that an earlier
    state-space layer kept (`mamba_mixer(keep=)`), W1 d_model x width, W2 width
    x d_model, no biases: the layer reads that layer's memory through a gate of
    its own input, for two products and no scan."""
    def project(t, name, out):
        return layers.fc(t, out, num_flatten_dims=2, param_attr=_attr(f"{prefix}.{name}.w"), bias_attr=False)

    with name_scope("gmu"):
        return project(layers.memory_gate(project(x, "in", int(memory.shape[-1])), memory), "out", d_model)


def encoder_layer(x, seq_len, d_model, n_heads, d_ff, prefix, dropout_prob=0.1, is_test=False,
                  use_ring_attention=False, causal=False, use_fused_attention=False,
                  norm="layer", norm_eps=1e-5, pre_norm=False, proj_bias=True,
                  qk_norm=False, positions=None, rope_theta=10000.0, moe=None, aux_losses=None,
                  n_kv_heads=None, head_dim=None, attention_mask=None,
                  operator="attention", conv_kernel=3, ffn="gelu", post_norm=False, operator_args=None,
                  unit_norms=False, keep=None, kept=None, sparse_index=None, index_losses=None, head_gate=False):
    """One transformer layer: a sequence operator (attention) and a
    feed-forward part, each with a residual connection and a norm.

    The defaults are BERT's: layer norm AFTER each residual sum, projection
    biases, a dense GELU feed-forward of width `d_ff`.  `norm="rms"` with
    `pre_norm=True` norms each part's INPUT instead (h = x + attn(norm(x));
    y = h + ffn(norm(h))); `post_norm` beside it norms each part's OUTPUT too,
    before the residual sum (the sandwich: h = x + norm(attn(norm(x))), gains
    `post_ln1` / `post_ln2`); `qk_norm` (True or "width": over the projected
    width; "head": over each head), `positions`, `rope_theta` (a number or the
    rotary embedding's description), `proj_bias`, `n_kv_heads`, `head_dim`,
    `head_gate` and `attention_mask` = (kind, block length) go to the
    attention.  `operator="conv"` puts a gated short convolution of
    `conv_kernel` taps (`layers.short_conv`) where the attention stands,
    `operator="kda"` a Kimi-Delta-Attention operator (`kimi_delta_attention`;
    `operator_args` = dict(n_heads=, head_dim=)), `operator="gated_delta_net"` a
    Gated DeltaNet operator (`gated_delta_net`; `operator_args` =
    dict(key_heads=, value_heads=, head_dim=)) and
    `operator="latent_attention"` attention over latent keys and values
    (`latent_attention`; `operator_args` = dict(rank=, nope_dim=, rope_dim=,
    v_dim=), with `rope=True` the layer's rotary embedding on `positions` at
    `rope_theta`, and `rope_interleave=`) and `operator="mamba"` a Mamba-1 mixer (`mamba_mixer`;
    `operator_args` = dict(expand=, state=, dt_rank=, inner_norms=, taps_bound=), its
    convolution of `conv_kernel` taps) and `operator="mamba2"` a Mamba-2 mixer
    (`mamba2_mixer`; `operator_args` = dict(heads=, head_dim=, state=, groups=,
    chunk=, taps_bound=)).  `unit_norms` starts a layer norm at
    gain 1 and bias 0, as a decoder's sources do (BERT's are drawn: PERF.md
    section 7, defect 3).

    A layer may hand on more than the residual stream.  `keep` is a dict the
    layer WRITES what it makes into: a Mamba layer its scan output
    (`keep["memory"]`), an attention layer its projected keys and values
    (`keep["kv"]`).  `kept` is such a dict a later layer READS:
    `operator="gmu"` is a Gated Memory Unit on `kept["memory"]`
    (`gated_memory_unit`), `operator="cross_attention"` causal attention of the
    layer's own queries on `kept["kv"]`, with no key or value weights
    (`multi_head_attention(kept_kv=)`).  Attention under a sliding window
    (`attention_mask=("sliding_window", W)`) stands in the scope
    `sliding_attention`, on kept keys and values in `cross_attention`.
    `sparse_index` = dict(heads=, head_dim=, topk=) gives the attention a
    learned indexer that chooses each query's keys, and `index_losses` the list
    that receives the call which makes its alignment term
    (`multi_head_attention(sparse_index=)`).

    A layer of ONE part (a pre-norm layer only: y = x + part(norm(x))):
    `ffn=None` is an operator with no feed-forward part (its norm `ln1`),
    `operator=None` a feed-forward part with no operator (its norm `ln2`); a
    layer with neither is refused.

    The feed-forward part: `ffn="gelu"` is BERT's biased pair, `"gated_silu"`
    W2(silu(W1 x) * (W3 x)) without biases, both `d_ff` wide;
    `moe=dict(num_experts=, top_k=, norm_topk_prob=, held=)` makes it
    `d_ff`-wide routed gated-SiLU experts instead (`held`: the range of them
    this layer holds; `router_seed`: a seed of the router's own, `_attr`;
    `scoring`, `routed_scaling_factor`, `norm_eps` and `bias` = (standard
    deviation, seed) of a router bias that enters the choice alone;
    `shared_experts`: how many shared experts every token passes beside the
    routed ones; `activation`, `gated`, `latent_size`, `shared_width`: the
    experts' form, the latent they live in and the shared expert's own width;
    `shared_gate`: the shared expert's output times a sigmoid gate of its own,
    one number a token; `router_ahead`: the router reads the LAYER's input x, before the input norm
    and the operator, and not the normed h the experts read, and its op stands
    ahead of the operator's: `layers.moe(router_input=)`); its two auxiliary
    losses are appended to `aux_losses` as (load balance, router z).
    """
    if (operator is None or ffn is None) and not pre_norm:
        raise ValueError("encoder_layer: a layer of one part (operator=None or ffn=None) is a pre-norm layer, "
                         "y = x + part(norm(x))")
    if operator is None and ffn is None and moe is None:
        raise ValueError("encoder_layer: operator=None and ffn=None leave the layer no part")
    def normed(t, name):
        if norm == "rms":
            return layers.rms_norm(t, begin_norm_axis=2, epsilon=norm_eps,
                                   param_attr=_attr_ones(f"{prefix}.{name}.w"))
        if unit_norms:
            return layers.layer_norm(t, begin_norm_axis=2, epsilon=norm_eps, param_attr=_attr_ones(f"{prefix}.{name}.w"),
                                     bias_attr=ParamAttr(name=f"{prefix}.{name}.b", initializer=ConstantInitializer(0.0)))
        return layers.layer_norm(t, begin_norm_axis=2, epsilon=norm_eps,
                                 param_attr=_attr(f"{prefix}.{name}.w"),
                                 bias_attr=_attr(f"{prefix}.{name}.b"))

    def feed_forward(t):
        if moe is not None:
            bias = moe.get("bias")
            out, balance, z_loss = layers.moe(
                t, moe["num_experts"], d_ff, moe["top_k"], router_input=layer_input if moe.get("router_ahead") else None,
                norm_topk_prob=moe.get("norm_topk_prob", False), held=moe.get("held"),
                router_attr=_attr(f"{prefix}.moe.router.w", seed=moe.get("router_seed", 0)),
                gate_attr=_attr(f"{prefix}.moe.gate.w"),
                up_attr=_attr(f"{prefix}.moe.up.w"), down_attr=_attr(f"{prefix}.moe.down.w"),
                scoring=moe.get("scoring", "softmax"),
                routed_scaling_factor=moe.get("routed_scaling_factor", 1.0),
                norm_eps=moe.get("norm_eps", 0.0),
                bias_attr=bias and _attr(f"{prefix}.moe.router.bias", *bias),
                shared_experts=moe.get("shared_experts", 0),
                shared_attrs=tuple(_attr(f"{prefix}.moe.shared.{n}.w") for n in ("gate", "up", "down")),
                **{n: moe[n] for n in ("activation", "gated", "latent_size", "shared_width") if n in moe},
                **({"shared_gate_attr": _attr(f"{prefix}.moe.shared_gate.w")} if moe.get("shared_gate") else {}),
                **({"latent_attrs": tuple(_attr(f"{prefix}.moe.latent_{n}.w") for n in ("in", "out"))}
                   if moe.get("latent_size") else {}))
            aux_losses.append((balance, z_loss))
            return out

        if ffn == "gated_silu":
            def project(u, name, width, act=None):
                return layers.fc(u, width, num_flatten_dims=2, act=act, bias_attr=False,
                                 param_attr=_attr(f"{prefix}.ffn.{name}.w"))

            hidden = layers.elementwise_mul(project(t, "gate", d_ff, act="swish"), project(t, "up", d_ff))
            return project(hidden, "down", d_model)
        ffn1 = layers.fc(t, d_ff, num_flatten_dims=2, act="gelu",
                         param_attr=_attr(f"{prefix}.ffn1.w"), bias_attr=_attr(f"{prefix}.ffn1.b"))
        return layers.fc(ffn1, d_model, num_flatten_dims=2,
                         param_attr=_attr(f"{prefix}.ffn2.w"), bias_attr=_attr(f"{prefix}.ffn2.b"))

    layer_input = x   # what a router ahead of the attention reads: the stream as it enters the layer, before any norm
    if operator is None:   # a feed-forward part with no operator
        return layers.elementwise_add(x, feed_forward(normed(x, "ln2")))
    operator_in = normed(x, "ln1") if pre_norm else x
    if operator == "conv":
        attn_out = layers.short_conv(operator_in, conv_kernel, in_attr=_attr(f"{prefix}.conv.in.w"),
                                     filter_attr=_attr(f"{prefix}.conv.filter.w"),
                                     out_attr=_attr(f"{prefix}.conv.out.w"))
    elif operator == "kda":
        attn_out = kimi_delta_attention(operator_in, d_model, prefix=f"{prefix}.kda", conv_kernel=conv_kernel,
                                        norm_eps=norm_eps, **operator_args)
    elif operator == "gated_delta_net":
        attn_out = gated_delta_net(operator_in, d_model, prefix=f"{prefix}.gdn", conv_kernel=conv_kernel,
                                   norm_eps=norm_eps, **operator_args)
    elif operator == "latent_attention":
        latent = dict(operator_args)
        attn_out = latent_attention(operator_in, d_model, n_heads, f"{prefix}.attn", norm_eps=norm_eps,
                                    positions=positions if latent.pop("rope", False) else None, rope_theta=rope_theta,
                                    **latent)
    elif operator == "mamba":
        attn_out = mamba_mixer(operator_in, d_model, f"{prefix}.mamba", conv_kernel=conv_kernel, norm_eps=norm_eps,
                               keep=keep, **operator_args)
    elif operator == "mamba2":
        attn_out = mamba2_mixer(operator_in, d_model, f"{prefix}.mamba2", conv_kernel=conv_kernel, norm_eps=norm_eps,
                                **operator_args)
    elif operator == "gmu":
        attn_out = gated_memory_unit(operator_in, kept["memory"], d_model, f"{prefix}.gmu")
    else:
        crossing = operator == "cross_attention"
        scope = ("cross_attention" if crossing
                 else "sliding_attention" if attention_mask and attention_mask[0] == "sliding_window" else None)
        with name_scope(scope) if scope else contextlib.nullcontext():
            attn_out = multi_head_attention(operator_in,
                                            seq_len, d_model, n_heads, f"{prefix}.attn",
                                            dropout_prob, is_test, use_ring_attention, causal,
                                            use_fused_attention=use_fused_attention,
                                            proj_bias=proj_bias,
                                            qk_norm_eps=norm_eps if qk_norm else None,
                                            positions=positions, rope_theta=rope_theta,
                                            n_kv_heads=n_kv_heads, head_dim=head_dim,
                                            qk_norm_per_head=qk_norm == "head",
                                            mask=attention_mask and attention_mask[0],
                                            mask_block=attention_mask and attention_mask[1],
                                            keep=keep, kept_kv=kept["kv"] if crossing else None,
                                            sparse_index=sparse_index, index_losses=index_losses,
                                            head_gate=head_gate)
    if post_norm:
        attn_out = normed(attn_out, "post_ln1")
    x = layers.elementwise_add(x, attn_out)
    if ffn is None and moe is None:   # an operator with no feed-forward part
        return x
    if not pre_norm:
        x = normed(x, "ln1")
    ffn_out = feed_forward(normed(x, "ln2") if pre_norm else x)
    if post_norm:
        ffn_out = normed(ffn_out, "post_ln2")
    if dropout_prob and not is_test:
        ffn_out = layers.dropout(ffn_out, dropout_prob, is_test=is_test,
                                 dropout_implementation="upscale_in_train")
    x = layers.elementwise_add(x, ffn_out)
    return x if pre_norm else normed(x, "ln2")


def build_bert(
    vocab_size=30522,
    seq_len=128,
    d_model=768,
    n_layers=12,
    n_heads=12,
    d_ff=3072,
    dropout_prob=0.1,
    learning_rate=1e-4,
    with_optimizer=True,
    is_test=False,
    use_ring_attention=False,
    causal=False,
    use_fused_attention=False,
    dtype="float32",
):
    """BERT-base-style masked-LM pretraining program.

    feeds: ids (B,L) int64, labels (B,L) int64 (-100 = unmasked/ignored).
    dtype="bfloat16" runs the encoder + LM head matmuls on the MXU in bf16
    (master weights stay f32 via per-op match_dtype; LN stats and the loss
    stay f32) — the TPU answer to the reference's fp16 AMP decorator.
    """
    main, startup = Program(), Program()
    with program_guard(main, startup):
        ids = layers.data("ids", [seq_len], dtype="int64")
        labels = layers.data("labels", [seq_len], dtype="int64")
        tok = layers.embedding(ids, size=[vocab_size, d_model], param_attr=_attr("bert.tok_emb"))
        pos_ids = layers.data("pos_ids", [seq_len], dtype="int64")
        pos = layers.embedding(pos_ids, size=[seq_len, d_model], param_attr=_attr("bert.pos_emb"))
        x = layers.elementwise_add(tok, pos)
        x = layers.layer_norm(x, begin_norm_axis=2, param_attr=_attr("bert.emb_ln.w"),
                              bias_attr=_attr("bert.emb_ln.b"))
        if dtype != "float32":
            x = layers.cast(x, dtype)
        for i in range(n_layers):
            x = encoder_layer(x, seq_len, d_model, n_heads, d_ff, f"bert.l{i}",
                              dropout_prob, is_test, use_ring_attention, causal,
                              use_fused_attention=use_fused_attention)
        logits = layers.fc(x, vocab_size, num_flatten_dims=2,
                           param_attr=_attr("bert.lm_head.w"), bias_attr=_attr("bert.lm_head.b"))
        # bf16 logits feed the CE directly: softmax_with_cross_entropy does
        # its reductions in f32 without materializing [N,V] f32 logp, so the
        # old cast here only added ~8 GB/step of HBM traffic at V=30522
        flat_logits = layers.reshape(logits, [-1, vocab_size])
        flat_labels = layers.reshape(labels, [-1, 1])
        loss_per = layers.softmax_with_cross_entropy(flat_logits, flat_labels, ignore_index=-100)
        loss = layers.mean(loss_per)
        if with_optimizer:
            optimizer.Adam(learning_rate=learning_rate).minimize(loss)
    return main, startup, {"ids": ids, "labels": labels, "pos_ids": pos_ids}, {"loss": loss}


def build_causal_lm(
    vocab_size=50304,
    seq_len=4096,
    d_model=2048,
    n_layers=None,
    n_heads=16,
    expert_width=1024,
    num_experts=64,
    top_k=8,
    norm_topk_prob=False,
    norm_eps=1e-5,
    rope_theta=10000.0,
    load_balance_coef=0.01,
    router_z_coef=0.001,
    learning_rate=4e-4,
    beta1=0.9,
    beta2=0.95,
    epsilon=1e-8,
    with_optimizer=True,
    use_fused_attention=True,
    dtype="float32",
    n_kv_heads=None,
    head_dim=None,
    qk_norm="width",
    attention_mask=None,
    experts_held=None,
    loss_positions=None,
    embedding_std=0.02,
    routing_seed=0,
    layer_types=None,
    conv_kernel=3,
    num_dense_layers=0,
    dense_width=None,
    tie_embedding=False,
    scoring="softmax",
    routed_scaling_factor=1.0,
    norm_topk_eps=0.0,
    expert_bias=None,
    post_norm=False,
    loop=None,
    exit_beta=0.0,
    shared_experts=0,
    kda_heads=None,
    kda_head_dim=None,
    latent=None,
    mamba=None,
    rotary=True,
    recompute_layers=False,
    norm="rms",
    proj_bias=False,
    sliding_window=None,
    memory_layer=None,
    kv_layer=None,
    sparse_index=None,
    mamba2=None,
    expert_form=None,
    attention_gate=False,
    linear_key_heads=None,
    linear_value_heads=None,
    linear_head_dim=None,
):
    """Decoder-only language model with routed experts in every layer: the
    OLMoE-1B-7B block at its defaults (Muennighoff et al. 2024,
    arXiv:2409.02060): pre-norm RMSNorm, rotary positions, q/k-norm, no
    biases, causal attention, `num_experts` gated-SiLU experts of
    `expert_width` with `top_k` a token, an untied head.

    feeds: ids, labels (the next token at every position), pos_ids, all
    (B, L) int64.  loss = mean cross entropy + `load_balance_coef` x the
    layers' mean load-balance loss + `router_z_coef` x their mean router
    z-loss; fetches also hold the three parts and the logits.
    dtype="bfloat16" as in `build_bert`: activations and matmuls in bf16
    over float32 master weights; norms' statistics, the router and the loss
    stay float32.

    What other decoders of the family change is an argument: `n_kv_heads`
    and `head_dim` (grouped key/value heads, a head width that is not
    d_model / n_heads), `qk_norm` ("width": OLMoE's, over the projected
    width; "head": Qwen3's, over each head; None), `attention_mask` = (kind,
    block length) in place of the causal mask (`layers.fused_attention`),
    `experts_held` = (first, count), the experts each layer holds
    (`layers.moe`), and `loss_positions` = P: the head and the loss run over
    the first P of the `seq_len` positions only, `labels` is (B, P), and a
    further feed `loss_weight` (B, P) float32 weighs each position's cross
    entropy, the loss being their sum over B . P.  Block-diffusion training
    (SDAR, BD3-LM) is `attention_mask=("block_diffusion", B)` over seq_len =
    2L positions [noised ; clean] with `loss_positions=L` and the weight
    1/t on the masked positions, 0 elsewhere.  An auxiliary loss whose
    coefficient is 0 is left out of the loss.

    Every weight is drawn N(0, 0.02) from the program's `random_seed` but
    for two arguments: `embedding_std` is the token embedding's (1.0 starts
    the residual stream at unit scale, so that a token's own embedding and
    not the attention's average over its context is what the first routers
    read), and `routing_seed`, where not 0, is the seed of the embedding
    (`routing_seed`) and of layer i's router (`routing_seed` + 1 + i)
    instead: which experts a token meets is then the same whatever the
    program's seed, as it is for a checkpoint.

    A hybrid of the family (LFM2) is arguments too.  `layer_types`, one of
    "full_attention" / "conv" a layer (its length is the depth: `n_layers`
    is then left out or equal to it; without `layer_types` it is the number of
    attention layers, 16 by default), puts a
    gated short convolution of `conv_kernel` taps where a layer's attention
    stands; the first `num_dense_layers` layers have a dense gated-SiLU
    feed-forward of `dense_width` in place of the experts; `tie_embedding`
    makes the head the embedding table transposed, one parameter with two
    uses whose two gradients are one sum; `scoring` ("softmax" / "sigmoid"),
    `routed_scaling_factor`, `norm_topk_eps` (added to the chosen scores' sum
    before the renormalisation) and `expert_bias` = (standard deviation,
    seed) go to the routers: layer i's bias is N(0, standard deviation) from
    seed + i, enters its choice and not its weights, and is no parameter
    (`layers.moe`).

    A hybrid of linear and latent attention (Kimi Linear) is arguments too:
    `layer_types` may hold "kda" (a Kimi-Delta-Attention operator of `kda_heads`
    heads of `kda_head_dim`, its three convolutions of `conv_kernel` taps) and
    "latent_attention" (`latent` = dict(rank=, nope_dim=, rope_dim=, v_dim=):
    keys and values from one normed latent a token; no rotary embedding unless
    the dict also says `rope=True`, which hands `pos_ids` and `rope_theta` to
    the layer's decoupled rotary embedding, and `rope_interleave=True` for the
    pairing (2i, 2i + 1) of DeepSeek-V3's family: `latent_attention`), and
    `shared_experts` = n gives every sparse layer n shared experts that every
    token passes, beside the routed ones and outside `experts_held`.

    A hybrid of state-space layers and attention (Jamba) is arguments too:
    `layer_types` may hold "mamba" (a Mamba-1 mixer, `mamba` = dict(expand=,
    state=, dt_rank=), its convolution of `conv_kernel` taps);
    `num_dense_layers` equal to the depth puts the dense feed-forward after
    EVERY operator; `rotary=False` leaves positions out of the attention
    altogether (the state-space layers carry the order; `pos_ids` is then no
    feed); and `recompute_layers` makes every layer a `recompute_scope`:
    backward keeps a layer's input and computes the layer again.  A SPARSE
    layer is such a segment like any other: its router decides again on the
    same input (the same choice, bit for bit), the held path's conditional and
    the `token_sum` way back are differentiated inside the segment, what
    `plan_kept` finds room for (the expert products' outputs, the router's
    logits, the shared experts' products) is kept, and the routing's
    statistics, the auxiliary terms and every fetchable output leave the
    segment as they leave the layer.

    A decoder whose later layers read what earlier ones made (SambaY: Ren et
    al. 2025, arXiv:2507.06607) is arguments too.  `layer_types` may hold
    "sliding_attention" (softmax over the `sliding_window` keys that end at the
    query's own: `layers.fused_attention(mask="sliding_window")`), "gmu" (a
    Gated Memory Unit, `gated_memory_unit`, on the scan output that the Mamba
    layer at index `memory_layer` hands on) and "cross_attention" (the layer's
    own queries, causally, on the keys and values that the attention layer at
    index `kv_layer` projected and hands on; no key or value weights).  A "gmu"
    or "cross_attention" at or before the layer that makes what it reads, or
    with no such layer, is refused here, with its index.  With
    `recompute_layers` the kept tensors leave their layer's segment as outputs
    and enter each reader's as inputs, and backward sums their gradients over
    the readers.  `norm="layer"` makes every norm, the final one too, a
    LayerNorm with gain 1 and bias 0 at the start; `proj_bias` gives the four
    attention projections biases; `mamba` may hold `inner_norms=False` (the
    plain Mamba-1 mixer) and `taps_bound=` (its taps' own initialisation).

    Attention whose mask is DATA (DeepSeek Sparse Attention) is an argument
    too: `layer_types` may hold "sparse_attention", the block's own attention
    (grouped heads, q/k-norm, rotary positions as the other arguments say) over
    the keys that a learned indexer chooses for every query, every step;
    `sparse_index` = dict(heads=, head_dim=, topk=) is the indexer
    (`multi_head_attention(sparse_index=)`).  The layers' alignment terms, each
    the mean over rows and queries, are averaged over those layers into
    `fetches["index_kl"]` and added to the loss where the routers' auxiliary
    terms are (L = L_LM + L_I): the language-model loss trains the main
    weights through the chosen pairs, this term the indexers, and neither the
    other's.  With `recompute_layers` a layer's choice is KEPT: the forward
    that backward makes again reads it (`ops/sparse_index_ops.py`); the
    alignment op stands after the layer's segment and is made once.

    A stack whose layers have ONE part each (a hybrid of the Nemotron-H kind: y
    = x + part(norm(x)), the part a state-space mixer, an attention or the
    experts) is arguments too.  `layer_types` may hold "mamba2" (a Mamba-2
    mixer, `mamba2` = dict(heads=, head_dim=, state=, groups=, chunk=): a
    scalar-decay scan as matrix products, `mamba2_mixer`, its convolution of
    `conv_kernel` taps) and "feed_forward" (NO operator: the layer is its
    feed-forward part alone, dense or the experts as `num_dense_layers` says);
    a stack that holds a "feed_forward" layer is such a stack, and every layer of
    it that HAS an operator has no feed-forward part.  `expert_form` = dict(activation="relu2", gated=False,
    latent_size=, shared_width=) gives the experts' own form where it is not
    the gated-SiLU one at the model's width (`layers.moe`: two matrices an
    expert with relu(.)^2 between, a latent the layer projects into and out of
    once a token, a shared expert of its own width).

    A decoder that chooses its experts AHEAD of the attention and mixes layers
    with and without positions (SmallThinker: arXiv:2507.20984) is arguments
    too.  `expert_form` may hold `router_ahead=True`: every sparse layer's
    router reads the layer's input itself, before the input norm and the
    attention, and its `moe_router` op stands ahead of the attention's ops
    (`encoder_layer`, `layers.moe(router_input=)`); the experts read the normed
    post-attention stream as ever; and `activation="relu"` with the gate is the
    gated ReLU.  `rotary` is a statement a LAYER where it is no single boolean:
    a sequence of booleans, one a layer as `layer_types` is, or a dict from a
    layer's kind to one (a kind it does not name rotates): a "full_attention"
    layer that attends to every earlier key without any position then stands
    beside "sliding_attention" layers whose queries and keys carry the rotary
    embedding.  `pos_ids` is a feed where any layer rotates.

    A decoder whose attention layers differ by KIND in more than their mask
    (Laguna: poolside/Laguna-XS.2) is arguments too.  `n_heads` is a number or
    a statement a layer kind, a dict from a layer's kind to its count of QUERY
    heads (a kind it does not name takes the default, 16): 48 "full_attention"
    heads beside 64 "sliding_attention" heads on the same `n_kv_heads` key/value
    heads of `head_dim`, so the two kinds' q, out and gate matrices have widths
    of their own.  `rope_theta` is a number or a statement a layer kind as well:
    a dict from a kind to the base, or to the rotary embedding's whole
    description dict(theta=, rotary_dim=, inv_freq=, scale=)
    (`layers.rotary_embedding`; a kind it does not name takes the default,
    10000): a half-rotary embedding stretched by YaRN (`yarn_frequencies`, its
    attention factor as `scale`) on the full layers beside a plain whole one on
    the windows.  `attention_gate` gives every attention layer a sigmoid gate a
    head on its output (`multi_head_attention(head_gate=)`, the scope
    `attention_gate`).

    A hybrid of Gated DeltaNet and gated softmax attention (Qwen3-Next:
    Qwen/Qwen3-Next-80B-A3B-Instruct) is arguments too.  `layer_types` may hold
    "gated_delta_net" (a delta rule whose decay is ONE number a head:
    `gated_delta_net`, of `linear_key_heads` key heads feeding
    `linear_value_heads` value heads, all `linear_head_dim` wide, one convolution
    of `conv_kernel` taps over [q | k | v], a SiLU-gated norm; the op is `kda`,
    its decay's rank says which rule).  `attention_gate="feature"` is the gate a
    FEATURE: the gate's columns ride in the query projection and the merged
    heads are multiplied by their sigmoid (True or "head" stays the gate a head).
    `expert_form` may hold `shared_gate=True`: the shared expert's output times
    sigmoid(m w_s), a gate of its own, one number a token (`layers.moe`, the
    scope `moe_shared_gate`).  A head 256 wide of which a quarter turns is
    `head_dim` and `rope_theta=dict(theta=, rotary_dim=)` as above.

    A looped (weight-shared) decoder is arguments as well.  `num_dense_layers`
    equal to the depth makes every layer dense: no router, and the auxiliary
    terms and their fetches are left out.  `post_norm` is `encoder_layer`'s
    sandwich.  `loop` = T runs the whole stack of layers AND the final norm T
    times over the one set of weights, as ONE `layers.Repeat` of the program
    (its body computed again in backward: `recompute=True`, the op's attribute), each pass
    reading the last one's normed output; the loop hands on every pass's
    output, `hidden` [T, B, L, d_model] (h_1 .. h_T), and on it the head gives
    each exit's logits and an exit gate (a [d_model] weight and a bias, read in
    float32) one logit a position: one product and one pass over the T outputs,
    outside the loop, so that they read what the loop WROTE (inside the body
    XLA may hand a consumer in the same fusion more digits than the bf16 the
    next pass reads, and then no check on the program's own h_t is exact).
    The loss is then `layers.exit_loss` of the T cross entropies and gate
    logits with `exit_beta`, and the fetches also hold `logits` [T, B, L, V]
    and `exit_p` [T, B, L, 1] (each position's exit distribution).  A loop over
    sparse layers or with `loss_positions` is not built: the routers'
    auxiliary terms would have to leave the body a pass."""
    main, startup = Program(), Program()
    if layer_types is not None and n_layers not in (None, len(layer_types)):
        raise ValueError(f"build_causal_lm: n_layers={n_layers} beside {len(layer_types)} layer_types; "
                         "layer_types alone states the depth")
    kinds = list(layer_types) if layer_types is not None else ["full_attention"] * (16 if n_layers is None else n_layers)
    operators = {"full_attention": "attention", "conv": "conv", "kda": "kda", "gated_delta_net": "gated_delta_net",
                 "latent_attention": "latent_attention",
                 "mamba": "mamba", "sliding_attention": "attention", "gmu": "gmu", "cross_attention": "cross_attention",
                 "sparse_attention": "attention", "mamba2": "mamba2", "feed_forward": None}
    operator_args = {"kda": dict(n_heads=kda_heads, head_dim=kda_head_dim), "latent_attention": latent, "mamba": mamba,
                     "mamba2": mamba2,
                     "gated_delta_net": dict(key_heads=linear_key_heads, value_heads=linear_value_heads,
                                             head_dim=linear_head_dim)}
    unknown = sorted(set(kinds) - set(operators))
    if unknown:
        raise ValueError(f"build_causal_lm: layer_types holds {unknown}; a layer is full_attention or conv, "
                         "kda or latent_attention, mamba, sliding_attention, gmu or cross_attention, or sparse_attention, "
                         "mamba2 or feed_forward, or gated_delta_net")
    if "gated_delta_net" in kinds and not (linear_key_heads and linear_value_heads and linear_head_dim
                                           and linear_value_heads % linear_key_heads == 0):
        raise ValueError("build_causal_lm: a gated_delta_net layer needs linear_key_heads, linear_value_heads (a multiple "
                         "of them) and linear_head_dim")
    if (("kda" in kinds and not (kda_heads and kda_head_dim)) or ("latent_attention" in kinds and not latent)
            or ("mamba" in kinds and not mamba) or ("mamba2" in kinds and not mamba2)):
        raise ValueError("build_causal_lm: a kda layer needs kda_heads and kda_head_dim, a latent_attention layer "
                         "latent=, a mamba layer mamba=, a mamba2 layer mamba2=")
    one_part = "feed_forward" in kinds
    if loop is not None and one_part:
        raise ValueError("build_causal_lm: loop= with a feed_forward layer: a looped stack's layers have both parts")
    if "sparse_attention" in kinds and not (sparse_index and all(sparse_index.get(n, 0) >= 1
                                                                 for n in ("heads", "head_dim", "topk"))):
        raise ValueError("build_causal_lm: a sparse_attention layer needs sparse_index=dict(heads=, head_dim=, topk=), "
                         "its indexer")
    if "sparse_attention" in kinds and (loop is not None or attention_mask is not None):
        raise ValueError("build_causal_lm: a sparse_attention layer under loop= or attention_mask=: the alignment terms "
                         "do not leave a loop's body, and the chosen keys are the layer's mask")
    if "sliding_attention" in kinds and not (sliding_window and sliding_window >= 1):
        raise ValueError("build_causal_lm: a sliding_attention layer needs sliding_window=, its width in keys")
    # what a layer reads of another: (the reading kind, the argument that names the maker, the kinds that make it)
    for reader, argument, maker, makers in (("gmu", "memory_layer", memory_layer, ("mamba",)),
                                            ("cross_attention", "kv_layer", kv_layer,
                                             ("full_attention", "sliding_attention"))):
        if maker is not None and not (0 <= maker < len(kinds) and kinds[maker] in makers):
            raise ValueError(f"build_causal_lm: {argument}={maker} names no layer of kind {' / '.join(makers)} "
                             f"among {len(kinds)} layers")
        for i, kind in enumerate(kinds):
            if kind == reader and (maker is None or maker >= i):
                raise ValueError(f"build_causal_lm: layer {i} is a {reader} layer and reads what layer {argument}="
                                 f"{maker} hands on, which has to be a layer before it")
    if loop is not None and (memory_layer is not None or kv_layer is not None):
        raise ValueError("build_causal_lm: loop= with memory_layer= or kv_layer=: a kept tensor does not leave a pass")
    if norm not in ("rms", "layer"):
        raise ValueError(f"build_causal_lm: norm={norm!r}; \"rms\" or \"layer\"")
    if not 0 <= num_dense_layers <= len(kinds) or (num_dense_layers and not dense_width):
        raise ValueError(f"build_causal_lm: num_dense_layers={num_dense_layers} leading dense layers of "
                         f"dense_width={dense_width} among {len(kinds)} layers")
    if isinstance(rotary, dict):
        rotates = [bool(rotary.get(kind, True)) for kind in kinds]
    elif isinstance(rotary, (list, tuple)):
        if len(rotary) != len(kinds):
            raise ValueError(f"build_causal_lm: rotary states {len(rotary)} layers beside {len(kinds)} layer_types")
        rotates = [bool(r) for r in rotary]
    else:
        rotates = [bool(rotary)] * len(kinds)
    rotary = any(rotates)
    # a statement a layer kind (a dict from the kind) or one value for the stack; an unnamed kind takes the default
    heads = [n_heads.get(kind, 16) if isinstance(n_heads, dict) else n_heads for kind in kinds]
    ropes = [rope_theta.get(kind, 10000.0) if isinstance(rope_theta, dict) else rope_theta for kind in kinds]
    if any(isinstance(rope, dict) and kind == "latent_attention" for rope, kind in zip(ropes, kinds)):
        raise ValueError("build_causal_lm: a latent_attention layer's decoupled rotary embedding turns by theta alone "
                         "(rope_theta= a number for that kind)")
    if latent and latent.get("rope") and not all(r for r, kind in zip(rotates, kinds) if kind == "latent_attention"):
        raise ValueError("build_causal_lm: latent=dict(rope=True) rotates by pos_ids, which rotary=False leaves out of "
                         "the feeds")
    if loop is not None and (num_dense_layers < len(kinds) or loss_positions):
        raise ValueError("build_causal_lm: loop= takes a stack of dense layers (num_dense_layers= the depth) with a "
                         "label at every position (no loss_positions=): a router's auxiliary terms do not leave a "
                         "loop's body")
    with program_guard(main, startup):
        n_labels = loss_positions or seq_len
        ids = layers.data("ids", [seq_len], dtype="int64")
        labels = layers.data("labels", [n_labels], dtype="int64")
        pos_ids = layers.data("pos_ids", [seq_len], dtype="int64") if rotary else None
        x = layers.embedding(ids, size=[vocab_size, d_model],
                             param_attr=_attr("lm.tok_emb", embedding_std, routing_seed))
        if dtype != "float32":
            x = layers.cast(x, dtype)
        aux, index_terms = [], []

        def stack_of_layers(x):
            kept = {}   # what a layer handed on: "memory" (a scan's output), "kv" (keys and values)
            for i, kind in enumerate(kinds):
                window = ("sliding_window", sliding_window) if kind == "sliding_attention" else None
                dense = i < num_dense_layers
                experts = dict(num_experts=num_experts, top_k=top_k,
                               norm_topk_prob=norm_topk_prob, held=experts_held,
                               router_seed=routing_seed and routing_seed + 1 + i,
                               scoring=scoring, routed_scaling_factor=routed_scaling_factor,
                               norm_eps=norm_topk_eps,
                               bias=expert_bias and (expert_bias[0], expert_bias[1] + i),
                               shared_experts=shared_experts, **(expert_form or {}))
                has_ffn = not (one_part and operators[kind] is not None)   # a layer of one part with an operator has none
                pending = []        # the calls that make a sparse-attention layer's alignment term, AFTER its segment
                with recompute_scope() if recompute_layers else contextlib.nullcontext():
                    x = encoder_layer(x, seq_len, d_model, heads[i], dense_width if dense else expert_width,
                                      f"lm.l{i}",
                                      dropout_prob=0.0, causal=(window or attention_mask) is None,
                                      use_fused_attention=use_fused_attention,
                                      norm=norm, unit_norms=True, norm_eps=norm_eps, pre_norm=True, proj_bias=proj_bias,
                                      qk_norm=qk_norm, positions=pos_ids if rotates[i] else None, rope_theta=ropes[i],
                                      moe=experts if has_ffn and not dense else None, ffn="gated_silu" if has_ffn else None,
                                      aux_losses=aux, n_kv_heads=n_kv_heads, head_dim=head_dim,
                                      attention_mask=window or attention_mask,
                                      operator=operators[kind], operator_args=operator_args.get(kind),
                                      conv_kernel=conv_kernel, post_norm=post_norm,
                                      keep=kept if i in (memory_layer, kv_layer) else None, kept=kept,
                                      sparse_index=sparse_index if kind == "sparse_attention" else None,
                                      index_losses=pending,
                                      head_gate=attention_gate if operators[kind] in ("attention", "cross_attention") else False)
                index_terms.extend(make() for make in pending)
            return x

        def final_norm(x):
            if norm == "layer":
                return layers.layer_norm(x, begin_norm_axis=2, epsilon=norm_eps, param_attr=_attr_ones("lm.final_norm.w"),
                                         bias_attr=ParamAttr(name="lm.final_norm.b", initializer=ConstantInitializer(0.0)))
            return layers.rms_norm(x, begin_norm_axis=2, epsilon=norm_eps,
                                   param_attr=_attr_ones("lm.final_norm.w"))

        def head(x):
            if tie_embedding:
                return layers.matmul(x, main.global_block().var("lm.tok_emb"), transpose_y=True)
            return layers.fc(x, vocab_size, num_flatten_dims=len(x.shape) - 1,
                             param_attr=_attr("lm.head.w"), bias_attr=False)

        feeds = {"ids": ids, "labels": labels, **({"pos_ids": pos_ids} if rotary else {})}
        fetches = {}
        if loop is not None:
            passes = layers.Repeat(loop, recompute=True)
            with passes.block():
                carried = passes.carry(x)
                x = final_norm(stack_of_layers(carried))  # the final norm closes EVERY pass
                passes.update(carried, x)
                passes.output(x)
            hidden = passes()                             # h_1 .. h_T: [T, B, L, d_model]
            with name_scope("exit_head"):
                logits = head(hidden)
                # the gate reads each pass's output in float32 and off the matrix unit
                gate = layers.reduce_sum(layers.elementwise_mul(
                    layers.cast(hidden, "float32"),
                    layers.create_parameter([d_model], "float32", attr=_attr("lm.exit_gate.w"))),
                    dim=-1, keep_dim=True)
                gate = layers.elementwise_add(gate, layers.create_parameter(
                    [1], "float32", is_bias=True,
                    attr=ParamAttr(name="lm.exit_gate.b", initializer=ConstantInitializer(0.0))))
            with name_scope("exit_loss"):
                every_exit = layers.expand(layers.reshape(labels, [1, -1, n_labels, 1]), [loop, 1, 1, 1])
                loss, exit_p = layers.exit_loss(layers.softmax_with_cross_entropy(logits, every_exit),
                                                gate, beta=exit_beta)
            fetches.update(exit_p=exit_p, hidden=hidden)
        else:
            x = stack_of_layers(x)
            if loss_positions:  # the rest of the positions are context: no logits of theirs are used
                x = layers.slice(x, axes=[1], starts=[0], ends=[loss_positions])
            logits = head(final_norm(x))
            ce = layers.softmax_with_cross_entropy(
                layers.reshape(logits, [-1, vocab_size]), layers.reshape(labels, [-1, 1]))
            if loss_positions:
                feeds["loss_weight"] = layers.data("loss_weight", [loss_positions], dtype="float32")
                ce = layers.elementwise_mul(ce, layers.reshape(feeds["loss_weight"], [-1, 1]))
            loss = fetches["ce"] = layers.mean(ce)
            if aux:  # a decoder of dense layers alone has no auxiliary term
                balance = layers.scale(layers.sums([b for b, _ in aux]), scale=1.0 / len(aux))
                z_loss = layers.scale(layers.sums([z for _, z in aux]), scale=1.0 / len(aux))
                fetches.update(load_balance=balance, router_z=z_loss)
                terms = [loss] + [layers.scale(term, scale=coef) for term, coef in
                                  ((balance, load_balance_coef), (z_loss, router_z_coef)) if coef]
                loss = layers.sums(terms) if len(terms) > 1 else loss
            if index_terms:  # the indexers' own loss, beside the language model's
                fetches["index_kl"] = layers.scale(layers.sums(index_terms), scale=1.0 / len(index_terms))
                loss = layers.sums([loss, fetches["index_kl"]])
        if with_optimizer:
            optimizer.Adam(learning_rate=learning_rate, beta1=beta1, beta2=beta2,
                           epsilon=epsilon).minimize(loss)
    return main, startup, feeds, {"loss": loss, "logits": logits, **fetches}


def fsdp_rules(program, axis="dp", ways=None):
    """Sharding hints that split every matrix of `program` (a
    `build_causal_lm` program, by the names it gives: `lm.tok_emb`, `lm.head.w`
    and the layers' `lm.l<i>.<part>.w`) `ways` ways along its first dimension
    that `ways` divides, over the mesh axis `axis` that also splits the batch:
    ZeRO-3 as GSPMD states it.  The optimizer's accumulators take a parameter's
    hint (`Optimizer._add_accumulator`), so master, gradient and moments lie
    split alike and each chip updates its own rows; GSPMD gathers a matrix where
    a product reads it and scatters its gradient's sum.  Vectors (norm gains,
    biases, D) and what no dimension divides stay whole.  `ways` None takes any
    dimension of 2 and more rows."""
    rules = {}
    for v in program.global_block().all_parameters():
        shape = tuple(v.shape or ())
        if len(shape) < 2 or not re.fullmatch(r"lm\.(tok_emb|head\.w|exit_gate\.w|l\d+\..*)", v.name):
            continue
        dim = next((i for i, n in enumerate(shape) if n > 1 and (ways is None or n % ways == 0)), None)
        if dim is not None:
            rules[re.escape(v.name)] = tuple(axis if i == dim else None for i in range(len(shape)))
    return rules


def tp_rules():
    """Megatron-style TP sharding hints: QKV & FFN1 column-parallel,
    attn-out & FFN2 row-parallel, embeddings vocab-sharded."""
    return {
        r".*\.attn\.[qkv]\.w": (None, "tp"),
        r".*\.attn\.[qkv]\.b": ("tp",),
        r".*\.attn\.out\.w": ("tp", None),
        r".*\.ffn1\.w": (None, "tp"),
        r".*\.ffn1\.b": ("tp",),
        r".*\.ffn2\.w": ("tp", None),
        r"bert\.tok_emb": ("tp", None),
        r"bert\.lm_head\.w": (None, "tp"),
    }


def make_fake_batch(batch_size, seq_len, vocab_size, rng=None, mask_frac=0.15):
    rng = rng or np.random.RandomState(0)
    ids = rng.randint(0, vocab_size, size=(batch_size, seq_len))
    labels = np.where(rng.rand(batch_size, seq_len) < mask_frac, ids, -100)
    pos = np.tile(np.arange(seq_len), (batch_size, 1))
    return {"ids": ids, "labels": labels, "pos_ids": pos}
