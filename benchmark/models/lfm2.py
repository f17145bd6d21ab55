"""LFM2-8B-A1B causal-LM training: how the benchmark builds it through the
framework, a plain float32 reference of the same architecture, and the
operations one sequence needs.

Architecture: LiquidAI/LFM2-8B-A1B `config.json` (`model_type: lfm2_moe`), the
equations as the family's published code has them.  A layer, eps 1e-5, with
a = rms(x; operator_norm) and m = rms(h; ffn_norm):

    h = x + op(a),   y = h + ffn(m);   after the last layer rms(.; embedding_norm) and the head
    conv op   [B, C, u] = split3(a W_in),  W_in 2048 x 6144;   z = B * u
              c[t] = sum_{j=0..K-1} w[:, j] * z[t - (K-1) + j],  z zero before the sequence's start,  K = 3
              op(a) = (C * c) W_out,  W_out 2048 x 2048      (depthwise, causal, no activation, no biases)
    attention q_j = rope(rms_64(Wq_j a)),  j < 32;  k_g = rope(rms_64(Wk_g a)),  v_g = Wv_g a,  g < 8   (theta 1e6, rotate-half)
              op(a) = Wo . concat_j softmax(q_j k_(j div 4)^T / sqrt(64), causal) v_(j div 4)
    dense     ffn(m) = W2( silu(W1 m) * (W3 m) ),  width 7168                    (the leading layers)
    sparse    s = sigmoid_f32(m Wr) over 32;  S = top4(s + b);  g_e = s_e / (sum_{e' in S} s_e' + 1e-6) . 1
              ffn(m) = sum_{e in S and e in HELD} g_e . W2_e( silu(W1_e m) * (W3_e m) ),  width 1792,  HELD = {0..7}
    loss      mean over every position of CE( rms(y_L) E^T, the next token ),  E the embedding table

`rms_64` is an RMSNorm over each head's 64 features with one 64-gain shared by
the heads, before the rotation.  The bias b enters the CHOICE and not the
weight: the weights are the unbiased scores of the chosen four, renormalised
over all four, held or not, so that the four chips' shares of a layer add up
to the layer.

Departures of the program under test
(`paddle_tpu.models.transformer.build_causal_lm`) from the published model,
which the reference follows so that the two compute the same function (the
configuration file's `departures` is this list, word for word):

  * five of the 24 layers, the published layers 1 to 5: conv, full_attention, conv, conv, conv, the first of them dense: one of the two leading dense layers (they count once) and one whole period of the sparse layers, four, the floor; further layers lie on further chips as pipeline stages;
  * 8 of the 32 experts of every sparse layer, experts 0 to 7: this chip's share of a layer whose experts are split over four chips; the router keeps its 32 outputs, its top 4 and its renormalisation over all four chosen, and what the 24 absent experts would have added is left out of the layer's output, in the program and in the reference alike, with no exchange standing in for the three absent chips;
  * 16384 of the 65536 vocabulary rows in the tied embedding table, which is also the head: one chip's quarter of the rows; token ids and labels are drawn from the slice and the loss is over the slice;
  * the expert bias is a buffer that the published training updates by a load-balancing rule `config.json` does not give: here it is drawn once, N(0, 0.02) from the configuration's `routing_seed`, and never updated, so that it changes choices (the run counts how many) and is not a zero added;
  * Adam for AdamW (the framework has no AdamW), learning rate 1e-4 reached by a linear warm-up over the first 200 steps from 1e-6, betas 0.9 / 0.95, epsilon 1e-8, no decay and no auxiliary loss;
  * weights are random, N(0, 0.02) from the run's seed, the short convolutions' filters too, norm gains 1;
  * token ids are uniform random with no padding and no document boundaries (a row is one whole sequence), every position is a label (the next token), so the cross entropy starts near ln(16384).
"""
from __future__ import annotations

import numpy as np

FEEDS = ("ids", "labels", "pos_ids")

#: Every limit below but one (`LEFT_OUT_LOGIT_MAX`) was set from two readings at
#: the published widths (my chip runs, PR 34: thirty runs of the cell, a seed
#: each, 8 x 8192 positions; PERF.md has the table): what the program reads,
#: and what it has to refuse: the same stage a precision lower, which each run
#: prints beside it (`*_bf16*` in its `reference_routing` line), or the
#: reference with a fault in its weights (tools/chip_lfm2_controls.py).
#:
#: A token may route differently in the program and in the reference where the
#: reference's 4th and 5th biased scores lie closer than this, as a share of
#: the 4th (benchmark/models/olmoe.py has the argument: top-k is discontinuous
#: and the program's router reads a bf16 input).  The margin is OLMoE's.  With
#: 32 outputs and four layers all but 0.1% of the positions have some layer
#: under it, so this says little here, as in SDAR's cell; the stage check of
#: the router on its own input says the rest.  A token that routes differently
#: ABOVE it is a routing fault.
ROUTING_MARGIN = 2.0 ** -4
#: Only a flip that moves a HELD expert in or out of a token's four changes what
#: this chip adds, so the logit comparison leaves out the sampled positions
#: whose held choice differs in some layer and counts them over all positions:
#: 5.58% to 5.98% in thirty sound runs (12.5% to 12.9% routed differently at
#: all; a quarter of the experts is held and four are chosen, so a flip meets a
#: held expert twice as often as in SDAR's cell).  The reading it has to refuse
#: (tools/chip_lfm2_controls.py: the reference with a fault in its weights):
#: one held expert that adds nothing leaves out 11.3%.  Nearer faults it does
#: NOT tell apart and `ROUTER_RTOL` does: routers a quarter too strong 7.7%, a
#: router whose logits are rounded to bf16 ~7.1%.
LEFT_OUT_MAX = 0.09
#: ... and how far a left-out position's logits may be off, over the largest
#: |reference logit|: one held expert's output more or less, a quarter of the
#: layer's weight: 0.17 to 0.24.  A sanity bound at twice the most seen (a NaN
#: fails it), NOT a limit between two readings: no fault put into the
#: reference moves it (a dropped expert 0.22, a zeroed last operator 0.18),
#: since what a fault does to these positions it does to all, and
#: `REFERENCE_RTOL` sees that.
LEFT_OUT_LOGIT_MAX = 0.45
#: The larger of the loss's relative error (1e-6 to 3e-5) and the sampled
#: logits' error over the largest |reference logit|, on the positions that
#: chose alike: 1.00e-2 to 1.32e-2 in thirty sound runs (bf16 activations over
#: float32 masters through five layers and a bf16 head over a table of N(0,
#: 0.02)); the limit is the accepted decoder cells'.  What it has to refuse:
#: the last layer's operator adding nothing reads 3.1e-2, one held expert
#: adding nothing 0.122.  Masters rounded to bf16 read CLOSER (9.2e-3: the
#: program casts its masters to bf16), and a bf16 router, bf16 sums in the
#: experts or a bf16 convolution do not show end to end either
#: (benchmark/models/olmoe.py); they are caught a stage at a time.
REFERENCE_RTOL = 2e-2
#: Logits are compared at this many positions of every row (8 x 8192 x 16384
#: float32 logits would be 4.3 GB): spread by a multiplicative hash, the same
#: in the program, the reference and the comparison.
LOGIT_SAMPLE = 256
#: The stages no end-to-end number resolves, on the PROGRAM'S OWN tensors.
#: The router on its own input m (float32 sigmoid scores, the choice by score +
#: bias, the weights the unbiased scores over their sum + 1e-6): the weights'
#: largest relative error 1.5e-6 to 1.7e-6, no position routed elsewhere (0 to
#: 4 ties); with its logits rounded to bf16 1.34e-3 to 1.42e-3 (a sigmoid's
#: weight moves by a quarter of its logit's error at the most, a softmax's by
#: all of it: SDAR's router reads 1.5e-2 there).
ROUTER_TIE = 1e-4
ROUTER_RTOL = 3e-4
#: The held experts on the program's own m, choice and weights: root-mean-square
#: error over the root-mean-square output, every `EXPERTS_SAMPLE`-th of the
#: tokens: 4.74e-3 to 4.76e-3 (bf16 operands into float32 accumulation); with
#: the running sums held in bf16, eight terms at a time, 3.38e-2 to 3.39e-2.
EXPERTS_RTOL = 1.2e-2
EXPERTS_SAMPLE = 512
#: The short convolution of the first layer on the program's own in-projection
#: (bf16) and its float32 filter, `CONV_ROWS` rows: root-mean-square error over
#: the root-mean-square output, 1.657e-3 to 1.660e-3: the op computes in
#: float32 and rounds once (half a bf16 step).  The same with B * u, each tap's
#: product, the running sum, the gate and the filter rounded to bf16: 3.96e-3
#: to 3.98e-3.  The limit lies 1.57x over the one and 1.52x under the other;
#: both readings repeat to three digits (134 million elements).
CONV_RTOL = 2.6e-3
CONV_ROWS = 2
#: The attention (the second layer's, the first there is) on the program's own
#: q, k and v for `ATTENTION_SAMPLE` queries of every row and head against all
#: keys before them, float32 scores: largest error over the largest |output|,
#: 2.0e-3 to 3.3e-3 (the flash kernel rounds the probabilities and the output
#: to bf16).  What it catches is a wrong mask or a wrong key head, which read
#: 0.3 and more (tests/test_sdar.py).  Like SDAR's it does NOT tell bf16 scores
#: apart: the same reference with its scores rounded to bf16 reads 2.6e-3 to
#: 4.4e-3 against itself (`attention_error_bf16_scores`), for under the
#: per-head norm with unit gains the scores are of order 1 (PERF.md, section 7).
ATTENTION_RTOL = 1e-2
ATTENTION_SAMPLE = 192
#: ... and that layer's queries and keys themselves, at the sampled positions,
#: against the reference's (after the per-head norm and the rotation): largest
#: error over the largest |value|, 1.07e-2 to 1.33e-2: a whole layer's bf16
#: roundings lie before them, where SDAR's first layer reads 6.4e-3 to 9.0e-3.
#: A norm over the whole projected width instead of each head moves a head's
#: scale by its own spread, 6% at a standard deviation.
QK_RTOL = 2.5e-2


def _sample(n: int, positions: int):
    return np.unique((np.arange(n, dtype=np.int64) * 2654435761 + 7) % positions)


def logit_sample(positions: int):
    """The positions whose logits are compared."""
    return _sample(LOGIT_SAMPLE, positions)


def attention_sample(positions: int):
    """The positions whose queries the attention stage checks."""
    return _sample(ATTENTION_SAMPLE, positions)


def held(cfg: dict) -> tuple:
    return (cfg["experts_held_first"], cfg["num_experts"])


def build(cfg: dict, job: dict):
    """(main, startup, feed variables by name, loss variable, the variables
    the reference is compared on: loss, the sampled positions' logits, layer by
    sparse layer the top-k expert choice, the router's input, the top-k
    weights, the held experts' output and the router's bias, then the first
    short convolution's input and output and the first attention's q, k, v,
    out) of the train program, as a
    user of the framework gets it: `build_causal_lm`, then the learning rate's
    warm-up and Adam from the traffic file."""
    import paddle_tpu as fluid
    from paddle_tpu import layers
    from paddle_tpu.models import transformer

    main, startup, feeds, fetches = transformer.build_causal_lm(
        vocab_size=cfg["vocab_size"], seq_len=job["seq_len"], d_model=cfg["hidden_size"],
        n_heads=cfg["num_attention_heads"], n_kv_heads=cfg["num_key_value_heads"],
        head_dim=cfg["head_dim"], qk_norm="head", norm_eps=cfg["norm_eps"], rope_theta=cfg["rope_theta"],
        layer_types=cfg["layer_types"], conv_kernel=cfg["conv_L_cache"],
        num_dense_layers=cfg["num_dense_layers"], dense_width=cfg["intermediate_size"],
        expert_width=cfg["moe_intermediate_size"], num_experts=cfg["num_routed_experts"],
        experts_held=held(cfg), top_k=cfg["num_experts_per_tok"], norm_topk_prob=cfg["norm_topk_prob"],
        scoring="sigmoid", routed_scaling_factor=cfg["routed_scaling_factor"],
        norm_topk_eps=cfg["norm_topk_eps"],
        expert_bias=(cfg["expert_bias_std"] if cfg["use_expert_bias"] else 0.0, cfg["routing_seed"]),
        tie_embedding=cfg["tie_word_embeddings"], load_balance_coef=0.0, router_z_coef=0.0,
        with_optimizer=False, dtype=cfg["compute_dtype"])
    with fluid.program_guard(main, startup):
        by_position = layers.transpose(fetches["logits"], [1, 0, 2])
        sampled = layers.gather(by_position, layers.assign(logit_sample(job["seq_len"]).astype("int32")))
        rate = layers.learning_rate_scheduler.linear_lr_warmup(
            job["learning_rate"], job["lr_warmup_steps"], job["lr_warmup_start"], job["learning_rate"])
        fluid.optimizer.Adam(learning_rate=rate, beta1=job["adam_beta1"], beta2=job["adam_beta2"],
                             epsilon=job["adam_epsilon"]).minimize(fetches["loss"])
    ops = main.global_block().ops
    stages = [name for router, experts in zip((op for op in ops if op.type == "moe_router"),
                                              (op for op in ops if op.type == "moe_experts"))
              for name in (router.outputs["TopKIndex"][0], router.inputs["X"][0],
                           router.outputs["TopKProb"][0], experts.outputs["Out"][0],
                           router.inputs["Bias"][0])]
    conv = next(op for op in ops if op.type == "short_conv")
    stages += [conv.inputs["X"][0], conv.outputs["Out"][0]]
    attention = next(op for op in ops if op.type == "fused_attention")
    stages += [attention.inputs[s][0] for s in ("Q", "K", "V")] + [attention.outputs["Out"][0]]
    return (main, startup, feeds, fetches["loss"],
            [fetches["loss"].name, sampled.name] + stages)


def make_batch(rng: np.random.RandomState, cfg: dict, job: dict, rows: int) -> dict:
    """One host batch as a reader yields it: uniform random ids from the
    slice, the next token as every position's label (the last position's is
    one id more), positions 0..L-1."""
    seq = job["seq_len"]
    tokens = rng.randint(0, cfg["vocab_size"], size=(rows, seq + 1)).astype("int64")
    return {"ids": tokens[:, :-1], "labels": tokens[:, 1:],
            "pos_ids": np.tile(np.arange(seq, dtype="int64"), (rows, 1))}


def flops_per_sample(cfg: dict, job: dict) -> float:
    """Operations the forward and backward passes of one sequence require
    (matrix multiplications only, 2 per multiply-add, backward twice the
    forward, nothing recomputed): per position a short-convolution layer's two
    projections (d x 3d, d x d), an attention layer's four (32 and 8 heads of
    64) and its two products over the causal pairs, a dense layer's three
    products at 7168, a sparse layer's router and three products in each of
    the position's held experts, ONE on average (4 chosen x 8 held of 32, a
    uniform router's share), and the head.  Nothing for the convolutions' taps
    and gates, which no matrix unit computes (`short_conv_flops` counts them)."""
    d, seq = cfg["hidden_size"], job["seq_len"]
    q_width = cfg["num_attention_heads"] * cfg["head_dim"]
    kv_width = cfg["num_key_value_heads"] * cfg["head_dim"]
    held_share = cfg["num_experts_per_tok"] * cfg["num_experts"] / cfg["num_routed_experts"]
    per_position = 0.0
    for i, kind in enumerate(cfg["layer_types"]):
        if kind == "conv":
            per_position += 2 * d * 3 * d + 2 * d * d
        else:
            per_position += 2 * d * (2 * q_width + 2 * kv_width) + 2 * 2 * q_width * (seq + 1) / 2
        if i < cfg["num_dense_layers"]:
            per_position += 3 * 2 * d * cfg["intermediate_size"]
        else:
            per_position += 2 * d * cfg["num_routed_experts"] + held_share * 3 * 2 * d * cfg["moe_intermediate_size"]
    per_position += 2 * d * cfg["vocab_size"]
    return 3.0 * seq * per_position


def _conv_elements(cfg: dict, job: dict) -> int:
    """Output elements of a step's short convolutions: tokens x d a layer."""
    return (job["batch_per_chip"] * job["seq_len"] * cfg["hidden_size"]
            * sum(kind == "conv" for kind in cfg["layer_types"]))


def short_conv_flops(cfg: dict, job: dict) -> float:
    """Operations of a training step's `short_conv` ops, nothing recomputed: an
    output element costs B * u, K multiply-adds and the gate forward (2 + 2K),
    and backward the two gates' gradients (dC, dc), K multiply-adds each into
    dz and into the filter's gradient, and dB and du (4 + 4K)."""
    taps = cfg["conv_L_cache"]
    return float((6 + 6 * taps) * _conv_elements(cfg, job))


def short_conv_bytes(cfg: dict, job: dict, itemsize: int = 2) -> float:
    """Bytes those ops have to move at the least: forward reads [tokens, 3d]
    and writes [tokens, d]; backward reads the incoming gradient [tokens, d]
    and [tokens, 3d] and writes [tokens, 3d]; the filter's bytes are nothing."""
    return float((4 + 7) * _conv_elements(cfg, job) * itemsize)


def reference(params: dict, batch: dict, cfg: dict, program=None):
    """(loss, the sampled positions' logits [rows, sample, vocab], margin
    [rows, L], choice [sparse layers, rows, L, 4], the float32 router, gate,
    up and down weights and the biases stacked by sparse layer and the first
    filter, for the stage checks, then (first held expert, the
    renormalisation's epsilon, the scaling factor), and the first attention's
    queries and keys at `attention_sample`'s positions [rows, heads, sample,
    64]) of `batch` in plain float32 jax.numpy, one sequence and two query
    heads at a time; `params` maps the program's parameter names to arrays
    (the routers' biases are no parameters: `router_biases` has where they
    come from).  No kernel and no sort: attention is explicit causal scores, the
    convolution K shifted products, and every held expert is applied to every
    position and weighted by the renormalised choice.  `margin` is the gap
    between a position's 4th and 5th biased score as a share of the 4th, the
    smallest over the layers; `choice` the chosen experts, ascending."""
    import jax
    import jax.numpy as jnp

    kinds, eps, theta = cfg["layer_types"], cfg["norm_eps"], cfg["rope_theta"]
    hq, hkv, dh = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    top_k, taps = cfg["num_experts_per_tok"], cfg["conv_L_cache"]
    first, n_held = held(cfg)
    heads_at_once = 2 if hq % 2 == 0 else 1
    sparse = [i for i in range(len(kinds)) if i >= cfg["num_dense_layers"]]
    biases = router_biases(params, cfg, sparse)

    def p(name):
        return jnp.asarray(params[name], jnp.float32)

    def rms(x, name):
        return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps) * p(name)

    def rope(t, pos):  # t [H, P, dh]
        half = dh // 2
        angle = pos.astype(jnp.float32)[:, None] * theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
        cos = jnp.concatenate([jnp.cos(angle)] * 2, -1)
        sin = jnp.concatenate([jnp.sin(angle)] * 2, -1)
        return t * cos + jnp.concatenate([-t[..., half:], t[..., :half]], -1) * sin

    def one_sequence(row):
        ids, labels, pos = row
        seq = ids.shape[0]
        at = jnp.arange(seq)
        x = p("lm.tok_emb")[ids]
        margin = jnp.full((seq,), jnp.inf)
        choices, first_qk = [], None
        sample = attention_sample(seq)
        for i, kind in enumerate(kinds):
            pre = f"lm.l{i}"
            a = rms(x, f"{pre}.ln1.w")
            if kind == "conv":
                gate_in, gate_out, u = jnp.split(a @ p(f"{pre}.conv.in.w"), 3, -1)
                z, w = gate_in * u, p(f"{pre}.conv.filter.w")
                c = sum(w[:, j] * jnp.pad(z, ((taps - 1 - j, 0), (0, 0)))[:seq] for j in range(taps))
                h = x + (gate_out * c) @ p(f"{pre}.conv.out.w")
            else:
                def heads(t, n, norm=None):
                    t = t.reshape(seq, n, dh)
                    if norm is not None:
                        t = rms(t, norm)
                    return t.transpose(1, 0, 2)

                q = rope(heads(a @ p(f"{pre}.attn.q.w"), hq, f"{pre}.attn.q_norm.w"), pos)
                k = rope(heads(a @ p(f"{pre}.attn.k.w"), hkv, f"{pre}.attn.k_norm.w"), pos)
                v = heads(a @ p(f"{pre}.attn.v.w"), hkv)
                first_qk = first_qk or (q[:, sample], k[:, sample])

                def some_heads(j):  # query heads j . heads_at_once and the next ones
                    qs = jax.lax.dynamic_slice_in_dim(q, j * heads_at_once, heads_at_once, 0)
                    group = (j * heads_at_once) // (hq // hkv)
                    scores = jnp.einsum("hqd,kd->hqk", qs, k[group]) / np.sqrt(dh)
                    scores = jnp.where(at[None, :] <= at[:, None], scores, -jnp.inf)
                    return jnp.einsum("hqk,kd->hqd", jax.nn.softmax(scores, -1), v[group])

                ctx = jax.lax.map(some_heads, jnp.arange(hq // heads_at_once)).reshape(hq, seq, dh)
                h = x + ctx.transpose(1, 0, 2).reshape(seq, hq * dh) @ p(f"{pre}.attn.out.w")
            m = rms(h, f"{pre}.ln2.w")
            if i < cfg["num_dense_layers"]:
                x = h + (jax.nn.silu(m @ p(f"{pre}.ffn.gate.w")) * (m @ p(f"{pre}.ffn.up.w"))) @ p(f"{pre}.ffn.down.w")
                continue
            scores = jax.nn.sigmoid(m @ p(f"{pre}.moe.router.w"))
            biased = scores + biases[sparse.index(i)]
            ranked = jnp.sort(biased, -1)[:, ::-1]
            kth, after = ranked[:, top_k - 1], ranked[:, top_k]
            chosen = jnp.where(biased >= kth[:, None], scores, 0.0)      # the UNBIASED scores of the chosen
            gates = (chosen / (jnp.sum(chosen, -1, keepdims=True) + cfg["norm_topk_eps"])
                     * cfg["routed_scaling_factor"])                      # over all four, held or not

            def expert(acc, ew):
                gate, up, down, g_e = ew
                return acc + (jax.nn.silu(m @ gate) * (m @ up)) @ down * g_e[:, None], None

            moe_out, _ = jax.lax.scan(
                expert, jnp.zeros_like(h),
                (p(f"{pre}.moe.gate.w"), p(f"{pre}.moe.up.w"), p(f"{pre}.moe.down.w"),
                 gates[:, first:first + n_held].T))
            x = h + moe_out
            margin = jnp.minimum(margin, (kth - after) / jnp.abs(kth))
            choices.append(jnp.sort(jax.lax.top_k(biased, top_k)[1], -1))
        head = p("lm.tok_emb").T if cfg["tie_word_embeddings"] else p("lm.head.w")
        out = rms(x, "lm.final_norm.w") @ head
        logp = jax.nn.log_softmax(out, -1)
        ce = -jnp.take_along_axis(logp, labels[:, None], 1)[:, 0]
        return (out[logit_sample(seq)], margin, jnp.stack(choices), jnp.sum(ce)) + first_qk

    with jax.default_matmul_precision("highest"):
        rows = tuple(jnp.asarray(batch[n], jnp.int32) for n in FEEDS)
        out, margin, choice, ce_sum, q_first, k_first = jax.lax.map(one_sequence, rows)
        loss = ce_sum.sum() / rows[1].size
        weights = tuple(jnp.stack([p(f"lm.l{i}.moe.{n}.w") for i in sparse])
                        for n in ("router", "gate", "up", "down"))
        conv_at = kinds.index("conv")
        return ((loss, out, margin, choice.transpose(1, 0, 2, 3)) + weights
                + (jnp.stack(biases), p(f"lm.l{conv_at}.conv.filter.w"),
                   jnp.asarray([first, cfg["norm_topk_eps"], cfg["routed_scaling_factor"]], jnp.float32),
                   q_first, k_first))


def router_biases(params: dict, cfg: dict, sparse: list) -> list:
    """Layer by sparse layer the routers' biases [experts], float32.  They are
    buffers and no parameters, so the runner's `params` does not hold them:
    they are then drawn as the configuration says, N(0, `expert_bias_std`)
    from `jax.random.PRNGKey(routing_seed + layer)`, whatever the run's seed
    (the comparison checks that the program's are these, bit for bit).  Where
    `params` holds them by name (the tests hand them in), those."""
    import jax
    import jax.numpy as jnp

    names = [f"lm.l{i}.moe.router.bias" for i in sparse]
    if all(n in params for n in names):
        return [jnp.asarray(params[n], jnp.float32) for n in names]
    std = cfg["expert_bias_std"] if cfg["use_expert_bias"] else 0.0
    return [std * jax.random.normal(jax.random.PRNGKey(cfg["routing_seed"] + i),
                                    (cfg["num_routed_experts"],), jnp.float32) for i in sparse]


def _bf16(x):
    """float32 holding the nearest bf16 values (round to nearest even)."""
    bits = np.ascontiguousarray(x, "f4").view(np.uint32)
    return ((bits + 0x7FFF + ((bits >> 16) & 1)) & 0xFFFF0000).view("f4")


def _sigmoid(x):
    return 1.0 / (1.0 + np.exp(-x))


def stage_errors(choice, m, top_p, out, bias, router, gate, up, down, first: int, eps: float, scaling: float) -> dict:
    """One layer's router and held experts on the program's own router input
    `m` [tokens, d] (see `ROUTER_RTOL`, `EXPERTS_RTOL`): its `choice` and
    `top_p` [tokens, 4] and its experts' `out` [tokens, d] against float32
    numpy over the float32 weights; `gate`, `up`, `down` hold the experts
    `first` on.  `bias_moved`: the choices the unbiased top-k would not have
    made, which the program's own counter must match."""
    tokens, k = choice.shape
    logits = (m @ router).astype("f8")
    scores = _sigmoid(logits)
    biased = scores + bias.astype("f8")
    ranked = np.sort(biased, -1)
    tie = (ranked[:, -k] - ranked[:, -k - 1]) < ROUTER_TIE * np.abs(ranked[:, -k])
    differs = (np.sort(np.argsort(-biased, -1)[:, :k], -1) != np.sort(choice, -1)).any(-1)
    unbiased = np.argsort(-scores, -1)[:, :k]
    moved = int((~(choice[:, :, None] == unbiased[:, None, :]).any(-1)).sum())

    def weights(s):  # of the program's choice, from the UNBIASED scores s
        mine = np.take_along_axis(s, choice, -1)
        return mine / (mine.sum(-1, keepdims=True) + eps) * scaling

    mine = weights(scores)
    sample = np.arange(0, tokens, max(tokens // EXPERTS_SAMPLE, 1))
    want = np.zeros((len(sample), m.shape[1]), "f4")
    for e in range(gate.shape[0]):
        row, slot = np.nonzero(choice[sample] == first + e)
        x = m[sample[row]]
        g = x @ gate[e]
        want[row] += ((g * _sigmoid(g) * (x @ up[e])) @ down[e]) * top_p[sample[row], slot][:, None]
    # the same two stages a precision lower, against this float32: a router
    # whose logits are rounded to bf16, and experts whose running sums are
    # (eight terms at a time): what the two limits have to exclude
    low_scores = _sigmoid(_bf16(logits.astype("f4")).astype("f8"))
    low = weights(low_scores)
    low_choice = np.argsort(-(low_scores + bias.astype("f8")), -1)[:, :k]

    def held_of(c):  # [tokens, held]: which held experts a token chose
        return (c[..., None] == np.arange(first, first + gate.shape[0])).any(-2)

    held_flips = (held_of(low_choice) != held_of(choice)).any(-1)

    def product_in_bf16(x, w):
        acc = np.zeros((x.shape[0], w.shape[1]), "f4")
        for i in range(0, x.shape[1], 8):
            acc = _bf16(acc + x[:, i:i + 8] @ w[i:i + 8])
        return acc

    rounded = np.zeros_like(want)
    for e in range(gate.shape[0]):
        row, slot = np.nonzero(choice[sample] == first + e)
        x = m[sample[row]]
        g, u = product_in_bf16(x, _bf16(gate[e])), product_in_bf16(x, _bf16(up[e]))
        hidden = _bf16(g * _sigmoid(g) * u * top_p[sample[row], slot][:, None])
        rounded[row] += product_in_bf16(hidden, _bf16(down[e]))
    mean_square = max(np.mean(np.square(want)), 1e-30)
    return {
        "router_choice_differs": int((differs & ~tie).sum()),
        "router_ties": int((differs & tie).sum()),
        "router_prob_error": float((np.abs(top_p - mine) / mine).max()),
        "router_prob_error_bf16_logits": float((np.abs(low - mine) / mine).max()),
        "bias_moved": moved,
        "held_choice_flips_bf16_logits": int(held_flips.sum()),
        "experts_error": float(np.sqrt(np.mean(np.square(out[sample] - want)) / mean_square)),
        "experts_error_bf16_sums": float(np.sqrt(np.mean(np.square(rounded - want)) / mean_square)),
    }


def _conv(x3, w, rounding=lambda t: t):
    """C * conv_K(B * u) of x3 [rows, T, 3d] with filter w [d, K] in float32
    numpy; `rounding` is applied to every intermediate."""
    gate_in, gate_out, u = np.split(np.asarray(x3, "f4"), 3, axis=-1)
    z = rounding(gate_in * u)
    taps, acc = w.shape[1], None
    for j in range(taps):
        back = taps - 1 - j
        shifted = np.pad(z, ((0, 0), (back, 0), (0, 0)))[:, :z.shape[1]]
        term = rounding(shifted * w[:, j])
        acc = term if acc is None else rounding(acc + term)
    return rounding(gate_out * acc)


def conv_errors(x3, out, w) -> dict:
    """The program's short convolution [rows, T, d] against float32 numpy on
    its own in-projection `x3` and filter `w`, the first `CONV_ROWS` rows:
    root-mean-square error over the root-mean-square output; and the same
    convolution with every intermediate (and the filter) rounded to bf16."""
    x3, out, w = np.asarray(x3[:CONV_ROWS], "f4"), np.asarray(out[:CONV_ROWS], "f4"), np.asarray(w, "f4")
    want = _conv(x3, w)
    scale = max(np.sqrt(np.mean(np.square(want))), 1e-30)
    return {"conv_error": float(np.sqrt(np.mean(np.square(out - want))) / scale),
            "conv_error_bf16": float(np.sqrt(np.mean(np.square(_conv(x3, _bf16(w), _bf16) - want))) / scale)}


def attention_errors(q, k, v, out, want_q, want_k) -> dict:
    """The program's attention output [rows, Hq, L, dh] against float32 numpy
    on its own q, k [rows, Hkv, L, dh] and v, for `attention_sample`'s queries
    of every row and head against the keys up to each: largest |error| over
    the largest |output|.  The same reference with its scores rounded to bf16
    against itself: what the limit must exclude.  And the program's q and k at
    those positions against the reference's `want_q`, `want_k`."""
    rows, hq, positions, dh = q.shape
    group = hq // k.shape[1]
    sample = attention_sample(positions)
    allowed = np.arange(positions)[None, :] <= sample[:, None]
    worst = rounded = largest = 0.0
    for r in range(rows):
        for g in range(k.shape[1]):
            keys, values = np.asarray(k[r, g], "f4"), np.asarray(v[r, g], "f4")
            for j in range(g * group, (g + 1) * group):
                scores = np.asarray(q[r, j][sample], "f4") @ keys.T / np.sqrt(dh)

                def attend(s):
                    s = np.where(allowed, s, -np.inf)
                    e = np.exp(s - s.max(-1, keepdims=True))
                    return (e / e.sum(-1, keepdims=True)) @ values

                want = attend(scores)
                worst = max(worst, float(np.abs(np.asarray(out[r, j][sample], "f4") - want).max()))
                rounded = max(rounded, float(np.abs(attend(_bf16(scores)) - want).max()))
                largest = max(largest, float(np.abs(want).max()))
    qk = max(float(np.abs(np.asarray(mine[:, :, sample], "f4") - theirs).max() / np.abs(theirs).max())
             for mine, theirs in ((q, np.asarray(want_q, "f4")), (k, np.asarray(want_k, "f4"))))
    return {"attention_error": worst / max(largest, 1e-30),
            "attention_error_bf16_scores": rounded / max(largest, 1e-30),
            "qk_error": qk}


def compare(got, want) -> dict:
    """The program's (loss, sampled logits [sample, rows, vocab], sparse layer
    by sparse layer top-k choice, router input, top-k weights, held experts'
    output and bias, then the first convolution's input and output and the
    first attention's q, k, v, out) against the reference's (loss, logits,
    margin, choice, router, gate, up and down weights, biases, filter, (first
    held expert, epsilon, scaling), sampled queries and keys): the two errors
    `REFERENCE_RTOL` bounds, the routing account, and the worst layer's stage
    errors."""
    loss, want_loss = float(np.asarray(got[0]).reshape(-1)[0]), float(want[0])
    want_logits = np.asarray(want[1], "f4")                                   # [rows, sample, vocab]
    logits = np.asarray(got[1], "f4").transpose(1, 0, 2)
    margin, want_choice = np.asarray(want[2]), np.asarray(want[3])
    rows, seq = margin.shape
    tokens, k = margin.size, want_choice.shape[-1]
    (first, eps, scaling), n_held = (float(n) for n in np.asarray(want[10])), np.asarray(want[5]).shape[1]
    first = int(first)
    layers = [got[i:i + 5] for i in range(2, len(got) - 6, 5)]
    biases_differ = int(sum((np.asarray(layer[4], "f4") != np.asarray(want[8][i], "f4")).sum()
                            for i, layer in enumerate(layers)))
    choice = np.sort(np.stack([np.asarray(layer[0]).reshape(want_choice.shape[1:])
                               for layer in layers]), -1)
    routed_differently = (choice != want_choice).any(axis=(0, 3))           # [rows, L]

    def held_choice(c):  # [layers, rows, L, held]: which held experts a position chose
        return (c[..., None] == np.arange(first, first + n_held)).any(-2)

    differs = (held_choice(choice) != held_choice(want_choice)).any(axis=(0, 3))
    sampled = differs[:, logit_sample(seq)]
    err = np.abs(logits - want_logits).max(-1)
    stages = [stage_errors(np.asarray(c).reshape(tokens, k), np.asarray(m, "f4").reshape(tokens, -1),
                           np.asarray(p, "f4").reshape(tokens, k), np.asarray(o, "f4").reshape(tokens, -1),
                           np.asarray(b, "f4"), *(np.asarray(w[i], "f4") for w in want[4:8]), first, eps, scaling)
              for i, (c, m, p, o, b) in enumerate(layers)]
    scale = max(np.abs(want_logits).max(), 1e-9)
    summed = ("bias_moved", "router_choice_differs", "router_ties", "held_choice_flips_bf16_logits")
    return {
        "loss_error": abs(loss - want_loss) / max(abs(want_loss), 1e-6),
        "logit_error": float(err[~sampled].max(initial=0.0) / scale),
        "logit_error_left_out": float(err[sampled].max(initial=0.0) / scale),
        "tokens": int(tokens),
        "left_out": int(differs.sum()),
        "routed_differently": int(routed_differently.sum()),
        "under_margin": int((margin < ROUTING_MARGIN).sum()),
        "routed_differently_above_margin": int((routed_differently & (margin >= ROUTING_MARGIN)).sum()),
        **{name: (sum if name in summed else max)(stage[name] for stage in stages) for name in stages[0]},
        "bias_moved_share_max": max(stage["bias_moved"] for stage in stages) / (tokens * k),
        "biases_differ": biases_differ,
        "held_rows_share": [float(held_choice(c[None]).sum() / (tokens * k)) for c in choice],
        **conv_errors(got[-6], got[-5], np.asarray(want[9], "f4")),
        **attention_errors(*got[-4:], want[11], want[12]),
    }


def reference_error(got, want) -> float:
    """How far the program is from the reference, as `REFERENCE_RTOL` counts
    it: the larger of the loss's and the sampled logits' error, the logits
    over the positions whose held choice agrees.  Positions that chose other
    held experts are left out AND COUNTED (the `reference_routing` line of the
    run).  A failure (infinite error) is: more than `LEFT_OUT_MAX` of them,
    one that routed differently across a gap wider than `ROUTING_MARGIN`, one
    whose logits are off by more than `LEFT_OUT_LOGIT_MAX`, or a router, held
    experts, a short convolution or an attention that miss float32 on the
    program's own tensors by more than `ROUTER_RTOL`, `EXPERTS_RTOL`,
    `CONV_RTOL` or `ATTENTION_RTOL`, or queries or keys that miss the
    reference's by more than `QK_RTOL`."""
    import json

    found = compare(got, want)
    print(json.dumps({"info": "reference_routing", **found,
                      "left_out_share": found["left_out"] / found["tokens"],
                      "routing_margin": ROUTING_MARGIN, "left_out_max": LEFT_OUT_MAX,
                      "left_out_logit_max": LEFT_OUT_LOGIT_MAX, "router_rtol": ROUTER_RTOL,
                      "experts_rtol": EXPERTS_RTOL, "conv_rtol": CONV_RTOL,
                      "attention_rtol": ATTENTION_RTOL, "qk_rtol": QK_RTOL}),
          flush=True)
    if (found["routed_differently_above_margin"]
            or not found["left_out"] <= LEFT_OUT_MAX * found["tokens"]
            or not found["logit_error_left_out"] <= LEFT_OUT_LOGIT_MAX
            or found["router_choice_differs"] or found["biases_differ"]
            or not found["router_prob_error"] <= ROUTER_RTOL
            or not found["experts_error"] <= EXPERTS_RTOL
            or not found["conv_error"] <= CONV_RTOL
            or not found["attention_error"] <= ATTENTION_RTOL
            or not found["qk_error"] <= QK_RTOL):
        return float("inf")
    return max(found["loss_error"], found["logit_error"])
