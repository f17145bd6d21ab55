"""Kimi-Linear-48B-A3B causal-LM training: how the benchmark builds it through
the framework, a plain float32 reference of the same architecture, and the
operations one sequence needs.

Architecture: moonshotai/Kimi-Linear-48B-A3B-Instruct `config.json`
(`model_type: kimi_linear`); what it does not give follows the Kimi Linear
report (arXiv:2510.26692) and the public `KimiDeltaAttention` layer it ships
with, and is listed in the configuration file's `assumed`.  A layer, eps 1e-5,
no biases but b_g, with a = rms(x; ln1) and m = rms(h; ln2):

    h = x + op(a),   y = h + ffn(m);   after the last layer rms(.; final_norm) and the untied head
    kda       32 heads of 128.  For z in (q, k, v): z' = a Wz [2304 -> 4096];  z'' = silu(conv_4(z')), depthwise,
              causal, zeros before the sequence's start;  q = l2(q'') 128^-0.5,  k = l2(k''),  v = v'',
              l2(t) = t / sqrt(sum_head t^2 + 1e-6)
              g = -exp(A_log[h]) softplus((a Wf1) Wf2 + dt_bias)   [2304 -> 128 -> 4096], float32, alpha = exp(g)
              beta = sigmoid(a Wb)[h]                              [2304 -> 32]
              S_t = (I - beta_t k_t k_t^T) Diag(alpha_t) S_{t-1} + beta_t k_t v_t^T,  S_0 = 0,  S in R^{128 x 128} a head
              o_t = S_t^T q_t
              op(a) = [ rms_head(o_t; o_norm) * sigmoid((a Wg1) Wg2 + b_g) ] Wo     [4096 -> 2304]
    latent    32 heads.  q = a Wq [2304 -> 32 x 192];  [c ; k_r] = a Wkva [2304 -> 512 + 64];
              [k_n ; v] = rms(c; kv_norm) Wkvb [512 -> 32 x (128 + 128)];  head h's key [k_n[h] ; k_r], 192 wide,
              NO rotary embedding on either part;  op(a) = Wo . concat_h softmax(q_h k_h^T / sqrt(192), causal) v_h
    dense     ffn(m) = W2( silu(W1 m) * (W3 m) ),  width 9216                     (the leading layer)
    sparse    s = sigmoid_f32(m Wr) over 256;  S = top8(s + b);  g_e = 2.446 s_e / (sum_{e' in S} s_e' + 1e-20)
              ffn(m) = sum_{e in S and e in HELD} g_e . W2_e( silu(W1_e m) * (W3_e m) )  +  shared(m),
              experts of width 1024,  HELD = {0..7},  shared(m) one more such expert that EVERY token passes, unweighted
    loss      mean over every position of CE( rms(y_L) W_head, the next token )

The reference computes KDA as the recurrence above, token by token (`lax.scan`
over t with a [128 x 128] state a head), never the chunked form the program's
op uses; the convolution as four shifted multiply-adds; latent attention as a
dense [L, L] softmax, two heads at a time; the experts as a loop over the
eight.

Departures of the program under test
(`paddle_tpu.models.transformer.build_causal_lm`) from the published model,
which the reference follows so that the two compute the same function (the
configuration file's `departures` is this list, word for word):

  * five of the 27 layers, the published layers 1 to 5: kda (dense), kda, kda, latent_attention, kda: the leading dense layer once and one whole period of the sparse layers at the published three to one, four, the floor; further layers lie on further chips as pipeline stages;
  * 8 of the 256 routed experts of every sparse layer, experts 0 to 7: this chip's share of a layer whose experts are split over 32 chips; the router keeps its 256 outputs, its top 8 and its renormalisation over all eight chosen, the shared expert is computed here as on every chip, and what the 248 absent experts would have added is left out of the layer's output, in the program and in the reference alike, with no exchange standing in for the 31 absent chips;
  * 20480 of the 163840 vocabulary rows, in the embedding and in the untied head: one chip's eighth of the rows, the guide's floor (not a thirty-second: the vocabulary is split eight ways here); token ids and labels are drawn from the slice and the loss is over the slice;
  * the expert bias is a buffer that the published training updates by a load-balancing rule `config.json` does not give: here it is drawn once, N(0, 0.02) from the configuration's `routing_seed`, and never updated, so that it changes choices (the run counts how many) and is not a zero added;
  * Adam for AdamW (the framework has no AdamW), learning rate 1e-4 reached by a linear warm-up over the first 200 steps from 1e-6, betas 0.9 / 0.95, epsilon 1e-8, no decay and no auxiliary loss;
  * weights are random, N(0, 0.02) from the run's seed, the convolutions' taps too, norm gains 1, A_log uniform on [0, ln 16] a head and dt_bias uniform on [ln 1e-3, ln 1e-1] a channel (a decay of 0.2 to 0.999 a token), b_g 0;
  * token ids are uniform random with no padding and no document boundaries (a row is one whole sequence, the state starts at zero with it), every position is a label (the next token), so the cross entropy starts near ln(20480).
"""
from __future__ import annotations

import functools

import numpy as np

from benchmark.models import lfm2 as _decoder

FEEDS = ("ids", "labels", "pos_ids")

#: The router's and the held experts' limits are LFM2's cell's (benchmark/models/
#: lfm2.py has each one's two readings): the same ops with the same scoring,
#: bias and renormalisation, over 256 outputs and 8 chosen in place of 32 and 4;
#: they read here as there (my chip runs, PR 42: `router_prob_error` 0.9e-6 to
#: 1.0e-6 and 1.2e-3 with bf16 logits, `experts_error` 4.6e-3 and 3.35e-2 with
#: bf16 sums).  The other limits are set from this cell's own readings (PERF.md,
#: section 6, PR 42, has the table and says which are from two seeds only).
ROUTING_MARGIN = _decoder.ROUTING_MARGIN
#: Sampled positions whose held choice differs in some layer are left out of
#: the logit comparison AND COUNTED over all positions: 2.9% to 3.4% in
#: fourteen runs (8 of 256 held and 8 chosen: a flip meets a held expert a
#: quarter as often as in LFM2's cell, but four layers of 256 near-tied outputs
#: flip 41% of the positions somewhere).  A sanity bound at 1.8x the most seen,
#: as LFM2's is at 1.5x; it tells no fault that `ROUTER_RTOL` does not.
LEFT_OUT_MAX = 0.06
#: ... and how far a left-out position's logits may be off, over the largest
#: |reference logit|: one held expert's output more or less (0.09 to 0.14).  As
#: LFM2's, a sanity bound (a NaN fails it), not a limit between two readings.
LEFT_OUT_LOGIT_MAX = 0.45
#: The larger of the loss's relative error (2e-7 to 3e-5) and the sampled
#: logits' error over the largest |reference logit|, on the positions that
#: chose alike: 1.43e-2 to 1.71e-2 in fourteen runs, a seed each (bf16 activations
#: over float32 masters through five layers, four of them scans whose output is
#: rounded once more, and a bf16 head), where LFM2's cell reads 1.0e-2 to
#: 1.3e-2 under the accepted 2e-2; that limit would leave this cell a sixth
#: of room over the most seen, so it has its own, 1.46x over it.  What it has to refuse
#: (tools/chip_kimi_controls.py): a sparse layer without its shared expert.
REFERENCE_RTOL = 2.5e-2
LOGIT_SAMPLE = 256
ROUTER_TIE = _decoder.ROUTER_TIE
ROUTER_RTOL = _decoder.ROUTER_RTOL
EXPERTS_RTOL = _decoder.EXPERTS_RTOL
#: The shared expert on the program's own m, every `EXPERTS_SAMPLE`-th token:
#: root-mean-square error over the root-mean-square output against float32
#: numpy: 4.23e-3 to 4.26e-3 (three bf16 products with float32 accumulation, as a routed
#: expert's 4.6e-3): the routed experts' limit.
SHARED_RTOL = _decoder.EXPERTS_RTOL
#: The stage rows: the program's own tensors of these stages are compared on
#: the first `STAGE_ROWS` of the 8 check rows (the slices are ops of the
#: program, so that 8 rows of every stage's operands never lie in the chip's
#: memory beside the optimizer's state).
STAGE_ROWS = 2
#: THE KDA STAGE: the op's output against the token-by-token float32 recurrence
#: ON THE PROGRAM'S OWN q, k, v, g, beta (first and last KDA layer, all 32
#: heads, 4096 tokens, the stage rows), the recurrence's output ROUNDED to bf16
#: as the op rounds its own: root-mean-square difference over the
#: root-mean-square output.  Against the float32 output the op's own rounding is
#: all there is to see (1.66e-3 sound, 1.73e-3 with a bf16 state: no limit fits
#: between); rounded alike, an op that computes in float32 and rounds once
#: differs only where its last bits cross a rounding boundary.  Readings (my
#: chip runs, PR 42): **6.08e-4 to 6.49e-4** in the cell's fourteen runs (on
#: drawn inputs the op alone reads 1.80e-4; the program's keys are mixtures of
#: their neighbours, T's entries are larger and the six bf16 passes of a
#: float32 product show).  What it has to refuse, on drawn inputs
#: (tools/chip_kimi_kernels.py): the state rounded to bf16 at the chunks'
#: boundaries 9.9e-4 (5.5x the sound 1.80e-4), the cumulative decay rounded to
#: bf16 1.37e-2, the op's products at the default precision (bf16 operands, the
#: nearest precision below) 4.4e-3, Diag(alpha) dropped 5.8; each run prints
#: the recurrence with a bf16 state a token beside it (1.16e-2 to 1.18e-2).
#: IN THE CELL (tools/chip_kimi_controls.py, my chip run, PR 42: the faults put
#: into the program, seed 3900000017, sound 6.24e-4): the bf16 state 2.31e-3, the
#: bf16 cumulative decay 4.69e-3, the default precision 4.76e-3, no decay 2.58;
#: the sampled logits stay under their limit for the first three.  The limit
#: stands 1.85x over the largest sound reading and 1.93x under the least fault.
KDA_RTOL = 1.2e-3
#: The three convolutions of the first KDA layer on the program's own q', k',
#: v' (bf16) and float32 taps, the stage rows: root-mean-square error over the
#: root-mean-square output, the worst of the three: 1.6585e-3 to 1.6594e-3 (the
#: op computes in float32 and rounds once; LFM2's gated form reads 1.66e-3);
#: with each tap's product, the running sum, the SiLU and the taps rounded to
#: bf16 3.88e-3 to 3.89e-3 (the least of the three).  LFM2's limit: 1.57x over
#: the one, 1.49x under the other.
CONV_RTOL = 2.6e-3
#: The latent attention on the program's own q, k (192 wide) and v (128 wide)
#: for `ATTENTION_SAMPLE` queries of the stage rows and every head against all
#: keys before them, float32 scores: largest error over the largest |output|,
#: 2.6e-3 to 4.1e-3.  It catches a wrong mask (tests/test_kimi_linear.py: keys
#: after the query) or a key whose shared part is another head's; like the
#: other cells' it does not tell bf16 scores from float32 (2.1e-3 to 3.1e-3
#: against itself: PERF.md section 7, defect 13c).
ATTENTION_RTOL = 1e-2
ATTENTION_SAMPLE = _decoder.ATTENTION_SAMPLE
#: ... and that layer's queries and keys themselves at the sampled positions
#: against the reference's, over the largest |value|, at the positions whose
#: held choice agrees in every layer: 1.25e-2 to 1.52e-2 in twelve runs (three
#: layers' bf16 roundings lie before them; LFM2's, after one layer, 1.07e-2 to
#: 1.33e-2 under the same limit).  The latent layer stands after two sparse
#: layers, so a position that chose other held experts carries an expert's
#: output more or less in its q and k: those read 6.4e-2 to 1.24e-1 and are
#: printed beside (`qk_error_left_out`), as the logits' are.  A norm over the
#: whole projected width instead of the latent, or a key whose shared part is
#: missing, is off by the part's own size.
QK_RTOL = 2.5e-2

logit_sample = _decoder.logit_sample
attention_sample = _decoder.attention_sample
held = _decoder.held
make_batch = _decoder.make_batch
router_biases = _decoder.router_biases
_bf16 = _decoder._bf16


def _latent(cfg: dict) -> dict:
    return dict(rank=cfg["kv_lora_rank"], nope_dim=cfg["qk_nope_head_dim"], rope_dim=cfg["qk_rope_head_dim"],
                v_dim=cfg["v_head_dim"])


def build(cfg: dict, job: dict):
    """(main, startup, feed variables by name, loss variable, the variables
    the reference is compared on) of the train program, as a user of the
    framework gets it: `build_causal_lm`, then the learning rate's warm-up and
    Adam from the traffic file.  The compared variables: loss, the sampled
    positions' logits; sparse layer by sparse layer the top-k choice, the
    router's input, the top-k weights, the held experts' output, the router's
    bias and the shared expert's output; then, on the first `STAGE_ROWS` rows,
    the first KDA layer's three convolutions' inputs and outputs, the first and
    the last KDA layer's q, k, v, g, beta and output, and the latent
    attention's q, k, v and output."""
    import paddle_tpu as fluid
    from paddle_tpu import layers
    from paddle_tpu.models import transformer

    linear = cfg["linear_attn_config"]
    main, startup, feeds, fetches = transformer.build_causal_lm(
        vocab_size=cfg["vocab_size"], seq_len=job["seq_len"], d_model=cfg["hidden_size"],
        n_heads=cfg["num_attention_heads"], qk_norm=None, norm_eps=cfg["rms_norm_eps"],
        layer_types=cfg["layer_types"], conv_kernel=linear["short_conv_kernel_size"],
        kda_heads=linear["num_heads"], kda_head_dim=linear["head_dim"], latent=_latent(cfg),
        num_dense_layers=cfg["first_k_dense_replace"], dense_width=cfg["intermediate_size"],
        expert_width=cfg["moe_intermediate_size"], num_experts=cfg["num_routed_experts"],
        experts_held=held(cfg), top_k=cfg["num_experts_per_token"], norm_topk_prob=cfg["moe_renormalize"],
        scoring=cfg["moe_router_activation_func"], routed_scaling_factor=cfg["routed_scaling_factor"],
        norm_topk_eps=cfg["norm_topk_eps"], shared_experts=cfg["num_shared_experts"],
        expert_bias=(cfg["expert_bias_std"] if cfg["use_expert_bias"] else 0.0, cfg["routing_seed"]),
        tie_embedding=cfg["tie_word_embeddings"], load_balance_coef=0.0, router_z_coef=0.0,
        with_optimizer=False, dtype=cfg["compute_dtype"])
    block = main.global_block()
    ops = block.ops

    def of(kind):
        return [op for op in ops if op.type == kind]

    with fluid.program_guard(main, startup):
        by_position = layers.transpose(fetches["logits"], [1, 0, 2])
        sampled = layers.gather(by_position, layers.assign(logit_sample(job["seq_len"]).astype("int32")))

        def rows(name):   # the stage rows of a variable, as an op of the program
            return layers.slice(block.var(name), axes=[0], starts=[0], ends=[STAGE_ROWS]).name

        stages = []
        for router, experts in zip(of("moe_router"), of("moe_experts")):
            routed = experts.outputs["Out"][0]
            joined = next(op for op in ops if op.type == "elementwise_add" and op.inputs["X"][0] == routed)
            stages += [router.outputs["TopKIndex"][0], router.inputs["X"][0], router.outputs["TopKProb"][0],
                       routed, router.inputs["Bias"][0], joined.inputs["Y"][0]]
        for conv in [op for op in of("short_conv") if not op.attr("gated", True)][:3]:
            stages += [rows(conv.inputs["X"][0]), rows(conv.outputs["Out"][0])]
        scans = of("kda")
        for scan in (scans[0], scans[-1]):
            stages += [rows(scan.inputs[s][0]) for s in ("Q", "K", "V", "G", "Beta")] + [rows(scan.outputs["Out"][0])]
        attention = of("fused_attention")[0]
        stages += [rows(attention.inputs[s][0]) for s in ("Q", "K", "V")] + [rows(attention.outputs["Out"][0])]
        rate = layers.learning_rate_scheduler.linear_lr_warmup(
            job["learning_rate"], job["lr_warmup_steps"], job["lr_warmup_start"], job["learning_rate"])
        fluid.optimizer.Adam(learning_rate=rate, beta1=job["adam_beta1"], beta2=job["adam_beta2"],
                             epsilon=job["adam_epsilon"]).minimize(fetches["loss"])
    return (main, startup, feeds, fetches["loss"], [fetches["loss"].name, sampled.name] + stages)


def _layer_flops(cfg: dict, seq: int) -> dict:
    """Multiply-adds x 2 a position of each kind of part, forward."""
    d, linear = cfg["hidden_size"], cfg["linear_attn_config"]
    heads, width = linear["num_heads"], linear["head_dim"]
    wide = heads * width
    qk = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    mla_heads = cfg["num_attention_heads"]
    held_share = cfg["num_experts_per_token"] * cfg["num_experts"] / cfg["num_routed_experts"]
    expert = 3 * 2 * d * cfg["moe_intermediate_size"]
    return {
        "kda": (4 * 2 * d * wide + 2 * (2 * d * width + 2 * width * wide) + 2 * d * heads
                + _chunk_flops(1, heads, width, width)),
        "latent_attention": (2 * d * mla_heads * qk + 2 * d * (cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"])
                             + 2 * cfg["kv_lora_rank"] * mla_heads * (cfg["qk_nope_head_dim"] + cfg["v_head_dim"])
                             + 2 * mla_heads * cfg["v_head_dim"] * d
                             + 2 * mla_heads * (qk + cfg["v_head_dim"]) * (seq + 1) / 2),
        "dense": 3 * 2 * d * cfg["intermediate_size"],
        "sparse": 2 * d * cfg["num_routed_experts"] + (cfg["num_shared_experts"] + held_share) * expert,
    }


def flops_per_sample(cfg: dict, job: dict) -> float:
    """Operations the forward and backward passes of one sequence require
    (matrix multiplications only, 2 per multiply-add, backward twice the
    forward, nothing recomputed): per position a KDA layer's four projections
    (2304 x 4096), its two low-rank pairs, the step's projection and the
    chunked recurrence's products (`kda_scan_flops`' count, ~9% of the layer);
    the latent layer's four projections and its two products over the causal
    pairs, 192 and 128 wide; a dense layer's three products at 9216; a sparse
    layer's router, the shared expert and the position's held experts, a
    QUARTER of one on average (8 chosen x 8 held of 256, a uniform router's
    share); and the head.  Nothing for the convolutions' taps and the gates."""
    seq = job["seq_len"]
    part = _layer_flops(cfg, seq)
    per_position = 2 * cfg["hidden_size"] * cfg["vocab_size"]
    for i, kind in enumerate(cfg["layer_types"]):
        per_position += part[kind] + part["dense" if i < cfg["first_k_dense_replace"] else "sparse"]
    return 3.0 * seq * per_position


def _chunk_flops(tokens: int, heads: int, k_width: int, v_width: int, chunk: int = 64) -> float:
    """Multiply-adds x 2 of the chunked recurrence's forward, a chunk's
    triangles counted as triangles: the keys' and the queries' decayed Grams
    (2 C^2 K together), the triangular solve of [C, K + V] right-hand sides (C^2
    (K + V)), the chunk's state transition and input (2 C K^2 + 2 C K V), the
    queries' and the output's corrections (C^2 K + C^2 V), and the state's two
    products (2 K^2 V + 2 C K V), for every chunk of `chunk` tokens and head."""
    C, K, V = chunk, k_width, v_width
    a_chunk = (2 * C * C * K + C * C * (K + V) + 2 * C * K * K + 2 * C * K * V
               + C * C * K + C * C * V + 2 * K * K * V + 2 * C * K * V)
    return float(a_chunk) * heads * tokens / C


def _kda_layers(cfg: dict) -> int:
    return sum(kind == "kda" for kind in cfg["layer_types"])


def kda_scan_flops(cfg: dict, job: dict) -> float:
    """Operations of a training step's `kda` ops, forward and the hand-written
    backward (twice the forward: every product has two transposes), nothing
    for the chunks' terms and states that backward makes again."""
    linear = cfg["linear_attn_config"]
    tokens = job["batch_per_chip"] * job["seq_len"]
    return 3.0 * _kda_layers(cfg) * _chunk_flops(tokens, linear["num_heads"], linear["head_dim"], linear["head_dim"])


def kda_scan_bytes(cfg: dict, job: dict) -> float:
    """Bytes those ops have to move at the least: q, k, v and the output in
    bf16, the log decay and beta in float32, once forward, and the gradients of
    the five inputs and of the output once backward."""
    linear = cfg["linear_attn_config"]
    width = linear["head_dim"]
    a_head_token = 3 * width * 2 + width * 4 + 4 + width * 2
    return float(2 * a_head_token * linear["num_heads"] * job["batch_per_chip"] * job["seq_len"] * _kda_layers(cfg))


def kda_recurrence(q, k, v, g, beta, bf16_state=False):
    """o [rows, T, H, V] of the recurrence in the module's docstring over q, k
    [rows, T, H, K], v, the log decay g and beta [rows, T, H]: one token at a
    time, a float32 [K, V] state a head.  `bf16_state` rounds the state to
    bf16's eight bits after every token (what the KDA stage's limit has to
    refuse; `reduce_precision`, which XLA may not take out as it may a pair of
    casts)."""
    import jax
    import jax.numpy as jnp

    def step(S, token):
        q_t, k_t, v_t, g_t, beta_t = token                                   # [rows, H, .]
        S = S * jnp.exp(g_t)[..., None]
        S = S + (beta_t[..., None] * k_t)[..., None] * (v_t - jnp.einsum("rhkv,rhk->rhv", S, k_t))[..., None, :]
        if bf16_state:
            S = jax.lax.reduce_precision(S, exponent_bits=8, mantissa_bits=7)
        return S, jnp.einsum("rhkv,rhk->rhv", S, q_t)

    with jax.default_matmul_precision("highest"):
        tokens = tuple(jnp.asarray(t, jnp.float32).swapaxes(0, 1) for t in (q, k, v, g, beta))
        rows, heads, width = tokens[1].shape[1:]
        _, o = jax.lax.scan(step, jnp.zeros((rows, heads, width, tokens[2].shape[-1]), jnp.float32), tokens)
        return o.swapaxes(0, 1)


def reference(params: dict, batch: dict, cfg: dict, program=None):
    """(loss, the sampled positions' logits [rows, sample, vocab], margin
    [rows, L], choice [sparse layers, rows, L, 8], the float32 router, gate,
    up and down weights and the biases stacked by sparse layer, the shared
    experts' three matrices stacked likewise, the first KDA layer's three
    filters [3, d, K], (first held expert, the renormalisation's epsilon, the
    scaling factor), and the latent attention's queries and keys at
    `attention_sample`'s positions [rows, heads, sample, 192]) of `batch` in
    plain float32 jax.numpy, one sequence at a time; `params` maps the
    program's parameter names to arrays (the routers' biases are no parameters:
    `router_biases` has where they come from).  No kernel, no chunk and no
    sort: KDA is `kda_recurrence`'s step over the tokens, the convolution four
    shifted products, latent attention explicit causal scores two heads at a
    time, and every held expert is applied to every position and weighted by
    the renormalised choice."""
    import jax
    import jax.numpy as jnp

    kinds, eps = cfg["layer_types"], cfg["rms_norm_eps"]
    linear = cfg["linear_attn_config"]
    heads, width, taps = linear["num_heads"], linear["head_dim"], linear["short_conv_kernel_size"]
    mla_heads, latent = cfg["num_attention_heads"], _latent(cfg)
    top_k = cfg["num_experts_per_token"]
    first, n_held = held(cfg)
    sparse = [i for i in range(len(kinds)) if i >= cfg["first_k_dense_replace"]]
    biases = router_biases(params, cfg, sparse)

    def p(name):
        return jnp.asarray(params[name], jnp.float32)

    def rms(x, gain=None):
        y = x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps)
        return y if gain is None else y * p(gain)

    def gated_silu(m, gate, up, down):
        return (jax.nn.silu(m @ gate) * (m @ up)) @ down

    def kda(a, pre, seq):
        def mixed(name):
            z, w = a @ p(f"{pre}.{name}.w"), p(f"{pre}.{name}_conv.w")
            c = sum(w[:, j] * jnp.pad(z, ((taps - 1 - j, 0), (0, 0)))[:seq] for j in range(taps))
            return jax.nn.silu(c).reshape(seq, heads, width)

        def unit(t):
            return t * jax.lax.rsqrt(jnp.sum(jnp.square(t), -1, keepdims=True) + 1e-6)

        q, k, v = unit(mixed("q")) * width ** -0.5, unit(mixed("k")), mixed("v")
        step = jax.nn.softplus((a @ p(f"{pre}.f_a.w")) @ p(f"{pre}.f_b.w") + p(f"{pre}.dt_bias"))
        g = -jnp.exp(p(f"{pre}.a_log"))[:, None] * step.reshape(seq, heads, width)
        beta = jax.nn.sigmoid(a @ p(f"{pre}.b.w"))
        o = kda_recurrence(q[None], k[None], v[None], g[None], beta[None])[0]
        gate = jax.nn.sigmoid((a @ p(f"{pre}.g_a.w")) @ p(f"{pre}.g_b.w") + p(f"{pre}.g_b.b"))
        o = rms(o) * p(f"{pre}.o_norm.w") * gate.reshape(seq, heads, width)
        return o.reshape(seq, heads * width) @ p(f"{pre}.out.w")

    def attention(a, pre, seq):
        rank, nope, rope, v_dim = (latent[n] for n in ("rank", "nope_dim", "rope_dim", "v_dim"))
        at = jnp.arange(seq)
        q = (a @ p(f"{pre}.q.w")).reshape(seq, mla_heads, nope + rope).transpose(1, 0, 2)
        down = a @ p(f"{pre}.kv_a.w")
        up = (rms(down[:, :rank], f"{pre}.kv_norm.w") @ p(f"{pre}.kv_b.w")).reshape(seq, mla_heads, nope + v_dim)
        shared = jnp.broadcast_to(down[:, None, rank:], (seq, mla_heads, rope))
        k = jnp.concatenate([up[..., :nope], shared], -1).transpose(1, 0, 2)  # [H, L, 192]: no rotation on either part
        v = up[..., nope:].transpose(1, 0, 2)

        def two_heads(j):
            qs, ks, vs = (jax.lax.dynamic_slice_in_dim(t, 2 * j, 2, 0) for t in (q, k, v))
            scores = jnp.einsum("hqd,hkd->hqk", qs, ks) / np.sqrt(nope + rope)
            scores = jnp.where(at[None, :] <= at[:, None], scores, -jnp.inf)
            return jnp.einsum("hqk,hkd->hqd", jax.nn.softmax(scores, -1), vs)

        ctx = jax.lax.map(two_heads, jnp.arange(mla_heads // 2)).reshape(mla_heads, seq, v_dim)
        sample = attention_sample(seq)
        return ctx.transpose(1, 0, 2).reshape(seq, mla_heads * v_dim) @ p(f"{pre}.out.w"), (q[:, sample], k[:, sample])

    def one_sequence(row):
        ids, labels, _ = row
        seq = ids.shape[0]
        x = p("lm.tok_emb")[ids]
        margin = jnp.full((seq,), jnp.inf)
        choices, first_qk = [], None
        for i, kind in enumerate(kinds):
            pre = f"lm.l{i}"
            a = rms(x, f"{pre}.ln1.w")
            if kind == "kda":
                h = x + kda(a, f"{pre}.kda", seq)
            else:
                out, qk = attention(a, f"{pre}.attn", seq)
                h, first_qk = x + out, first_qk or qk
            m = rms(h, f"{pre}.ln2.w")
            if i < cfg["first_k_dense_replace"]:
                x = h + gated_silu(m, *(p(f"{pre}.ffn.{n}.w") for n in ("gate", "up", "down")))
                continue
            scores = jax.nn.sigmoid(m @ p(f"{pre}.moe.router.w"))
            biased = scores + biases[sparse.index(i)]
            ranked = jnp.sort(biased, -1)[:, ::-1]
            kth, after = ranked[:, top_k - 1], ranked[:, top_k]
            chosen = jnp.where(biased >= kth[:, None], scores, 0.0)      # the UNBIASED scores of the chosen
            gates = (chosen / (jnp.sum(chosen, -1, keepdims=True) + cfg["norm_topk_eps"])
                     * cfg["routed_scaling_factor"])                      # over all eight, held or not

            def expert(acc, ew):
                gate, up, down, g_e = ew
                return acc + gated_silu(m, gate, up, down) * g_e[:, None], None

            routed, _ = jax.lax.scan(
                expert, jnp.zeros_like(h),
                (p(f"{pre}.moe.gate.w"), p(f"{pre}.moe.up.w"), p(f"{pre}.moe.down.w"),
                 gates[:, first:first + n_held].T))
            x = h + routed + gated_silu(m, *(p(f"{pre}.moe.shared.{n}.w") for n in ("gate", "up", "down")))
            margin = jnp.minimum(margin, (kth - after) / jnp.abs(kth))
            choices.append(jnp.sort(jax.lax.top_k(biased, top_k)[1], -1))
        out = rms(x, "lm.final_norm.w") @ p("lm.head.w")
        logp = jax.nn.log_softmax(out, -1)
        ce = -jnp.take_along_axis(logp, labels[:, None], 1)[:, 0]
        return (out[logit_sample(seq)], margin, jnp.stack(choices), jnp.sum(ce)) + first_qk

    with jax.default_matmul_precision("highest"):
        rows = tuple(jnp.asarray(batch[n], jnp.int32) for n in FEEDS)
        out, margin, choice, ce_sum, q_first, k_first = jax.lax.map(one_sequence, rows)
        loss = ce_sum.sum() / rows[1].size
        weights = tuple(jnp.stack([p(f"lm.l{i}.moe.{n}.w") for i in sparse])
                        for n in ("router", "gate", "up", "down", "shared.gate", "shared.up", "shared.down"))
        scan_at = kinds.index("kda")
        filters = jnp.stack([p(f"lm.l{scan_at}.kda.{n}_conv.w") for n in ("q", "k", "v")])
        return ((loss, out, margin, choice.transpose(1, 0, 2, 3)) + weights[:4] + (jnp.stack(biases),) + weights[4:]
                + (filters, jnp.asarray([first, cfg["norm_topk_eps"], cfg["routed_scaling_factor"]], jnp.float32),
                   q_first, k_first))


def _rms(t) -> float:
    return float(np.sqrt(np.mean(np.square(t))))


def _plain_conv(x, w, rounding=lambda t: t):
    """silu(conv_K(x)) of x [rows, T, d] with filter w [d, K] in float32 numpy;
    `rounding` is applied to every intermediate."""
    x = np.asarray(x, "f4")
    taps, acc = w.shape[1], None
    for j in range(taps):
        term = rounding(np.pad(x, ((0, 0), (taps - 1 - j, 0), (0, 0)))[:, :x.shape[1]] * w[:, j])
        acc = term if acc is None else rounding(acc + term)
    return rounding(acc / (1.0 + np.exp(-acc)))


def conv_errors(pairs, filters) -> dict:
    """The program's three plain short convolutions against float32 numpy on
    their own inputs (q', k', v' of the first KDA layer, the stage rows) and
    float32 filters: the worst root-mean-square error over the
    root-mean-square output; and the same convolution with every intermediate
    and the filter rounded to bf16, the least of the three."""
    mine, low = [], []
    for (x, out), w in zip(pairs, filters):
        w = np.asarray(w, "f4")
        want = _plain_conv(x, w)
        scale = max(_rms(want), 1e-30)
        mine.append(_rms(np.asarray(out, "f4") - want) / scale)
        low.append(_rms(_plain_conv(x, _bf16(w), _bf16) - want) / scale)
    return {"conv_error": max(mine), "conv_error_bf16": min(low)}


@functools.lru_cache(maxsize=2)
def _recurrence_jit(bf16_state):
    import jax

    return jax.jit(functools.partial(kda_recurrence, bf16_state=bf16_state))


def kda_errors(layers) -> dict:
    """The program's `kda` output against the float32 recurrence on its own q,
    k, v, g, beta, the worst of `layers` (the first and the last KDA layer's
    six stage tensors).  `kda_error`: root-mean-square difference from the
    recurrence's output ROUNDED to bf16 as the op rounds its own, over the
    root-mean-square output: an op that computes in float32 and rounds once
    differs only where its last float32 bits cross a rounding boundary.
    `kda_error_unrounded`: the same against the float32 output, which the
    output's own rounding (2^-9 / sqrt(3) = 1.1e-3 and more) dominates.  Beside
    them the recurrence with its state rounded to bf16 after every token, read
    the first way: what the limit has to refuse."""
    mine, plain, low, states = [], [], [], []
    for q, k, v, g, beta, out in layers:
        operands = tuple(np.asarray(t, "f4") for t in (q, k, v, g, beta))
        want = np.asarray(_recurrence_jit(False)(*operands))
        scale = max(_rms(want), 1e-30)
        rounded = want if np.asarray(out).dtype == np.float32 else _bf16(want)    # as the op rounded its own
        out = np.asarray(out, "f4")
        mine.append(_rms(out - rounded) / scale)
        plain.append(_rms(out - want) / scale)
        low.append(_rms(_bf16(np.asarray(_recurrence_jit(True)(*operands))) - rounded) / scale)
        states.append(float(np.exp(operands[3]).mean()))
    return {"kda_error": max(mine), "kda_error_unrounded": max(plain), "kda_error_bf16_state": min(low),
            "kda_decay_mean": states}


def shared_errors(m, out, gate, up, down) -> float:
    """One layer's shared expert on the program's own m [tokens, d], every
    `EXPERTS_SAMPLE`-th token, against float32 numpy: root-mean-square error
    over the root-mean-square output."""
    sample = np.arange(0, m.shape[0], max(m.shape[0] // _decoder.EXPERTS_SAMPLE, 1))
    x = m[sample]
    g = x @ gate
    want = (g / (1.0 + np.exp(-g)) * (x @ up)) @ down
    return float(np.sqrt(np.mean(np.square(out[sample] - want)) / max(np.mean(np.square(want)), 1e-30)))


_TAIL = 6 + 12 + 4   # the convolutions', the two KDA layers' and the attention's stage tensors


def compare(got, want) -> dict:
    """The program's fetched variables (`build`) against the reference's
    outputs (`reference`): the two errors `REFERENCE_RTOL` bounds, the routing
    account, and the worst layer's stage errors."""
    loss, want_loss = float(np.asarray(got[0]).reshape(-1)[0]), float(want[0])
    want_logits = np.asarray(want[1], "f4")                                   # [rows, sample, vocab]
    logits = np.asarray(got[1], "f4").transpose(1, 0, 2)
    margin, want_choice = np.asarray(want[2]), np.asarray(want[3])
    rows, seq = margin.shape
    tokens, k = margin.size, want_choice.shape[-1]
    (first, eps, scaling), n_held = (float(n) for n in np.asarray(want[13])), np.asarray(want[5]).shape[1]
    first = int(first)
    layers = [got[i:i + 6] for i in range(2, len(got) - _TAIL, 6)]
    tail = got[len(got) - _TAIL:]
    biases_differ = int(sum((np.asarray(layer[4], "f4") != np.asarray(want[8][i], "f4")).sum()
                            for i, layer in enumerate(layers)))
    choice = np.sort(np.stack([np.asarray(layer[0]).reshape(want_choice.shape[1:]) for layer in layers]), -1)
    routed_differently = (choice != want_choice).any(axis=(0, 3))           # [rows, L]

    def held_choice(c):  # [layers, rows, L, held]: which held experts a position chose
        return (c[..., None] == np.arange(first, first + n_held)).any(-2)

    differs = (held_choice(choice) != held_choice(want_choice)).any(axis=(0, 3))
    sampled = differs[:, logit_sample(seq)]
    err = np.abs(logits - want_logits).max(-1)
    flat = [(np.asarray(c).reshape(tokens, k), np.asarray(m, "f4").reshape(tokens, -1),
             np.asarray(p, "f4").reshape(tokens, k), np.asarray(o, "f4").reshape(tokens, -1), np.asarray(b, "f4"),
             np.asarray(s, "f4").reshape(tokens, -1)) for c, m, p, o, b, s in layers]
    stages = [_decoder.stage_errors(c, m, p, o, b, *(np.asarray(w[i], "f4") for w in want[4:8]), first, eps, scaling)
              for i, (c, m, p, o, b, _) in enumerate(flat)]
    shared = [shared_errors(m, s, *(np.asarray(w[i], "f4") for w in want[9:12]))
              for i, (_, m, _, _, _, s) in enumerate(flat)]
    scale = max(np.abs(want_logits).max(), 1e-9)
    summed = ("bias_moved", "router_choice_differs", "router_ties", "held_choice_flips_bf16_logits")
    q, key, v, out = (np.asarray(t, "f4").transpose(0, 2, 1, 3) for t in tail[18:])      # (rows, L, H, .) as handed
    stage_rows = q.shape[0]
    attention = _decoder.attention_errors(q, key, v, out, np.asarray(want[14])[:stage_rows], np.asarray(want[15])[:stage_rows])
    # the queries and keys of a position that chose other held experts in a layer BEFORE the attention carry that
    # expert's output more or less, as its logits do: left out of the limit's reading as there, and read beside it
    at = attention_sample(seq)
    kept = ~differs[:stage_rows, at]                                                     # [stage rows, sample]
    off = [np.abs(mine[:, :, at] - np.asarray(theirs, "f4")[:stage_rows]).max(axis=(1, 3)) / np.abs(theirs).max()
           for mine, theirs in ((q, want[14]), (key, want[15]))]
    attention.update(qk_error=float(max(e[kept].max(initial=0.0) for e in off)),
                     qk_error_left_out=float(max(e[~kept].max(initial=0.0) for e in off)))
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
        "shared_error": max(shared),
        **conv_errors([tail[0:2], tail[2:4], tail[4:6]], np.asarray(want[12], "f4")),
        **kda_errors([tail[6:12], tail[12:18]]),
        **attention,
    }


def reference_error(got, want) -> float:
    """How far the program is from the reference, as `REFERENCE_RTOL` counts
    it: the larger of the loss's and the sampled logits' error, the logits
    over the positions whose held choice agrees.  Positions that chose other
    held experts are left out AND COUNTED (the `reference_routing` line of the
    run).  A failure (infinite error) is: more than `LEFT_OUT_MAX` of them,
    one that routed differently across a gap wider than `ROUTING_MARGIN`, one
    whose logits are off by more than `LEFT_OUT_LOGIT_MAX`, or a router, held
    experts, a shared expert, a convolution, a KDA scan or a latent attention
    that miss float32 on the program's own tensors by more than `ROUTER_RTOL`,
    `EXPERTS_RTOL`, `SHARED_RTOL`, `CONV_RTOL`, `KDA_RTOL` or `ATTENTION_RTOL`,
    or queries or keys that miss the reference's by more than `QK_RTOL`."""
    import json

    found = compare(got, want)
    print(json.dumps({"info": "reference_routing", **found,
                      "left_out_share": found["left_out"] / found["tokens"],
                      "routing_margin": ROUTING_MARGIN, "left_out_max": LEFT_OUT_MAX,
                      "left_out_logit_max": LEFT_OUT_LOGIT_MAX, "router_rtol": ROUTER_RTOL,
                      "experts_rtol": EXPERTS_RTOL, "shared_rtol": SHARED_RTOL, "conv_rtol": CONV_RTOL,
                      "kda_rtol": KDA_RTOL, "attention_rtol": ATTENTION_RTOL, "qk_rtol": QK_RTOL}),
          flush=True)
    if (found["routed_differently_above_margin"]
            or not found["left_out"] <= LEFT_OUT_MAX * found["tokens"]
            or not found["logit_error_left_out"] <= LEFT_OUT_LOGIT_MAX
            or found["router_choice_differs"] or found["biases_differ"]
            or not found["router_prob_error"] <= ROUTER_RTOL
            or not found["experts_error"] <= EXPERTS_RTOL
            or not found["shared_error"] <= SHARED_RTOL
            or not found["conv_error"] <= CONV_RTOL
            or not found["kda_error"] <= KDA_RTOL
            or not found["attention_error"] <= ATTENTION_RTOL
            or not found["qk_error"] <= QK_RTOL):
        return float("inf")
    return max(found["loss_error"], found["logit_error"])
