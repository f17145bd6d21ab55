"""Kanana-2-30B-A3B causal-LM training: how the benchmark builds it through the
framework, a plain float32 reference of the same architecture, and the
operations one sequence needs.

Architecture: kakaocorp/kanana-2-30b-a3b-instruct-2601 `config.json`
(`model_type: deepseek_v3`); what it does not give follows the DeepSeek-V3
report (arXiv:2412.19437) and the family's public modelling code, and is listed
in the configuration file's `assumed`.  A layer, eps 1e-6, no biases, with
a = rms(x; ln1) and m = rms(h; ln2):

    h = x + mla(a),   y = h + ffn(m);   after the last layer rms(.; final_norm) and the untied head
    mla       32 heads.  q = a Wq [2048 -> 32 x 192], a head [q_n (128) ; q_r (64)];  [c ; k_r] = a Wkva [2048 -> 512 + 64];
              [k_n ; v] = rms(c; kv_norm) Wkvb [512 -> 32 x (128 + 128)];  k_r is not normed
              q_r, k_r <- RoPE(., position):  (t_2i, t_2i+1) <- (t_2i cos w - t_2i+1 sin w, t_2i+1 cos w + t_2i sin w),
              w = position . 1e6^(-2i/64), float32;  k_r ONE 64-wide head a token, shared by the 32
              mla(a) = Wo . concat_h softmax((q_n . k_n + q_r . k_r) / sqrt(192), causal) v_h        [4096 -> 2048]
    dense     ffn(m) = W2( silu(W1 m) * (W3 m) ),  width 6144                     (layer 0)
    sparse    s = sigmoid_f32(m Wr) over 128;  S = top6(s + b);  g_e = 2.448 s_e / (sum_{e' in S} s_e' + 1e-20)
              ffn(m) = sum_{e in S and e in HELD} g_e . W2_e( silu(W1_e m) * (W3_e m) )  +  shared(m),
              experts of width 768,  HELD = {0..7},  shared(m) the two shared experts as ONE gated SiLU of width 1536 that
              EVERY token passes, unweighted
    loss      mean over every position of CE( rms(y_L) W_head, the next token )

The reference computes the attention as explicit causal scores, two heads and
`ATTENTION_BLOCK` queries at a time against the keys up to the block's end (so
that 16384 keys fit: never a [L, L] array a head), the rotation as the pairs
above, the experts as a loop over the eight, and the loss `ATTENTION_BLOCK`
positions at a time.

Departures of the program under test
(`paddle_tpu.models.transformer.build_causal_lm`) from the published model,
which the reference follows so that the two compute the same function (the
configuration file's `departures` is this list, word for word):

  * five of the 48 layers, the published layers 0 to 4: the leading dense layer (`first_k_dense_replace` 1) and the four sparse layers after it, the floor; every one of them latent attention with the rotary shared key; further layers lie on further chips as pipeline stages;
  * 8 of the 128 routed experts of every sparse layer, experts 0 to 7: this chip's share of a layer whose experts are split over 16 chips; the router keeps its 128 outputs, its top 6 and its renormalisation over all six chosen, the two shared experts are computed here as on every chip, and what the 120 absent experts would have added is left out of the layer's output, in the program and in the reference alike, with no exchange standing in for the 15 absent chips;
  * 16032 of the 128256 vocabulary rows, in the embedding and in the untied head: one chip's eighth of the rows, the guide's floor; token ids and labels are drawn from the slice and the loss is over the slice;
  * the expert bias (`topk_method` noaux_tc: e_score_correction_bias) is a buffer that the published training updates by a load-balancing rule `config.json` does not give: here it is drawn once, N(0, 0.02) from the configuration's `routing_seed`, and never updated, so that it changes choices (the run counts how many) and is not a zero added;
  * every layer is a `recompute_scope`: backward keeps a layer's input and what `plan_kept` finds room for and makes the rest of the layer again, the sparse layers' routing with it; the numbers are the same either way (tests/test_kanana.py holds the gradients equal to the last bit);
  * Adam for AdamW (the framework has no AdamW), learning rate 1e-4 reached by a linear warm-up over the first 200 steps from 1e-6, betas 0.9 / 0.95, epsilon 1e-8, no decay and no auxiliary loss;
  * weights are random, N(0, 0.02) from the run's seed, norm gains 1;
  * token ids are uniform random with no padding and no document boundaries (a row is one whole sequence of 16384 positions 0 to 16383, half of `max_position_embeddings`), every position is a label (the next token), so the cross entropy starts near ln(16032).
"""
from __future__ import annotations

import numpy as np

from benchmark.models import lfm2 as _decoder
from benchmark.models.kimi_linear import shared_errors   # one gated SiLU on the program's own m: the same stage
from benchmark.runners import train as _runner

FEEDS = ("ids", "labels", "pos_ids")

#: Every limit below was set from this cell's own readings at the published
#: widths and 16384 tokens (my chip runs, PR 54: fourteen runs of the cell, a seed
#: each, 8 x 16384 positions; PERF.md section 6 has the table): what the sound
#: program reads, and what the same comparison reads with a fault put in
#: (tools/chip_kanana_controls.py, seed 3900000017), the limit between the two
#: with room on both sides.  The routing margin alone is OLMoE's argument
#: (benchmark/models/olmoe.py: top-k is discontinuous and the program's router
#: reads a bf16 input): all 131072 positions have some layer under it here, so
#: it says little; the router's stage on its own input says the rest.
ROUTING_MARGIN = _decoder.ROUTING_MARGIN
#: Sampled positions whose HELD choice differs in some layer are left out of the
#: logit comparison and counted over all positions: 3.06% to 3.49% sound (6
#: chosen of 128, 8 held, four layers); a fault that moves the residual stream
#: reads 34% (bf16 angles) to 72%; a router whose matrix is bf16 reads 3.12% and
#: is `ROUTER_RTOL`'s to tell.  1.7x over the most seen, 5.7x under the least fault.
LEFT_OUT_MAX = 0.06
#: ... and how far a left-out position's logits may be off, over the largest
#: |reference logit|: one held expert's output more or less, 0.127 to 0.183
#: sound.  A sanity bound (a NaN fails it): two of the faults read 0.54 and 0.71,
#: the others 0.23 to 0.26, which the limits below refuse.
LEFT_OUT_LOGIT_MAX = 0.45
#: The larger of the loss's relative error (5e-6 to 1.4e-5) and the sampled
#: logits' error over the largest |reference logit|, on the positions that
#: chose alike: 1.23e-2 to 1.59e-2 sound (bf16 activations over float32 masters
#: through five layers and a bf16 head).  The least any fault to the residual
#: stream reads: scores at 128^-0.5 0.178, bf16 angles 0.201, the shared experts
#: missing 0.52.  2.5x over the most seen, 4.5x under the least fault.
REFERENCE_RTOL = 4e-2
ROUTER_TIE = _decoder.ROUTER_TIE
#: The router on the program's own input m, the stage row: the weights' largest
#: relative error against float64 numpy 1.1e-6 to 1.2e-6, no position routed
#: elsewhere (0 to 2 ties); with its float32 matrix rounded to bf16 IN THE
#: PROGRAM 1.305e-3 and 532 positions routed elsewhere, with its logits rounded
#: to bf16 in numpy 1.29e-3.  90x over the one, 13x under the other.
ROUTER_RTOL = 1e-4
#: The held experts on the program's own m, choice and weights, every
#: `EXPERTS_SAMPLE`-th token of the stage row: root-mean-square error over the
#: root-mean-square output against float32 numpy 4.63e-3 to 4.68e-3 (bf16
#: operands into float32 accumulation); with the running sums held in bf16,
#: eight terms at a time, 3.12e-2.  2.6x over the one, 2.6x under the other.
EXPERTS_RTOL = 1.2e-2
#: The two shared experts' one gated SiLU of 1536 on the same m and tokens:
#: 4.23e-3 to 4.25e-3 (the same three bf16 products); the reference without the
#: first sparse layer's shared experts reads ~1e14 here (the error is over an
#: output of zeros) and 0.52 end to end.  The held experts' limit.
SHARED_RTOL = 1.2e-2
#: The stage row: the program's own tensors of the stages are compared on the
#: first `STAGE_ROWS` of the 8 check rows (the slices are ops of the program, so
#: that 8 rows of every stage's operands never lie in the chip's memory beside
#: the optimizer's state: one row's q alone is 201 MB at 16384 tokens, and the
#: 8-row clone compiled for the described v5e planned 4.65 GB of fetched stage
#: tensors at two rows with every query fetched, 1.18 GB so).
STAGE_ROWS = 1
#: THE ROTARY STAGE: the first layer's rotated q_r [rows, L, 32, 64] and k_r
#: [rows, L, 1, 64] against the float64 rotation of the program's OWN unrotated
#: ones, every position to 16383: largest error over the largest |value|, 2.9e-3
#: to 3.7e-3 sound (the op computes in float32 and rounds once to bf16: half a
#: bf16 step of the largest value, and the float32 angle's own 3e-4).  What it
#: has to refuse, put into the program: the angle in bf16 1.71 (whole turns off
#: past position 256; the same in numpy 1.80), k_r not rotated 1.69, q_r in the
#: halves' order and k_r in pairs 1.40.  8x over the most seen, 47x under the least.
ROTARY_RTOL = 3e-2
#: The latent attention of the first and of the last layer on the program's own
#: q, k (192 wide, rotated) and v (128 wide) for `ATTENTION_SAMPLE` queries of
#: the stage row and every head against ALL the keys before them (16384 at the
#: last), float32 scores: largest error over the largest |output|, the worse
#: layer's, 2.5e-3 to 4.0e-3 sound; scores at 128^-0.5 read 0.159 in the first
#: layer and 0.059 in the last.  5x over the most seen, 8x under the fault's
#: reading (3x under its last layer's).  Like the other cells' it does not tell
#: bf16 scores from float32 (6.8e-4 against itself: PERF.md section 7, defect 13c).
ATTENTION_RTOL = 2e-2
ATTENTION_SAMPLE = _decoder.ATTENTION_SAMPLE
#: ... and those layers' queries and keys themselves at the sampled positions
#: against the reference's (which rotates on its own), over the largest |value|,
#: at the positions whose held choice agrees in every layer: 1.18e-2 to 1.63e-2
#: sound (the last layer's: four layers' bf16 roundings lie before it; the
#: first layer's 4.9e-3 to 5.9e-3); scores at 128^-0.5 in the layers before read
#: 0.180, the shared experts missing 0.52, the three rotary faults 1.3 to 1.9.
#: 2.5x over the most seen, 4.5x under the least fault.
QK_RTOL = 4e-2
#: THE REFERENCE ITSELF: its first layer's queries of the first row at the
#: sampled positions, each head's 128 unrotated features, against float64 numpy
#: of the same product on the reference's own normed input and the float32
#: matrix: largest error over the largest |query|.  Float32 products at the
#: highest precision read 1.7e-7 to 2.0e-7; at the chip's default precision
#: (bf16 operands, the nearest precision below) 2.24e-3, which nothing else here
#: tells apart: the program rounds as much itself (Phi-4-mini-flash's and Jamba's cells, PERF.md, PRs 47
#: and 50, could refuse no such reference).
REFERENCE_SELF_RTOL = 1e-4
#: Queries a block of the reference's attention and positions a block of its loss.
ATTENTION_BLOCK = 2048

logit_sample = _decoder.logit_sample
attention_sample = _decoder.attention_sample
make_batch = _decoder.make_batch
router_biases = _decoder.router_biases
_bf16 = _decoder._bf16


def expert_sample(tokens: int):
    """The tokens of the stage rows whose held and shared experts' outputs are
    compared: every `EXPERTS_SAMPLE`-th, as `lfm2.stage_errors` takes them."""
    return np.arange(0, tokens, max(tokens // _decoder.EXPERTS_SAMPLE, 1))


def held(cfg: dict) -> tuple:
    """(first, count) of the routed experts this chip holds."""
    return (cfg["experts_held_first"], cfg["n_routed_experts"])


def _latent(cfg: dict) -> dict:
    return dict(rank=cfg["kv_lora_rank"], nope_dim=cfg["qk_nope_head_dim"], rope_dim=cfg["qk_rope_head_dim"],
                v_dim=cfg["v_head_dim"], rope=True, rope_interleave=cfg["rope_interleave"])


def _sparse_layers(cfg: dict) -> list:
    return list(range(cfg["first_k_dense_replace"], cfg["num_hidden_layers"]))


def build(cfg: dict, job: dict):
    """(main, startup, feed variables by name, loss variable, the variables the
    reference is compared on) of the train program, as a user of the framework
    gets it: `build_causal_lm` with every layer a recomputed segment (a job may
    say `recompute_layers` false: the tests', which hold the two alike), then
    the learning rate's warm-up and Adam from the traffic file.  The compared
    variables: loss, the sampled positions' logits; sparse layer by sparse layer
    the top-k choice of every row and, on the first `STAGE_ROWS` rows, the
    router's input and the top-k weights, at `expert_sample`'s tokens of them
    the held experts' and the shared experts' output, and the router's bias
    (whole); then, on the stage rows, the
    first layer's unrotated and rotated q_r and k_r, and of the first and the
    last layer's attention the keys and values of every position and the
    queries and outputs of `attention_sample`'s positions."""
    import paddle_tpu as fluid
    from paddle_tpu import layers
    from paddle_tpu.models import transformer

    main, startup, feeds, fetches = transformer.build_causal_lm(
        vocab_size=cfg["vocab_size"], seq_len=job["seq_len"], d_model=cfg["hidden_size"],
        n_heads=cfg["num_attention_heads"], qk_norm=None, norm_eps=cfg["rms_norm_eps"], rope_theta=cfg["rope_theta"],
        layer_types=cfg["layer_types"], latent=_latent(cfg),
        num_dense_layers=cfg["first_k_dense_replace"], dense_width=cfg["intermediate_size"],
        expert_width=cfg["moe_intermediate_size"], num_experts=cfg["num_routed_experts"],
        experts_held=held(cfg), top_k=cfg["num_experts_per_tok"], norm_topk_prob=cfg["norm_topk_prob"],
        scoring=cfg["scoring_func"], routed_scaling_factor=cfg["routed_scaling_factor"],
        norm_topk_eps=cfg["norm_topk_eps"], shared_experts=cfg["n_shared_experts"],
        expert_bias=(cfg["expert_bias_std"] if cfg["use_expert_bias"] else 0.0, cfg["routing_seed"]),
        tie_embedding=cfg["tie_word_embeddings"], load_balance_coef=0.0, router_z_coef=0.0,
        recompute_layers=job.get("recompute_layers", True), with_optimizer=False, dtype=cfg["compute_dtype"])
    block = main.global_block()
    ops = block.ops

    def of(kind):
        return [op for op in ops if op.type == kind]

    with fluid.program_guard(main, startup):
        # The sampled positions' logits [sample, rows, vocab] from the head's own operands: the final norm's output at
        # the sampled (row, position) pairs times the head's matrix, a second product beside the head.  NOT a gather from
        # the head's output: XLA lays 8 rows' logits out for the loss's reductions and copies all 4.2 GB of them
        # position-major for a gather (benchmark/models/phi4flash.py met it at 3.3 GB).  The head's output is the loss's.
        head = next(op for op in ops if fetches["logits"].name in op.output_arg_names)
        assert head.type == "mul" and head.inputs["Y"] == ["lm.head.w"], "the untied head"
        at = logit_sample(job["seq_len"])
        check_rows = np.arange(_runner.CHECK_ROWS)      # the rows the runner's clone is fed: the index is built for them
        pairs = np.stack(np.broadcast_arrays(check_rows[None, :], at[:, None]), -1).astype("int32")
        hidden = layers.gather_nd(block.var(head.inputs["X"][0]), layers.assign(pairs))
        sampled = layers.matmul(hidden, block.var("lm.head.w"))

        def rows(name):   # the stage rows of a variable, as an op of the program
            return layers.slice(block.var(name), axes=[0], starts=[0], ends=[STAGE_ROWS]).name

        sample = attention_sample(job["seq_len"])
        stage_pairs = layers.assign(np.stack(np.broadcast_arrays(
            np.arange(STAGE_ROWS)[:, None], sample[None, :]), -1).astype("int32"))

        def sampled_queries(name):   # (stage rows, sample, H, .) of a (B, L, H, .) variable: the sampled positions' alone
            # of the stage rows' SLICE: a gather from all 8 rows made XLA write the whole 1.6 GB operand out position-major
            # and copy it once more (the clone compiled for the described v5e: 11.9 GB of temporaries so)
            return layers.gather_nd(block.var(rows(name)), stage_pairs).name

        tokens = expert_sample(STAGE_ROWS * job["seq_len"])
        token_pairs = layers.assign(np.stack([tokens // job["seq_len"], tokens % job["seq_len"]], -1).astype("int32"))

        def sampled_tokens(name):    # (sample, d) of a (B, L, d) variable: the tokens the experts' stages read, alone
            return layers.gather_nd(block.var(rows(name)), token_pairs).name

        stages = []
        for router, experts in zip(of("moe_router"), of("moe_experts")):
            routed = experts.outputs["Out"][0]
            joined = next(op for op in ops if op.type == "elementwise_add" and op.inputs["X"][0] == routed)
            stages += [router.outputs["TopKIndex"][0], rows(router.inputs["X"][0]), rows(router.outputs["TopKProb"][0]),
                       sampled_tokens(routed), router.inputs["Bias"][0], sampled_tokens(joined.inputs["Y"][0])]
        for rotation in of("rotary_embedding")[:2]:       # the first layer's: each head's q_r, the one k_r
            stages += [rows(rotation.inputs["X"][0]), rows(rotation.outputs["Out"][0])]
        attentions = of("fused_attention")
        for attention in (attentions[0], attentions[-1]):  # the sampled queries and their outputs, ALL the keys and values
            stages += [sampled_queries(attention.inputs["Q"][0]), rows(attention.inputs["K"][0]),
                       rows(attention.inputs["V"][0]), sampled_queries(attention.outputs["Out"][0])]
        rate = layers.learning_rate_scheduler.linear_lr_warmup(
            job["learning_rate"], job["lr_warmup_steps"], job["lr_warmup_start"], job["learning_rate"])
        fluid.optimizer.Adam(learning_rate=rate, beta1=job["adam_beta1"], beta2=job["adam_beta2"],
                             epsilon=job["adam_epsilon"]).minimize(fetches["loss"])
    return (main, startup, feeds, fetches["loss"], [fetches["loss"].name, sampled.name] + stages)


# -- the arithmetic --------------------------------------------------------------

def _layer_flops(cfg: dict, seq: int) -> dict:
    """Multiply-adds x 2 a position of each kind of part, forward."""
    d, heads = cfg["hidden_size"], cfg["num_attention_heads"]
    qk = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    held_share = cfg["num_experts_per_tok"] * cfg["n_routed_experts"] / cfg["num_routed_experts"]
    expert = 3 * 2 * d * cfg["moe_intermediate_size"]
    return {
        "latent_attention": (2 * d * heads * qk + 2 * d * (cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"])
                             + 2 * cfg["kv_lora_rank"] * heads * (cfg["qk_nope_head_dim"] + cfg["v_head_dim"])
                             + 2 * heads * cfg["v_head_dim"] * d
                             + 2 * heads * (qk + cfg["v_head_dim"]) * (seq + 1) / 2),
        "dense": 3 * 2 * d * cfg["intermediate_size"],
        "sparse": 2 * d * cfg["num_routed_experts"] + (cfg["n_shared_experts"] + held_share) * expert,
    }


def flops_per_sample(cfg: dict, job: dict) -> float:
    """Operations the forward and backward passes of one sequence require
    (matrix multiplications only, 2 per multiply-add, backward twice the
    forward, nothing recomputed): per position a layer's four attention
    projections and its two products over the causal pairs, 192 and 128 wide;
    the dense layer's three products at 6144; a sparse layer's router, the two
    shared experts and the position's held experts, THREE EIGHTHS of one on
    average (6 chosen x 8 held of 128, a uniform router's share); and the head.
    Nothing for the rotations, the norms and the gates."""
    seq = job["seq_len"]
    part = _layer_flops(cfg, seq)
    per_position = 2 * cfg["hidden_size"] * cfg["vocab_size"]
    for i, kind in enumerate(cfg["layer_types"]):
        per_position += part[kind] + part["dense" if i < cfg["first_k_dense_replace"] else "sparse"]
    return 3.0 * seq * per_position


def latent_attention_flops(cfg: dict, job: dict) -> float:
    """Operations of a training step's attention products over the pairs the
    causal mask ALLOWS (L (L + 1) / 2 a head and sequence): q k^T over 192 and
    p v over 128 forward; backward dv and dp over 128, dq and dk over 192; 2 per
    multiply-add, for every sequence, layer and head.  Nothing for a masked
    pair a kernel computes anyway, nothing for the scores a backward kernel
    computes again and nothing for a forward that a `recompute_scope` makes a
    second time."""
    seq = job["seq_len"]
    qk, v = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"], cfg["v_head_dim"]
    per_pair = 2 * (qk + v) + 2 * (2 * v + 2 * qk)
    return float(per_pair * (seq * (seq + 1) // 2) * cfg["num_attention_heads"] * len(cfg["layer_types"])
                 * job["batch_per_chip"])


def latent_attention_bytes(cfg: dict, job: dict, itemsize: int = 2) -> float:
    """Bytes those products have to move at the least: forward reads q, k
    (192 wide) and v and writes the output (128 wide); backward reads those four
    and the output's gradient and writes the three gradients; each once."""
    qk, v = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"], cfg["v_head_dim"]
    forward, backward = 2 * qk + 2 * v, (2 * qk + 3 * v) + (2 * qk + v)
    return float((forward + backward) * itemsize * cfg["num_attention_heads"] * job["seq_len"] * len(cfg["layer_types"])
                 * job["batch_per_chip"])


# -- the reference ---------------------------------------------------------------

def rotate_pairs(t, positions, theta: float, xp=None):
    """The rotary embedding over pairs (2i, 2i + 1) of t [..., L, H, dh] at
    `positions` [L], in `xp` (jax.numpy, or numpy for a float64 check)."""
    if xp is None:
        import jax.numpy as xp
    half = t.shape[-1] // 2
    angle = positions[:, None].astype(t.dtype) * xp.asarray(theta ** (-np.arange(half, dtype=np.float64) / half), t.dtype)
    cos, sin = xp.cos(angle)[:, None, :], xp.sin(angle)[:, None, :]
    even, odd = t[..., 0::2], t[..., 1::2]
    return xp.stack([even * cos - odd * sin, odd * cos + even * sin], -1).reshape(t.shape)


def reference(params: dict, batch: dict, cfg: dict, program=None, precision: str = "highest"):
    """(loss, the sampled positions' logits [rows, sample, vocab], margin [rows,
    L], choice [sparse layers, rows, L, 6], the float32 router, gate, up and
    down weights and the biases stacked by sparse layer, the shared experts'
    three matrices stacked likewise, (first held expert, the renormalisation's
    epsilon, the scaling factor), and the first and the last layer's rotated
    queries and keys at `attention_sample`'s positions, four arrays [rows,
    heads, sample, 192], and for `reference_self_error` the first row's normed
    input of the first layer at those positions [sample, d] and that layer's
    query matrix) of `batch` in plain float32 jax.numpy, one sequence at
    a time; `params` maps the program's parameter names to arrays (the routers'
    biases are no parameters: `router_biases` has where they come from).  No
    kernel, no sort and no [L, L] array: see the module's docstring.
    `precision` is the float32 products': "highest" is the reference;
    tools/chip_kanana_controls.py asks for "default" (bf16 operands on the chip,
    the nearest precision below) to show that the comparison tells it."""
    import jax
    import jax.numpy as jnp

    depth, eps, theta = len(cfg["layer_types"]), cfg["rms_norm_eps"], float(cfg["rope_theta"])
    heads, rank = cfg["num_attention_heads"], cfg["kv_lora_rank"]
    nope, rope, v_dim = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], cfg["v_head_dim"]
    top_k = cfg["num_experts_per_tok"]
    first, n_held = held(cfg)
    sparse = _sparse_layers(cfg)
    biases = router_biases(params, cfg, sparse)

    def p(name):
        return jnp.asarray(params[name], jnp.float32)

    def rms(x, gain):
        return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps) * p(gain)

    def gated_silu(m, gate, up, down):
        return (jax.nn.silu(m @ gate) * (m @ up)) @ down

    def attention(a, pre, positions):
        seq = a.shape[0]
        q = (a @ p(f"{pre}.q.w")).reshape(seq, heads, nope + rope)
        down = a @ p(f"{pre}.kv_a.w")
        up = (rms(down[:, :rank], f"{pre}.kv_norm.w") @ p(f"{pre}.kv_b.w")).reshape(seq, heads, nope + v_dim)
        q = jnp.concatenate([q[..., :nope], rotate_pairs(q[..., nope:], positions, theta)], -1)
        shared = rotate_pairs(down[:, None, rank:], positions, theta)            # ONE head a token, rotated once
        k = jnp.concatenate([up[..., :nope], jnp.broadcast_to(shared, (seq, heads, rope))], -1)
        q, k, v = q.transpose(1, 0, 2), k.transpose(1, 0, 2), up[..., nope:].transpose(1, 0, 2)     # [H, L, .]
        block = min(seq, ATTENTION_BLOCK)
        blocks = []
        for start in range(0, seq, block):        # the queries of a block against the keys up to the block's end
            end = min(start + block, seq)
            allowed = jnp.arange(end)[None, :] <= jnp.arange(start, end)[:, None]

            def two_heads(j, start=start, end=end, allowed=allowed):
                qs = jax.lax.dynamic_slice_in_dim(q[:, start:end], 2 * j, 2, 0)
                ks, vs = (jax.lax.dynamic_slice_in_dim(t[:, :end], 2 * j, 2, 0) for t in (k, v))
                scores = jnp.einsum("hqd,hkd->hqk", qs, ks) / np.sqrt(nope + rope)
                return jnp.einsum("hqk,hkd->hqd", jax.nn.softmax(jnp.where(allowed, scores, -jnp.inf), -1), vs)

            blocks.append(jax.lax.map(two_heads, jnp.arange(heads // 2)).reshape(heads, end - start, v_dim))
        ctx = jnp.concatenate(blocks, 1).transpose(1, 0, 2).reshape(seq, heads * v_dim)
        sample = attention_sample(seq)
        return ctx @ p(f"{pre}.out.w"), (q[:, sample], k[:, sample])

    def one_sequence(row):
        ids, labels, positions = row
        seq = ids.shape[0]
        x = p("lm.tok_emb")[ids]
        margin = jnp.full((seq,), jnp.inf)
        choices, sampled_qk = [], {}
        first_input = rms(x, "lm.l0.ln1.w")[attention_sample(seq)]      # what the first q projection reads, sampled
        for i in range(depth):
            pre = f"lm.l{i}"
            out, qk = attention(rms(x, f"{pre}.ln1.w"), f"{pre}.attn", positions)
            h = x + out
            if i in (0, depth - 1):
                sampled_qk[i] = qk
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
                     * cfg["routed_scaling_factor"])                      # over all six, held or not

            def expert(acc, ew, m=m):
                gate, up, down, g_e = ew
                return acc + gated_silu(m, gate, up, down) * g_e[:, None], None

            routed, _ = jax.lax.scan(
                expert, jnp.zeros_like(h),
                (p(f"{pre}.moe.gate.w"), p(f"{pre}.moe.up.w"), p(f"{pre}.moe.down.w"),
                 gates[:, first:first + n_held].T))
            x = h + routed + gated_silu(m, *(p(f"{pre}.moe.shared.{n}.w") for n in ("gate", "up", "down")))
            margin = jnp.minimum(margin, (kth - after) / jnp.abs(kth))
            choices.append(jnp.sort(jax.lax.top_k(biased, top_k)[1], -1))
        normed = rms(x, "lm.final_norm.w")
        block = min(seq, ATTENTION_BLOCK)

        def ce_of(lo):   # the cross entropies of a block of positions, summed: never [L, vocab] at once
            logp = jax.nn.log_softmax(jax.lax.dynamic_slice_in_dim(normed, lo, block, 0) @ p("lm.head.w"), -1)
            return -jnp.sum(jnp.take_along_axis(logp, jax.lax.dynamic_slice_in_dim(labels, lo, block, 0)[:, None], 1))

        ce_sum = jnp.sum(jax.lax.map(ce_of, jnp.arange(0, seq, block)))
        out = normed[logit_sample(seq)] @ p("lm.head.w")
        return (out, margin, jnp.stack(choices), ce_sum) + sampled_qk[0] + sampled_qk[depth - 1] + (first_input,)

    with jax.default_matmul_precision(precision):
        rows = tuple(jnp.asarray(batch[n], jnp.int32) for n in FEEDS)
        out, margin, choice, ce_sum, *sampled_qk, first_input = jax.lax.map(one_sequence, rows)
        loss = ce_sum.sum() / rows[1].size
        weights = tuple(jnp.stack([p(f"lm.l{i}.moe.{n}.w") for i in sparse])
                        for n in ("router", "gate", "up", "down", "shared.gate", "shared.up", "shared.down"))
        return ((loss, out, margin, choice.transpose(1, 0, 2, 3)) + weights[:4] + (jnp.stack(biases),) + weights[4:]
                + (jnp.asarray([first, cfg["norm_topk_eps"], cfg["routed_scaling_factor"], theta], jnp.float32),)
                + tuple(sampled_qk) + (first_input[0], p("lm.l0.attn.q.w")))


# -- the comparison ----------------------------------------------------------------

def rotary_errors(pairs, theta: float) -> dict:
    """The program's rotations against float64 numpy on their own inputs:
    `pairs` = [(unrotated, rotated)] of (rows, L, H, dh) each, positions 0 to
    L - 1: the largest error over the largest |value|, the worst of the pairs;
    and what the same float64 rotation reads with its ANGLES rounded to bf16
    (the nearest precision below the float32 the op computes them in), the
    least of the pairs: what the limit has to refuse."""
    mine, low = [], []
    for x, out in pairs:
        x = np.asarray(x, "f4").astype("f8")
        positions = np.arange(x.shape[1], dtype="f8")
        want = np.stack([rotate_pairs(row, positions, theta, np) for row in x])
        half = x.shape[-1] // 2
        angle = _bf16((positions[:, None] * theta ** (-np.arange(half, dtype="f8") / half)).astype("f4")).astype("f8")
        cos, sin = np.cos(angle)[None, :, None, :], np.sin(angle)[None, :, None, :]
        rounded = np.stack([x[..., 0::2] * cos - x[..., 1::2] * sin, x[..., 1::2] * cos + x[..., 0::2] * sin], -1)
        scale = max(np.abs(want).max(), 1e-30)
        mine.append(float(np.abs(np.asarray(out, "f4") - want).max() / scale))
        low.append(float(np.abs(rounded.reshape(x.shape) - want).max() / scale))
    return {"rotary_error": max(mine), "rotary_error_bf16_angles": min(low)}


def attention_errors(q, k, v, out, sample) -> dict:
    """The program's attention output at the sampled queries, `out` [rows,
    sample, H, dv], against float32 numpy on its own `q` [rows, sample, H, dk]
    and ALL its keys and values `k` [rows, L, H, dk], `v` [rows, L, H, dv], each
    query against the keys up to its own position: largest |error| over the
    largest |output|; and the same reference with its scores rounded to bf16
    against itself."""
    allowed = np.arange(k.shape[1])[None, :] <= np.asarray(sample)[:, None]
    worst = rounded = largest = 0.0
    for r in range(q.shape[0]):
        for h in range(q.shape[2]):
            scores = q[r, :, h] @ k[r, :, h].T / np.sqrt(q.shape[-1])

            def attend(s, values=v[r, :, h]):
                s = np.where(allowed, s, -np.inf)
                e = np.exp(s - s.max(-1, keepdims=True))
                return (e / e.sum(-1, keepdims=True)) @ values

            want = attend(scores)
            worst = max(worst, float(np.abs(out[r, :, h] - want).max()))
            rounded = max(rounded, float(np.abs(attend(_bf16(scores)) - want).max()))
            largest = max(largest, float(np.abs(want).max()))
    return {"attention_error": worst / max(largest, 1e-30), "attention_error_bf16_scores": rounded / max(largest, 1e-30)}


_TAIL = 4 + 8   # the two rotations' and the two attentions' stage tensors


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
    first, eps, scaling, theta = (float(n) for n in np.asarray(want[12]))
    first, n_held = int(first), np.asarray(want[5]).shape[1]
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
    # the stages on the program's own tensors, the stage rows: the choice is fetched for every row, the rest for those
    stage_rows = np.asarray(layers[0][1]).shape[0]
    staged = stage_rows * seq
    sample = expert_sample(staged)

    def spread(t):   # the sampled tokens' rows at their places among the staged tokens: the stages read those alone
        full = np.zeros((staged, t.shape[-1]), "f4")
        full[sample] = np.asarray(t, "f4")
        return full

    flat = [(np.asarray(c).reshape(tokens, k)[:staged], np.asarray(m, "f4").reshape(staged, -1),
             np.asarray(p, "f4").reshape(staged, k), spread(o), np.asarray(b, "f4"), spread(s))
            for c, m, p, o, b, s in layers]
    stages = [_decoder.stage_errors(c, m, p, o, b, *(np.asarray(w[i], "f4") for w in want[4:8]), first, eps, scaling)
              for i, (c, m, p, o, b, _) in enumerate(flat)]
    shared = [shared_errors(m, s, *(np.asarray(w[i], "f4") for w in want[9:12]))
              for i, (_, m, _, _, _, s) in enumerate(flat)]
    scale = max(np.abs(want_logits).max(), 1e-9)
    summed = ("bias_moved", "router_choice_differs", "router_ties", "held_choice_flips_bf16_logits")
    at = attention_sample(seq)
    kept = ~differs[:stage_rows, at]                                                     # [stage rows, sample]
    attention, qk, qk_left_out = [], [], []
    for where, layer in enumerate((tail[4:8], tail[8:12])):
        q, key, v, out = (np.asarray(t, "f4") for t in layer)      # q, out (rows, sample, H, .); key, v (rows, L, H, .)
        attention.append(attention_errors(q, key, v, out, at))
        # the queries and keys of a position that chose other held experts in a layer BEFORE the attention carry that
        # expert's output more or less, as its logits do: left out of the limit's reading as there, and read beside it
        want_q, want_k = (np.asarray(want[13 + 2 * where + j], "f4")[:stage_rows].transpose(0, 2, 1, 3) for j in (0, 1))
        off = [np.abs(mine - theirs).max(axis=(2, 3)) / np.abs(theirs).max()
               for mine, theirs in ((q, want_q), (key[:, at], want_k))]
        mask = kept if where else np.ones_like(kept)        # before the first layer's attention no expert stands
        qk.append(float(max(e[mask].max(initial=0.0) for e in off)))
        qk_left_out.append(float(max(e[~mask].max(initial=0.0) for e in off)))
    # the yardstick's own precision: the reference's first queries of the first row against float64 on its own input
    # (each head's UNROTATED part: a float32 angle at position 16383 is itself 3e-4 of a query from float64's)
    rope = np.asarray(tail[0]).shape[-1]
    first_q = np.asarray(want[13], "f8")[0].transpose(1, 0, 2)[..., :-rope]            # [sample, H, 128]
    exact = (np.asarray(want[17], "f8") @ np.asarray(want[18], "f8")).reshape(first_q.shape[:2] + (-1,))[..., :-rope]
    return {
        "reference_self_error": float(np.abs(first_q - exact).max() / np.abs(exact).max()),
        "loss_error": abs(loss - want_loss) / max(abs(want_loss), 1e-6),
        "logit_error": float(err[~sampled].max(initial=0.0) / scale),
        "logit_error_left_out": float(err[sampled].max(initial=0.0) / scale),
        "tokens": int(tokens),
        "left_out": int(differs.sum()),
        "routed_differently": int(routed_differently.sum()),
        "under_margin": int((margin < ROUTING_MARGIN).sum()),
        "routed_differently_above_margin": int((routed_differently & (margin >= ROUTING_MARGIN)).sum()),
        **{name: (sum if name in summed else max)(stage[name] for stage in stages) for name in stages[0]},
        "bias_moved_share_max": max(stage["bias_moved"] for stage in stages) / (staged * k),
        "biases_differ": biases_differ,
        "held_rows_share": [float(held_choice(c[None]).sum() / (tokens * k)) for c in choice],
        "shared_error": max(shared),
        **rotary_errors([tail[0:2], tail[2:4]], theta),
        "attention_error": max(a["attention_error"] for a in attention),
        "attention_error_bf16_scores": min(a["attention_error_bf16_scores"] for a in attention),
        "attention_errors": [a["attention_error"] for a in attention],
        "qk_error": max(qk), "qk_errors": qk, "qk_error_left_out": max(qk_left_out),
    }


def reference_error(got, want) -> float:
    """How far the program is from the reference, as `REFERENCE_RTOL` counts
    it: the larger of the loss's and the sampled logits' error, the logits
    over the positions whose held choice agrees.  Positions that chose other
    held experts are left out AND COUNTED (the `reference_routing` line of the
    run).  A failure (infinite error) is: more than `LEFT_OUT_MAX` of them,
    one that routed differently across a gap wider than `ROUTING_MARGIN`, one
    whose logits are off by more than `LEFT_OUT_LOGIT_MAX`, or a router, held
    experts, shared experts, a rotation or a latent attention that miss
    float32 (float64 for the rotation) on the program's own tensors by more
    than `ROUTER_RTOL`, `EXPERTS_RTOL`, `SHARED_RTOL`, `ROTARY_RTOL` or
    `ATTENTION_RTOL`, queries or keys that miss the reference's by more than
    `QK_RTOL`, or a reference whose own first product misses float64 by more
    than `REFERENCE_SELF_RTOL` (`failed_limits` names them)."""
    import json

    found = compare(got, want)
    print(json.dumps({"info": "reference_routing", **found,
                      "left_out_share": found["left_out"] / found["tokens"],
                      "routing_margin": ROUTING_MARGIN, "left_out_max": LEFT_OUT_MAX,
                      "left_out_logit_max": LEFT_OUT_LOGIT_MAX, "router_rtol": ROUTER_RTOL,
                      "experts_rtol": EXPERTS_RTOL, "shared_rtol": SHARED_RTOL, "rotary_rtol": ROTARY_RTOL,
                      "attention_rtol": ATTENTION_RTOL, "qk_rtol": QK_RTOL, "reference_self_rtol": REFERENCE_SELF_RTOL}),
          flush=True)
    return float("inf") if failed_limits(found) else max(found["loss_error"], found["logit_error"])


def failed_limits(found: dict) -> list:
    """The names of the limits that `found` (`compare`'s account) passes,
    `REFERENCE_RTOL` among them: empty for a sound program."""
    checks = {
        "ROUTING_MARGIN": not found["routed_differently_above_margin"],
        "LEFT_OUT_MAX": found["left_out"] <= LEFT_OUT_MAX * found["tokens"],
        "LEFT_OUT_LOGIT_MAX": found["logit_error_left_out"] <= LEFT_OUT_LOGIT_MAX,
        "ROUTER_TIE": not found["router_choice_differs"],
        "router_bias": not found["biases_differ"],
        "ROUTER_RTOL": found["router_prob_error"] <= ROUTER_RTOL,
        "EXPERTS_RTOL": found["experts_error"] <= EXPERTS_RTOL,
        "SHARED_RTOL": found["shared_error"] <= SHARED_RTOL,
        "ROTARY_RTOL": found["rotary_error"] <= ROTARY_RTOL,
        "ATTENTION_RTOL": found["attention_error"] <= ATTENTION_RTOL,
        "QK_RTOL": found["qk_error"] <= QK_RTOL,
        "REFERENCE_RTOL": max(found["loss_error"], found["logit_error"]) <= REFERENCE_RTOL,
        "REFERENCE_SELF_RTOL": found["reference_self_error"] <= REFERENCE_SELF_RTOL,
    }
    return [name for name, passed in checks.items() if not passed]
