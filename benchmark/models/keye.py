"""Keye-VL-2.0-30B-A3B's language model, causal-LM training: how the benchmark
builds it through the framework, a plain float32 reference of the same
architecture, and the operations one sequence needs.

Architecture: Kwai-Keye/Keye-VL-2.0-30B-A3B `config.json` (`model_type: KeyeVL2`;
the language model's keys: Qwen3-MoE's block with `sa_config`, a DeepSeek Sparse
Attention indexer in every layer).  What the config does not give follows
DeepSeek-V3.2-Exp's report, section 2, and is listed in the configuration file's
`assumed`.  A layer, eps 1e-6, no biases, with u = rms(x; ln1), m = rms(h; ln2):

    h = x + Attn(u),   y = h + MoE(m);   after the last layer rms(.; final_norm) and the untied head
    main      q = u Wq [2048 -> 32 x 128], k = u Wk, v = u Wv [2048 -> 4 x 128]; q and k RMS-normed a head (one gain of 128),
              then the rotary embedding, halves rotated, theta 1e7, in the sections `mrope_section` [16, 24, 24] of the 64
              angles over THREE position streams (text: all three the token index)
    indexer   reads stop_gradient(u):  qI = u WqI [2048 -> 16 x 64],  kI = LayerNorm(u WkI) [2048 -> 64, gain and bias],
              w = u Ww [2048 -> 16] float32; qI and kI rotated over their whole 64 (32 angles, theta 1e7, the token index)
              I[t, s] = sum_j w[t, j] 16^-0.5 64^-0.5 relu(qI[t, j] . kI[s])     float32, s <= t
              S_t = the min(2048, t + 1) keys of the largest I[t, .], the lower index first among equals
    selected  o[t, h] = sum_{s in S_t} softmax_{s in S_t}(q[t, h] . k[s, h div 8] 128^-0.5) v[s, h div 8];  Attn = concat_h(o) Wo
    MoE       p = softmax_f32(m Wr) over 128; S = top8(p); g_e = p_e / sum_{e' in S} p_e';
              MoE(m) = sum_{e in S and e in HELD} g_e W2_e( silu(W1_e m) * (W3_e m) ),  experts of 768,  HELD = {0..15}
    loss      L = L_LM + L_I:  L_LM the mean over every position of CE(logits, the next token);
              L_I = mean over layers and queries of KL(p_t || softmax_{S_t}(I[t, .])),
              p_t[s] = (1 / 32) sum_h P[t, h, s] over S_t, a constant (stop_gradient)

The reference computes a block of `ATTENTION_BLOCK` queries at a time against the
keys up to the block's end (so that 16384 keys fit: no [L, L] array of a layer):
the index scores a head at a time, the choice from a sort of its own (the k-th
largest, then the equals by a running count: not the program's `lax.top_k`
rule), the selected attention two heads at a time under the dense mask of the
block, the head-summed probabilities with it, and the loss `ATTENTION_BLOCK`
positions at a time.

Departures of the program under test
(`paddle_tpu.models.transformer.build_causal_lm`) from the published model,
which the reference follows so that the two compute the same function (the
configuration file's `departures` is this list, word for word):

  * the language model alone: the vision tower and its merger are left out (the source's language-model keys hold no size of theirs, and the repository has no image tower), so every position is a text token;
  * text positions: the three position streams of the sectioned rotary embedding (`mrope_section` [16, 24, 24]) are all the token index, for which the sectioned rotation IS the plain one; the program applies `layers.rotary_embedding` as it is, the reference writes the three sections out, and tests/test_keye.py holds the two equal for equal streams;
  * four of the 48 layers (all alike, every one sparse attention over routed experts: one layer is one period of the pattern, four the floor); further layers lie on further chips as pipeline stages;
  * 16 of the 128 experts of every layer, experts 0 to 15: this chip's share of a layer whose experts are split over eight chips; the router keeps its 128 outputs, its top 8 and its renormalisation over all eight chosen, and what the 112 absent experts would have added is left out of the layer's output, in the program and in the reference alike, with no exchange standing in for the seven absent chips;
  * 18992 of the 151936 vocabulary rows, in the embedding and in the untied head: one chip's eighth of the rows, the guide's floor; token ids and labels are drawn from the slice and the loss is over the slice;
  * the training recipe is assumed, for `config.json` gives none: the report's sparse training stage, L = L_LM + L_I with the indexer's input and the alignment target detached; no indexer cache and no decoding (the benchmark has no serve cell);
  * every layer is a `recompute_scope`: backward keeps a layer's input, the layer's CHOICE of keys (which it must: the forward made again reads it and never chooses again) and what `plan_kept` finds room for, and makes the rest of the layer again; the numbers are the same either way (tests/test_keye.py holds the gradients equal to the last bit);
  * Adam for AdamW (the framework has no AdamW), learning rate 1e-4 reached by a linear warm-up over the first 200 steps from 1e-6, betas 0.9 / 0.95, epsilon 1e-8, no decay and no auxiliary loss of the routers;
  * weights are random, N(0, 0.02) from the run's seed, norm gains 1 and the key norm's bias 0, but for the token embedding, N(0, 1), and the embedding and the routers' matrices together, which come from the configuration's `routing_seed` and not from the run's (SDAR's cell has the argument: they decide which experts a token meets, as a checkpoint's do);
  * token ids are uniform random with no padding and no document boundaries (a row is one whole sequence of 16384 positions 0 to 16383), every position is a label (the next token), so the cross entropy starts near ln(18992).
"""
from __future__ import annotations

import numpy as np

from benchmark.models import lfm2 as _decoder
from benchmark.models import sdar as _block      # the same block but for the indexer: its router's and experts' stage
from benchmark.runners import train as _runner

FEEDS = ("ids", "labels", "pos_ids")

#: Every limit below was set from this cell's own readings at the published
#: widths and 16384 tokens (my chip runs, PR 56: eleven runs of the cell and the
#: controls' sound runs, a seed each, 8 x 16384 positions; PERF.md section 6 has the
#: table): what the sound program reads, and what the same comparison reads
#: with a fault put in (tools/chip_keye_controls.py, seed 3560000501), the limit
#: between the two with room on both sides.  The routing margin alone is
#: OLMoE's argument (benchmark/models/olmoe.py: top-k is discontinuous and the
#: program's router reads a bf16 input): all but 1% of the positions have some
#: layer under it, so it says little; the router's stage on its own input says
#: the rest.  A fault that moves the residual stream routes 445 to 1000
#: positions otherwise ABOVE it; the sound program none.
ROUTING_MARGIN = _block.ROUTING_MARGIN
ROUTER_TIE = _block.ROUTER_TIE
#: Sampled positions whose HELD choice differs in some layer are left out of the
#: logit comparison and counted over all positions: 5.35% to 5.55% sound (8
#: chosen of 128, 16 held, four layers; 5.82% against the reference at the
#: chip's default precision); a choice from other index scores reads 26.5% (no
#: ReLU) to 40.4% (no weights), half the picks 35.9%.  1.8x over the most seen,
#: 2.6x under the least fault.
LEFT_OUT_MAX = 0.10
#: ... and how far a left-out position's logits may be off, over the largest
#: |reference logit|: one held expert's output more or less, 0.035 to 0.037
#: sound.  A sanity bound (a NaN fails it): the faults read 0.090 to 0.126 and
#: the limits below refuse them.
LEFT_OUT_LOGIT_MAX = 0.2
#: The larger of the language-model term's relative error (1.3e-6 to 2.2e-6) and
#: the sampled logits' error over the largest |reference logit|, on the
#: positions whose held choice agrees: 1.09e-2 to 1.38e-2 sound (bf16
#: activations over float32 masters through four layers, and the few picks at the
#: threshold that the reference's float32 scores place otherwise); the least a
#: fault to the choice reads: no ReLU 7.25e-2, half the picks 8.13e-2, no
#: weights 0.131.  2.2x over the most seen, 2.4x under the least fault.
REFERENCE_RTOL = 3e-2
#: The alignment term end to end, relative (the reference chooses on its own
#: float32 scores of float32 operands, the program on its bf16 operands'): 8e-6
#: to 3e-5 sound; half the picks 0.165, no ReLU 0.245, no weights 1.49.  330x
#: over the most seen, 16x under the least fault.
INDEX_KL_RTOL = 1e-2
#: The router on the program's own input m, the stage row: the weights' largest
#: relative error against float64 numpy 5.0e-6 to 5.5e-6, no position routed
#: elsewhere; with its logits rounded to bf16 in numpy 1.37e-2 to 1.49e-2.
ROUTER_RTOL = 1e-4
#: The held experts on the program's own m, choice and weights, every
#: `EXPERTS_SAMPLE`-th token of the stage row: 4.75e-3 to 4.78e-3 (bf16 operands
#: into float32 accumulation); with the running sums held in bf16, eight terms
#: at a time, 3.11e-2 to 3.13e-2.  2.5x over the one, 2.6x under the other.
EXPERTS_RTOL = 1.2e-2
#: The stage rows (see benchmark/models/kanana.py: 8 rows of every stage's
#: operands do not lie beside the optimizer's state).
STAGE_ROWS = 1
#: THE CHOICE, first and last layer, `ATTENTION_SAMPLE` queries of the stage
#: row: the program's picks against float64 numpy's top 2048 of the scores of
#: the program's OWN qI, kI and w: the share of (query, pick) pairs that differ,
#: 0.0 in every sound run (on its own bf16 operands float32 chooses as float64
#: does); each index head's products rounded to bf16 IN THE PROGRAM 8.7e-4 (the
#: same in numpy with bf16 running sums 1.8e-3), no ReLU 0.241, no weights 0.608,
#: half the picks 0.242.  74 pairs of the 369 thousand compared, 4.3x under the
#: least fault.
PICKS_DIFFER_MAX = 2e-4
#: ... and for every differing pair how far its float64 score lies from the
#: row's 2048th, over the row's largest |score|: a pair that float32 may place
#: on either side of the threshold reads ~1e-7 (none was met: 0.0); a choice from
#: bf16 products 1.53e-3, from other scores 0.64 to 1.32.  150x under the least
#: fault.  Beside both the COUNT: every sampled query holds min(2048, t + 1)
#: keys and none after itself (`picks_count`): half the picks miscounts 177 of
#: the 192 sampled queries, a key after the query 192.
PICKS_GAP_MAX = 1e-5
#: THE SELECTED ATTENTION on the program's own q, k, v AND its own picks, the
#: sampled queries of the stage row and every head over all the keys: largest
#: error over the largest |output|, the worse layer's, 2.8e-3 to 4.1e-3 sound
#: (the kernel rounds the queries' scaling, the probabilities and the output to
#: bf16); dense causal attention over the same operands 5.8e-2 to 7.5e-2
#: (`attention_error_dense`, numpy on the same tensors in every run; the 8-row
#: clone with `dense_attention` put into the program does not load beside the
#: state: PERF.md section 6), a key after the query 0.79.  3.7x over the most seen, 3.8x under
#: the least fault.
ATTENTION_RTOL = 1.5e-2
ATTENTION_SAMPLE = _decoder.ATTENTION_SAMPLE
#: ... those layers' queries and keys at the sampled positions against the
#: reference's (which writes the three sections out), over the largest |value|,
#: at the positions whose held choice agrees: 1.11e-2 to 1.21e-2 sound (the last
#: layer's: three layers' bf16 roundings lie before it; the first layer's 6.3e-3
#: to 6.6e-3); a choice from other scores in the layers before reads 7.06e-2 (no
#: ReLU), 7.72e-2 (half the picks), 0.129 (no weights).  2.5x over the most seen,
#: 2.35x under the least fault.
QK_RTOL = 3e-2
#: THE ALIGNMENT TERM of the stage row on the program's own operands (qI, kI, w,
#: q, k and picks), against the float32 reference's function on them at the
#: highest precision: relative error, the worse layer's, 0 to 2.1e-7 sound (two
#: float32 sums over 31 million pairs in another order; 24 readings); with the
#: term's own index scores rounded to bf16 a head IN THE PROGRAM 7.4e-6 (a
#: target of one head for the mean of 32 was not run on the chip; tiny on the CPU
#: it reads 0.3 and more).  7x over the most seen, 5x under the least fault.
ALIGNMENT_RTOL = 1.5e-6
#: THE REFERENCE ITSELF: its first layer's queries of the first row at the
#: sampled positions, before the norm, against float64 numpy of the same product
#: on the reference's own normed input and the float32 matrix: float32 products
#: at the highest precision read 1.6e-7 to 2.0e-7; at the chip's default
#: precision (bf16 operands, the nearest precision below) 2.17e-3, which nothing
#: else here tells apart (logits 1.36e-2, left out 5.82%): the program rounds as
#: much itself.  500x over the one, 22x under the other.
REFERENCE_SELF_RTOL = 1e-4
#: Queries a block of the reference's attention and positions a block of its loss.
ATTENTION_BLOCK = 2048

logit_sample = _decoder.logit_sample
attention_sample = _decoder.attention_sample
make_batch = _decoder.make_batch
_bf16 = _decoder._bf16


def expert_sample(tokens: int):
    return np.arange(0, tokens, max(tokens // _block.EXPERTS_SAMPLE, 1))


def held(cfg: dict) -> tuple:
    """(first, count) of the routed experts this chip holds."""
    return (cfg["experts_held_first"], cfg["num_experts"])


def indexer(cfg: dict) -> dict:
    sa = cfg["sa_config"]
    return dict(heads=sa["indexer_num_heads"], head_dim=sa["indexer_head_dim"], topk=sa["topk"])


def build(cfg: dict, job: dict):
    """(main, startup, feed variables by name, loss variable, the variables the
    reference is compared on) of the train program, as a user of the framework
    gets it: `build_causal_lm` with every layer a sparse-attention layer in a
    recomputed segment (a job may say `recompute_layers` false: the tests',
    which hold the two alike), then the learning rate's warm-up and Adam from
    the traffic file.  The compared variables: the loss, its two terms, the
    sampled positions' logits; layer by layer the top-k choice of every row
    and, on the first `STAGE_ROWS` rows, the router's input, the top-k weights
    and at `expert_sample`'s tokens the held experts' output; then of the first
    and of the last layer, on the stage rows, the indexer's qI, kI and w, the
    picks, the main attention's q, k, v and output, and the alignment term a
    row."""
    import paddle_tpu as fluid
    from paddle_tpu import layers
    from paddle_tpu.models import transformer

    main, startup, feeds, fetches = transformer.build_causal_lm(
        vocab_size=cfg["vocab_size"], seq_len=job["seq_len"], d_model=cfg["hidden_size"],
        n_heads=cfg["num_attention_heads"], n_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        qk_norm="head", norm_eps=cfg["rms_norm_eps"], rope_theta=cfg["rope_theta"],
        layer_types=["sparse_attention"] * cfg["num_hidden_layers"], sparse_index=indexer(cfg),
        expert_width=cfg["moe_intermediate_size"], num_experts=cfg["num_routed_experts"], experts_held=held(cfg),
        top_k=cfg["num_experts_per_tok"], norm_topk_prob=cfg["norm_topk_prob"],
        tie_embedding=cfg["tie_word_embeddings"], load_balance_coef=0.0, router_z_coef=0.0,
        embedding_std=cfg["embedding_std"], routing_seed=cfg["routing_seed"],
        recompute_layers=job.get("recompute_layers", True), with_optimizer=False, dtype=cfg["compute_dtype"])
    block = main.global_block()
    ops = block.ops

    def of(kind):
        return [op for op in ops if op.type == kind]

    with fluid.program_guard(main, startup):
        # the sampled positions' logits from the head's own operands (benchmark/models/kanana.py has why it is no gather
        # from the head's output)
        head = next(op for op in ops if fetches["logits"].name in op.output_arg_names)
        assert head.type == "mul" and head.inputs["Y"] == ["lm.head.w"], "the untied head"
        at = logit_sample(job["seq_len"])
        check_rows = np.arange(_runner.CHECK_ROWS)
        pairs = np.stack(np.broadcast_arrays(check_rows[None, :], at[:, None]), -1).astype("int32")
        hidden = layers.gather_nd(block.var(head.inputs["X"][0]), layers.assign(pairs))
        sampled = layers.matmul(hidden, block.var("lm.head.w"))

        def rows(name):   # the stage rows of a variable, as an op of the program
            return layers.slice(block.var(name), axes=[0], starts=[0], ends=[STAGE_ROWS]).name

        tokens = expert_sample(STAGE_ROWS * job["seq_len"])
        token_pairs = layers.assign(np.stack([tokens // job["seq_len"], tokens % job["seq_len"]], -1).astype("int32"))

        def sampled_tokens(name):
            return layers.gather_nd(block.var(rows(name)), token_pairs).name

        stages = []
        for router, experts in zip(of("moe_router"), of("moe_experts")):
            stages += [router.outputs["TopKIndex"][0], rows(router.inputs["X"][0]), rows(router.outputs["TopKProb"][0]),
                       sampled_tokens(experts.outputs["Out"][0])]
        attentions, alignments = of("fused_attention"), of("index_alignment")
        for where in (0, -1):
            attention, alignment = attentions[where], alignments[where]
            stages += [rows(alignment.inputs[slot][0]) for slot in ("QI", "KI", "W", "Picks")]
            stages += [rows(attention.inputs[slot][0]) for slot in ("Q", "K", "V")] + [rows(attention.outputs["Out"][0])]
            stages += [alignment.outputs["Rows"][0]]
        rate = layers.learning_rate_scheduler.linear_lr_warmup(
            job["learning_rate"], job["lr_warmup_steps"], job["lr_warmup_start"], job["learning_rate"])
        fluid.optimizer.Adam(learning_rate=rate, beta1=job["adam_beta1"], beta2=job["adam_beta2"],
                             epsilon=job["adam_epsilon"]).minimize(fetches["loss"])
    return (main, startup, feeds, fetches["loss"],
            [fetches["loss"].name, sampled.name, fetches["ce"].name, fetches["index_kl"].name] + stages)


_HEAD, _LAYER, _STAGE = 4, 4, 9      # `build`'s variables: at the head, a layer, a staged layer


# -- the arithmetic --------------------------------------------------------------

def chosen_pairs(seq: int, topk: int) -> int:
    """(query, key) pairs a sequence's queries hold: min(topk, t + 1) each."""
    k = min(topk, seq)
    return k * (k + 1) // 2 + (seq - k) * k


def flops_per_sample(cfg: dict, job: dict) -> float:
    """Operations the forward and backward passes of one sequence require
    (matrix multiplications only, 2 per multiply-add, nothing recomputed).  Per
    position and layer, forward and twice that backward: the four attention
    projections at 32 and 4 heads of 128, the router, and three products in each
    of the position's held experts, ONE on average (8 chosen x 16 held of 128);
    the indexer's three projections forward and once backward (their input is
    detached: no gradient to it).  Per layer over the CHOSEN pairs: the two
    attention products forward and four backward a query head, the alignment
    target's scores once, the index scores' two products backward; over the
    causal TRIANGLE the index scores forward (every pair is scored before any is
    chosen).  Once a position the head."""
    d, f, seq = cfg["hidden_size"], cfg["moe_intermediate_size"], job["seq_len"]
    hq, dh = cfg["num_attention_heads"], cfg["head_dim"]
    q_width, kv_width = hq * dh, cfg["num_key_value_heads"] * dh
    index = indexer(cfg)
    held_share = cfg["num_experts_per_tok"] * cfg["num_experts"] / cfg["num_routed_experts"]
    block = 2 * d * (2 * q_width + 2 * kv_width) + 2 * d * cfg["num_routed_experts"] + held_share * 3 * 2 * d * f
    index_projections = 2 * d * (index["heads"] * index["head_dim"] + index["head_dim"] + index["heads"])
    chosen, triangle = chosen_pairs(seq, index["topk"]), seq * (seq + 1) // 2
    per_layer = (seq * (3 * block + 2 * index_projections)
                 + chosen * hq * (6 + 1) * 2 * dh
                 + (triangle + 2 * chosen) * index["heads"] * 2 * index["head_dim"])
    return float(cfg["num_hidden_layers"] * per_layer + 3 * seq * 2 * d * cfg["vocab_size"])


def index_flops(cfg: dict, job: dict) -> float:
    """Operations of a step's choosing: the index scores of every pair of the
    causal triangle, 16 heads of 64, 2 per multiply-add, for every sequence and
    layer: the same work whatever implements it.  Nothing for the ReLU, the
    weights, the sum over the heads or the choosing."""
    index, seq = indexer(cfg), job["seq_len"]
    return float(seq * (seq + 1) // 2 * index["heads"] * 2 * index["head_dim"]
                 * cfg["num_hidden_layers"] * job["batch_per_chip"])


def index_bytes(cfg: dict, job: dict, itemsize: int = 2) -> float:
    """Bytes the choosing has to move at the least: qI, kI and w read once, the
    picks written once as bits."""
    index, seq = indexer(cfg), job["seq_len"]
    per_position = (index["heads"] + 1) * index["head_dim"] * itemsize + 4 * index["heads"] + seq // 8
    return float(per_position * seq * cfg["num_hidden_layers"] * job["batch_per_chip"])


def selected_attention_flops(cfg: dict, job: dict) -> float:
    """Operations of a step's attention over the CHOSEN pairs (31.46 M a
    sequence at 16384 tokens and 2048 picks): q k^T and p v forward, four
    products backward, and the alignment target's scores once; 2 per
    multiply-add, for every sequence, layer and query head.  Nothing for a pair
    outside the picks that a kernel computes anyway, nothing for the scores a
    backward kernel computes again and nothing for a forward that a
    `recompute_scope` makes a second time."""
    pairs = chosen_pairs(job["seq_len"], indexer(cfg)["topk"])
    return float(7 * 2 * cfg["head_dim"] * pairs * cfg["num_attention_heads"] * cfg["num_hidden_layers"]
                 * job["batch_per_chip"])


def selected_attention_bytes(cfg: dict, job: dict, itemsize: int = 2) -> float:
    """Bytes those products have to move at the least: forward reads q, k and v
    and writes the output; backward reads those four and the output's gradient
    and writes the three gradients; the target reads q and k once more; the
    picks are read three times as bits."""
    hq, hkv, dh, seq = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"], job["seq_len"]
    values = (2 * hq + 2 * hkv) + (4 * hq + 4 * hkv) + (hq + hkv)
    return float((values * dh * itemsize + 3 * seq // 8) * seq * cfg["num_hidden_layers"] * job["batch_per_chip"])


# -- the reference ---------------------------------------------------------------

def rotate_sections(t, streams, theta: float, sections=None, xp=None):
    """The rotary embedding, halves rotated, of t [H, L, dh]: angle i of the
    dh / 2 is position . theta^(-i / (dh / 2)), the position read from the
    stream that `sections` gives angle i (`streams` [3, L]: temporal, height,
    width; the first 16 angles the first stream's, the next 24 the second's,
    the last 24 the third's), or from the first stream where `sections` is None."""
    if xp is None:
        import jax.numpy as xp
    half = t.shape[-1] // 2
    frequency = xp.asarray(theta ** (-np.arange(half, dtype=np.float64) / half), t.dtype)
    stream_of = np.zeros(half, np.int64) if sections is None else np.repeat(np.arange(len(sections)), sections)
    assert len(stream_of) == half, (sections, half)
    position = xp.stack([streams[s] for s in stream_of], -1).astype(t.dtype)        # [L, half]
    angle = position * frequency
    cos, sin = xp.concatenate([xp.cos(angle)] * 2, -1), xp.concatenate([xp.sin(angle)] * 2, -1)
    return t * cos + xp.concatenate([-t[..., half:], t[..., :half]], -1) * sin


def choose(scores, first_query: int, topk: int):
    """bool [C, K]: the keys that queries `first_query` on hold, from their
    float32 scores against keys 0 to K - 1: the causal ones above the row's
    `topk`-th largest, and of those equal to it the first (lowest) as many as
    are left.  From a sort and a running count: not the program's rule."""
    import jax.numpy as jnp

    queries, keys = scores.shape
    causal = jnp.arange(keys)[None, :] <= first_query + jnp.arange(queries)[:, None]
    if keys <= topk:
        return causal
    masked = jnp.where(causal, scores, -jnp.inf)
    kth = jnp.sort(masked, -1)[:, keys - topk][:, None]
    above, equal = masked > kth, masked == kth
    left = topk - jnp.sum(above, -1, keepdims=True)
    return causal & (above | (equal & (jnp.cumsum(equal, -1) <= left)))


def sparse_attention(q, k, v, qi, ki, w, topk: int, picks=None):
    """(context [Hq, L, dh], the alignment term summed over the queries, the
    picks bool [L, L] where `keep_picks`) of one sequence and layer: q [Hq, L,
    dh], k and v [Hkv, L, dh], the indexer's qi [Hi, L, Di], ki [L, Di] and w
    [L, Hi] (the two scales in it).  `picks` bool [L, L]: a choice handed in
    (the program's own, for the stage check) instead of chosen here."""
    import jax
    import jax.numpy as jnp

    heads, seq, dh = q.shape
    group = heads // k.shape[0]
    assert heads % 2 == 0 and (group % 2 == 0 or group == 1), (heads, group)
    block = min(seq, ATTENTION_BLOCK)
    contexts, divergence = [], 0.0
    for start in range(0, seq, block):          # the queries of a block against the keys up to the block's end
        end = min(start + block, seq)

        def index_head(total, operands, end=end):
            q_head, w_head = operands            # [C, Di], [C]
            return total + jax.nn.relu(q_head @ ki[:end].T) * w_head[:, None], None

        scores, _ = jax.lax.scan(index_head, jnp.zeros((end - start, end), jnp.float32),
                                 (qi[:, start:end], w[start:end].T))
        chosen = choose(scores, start, topk) if picks is None else picks[start:end, :end]

        def two_heads(summed, j, start=start, end=end, chosen=chosen):
            qs = jax.lax.dynamic_slice_in_dim(q[:, start:end], 2 * j, 2, 0)
            ks, vs = (jnp.repeat(jax.lax.dynamic_slice_in_dim(t[:, :end], (2 * j) // group, max(2 // group, 1), 0),
                                 min(group, 2), 0) for t in (k, v))
            s = jnp.einsum("hqd,hkd->hqk", qs, ks) / np.sqrt(dh)
            p = jax.nn.softmax(jnp.where(chosen, s, -jnp.inf), -1)
            return summed + jnp.sum(p, 0), jnp.einsum("hqk,hkd->hqd", p, vs)

        summed, ctx = jax.lax.scan(two_heads, jnp.zeros((end - start, end), jnp.float32), jnp.arange(heads // 2))
        contexts.append(ctx.reshape(heads, end - start, v.shape[-1]))
        target = jax.lax.stop_gradient(summed / heads)
        log_r = jax.nn.log_softmax(jnp.where(chosen, scores, -jnp.inf), -1)
        live = chosen & (target > 0)
        divergence = divergence + jnp.sum(jnp.where(
            live, target * (jnp.log(jnp.where(live, target, 1.0)) - jnp.where(live, log_r, 0.0)), 0.0))
    return jnp.concatenate(contexts, 1), divergence


def reference(params: dict, batch: dict, cfg: dict, program=None, precision: str = "highest"):
    """(loss, the sampled positions' logits [rows, sample, vocab], the
    language-model term, the alignment term, margin [rows, L], choice [layers,
    rows, L, 8], the float32 router, gate, up and down weights stacked by
    layer, (first held expert, topk), the first and the last layer's queries
    and keys at `attention_sample`'s positions, four arrays [rows, heads,
    sample, 128], and for `reference_self_error` the first row's normed input of
    the first layer at those positions [sample, d], that layer's query matrix
    and their product as the reference made it)
    of `batch` in plain float32 jax.numpy, one sequence at a time; `params` maps
    the program's parameter names to arrays.  No kernel and no [L, L] array:
    see the module's docstring.  `precision` is the float32 products':
    "highest" is the reference; tools/chip_keye_controls.py asks for "default"
    (bf16 operands on the chip, the nearest precision below) to show that the
    comparison tells it."""
    import jax
    import jax.numpy as jnp

    depth, eps, theta = cfg["num_hidden_layers"], cfg["rms_norm_eps"], float(cfg["rope_theta"])
    hq, hkv, dh = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    index, top_k = indexer(cfg), cfg["num_experts_per_tok"]
    sections = cfg["rope_scaling"]["mrope_section"]
    first, n_held = held(cfg)

    def p(name):
        return jnp.asarray(params[name], jnp.float32)

    def rms(x, gain):
        return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps) * p(gain)

    def one_sequence(row):
        ids, labels, positions = row
        seq = ids.shape[0]
        streams = jnp.stack([positions] * 3)            # text: temporal, height and width are the token index
        x = p("lm.tok_emb")[ids]
        margin = jnp.full((seq,), jnp.inf)
        choices, sampled_qk, divergences = [], {}, []
        sample = attention_sample(seq)
        first_input = rms(x, "lm.l0.ln1.w")[sample]
        first_product = first_input @ p("lm.l0.attn.q.w")       # what `reference_self_error` holds against float64
        for i in range(depth):
            pre = f"lm.l{i}"
            u = rms(x, f"{pre}.ln1.w")

            def heads_of(t, n, norm=None):
                t = t.reshape(seq, n, dh)
                return (t if norm is None else rms(t, norm)).transpose(1, 0, 2)

            q = rotate_sections(heads_of(u @ p(f"{pre}.attn.q.w"), hq, f"{pre}.attn.q_norm.w"), streams, theta, sections)
            k = rotate_sections(heads_of(u @ p(f"{pre}.attn.k.w"), hkv, f"{pre}.attn.k_norm.w"), streams, theta, sections)
            v = heads_of(u @ p(f"{pre}.attn.v.w"), hkv)
            if i in (0, depth - 1):
                sampled_qk[i] = (q[:, sample], k[:, sample])
            detached = jax.lax.stop_gradient(u)          # the indexer trains on its own loss
            qi = (detached @ p(f"{pre}.attn.index.q.w")).reshape(seq, index["heads"], index["head_dim"]).transpose(1, 0, 2)
            ki = detached @ p(f"{pre}.attn.index.k.w")
            centred = ki - jnp.mean(ki, -1, keepdims=True)
            ki = (centred * jax.lax.rsqrt(jnp.mean(jnp.square(centred), -1, keepdims=True) + 1e-6)
                  * p(f"{pre}.attn.index.k_norm.w") + p(f"{pre}.attn.index.k_norm.b"))
            qi, ki = rotate_sections(qi, streams, theta), rotate_sections(ki[None], streams, theta)[0]
            w = (detached @ p(f"{pre}.attn.index.w.w")) * (index["heads"] ** -0.5 * index["head_dim"] ** -0.5)
            ctx, divergence = sparse_attention(q, k, v, qi, ki, w, index["topk"])
            divergences.append(divergence / seq)
            h = x + ctx.transpose(1, 0, 2).reshape(seq, hq * dh) @ p(f"{pre}.attn.out.w")
            m = rms(h, f"{pre}.ln2.w")
            probs = jax.nn.softmax(m @ p(f"{pre}.moe.router.w"), -1)
            ranked = jnp.sort(probs, -1)[:, ::-1]
            kth, after = ranked[:, top_k - 1], ranked[:, top_k]
            chosen = jnp.where(probs >= kth[:, None], probs, 0.0)
            gates = chosen / jnp.sum(chosen, -1, keepdims=True)   # over all eight, held or not

            def expert(acc, ew, m=m):
                gate, up, down, g_e = ew
                return acc + (jax.nn.silu(m @ gate) * (m @ up)) @ down * g_e[:, None], None

            routed, _ = jax.lax.scan(
                expert, jnp.zeros_like(h),
                (p(f"{pre}.moe.gate.w"), p(f"{pre}.moe.up.w"), p(f"{pre}.moe.down.w"),
                 gates[:, first:first + n_held].T))
            x = h + routed
            margin = jnp.minimum(margin, (kth - after) / kth)
            choices.append(jnp.sort(jax.lax.top_k(probs, top_k)[1], -1))
        normed = rms(x, "lm.final_norm.w")
        block = min(seq, ATTENTION_BLOCK)

        def ce_of(lo):   # the cross entropies of a block of positions, summed: never [L, vocab] at once
            logp = jax.nn.log_softmax(jax.lax.dynamic_slice_in_dim(normed, lo, block, 0) @ p("lm.head.w"), -1)
            return -jnp.sum(jnp.take_along_axis(logp, jax.lax.dynamic_slice_in_dim(labels, lo, block, 0)[:, None], 1))

        ce_sum = jnp.sum(jax.lax.map(ce_of, jnp.arange(0, seq, block)))
        out = normed[logit_sample(seq)] @ p("lm.head.w")
        return ((out, margin, jnp.stack(choices), ce_sum, jnp.mean(jnp.stack(divergences)))
                + sampled_qk[0] + sampled_qk[depth - 1] + (first_input, first_product))

    with jax.default_matmul_precision(precision):
        rows = tuple(jnp.asarray(batch[n], jnp.int32) for n in FEEDS)
        out, margin, choice, ce_sum, divergence, *sampled_qk, first_input, first_product = jax.lax.map(one_sequence, rows)
        ce, index_kl = ce_sum.sum() / rows[1].size, jnp.mean(divergence)
        weights = tuple(jnp.stack([p(f"lm.l{i}.moe.{n}.w") for i in range(depth)])
                        for n in ("router", "gate", "up", "down"))
        return ((ce + index_kl, out, ce, index_kl, margin, choice.transpose(1, 0, 2, 3)) + weights
                + (jnp.asarray([first, index["topk"]], jnp.int32),) + tuple(sampled_qk)
                + (first_input[0], p("lm.l0.attn.q.w"), first_product[0]))


# -- the comparison ----------------------------------------------------------------

def unpack(picks, length: int):
    """bool [..., length] of the program's picks int32 [..., length / 32]:
    bit j of word w is key 32 w + j."""
    words = np.ascontiguousarray(picks).view(np.uint32)
    return ((words[..., None] >> np.arange(32, dtype=np.uint32)) & 1).astype(bool).reshape(
        words.shape[:-1] + (-1,))[..., :length]


def index_scores(qi, ki, w, at):
    """float64 I[t, .] of the sampled queries `at`, from the program's own qI
    [L, Hi, Di], kI [L, 1, Di] and w [L, Hi] (unscaled): every key, causal or not."""
    qi, ki, w = (np.asarray(t, "f4").astype("f8") for t in (qi, ki, w))
    heads, width = qi.shape[1], qi.shape[2]
    products = np.einsum("chd,kd->chk", qi[at], ki[:, 0])
    return np.einsum("chk,ch->ck", np.maximum(products, 0.0), w[at]) * heads ** -0.5 * width ** -0.5


def picks_errors(qi, ki, w, picks, topk: int, at) -> dict:
    """The program's choice at the sampled queries against float64's on the
    scores of its own operands (see `PICKS_DIFFER_MAX`, `PICKS_GAP_MAX`), the
    count of keys a query holds and of keys after the query; and what the same
    float64 scores read with the products' sums held in bf16, eight terms at a
    time (`picks_differ_bf16_sums`): what the limit has to refuse."""
    seq = picks.shape[0]
    scores = index_scores(qi, ki, w, at)
    mine = unpack(picks[at], seq)
    causal = np.arange(seq)[None, :] <= np.asarray(at)[:, None]

    def choice_of(s):
        order = np.argsort(-np.where(causal, s, -np.inf), -1, kind="stable")        # the lower index first among equals
        want = np.zeros_like(causal)
        np.put_along_axis(want, order[:, :topk], True, -1)
        return want & causal

    want = choice_of(scores)
    differs = mine != want
    kth = np.sort(np.where(causal, scores, -np.inf), -1)[:, -min(topk, seq)][:, None]
    scale = np.abs(np.where(causal, scores, 0.0)).max(-1, keepdims=True)
    gap = np.where(differs & causal, np.abs(scores - np.where(np.isfinite(kth), kth, 0.0)) / np.maximum(scale, 1e-30), 0.0)
    qi8, ki8 = _bf16(np.asarray(qi, "f4")[at]), _bf16(np.asarray(ki, "f4")[:, 0])
    low = np.zeros(qi8.shape[:2] + (seq,), "f4")
    for i in range(0, qi8.shape[-1], 8):
        low = _bf16(low + np.einsum("chd,kd->chk", qi8[..., i:i + 8], ki8[:, i:i + 8]))
    low = np.einsum("chk,ch->ck", np.maximum(low, 0.0).astype("f8"), np.asarray(w, "f4").astype("f8")[at])
    held_keys = np.minimum(topk, np.asarray(at) + 1)
    return {"picks_differ": float(differs.sum() / 2 / max(want.sum(), 1)),
            "picks_gap": float(gap.max(initial=0.0)),
            "picks_miscounted": int((mine.sum(-1) != held_keys).sum()),
            "picks_after_query": int((mine & ~causal).sum()),
            "picks_differ_bf16_sums": float((choice_of(low) != want).sum() / 2 / max(want.sum(), 1))}


def attention_errors(q, k, v, out, picks, at) -> dict:
    """The program's attention output at the sampled queries against float32
    numpy on its own q [Hq, L, dh], k, v [Hkv, L, dh] and its OWN picks, each
    query over the keys it holds among all L: largest |error| over the largest
    |output|; the same under the DENSE causal mask (what an attention that
    ignores the picks would give: `attention_error_dense`, to be refused)."""
    seq, group = q.shape[1], q.shape[0] // k.shape[0]
    allowed = unpack(picks[at], seq)
    causal = np.arange(seq)[None, :] <= np.asarray(at)[:, None]
    worst = dense = largest = 0.0
    for h in range(q.shape[0]):
        keys, values = np.asarray(k[h // group], "f4"), np.asarray(v[h // group], "f4")
        scores = np.asarray(q[h][at], "f4") @ keys.T / np.sqrt(q.shape[-1])

        def attend(mask):
            s = np.where(mask, scores, -np.inf)
            e = np.exp(s - s.max(-1, keepdims=True))
            return (e / e.sum(-1, keepdims=True)) @ values

        want, mine = attend(allowed), np.asarray(out[h][at], "f4")
        worst = max(worst, float(np.abs(mine - want).max()))
        dense = max(dense, float(np.abs(attend(causal) - want).max()))
        largest = max(largest, float(np.abs(want).max()))
    return {"attention_error": worst / max(largest, 1e-30), "attention_error_dense": dense / max(largest, 1e-30)}


def alignment_of(qi, ki, w, q, k, picks, topk: int) -> float:
    """One row's alignment term from the program's OWN operands and picks, by
    the reference's function at the highest precision."""
    import jax
    import jax.numpy as jnp

    seq = q.shape[1]
    heads, width = qi.shape[1], qi.shape[2]

    def term(qi, ki, w, q, k, picks):
        f32 = [jnp.asarray(t, jnp.float32) for t in (qi, ki, w, q, k)]
        _, divergence = sparse_attention(f32[3], f32[4], jnp.zeros_like(f32[4]), f32[0].transpose(1, 0, 2), f32[1][:, 0],
                                         f32[2] * (heads ** -0.5 * width ** -0.5), topk, picks=picks)
        return divergence / seq

    with jax.default_matmul_precision("highest"):
        return float(jax.jit(term)(qi, ki, w, q, k, unpack(np.asarray(picks), seq)))


def compare(got, want) -> dict:
    """The program's fetched variables (`build`) against the reference's
    outputs (`reference`): the errors `REFERENCE_RTOL` bounds, the routing
    account, and the worst layer's stage errors."""
    loss, ce, index_kl = (float(np.asarray(got[i]).reshape(-1)[0]) for i in (0, 2, 3))
    want_loss, want_ce, want_kl = float(want[0]), float(want[2]), float(want[3])
    want_logits = np.asarray(want[1], "f4")                                   # [rows, sample, vocab]
    logits = np.asarray(got[1], "f4").transpose(1, 0, 2)
    margin, want_choice = np.asarray(want[4]), np.asarray(want[5])
    rows, seq = margin.shape
    tokens, k = margin.size, want_choice.shape[-1]
    (first, topk), n_held = (int(n) for n in np.asarray(want[10])), np.asarray(want[7]).shape[1]
    depth = want_choice.shape[0]
    layers = [got[i:i + _LAYER] for i in range(_HEAD, _HEAD + _LAYER * depth, _LAYER)]
    staged = [got[_HEAD + _LAYER * depth + j * _STAGE:][:_STAGE] for j in (0, 1)]
    choice = np.sort(np.stack([np.asarray(layer[0]).reshape(want_choice.shape[1:]) for layer in layers]), -1)
    routed_differently = (choice != want_choice).any(axis=(0, 3))           # [rows, L]

    def held_choice(c):  # [layers, rows, L, held]: which held experts a position chose
        return (c[..., None] == np.arange(first, first + n_held)).any(-2)

    differs = (held_choice(choice) != held_choice(want_choice)).any(axis=(0, 3))
    sampled = differs[:, logit_sample(seq)]
    err = np.abs(logits - want_logits).max(-1)
    stage_rows = np.asarray(layers[0][1]).shape[0]
    n_staged = stage_rows * seq
    sample = expert_sample(n_staged)

    def spread(t):   # the sampled tokens' rows at their places among the staged tokens: the stages read those alone
        full = np.zeros((n_staged, t.shape[-1]), "f4")
        full[sample] = np.asarray(t, "f4")
        return full

    stages = [_block.stage_errors(np.asarray(c).reshape(tokens, k)[:n_staged], np.asarray(m, "f4").reshape(n_staged, -1),
                                  np.asarray(p, "f4").reshape(n_staged, k), spread(o),
                                  *(np.asarray(w[i], "f4") for w in want[6:10]), first)
              for i, (c, m, p, o) in enumerate(layers)]
    scale = max(np.abs(want_logits).max(), 1e-9)
    summed = ("router_choice_differs", "router_ties")
    at = attention_sample(seq)
    kept = ~differs[0, at]
    picked, attention, alignment, qk, qk_left_out = [], [], [], [], []
    for where, (qi, ki, w, picks, q, key, v, out, term) in enumerate(staged):
        qi, ki, w, picks, q, key, v, out = (np.asarray(t)[0] for t in (qi, ki, w, picks, q, key, v, out))   # the stage row
        picked.append(picks_errors(qi, ki, w, picks, topk, at))
        attention.append(attention_errors(q, key, v, out, picks, at))
        mine, theirs = float(np.asarray(term).reshape(-1)[0]), alignment_of(qi, ki, w, q, key, picks, topk)
        alignment.append(abs(mine - theirs) / max(abs(theirs), 1e-30))
        want_q, want_k = (np.asarray(want[11 + 2 * where + j], "f4")[0] for j in (0, 1))      # [heads, sample, dh]
        off = [np.abs(np.asarray(mine, "f4")[:, at] - theirs).max(axis=(0, 2)) / np.abs(theirs).max()
               for mine, theirs in ((q, want_q), (key, want_k))]
        mask = kept if where else np.ones_like(kept)        # before the first layer's attention no expert stands
        qk.append(float(max(e[mask].max(initial=0.0) for e in off)))
        qk_left_out.append(float(max(e[~mask].max(initial=0.0) for e in off)))
    # the yardstick's own precision: the reference's first product of the first row (before the norm) against float64
    exact = np.asarray(want[15], "f8") @ np.asarray(want[16], "f8")
    return {
        "reference_self_error": float(np.abs(np.asarray(want[17], "f8") - exact).max() / np.abs(exact).max()),
        "loss_error": abs(loss - want_loss) / max(abs(want_loss), 1e-6),
        "ce_error": abs(ce - want_ce) / max(abs(want_ce), 1e-6),
        "index_kl": index_kl, "index_kl_reference": want_kl,
        "index_kl_error": abs(index_kl - want_kl) / max(abs(want_kl), 1e-30),
        "logit_error": float(err[~sampled].max(initial=0.0) / scale),
        "logit_error_left_out": float(err[sampled].max(initial=0.0) / scale),
        "tokens": int(tokens),
        "left_out": int(differs.sum()),
        "routed_differently": int(routed_differently.sum()),
        "under_margin": int((margin < ROUTING_MARGIN).sum()),
        "routed_differently_above_margin": int((routed_differently & (margin >= ROUTING_MARGIN)).sum()),
        **{name: (sum if name in summed else max)(stage[name] for stage in stages) for name in stages[0]},
        "held_rows_share": [float(held_choice(c[None]).sum() / (tokens * k)) for c in choice],
        **{name: max(p[name] for p in picked) for name in picked[0] if name != "picks_differ_bf16_sums"},
        "picks_differ_bf16_sums": min(p["picks_differ_bf16_sums"] for p in picked),
        "attention_error": max(a["attention_error"] for a in attention),
        "attention_error_dense": min(a["attention_error_dense"] for a in attention),
        "attention_errors": [a["attention_error"] for a in attention],
        "alignment_error": max(alignment), "alignment_errors": alignment,
        "qk_error": max(qk), "qk_errors": qk, "qk_error_left_out": max(qk_left_out),
    }


def reference_error(got, want) -> float:
    """How far the program is from the reference, as `REFERENCE_RTOL` counts
    it: the largest of the language-model term's and the sampled logits' error,
    the logits over the positions whose held choice agrees.  A failure
    (infinite error) is any other limit of `failed_limits` passed; the run's
    `reference_routing` line holds every reading beside its limit."""
    import json

    found = compare(got, want)
    print(json.dumps({"info": "reference_routing", **found,
                      "left_out_share": found["left_out"] / found["tokens"],
                      "routing_margin": ROUTING_MARGIN, "left_out_max": LEFT_OUT_MAX,
                      "left_out_logit_max": LEFT_OUT_LOGIT_MAX, "router_rtol": ROUTER_RTOL,
                      "experts_rtol": EXPERTS_RTOL, "index_kl_rtol": INDEX_KL_RTOL,
                      "picks_differ_max": PICKS_DIFFER_MAX, "picks_gap_max": PICKS_GAP_MAX,
                      "attention_rtol": ATTENTION_RTOL, "alignment_rtol": ALIGNMENT_RTOL, "qk_rtol": QK_RTOL,
                      "reference_self_rtol": REFERENCE_SELF_RTOL, "failed_limits": failed_limits(found)}),
          flush=True)
    return float("inf") if failed_limits(found) else max(found["ce_error"], found["logit_error"])


def failed_limits(found: dict) -> list:
    """The names of the limits that `found` (`compare`'s account) passes,
    `REFERENCE_RTOL` among them: empty for a sound program."""
    checks = {
        "ROUTING_MARGIN": not found["routed_differently_above_margin"],
        "LEFT_OUT_MAX": found["left_out"] <= LEFT_OUT_MAX * found["tokens"],
        "LEFT_OUT_LOGIT_MAX": found["logit_error_left_out"] <= LEFT_OUT_LOGIT_MAX,
        "ROUTER_TIE": not found["router_choice_differs"],
        "ROUTER_RTOL": found["router_prob_error"] <= ROUTER_RTOL,
        "EXPERTS_RTOL": found["experts_error"] <= EXPERTS_RTOL,
        "INDEX_KL_RTOL": found["index_kl_error"] <= INDEX_KL_RTOL,
        "picks_count": not (found["picks_miscounted"] or found["picks_after_query"]),
        "PICKS_DIFFER_MAX": found["picks_differ"] <= PICKS_DIFFER_MAX,
        "PICKS_GAP_MAX": found["picks_gap"] <= PICKS_GAP_MAX,
        "ATTENTION_RTOL": found["attention_error"] <= ATTENTION_RTOL,
        "ALIGNMENT_RTOL": found["alignment_error"] <= ALIGNMENT_RTOL,
        "QK_RTOL": found["qk_error"] <= QK_RTOL,
        "REFERENCE_RTOL": max(found["ce_error"], found["logit_error"]) <= REFERENCE_RTOL,
        "REFERENCE_SELF_RTOL": found["reference_self_error"] <= REFERENCE_SELF_RTOL,
    }
    return [name for name, passed in checks.items() if not passed]
