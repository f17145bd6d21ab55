"""Qwen3-Next-80B-A3B-Instruct causal-LM training: how the benchmark builds it
through the framework, a plain float32 reference of the same architecture, and
the operations one sequence needs.

Architecture: Qwen/Qwen3-Next-80B-A3B-Instruct `config.json` (`model_type:
qwen3_next`) and the family's public `modeling_qwen3_next.py`; what `config.json`
does not give is listed in the configuration file's `assumed`.  A layer, eps
1e-6, no bias anywhere, x the layer's input [tokens, 2048], rms(t; w) = t /
sqrt(mean t^2 + eps) . w, layer i a softmax attention where (i + 1) % 4 == 0 and
a Gated DeltaNet otherwise:

    a = rms(x; ln1)
    linear    [q | k | v | z] = a Wqkvz [2048 -> 2048 + 2048 + 4096 + 4096];  [b | alpha] = a Wba [2048 -> 32 + 32]
              [q | k | v] <- silu(conv_4(.)), depthwise, causal, zeros before the sequence's start
              q, k: 16 heads of 128, l2(t) = t / sqrt(sum_head t^2 + 1e-6), q <- l2(q) 128^-0.5, k <- l2(k);  v, z: 32 heads
              of 128;  value head h reads key head h div 2
              beta = sigmoid(b),  g = -exp(A_log[h]) softplus(alpha + dt_bias[h]):  ONE float32 number a value head a token
              S_t = exp(g_t) (I - beta_t k_t k_t^T) S_{t-1} + beta_t k_t v_t^T,  S_0 = 0,  S in R^{128 x 128} a head, float32
              o_t = S_t^T q_t;   h = x + [ rms_head(o_t; o_norm) * silu(z_t) ] Wo     [4096 -> 2048]
    full      [q | gate] = a Wq [2048 -> 16 x (256 + 256)]: a head's 256 query features, then its 256 gate features
              k = a Wk, v = a Wv (2 heads of 256);  q, k <- rms over a head (one gain of 256 each)
              the FIRST 64 features of a head turn, (t_i, t_i+32) <- (t_i cos w - t_i+32 sin w, t_i+32 cos w + t_i sin w),
              w = position . 1e7^(-i/32), i < 32, float32; the last 192 pass
              causal softmax at 256^-0.5, float32; query head j reads key/value head j div 8
              h = x + [ o * sigmoid(gate) ] Wo     [4096 -> 2048]: the gate a FEATURE, 4096 numbers a token
    sparse    m = rms(h; ln2);  p = softmax_f32(m Wr) over 512;  S = the 10 largest of p;  w_e = p_e / sum_{e' in S} p_e'
              y = h + sum_{e in S and e in HELD} w_e E_e(m) + sigmoid(m w_s) . E_shared(m)
              every E a gated SiLU feed-forward of 512, W2( silu(W1 m) * (W3 m) );  HELD = {0..15};  w_s [2048, 1]
    loss      mean over every position of CE( rms(y_L; final_norm) W_head, the next token ), the head untied

The reference computes the DeltaNet as the RECURRENCE above, a token at a time
under `lax.scan` (never the chunked form the program's op uses), the
convolution as four shifted products, the L2 norms, the softplus, the gated
norm, the partial rotation, the attention as explicit causal scores a
key/value head's query heads and `ATTENTION_BLOCK` queries at a time, the top
10 by a sort, both gates, the experts as a loop over the held ones, and the
loss `ATTENTION_BLOCK` positions at a time.  It shares no code with
`paddle_tpu` nor with another model's reference.

Departures of the program under test
(`paddle_tpu.models.transformer.build_causal_lm`) from the published model,
which the reference follows so that the two compute the same function (the
configuration file's `departures` is this list, word for word):

  * four of the 48 layers, the published layers 0 to 3: linear, linear, linear, full: one whole period at the published three to one, the floor of four (the model has no leading dense layer); further layers lie on further chips as pipeline stages;
  * 16 of the 512 routed experts of every layer, experts 0 to 15: this chip's share of a layer whose experts are split over 32 chips; the router keeps its 512 outputs, its top 10 and its renormalisation over all ten chosen, the shared expert and its gate are computed here as on every chip, and what the 496 absent experts would have added is left out of the layer's output, in the program and in the reference alike, with no exchange standing in for the 31 absent chips;
  * 18992 of the 151936 vocabulary rows, in the embedding and in the untied head: one chip's eighth of the rows, the guide's floor (not a thirty-second: the vocabulary is split eight ways here); token ids and labels are drawn from the slice and the loss is over the slice;
  * every layer is a `recompute_scope`: backward keeps a layer's input and what `plan_kept` finds room for and makes the rest of the layer again, the routing with it (the scans' outputs, start states and T are kept: no scan runs twice); the numbers are the same either way (tests/test_qwen3_next.py holds the gradients equal to the last bit);
  * Adam for AdamW (the framework has no AdamW), learning rate 1e-4 reached by a linear warm-up over the first 200 steps from 1e-6, betas 0.9 / 0.95, epsilon 1e-8, no decay, no auxiliary loss and no multi-token-prediction module;
  * weights are random, N(0, 0.02) from the run's seed, the convolution's taps too, norm gains 1, A_log uniform on [0, ln 16] a head and dt_bias uniform on [ln 1e-3, ln 1e-1] a head (a decay of 0.2 to 0.999 a token), but for the token embedding and the routers' matrices, which come from the configuration's `routing_seed` and not from the run's: they decide which experts a token meets, as a checkpoint's do, and which of the 32 chips this is;
  * token ids are uniform random with no padding and no document boundaries (a row is one whole sequence of 16384 positions 0 to 16383, the state starts at zero with it), every position is a label (the next token), so the cross entropy starts near ln(18992).
"""
from __future__ import annotations

import functools

import numpy as np

from benchmark.models import lfm2 as _decoder
from benchmark.runners import train as _runner

FEEDS = ("ids", "labels", "pos_ids")

#: Every limit below was set from this cell's own readings at the published
#: widths and 16384 tokens (my chip runs, PR 69; PERF.md section 6 has the table
#: and the seeds): what the sound program reads, and what the same comparison
#: reads with a fault put in (tools/chip_qwen3_next_controls.py), the limit
#: between the two with room on both sides.  The routing margin is OLMoE's
#: argument (benchmark/models/olmoe.py: top-k is discontinuous and the program's
#: router reads a bf16 stream).
ROUTING_MARGIN = _decoder.ROUTING_MARGIN
#: ... and the share of all positions that may route differently ACROSS a gap
#: wider than that margin.  The other cells allow none; here the stream that the
#: fourth router reads stands 3.2% (median; 8.8% the worst sampled position)
#: from the reference's after three delta-rule layers of 16384 tokens
#: (`stream_errors`), and ten weights of 512 near-tied outputs leave 6 to 10 of
#: 131072 positions (4.6e-5 to 7.6e-5) that cross it, SOUND.  The least a fault
#: reads: a gate a head for a gate a feature 12 (9.2e-5: not told), theta 1e6 12,
#: half a head turned 26, the feature gate left out 53 (4.0e-4), every fault of a
#: linear layer 117 to 145 (8.9e-4 to 1.1e-3), top 8 for top 10 408.  3.9x over
#: the most seen, 1.3x under the first fault it tells, 3x under the linear layers'.
ROUTED_ABOVE_MARGIN_MAX = 3e-4
#: Sampled positions whose HELD choice differs in some layer are left out of the
#: logit comparison and counted over all positions (10 chosen of 512, 16 held,
#: four sparse layers): 5.9% to 7.5% sound in sixteen runs.  The least a fault that has no stage
#: of its own reads: the renormalisation left out 22.6%, top 8 for top 10 23.6%,
#: the shared gate left out 72.5%, every fault of a linear layer 77% to 92% (a
#: sigmoid router 10.7% and the attention's faults 7.2% to 9.1% it does not tell:
#: `ROUTER_RTOL`, `QK_RTOL` and `REFERENCE_RTOL` do).  1.33x over the most seen,
#: 2.3x under the least fault that is its own (a sigmoid router IN THE REFERENCE
#: is refused by this limit alone and barely, 10.65%: its twin in the program
#: reads 2.87 against `ROUTER_RTOL`).
LEFT_OUT_MAX = 0.10
#: ... and how far a left-out position's logits may be off, over the largest
#: |reference logit|: one held expert's output more or less, 0.103 to 0.112
#: sound.  A sanity bound at 4x the most seen (a NaN fails it), NOT a limit
#: between two readings, as the other held cells'.
LEFT_OUT_LOGIT_MAX = 0.45
#: The larger of the loss's relative error (1.6e-5) and the sampled logits'
#: error over the largest |reference logit|, on the positions that chose alike:
#: each sampled position's worst logit, and of the 2048 positions the
#: `LOGIT_QUANTILE`.  NOT their maximum: the median position's stream stands
#: 3.4% from the reference's at the final norm (1.0% a layer: bf16 activations
#: over float32 masters through three delta-rule layers of 16384 tokens, each a
#: scan whose output is rounded once more, a 256-wide attention, four sparse
#: layers and a bf16 head), but the WORST position's read 0.063, 0.065, 0.066,
#: 0.070, 0.077, 0.098, 0.144 and 0.168 in eight sound runs, a seed each: a tail
#: a maximum of 2048 cannot be held to (`WORST_POSITION_MAX` bounds it as a
#: sanity).  The quantile (my chip runs, PR 69, call 4, the limit set before it):
#: 0.030 to 0.047 in seven sound runs, a seed each.  The least a fault that
#: nothing else tells reads: the feature gate left out 0.235, query head j on
#: key/value head j mod 2 0.263, the renormalisation left out 0.230 (each has a
#: stage of its own too); every fault of a linear layer 0.74 to 1.24, the shared
#: gate left out 0.82.  It does NOT tell a head's mean for the feature gate
#: (0.099), a sigmoid router (0.112), top 8 (0.077) or a wrong rotation (0.066 to
#: 0.162): `GATED_RTOL`, `ROUTER_RTOL`, `LEFT_OUT_MAX` and `QK_RTOL` do.  4.3x over
#: the most seen, 1.15x under the least fault it tells and 3.7x under the linear
#: layers'.
REFERENCE_RTOL = 0.2
LOGIT_QUANTILE = 0.99
#: ... and the worst sampled position's logits and full-layer queries and keys,
#: over the largest |value|: 0.063 to 0.168 and 0.033 to 0.154 sound in nine
#: runs.  A sanity bound as `LEFT_OUT_LOGIT_MAX` is (an expert's output more or
#: less moves a position by 0.10 to 0.11), 2.7x over the most seen; every fault
#: of a linear layer reads 0.77 to 1.31 and 0.93 to 3.3.
WORST_POSITION_MAX = 0.45
ROUTER_TIE = _decoder.ROUTER_TIE
#: The router on the program's own input m, the stage row: the ten weights'
#: largest relative error against float64 numpy softmax, top 10 and
#: renormalisation, and no token whose ten are not float64's across a gap wider
#: than `ROUTER_TIE`: 5.0e-6 sound, no token.  With the logits rounded to bf16
#: (numpy) 1.3e-2 and 238 held choices flipped, a sigmoid for the softmax 1.70,
#: the renormalisation left out 0.92.  6x over the most seen, 440x under the least.
ROUTER_RTOL = 3e-5
#: The shared expert's gate on the same m: sigmoid(m w_s) against float64 numpy
#: on the program's own (bf16) m, largest relative error: 5.1e-3 to 5.2e-3 sound.
#: The projection is float32 at the highest precision, but a product ONE column
#: wide is a multiply-and-reduce that XLA fuses into the norm that makes m, where
#: it reads m BEFORE its rounding to bf16 (excess precision: the float64 gate on
#: the rounded m rounded to bf16 itself reads 3.9e-3), so the stage cannot tell a
#: bf16 gate; what it has to refuse is a gate that is not this one: g = 1 reads
#: (1 - g) / g, ~1.  10x over the most seen, 20x under that.
SHARED_GATE_RTOL = 5e-2
#: The held experts on the program's own m, choice and weights, every
#: `EXPERTS_SAMPLE`-th token of the stage row: root-mean-square error over the
#: root-mean-square output against float32 numpy (bf16 operands into float32
#: accumulation): 4.66e-3 to 4.72e-3 sound.  The same op at the same [2048, 512]
#: as Laguna-XS.2's cell, whose faulty reading (3.05e-2 with bf16 running sums,
#: numpy) stands for this one's.  2.5x over the one, 2.5x under the other.
EXPERTS_RTOL = 1.2e-2
#: The GATED shared expert's output on the same m and tokens against float32
#: numpy sigmoid(m w_s) . E_shared(m): 4.30e-3 to 4.32e-3 sound (the same three
#: bf16 products and one more rounding); without its gate 0.977 (the gate is
#: near a half).  2.8x over the one, 81x under the other.
SHARED_RTOL = 1.2e-2
#: The stage row: the program's own tensors of the stages are compared on the
#: first `STAGE_ROWS` of the 8 check rows (the slices are ops of the program).
STAGE_ROWS = 1
#: THE SCAN STAGE, layer 0: the op's output against the token-by-token float32
#: recurrence ON THE PROGRAM'S OWN q, k, v, g, beta (16 key heads feeding 32
#: value heads, all 16384 tokens of the stage row), the recurrence's output
#: ROUNDED to bf16 as the op rounds its own, at the first `SCAN_RUN`, a middle
#: `SCAN_RUN` and the LAST `SCAN_RUN` positions (an error that grows with the
#: state's age shows last): root-mean-square difference over the
#: root-mean-square output, the worst of the three runs: 3.9e-4 to 5.1e-4 sound
#: (3.8e-4 | 3.9e-4 | 3.9e-4 and 4.6e-4 | 5.1e-4 | 5.1e-4 by run: no growth with
#: the state's age; the op alone on drawn inputs 2.0e-4).  What it has to refuse:
#: the recurrence itself with its state rounded to bf16 a token 9.5e-3 (numpy
#: beside every run), and IN THE PROGRAM (tools/chip_qwen3_next_controls.py) the
#: state rounded to bf16 where a chunk hands it on and the scan's products at the
#: chip's default precision (PERF.md section 6 has their readings).  2.3x over
#: the most seen, 7.9x under the recurrence's.
SCAN_RTOL = 1.2e-3
SCAN_RUN = 512
#: The convolution of layer 0 on the program's own [q | k | v] columns (bf16)
#: and float32 taps at the scan's runs: root-mean-square error over the
#: root-mean-square output: 1.658e-3 sound (the op computes in float32 and
#: rounds once); with every intermediate and the taps in bf16 3.89e-3, three taps
#: for four 0.50 (numpy).  1.57x over the one, 1.5x under the nearest.
CONV_RTOL = 2.6e-3
#: q and k of layer 0 after the L2 norm (and q's 128^-0.5) against float32 numpy
#: on the program's own convolution output, the scan's runs: largest error over
#: the largest |value| (one rounding to bf16): 2.96e-3 to 3.07e-3 sound; the L2
#: norm left out 0.78, q's 128^-0.5 left out 10.3.  3.9x over, 65x under.
UNIT_RTOL = 1.2e-2
#: g and beta of layer 0 against float64 numpy on the program's own normed
#: input a (bf16) and float32 Wba, A_log, dt_bias, the scan's runs: largest
#: error over the largest |value| (the projection is a bf16 product: its 64
#: outputs are rounded to bf16 before the sigmoid and the softplus): 5.0e-3 to
#: 5.4e-3 sound; the decay of head h on head h + 1 1.00, the decay over the
#: channels (g / 128) 0.99, beta left out 1.00.  3.7x over, 50x under.
DECAY_RTOL = 2e-2
#: The gated norm of layer 0 on the program's own scan output and z, the scan's
#: runs: rms_head(o; gain) * silu(z) against float32 numpy, root-mean-square
#: error over the root-mean-square output: 1.658e-3 sound (one rounding of the
#: product: the norm's own stays inside the fusion); a sigmoid for z's SiLU 0.89.
#: 7.2x over, 74x under.
GATED_NORM_RTOL = 1.2e-2
#: The attention of layer 3 on the program's own q, k, v for
#: `ATTENTION_SAMPLE` queries of the stage row and every head, each against the
#: keys at or before it, float32 scores: largest error over the largest
#: |output|: 2.50e-3 to 2.58e-3 sound; query head j on key/value head j mod 2
#: 1.27 (numpy).  4.7x over, 106x under.
ATTENTION_RTOL = 1.2e-2
ATTENTION_SAMPLE = _decoder.ATTENTION_SAMPLE
#: ... and layer 3's queries and keys themselves, after the per-head norm and
#: the partial rotation, at the sampled positions whose held choice agrees in
#: every layer, against the reference's (which rotates on its own), over the
#: largest |value|: each position's worst feature, and of the positions the
#: `QK_QUANTILE` (the stream that the full layer reads stands 3.2% from the
#: reference's at the median position and 5% to 12% at the worst:
#: `stream_errors`); and layer 0's q and k after the L2 norm likewise, their
#: MAXIMUM (no sparse layer lies before them): 5.3e-3 to 6.0e-3 sound.  The
#: least a fault reads by the maximum: a sigmoid router 0.35, the
#: renormalisation left out 0.40, three taps for four 0.71 (layer 0's), q's
#: 128^-0.5 left out 0.91, theta 1e6 1.63, half a head turned 1.66, the whole
#: head 1.94, the L2 norm left out 3.3.  The quantile (call 4, the limit set
#: before it): 0.025 to 0.037 in seven sound runs; theta 1e6 1.32, half a head
#: turned 1.42, the whole head 1.54, the renormalisation left out 0.19, every
#: fault of a linear layer 0.73 to 3.3; a sigmoid router 0.097 and top 8 0.064 it
#: does not tell.  4.1x over the most seen, 1.3x under the least fault it tells,
#: 8.8x under the least rotation.
QK_RTOL = 0.15
QK_QUANTILE = 0.95
#: THE FEATURE GATE, layer 3: the program's gated output [sample, 16, 256]
#: against float32 numpy o . sigmoid(gate) on the program's own attention output
#: and gate columns, over the largest |value| (one rounding to bf16): 1.9e-3 to
#: 2.4e-3 sound; a head's mean of the gate in its place 0.43, no gate 0.96
#: (numpy).  5x over, 36x under.
GATED_RTOL = 1.2e-2
#: Queries a block of the reference's attention and positions a block of its loss.
ATTENTION_BLOCK = 1024

logit_sample = _decoder.logit_sample
make_batch = _decoder.make_batch
_bf16 = _decoder._bf16


def _runs(positions: int, run: int) -> list:
    """Three runs of `run` positions, [first, past the last): at the sequence's
    start, middle and end; the whole sequence where three do not fit apart."""
    if positions < 3 * run:
        return [(0, positions)]
    return [(lo, lo + run) for lo in (0, (positions - run) // 2, positions - run)]


def _positions(runs) -> np.ndarray:
    return np.concatenate([np.arange(lo, hi) for lo, hi in runs])


def scan_sample(positions: int) -> np.ndarray:
    """The positions at which layer 0's convolution, norms, decay, scan and gated norm are compared."""
    return _positions(_runs(positions, SCAN_RUN))


def attention_sample(positions: int) -> np.ndarray:
    """The positions whose queries, attention outputs and gates layer 3's stages read."""
    return _positions(_runs(positions, ATTENTION_SAMPLE // 3))


def expert_sample(tokens: int) -> np.ndarray:
    """The tokens of the stage rows whose held and shared experts' outputs are compared."""
    return np.arange(0, tokens, max(tokens // _decoder.EXPERTS_SAMPLE, 1))


def held(cfg: dict) -> tuple:
    """(first, count) of the routed experts this chip holds."""
    return (cfg["experts_held_first"], cfg["num_experts"])


def _linear(cfg: dict) -> tuple:
    """(key heads, value heads, a head's width, taps) of the Gated DeltaNet layers."""
    assert cfg["linear_key_head_dim"] == cfg["linear_value_head_dim"], "keys and values of one width"
    return cfg["linear_num_key_heads"], cfg["linear_num_value_heads"], cfg["linear_key_head_dim"], cfg["linear_conv_kernel_dim"]


def _turned(cfg: dict) -> int:
    return int(cfg["head_dim"] * cfg["partial_rotary_factor"])


#: a sparse layer's matrices, as `reference` hands them on, stacked by layer, after its nine outputs (the program's own
#: float32 masters: through the reference's call and not the clone's, whose outputs would hold 0.9 GB of copies)
_MATRICES = ("router", "gate", "up", "down", "shared.gate", "shared.up", "shared.down", "shared_gate")
#: layer 0's parameters, as `build` fetches them after its ten stage tensors
_LINEAR_PARAMETERS = ("qkv_conv.w", "ba.w", "a_log", "dt_bias", "o_norm.w")
_PER_LAYER, _PER_LINEAR, _PER_ATTENTION = 6, 10 + len(_LINEAR_PARAMETERS), 6


def build(cfg: dict, job: dict):
    """(main, startup, feed variables by name, loss variable, the variables the
    reference is compared on) of the train program, as a user of the framework
    gets it: `build_causal_lm` with every layer a recomputed segment (a job may
    say `recompute_layers` false: the tests', which hold the two alike), then
    the learning rate's warm-up and Adam from the traffic file.  The compared
    variables: loss, the sampled positions' logits; layer by layer the top-k
    choice of every row and, on the first `STAGE_ROWS` rows, the router's input
    m, the top-k weights, at `expert_sample`'s tokens the held experts' and the
    gated shared expert's output and the shared gate's values; then layer 0's Gated DeltaNet on the stage rows: at
    `scan_sample`'s positions the normed input a, the projection [q | k | v | z]
    and the convolution's output, the op's q, k, v, g, beta and output at every
    position, the gated norm's output at those positions, and five parameters;
    then layer 3's attention as (rows, ., heads, 256): the rotated queries and
    the outputs at `attention_sample`'s positions, the keys and values of every
    position, and the gate's columns and the gated output at those positions;
    last, the first stage row's residual stream as it enters each layer and the
    final norm, at the logits' positions."""
    import paddle_tpu as fluid
    from paddle_tpu import layers
    from paddle_tpu.models import transformer

    key_heads, value_heads, width, taps = _linear(cfg)
    main, startup, feeds, fetches = transformer.build_causal_lm(
        vocab_size=cfg["vocab_size"], seq_len=job["seq_len"], d_model=cfg["hidden_size"],
        n_heads=cfg["num_attention_heads"], n_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        qk_norm="head", norm_eps=cfg["rms_norm_eps"], rope_theta={"full_attention": dict(theta=float(cfg["rope_theta"]), rotary_dim=_turned(cfg))},
        attention_gate="feature", layer_types=cfg["layer_types"], conv_kernel=taps, linear_key_heads=key_heads,
        linear_value_heads=value_heads, linear_head_dim=width, num_dense_layers=0,
        expert_width=cfg["moe_intermediate_size"], num_experts=cfg["num_routed_experts"], experts_held=held(cfg),
        top_k=cfg["num_experts_per_tok"], norm_topk_prob=cfg["norm_topk_prob"], scoring="softmax", shared_experts=1,
        expert_form=dict(shared_width=cfg["shared_expert_intermediate_size"], shared_gate=True),
        routing_seed=cfg["routing_seed"], tie_embedding=cfg["tie_word_embeddings"], load_balance_coef=0.0,
        router_z_coef=0.0, recompute_layers=job.get("recompute_layers", True), with_optimizer=False,
        dtype=cfg["compute_dtype"])
    block = main.global_block()
    ops = block.ops

    def of(kind):
        return [op for op in ops if op.type == kind]

    def scoped(kind, scope):
        return [op for op in of(kind) if scope in op.attrs.get("op_namescope", "")]

    def product_with(matrix):   # the `mul` op whose right operand is the named matrix
        return next(op for op in of("mul") if op.inputs["Y"] == [matrix])

    with fluid.program_guard(main, startup):
        # The sampled positions' logits from the head's own operands, a second product beside the head and no gather
        # from its output (benchmark/models/kanana.py has why).
        head = next(op for op in ops if fetches["logits"].name in op.output_arg_names)
        assert head.type == "mul" and head.inputs["Y"] == ["lm.head.w"], "the untied head"
        at = logit_sample(job["seq_len"])
        check_rows = np.arange(_runner.CHECK_ROWS)
        pairs = np.stack(np.broadcast_arrays(check_rows[None, :], at[:, None]), -1).astype("int32")
        hidden = layers.gather_nd(block.var(head.inputs["X"][0]), layers.assign(pairs))
        sampled = layers.matmul(hidden, block.var("lm.head.w"))

        def rows(name):   # the stage rows of a variable, as an op of the program
            return layers.slice(block.var(name), axes=[0], starts=[0], ends=[STAGE_ROWS])

        def at_runs(t, runs):   # (rows, sample, ...) of a (rows, L, ...) variable: each run a slice
            parts = [layers.slice(t, axes=[1], starts=[lo], ends=[hi]) for lo, hi in runs]
            return (layers.concat(parts, axis=1) if len(parts) > 1 else parts[0]).name

        tokens = expert_sample(STAGE_ROWS * job["seq_len"])
        token_pairs = layers.assign(np.stack([tokens // job["seq_len"], tokens % job["seq_len"]], -1).astype("int32"))

        def sampled_tokens(name):    # (sample, d) of a (B, L, d) variable
            return layers.gather_nd(rows(name), token_pairs).name

        stages = []
        shared_gates = scoped("sigmoid", "moe_shared_gate")
        for layer, (router, experts) in enumerate(zip(of("moe_router"), of("moe_experts"))):
            routed = experts.outputs["Out"][0]
            joined = next(op for op in ops if op.type == "elementwise_add" and op.inputs["X"][0] == routed)
            stages += [router.outputs["TopKIndex"][0], rows(router.inputs["X"][0]).name, rows(router.outputs["TopKProb"][0]).name,
                       sampled_tokens(routed), sampled_tokens(joined.inputs["Y"][0]),
                       rows(shared_gates[layer].outputs["Out"][0]).name]
        # layer 0's Gated DeltaNet
        scan_runs = _runs(job["seq_len"], SCAN_RUN)
        scan, conv = of("kda")[0], of("short_conv")[0]
        projection = product_with("lm.l0.gdn.qkvz.w")
        stages += [at_runs(rows(projection.inputs["X"][0]), scan_runs), at_runs(rows(projection.outputs["Out"][0]), scan_runs),
                   at_runs(rows(conv.outputs["Out"][0]), scan_runs)]
        stages += [rows(scan.inputs[s][0]).name for s in ("Q", "K", "V", "G", "Beta")] + [rows(scan.outputs["Out"][0]).name]
        stages += [at_runs(rows(product_with("lm.l0.gdn.out.w").inputs["X"][0]), scan_runs)]
        stages += [f"lm.l0.gdn.{n}" for n in _LINEAR_PARAMETERS]
        # the full layer's attention and its gate
        attention_runs = _runs(job["seq_len"], ATTENTION_SAMPLE // 3)
        attention = of("fused_attention")[0]
        heads_major = attention.attr("layout", "bhld") == "bhld"

        def by_position(name):    # the stage rows as (rows, L, H, dh)
            t = rows(name)
            return layers.transpose(t, [0, 2, 1, 3]) if heads_major else t

        gate = scoped("sigmoid", "attention_gate")[0]
        mine = [op for op in ops if op.attrs.get("op_namescope") == gate.attrs["op_namescope"]]
        columns = next(op for op in scoped("slice", "attention_gate"))
        stages += [at_runs(by_position(attention.inputs["Q"][0]), attention_runs), by_position(attention.inputs["K"][0]).name,
                   by_position(attention.inputs["V"][0]).name, at_runs(by_position(attention.outputs["Out"][0]), attention_runs),
                   at_runs(rows(columns.outputs["Out"][0]), attention_runs), at_runs(rows(mine[-1].outputs["Out"][0]), attention_runs)]
        # the residual stream as it enters each layer and the final norm, the stage rows at the logits' positions: where
        # along the depth the program leaves the reference (read, not limited: `stream_errors`)
        entering = [next(op for op in of("rms_norm") if op.inputs.get("Scale") == [name]).inputs["X"][0]
                    for name in [f"lm.l{i}.ln1.w" for i in range(len(cfg["layer_types"]))] + ["lm.final_norm.w"]]
        stream_pairs = layers.assign(np.stack([np.zeros_like(at), at], -1).astype("int32"))
        stages += [layers.gather_nd(rows(name), stream_pairs).name for name in entering]
        rate = layers.learning_rate_scheduler.linear_lr_warmup(
            job["learning_rate"], job["lr_warmup_steps"], job["lr_warmup_start"], job["learning_rate"])
        fluid.optimizer.Adam(learning_rate=rate, beta1=job["adam_beta1"], beta2=job["adam_beta2"],
                             epsilon=job["adam_epsilon"]).minimize(fetches["loss"])
    return (main, startup, feeds, fetches["loss"], [fetches["loss"].name, sampled.name] + stages)


# -- the arithmetic --------------------------------------------------------------

def _kinds(cfg: dict, kind: str) -> int:
    return sum(k == kind for k in cfg["layer_types"])


def _chunk_flops(tokens: int, key_heads: int, value_heads: int, width: int, chunk: int = 64) -> float:
    """Multiply-adds x 2 of the chunked recurrence's forward at a decay of one
    number a head, a chunk's triangles counted as triangles: the keys' and the
    queries' Grams ONCE A KEY HEAD (2 C^2 K together: the decay factors out of
    them), and a value head the triangular solve of [C, K + V] right-hand sides
    (C^2 (K + V)), the chunk's state transition and input (2 C K^2 + 2 C K V),
    the queries' and the output's corrections (C^2 K + C^2 V) and the state's two
    products (2 K^2 V + 2 C K V), for every chunk of `chunk` tokens."""
    C, K = chunk, width
    a_key_head = 2 * C * C * K
    a_value_head = C * C * 2 * K + 2 * C * K * K + 2 * C * K * K + C * C * K + C * C * K + 2 * K * K * K + 2 * C * K * K
    return float(a_key_head * key_heads + a_value_head * value_heads) * tokens / C


def flops_per_sample(cfg: dict, job: dict) -> float:
    """Operations the forward and backward passes of one sequence require
    (matrix multiplications only, 2 per multiply-add, backward twice the
    forward, nothing recomputed): per position a Gated DeltaNet layer's three
    projections (2048 x 12288, 2048 x 64, 4096 x 2048) and the chunked
    recurrence's products (`_chunk_flops`); the full layer's four projections
    (the query's 8192 wide: the gate's columns ride in it) and its two products
    over the causal triangle's allowed pairs, 16 heads of 256; every layer's
    router (512 wide), shared expert and its gate, and the position's held
    experts, 10 x 16 / 512 of one on average (a uniform router's share), three
    matrices each; and the head.  Nothing for the taps, the norms, the
    rotation and the gates' products."""
    seq, d = job["seq_len"], cfg["hidden_size"]
    key_heads, value_heads, width, _ = _linear(cfg)
    heads, kv, head = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    held_share = cfg["num_experts_per_tok"] * cfg["num_experts"] / cfg["num_routed_experts"]
    sparse = (2 * d * cfg["num_routed_experts"] + 3 * 2 * d * cfg["shared_expert_intermediate_size"] + 2 * d
              + held_share * 3 * 2 * d * cfg["moe_intermediate_size"])
    linear = (2 * d * (2 * key_heads + 2 * value_heads) * width + 2 * d * 2 * value_heads + 2 * value_heads * width * d
              + _chunk_flops(1, key_heads, value_heads, width))
    full = 2 * d * 2 * heads * head + 2 * 2 * d * kv * head + 2 * heads * head * d
    forward = 2.0 * d * cfg["vocab_size"] * seq
    forward += _kinds(cfg, "gated_delta_net") * seq * (linear + sparse)
    forward += _kinds(cfg, "full_attention") * (seq * (full + sparse) + 2 * 2.0 * heads * head * seq * (seq + 1) / 2)
    return 3.0 * forward


def kda_scan_flops(cfg: dict, job: dict) -> float:
    """Operations of a training step's `kda` ops, forward and the hand-written
    backward (twice the forward: every product has two transposes), as the
    chunked recurrence NEEDS them at a decay of one number a head and 16 key
    heads feeding 32 value heads: the same work whatever implements it (a form
    that writes the decay out over the channels or repeats the keys does more
    and reads LOWER), nothing for the chunks' terms and states that backward or
    a `recompute_scope` makes again."""
    key_heads, value_heads, width, _ = _linear(cfg)
    tokens = job["batch_per_chip"] * job["seq_len"]
    return 3.0 * _kinds(cfg, "gated_delta_net") * _chunk_flops(tokens, key_heads, value_heads, width)


def kda_scan_bytes(cfg: dict, job: dict) -> float:
    """Bytes those ops have to move at the least: q and k (16 heads) and v and
    the output (32 heads) in bf16, the log decay and beta ONE float32 a value
    head a token each, once forward, and the gradients of the five inputs and
    of the output once backward."""
    key_heads, value_heads, width, _ = _linear(cfg)
    a_token = 2 * key_heads * width * 2 + 2 * value_heads * width * 2 + 2 * value_heads * 4
    return float(2 * a_token * job["batch_per_chip"] * job["seq_len"] * _kinds(cfg, "gated_delta_net"))


def causal_attention_flops(cfg: dict, job: dict) -> float:
    """Operations of a step's full-layer attention (one layer, 16 heads on 2 of
    256): the two products forward and the four backward over the causal
    triangle's allowed pairs, L (L + 1) / 2 a head and sequence; nothing for a
    masked pair a kernel computes anyway, nothing for the scores backward
    computes again and nothing for a forward that a `recompute_scope` makes a
    second time.  The same work whatever implements it."""
    seq = job["seq_len"]
    return float(_kinds(cfg, "full_attention") * 6 * 2.0 * cfg["num_attention_heads"] * cfg["head_dim"]
                 * (seq * (seq + 1) // 2) * job["batch_per_chip"])


def causal_attention_bytes(cfg: dict, job: dict) -> float:
    """Bytes that attention has to move at the least: q, k, v and the output
    once forward and their four gradients once backward, bf16."""
    return float(_kinds(cfg, "full_attention") * 2 * 2 * (2 * cfg["num_attention_heads"] + 2 * cfg["num_key_value_heads"])
                 * cfg["head_dim"] * job["seq_len"] * job["batch_per_chip"])


# -- the reference ---------------------------------------------------------------

#: What tools/chip_qwen3_next_controls.py may put INTO THE REFERENCE, one a run: each makes the reference another
#: function than the program's, and the comparison has to say so by a committed limit.
FAULTS = ("no_decay", "decay_of_next_head", "decay_over_channels", "no_beta", "no_delta_correction", "key_head_by_modulo",
          "no_l2_norm", "no_query_scale", "sigmoid_for_silu", "three_taps", "whole_head_turned", "half_head_turned",
          "theta_1e6", "sigmoid_router", "top_8",
          "no_renormalisation", "no_shared_gate")


def recurrence(q, k, v, g, beta, bf16_state=False, correction=True):
    """o [T, H, V] of the delta rule in the module's docstring over q, k [T, H,
    K] (a key head a value head: the caller has repeated them), v [T, H, V], the
    log decay g and beta [T, H]: ONE TOKEN AT A TIME, a float32 [K, V] state a
    head.  `bf16_state` rounds the state to bf16's eight bits after every token
    (what the scan stage's limit has to refuse; `reduce_precision`, which XLA may
    not take out as it may a pair of casts); `correction` False leaves the delta
    rule's correction out (plain gated linear attention: a control)."""
    import jax
    import jax.numpy as jnp

    def step(S, token):
        q_t, k_t, v_t, g_t, beta_t = token                                    # [H, .]
        S = S * jnp.exp(g_t)[:, None, None]
        seen = jnp.einsum("hkv,hk->hv", S, k_t) if correction else 0.0
        S = S + (beta_t[:, None] * k_t)[:, :, None] * (v_t - seen)[:, None, :]
        if bf16_state:
            S = jax.lax.reduce_precision(S, exponent_bits=8, mantissa_bits=7)
        return S, jnp.einsum("hkv,hk->hv", S, q_t)

    with jax.default_matmul_precision("highest"):
        tokens = tuple(jnp.asarray(t, jnp.float32) for t in (q, k, v, g, beta))
        heads, width = tokens[1].shape[1:]
        _, o = jax.lax.scan(step, jnp.zeros((heads, width, tokens[2].shape[-1]), jnp.float32), tokens)
        return o


def reference(params: dict, batch: dict, cfg: dict, program=None, faults=()):
    """(loss, the sampled positions' logits [rows, sample, vocab], margin [rows,
    L], choice [layers, rows, L, 10], (first held expert,), layer 0's q and k
    after the L2 norm at `attention_sample`'s positions [rows, sample, 16, 128]
    and the full layer's rotated queries [rows, sample, 16, 256] and keys [rows,
    sample, 2, 256] there, the layers' eight float32 matrices as the program
    holds them, stacked by layer, for the comparison's stages, and the first
    row's residual stream as it enters each layer and the final norm at
    `logit_sample`'s positions [layers + 1, sample, 2048]) of `batch` in plain float32 jax.numpy, one sequence
    at a time; `params` maps the program's parameter names to arrays.  No
    kernel, no chunk, no cache and no [L, L] array: see the module's docstring.
    `faults` (names of `FAULTS`) are the controls'."""
    import jax
    import jax.numpy as jnp

    faults = frozenset(faults)
    assert faults <= set(FAULTS), sorted(faults - set(FAULTS))
    kinds, eps = cfg["layer_types"], cfg["rms_norm_eps"]
    key_heads, value_heads, width, taps = _linear(cfg)
    heads, kv_heads, head = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    turned = head if "whole_head_turned" in faults else head // 2 if "half_head_turned" in faults else _turned(cfg)
    theta = 1e6 if "theta_1e6" in faults else float(cfg["rope_theta"])
    top_k = 8 if "top_8" in faults else cfg["num_experts_per_tok"]
    first, n_held = held(cfg)

    def p(name):
        return jnp.asarray(params[name], jnp.float32)

    def rms(x, gain):
        return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps) * p(gain)

    def gated_silu(m, gate, up, down):
        return (jax.nn.silu(m @ gate) * (m @ up)) @ down

    def delta_net(a, pre, seq):
        keys, values = key_heads * width, value_heads * width
        mixed = a @ p(f"{pre}.qkvz.w")                                             # [L, q | k | v | z]
        w = p(f"{pre}.qkv_conv.w")                                                 # [q | k | v columns, taps]: the last tap the token's own
        into = mixed[:, :2 * keys + values]
        c = sum(w[:, j] * jnp.pad(into, ((taps - 1 - j, 0), (0, 0)))[:seq] for j in range(1 if "three_taps" in faults else 0, taps))
        c = jax.nn.silu(c)
        q, k = c[:, :keys].reshape(seq, key_heads, width), c[:, keys:2 * keys].reshape(seq, key_heads, width)
        v = c[:, 2 * keys:].reshape(seq, value_heads, width)
        z = mixed[:, 2 * keys + values:].reshape(seq, value_heads, width)
        if "no_l2_norm" not in faults:
            q, k = (t * jax.lax.rsqrt(jnp.sum(jnp.square(t), -1, keepdims=True) + 1e-6) for t in (q, k))
        if "no_query_scale" not in faults:
            q = q * width ** -0.5
        ba = a @ p(f"{pre}.ba.w")
        beta = jnp.ones((seq, value_heads)) if "no_beta" in faults else jax.nn.sigmoid(ba[:, :value_heads])
        g = -jnp.exp(p(f"{pre}.a_log")) * jax.nn.softplus(ba[:, value_heads:] + p(f"{pre}.dt_bias"))    # [L, 32]: a number a head
        if "no_decay" in faults:
            g = jnp.zeros_like(g)
        if "decay_of_next_head" in faults:
            g = jnp.roll(g, -1, axis=1)
        if "decay_over_channels" in faults:
            g = g / width
        share = value_heads // key_heads
        if "key_head_by_modulo" in faults:       # value head h on key head h mod 16
            spread = lambda t: jnp.tile(t, (1, share, 1))
        else:                                    # value head h on key head h div 2
            spread = lambda t: jnp.repeat(t, share, axis=1)
        o = recurrence(spread(q), spread(k), v, g, beta, correction="no_delta_correction" not in faults)
        o = o * jax.lax.rsqrt(jnp.mean(jnp.square(o), -1, keepdims=True) + eps) * p(f"{pre}.o_norm.w")
        o = o * (jax.nn.sigmoid(z) if "sigmoid_for_silu" in faults else jax.nn.silu(z))
        sample = attention_sample(seq)
        return o.reshape(seq, values) @ p(f"{pre}.out.w"), (q[sample], k[sample])

    def rotate(t, positions):     # [L, H, dh]: the first `turned` features over the pairs (i, i + turned / 2)
        half = turned // 2
        angle = positions[:, None].astype(jnp.float32) * (theta ** (-jnp.arange(half, dtype=jnp.float32) / half))
        cos, sin = jnp.cos(angle)[:, None, :], jnp.sin(angle)[:, None, :]
        a, b = t[..., :half], t[..., half:turned]
        return jnp.concatenate([a * cos - b * sin, b * cos + a * sin, t[..., turned:]], -1)

    def attention(a, pre, positions, seq):
        group = heads // kv_heads
        both = (a @ p(f"{pre}.q.w")).reshape(seq, heads, 2 * head)                 # a head's query features, then its gate's
        q, gate = both[..., :head], both[..., head:]
        k, v = ((a @ p(f"{pre}.{n}.w")).reshape(seq, kv_heads, head) for n in ("k", "v"))
        q, k = (t * jax.lax.rsqrt(jnp.mean(jnp.square(t), -1, keepdims=True) + eps) * p(f"{pre}.{n}_norm.w")
                for t, n in ((q, "q"), (k, "k")))
        q, k = rotate(q, positions), rotate(k, positions)
        grouped = q.reshape(seq, kv_heads, group, head).transpose(1, 2, 0, 3)      # [kv head, its query heads (j div 8), L, dh]
        keys, values = k.transpose(1, 0, 2), v.transpose(1, 0, 2)
        block = min(seq, ATTENTION_BLOCK)
        blocks = []
        for start in range(0, seq, block):       # the queries of a block against the keys at or before them
            end = min(start + block, seq)
            seen = jnp.arange(end)[None, :] <= jnp.arange(start, end)[:, None]

            def a_group(operands, seen=seen):
                qs, ks, vs = operands
                scores = jnp.einsum("gqd,kd->gqk", qs, ks) / np.sqrt(head)
                return jnp.einsum("gqk,kd->gqd", jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), -1), vs)

            blocks.append(jax.lax.map(a_group, (grouped[:, :, start:end], keys[:, :end], values[:, :end])))
        ctx = jnp.concatenate(blocks, 2).reshape(heads, seq, head).transpose(1, 0, 2)    # [L, H, dh]
        sample = attention_sample(seq)
        return (ctx * jax.nn.sigmoid(gate)).reshape(seq, heads * head) @ p(f"{pre}.out.w"), (q[sample], k[sample])

    def one_sequence(row):
        ids, labels, positions = row
        seq = ids.shape[0]
        x = p("lm.tok_emb")[ids]
        margin = jnp.full((seq,), jnp.inf)
        choices, staged, entering = [], {}, []
        for i, kind in enumerate(kinds):
            pre = f"lm.l{i}"
            entering.append(x[logit_sample(seq)])
            a = rms(x, f"{pre}.ln1.w")
            if kind == "gated_delta_net":
                out, qk = delta_net(a, f"{pre}.gdn", seq)
            else:
                out, qk = attention(a, f"{pre}.attn", positions, seq)
            staged.setdefault(kind, qk)
            h = x + out
            m = rms(h, f"{pre}.ln2.w")
            logits = m @ p(f"{pre}.moe.router.w")
            scores = jax.nn.sigmoid(logits) if "sigmoid_router" in faults else jax.nn.softmax(logits, -1)
            ranked = jnp.sort(scores, -1)[:, ::-1]
            kth, after = ranked[:, top_k - 1], ranked[:, top_k]
            chosen = jnp.where(scores >= kth[:, None], scores, 0.0)
            weights = chosen if "no_renormalisation" in faults else chosen / jnp.sum(chosen, -1, keepdims=True)   # over all ten

            def expert(acc, ew, m=m):
                gate, up, down, w_e = ew
                return acc + gated_silu(m, gate, up, down) * w_e[:, None], None

            routed, _ = jax.lax.scan(
                expert, jnp.zeros_like(h),
                (p(f"{pre}.moe.gate.w"), p(f"{pre}.moe.up.w"), p(f"{pre}.moe.down.w"), weights[:, first:first + n_held].T))
            shared = gated_silu(m, *(p(f"{pre}.moe.shared.{n}.w") for n in ("gate", "up", "down")))
            if "no_shared_gate" not in faults:
                shared = shared * jax.nn.sigmoid(m @ p(f"{pre}.moe.shared_gate.w"))
            x = h + routed + shared
            margin = jnp.minimum(margin, (kth - after) / jnp.abs(kth))
            picked = jnp.sort(jax.lax.top_k(scores, top_k)[1], -1)
            choices.append(jnp.pad(picked, ((0, 0), (0, max(cfg["num_experts_per_tok"] - top_k, 0))), constant_values=-1)[:, :cfg["num_experts_per_tok"]])
        entering.append(x[logit_sample(seq)])
        normed = rms(x, "lm.final_norm.w")
        block = min(seq, ATTENTION_BLOCK)

        def ce_of(lo):   # the cross entropies of a block of positions, summed: never [L, vocab] at once
            logp = jax.nn.log_softmax(jax.lax.dynamic_slice_in_dim(normed, lo, block, 0) @ p("lm.head.w"), -1)
            return -jnp.sum(jnp.take_along_axis(logp, jax.lax.dynamic_slice_in_dim(labels, lo, block, 0)[:, None], 1))

        ce_sum = jnp.sum(jax.lax.map(ce_of, jnp.arange(0, seq, block)))
        out = normed[logit_sample(seq)] @ p("lm.head.w")
        return ((out, margin, jnp.stack(choices), ce_sum, jnp.stack(entering)) + staged.get("gated_delta_net", ())
                + staged.get("full_attention", ()))

    with jax.default_matmul_precision("highest"):
        rows = tuple(jnp.asarray(batch[n], jnp.int32) for n in FEEDS)
        out, margin, choice, ce_sum, entering, *staged = jax.lax.map(one_sequence, rows)
        loss = ce_sum.sum() / rows[1].size
        matrices = tuple(jnp.stack([p(f"lm.l{i}.moe.{n}.w") for i in range(len(kinds))]) for n in _MATRICES)
        return (loss, out, margin, choice.transpose(1, 0, 2, 3), jnp.asarray([first], jnp.float32), *staged, *matrices, entering[0])


# -- the comparison ----------------------------------------------------------------

def _rms(t) -> float:
    return float(np.sqrt(np.mean(np.square(t))))


def _sigmoid(x):
    return 1.0 / (1.0 + np.exp(-x))


def _silu(x):
    return x * _sigmoid(x)


def _largest(mine, want) -> float:
    """Largest |error| over the largest |value|."""
    return float(np.abs(mine - want).max() / max(np.abs(want).max(), 1e-30))


def _relative_rms(mine, want) -> float:
    return _rms(mine - want) / max(_rms(want), 1e-30)


def routing_errors(choice, m, top_p, routed, shared, shared_gate, router, gate, up, down, shared_w1, shared_up, shared_down,
                   w_s, first: int) -> dict:
    """One layer's router, held experts and gated shared expert on the program's
    own router input `m` [tokens, d]: its `choice` and `top_p` [tokens, 10]
    against float64 numpy softmax over the float32 `router`, the 10 largest and
    their renormalisation; its experts' `routed` and its gated shared expert's
    `shared` [sample, d] at `expert_sample`'s tokens against float32 numpy (`gate`,
    `up`, `down` hold the experts `first` on); its shared gate's values
    `shared_gate` [tokens, 1] against float64 sigmoid(m w_s) (`shared_w1`,
    `shared_up`, `shared_down` the shared expert's three matrices, `w_s` its
    gate's [d, 1]).  Beside them what the limits have to refuse: the router's
    logits rounded to bf16, a sigmoid for the softmax, the weights without their
    renormalisation, and the shared expert without its gate."""
    tokens, k = choice.shape
    logits = m.astype("f8") @ router.astype("f8")

    def softmax(t):
        e = np.exp(t - t.max(-1, keepdims=True))
        return e / e.sum(-1, keepdims=True)

    def weights(scores, chosen=choice, renormalise=True):
        mine = np.take_along_axis(scores, chosen, -1)
        return mine / mine.sum(-1, keepdims=True) if renormalise else mine

    scores = softmax(logits)
    ranked = np.sort(scores, -1)
    tie = (ranked[:, -k] - ranked[:, -k - 1]) < ROUTER_TIE * np.abs(ranked[:, -k])
    differs = (np.sort(np.argsort(-scores, -1)[:, :k], -1) != np.sort(choice, -1)).any(-1)
    mine = weights(scores)
    low_scores = softmax(_bf16(logits.astype("f4")).astype("f8"))
    low_choice = np.argsort(-low_scores, -1)[:, :k]

    def held_of(c):  # [tokens, held]: which held experts a token chose
        return (c[..., None] == np.arange(first, first + gate.shape[0])).any(-2)

    sample = expert_sample(tokens)
    want = np.zeros((len(sample), m.shape[1]), "f4")
    for e in range(gate.shape[0]):
        row, slot = np.nonzero(choice[sample] == first + e)
        x = m[sample[row]]
        want[row] += (_silu(x @ gate[e]) * (x @ up[e])) @ down[e] * top_p[sample[row], slot][:, None]
    want_gate = _sigmoid(m.astype("f8") @ w_s.astype("f8"))
    x = m[sample]
    plain = (_silu(x @ shared_w1) * (x @ shared_up)) @ shared_down
    want_shared = plain * want_gate[sample].astype("f4")
    return {
        "router_choice_differs": int((differs & ~tie).sum()),
        "router_ties": int((differs & tie).sum()),
        "router_prob_error": float((np.abs(top_p - mine) / mine).max()),
        "router_prob_error_bf16_logits": float((np.abs(weights(low_scores) - mine) / mine).max()),
        "router_prob_error_sigmoid": float((np.abs(weights(_sigmoid(logits)) - mine) / mine).max()),
        "router_prob_error_no_renormalisation": float((np.abs(weights(scores, renormalise=False) - mine) / mine).max()),
        "held_choice_flips_bf16_logits": int((held_of(low_choice) != held_of(choice)).any(-1).sum()),
        "experts_error": _relative_rms(routed, want),
        "shared_gate_error": float((np.abs(shared_gate - want_gate) / want_gate).max()),
        "shared_gate_error_bf16": float((np.abs(_bf16(want_gate.astype("f4")) - want_gate) / want_gate).max()),
        "shared_error": _relative_rms(shared, want_shared),
        "shared_error_no_gate": _relative_rms(plain, want_shared),
    }


def _conv(x, w, lo: int, rounding=lambda t: t, first_tap: int = 0):
    """silu(conv(x)) of one run x [R, d] that starts at position `lo`, filter w
    [d, taps] (the last tap the token's own), float32 numpy; zeros before the
    SEQUENCE's start, so the first taps - 1 rows of a run that starts later lack
    their context and are returned as NaN.  `rounding` is applied to every
    intermediate; `first_tap` 1 leaves the oldest tap out."""
    taps, acc = w.shape[1], None
    for j in range(first_tap, taps):
        term = rounding(np.pad(x, ((taps - 1 - j, 0), (0, 0)))[:x.shape[0]] * w[:, j])
        acc = term if acc is None else rounding(acc + term)
    out = rounding(_silu(acc))
    if lo:
        out[:taps - 1] = np.nan
    return out


@functools.lru_cache(maxsize=2)
def _recurrence_jit(bf16_state):
    import jax

    return jax.jit(functools.partial(recurrence, bf16_state=bf16_state))


def linear_errors(stage, runs, eps: float) -> dict:
    """Layer 0's Gated DeltaNet on the program's own tensors (`build`'s fifteen,
    the first stage row): the convolution, the L2 norms, the decay and beta, the
    scan at the three runs, and the gated norm; beside each, what its limit has
    to refuse."""
    a, mixed, conv, q, k, v, g, beta, out, gated, taps_w, ba_w, a_log, dt_bias, gain = (np.asarray(t) for t in stage)
    a, mixed, conv, gated = (np.asarray(t[0], "f4") for t in (a, mixed, conv, gated))
    out_dtype = out.dtype
    q, k, v, g, beta, out = (np.asarray(t[0], "f4") for t in (q, k, v, g, beta, out))
    seq, key_heads, width = q.shape
    value_heads = v.shape[1]
    keys, values = key_heads * width, value_heads * width
    at = _positions(runs)
    taps_w = np.asarray(taps_w, "f4")
    # the convolution, a run at a time
    want_conv, low_conv, three = (np.concatenate(
        [_conv(mixed[s:e, :2 * keys + values], w, lo, rounding, first_tap)
         for (lo, hi), (s, e) in zip(runs, _spans(runs))]) for w, rounding, first_tap in
        ((taps_w, lambda t: t, 0), (_bf16(taps_w), _bf16, 0), (taps_w, lambda t: t, 1)))
    known = ~np.isnan(want_conv[:, 0])
    found = {"conv_error": _relative_rms(conv[known], want_conv[known]),
             "conv_error_bf16": _relative_rms(low_conv[known], want_conv[known]),
             "conv_error_three_taps": _relative_rms(three[known], want_conv[known])}
    # the L2 norms on the program's own convolution output
    def unit(t, scale=1.0, norm=True):
        t = t.reshape(len(at), key_heads, width)
        return (t / np.sqrt(np.sum(np.square(t), -1, keepdims=True) + 1e-6) if norm else t) * scale

    want_q, want_k = unit(conv[:, :keys], width ** -0.5), unit(conv[:, keys:2 * keys])
    found["unit_error"] = max(_largest(q[at], want_q), _largest(k[at], want_k))
    found["unit_error_no_scale"] = _largest(unit(conv[:, :keys]), want_q)
    found["unit_error_no_norm"] = _largest(unit(conv[:, keys:2 * keys], norm=False), want_k)
    # the decay and the step against float64 on the program's own normed input
    ba = a.astype("f8") @ np.asarray(ba_w, "f8")
    want_beta = _sigmoid(ba[:, :value_heads])
    softplus = np.logaddexp(0.0, ba[:, value_heads:] + np.asarray(dt_bias, "f8"))
    want_g = -np.exp(np.asarray(a_log, "f8")) * softplus
    found["decay_error"] = max(_largest(g[at], want_g), _largest(beta[at], want_beta))
    found["decay_error_next_head"] = _largest(np.roll(want_g, -1, 1), want_g)
    found["decay_error_over_channels"] = _largest(want_g / width, want_g)
    found["decay_error_no_beta"] = _largest(np.ones_like(want_beta), want_beta)
    # the scan against the recurrence, a token at a time, on the program's own operands
    share = value_heads // key_heads
    operands = (np.repeat(q, share, 1), np.repeat(k, share, 1), v, g, beta)
    want = np.asarray(_recurrence_jit(False)(*operands))
    rounded = want if out_dtype == np.float32 else _bf16(want)                   # as the op rounded its own
    low = _bf16(np.asarray(_recurrence_jit(True)(*operands)))
    by_run = [(_rms(out[lo:hi] - rounded[lo:hi]) / max(_rms(want[lo:hi]), 1e-30),
               _rms(low[lo:hi] - rounded[lo:hi]) / max(_rms(want[lo:hi]), 1e-30)) for lo, hi in runs]
    found.update(scan_error=max(e for e, _ in by_run), scan_errors=[e for e, _ in by_run],
                 scan_error_unrounded=_relative_rms(out[at], want[at]),
                 scan_error_bf16_state=min(e for _, e in by_run), scan_decay_mean=float(np.exp(g).mean()))
    # the gated norm on the program's own scan output and z
    z = mixed[:, 2 * keys + values:].reshape(len(at), value_heads, width)
    normed = out[at] / np.sqrt(np.mean(np.square(out[at]), -1, keepdims=True) + eps) * np.asarray(gain, "f4")
    want_gated = (normed * _silu(z)).reshape(len(at), values)
    found["gated_norm_error"] = _relative_rms(gated, want_gated)
    found["gated_norm_error_sigmoid"] = _relative_rms((normed * _sigmoid(z)).reshape(len(at), values), want_gated)
    return found


def _spans(runs):
    """Where each run's rows lie among the concatenated runs' rows."""
    starts = np.cumsum([0] + [hi - lo for lo, hi in runs])
    return list(zip(starts[:-1], starts[1:]))


def attention_errors(q, k, v, out, sample) -> dict:
    """The program's attention output at the sampled queries, `out` [sample, Hq,
    dh], against float32 numpy on its own `q` [sample, Hq, dh] and ALL its keys
    and values `k`, `v` [L, Hkv, dh], each query against the keys at or before
    it; query head j reads key/value head j div (Hq / Hkv): largest |error| over
    the largest |output|; and what the same numpy reads against itself with
    query head j reading key/value head j mod Hkv (the other grouping)."""
    heads, kv_heads = q.shape[1], k.shape[1]
    group = heads // kv_heads
    seen = np.arange(k.shape[0])[None, :] <= np.asarray(sample)[:, None]
    worst = regrouped = largest = 0.0
    for h in range(heads):
        def attend(kv):
            s = np.where(seen, q[:, h] @ k[:, kv].T / np.sqrt(q.shape[-1]), -np.inf)
            e = np.exp(s - s.max(-1, keepdims=True))
            return (e / e.sum(-1, keepdims=True)) @ v[:, kv]

        want = attend(h // group)
        worst = max(worst, float(np.abs(out[:, h] - want).max()))
        regrouped = max(regrouped, float(np.abs(attend(h % kv_heads) - want).max()))
        largest = max(largest, float(np.abs(want).max()))
    largest = max(largest, 1e-30)
    return {"attention_error": worst / largest, "attention_error_other_grouping": regrouped / largest}


def compare(got, want) -> dict:
    """The program's fetched variables (`build`) against the reference's
    outputs (`reference`): the two errors `REFERENCE_RTOL` bounds, the routing
    account, and the stages' errors on the program's own tensors."""
    loss, want_loss = float(np.asarray(got[0]).reshape(-1)[0]), float(want[0])
    want_logits = np.asarray(want[1], "f4")                                   # [rows, sample, vocab]
    logits = np.asarray(got[1], "f4").transpose(1, 0, 2)
    margin, want_choice = np.asarray(want[2]), np.asarray(want[3])
    rows, seq = margin.shape
    tokens, k = margin.size, want_choice.shape[-1]
    first = int(np.asarray(want[4])[0])
    want_streams = np.asarray(want[-1], "f4")                                 # [layers + 1, sample, d], the first row's
    streams, got = [np.asarray(t, "f4") for t in got[len(got) - len(want_streams):]], got[:len(got) - len(want_streams)]
    tail = got[len(got) - _PER_LINEAR - _PER_ATTENTION:]
    layers = [got[i:i + _PER_LAYER] for i in range(2, len(got) - len(tail), _PER_LAYER)]
    matrices = [np.asarray(w, "f4") for w in want[9:9 + len(_MATRICES)]]       # stacked by layer
    n_held = matrices[1].shape[1]
    choice = np.sort(np.stack([np.asarray(layer[0]).reshape(want_choice.shape[1:]) for layer in layers]), -1)
    routed_differently = (choice != want_choice).any(axis=(0, 3))           # [rows, L]

    def held_choice(c):  # [layers, rows, L, held]: which held experts a position chose
        return (c[..., None] == np.arange(first, first + n_held)).any(-2)

    differs = (held_choice(choice) != held_choice(want_choice)).any(axis=(0, 3))
    sampled = differs[:, logit_sample(seq)]
    err = np.abs(logits - want_logits).max(-1)                                # [rows, sample]: a position's worst logit
    stage_rows = np.asarray(layers[0][1]).shape[0]
    staged = stage_rows * seq
    stages = []
    for i, (c, m, p, o, s, g) in enumerate(layers):
        stages.append(routing_errors(
            np.asarray(c).reshape(tokens, k)[:staged], np.asarray(m, "f4").reshape(staged, -1), np.asarray(p, "f4").reshape(staged, k),
            np.asarray(o, "f4"), np.asarray(s, "f4"), np.asarray(g, "f4").reshape(staged, 1), *(w[i] for w in matrices), first))
    scale = max(np.abs(want_logits).max(), 1e-9)
    summed = ("router_choice_differs", "router_ties", "held_choice_flips_bf16_logits")
    least = ("router_prob_error_bf16_logits", "router_prob_error_sigmoid", "router_prob_error_no_renormalisation",
             "shared_gate_error_bf16", "shared_error_no_gate")
    linear = linear_errors(tail[:_PER_LINEAR], _runs(seq, SCAN_RUN), eps=1e-6)
    q, key, v, out, columns, gated = (np.asarray(t, "f4")[0] for t in tail[_PER_LINEAR:])
    at = attention_sample(seq)
    attention = attention_errors(q, key, v, out, at)
    product = out * _sigmoid(columns)
    # the queries and keys against the reference's at the sampled positions: layer 0's after the L2 norm (nothing sparse
    # before them), the full layer's after the norm and the rotation, those at the positions whose held choice agrees
    kept = ~differs[0, at]
    mine_q0, mine_k0 = (np.asarray(tail[i], "f4")[0][at] for i in (3, 4))
    want_q0, want_k0, want_q3, want_k3 = (np.asarray(want[i], "f4")[0] for i in (5, 6, 7, 8))
    off = [np.abs(mine - theirs).max(axis=(1, 2)) / np.abs(theirs).max() for mine, theirs in ((q, want_q3), (key[at], want_k3))]
    off_kept = np.concatenate([e[kept] for e in off]) if kept.any() else np.zeros(1)
    # the stream entering each layer and the final norm, the first row at the logits' positions: each position's error over
    # its norm, the median and the worst of the positions whose held choice agrees in every layer
    agrees = ~differs[0, logit_sample(seq)]
    apart = [np.linalg.norm(mine - theirs, axis=-1) / np.maximum(np.linalg.norm(theirs, axis=-1), 1e-30)
             for mine, theirs in zip(streams, want_streams)]
    return {
        "loss_error": abs(loss - want_loss) / max(abs(want_loss), 1e-6),
        "logit_error": float(np.quantile(err[~sampled], LOGIT_QUANTILE) / scale) if (~sampled).any() else 0.0,
        "logit_error_worst": float(err[~sampled].max(initial=0.0) / scale),
        "logit_error_left_out": float(err[sampled].max(initial=0.0) / scale),
        "tokens": int(tokens),
        "left_out": int(differs.sum()),
        "routed_differently": int(routed_differently.sum()),
        "under_margin": int((margin < ROUTING_MARGIN).sum()),
        "routed_differently_above_margin": int((routed_differently & (margin >= ROUTING_MARGIN)).sum()),
        **{name: (sum if name in summed else min if name in least else max)(stage[name] for stage in stages) for name in stages[0]},
        "held_rows_share": [float(held_choice(c[None]).sum() / (tokens * k)) for c in choice],
        **linear,
        **attention,
        "qk_error": float(max(np.quantile(off_kept, QK_QUANTILE), _largest(mine_q0, want_q0), _largest(mine_k0, want_k0))),
        "qk_error_worst": float(off_kept.max()),
        "qk_error_left_out": float(max(e[~kept].max(initial=0.0) for e in off)),
        "qk_errors": [_largest(mine_q0, want_q0), _largest(mine_k0, want_k0)] + [float(e[kept].max(initial=0.0)) for e in off],
        "stream_errors_median": [float(np.median(e[agrees])) if agrees.any() else 0.0 for e in apart],
        "stream_errors_worst": [float(e[agrees].max(initial=0.0)) for e in apart],
        "gated_error": _largest(gated, product),
        "gated_error_no_gate": _largest(out, product),
        "gated_error_a_head": _largest(out * _sigmoid(columns.mean(-1, keepdims=True)), product),
    }


def reference_error(got, want) -> float:
    """How far the program is from the reference, as `REFERENCE_RTOL` counts
    it: the larger of the loss's and the sampled logits' error, the logits
    over the positions whose held choice agrees.  Positions that chose other
    held experts are left out AND COUNTED (the `reference_routing` line of the
    run), as are those that routed differently across a gap wider than
    `ROUTING_MARGIN`.  A failure (infinite error) is any other limit of
    `failed_limits`."""
    import json

    found = compare(got, want)
    print(json.dumps({"info": "reference_routing", **found,
                      "left_out_share": found["left_out"] / found["tokens"], "failed_limits": failed_limits(found),
                      **{name.lower(): globals()[name] for name in _LIMITS}}), flush=True)
    return float("inf") if failed_limits(found) else max(found["loss_error"], found["logit_error"])


_LIMITS = ("ROUTING_MARGIN", "ROUTED_ABOVE_MARGIN_MAX", "LEFT_OUT_MAX", "LEFT_OUT_LOGIT_MAX", "ROUTER_RTOL", "SHARED_GATE_RTOL", "EXPERTS_RTOL", "SHARED_RTOL",
           "CONV_RTOL", "UNIT_RTOL", "DECAY_RTOL", "SCAN_RTOL", "GATED_NORM_RTOL", "ATTENTION_RTOL", "QK_RTOL", "WORST_POSITION_MAX", "GATED_RTOL",
           "REFERENCE_RTOL")


def failed_limits(found: dict) -> list:
    """The names of the limits that `found` (`compare`'s account) does NOT
    pass, `REFERENCE_RTOL` among them: empty for a sound program."""
    checks = {
        "ROUTED_ABOVE_MARGIN_MAX": found["routed_differently_above_margin"] <= ROUTED_ABOVE_MARGIN_MAX * found["tokens"],
        "LEFT_OUT_MAX": found["left_out"] <= LEFT_OUT_MAX * found["tokens"],
        "LEFT_OUT_LOGIT_MAX": found["logit_error_left_out"] <= LEFT_OUT_LOGIT_MAX,
        "ROUTER_TIE": not found["router_choice_differs"],
        "ROUTER_RTOL": found["router_prob_error"] <= ROUTER_RTOL,
        "SHARED_GATE_RTOL": found["shared_gate_error"] <= SHARED_GATE_RTOL,
        "EXPERTS_RTOL": found["experts_error"] <= EXPERTS_RTOL,
        "SHARED_RTOL": found["shared_error"] <= SHARED_RTOL,
        "CONV_RTOL": found["conv_error"] <= CONV_RTOL,
        "UNIT_RTOL": found["unit_error"] <= UNIT_RTOL,
        "DECAY_RTOL": found["decay_error"] <= DECAY_RTOL,
        "SCAN_RTOL": found["scan_error"] <= SCAN_RTOL,
        "GATED_NORM_RTOL": found["gated_norm_error"] <= GATED_NORM_RTOL,
        "ATTENTION_RTOL": found["attention_error"] <= ATTENTION_RTOL,
        "QK_RTOL": found["qk_error"] <= QK_RTOL,
        "WORST_POSITION_MAX": max(found["logit_error_worst"], found["qk_error_worst"]) <= WORST_POSITION_MAX,
        "GATED_RTOL": found["gated_error"] <= GATED_RTOL,
        "REFERENCE_RTOL": max(found["loss_error"], found["logit_error"]) <= REFERENCE_RTOL,
    }
    return [name for name, passed in checks.items() if not passed]
