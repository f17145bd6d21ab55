"""Laguna-XS.2 causal-LM training: how the benchmark builds it through the
framework, a plain float32 reference of the same architecture, and the
operations one sequence needs.

Architecture: poolside/Laguna-XS.2 `config.json` (`model_type: laguna`); what it
does not give is listed in the configuration file's `assumed`.  A layer, eps
1e-6, no biases, no q/k-norm, with x the layer's input [tokens, 2048] and H its
count of QUERY heads (`num_attention_heads_per_layer`: 48 where `layer_types`
says full_attention, 64 where it says sliding_attention):

    attention a = rms(x; ln1);  q = a Wq (H heads of 128), k = a Wk, v = a Wv (8 heads of 128; query head j reads
              key/value head j div (H / 8): groups of 6 in a full layer, of 8 in a window layer)
    positions window layers: q, k <- RoPE over the whole head, (t_i, t_i+64) <- (t_i cos w - t_i+64 sin w,
              t_i+64 cos w + t_i sin w), w = position . 1e4^(-i/64), float32.
              full layers: the FIRST 64 features of a head alone turn (i with i + 32, i < 32), the last 64 pass; the
              angle is position . f_i, YaRN's blend f_i = (1 - r_i) b^(-i/32) + r_i b^(-i/32) / 64, b = 5e5,
              r_i = clip((i - 5) / (16 - 5), 0, 1) (low = floor(d(64)) = 5, high = ceil(d(1)) = 16,
              d(n) = 64 ln(4096 / (2 pi n)) / (2 ln b)); cos and sin times `attention_factor` 1.4158883
    rule      full: key j for query i where j <= i;  window: where i - 512 < j <= i.  scores at 128^-0.5, float32 softmax
    gate      g = sigmoid(a Wg), Wg [2048, H], ONE number a head a token, float32;  h = x + concat_h(g_h . o_h) Wo
    dense     m = rms(h; ln2);  y = h + W2( silu(W1 m) * (W3 m) ),  width 8192                          (layer 0)
    sparse    s = sigmoid_f32(m Wr) over 256;  S = the 8 largest of s;  w_e = 2.5 s_e / sum_{e' in S} s_e'
              y = h + sum_{e in S and e in HELD} w_e . W2_e( silu(W1_e m) * (W3_e m) ) + shared(m),
              experts of width 512, HELD = {0..15}, shared(m) one gated SiLU of 512 that EVERY token passes, unweighted
    loss      mean over every position of CE( rms(y_L; final_norm) W_head, the next token ), the head untied

The reference computes YaRN's table from the configuration's keys by its own
lines, the rotation as the halves above, the attention as explicit scores under
the rule made from positions, a key/value head's query heads and
`ATTENTION_BLOCK` queries at a time against the keys the rule can allow them
(never an [L, L] array a head), the gate, the top 8 by a sort, the experts as a
loop over the held ones, and the loss `ATTENTION_BLOCK` positions at a time.  It
shares no code with `paddle_tpu`.

Departures of the program under test
(`paddle_tpu.models.transformer.build_causal_lm`) from the published model,
which the reference follows so that the two compute the same function (the
configuration file's `departures` is this list, word for word):

  * five of the 40 layers, the published layers 0 to 4: the one leading dense layer (layer 0, a full-attention layer) and the four that follow it, one whole period of the pattern (window, window, window, full), the floor of four; with the leading layer counted two of the five layers are full for one in four published; further layers lie on further chips as pipeline stages;
  * 16 of the 256 routed experts of every sparse layer, experts 0 to 15: this chip's share of a layer whose experts are split over 16 chips (32 held, the share of eight chips, did not fit: the 8-row check's program beside the optimizer's state planned 20.2 GB); the router keeps its 256 outputs, its top 8 and its renormalisation over all eight chosen, the shared expert is computed here as on every chip, and what the 240 absent experts would have added is left out of the layer's output, in the program and in the reference alike, with no exchange standing in for the 15 absent chips;
  * 12544 of the 100352 vocabulary rows, in the embedding and in the untied head: one chip's eighth of the rows, the guide's floor; token ids and labels are drawn from the slice and the loss is over the slice;
  * every layer is a `recompute_scope`: backward keeps a layer's input and what `plan_kept` finds room for and makes the rest of the layer again, the sparse layers' routing with it; the numbers are the same either way (tests/test_laguna.py holds the gradients equal to the last bit);
  * Adam for AdamW (the framework has no AdamW), learning rate 1e-4 reached by a linear warm-up over the first 200 steps from 1e-6, betas 0.9 / 0.95, epsilon 1e-8, no decay and no auxiliary loss;
  * weights are random, N(0, 0.02) from the run's seed, norm gains 1, but for the token embedding and the routers' matrices, which come from the configuration's `routing_seed` and not from the run's: they decide which experts a token meets, as a checkpoint's do, and which of the sixteen chips this is;
  * token ids are uniform random with no padding and no document boundaries (a row is one whole sequence of 16384 positions 0 to 16383, a sixteenth of `max_position_embeddings` and four times the unstretched 4096), every position is a label (the next token), so the cross entropy starts near ln(12544).
"""
from __future__ import annotations

import numpy as np

from benchmark.models import lfm2 as _decoder
from benchmark.models.kimi_linear import shared_errors   # one gated SiLU on the program's own m: the same stage
from benchmark.models.smallthinker import attention_errors   # a rule made from positions, grouped heads, the window's edge
from benchmark.runners import train as _runner

FEEDS = ("ids", "labels", "pos_ids")

#: Every limit below was set from this cell's own readings at the published
#: widths and 16384 tokens (my chip runs, PR 65: nine sound runs of the cell's
#: comparison, a seed each, 8 x 16384 positions; PERF.md section 6 has the
#: table): what the sound program reads, and what the same comparison reads with
#: a fault put in (tools/chip_laguna_controls.py, seed 3650000017), the limit
#: between the two with room on both sides.  The routing margin is OLMoE's
#: argument (benchmark/models/olmoe.py: top-k is discontinuous and the program's
#: router reads a bf16 stream): no sound run routed differently above it.
ROUTING_MARGIN = _decoder.ROUTING_MARGIN
#: Sampled positions whose HELD choice differs in some layer are left out of the
#: logit comparison and counted over all positions: 5.16% to 5.62% sound (8
#: chosen of 256, 16 held, four sparse layers).  The least a fault that has no
#: stage of its own reads: the shared expert left out 51.1% (half of a head
#: turned in layer 1 28.6%; the factor 2.5 left out 12.4%, which `ROUTER_RTOL`
#: holds too).  A softmax router (7.7%) and a bf16 router (5.39%) it does not
#: tell: `ROUTER_RTOL` does.  1.8x over the most seen, 1.24x under the factor's
#: reading, 2.9x under the least fault that is its own.
LEFT_OUT_MAX = 0.10
#: ... and how far a left-out position's logits may be off, over the largest
#: |reference logit|: one held expert's output more or less, 0.075 to 0.099
#: sound.  A sanity bound at 4.5x the most seen (a NaN fails it), NOT a limit
#: between two readings.
LEFT_OUT_LOGIT_MAX = 0.45
#: The larger of the loss's relative error (1e-6 to 5e-6) and the sampled
#: logits' error over the largest |reference logit|, on the positions that chose
#: alike: 1.33e-2 to 1.46e-2 sound (bf16 activations over float32 masters
#: through five layers and a bf16 head).  The least any fault to the stream
#: reads: the factor 2.5 left out 0.102, half a head turned in layer 1 0.158, a
#: softmax router 0.188, the shared expert left out 0.306, the gate of the
#: neighbouring head 0.649.  2.7x over the most seen, 2.55x under the least.
REFERENCE_RTOL = 4e-2
ROUTER_TIE = _decoder.ROUTER_TIE
#: The router on the program's own input m, the stage row: the eight weights'
#: largest relative error against float64 numpy 7.8e-7 to 1.0e-6, and no token
#: whose eight are not float64's across a gap wider than `ROUTER_TIE`.  With the
#: router's float32 matrix rounded to bf16 IN THE PROGRAM 1.08e-3 and 745 tokens
#: routed elsewhere (its logits rounded to bf16 in numpy 1.16e-3 to 1.17e-3);
#: the factor 2.5 left out 0.60, a softmax for the sigmoid 4.35.  30x over the
#: most seen, 36x under the least fault.
ROUTER_RTOL = 3e-5
#: The held experts on the program's own m, choice and weights, every
#: `EXPERTS_SAMPLE`-th token of the stage row: root-mean-square error over the
#: root-mean-square output against float32 numpy 4.68e-3 to 4.75e-3 (bf16
#: operands into float32 accumulation); with the running sums held in bf16,
#: eight terms at a time, 3.05e-2 to 3.06e-2 (numpy).  2.5x over the one, 2.5x
#: under the other.
EXPERTS_RTOL = 1.2e-2
#: The shared expert's gated SiLU of 512 on the same m and tokens: 4.24e-3 to
#: 4.26e-3 (the same three bf16 products).  The held experts' limit; a layer
#: without it is `REFERENCE_RTOL`'s and `LEFT_OUT_MAX`'s (0.306, 51.1%).
SHARED_RTOL = 1.2e-2
#: The stage row: the program's own tensors of the stages are compared on the
#: first `STAGE_ROWS` of the 8 check rows (the slices are ops of the program, so
#: that 8 rows of every stage's operands never lie in the chip's memory beside
#: the optimizer's state).
STAGE_ROWS = 1
#: The attention of layer 0 (full, groups of 6) and of layer 1 (window 512,
#: groups of 8) on the program's own q, k, v for `ATTENTION_SAMPLE` queries of
#: the stage row and every head, each against the keys its rule allows (made
#: here from positions), float32 scores: largest error over the largest
#: |output|, the worse layer's: 2.67e-3 to 3.63e-3 in layer 0, 2.70e-3 to 3.37e-3
#: in layer 1.  What it has to refuse: query head j on key/value head j mod 8 IN
#: THE PROGRAM 1.46 and 1.57 (numpy 1.38 to 1.46); a window of 513 reads 2.33e-2
#: here and of 511 4.99e-2 (the next limit's).  3.3x over the most seen, 1.9x
#: under the least.  It does NOT tell bf16 scores (1.7e-3 to 2.7e-3 against
#: itself, under the sound reading, as in the other cells: PERF.md section 7,
#: defect 13c).
ATTENTION_RTOL = 1.2e-2
ATTENTION_SAMPLE = _decoder.ATTENTION_SAMPLE
#: THE WINDOW'S EDGE, layer 1's stage: how much of what a window one key short,
#: and one key long, would add to the output the program's output holds
#: (`smallthinker.attention_errors`: the error along each fault's own direction,
#: pooled over the sampled queries past the window's length and the 64 heads):
#: -2.9e-4 to 2.1e-4 sound, both coefficients (3.1e-3 the other one under a
#: window off by a key); a window of 511 in the program reads 1.000 (missing), of
#: 513 1.000 (extra).  160x over the one, 2x under the other.
WINDOW_EDGE_MAX = 0.5
#: ... and those layers' queries and keys themselves, after the rotation, at the
#: sampled positions against the reference's (which computes YaRN's table and
#: rotates on its own), over the largest |value|: 4.7e-3 to 6.1e-3 in layer 0
#: (YaRN, half the head, the factor on cos and sin), 1.12e-2 to 1.32e-2 in layer
#: 1 (plain, the whole head; a layer's bf16 roundings lie before it).  The least
#: a fault reads: `attention_factor` left out 0.293 in layer 0 (1 - 1/1.4159),
#: YaRN's blend left out 1.83, the whole head turned in layer 0 1.87, half of it
#: in layer 1 1.94, the thetas exchanged 2.04 and 1.71, the angles in bf16 1.66
#: and 1.78.  3.0x over the most seen, 7.3x under the least fault.
QK_RTOL = 4e-2
#: THE GATE, layers 0 and 1, the sampled positions of the stage row: the
#: program's g [sample, H] against float64 numpy sigmoid(a Wg) on the program's
#: own normed input a, largest relative error: 3.4e-6 to 4.4e-6 sound (the
#: projection at the highest precision: at the chip's default it read 3.87e-3,
#: this PR's first run).  What it has to refuse: the same float64 gate rounded to
#: bf16, 3.82e-3 to 3.88e-3 (numpy); the gate's logits and value rounded to bf16
#: IN THE PROGRAM 8.8e-3; no gate at all (g = 1) 25.5.  29x over the most seen,
#: 29x under the least.
GATE_RTOL = 1.3e-4
#: ... and the gate's PRODUCT: the program's gated output [sample, H, 128]
#: against float32 numpy g_h . o_h on the program's own g and attention output,
#: over the largest |value|: 2.85e-3 to 3.31e-3 sound (one rounding to bf16).
#: The gate of head j on head j + 1 IN THE PROGRAM reads 0.617 (numpy 0.59 to
#: 0.79).  3.6x over the most seen, 51x under the fault.
GATED_RTOL = 1.2e-2
#: THE REFERENCE ITSELF, the first row: its first layer's queries at the sampled
#: positions, each head's 64 PASSED features (a float32 angle at position 16383
#: is itself 3e-4 of a turned feature from float64's), against float64 numpy of
#: the same product on its own normed input, and its first layer's attention
#: output there against float64 numpy on its own q, k and v: the larger of the
#: two, over the largest |value|.  Float32 products at the highest precision
#: read 9.0e-7 to 1.3e-6; the attention's two products at the chip's default
#: precision (bf16 operands, the nearest precision below) 4.39e-3, which
#: nothing else here tells apart: the program rounds as much itself.  79x over
#: the one, 44x under the other.
REFERENCE_SELF_RTOL = 1e-4
#: ... its attention's on the sampled queries among the first `SELF_KEYS` positions, which see those keys alone.
SELF_KEYS = 2048
#: Queries a block of the reference's attention and positions a block of its loss.
ATTENTION_BLOCK = 1024
#: The layers whose attention, queries, keys and gate are staged: the first full layer and the first window layer.
STAGE_LAYERS = (0, 1)

logit_sample = _decoder.logit_sample


def _sample_runs(positions: int) -> list:
    """The runs of positions whose queries the attention's and the gate's stages
    read, [first, past the last): three of `ATTENTION_SAMPLE` / 3, at the
    sequence's start, middle and end.  RUNS, so that the program takes them by
    plain slices of the stage row and the window's edge is met by neighbouring
    queries too; positions past the window's length are in two of the three."""
    run = min(ATTENTION_SAMPLE // 3, positions)
    starts = sorted({0, max((positions - run) // 2, 0), positions - run})
    return [(lo, lo + run) for lo in starts]


def attention_sample(positions: int):
    """The positions of `_sample_runs`, in order."""
    return np.unique(np.concatenate([np.arange(lo, hi) for lo, hi in _sample_runs(positions)]))
make_batch = _decoder.make_batch
_bf16 = _decoder._bf16


def expert_sample(tokens: int):
    """The tokens of the stage rows whose held and shared experts' outputs are
    compared: every `EXPERTS_SAMPLE`-th, as `lfm2.stage_errors` takes them."""
    return np.arange(0, tokens, max(tokens // _decoder.EXPERTS_SAMPLE, 1))


def held(cfg: dict) -> tuple:
    """(first, count) of the routed experts this chip holds."""
    return (cfg["experts_held_first"], cfg["num_experts"])


def _windows(cfg: dict) -> list:
    """A layer's window in keys, None where it attends to every earlier key."""
    return [cfg["sliding_window"] if kind == "sliding_attention" else None for kind in cfg["layer_types"]]


def _sparse_layers(cfg: dict) -> list:
    return [i for i, kind in enumerate(cfg["mlp_layer_types"]) if kind == "sparse"]


def _dense_layers(cfg: dict) -> int:
    """The leading dense layers; a dense layer after a sparse one is not this family's."""
    sparse = _sparse_layers(cfg)
    dense = sparse[0] if sparse else len(cfg["mlp_layer_types"])
    assert sparse == list(range(dense, len(cfg["mlp_layer_types"]))), "the dense layers lead"
    return dense


def heads_by_kind(cfg: dict) -> dict:
    """{a layer's kind: its count of query heads}, from the two per-layer lists, which have to agree a kind."""
    by_kind = {}
    for kind, heads in zip(cfg["layer_types"], cfg["num_attention_heads_per_layer"]):
        assert by_kind.setdefault(kind, heads) == heads, f"{kind} layers of {by_kind[kind]} and of {heads} query heads"
    return by_kind


def _rotary(cfg: dict, kind: str) -> dict:
    """`layers.rotary_embedding`'s description of a layer kind's rotary embedding, from `rope_parameters`."""
    from paddle_tpu.models import transformer

    stated = cfg["rope_parameters"][kind]
    rope = dict(theta=float(stated["rope_theta"]), rotary_dim=int(cfg["head_dim"] * stated["partial_rotary_factor"]))
    if stated["rope_type"] == "yarn":
        rope.update(inv_freq=transformer.yarn_frequencies(
            stated["rope_theta"], rope["rotary_dim"], stated["factor"], stated["original_max_position_embeddings"],
            stated["beta_fast"], stated["beta_slow"]), scale=stated["attention_factor"])
    else:
        assert stated["rope_type"] == "default", stated["rope_type"]
    return rope


def build(cfg: dict, job: dict):
    """(main, startup, feed variables by name, loss variable, the variables the
    reference is compared on) of the train program, as a user of the framework
    gets it: `build_causal_lm` with every layer a recomputed segment (a job may
    say `recompute_layers` false: the tests', which hold the two alike), then
    the learning rate's warm-up and Adam from the traffic file.  The compared
    variables: loss, the sampled positions' logits; sparse layer by sparse layer
    the top-k choice of every row and, on the first `STAGE_ROWS` rows, the
    router's input m and the top-k weights, at `expert_sample`'s tokens of them
    the held experts' and the shared expert's output, and the layer's seven
    float32 matrices (router; gate, up, down of the held experts; of the shared
    one) as the program holds them: fetched, so that no copy of them stays on
    the chip through the run; then, for each of `STAGE_LAYERS`, as (rows, .,
    heads, 128): the rotated queries and the attention's outputs at
    `attention_sample`'s positions, the keys and values of every position, and
    the gate's stage at those positions: the normed input a, the gate's values,
    the gated output, and the gate's matrix."""
    import paddle_tpu as fluid
    from paddle_tpu import layers
    from paddle_tpu.models import transformer

    kinds = cfg["layer_types"]
    main, startup, feeds, fetches = transformer.build_causal_lm(
        vocab_size=cfg["vocab_size"], seq_len=job["seq_len"], d_model=cfg["hidden_size"],
        n_heads=heads_by_kind(cfg), n_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        qk_norm=None, norm_eps=cfg["rms_norm_eps"], rope_theta={kind: _rotary(cfg, kind) for kind in set(kinds)},
        layer_types=kinds, sliding_window=cfg["sliding_window"], attention_gate=cfg["gating"],
        num_dense_layers=_dense_layers(cfg), dense_width=cfg["intermediate_size"],
        expert_width=cfg["moe_intermediate_size"], num_experts=cfg["num_routed_experts"], experts_held=held(cfg),
        top_k=cfg["num_experts_per_tok"], norm_topk_prob=cfg["norm_topk_prob"], scoring=cfg["scoring_func"],
        routed_scaling_factor=cfg["moe_routed_scaling_factor"], shared_experts=1,
        expert_form=dict(shared_width=cfg["shared_expert_intermediate_size"]), routing_seed=cfg["routing_seed"],
        tie_embedding=cfg["tie_word_embeddings"], load_balance_coef=0.0, router_z_coef=0.0,
        recompute_layers=job.get("recompute_layers", True), with_optimizer=False, dtype=cfg["compute_dtype"])
    block = main.global_block()
    ops = block.ops

    def of(kind):
        return [op for op in ops if op.type == kind]

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

        runs = _sample_runs(job["seq_len"])

        def at_sample(t):   # (rows, sample, ...) of a (rows, L, ...) variable: `attention_sample`'s runs, each a slice
            return layers.concat([layers.slice(t, axes=[1], starts=[lo], ends=[hi]) for lo, hi in runs], axis=1).name

        tokens = expert_sample(STAGE_ROWS * job["seq_len"])
        token_pairs = layers.assign(np.stack([tokens // job["seq_len"], tokens % job["seq_len"]], -1).astype("int32"))

        def sampled_tokens(name):    # (sample, d) of a (B, L, d) variable
            return layers.gather_nd(rows(name), token_pairs).name

        stages = []
        for layer, router, experts in zip(_sparse_layers(cfg), of("moe_router"), of("moe_experts")):
            routed = experts.outputs["Out"][0]
            joined = next(op for op in ops if op.type == "elementwise_add" and op.inputs["X"][0] == routed)
            stages += [router.outputs["TopKIndex"][0], rows(router.inputs["X"][0]).name,
                       rows(router.outputs["TopKProb"][0]).name, sampled_tokens(routed), sampled_tokens(joined.inputs["Y"][0])]
            stages += [f"lm.l{layer}.moe.{n}.w" for n in _MATRICES]
        attentions = of("fused_attention")
        gates = [op for op in of("sigmoid") if "attention_gate" in op.attrs.get("op_namescope", "")]
        for i in STAGE_LAYERS:
            attention = attentions[i]
            heads_major = attention.attr("layout", "bhld") == "bhld"

            def by_position(name, heads_major=heads_major):    # the stage rows as (rows, L, H, dh)
                t = rows(name)
                return layers.transpose(t, [0, 2, 1, 3]) if heads_major else t

            stages += [at_sample(by_position(attention.inputs["Q"][0])), by_position(attention.inputs["K"][0]).name,
                       by_position(attention.inputs["V"][0]).name, at_sample(by_position(attention.outputs["Out"][0]))]
            # the gate's ops, in the order `transformer._head_gate` appends them: the first reads a, the last made the product
            mine = [op for op in ops if op.attrs.get("op_namescope") == gates[i].attrs["op_namescope"]]
            stages += [at_sample(rows(mine[0].inputs["X"][0])), at_sample(rows(gates[i].outputs["Out"][0])),
                       at_sample(by_position(mine[-1].outputs["Out"][0])), f"lm.l{i}.attn.gate.w"]
        rate = layers.learning_rate_scheduler.linear_lr_warmup(
            job["learning_rate"], job["lr_warmup_steps"], job["lr_warmup_start"], job["learning_rate"])
        fluid.optimizer.Adam(learning_rate=rate, beta1=job["adam_beta1"], beta2=job["adam_beta2"],
                             epsilon=job["adam_epsilon"]).minimize(fetches["loss"])
    return (main, startup, feeds, fetches["loss"], [fetches["loss"].name, sampled.name] + stages)


#: a sparse layer's matrices, as `build` fetches them after the layer's five stage tensors
_MATRICES = ("router", "gate", "up", "down", "shared.gate", "shared.up", "shared.down")
_PER_LAYER, _PER_STAGE = 5 + len(_MATRICES), 8


# -- the arithmetic --------------------------------------------------------------

def _pairs(seq: int, window) -> int:
    """(query, key) pairs a layer's rule allows among `seq` positions."""
    w = seq if window is None else min(window, seq)
    return w * (w + 1) // 2 + (seq - w) * w


def flops_per_sample(cfg: dict, job: dict) -> float:
    """Operations the forward and backward passes of one sequence require
    (matrix multiplications only, 2 per multiply-add, backward twice the
    forward, nothing recomputed): per position a layer's four attention
    projections at its own count of query heads and its gate's; the dense
    layer's three products at 8192; a sparse layer's router, its shared expert
    and the position's held experts, HALF of one on average (8 chosen x 16 held of 256,
    a uniform router's share), three matrices each; each attention's two
    products over the pairs its rule ALLOWS (the causal triangle for a full
    layer, the band of 512 for a window layer); and the head.  Nothing for the
    rotations, the norms and the gates' products."""
    seq, d, kv, head = job["seq_len"], cfg["hidden_size"], cfg["num_key_value_heads"], cfg["head_dim"]
    held_share = cfg["num_experts_per_tok"] * cfg["num_experts"] / cfg["num_routed_experts"]
    dense = _dense_layers(cfg)
    forward = 2.0 * d * cfg["vocab_size"] * seq
    for i, (heads, window) in enumerate(zip(cfg["num_attention_heads_per_layer"], _windows(cfg))):
        per_position = 2 * (2 * d * heads * head + 2 * d * kv * head) + 2 * d * heads
        if i < dense:
            per_position += 3 * 2 * d * cfg["intermediate_size"]
        else:
            per_position += (2 * d * cfg["num_routed_experts"] + 3 * 2 * d * cfg["shared_expert_intermediate_size"]
                             + held_share * 3 * 2 * d * cfg["moe_intermediate_size"])
        forward += seq * per_position + 2 * 2.0 * heads * head * _pairs(seq, window)
    return 3.0 * forward


def _attention_layers(cfg: dict, sliding: bool) -> list:
    return [(heads, window) for heads, window in zip(cfg["num_attention_heads_per_layer"], _windows(cfg))
            if (window is not None) == sliding]


def _attention_flops(cfg: dict, job: dict, sliding: bool) -> float:
    return float(sum(6 * 2.0 * heads * cfg["head_dim"] * _pairs(job["seq_len"], window)
                     for heads, window in _attention_layers(cfg, sliding)) * job["batch_per_chip"])


def _attention_bytes(cfg: dict, job: dict, sliding: bool) -> float:
    return float(sum(2 * 2 * (2 * heads + 2 * cfg["num_key_value_heads"]) * cfg["head_dim"]
                     for heads, _ in _attention_layers(cfg, sliding)) * job["seq_len"] * job["batch_per_chip"])


def window_attention_flops(cfg: dict, job: dict) -> float:
    """Operations of a step's window attentions (the three window layers): the
    two products forward and the four backward over the pairs the rule ALLOWS
    (the band of 512 keys), 64 heads on 8 of 128; nothing for a masked pair a
    kernel computes anyway, nothing for the scores backward computes again and
    nothing for a forward that a `recompute_scope` makes a second time.  The
    same work whatever implements it."""
    return _attention_flops(cfg, job, True)


def window_attention_bytes(cfg: dict, job: dict) -> float:
    """Bytes those attentions have to move at the least: q, k, v and the output
    once forward and their four gradients once backward, bf16."""
    return _attention_bytes(cfg, job, True)


def causal_attention_flops(cfg: dict, job: dict) -> float:
    """`window_attention_flops` for the two full layers, 48 heads on 8: the same
    six products over the causal triangle's allowed pairs, L (L + 1) / 2 a head
    and sequence."""
    return _attention_flops(cfg, job, False)


def causal_attention_bytes(cfg: dict, job: dict) -> float:
    """`window_attention_bytes` for the full layers."""
    return _attention_bytes(cfg, job, False)


# -- the reference ---------------------------------------------------------------

def rotary_table(stated: dict, head_dim: int):
    """(the float64 frequencies a turned pair, how many leading features of a
    head turn, the factor on cos and sin) of one entry of the configuration's
    `rope_parameters`: theta's own frequencies b^(-2i/r), or under `rope_type`
    "yarn" the blend of them with their `factor`-th between the pair that makes
    `beta_fast` turns over the original context and the one that makes
    `beta_slow`, as the public implementation of that type computes it
    (`truncate` at its default: the two bounds floored and ceiled)."""
    turned = int(head_dim * stated["partial_rotary_factor"])
    base = float(stated["rope_theta"])
    own = base ** (-np.arange(0, turned, 2, dtype=np.float64) / turned)
    if stated["rope_type"] != "yarn":
        return own, turned, 1.0

    def pair_of(turns):
        return turned * np.log(stated["original_max_position_embeddings"] / (turns * 2 * np.pi)) / (2 * np.log(base))

    low, high = max(np.floor(pair_of(stated["beta_fast"])), 0), min(np.ceil(pair_of(stated["beta_slow"])), turned - 1)
    if low == high:
        high += 0.001
    stretched = np.clip((np.arange(turned // 2, dtype=np.float64) - low) / (high - low), 0.0, 1.0)
    return own * (1.0 - stretched) + own / stated["factor"] * stretched, turned, float(stated["attention_factor"])


def rotate(t, positions, table, xp=None):
    """The rotary embedding of t [L, H, dh] at `positions` [L] under `table` =
    (frequencies, turned, factor) (`rotary_table`): the first `turned` features
    turn over the pairs (i, i + turned / 2), the rest pass; in `xp` (jax.numpy,
    or numpy for a float64 check)."""
    if xp is None:
        import jax.numpy as xp
    frequencies, turned, factor = table
    half = turned // 2
    angle = positions[:, None].astype(t.dtype) * xp.asarray(frequencies, t.dtype)
    cos, sin = xp.cos(angle)[:, None, :] * factor, xp.sin(angle)[:, None, :] * factor
    first, second = t[..., :half], t[..., half:turned]
    return xp.concatenate([first * cos - second * sin, second * cos + first * sin, t[..., turned:]], -1)


def allowed(queries, keys, window):
    """May the query at position `queries` see the key at position `keys`?  The
    causal rule, and under a `window` the last `window` keys, the query's own
    among them.  Broadcasts; numpy or jax."""
    seen = keys <= queries
    return seen if window is None else seen & (keys > queries - window)


def reference(params: dict, batch: dict, cfg: dict, program=None, precision: str = "highest",
              attention_precision: str = None):
    """(loss, the sampled positions' logits [rows, sample, vocab], margin [rows,
    L], choice [sparse layers, rows, L, 8], (first held expert, window, the
    scaling factor), for each of `STAGE_LAYERS` the rotated queries [rows, H,
    sample, 128] and keys [rows, 8, sample, 128] at `attention_sample`'s
    positions, and for `reference_self_error` the first row's first layer: its
    normed input at those positions [sample, d], its query matrix, its queries
    [48, sample, 128], its first `SELF_KEYS` keys and values [8, ., 128] and its
    attention's output at those positions [48, sample, 128]) of `batch` in
    plain float32 jax.numpy, one sequence at a time; `params` maps the program's
    parameter names to arrays.  No kernel, no cache and no [L, L] array: see the
    module's docstring.  `precision` is the float32 products': "highest" is the
    reference; tools/chip_laguna_controls.py asks for `attention_precision`
    "default" (bf16 operands on the chip, the nearest precision below) in the
    attention's two products alone, to show that the comparison tells it."""
    import jax
    import jax.numpy as jnp

    depth, eps, head, kv_heads = len(cfg["layer_types"]), cfg["rms_norm_eps"], cfg["head_dim"], cfg["num_key_value_heads"]
    top_k, scaling = cfg["num_experts_per_tok"], cfg["moe_routed_scaling_factor"]
    first, n_held = held(cfg)
    windows, sparse = _windows(cfg), _sparse_layers(cfg)
    tables = {kind: rotary_table(stated, head) for kind, stated in cfg["rope_parameters"].items() if isinstance(stated, dict)}

    def p(name):
        return jnp.asarray(params[name], jnp.float32)

    def rms(x, gain):
        return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps) * p(gain)

    def gated_silu(m, gate, up, down):
        return (jax.nn.silu(m @ gate) * (m @ up)) @ down

    def attention(a, pre, positions, window, table, heads):
        seq, group = a.shape[0], heads // kv_heads
        q = rotate((a @ p(f"{pre}.q.w")).reshape(seq, heads, head), positions, table)
        k = rotate((a @ p(f"{pre}.k.w")).reshape(seq, kv_heads, head), positions, table)
        v = (a @ p(f"{pre}.v.w")).reshape(seq, kv_heads, head)
        q = q.reshape(seq, kv_heads, group, head).transpose(1, 2, 0, 3)           # [kv head, its query heads, L, dh]
        k, v = k.transpose(1, 0, 2), v.transpose(1, 0, 2)                          # [kv head, L, dh]
        block = min(seq, ATTENTION_BLOCK)
        blocks = []
        for start in range(0, seq, block):        # the queries of a block against the keys its rule can allow them
            end = min(start + block, seq)
            lo = 0 if window is None else max(start - window + 1, 0)
            seen = allowed(jnp.arange(start, end)[:, None], jnp.arange(lo, end)[None, :], window)

            def a_group(operands, seen=seen):
                qs, ks, vs = operands
                with jax.default_matmul_precision(attention_precision or precision):
                    scores = jnp.einsum("gqd,kd->gqk", qs, ks) / np.sqrt(head)
                    return jnp.einsum("gqk,kd->gqd", jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), -1), vs)

            blocks.append(jax.lax.map(a_group, (q[:, :, start:end], k[:, lo:end], v[:, lo:end])))
        ctx = jnp.concatenate(blocks, 2).reshape(heads, seq, head)                 # head = kv head x group + its query head
        gate = jax.nn.sigmoid(a @ p(f"{pre}.gate.w"))                              # [L, H]: one number a head a token
        sample = attention_sample(seq)
        mixed = (ctx * gate.T[:, :, None]).transpose(1, 0, 2).reshape(seq, heads * head) @ p(f"{pre}.out.w")
        return (mixed, (q.reshape(heads, seq, head)[:, sample], k[:, sample]),
                (k[:, :SELF_KEYS], v[:, :SELF_KEYS], ctx[:, sample]))

    def one_sequence(row):
        ids, labels, positions = row
        seq = ids.shape[0]
        x = p("lm.tok_emb")[ids]
        margin = jnp.full((seq,), jnp.inf)
        choices, staged = [], {}
        first_input = rms(x, "lm.l0.ln1.w")[attention_sample(seq)]      # what the first q projection reads, sampled
        for i in range(depth):
            pre = f"lm.l{i}"
            out, qk, rest = attention(rms(x, f"{pre}.ln1.w"), f"{pre}.attn", positions, windows[i],
                                      tables[cfg["layer_types"][i]], cfg["num_attention_heads_per_layer"][i])
            h = x + out
            if i in STAGE_LAYERS:
                staged[i] = qk + (rest if i == 0 else ())
            m = rms(h, f"{pre}.ln2.w")
            if i not in sparse:
                x = h + gated_silu(m, *(p(f"{pre}.ffn.{n}.w") for n in ("gate", "up", "down")))
                continue
            scores = jax.nn.sigmoid(m @ p(f"{pre}.moe.router.w"))
            ranked = jnp.sort(scores, -1)[:, ::-1]
            kth, after = ranked[:, top_k - 1], ranked[:, top_k]
            chosen = jnp.where(scores >= kth[:, None], scores, 0.0)
            weights = chosen / jnp.sum(chosen, -1, keepdims=True) * scaling      # over all eight, held or not

            def expert(acc, ew, m=m):
                gate, up, down, w_e = ew
                return acc + gated_silu(m, gate, up, down) * w_e[:, None], None

            routed, _ = jax.lax.scan(
                expert, jnp.zeros_like(h),
                (p(f"{pre}.moe.gate.w"), p(f"{pre}.moe.up.w"), p(f"{pre}.moe.down.w"), weights[:, first:first + n_held].T))
            x = h + routed + gated_silu(m, *(p(f"{pre}.moe.shared.{n}.w") for n in ("gate", "up", "down")))
            margin = jnp.minimum(margin, (kth - after) / jnp.abs(kth))
            choices.append(jnp.sort(jax.lax.top_k(scores, top_k)[1], -1))
        normed = rms(x, "lm.final_norm.w")
        block = min(seq, ATTENTION_BLOCK)

        def ce_of(lo):   # the cross entropies of a block of positions, summed: never [L, vocab] at once
            logp = jax.nn.log_softmax(jax.lax.dynamic_slice_in_dim(normed, lo, block, 0) @ p("lm.head.w"), -1)
            return -jnp.sum(jnp.take_along_axis(logp, jax.lax.dynamic_slice_in_dim(labels, lo, block, 0)[:, None], 1))

        ce_sum = jnp.sum(jax.lax.map(ce_of, jnp.arange(0, seq, block)))
        out = normed[logit_sample(seq)] @ p("lm.head.w")
        return (out, margin, jnp.stack(choices), ce_sum) + tuple(t for i in STAGE_LAYERS for t in staged[i]) + (first_input,)

    with jax.default_matmul_precision(precision):
        rows = tuple(jnp.asarray(batch[n], jnp.int32) for n in FEEDS)
        out, margin, choice, ce_sum, q0, k0, keys, values, ctx, *later, first_input = jax.lax.map(one_sequence, rows)
        loss = ce_sum.sum() / rows[1].size
        window = next((w for w in windows if w is not None), 0)
        return ((loss, out, margin, choice.transpose(1, 0, 2, 3), jnp.asarray([first, window, scaling], jnp.float32), q0, k0)
                + tuple(later) + (first_input[0], p("lm.l0.attn.q.w"), q0[0], keys[0], values[0], ctx[0]))


# -- the comparison ----------------------------------------------------------------

def gate_errors(a, g, out, gated, matrix) -> dict:
    """One layer's head gate on the program's own tensors at the sampled
    positions (see `GATE_RTOL`, `GATED_RTOL`): its values `g` [rows, sample, H]
    against float64 numpy sigmoid(a Wg) on its own normed input `a` [rows,
    sample, d] and float32 `matrix` [d, H], largest relative error; its gated
    output `gated` [rows, sample, H, dh] against float32 numpy g_h . o_h on its
    own g and attention output `out`, over the largest |value|.  Beside each,
    the nearest fault: the float64 gate rounded to bf16, the gate of head j on
    head j + 1, no gate at all."""
    want = 1.0 / (1.0 + np.exp(-(a.astype("f8") @ matrix.astype("f8"))))
    product = out * g[..., None]
    largest = max(float(np.abs(product).max()), 1e-30)
    return {
        "gate_error": float((np.abs(g - want) / want).max()),
        "gate_error_bf16": float((np.abs(_bf16(want.astype("f4")) - want) / want).max()),
        "gated_error": float(np.abs(gated - product).max() / largest),
        "gated_error_next_head": float(np.abs(out * np.roll(g, 1, -1)[..., None] - product).max() / largest),
        "gated_error_no_gate": float(np.abs(out - product).max() / largest),
    }


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
    first, window, scaling = (float(n) for n in np.asarray(want[4]))
    first, window = int(first), int(window) or None
    tail = got[len(got) - _PER_STAGE * len(STAGE_LAYERS):]
    layers = [got[i:i + _PER_LAYER] for i in range(2, len(got) - len(tail), _PER_LAYER)]
    n_held = np.asarray(layers[0][6]).shape[0]
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

    stages, shared = [], []
    for c, m, p, o, s, router, gate, up, down, *shared_matrices in layers:
        m = np.asarray(m, "f4").reshape(staged, -1)
        stages.append(_decoder.stage_errors(
            np.asarray(c).reshape(tokens, k)[:staged], m, np.asarray(p, "f4").reshape(staged, k), spread(o),
            np.zeros(np.asarray(router).shape[1], "f4"), *(np.asarray(w, "f4") for w in (router, gate, up, down)),
            first, 0.0, scaling))
        shared.append(shared_errors(m, spread(s), *(np.asarray(w, "f4") for w in shared_matrices)))
    scale = max(np.abs(want_logits).max(), 1e-9)
    summed = ("bias_moved", "router_choice_differs", "router_ties", "held_choice_flips_bf16_logits")
    at = attention_sample(seq)
    attention, qk, gate_stage = [], [], []
    for where, layer in enumerate(STAGE_LAYERS):
        q, key, v, out, a, g, gated, matrix = (np.asarray(t, "f4") for t in tail[_PER_STAGE * where:_PER_STAGE * (where + 1)])
        attention.append(attention_errors(q, key, v, out, at, window if where else None))
        want_q, want_k = (np.asarray(want[5 + 2 * where + j], "f4")[:stage_rows].transpose(0, 2, 1, 3) for j in (0, 1))
        qk.append(float(max(np.abs(mine - theirs).max() / np.abs(theirs).max()
                            for mine, theirs in ((q, want_q), (key[:, at], want_k)))))
        gate_stage.append(gate_errors(a, g, out, gated, matrix))
    # the yardstick's own precision, the first row's first layer: its queries' passed features against float64 on its own
    # normed input, and its attention's output at the sampled queries against float64 on its own q, k and v
    base = 5 + 2 * len(STAGE_LAYERS)
    first_q = np.asarray(want[base + 2], "f8").transpose(1, 0, 2)                        # [sample, H, dh]
    exact_q = (np.asarray(want[base], "f8") @ np.asarray(want[base + 1], "f8")).reshape(first_q.shape)
    passed = slice(first_q.shape[-1] // 2, None)           # at most half of a head turns: the rest is the product itself
    own_keys, own_values = (np.asarray(want[base + j], "f8").transpose(1, 0, 2)[None] for j in (3, 4))
    near = at < own_keys.shape[1]                                                        # the queries that see those keys alone
    own = attention_errors(first_q[None, near], own_keys, own_values,
                           np.asarray(want[base + 5], "f8").transpose(1, 0, 2)[None, near], at[near], None)
    worse = ("gate_error", "gated_error")
    return {
        "reference_self_error": max(float(np.abs(first_q[..., passed] - exact_q[..., passed]).max()
                                          / np.abs(exact_q[..., passed]).max()), own["attention_error"]),
        "loss_error": abs(loss - want_loss) / max(abs(want_loss), 1e-6),
        "logit_error": float(err[~sampled].max(initial=0.0) / scale),
        "logit_error_left_out": float(err[sampled].max(initial=0.0) / scale),
        "tokens": int(tokens),
        "left_out": int(differs.sum()),
        "routed_differently": int(routed_differently.sum()),
        "under_margin": int((margin < ROUTING_MARGIN).sum()),
        "routed_differently_above_margin": int((routed_differently & (margin >= ROUTING_MARGIN)).sum()),
        **{name: (sum if name in summed else max)(stage[name] for stage in stages) for name in stages[0]},
        "held_rows_share": [float(held_choice(c[None]).sum() / (tokens * k)) for c in choice],
        "shared_error": max(shared),
        "attention_error": max(a["attention_error"] for a in attention),
        "attention_error_bf16_scores": min(a["attention_error_bf16_scores"] for a in attention),
        "attention_error_other_grouping": min(a["attention_error_other_grouping"] for a in attention),
        "window_edge_missing": attention[-1].get("window_edge_missing", 0.0),
        "window_edge_extra": attention[-1].get("window_edge_extra", 0.0),
        "attention_errors": [a["attention_error"] for a in attention],
        "qk_error": max(qk), "qk_errors": qk,
        **{name: (max if name in worse else min)(stage[name] for stage in gate_stage) for name in gate_stage[0]},
        "gate_errors": [stage["gate_error"] for stage in gate_stage],
    }


def reference_error(got, want) -> float:
    """How far the program is from the reference, as `REFERENCE_RTOL` counts
    it: the larger of the loss's and the sampled logits' error, the logits
    over the positions whose held choice agrees.  Positions that chose other
    held experts are left out AND COUNTED (the `reference_routing` line of the
    run).  A failure (infinite error) is any other limit of `failed_limits`."""
    import json

    found = compare(got, want)
    print(json.dumps({"info": "reference_routing", **found,
                      "left_out_share": found["left_out"] / found["tokens"],
                      "routing_margin": ROUTING_MARGIN, "left_out_max": LEFT_OUT_MAX,
                      "left_out_logit_max": LEFT_OUT_LOGIT_MAX, "router_rtol": ROUTER_RTOL,
                      "experts_rtol": EXPERTS_RTOL, "shared_rtol": SHARED_RTOL, "attention_rtol": ATTENTION_RTOL,
                      "window_edge_max": WINDOW_EDGE_MAX, "qk_rtol": QK_RTOL, "gate_rtol": GATE_RTOL,
                      "gated_rtol": GATED_RTOL, "reference_self_rtol": REFERENCE_SELF_RTOL}),
          flush=True)
    return float("inf") if failed_limits(found) else max(found["loss_error"], found["logit_error"])


def failed_limits(found: dict) -> list:
    """The names of the limits that `found` (`compare`'s account) does NOT
    pass, `REFERENCE_RTOL` among them: empty for a sound program."""
    checks = {
        "ROUTING_MARGIN": not found["routed_differently_above_margin"],
        "LEFT_OUT_MAX": found["left_out"] <= LEFT_OUT_MAX * found["tokens"],
        "LEFT_OUT_LOGIT_MAX": found["logit_error_left_out"] <= LEFT_OUT_LOGIT_MAX,
        "ROUTER_TIE": not found["router_choice_differs"],
        "ROUTER_RTOL": found["router_prob_error"] <= ROUTER_RTOL,
        "EXPERTS_RTOL": found["experts_error"] <= EXPERTS_RTOL,
        "SHARED_RTOL": found["shared_error"] <= SHARED_RTOL,
        "ATTENTION_RTOL": found["attention_error"] <= ATTENTION_RTOL,
        "WINDOW_EDGE_MAX": max(abs(found["window_edge_missing"]), abs(found["window_edge_extra"])) <= WINDOW_EDGE_MAX,
        "QK_RTOL": found["qk_error"] <= QK_RTOL,
        "GATE_RTOL": found["gate_error"] <= GATE_RTOL,
        "GATED_RTOL": found["gated_error"] <= GATED_RTOL,
        "REFERENCE_RTOL": max(found["loss_error"], found["logit_error"]) <= REFERENCE_RTOL,
        "REFERENCE_SELF_RTOL": found["reference_self_error"] <= REFERENCE_SELF_RTOL,
    }
    return [name for name, passed in checks.items() if not passed]
