"""NVIDIA-Nemotron-3-Super-120B-A12B causal-LM training: how the benchmark builds
it through the framework, a plain float32 reference of the same architecture,
and the operations one sequence needs.

Architecture: nvidia/NVIDIA-Nemotron-3-Super-120B-A12B-BF16 `config.json`
(`model_type: nemotron_h`); what it does not give is listed in the configuration
file's `assumed`.  Every layer has ONE part, y = x + part(a) with a = rms(x;
gain, eps 1e-5); `hybrid_override_pattern` says which (`M`, `E`, `*`):

    M  Mamba-2    [z, xBC, dt] = split(a W_in)                  4096 -> 8192 + 10240 + 128   (128 heads x 64; 10240 = 8192 + 2 x 8 groups x 128)
                  xBC = silu(conv_4(xBC) + b_conv)              depthwise, causal, zeros before the row's start
                  [xs, B, C] = split(xBC)                       xs [128 heads, 64], B and C [8 groups, 128]; head h reads group h // 16
                  dt = softplus(dt + dt_bias)  [128], float32;  A = -exp(A_log)  ONE scalar a head
                  h_t = exp(dt_t A) h_{t-1} + dt_t xs_t (x) B_t     state [64, 128] a head, float32, h_0 = 0
                  y_t = h_t C_t + D xs_t                        D one scalar a head
                  part(a) = rms_group(y * silu(z); gain 8192, groups of 1024) W_out           8192 -> 4096
    *  attention  32 query heads on 2 key/value heads of 128, no bias, causal, scale 128^-0.5, NO positions
    E  LatentMoE  s = sigmoid(a W_r) [512] float32; the top 22 of s + bias; w = s[chosen] / (sum + 1e-20) x 5.0
                  u = a W_dn                                    4096 -> 1024
                  r = sum_{k chosen and HELD} w_k relu(u W1_e)^2 W2_e       W1 [1024, 2688], W2 [2688, 1024], HELD = {0..31}
                  part(a) = r W_up + relu(a S1)^2 S2            W_up 1024 -> 4096; S1 [4096, 5376], S2 [5376, 4096]
    loss          mean CE( rms(y_L) H^T, next token ), H untied

The reference computes the scan as the recurrence above, token by token
(`lax.scan` over t with a [128, 64, 128] state), never the chunked form the
program's op uses; the convolution as four shifted multiply-adds; attention as
a dense [L, L] softmax, two heads at a time; the experts as a loop over the 32
held with a mask (no sort, no grouped product); the head and the loss a block of
positions at a time.

Departures of the program under test
(`paddle_tpu.models.transformer.build_causal_lm`) from the published model,
which the reference follows so that the two compute the same function (the
configuration file's `departures` is this list, word for word):

  * 11 of the 88 layers, the published layers 31 to 41, `MEMEM*EMEME`: one whole period of the pattern at the published 5 : 5 : 1, opening on a Mamba-2 layer as the model does, the attention behind three state-space layers; the other 77 layers lie on further hosts as pipeline stages;
  * 32 of the 512 routed experts of every expert layer, experts 0 to 31: this host's share of a layer whose experts are split over 16 four-chip hosts; the router keeps its 512 outputs, its top 22 and its renormalisation over all 22 chosen (x 5.0), the shared expert and the two latent projections are computed here as on every host, and what the 480 absent experts would have added is left out of the layer's output, in the program and in the reference alike, with no exchange standing in for the 15 absent hosts;
  * 16384 of the 131072 vocabulary rows, in the embedding and in the untied head: a host's eighth of the rows, the guide's floor; token ids and labels are drawn from the slice and the loss is over the slice;
  * the multi-token-prediction module is left out (`num_nextn_predict_layers` 1 -> 0): `config.json` gives its layer pattern (`*E`) and its count, not how the trunk's state and the next token's embedding are joined, normed and weighed into the loss; it sits behind layer 87 with the head's last stage, on another host;
  * the expert bias (`e_score_correction_bias`) is a buffer that the published training updates by a load-balancing rule `config.json` does not give: here it is drawn once, N(0, 0.02) from the configuration's `routing_seed`, and never updated, so that it changes choices (the run counts how many) and is not a zero added;
  * Adam for AdamW (the framework has no AdamW), learning rate 1e-4 reached by a linear warm-up over the first 200 steps from 1e-6, betas 0.9 / 0.95, epsilon 1e-8, no decay and no auxiliary loss;
  * weights are random, N(0, 0.02) from the run's seed (no `rescale_prenorm_residual`), the convolution's taps U(-0.5, 0.5) (`nn.Conv1d`'s default at 4 taps a channel, which Mamba-2's code leaves them at: with taps of the weights' size the state's part of a mixer's output is a hundredth of the skip D x and no check sees the scan), its bias 0, norm gains 1, A = 1 .. 16 evenly over the 128 heads (A_log its logarithm: Mamba-2 draws A U(1, 16)), D = 1, dt_bias the inverse softplus of a log-uniform draw on [`time_step_min`, `time_step_max`] = [1e-3, 1e-1] (above `time_step_floor` 1e-4 by construction);
  * token ids are uniform random over the slice with no padding and no document boundaries (a row is one whole sequence, the state starts at zero with it), every position is a label (the next token), so the cross entropy starts near ln(16384).
"""
from __future__ import annotations

import functools

import numpy as np

from benchmark.models import jamba as _hybrid
from benchmark.models import lfm2 as _decoder

FEEDS = ("ids", "labels")

#: The larger of the loss's relative error and the sampled logits' error over
#: the largest |reference logit|, on the positions whose held choice agrees in
#: every expert layer (bf16 activations over float32 masters through 11 one-part
#: layers and a bf16 head).  Readings: PERF.md section 6, PR 60.
REFERENCE_RTOL = 4e-2
LOGIT_SAMPLE = _decoder.LOGIT_SAMPLE
#: A position whose HELD choice differs from the float32 reference's in some
#: expert layer (a bf16 stream moves the gap between the 22nd and the 23rd of
#: 512 scores) carries one held expert's output more or less.  `reference` is not
#: told the program's choice (it is handed parameters and ids, nothing the
#: program fetched), so these positions are COMPARED ALL THE SAME, under a limit
#: of their own: their logits over the largest |reference logit| (sound 0.042 to
#: 0.059 over nine seeds of the cell | 0.352 the norm before the gate, 0.736 relu
#: for relu^2), and their queries and keys at the attention over the largest
#: |value| (sound 0.028 to 0.046 | 0.276, 0.700).  PERF.md section 6, PR 60.
OTHER_CHOICE_RTOL = 0.12
#: ... and the share of all positions that may stand under that wider limit:
#: sound 14.4 to 18.1% over nine seeds | 92.4% the norm before the gate, 99.9%
#: relu for relu^2 (a fault that moves the stream moves every near-tied choice).
OTHER_CHOICE_MAX = 0.40
#: A position that routed differently from the reference across a gap wider
#: than this (relative, between the 22nd and 23rd biased score) is a fault.
ROUTING_MARGIN = 2.0 ** -4
#: The stage rows: the program's own tensors of the stages below are compared
#: on the first `STAGE_ROWS` of the 8 check rows; the slices are ops of the
#: program, so that 8 rows of every stage's operands never lie in a chip's
#: memory beside the state.
STAGE_ROWS = 2
#: ... of which the router and expert stages read the first `STAGE_TOKENS`
#: positions (the router's input is 4096 wide), the convolution the first
#: `STAGE_CHANNELS` of its 10240 channels (it is depthwise), and the scan the
#: first `STAGE_HEADS` heads, which are the first two GROUPS' (16 heads a group:
#: a head that reads another group's B shows).
STAGE_TOKENS = 2048
STAGE_CHANNELS = 1024
STAGE_HEADS = 32
#: THE SCAN STAGE: `ssd_scan` against the token-by-token float32 recurrence ON
#: THE PROGRAM'S OWN xs, dt, B, C (the stage rows and heads), by three numbers.
#: `scan_state_error`: the FIRST Mamba-2 layer's float32 STATE after the last
#: token against the recurrence's, root-mean-square difference over the
#: root-mean-square state.  Nothing rounds it, so it reads the two computations'
#: own difference: sound 2.0e-5 to 7.1e-5 over six seeds of the cell | 1.52e-3 to
#: 1.60e-3 the three float32 products at the matrix unit's default precision,
#: 1.64e-3 to 1.67e-3 the state kept in bf16 where a chunk hands it on, 5.0e-2 a
#: bf16 cumulative decay, 1.3 a wrong group (tools/chip_nemotron_controls.py on
#: the cell's four chips, seeds 3600000701 to 703; PERF.md section 6, PR 60):
#: 4.9x over the most a sound run read, 4.3x under the least fault.  The LAST
#: layer's state is read beside it and not limited: five seeds read 3.4e-5 to
#: 7.4e-5 and a sixth 2.8e-4; against the recurrence written out in float64 on
#: the host BOTH stand about 1e-4 off there, the op (4.0e-5 to 1.2e-4 over three
#: seeds) and the float32 recurrence (9.6e-5 to 1.3e-4): PERF.md section 6.
SCAN_STATE_RTOL = 3.5e-4
#: `scan_error`: the FIRST Mamba-2 layer's output against the recurrence's
#: ROUNDED to bf16 as the op rounds its own.  Rounded alike, two float32
#: computations differ only where their last bits cross a rounding boundary, so
#: the reading grows as the ROOT of the difference (a bf16 carried state reads
#: 3.8e-4 to 4.4e-4, twice the sound reading: the state's own limit tells it,
#: not this one); the chunked form and the recurrence reach a decay by different
#: roads (one exp of a difference of sums; a product of exps a token), and the
#: chip's own `exp` sets the floor.  Sound 1.59e-4 to 2.44e-4 over fourteen seeds
#: | 1.49e-3 to 1.66e-3 default-precision products over six, 1.6e-2 a bf16
#: cumulative decay, 0.45 a wrong group: 2.5x of room on either side.
SCAN_RTOL = 6e-4
#: ... and `scan_error_deep` the LAST Mamba-2 layer's (behind nine layers, where
#: the operands' range is the stream's and moves with the seed): sound 2.30e-4 to
#: 4.95e-4 over fourteen seeds | 1.36e-3 to 1.54e-3 default-precision products,
#: 1.4e-2 a bf16 cumulative decay, 0.40 a wrong group.  1.8x over the most a
#: sound run read, 1.5x under the least fault; each of those faults is also the
#: first layer's two limits', with 2.5 and four times of room.
SCAN_DEEP_RTOL = 9e-4
#: The first Mamba-2 layer's convolution on the program's own in-projection
#: (bf16), float32 taps and bias: root-mean-square error over the
#: root-mean-square output (the op computes in float32 and rounds once; with
#: every intermediate in bf16 it is printed beside).
CONV_RTOL = 2.6e-3
#: The router on its OWN input (bf16 rows, float32 weights): the program's 22
#: weights against float64 numpy's for the program's choice, the largest
#: relative error; a choice that differs from float64's on the same input
#: outside a tie of `ROUTER_TIE` is a fault whatever this reads.
ROUTER_RTOL = 3e-4
ROUTER_TIE = 1e-4
#: The latent expert sum on the program's own u, choice and weights, every
#: `EXPERTS_SAMPLE`-th stage token, against float32 numpy over the float32
#: matrices: root-mean-square error over the root-mean-square sum (two bf16
#: products with float32 accumulation and one rounding of the hidden rows).
#: What it has to refuse: `relu` for `relu^2`, and bf16 running sums.
EXPERTS_RTOL = 1.2e-2
EXPERTS_SAMPLE = 512
#: The attention on the program's own q (32 heads), k and v (2 heads) for
#: `ATTENTION_SAMPLE` queries of the stage rows against all keys before them,
#: float32 scores: largest error over the largest |output|.  Sound 3.1e-3 to
#: 4.6e-3 over nine seeds | 0.767 a query that also sees the key after it, 1.38 a
#: query head that reads the other key/value head; bf16 scores (5.4e-3 to 6.7e-3)
#: it does not tell (PERF.md section 7, defect 13c).
ATTENTION_RTOL = 2e-2
ATTENTION_SAMPLE = _decoder.ATTENTION_SAMPLE
#: ... and that layer's queries and keys at the sampled positions against the
#: reference's, over the largest |value|, at the positions whose held choice
#: agrees in the layers before: five one-part layers' bf16 roundings lie before
#: them.  What it has to refuse: the gated norm norming BEFORE it gates.
QK_RTOL = 4e-2

logit_sample = _decoder.logit_sample
attention_sample = _decoder.attention_sample
router_biases = _decoder.router_biases       # N(0, `expert_bias_std`) from `routing_seed` + the layer's index: buffers, no parameters
_bf16, _sigmoid = _decoder._bf16, _decoder._sigmoid
make_batch = _hybrid.make_batch              # uniform random ids over the vocabulary's slice, the next token every position's label
conv_errors, _rms = _hybrid.conv_errors, _hybrid._rms   # the plain short convolution with its bias: Jamba's stage, the same op

KINDS = {"M": "mamba2", "*": "full_attention", "E": "feed_forward"}


def layer_types(cfg: dict) -> list:
    """`hybrid_override_pattern` written out: `M` a Mamba-2 mixer, `*` an
    attention, `E` the expert layer, each a layer of one part."""
    return [KINDS[c] for c in cfg["hybrid_override_pattern"]]


def held(cfg: dict) -> tuple:
    return (cfg["experts_held_first"], cfg["n_routed_experts"])


def _mamba2(cfg: dict) -> dict:
    return dict(heads=cfg["mamba_num_heads"], head_dim=cfg["mamba_head_dim"], state=cfg["ssm_state_size"],
                groups=cfg["n_groups"], chunk=cfg["chunk_size"])


def _mixer(cfg: dict) -> dict:
    """... and how the mixer is built: the taps' own initialisation beside them."""
    return dict(_mamba2(cfg), taps_bound=cfg["conv_taps_bound"])


def _expert_form(cfg: dict) -> dict:
    assert cfg["mlp_hidden_act"] == "relu2"
    return dict(activation="relu2", gated=False, latent_size=cfg["moe_latent_size"],
                shared_width=cfg["moe_shared_expert_intermediate_size"])


def build(cfg: dict, job: dict):
    """(main, startup, feed variables by name, loss variable, the variables the
    reference is compared on) of the train program, as a user of the framework
    gets it: `build_causal_lm` with `layer_types` written out from the pattern,
    every layer a recomputed segment of one part, the ZeRO-3 hints over the
    traffic file's mesh on BOTH programs (the state is born split), then the
    learning rate's warm-up and Adam.  The compared variables: loss, the sampled
    positions' logits; expert layer by expert layer the top-22 choice (all
    rows) and, on the stage rows and tokens, the router's input, the 22 weights,
    the latent u, the held experts' sum in the latent, and the router's bias;
    then on the stage rows the first Mamba-2 layer's convolution's input and
    output (`STAGE_CHANNELS` channels), the first and the last Mamba-2 layer's
    xs, dt, B, C, output and last state (`STAGE_HEADS` heads, their groups), and
    the attention's q, k, v and output."""
    import paddle_tpu as fluid
    from paddle_tpu import layers
    from paddle_tpu.models import transformer

    kinds = cfg["layer_types"]
    assert kinds == layer_types(cfg), "layer_types is the published pattern written out"
    main, startup, feeds, fetches = transformer.build_causal_lm(
        vocab_size=cfg["vocab_size"], seq_len=job["seq_len"], d_model=cfg["hidden_size"],
        n_heads=cfg["num_attention_heads"], n_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        qk_norm=None, rotary=False, norm_eps=cfg["norm_eps"], layer_types=kinds, conv_kernel=cfg["conv_kernel"],
        mamba2=_mixer(cfg), expert_width=cfg["moe_intermediate_size"],
        num_experts=cfg["num_routed_experts"], experts_held=held(cfg), top_k=cfg["num_experts_per_tok"],
        norm_topk_prob=cfg["norm_topk_prob"], scoring="sigmoid", routed_scaling_factor=float(cfg["routed_scaling_factor"]),
        norm_topk_eps=cfg["norm_topk_eps"], shared_experts=cfg["n_shared_experts"],
        expert_bias=(cfg["expert_bias_std"], cfg["routing_seed"]), expert_form=_expert_form(cfg),
        tie_embedding=cfg["tie_word_embeddings"], load_balance_coef=0.0, router_z_coef=0.0, recompute_layers=True,
        with_optimizer=False, dtype=cfg["compute_dtype"])
    if "mesh_shape" in job:
        mesh = fluid.parallel.make_mesh(tuple(job["mesh_shape"]), tuple(job["mesh_axes"]))
        axis = job["mesh_axes"][0]
        rules = transformer.fsdp_rules(main, axis, int(mesh.shape[axis]))
        for program in (main, startup):
            fluid.parallel.shard_parameters(program, rules, mesh=mesh, batch_axis=axis)
    block = main.global_block()
    m2 = _mamba2(cfg)
    per_group = m2["heads"] // m2["groups"]
    stage_groups = -(-STAGE_HEADS // per_group)

    def of(kind):
        return [op for op in block.ops if op.type == kind]

    with fluid.program_guard(main, startup):
        by_position = layers.transpose(fetches["logits"], [1, 0, 2])
        sampled = layers.gather(by_position, layers.assign(logit_sample(job["seq_len"]).astype("int32")))

        def rows(name, last=None, tokens=None):   # the stage rows (tokens, last-axis entries) of a variable, as an op of the program
            var = block.var(name)
            axes, ends = [0], [STAGE_ROWS]
            if tokens is not None:
                axes, ends = axes + [1], ends + [min(tokens, job["seq_len"])]
            if last is not None:
                axes, ends = axes + [len(var.shape) - 1], ends + [last]
            return layers.slice(var, axes=axes, starts=[0] * len(axes), ends=ends).name

        stages = []
        for router, experts in zip(of("moe_router"), of("moe_experts")):
            stages += [router.outputs["TopKIndex"][0], rows(router.inputs["X"][0], tokens=STAGE_TOKENS),
                       rows(router.outputs["TopKProb"][0], tokens=STAGE_TOKENS), rows(experts.inputs["X"][0], tokens=STAGE_TOKENS),
                       rows(experts.outputs["Out"][0], tokens=STAGE_TOKENS), router.inputs["Bias"][0]]
        conv = of("short_conv")[0]
        stages += [rows(conv.inputs["X"][0], STAGE_CHANNELS), rows(conv.outputs["Out"][0], STAGE_CHANNELS)]
        scans = of("ssd_scan")
        for scan in (scans[0], scans[-1]):
            stages += [rows(scan.inputs["X"][0], STAGE_HEADS * m2["head_dim"]), rows(scan.inputs["Dt"][0], STAGE_HEADS),
                       rows(scan.inputs["B"][0], stage_groups * m2["state"]), rows(scan.inputs["C"][0], stage_groups * m2["state"]),
                       rows(scan.outputs["Out"][0], STAGE_HEADS * m2["head_dim"]),
                       layers.slice(block.var(scan.outputs["State"][0]), axes=[0, 1], starts=[0, 0],
                                    ends=[STAGE_ROWS, STAGE_HEADS]).name]
        attention = of("fused_attention")[0]
        stages += [rows(attention.inputs[s][0]) for s in ("Q", "K", "V")] + [rows(attention.outputs["Out"][0])]
        rate = layers.learning_rate_scheduler.linear_lr_warmup(
            job["learning_rate"], job["lr_warmup_steps"], job["lr_warmup_start"], job["learning_rate"])
        fluid.optimizer.Adam(learning_rate=rate, beta1=job["adam_beta1"], beta2=job["adam_beta2"],
                             epsilon=job["adam_epsilon"]).minimize(fetches["loss"])
    return (main, startup, feeds, fetches["loss"], [fetches["loss"].name, sampled.name] + stages)


# -- the count ------------------------------------------------------------------

def _layer_parameters(cfg: dict, experts: int) -> dict:
    """Parameters of each kind of layer, its norm's gain included, with
    `experts` routed experts in an expert layer."""
    d, m2 = cfg["hidden_size"], _mamba2(cfg)
    inner, wide = m2["heads"] * m2["head_dim"], m2["groups"] * m2["state"]
    heads, kv, head = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    latent, width = cfg["moe_latent_size"], cfg["moe_intermediate_size"]
    return {
        "mamba2": (d * (2 * inner + 2 * wide + m2["heads"]) + (inner + 2 * wide) * (cfg["conv_kernel"] + 1)
                   + 3 * m2["heads"] + inner + inner * d + d),
        "full_attention": d * heads * head + 2 * d * kv * head + heads * head * d + d,
        "feed_forward": (d * cfg["num_routed_experts"] + 2 * d * latent
                         + 2 * d * cfg["moe_shared_expert_intermediate_size"] * cfg["n_shared_experts"]
                         + experts * 2 * latent * width + d),
    }


def parameters(cfg: dict) -> int:
    """The parameters the program builds, counted from the configuration."""
    part = _layer_parameters(cfg, cfg["n_routed_experts"])
    d = cfg["hidden_size"]
    return int(sum(part[kind] for kind in cfg["layer_types"]) + 2 * cfg["vocab_size"] * d + d)


def published_parameters(cfg: dict) -> tuple:
    """(all, active a token) parameters of the PUBLISHED model by the same
    formulas: 88 layers of `reduced_from`'s pattern, 512 experts (22 a token),
    the whole vocabulary: "120B-A12B"."""
    whole = {**cfg, **cfg["reduced_from"]}
    kinds = [KINDS[c] for c in whole["hybrid_override_pattern"]]
    d, ends = whole["hidden_size"], 2 * whole["vocab_size"] * whole["hidden_size"] + whole["hidden_size"]
    every = _layer_parameters({**whole, "num_routed_experts": whole["n_routed_experts"]}, whole["n_routed_experts"])
    active = _layer_parameters({**whole, "num_routed_experts": whole["n_routed_experts"]}, whole["num_experts_per_tok"])
    # a token reads one row of the embedding and the whole head
    return (int(sum(every[k] for k in kinds) + ends),
            int(sum(active[k] for k in kinds) + whole["vocab_size"] * d + 2 * d))


def _counts(cfg: dict) -> dict:
    return {kind: sum(k == kind for k in cfg["layer_types"]) for kind in KINDS.values()}


def flops_per_sample(cfg: dict, job: dict) -> float:
    """Operations the forward and backward passes of one sequence require
    (matrix multiplications only, 2 per multiply-add, backward twice the
    forward, nothing recomputed): per position a Mamba-2 layer's two projections
    (4096 x 18560, 8192 x 4096) and the recurrence's own products
    (`ssd_recurrence_flops`' count: the state's update and its read, P N a head
    each); the attention layer's four projections and its two products over the
    causal pairs; an expert layer's router, its two latent projections, the
    shared expert and the position's HELD experts, 22 x 32 / 512 of one on
    average (a uniform router's share); and the head.  Nothing for the
    convolution's taps, the gates and the norms."""
    d, m2, seq = cfg["hidden_size"], _mamba2(cfg), job["seq_len"]
    inner, wide = m2["heads"] * m2["head_dim"], m2["groups"] * m2["state"]
    heads, kv, head = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    latent, width = cfg["moe_latent_size"], cfg["moe_intermediate_size"]
    held_share = cfg["num_experts_per_tok"] * cfg["n_routed_experts"] / cfg["num_routed_experts"]
    part = {
        "mamba2": (2 * d * (2 * inner + 2 * wide + m2["heads"]) + 2 * inner * d
                   + 2 * 2 * m2["heads"] * m2["head_dim"] * m2["state"]),
        "full_attention": (2 * d * heads * head + 2 * 2 * d * kv * head + 2 * heads * head * d
                           + 2 * heads * 2 * head * (seq + 1) / 2),
        "feed_forward": (2 * d * cfg["num_routed_experts"] + 2 * 2 * d * latent
                         + 2 * 2 * d * cfg["moe_shared_expert_intermediate_size"] * cfg["n_shared_experts"]
                         + held_share * 2 * 2 * latent * width),
    }
    per_position = 2 * d * cfg["vocab_size"] + sum(part[kind] for kind in cfg["layer_types"])
    return 3.0 * seq * per_position


def ssd_recurrence_flops(cfg: dict, job: dict) -> float:
    """Operations of the RECURRENCE of a training step's Mamba-2 layers on a
    chip, forward and backward (twice the forward), nothing for what backward
    makes again, counted from the mathematics and not from what implements it:
    a token and head, the state's update dt x (x) B and decay (2 P N) and its
    read h C (2 P N).  What the chunked form adds to it is NOT counted (a
    chunk's C B^T scores and the decayed scores by x: Q (N / 16 + P) a token and
    head at Q = 128 against 2 P N = 16384: +56%)."""
    m2 = _mamba2(cfg)
    tokens = job["batch_per_chip"] * job["seq_len"]
    return 3.0 * _counts(cfg)["mamba2"] * tokens * m2["heads"] * 4.0 * m2["head_dim"] * m2["state"]


def ssd_recurrence_bytes(cfg: dict, job: dict) -> float:
    """Bytes those ops have to move at the least: xs and the output in bf16,
    B and C in bf16 once a group, the step once a head, forward; the same again
    for the gradients backward and the inputs read once more there."""
    m2 = _mamba2(cfg)
    a_token = 2 * m2["heads"] * m2["head_dim"] * 2 + 2 * m2["groups"] * m2["state"] * 2 + m2["heads"] * 2
    return float(3 * a_token * job["batch_per_chip"] * job["seq_len"] * _counts(cfg)["mamba2"])


def latent_expert_gemm_flops(cfg: dict, job: dict, rows: float = None) -> float:
    """Operations of the held experts' grouped products of a step on a chip,
    forward and backward (twice the forward): two products of 1024 x 2688 a row
    that a held expert received; `rows` a layer (the run's own count), or the
    uniform router's share of a chip's tokens x 22."""
    tokens = job["batch_per_chip"] * job["seq_len"]
    if rows is None:
        rows = tokens * cfg["num_experts_per_tok"] * cfg["n_routed_experts"] / cfg["num_routed_experts"]
    return 3.0 * _counts(cfg)["feed_forward"] * rows * 2 * 2.0 * cfg["moe_latent_size"] * cfg["moe_intermediate_size"]


def latent_expert_gemm_bytes(cfg: dict, job: dict, rows: float = None) -> float:
    """Bytes those products have to move at the least: every held expert's two
    matrices in bf16 once forward and once backward and their float32 gradients
    once; a row's latent in and out and its hidden row in and out, bf16, forward
    and twice backward."""
    tokens = job["batch_per_chip"] * job["seq_len"]
    if rows is None:
        rows = tokens * cfg["num_experts_per_tok"] * cfg["n_routed_experts"] / cfg["num_routed_experts"]
    latent, width = cfg["moe_latent_size"], cfg["moe_intermediate_size"]
    matrices = cfg["n_routed_experts"] * 2 * latent * width * (2 + 2 + 4)
    return float(_counts(cfg)["feed_forward"] * (matrices + 3 * rows * 2 * (latent + width) * 2))


# -- the reference ----------------------------------------------------------------

def scan_recurrence(x, dt, b, c, a_log, d_skip, dt_bias, groups, bf16_state=False, with_state=False):
    """y [rows, T, H P] float32 (`with_state`: and the state after the last token
    [rows, H, P, N]) of the recurrence in the module's docstring over
    x [rows, T, H P], the step's projection dt [rows, T, H], B, C [rows, T, G N],
    A_log, D and dt_bias [H]: one token at a time, a float32 [H, P, N] state.
    `bf16_state` rounds the state to bf16's eight bits after every token (read
    beside the stage's error; `reduce_precision`, which XLA may not take out as
    it may a pair of casts)."""
    import jax
    import jax.numpy as jnp

    with jax.default_matmul_precision("highest"):
        x, dt, b, c = (jnp.asarray(t, jnp.float32) for t in (x, dt, b, c))
        rows, T, width = x.shape
        heads = a_log.shape[0]
        P, N = width // heads, b.shape[-1] // groups
        A = -jnp.exp(jnp.asarray(a_log, jnp.float32))
        step = jax.nn.softplus(dt + jnp.asarray(dt_bias, jnp.float32))              # [rows, T, H]
        xh = x.reshape(rows, T, heads, P)
        of_head = jnp.arange(heads) // (heads // groups)                            # head h reads group h // (H / G)
        bh, ch = (t.reshape(rows, T, groups, N) for t in (b, c))

        def token(h, at):
            x_t, s_t, b_t, c_t = at                                                  # [rows, H, P], [rows, H], [rows, G, N] x 2
            h = jnp.exp(s_t * A)[..., None, None] * h + (s_t[..., None] * x_t)[..., None] * b_t[:, of_head][:, :, None, :]
            if bf16_state:
                h = jax.lax.reduce_precision(h, exponent_bits=8, mantissa_bits=7)
            return h, jnp.sum(h * c_t[:, of_head][:, :, None, :], -1)

        h0 = jnp.zeros((rows, heads, P, N), jnp.float32)
        last, y = jax.lax.scan(token, h0, tuple(t.swapaxes(0, 1) for t in (xh, step, bh, ch)))
        y = (y.swapaxes(0, 1) + jnp.asarray(d_skip, jnp.float32)[:, None] * xh).reshape(rows, T, width)
        return (y, last) if with_state else y


def reference(params: dict, batch: dict, cfg: dict, program=None, activation="relu2", gate_first=True, precision="highest"):
    """(loss, the sampled positions' logits [rows, sample, vocab], margin [rows,
    L], choice [expert layers, rows, L, 22], the routers' float32 weights
    stacked by expert layer, the first and the last expert layer's W1 and W2
    stacked, the biases stacked, the first Mamba-2 layer's taps and convolution
    bias (`STAGE_CHANNELS`), A_log, D and dt_bias of the first and the last
    Mamba-2 layer stacked (`STAGE_HEADS`), (first held expert, the
    renormalisation's epsilon, the scaling factor, the state's width), the attention's queries
    [rows, 32, sample, 128] and keys [rows, 2, sample, 128] at
    `attention_sample`'s positions) of `batch` in plain float32 jax.numpy, one
    sequence at a time; `params` maps the program's parameter names to arrays,
    which may lie split over a mesh (every operation here is one GSPMD
    partitions by itself).  No kernel, no chunk and no sort: the scan is
    `scan_recurrence`'s step over the tokens, the convolution four shifted
    products, attention explicit causal scores two heads at a time, every held
    expert applied to every position and weighted by the renormalised choice,
    the head and the loss 1024 positions at a time.  Three controls
    (tools/chip_nemotron_controls.py): `activation` "relu" is relu for relu^2,
    `gate_first` False norms before it gates, `precision` "default" computes
    the products as the chip does unasked (bf16 operands)."""
    import jax
    import jax.numpy as jnp

    kinds, eps = cfg["layer_types"], cfg["norm_eps"]
    d, m2 = cfg["hidden_size"], _mamba2(cfg)
    inner, wide, groups = m2["heads"] * m2["head_dim"], m2["groups"] * m2["state"], m2["groups"]
    taps, heads, kv, head = cfg["conv_kernel"], cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    top_k = cfg["num_experts_per_tok"]
    first, n_held = held(cfg)
    mambas = [i for i, kind in enumerate(kinds) if kind == "mamba2"]
    sparse = [i for i, kind in enumerate(kinds) if kind == "feed_forward"]
    biases = router_biases(params, cfg, sparse)

    def p(name):
        return jnp.asarray(params[name], jnp.float32)

    def rms(x, gain=None):
        y = x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps)
        return y if gain is None else y * p(gain)

    def act(t):
        return jnp.square(jax.nn.relu(t)) if activation == "relu2" else jax.nn.relu(t)

    def mamba(a, pre, seq):
        both = a @ p(f"{pre}.in.w")
        z, xbc, dt = both[:, :inner], both[:, inner:2 * inner + 2 * wide], both[:, 2 * inner + 2 * wide:]
        w = p(f"{pre}.conv.w")
        xbc = jax.nn.silu(sum(w[:, j] * jnp.pad(xbc, ((taps - 1 - j, 0), (0, 0)))[:seq] for j in range(taps))
                          + p(f"{pre}.conv.b"))
        xs, b, c = xbc[:, :inner], xbc[:, inner:inner + wide], xbc[:, inner + wide:]
        y = scan_recurrence(xs[None], dt[None], b[None], c[None], p(f"{pre}.a_log"), p(f"{pre}.d"), p(f"{pre}.dt_bias"),
                            groups)[0]

        def by_group(t):
            return rms(t.reshape(seq, groups, inner // groups)).reshape(seq, inner)

        gated = by_group(y * jax.nn.silu(z)) if gate_first else by_group(y) * jax.nn.silu(z)
        return (gated * p(f"{pre}.norm.w")) @ p(f"{pre}.out.w")

    def attention(a, pre, seq):
        at = jnp.arange(seq)
        q = (a @ p(f"{pre}.q.w")).reshape(seq, heads, head).transpose(1, 0, 2)
        k = (a @ p(f"{pre}.k.w")).reshape(seq, kv, head).transpose(1, 0, 2)
        v = (a @ p(f"{pre}.v.w")).reshape(seq, kv, head).transpose(1, 0, 2)
        share = heads // kv

        def two_heads(j):   # NO positions: the scores are the plain products; heads 2j, 2j + 1 read key/value head 2j // share
            qs = jax.lax.dynamic_slice_in_dim(q, 2 * j, 2, 0)
            ks = jax.lax.dynamic_index_in_dim(k, 2 * j // share, 0, keepdims=False)
            vs = jax.lax.dynamic_index_in_dim(v, 2 * j // share, 0, keepdims=False)
            scores = jnp.einsum("hqd,kd->hqk", qs, ks) / np.sqrt(head)
            scores = jnp.where(at[None, :] <= at[:, None], scores, -jnp.inf)
            return jnp.einsum("hqk,kd->hqd", jax.nn.softmax(scores, -1), vs)

        ctx = jax.lax.map(two_heads, jnp.arange(heads // 2)).reshape(heads, seq, head)
        sample = attention_sample(seq)
        return ctx.transpose(1, 0, 2).reshape(seq, heads * head) @ p(f"{pre}.out.w"), (q[:, sample], k[:, sample])

    def experts(a, pre, bias):
        scores = jax.nn.sigmoid(a @ p(f"{pre}.router.w"))
        biased = scores + bias
        ranked = jnp.sort(biased, -1)[:, ::-1]
        kth, after = ranked[:, top_k - 1], ranked[:, top_k]
        chosen = jnp.where(biased >= kth[:, None], scores, 0.0)          # the UNBIASED scores of the chosen
        gates = (chosen / (jnp.sum(chosen, -1, keepdims=True) + cfg["norm_topk_eps"])
                 * cfg["routed_scaling_factor"])                          # over all 22, held or not
        u = a @ p(f"{pre}.latent_in.w")

        def expert(acc, ew):
            w1, w2, g_e = ew
            return acc + (act(u @ w1) @ w2) * g_e[:, None], None

        routed, _ = jax.lax.scan(expert, jnp.zeros_like(u),
                                 (p(f"{pre}.up.w"), p(f"{pre}.down.w"), gates[:, first:first + n_held].T))
        out = routed @ p(f"{pre}.latent_out.w") + act(a @ p(f"{pre}.shared.up.w")) @ p(f"{pre}.shared.down.w")
        return out, (kth - after) / jnp.abs(kth), jnp.sort(jax.lax.top_k(biased, top_k)[1], -1)

    def one_sequence(row):
        ids, labels = row
        seq = ids.shape[0]
        x = p("lm.tok_emb")[ids]
        margin = jnp.full((seq,), jnp.inf)
        choices, first_qk = [], None
        for i, kind in enumerate(kinds):
            pre = f"lm.l{i}"
            if kind == "mamba2":
                x = x + mamba(rms(x, f"{pre}.ln1.w"), f"{pre}.mamba2", seq)
            elif kind == "full_attention":
                out, qk = attention(rms(x, f"{pre}.ln1.w"), f"{pre}.attn", seq)
                x, first_qk = x + out, first_qk or qk
            else:
                out, gap, choice = experts(rms(x, f"{pre}.ln2.w"), f"{pre}.moe", biases[sparse.index(i)])
                x, margin = x + out, jnp.minimum(margin, gap)
                choices.append(choice)
        x = rms(x, "lm.final_norm.w")
        table = p("lm.head.w")
        block = min(1024, seq)

        def ce_of(part):   # the head and the cross entropy, a block of positions at a time
            hidden, target = part
            logp = jax.nn.log_softmax(hidden @ table, -1)
            return -jnp.take_along_axis(logp, target[:, None], 1)[:, 0].sum()

        whole = seq - seq % block
        ce = jax.lax.map(ce_of, (x[:whole].reshape(-1, block, d), labels[:whole].reshape(-1, block))).sum()
        if whole < seq:
            ce = ce + ce_of((x[whole:], labels[whole:]))
        return (x[logit_sample(seq)] @ table, margin, jnp.stack(choices), ce) + first_qk

    with jax.default_matmul_precision(precision):
        rows = tuple(jnp.asarray(batch[n], jnp.int32) for n in FEEDS)
        out, margin, choice, ce_sum, q_first, k_first = jax.lax.map(one_sequence, rows)
        routers = jnp.stack([p(f"lm.l{i}.moe.router.w") for i in sparse])
        ends = (sparse[0], sparse[-1])
        w1, w2 = (jnp.stack([p(f"lm.l{i}.moe.{n}.w") for i in ends]) for n in ("up", "down"))
        pre = f"lm.l{mambas[0]}.mamba2"
        scan_params = tuple(jnp.stack([p(f"lm.l{i}.mamba2.{n}")[:STAGE_HEADS] for i in (mambas[0], mambas[-1])])
                            for n in ("a_log", "d", "dt_bias"))
        return ((ce_sum.sum() / rows[1].size, out, margin, choice.transpose(1, 0, 2, 3), routers, w1, w2, jnp.stack(biases),
                 p(f"{pre}.conv.w")[:STAGE_CHANNELS], p(f"{pre}.conv.b")[:STAGE_CHANNELS]) + scan_params
                + (jnp.asarray([first, cfg["norm_topk_eps"], cfg["routed_scaling_factor"], m2["state"]], jnp.float32),
                   q_first, k_first))


# -- the comparison ------------------------------------------------------------------

def router_errors(choice, a, top_p, bias, router, eps: float, scaling: float) -> dict:
    """One layer's router on the program's own input `a` [tokens, d] (bf16 rows):
    its `choice` and `top_p` [tokens, 22] against float64 numpy over the float32
    weights.  `bias_moved`: the choices the unbiased top-k would not have made."""
    tokens, k = choice.shape
    logits = (a.astype("f4") @ router).astype("f8")
    scores = _sigmoid(logits)
    biased = scores + bias.astype("f8")
    ranked = np.sort(biased, -1)
    tie = (ranked[:, -k] - ranked[:, -k - 1]) < ROUTER_TIE * np.abs(ranked[:, -k])
    differs = (np.sort(np.argsort(-biased, -1)[:, :k], -1) != np.sort(choice, -1)).any(-1)
    unbiased = np.argsort(-scores, -1)[:, :k]
    moved = int((~(choice[:, :, None] == unbiased[:, None, :]).any(-1)).sum())

    def weights(s):
        mine = np.take_along_axis(s, choice, -1)
        return mine / (mine.sum(-1, keepdims=True) + eps) * scaling

    mine = weights(scores)
    low = weights(_sigmoid(_bf16(logits.astype("f4")).astype("f8")))
    return {"router_choice_differs": int((differs & ~tie).sum()), "router_ties": int((differs & tie).sum()),
            "router_prob_error": float((np.abs(top_p - mine) / mine).max()),
            "router_prob_error_bf16_logits": float((np.abs(low - mine) / mine).max()), "bias_moved": moved}


def expert_errors(choice, u, top_p, out, w1, w2, first: int) -> dict:
    """One layer's held experts' sum IN THE LATENT on the program's own u
    [tokens, 1024], choice and weights, every `EXPERTS_SAMPLE`-th token, against
    float32 numpy over the float32 matrices `w1`, `w2` of the experts `first`
    on: root-mean-square error over the root-mean-square sum; and the same with
    relu for relu^2, and with bf16 running sums (eight terms at a time), against
    that float32: what the limit has to refuse."""
    tokens = choice.shape[0]
    sample = np.arange(0, tokens, max(tokens // EXPERTS_SAMPLE, 1))
    want, plain, rounded = (np.zeros((len(sample), u.shape[1]), "f4") for _ in range(3))

    def product_in_bf16(x, w):
        acc = np.zeros((x.shape[0], w.shape[1]), "f4")
        for i in range(0, x.shape[1], 8):
            acc = _bf16(acc + x[:, i:i + 8] @ w[i:i + 8])
        return acc

    for e in range(w1.shape[0]):
        row, slot = np.nonzero(choice[sample] == first + e)
        if not len(row):
            continue
        x, weight = u[sample[row]].astype("f4"), top_p[sample[row], slot][:, None]
        opened = np.maximum(x @ w1[e], 0.0)
        want[row] += (np.square(opened) * weight) @ w2[e]
        plain[row] += (opened * weight) @ w2[e]
        low = np.maximum(product_in_bf16(x, _bf16(w1[e])), 0.0)
        rounded[row] += product_in_bf16(_bf16(np.square(low) * weight), _bf16(w2[e]))
    scale = max(_rms(want), 1e-30)
    return {"experts_error": _rms(out[sample].astype("f4") - want) / scale, "experts_error_relu": _rms(plain - want) / scale,
            "experts_error_bf16_sums": _rms(rounded - want) / scale}


@functools.lru_cache(maxsize=4)
def _recurrence_jit(groups, bf16_state):
    import jax

    return jax.jit(functools.partial(scan_recurrence, groups=groups, bf16_state=bf16_state, with_state=True))


def scan_errors(layers, a_log, d_skip, dt_bias, state: int) -> dict:
    """The program's `ssd_scan` against the float32 token-by-token recurrence on
    its own xs, dt, B, C, for each of `layers` (the first and the last Mamba-2
    layer's six stage tensors; the parameters' rows stacked alike; B and C hold
    the stage heads' groups, `state` wide each).  `scan_state_error`: the FIRST
    layer's float32 state after the last token, root-mean-square difference from
    the recurrence's over the root-mean-square state (`scan_state_error_by_layer`
    has the last layer's beside it).  `scan_error` (the first layer's) and
    `scan_error_deep` (the last's): the output's root-mean-square difference
    from the recurrence's output ROUNDED to bf16 as the op rounds its own, over
    the root-mean-square output.  `scan_error_unrounded`: the same against the
    float32 output, which the output's own rounding dominates.  Beside them the
    recurrence with its state rounded to bf16 after every token, read the same
    ways, and the mean decay."""
    mine, plain, low, states, low_states, decays = [], [], [], [], [], []
    for (x, dt, b, c, out, last), A, D, bias in zip(layers, a_log, d_skip, dt_bias):
        operands = tuple(np.asarray(t, "f4") for t in (x, dt, b, c, A, D, bias))
        groups = operands[2].shape[-1] // state
        want, want_last = (np.asarray(t) for t in _recurrence_jit(groups, False)(*operands))
        rough, rough_last = (np.asarray(t) for t in _recurrence_jit(groups, True)(*operands))
        scale = max(_rms(want), 1e-30)
        rounded = want if np.asarray(out).dtype == np.float32 else _bf16(want)    # as the op rounded its own
        out = np.asarray(out, "f4")
        mine.append(_rms(out - rounded) / scale)
        plain.append(_rms(out - want) / scale)
        low.append(_rms(_bf16(rough) - rounded) / scale)
        states.append(_rms(np.asarray(last, "f4") - want_last) / max(_rms(want_last), 1e-30))
        low_states.append(_rms(rough_last - want_last) / max(_rms(want_last), 1e-30))
        step = np.log1p(np.exp(operands[1] + operands[6]))
        decays.append(float(np.exp(-step * np.exp(operands[4])).mean()))
    return {"scan_state_error": states[0], "scan_state_error_by_layer": states, "scan_error": mine[0], "scan_error_deep": mine[-1],
            "scan_error_unrounded": max(plain), "scan_error_bf16_state": min(low), "scan_state_error_bf16_state": min(low_states),
            "scan_decay_mean": decays}


_HEAD = 2   # loss, sampled logits
_PER_LAYER = 6
_TAIL = 2 + 12 + 4   # the convolution's, the two Mamba-2 layers' and the attention's stage tensors


def compare(got, want) -> dict:
    """The program's fetched variables (`build`) against the reference's outputs
    (`reference`): the two errors `REFERENCE_RTOL` bounds, the routing account,
    and the stage errors.  Every sampled position is compared: those whose held
    choice agrees with the reference's in every expert layer under
    `REFERENCE_RTOL` (`logit_error`) and `QK_RTOL`, the others under
    `OTHER_CHOICE_RTOL` (`logit_error_other_choice`, `qk_error_other_choice`)."""
    loss, want_loss = float(np.asarray(got[0]).reshape(-1)[0]), float(want[0])
    want_logits = np.asarray(want[1], "f4")                                   # [rows, sample, vocab]
    logits = np.asarray(got[1], "f4").transpose(1, 0, 2)
    margin, want_choice = np.asarray(want[2]), np.asarray(want[3])
    rows, seq = margin.shape
    k = want_choice.shape[-1]
    (first, eps, scaling, state), n_held = (float(n) for n in np.asarray(want[13])), np.asarray(want[5]).shape[1]
    first, state = int(first), int(state)
    layers = [got[i:i + _PER_LAYER] for i in range(_HEAD, len(got) - _TAIL, _PER_LAYER)]
    tail = got[len(got) - _TAIL:]
    biases_differ = int(sum((np.asarray(layer[5], "f4") != np.asarray(want[7][i], "f4")).sum()
                            for i, layer in enumerate(layers)))
    choice = np.sort(np.stack([np.asarray(layer[0]).reshape(want_choice.shape[1:]) for layer in layers]), -1)
    routed_differently = (choice != want_choice).any(axis=(0, 3))           # [rows, L]

    def held_choice(c):  # [..., held]: which held experts a position chose
        return (c[..., None] == np.arange(first, first + n_held)).any(-2)

    flips = held_choice(choice) != held_choice(want_choice)                  # [layers, rows, L, held]
    differs = flips.any(axis=(0, 3))
    sampled = differs[:, logit_sample(seq)]
    err = np.abs(logits - want_logits).max(-1)
    scale = max(np.abs(want_logits).max(), 1e-9)

    routers, experts = [], []
    for i, (c, a, top_p, u, out, bias) in enumerate(layers):
        stage_rows, tokens = np.asarray(a).shape[:2]
        mine = np.asarray(c).reshape(rows, seq, k)[:stage_rows, :tokens].reshape(-1, k)
        flat = [np.asarray(t).reshape(stage_rows * tokens, -1) for t in (a, top_p, u, out)]
        routers.append(router_errors(mine, flat[0], flat[1].astype("f4"), np.asarray(bias, "f4"),
                                     np.asarray(want[4][i], "f4"), eps, scaling))
        if i in (0, len(layers) - 1):
            j = 0 if i == 0 else -1
            experts.append(expert_errors(mine, flat[2], flat[1].astype("f4"), flat[3],
                                         np.asarray(want[5][j], "f4"), np.asarray(want[6][j], "f4"), first))
    summed = ("bias_moved", "router_choice_differs", "router_ties")
    q, key, v, out = (np.asarray(t, "f4").transpose(0, 2, 1, 3) for t in tail[14:])      # (rows, L, H, .) as handed
    stage_rows = q.shape[0]
    attention = _decoder.attention_errors(q, key, v, out, np.asarray(want[14])[:stage_rows], np.asarray(want[15])[:stage_rows])
    # the queries and keys of a position that chose other held experts in a layer BEFORE the attention carry that
    # expert's output more or less, as its logits do: read under the wider limit as there
    at = attention_sample(seq)
    kept = ~differs[:stage_rows, at]
    off = [np.abs(mine[:, :, at] - np.asarray(theirs, "f4")[:stage_rows]).max(axis=(1, 3)) / np.abs(theirs).max()
           for mine, theirs in ((q, want[14]), (key, want[15]))]
    attention.update(qk_error=float(max(e[kept].max(initial=0.0) for e in off)),
                     qk_error_other_choice=float(max(e[~kept].max(initial=0.0) for e in off)))
    return {
        "loss_error": abs(loss - want_loss) / max(abs(want_loss), 1e-6),
        "logit_error": float(err[~sampled].max(initial=0.0) / scale),
        "logit_error_other_choice": float(err[sampled].max(initial=0.0) / scale),
        "tokens": int(rows * seq),
        "other_choice": int(differs.sum()),
        "other_choice_share": float(differs.sum() / (rows * seq)),
        "routed_differently": int(routed_differently.sum()),
        "under_margin": int((margin < ROUTING_MARGIN).sum()),
        "routed_differently_above_margin": int((routed_differently & (margin >= ROUTING_MARGIN)).sum()),
        **{name: (sum if name in summed else max)(r[name] for r in routers) for name in routers[0]},
        **{name: max(e[name] for e in experts) if name == "experts_error" else min(e[name] for e in experts)
           for name in experts[0]},
        "biases_differ": biases_differ,
        "held_rows_share": [float(held_choice(c[None]).sum() / (rows * seq * k)) for c in choice],
        **conv_errors(tail[0], tail[1], want[8], want[9]),
        **scan_errors([tail[2:8], tail[8:14]], *(np.asarray(t, "f4") for t in want[10:13]), state),
        **attention,
    }


LIMITS = {"logit_error": "REFERENCE_RTOL", "loss_error": "REFERENCE_RTOL", "logit_error_other_choice": "OTHER_CHOICE_RTOL",
          "qk_error_other_choice": "OTHER_CHOICE_RTOL", "other_choice_share": "OTHER_CHOICE_MAX",
          "scan_state_error": "SCAN_STATE_RTOL", "scan_error": "SCAN_RTOL", "scan_error_deep": "SCAN_DEEP_RTOL",
          "conv_error": "CONV_RTOL", "router_prob_error": "ROUTER_RTOL", "experts_error": "EXPERTS_RTOL",
          "attention_error": "ATTENTION_RTOL", "qk_error": "QK_RTOL"}


def reference_error(got, want) -> float:
    """How far the program is from the reference, as `REFERENCE_RTOL` counts it:
    the larger of the loss's and the sampled logits' error, the logits over the
    positions whose held choice agrees (the `reference_routing` line of the run
    has every reading beside its limit).  A failure (infinite error) is: a
    reading over its limit (`LIMITS`: the positions that chose other held
    experts, their share, every stage on the program's own tensors), a position
    that routed differently across a gap wider than `ROUTING_MARGIN`, a router
    whose choice on its own input differs from float64's outside a tie or whose
    bias is not the configuration's."""
    import json

    found = compare(got, want)
    here = globals()
    print(json.dumps({"info": "reference_routing", **found, "routing_margin": ROUTING_MARGIN,
                      **{limit.lower(): here[limit] for limit in sorted(set(LIMITS.values()))}}), flush=True)
    if (found["routed_differently_above_margin"] or found["router_choice_differs"] or found["biases_differ"]
            or any(not found[name] <= here[limit] for name, limit in LIMITS.items() if limit != "REFERENCE_RTOL")):
        return float("inf")
    return max(found["loss_error"], found["logit_error"])
