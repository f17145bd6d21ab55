"""SmallThinker-21BA3B-Instruct causal-LM training: how the benchmark builds it
through the framework, a plain float32 reference of the same architecture, and
the operations one sequence needs.

Architecture: PowerInfer/SmallThinker-21BA3B-Instruct `config.json` and the
family's paper (SmallThinker, arXiv:2507.20984) and public modelling code; what
`config.json` does not give is listed in the configuration file's `assumed`.  A
layer, eps 1e-6, no biases, no q/k-norm, with x the layer's input [tokens, 2560]:

    router    r = x Wr in float32, 64 logits a token, read from the layer's input ITSELF: before the input norm and
              before the attention (the family's pre-attention router).  S = the 6 largest of r;  g = softmax(r[S])
    attention a = rms(x; ln1);  q = a Wq (28 heads of 128), k = a Wk, v = a Wv (4 heads; query head j reads key/value
              head j div 7).  Where the layer's `rope_layout` is 1: q, k <- RoPE(., position), halves paired
              (t_i, t_i+64) <- (t_i cos w - t_i+64 sin w, t_i+64 cos w + t_i sin w), w = position . 1.5e6^(-i/64),
              float32, and key j allowed for query i where i - 4096 < j <= i.  Where it is 0: NO rotation, j <= i.
              scores at 128^-0.5, float32 softmax;  h = x + concat(heads) Wo
    experts   m = rms(h; ln2);  y = h + sum_{e in S and e in HELD} g_e . Wdown_e( relu(Wgate_e m) * (Wup_e m) ),
              experts of width 768, HELD = {0..7}, no shared expert
    loss      mean over every position of CE( rms(y_L; final_norm) W_head, the next token ), the head untied

The reference computes the attention as explicit scores under the rule made
from positions, a key/value head's seven query heads and `ATTENTION_BLOCK`
queries at a time against the keys up to the block's end (never an [L, L] array
a head), the rotation as the halves above, the top 6 by a sort, the experts as
a loop over the eight, and the loss `ATTENTION_BLOCK` positions at a time.  It
shares no code with `paddle_tpu`.

Departures of the program under test
(`paddle_tpu.models.transformer.build_causal_lm`) from the published model,
which the reference follows so that the two compute the same function (the
configuration file's `departures` is this list, word for word):

  * four of the 52 layers, the published layers 0 to 3: one whole period of the pattern (`rope_layout` and `sliding_window_layout` both [0, 1, 1, 1]: a full-attention layer without positions, then three rotary layers under a window of 4096), the floor of four; further layers lie on further chips as pipeline stages;
  * 8 of the 64 experts of every layer, experts 0 to 7: this chip's share of a layer whose experts are split over 8 chips; the router keeps its 64 outputs, its top 6 and its softmax over all six chosen, and what the 56 absent experts would have added is left out of the layer's output, in the program and in the reference alike, with no exchange standing in for the 7 absent chips;
  * 18992 of the 151936 vocabulary rows, in the embedding and in the untied head: one chip's eighth of the rows, the guide's floor; token ids and labels are drawn from the slice and the loss is over the slice;
  * the secondary experts that the family's description names have no key in `config.json` and are left out;
  * every layer is a `recompute_scope`: backward keeps a layer's input and what `plan_kept` finds room for and makes the rest of the layer again, the routing with it; the numbers are the same either way (tests/test_smallthinker.py holds the gradients equal to the last bit);
  * Adam for AdamW (the framework has no AdamW), learning rate 1e-4 reached by a linear warm-up over the first 200 steps from 1e-6, betas 0.9 / 0.95, epsilon 1e-8, no decay and no auxiliary loss;
  * weights are random, N(0, 0.02) from the run's seed, norm gains 1, but for the token embedding, N(0, 1) (the routers read the residual stream UNNORMED: at N(0, 0.02) the first layer's logits are of order 0.02 and every token chooses much alike), and for the embedding and the routers' matrices together, which come from the configuration's `routing_seed` and not from the run's: they decide which experts a token meets, as a checkpoint's do, and which of the eight chips this is;
  * token ids are uniform random with no padding and no document boundaries (a row is one whole sequence of 16384 positions 0 to 16383, the model's whole `max_position_embeddings`), every position is a label (the next token), so the cross entropy starts near ln(18992).
"""
from __future__ import annotations

import numpy as np

from benchmark.models import lfm2 as _decoder
from benchmark.runners import train as _runner

FEEDS = ("ids", "labels", "pos_ids")

#: Every limit below was set from this cell's own readings at the published
#: widths and 16384 tokens (my chip runs, PR 63: eight sound runs of the cell's
#: comparison, a seed each, 8 x 16384 positions; PERF.md section 6 has the
#: table): what the sound program reads, and what the same comparison reads with
#: a fault put in (tools/chip_smallthinker_controls.py, seed 3630000017), the
#: limit between the two with room on both sides.  The routing margin is OLMoE's
#: argument (benchmark/models/olmoe.py: top-k is discontinuous and the program's
#: router reads a bf16 stream): no sound run routed differently above it, the
#: faults that move the stream 387 to 4442 positions.
ROUTING_MARGIN = _decoder.ROUTING_MARGIN
#: Sampled positions whose HELD choice differs in some layer are left out of the
#: logit comparison and counted over all positions: 1.56% to 1.65% sound (6
#: chosen of 64, 8 held, four layers); the least a fault to the stream reads is
#: 8.5% (a SiLU for the ReLU; the rotary faults 10.7% and 13.8%, a router on the
#: post-attention stream 30%).  A router in bf16 (1.87%) or on the normed input
#: (1.81%) it does not tell: `ROUTER_RTOL` does.  2.4x over the most seen, 2.1x
#: under the least fault.
LEFT_OUT_MAX = 0.04
#: ... and how far a left-out position's logits may be off, over the largest
#: |reference logit|: one held expert's output more or less, 0.047 to 0.070
#: sound.  A sanity bound at 3.6x the most seen (a NaN fails it), NOT a limit
#: between two readings: the faults read 0.07 to 0.14 here and are refused above.
LEFT_OUT_LOGIT_MAX = 0.25
#: The larger of the loss's relative error (1e-7 to 3.5e-6) and the sampled
#: logits' error over the largest |reference logit|, on the positions that chose
#: alike: 6.04e-3 to 7.43e-3 sound (bf16 activations over float32 masters
#: through four layers and a bf16 head).  The least any fault to the stream
#: reads: a router on the post-attention stream 6.17e-2 (3.90e-2 at the second
#: seed of the controls, 3630000019), a SiLU for the ReLU 6.72e-2, no rotation in
#: layer 1 0.112, a rotation in layer 0 0.148.  2.7x over the most seen, 1.95x
#: under the least fault.  (A router in bf16 6.3e-3, a router
#: on the normed input 9.9e-3 and a window off by one key 6.1e-3 are not told
#: end to end; each has a stage that tells it.)
REFERENCE_RTOL = 2e-2
ROUTER_TIE = _decoder.ROUTER_TIE
#: The router on THE LAYER'S INPUT, the stage row (the stream as the layer's
#: input norm reads it, whatever the router's op was handed): the six weights'
#: largest relative error against float64 numpy 4.96e-6 to 5.31e-6, and no token
#: whose six are not float64's across a gap wider than `ROUTER_TIE` (0 ties
#: either).  With the router's float32 matrix rounded to bf16 IN THE PROGRAM
#: 9.87e-3 and 636 tokens routed elsewhere; handed the normed input 1.13 and 546;
#: handed the post-attention stream 10.9 and 22326; its logits rounded to bf16 in
#: numpy 1.6e-2 to 1.8e-2.  38x over the most seen, 49x under the least fault.
ROUTER_RTOL = 2e-4
#: The held experts on the program's own m, choice and weights, every
#: `EXPERTS_SAMPLE`-th token of the stage row: root-mean-square error over the
#: root-mean-square output against float32 numpy 4.55e-3 to 4.60e-3 (bf16
#: operands into float32 accumulation); with the running sums held in bf16,
#: eight terms at a time, 3.2e-2 to 3.3e-2 (numpy); with a SiLU for the ReLU IN
#: THE PROGRAM 0.293.  2.6x over the one, 2.7x under the nearer other.
EXPERTS_RTOL = 1.2e-2
#: The stage row: the program's own tensors of the stages are compared on the
#: first `STAGE_ROWS` of the 8 check rows (the slices are ops of the program, so
#: that 8 rows of every stage's operands never lie in the chip's memory beside
#: the optimizer's state).
STAGE_ROWS = 1
#: The attention of the full layer (layer 0, no positions) and of the first
#: window layer (layer 1, rotary) on the program's own q, k, v for
#: `ATTENTION_SAMPLE` queries of the stage row and every head, each against the
#: keys its rule allows (made here from positions: j <= i, and i - 4096 < j),
#: float32 scores: largest error over the largest |output|, the worse layer's:
#: 2.92e-3 to 4.12e-3 in the full layer, 2.35e-3 to 4.09e-3 in the window layer.
#: What it has to refuse: query head j on key/value head j mod 4 for j div 7
#: reads 1.14 (numpy, every run).  2.9x over the most seen.  It does NOT tell
#: bf16 scores (1.4e-3 to 3.1e-3 against itself, as in the other cells: PERF.md
#: section 7, defect 13c) and it does NOT tell a window off by one key (a window
#: of 4095 in the program reads 5.9e-3 here, of 4097 2.1e-2 at this seed: one
#: weight of 4096 is of the size of the output's own rounding): the next limit's.
ATTENTION_RTOL = 1.2e-2
ATTENTION_SAMPLE = _decoder.ATTENTION_SAMPLE
#: THE WINDOW'S EDGE, the window layer's stage: how much of what a window one key
#: short, and one key long, would add to the output the program's output holds
#: (`attention_errors`: the error along each fault's own direction, pooled over
#: ~4000 (query, head) pairs): -7.2e-4 to 6.8e-4 sound, both coefficients (up to
#: 1.4e-3 under the other controls); a window of 4095 in the program reads 1.0005
#: (missing), of 4097 1.0002 (extra).  350x over the one, 2x under the other.
WINDOW_EDGE_MAX = 0.5
#: ... and those layers' queries and keys themselves at the sampled positions
#: against the reference's (which rotates, or does not, on its own), over the
#: largest |value|, at the positions whose held choice agrees in the layers
#: before: 3.87e-3 to 5.22e-3 in layer 0, 5.32e-3 to 7.00e-3 in layer 1 (a
#: layer's bf16 roundings lie before it).  A rotation applied in layer 0 reads
#: 1.87, one left out of layer 1 1.82.  3.6x over the most seen, 73x under the
#: least fault.
QK_RTOL = 2.5e-2
#: THE REFERENCE ITSELF, the first row: its first layer's queries at the sampled
#: positions against float64 numpy of the same product on its own normed input,
#: and its first layer's attention output there against float64 numpy on its own
#: q, k and v: the larger of the two, over the largest |value|.  Float32
#: products at the highest precision read 9.5e-7 to 1.4e-6; the attention's two
#: products at the chip's default precision (bf16 operands, the nearest
#: precision below) 4.85e-3, which nothing else here tells apart: the program
#: rounds as much itself.  71x over the one, 48x under the other.
REFERENCE_SELF_RTOL = 1e-4
#: ... its attention's on the sampled queries among the first `SELF_KEYS` positions, which see those keys alone.
SELF_KEYS = 2048
#: Queries a block of the reference's attention and positions a block of its loss.
ATTENTION_BLOCK = 1024
#: The layers whose attention, queries and keys are staged: the full layer and the first window layer.
STAGE_LAYERS = (0, 1)

logit_sample = _decoder.logit_sample
attention_sample = _decoder.attention_sample
make_batch = _decoder.make_batch
_bf16 = _decoder._bf16


def expert_sample(tokens: int):
    """The tokens of the stage rows whose held experts' outputs are compared:
    every `EXPERTS_SAMPLE`-th, as `lfm2.stage_errors` takes them."""
    return np.arange(0, tokens, max(tokens // _decoder.EXPERTS_SAMPLE, 1))


def held(cfg: dict) -> tuple:
    """(first, count) of the experts this chip holds."""
    return (cfg["experts_held_first"], cfg["moe_num_primary_experts"])


def _windows(cfg: dict) -> list:
    """A layer's window in keys, None where it attends to every earlier key."""
    return [cfg["sliding_window_size"] if kind == "sliding_attention" else None for kind in cfg["layer_types"]]


def build(cfg: dict, job: dict):
    """(main, startup, feed variables by name, loss variable, the variables the
    reference is compared on) of the train program, as a user of the framework
    gets it: `build_causal_lm` with every layer a recomputed segment (a job may
    say `recompute_layers` false: the tests', which hold the two alike), then
    the learning rate's warm-up and Adam from the traffic file.  The compared
    variables: loss, the sampled positions' logits; layer by layer the top-k
    choice of every row and, on the first `STAGE_ROWS` rows, the LAYER'S INPUT
    (what its input norm reads) and the top-k weights, and at `expert_sample`'s
    tokens the held experts' output and their input m; then, for each of
    `STAGE_LAYERS`, (rows, ., heads, 128) whatever layout the layer's attention
    took, the queries and outputs of `attention_sample`'s positions and the keys
    and values of every position."""
    import paddle_tpu as fluid
    from paddle_tpu import layers
    from paddle_tpu.models import transformer

    main, startup, feeds, fetches = transformer.build_causal_lm(
        vocab_size=cfg["vocab_size"], seq_len=job["seq_len"], d_model=cfg["hidden_size"],
        n_heads=cfg["num_attention_heads"], n_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        qk_norm=None, norm_eps=cfg["rms_norm_eps"], rope_theta=cfg["rope_theta"],
        layer_types=cfg["layer_types"], sliding_window=cfg["sliding_window_size"],
        rotary=[bool(r) for r in cfg["rope_layout"]],
        expert_width=cfg["moe_ffn_hidden_size"], num_experts=cfg["num_routed_experts"], experts_held=held(cfg),
        top_k=cfg["moe_num_active_primary_experts"], norm_topk_prob=cfg["norm_topk_prob"],
        expert_form=dict(activation="relu", router_ahead=True),
        embedding_std=cfg["embedding_std"], routing_seed=cfg["routing_seed"],
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

        sample = attention_sample(job["seq_len"])
        stage_pairs = layers.assign(np.stack(np.broadcast_arrays(
            np.arange(STAGE_ROWS)[:, None], sample[None, :]), -1).astype("int32"))
        tokens = expert_sample(STAGE_ROWS * job["seq_len"])
        token_pairs = layers.assign(np.stack([tokens // job["seq_len"], tokens % job["seq_len"]], -1).astype("int32"))

        def sampled_tokens(name):    # (sample, d) of a (B, L, d) variable
            return layers.gather_nd(rows(name), token_pairs).name

        stages = []
        input_norms = [op for op in of("rms_norm") if op.inputs.get("Scale", [""])[0].endswith(".ln1.w")]
        for norm, router, experts in zip(input_norms, of("moe_router"), of("moe_experts")):
            stages += [router.outputs["TopKIndex"][0], rows(norm.inputs["X"][0]).name, rows(router.outputs["TopKProb"][0]).name,
                       sampled_tokens(experts.outputs["Out"][0]), sampled_tokens(experts.inputs["X"][0])]
        attentions = of("fused_attention")
        for i in STAGE_LAYERS:
            attention = attentions[i]
            heads_major = attention.attr("layout", "bhld") == "bhld"

            def by_position(name, heads_major=heads_major):    # the stage rows as (rows, L, H, dh)
                t = rows(name)
                return layers.transpose(t, [0, 2, 1, 3]) if heads_major else t

            stages += [layers.gather_nd(by_position(attention.inputs["Q"][0]), stage_pairs).name,
                       by_position(attention.inputs["K"][0]).name, by_position(attention.inputs["V"][0]).name,
                       layers.gather_nd(by_position(attention.outputs["Out"][0]), stage_pairs).name]
        rate = layers.learning_rate_scheduler.linear_lr_warmup(
            job["learning_rate"], job["lr_warmup_steps"], job["lr_warmup_start"], job["learning_rate"])
        fluid.optimizer.Adam(learning_rate=rate, beta1=job["adam_beta1"], beta2=job["adam_beta2"],
                             epsilon=job["adam_epsilon"]).minimize(fetches["loss"])
    return (main, startup, feeds, fetches["loss"], [fetches["loss"].name, sampled.name] + stages)


# -- the arithmetic --------------------------------------------------------------

def _pairs(seq: int, window) -> int:
    """(query, key) pairs a layer's rule allows among `seq` positions."""
    w = seq if window is None else min(window, seq)
    return w * (w + 1) // 2 + (seq - w) * w


def flops_per_sample(cfg: dict, job: dict) -> float:
    """Operations the forward and backward passes of one sequence require
    (matrix multiplications only, 2 per multiply-add, backward twice the
    forward, nothing recomputed): per position a layer's router, its four
    attention projections and the position's held experts, THREE QUARTERS of
    one on average (6 chosen x 8 held of 64, a uniform router's share), three
    matrices each; each attention's two products over the pairs its rule
    ALLOWS (the causal triangle for the full layer, the band for a window
    layer); and the head.  Nothing for the rotations, the norms and the gates."""
    seq, d = job["seq_len"], cfg["hidden_size"]
    heads, kv, head = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    held_share = cfg["moe_num_active_primary_experts"] * cfg["moe_num_primary_experts"] / cfg["num_routed_experts"]
    per_position = (2 * d * cfg["num_routed_experts"] + 2 * (2 * d * heads * head + 2 * d * kv * head)
                    + held_share * 3 * 2 * d * cfg["moe_ffn_hidden_size"])
    forward = seq * (len(cfg["layer_types"]) * per_position + 2.0 * d * cfg["vocab_size"])
    forward += sum(2 * 2.0 * heads * head * _pairs(seq, window) for window in _windows(cfg))
    return 3.0 * forward


def _attention_flops(cfg: dict, job: dict, sliding: bool) -> float:
    pairs = sum(_pairs(job["seq_len"], window) for window in _windows(cfg) if (window is not None) == sliding)
    return 6 * 2.0 * cfg["num_attention_heads"] * cfg["head_dim"] * pairs * job["batch_per_chip"]


def _attention_bytes(cfg: dict, job: dict, sliding: bool) -> float:
    layers_ = sum((window is not None) == sliding for window in _windows(cfg))
    return float(2 * 2 * (2 * cfg["num_attention_heads"] + 2 * cfg["num_key_value_heads"]) * cfg["head_dim"]
                 * job["seq_len"] * job["batch_per_chip"] * layers_)


def window_attention_flops(cfg: dict, job: dict) -> float:
    """Operations of a step's window attentions (the three rotary layers): the
    two products forward and the four backward over the pairs the rule ALLOWS
    (the band of 4096 keys), 28 heads of 128; nothing for a masked pair a kernel
    computes anyway, nothing for the scores backward computes again and nothing
    for a forward that a `recompute_scope` makes a second time.  The same work
    whatever implements it."""
    return _attention_flops(cfg, job, True)


def window_attention_bytes(cfg: dict, job: dict) -> float:
    """Bytes those attentions have to move at the least: q, k, v and the output
    once forward and their four gradients once backward, bf16."""
    return _attention_bytes(cfg, job, True)


def causal_attention_flops(cfg: dict, job: dict) -> float:
    """`window_attention_flops` for the full layers, which have no positions:
    the same six products over the causal triangle's allowed pairs, L (L + 1) / 2
    a head and sequence."""
    return _attention_flops(cfg, job, False)


def causal_attention_bytes(cfg: dict, job: dict) -> float:
    """`window_attention_bytes` for the full layers."""
    return _attention_bytes(cfg, job, False)


# -- the reference ---------------------------------------------------------------

def rotate_halves(t, positions, theta: float, xp=None):
    """The rotary embedding over the pairs (i, i + dh / 2) of t [L, H, dh] at
    `positions` [L], in `xp` (jax.numpy, or numpy for a float64 check)."""
    if xp is None:
        import jax.numpy as xp
    half = t.shape[-1] // 2
    angle = positions[:, None].astype(t.dtype) * xp.asarray(theta ** (-np.arange(half, dtype=np.float64) / half), t.dtype)
    cos, sin = xp.cos(angle)[:, None, :], xp.sin(angle)[:, None, :]
    first, second = t[..., :half], t[..., half:]
    return xp.concatenate([first * cos - second * sin, second * cos + first * sin], -1)


def allowed(queries, keys, window):
    """May the query at position `queries` see the key at position `keys`?  The
    causal rule, and under a `window` the last `window` keys, the query's own
    among them.  Broadcasts; numpy or jax."""
    seen = keys <= queries
    return seen if window is None else seen & (keys > queries - window)


def reference(params: dict, batch: dict, cfg: dict, program=None, precision: str = "highest",
              attention_precision: str = None):
    """(loss, the sampled positions' logits [rows, sample, vocab], margin [rows,
    L], choice [layers, rows, L, 6], the float32 router, gate, up and down
    weights stacked by layer, (first held expert, window, theta), for each of
    `STAGE_LAYERS` the queries [rows, 28, sample, 128] and keys [rows, 4,
    sample, 128] at `attention_sample`'s positions, and for
    `reference_self_error` the first row's first layer: its normed input at those
    positions [sample, d], its query matrix, its queries [28, sample, 128], its
    first `SELF_KEYS` keys and values [4, ., 128] and its attention's output at
    those positions [28, sample, 128]) of `batch` in plain float32 jax.numpy, one
    sequence at a time; `params` maps the program's parameter names to arrays.
    No kernel, no cache and no [L, L] array: see the module's docstring.
    `precision` is the float32 products': "highest" is the reference;
    tools/chip_smallthinker_controls.py asks for `attention_precision`
    "default" (bf16 operands on the chip, the nearest precision below) in the
    attention's two products alone, to show that the comparison tells it."""
    import jax
    import jax.numpy as jnp

    depth, eps, theta = len(cfg["layer_types"]), cfg["rms_norm_eps"], float(cfg["rope_theta"])
    heads, kv_heads, head = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    group, top_k = heads // kv_heads, cfg["moe_num_active_primary_experts"]
    first, n_held = held(cfg)
    windows, rotates = _windows(cfg), [bool(r) for r in cfg["rope_layout"]]

    def p(name):
        return jnp.asarray(params[name], jnp.float32)

    def rms(x, gain):
        return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps) * p(gain)

    def attention(a, pre, positions, window, rotated):
        seq = a.shape[0]
        q = (a @ p(f"{pre}.q.w")).reshape(seq, heads, head)
        k = (a @ p(f"{pre}.k.w")).reshape(seq, kv_heads, head)
        v = (a @ p(f"{pre}.v.w")).reshape(seq, kv_heads, head)
        if rotated:
            q, k = rotate_halves(q, positions, theta), rotate_halves(k, positions, theta)
        q = q.reshape(seq, kv_heads, group, head).transpose(1, 2, 0, 3)           # [kv head, its query heads, L, dh]
        k, v = k.transpose(1, 0, 2), v.transpose(1, 0, 2)                          # [kv head, L, dh]
        block = min(seq, ATTENTION_BLOCK)
        blocks = []
        for start in range(0, seq, block):        # the queries of a block against the keys up to the block's end
            end = min(start + block, seq)
            seen = allowed(jnp.arange(start, end)[:, None], jnp.arange(end)[None, :], window)

            def a_group(operands, seen=seen):
                qs, ks, vs = operands
                with jax.default_matmul_precision(attention_precision or precision):
                    scores = jnp.einsum("gqd,kd->gqk", qs, ks) / np.sqrt(head)
                    return jnp.einsum("gqk,kd->gqd", jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), -1), vs)

            blocks.append(jax.lax.map(a_group, (q[:, :, start:end], k[:, :end], v[:, :end])))
        ctx = jnp.concatenate(blocks, 2).reshape(heads, seq, head)                 # head = kv head x 7 + its query head
        sample = attention_sample(seq)
        mixed = ctx.transpose(1, 0, 2).reshape(seq, heads * head) @ p(f"{pre}.out.w")
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
            logits = x @ p(f"{pre}.moe.router.w")                       # the layer's input ITSELF: no norm before it
            ranked = jnp.sort(logits, -1)[:, ::-1]
            kth, after = ranked[:, top_k - 1], ranked[:, top_k]
            chosen = logits >= kth[:, None]
            weight = jnp.where(chosen, jnp.exp(logits - ranked[:, :1]), 0.0)
            gates = weight / jnp.sum(weight, -1, keepdims=True)         # the softmax over the six chosen logits
            out, qk, rest = attention(rms(x, f"{pre}.ln1.w"), f"{pre}.attn", positions, windows[i], rotates[i])
            h = x + out
            if i in STAGE_LAYERS:
                staged[i] = qk + (rest if i == 0 else ())
            m = rms(h, f"{pre}.ln2.w")

            def expert(acc, ew, m=m):
                gate, up, down, g_e = ew
                return acc + ((jax.nn.relu(m @ gate) * (m @ up)) @ down) * g_e[:, None], None

            routed, _ = jax.lax.scan(
                expert, jnp.zeros_like(h),
                (p(f"{pre}.moe.gate.w"), p(f"{pre}.moe.up.w"), p(f"{pre}.moe.down.w"), gates[:, first:first + n_held].T))
            x = h + routed
            # the 6th and 7th probabilities of the softmax over all 64, as a share of the 6th
            margin = jnp.minimum(margin, 1.0 - jnp.exp(after - kth))
            choices.append(jnp.sort(jax.lax.top_k(logits, top_k)[1], -1))
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
        weights = tuple(jnp.stack([p(f"lm.l{i}.moe.{n}.w") for i in range(depth)]) for n in ("router", "gate", "up", "down"))
        window = next((w for w in windows if w is not None), 0)
        return ((loss, out, margin, choice.transpose(1, 0, 2, 3)) + weights
                + (jnp.asarray([first, window, theta], jnp.float32), q0, k0) + tuple(later)
                + (first_input[0], p("lm.l0.attn.q.w"), q0[0], keys[0], values[0], ctx[0]))


# -- the comparison ----------------------------------------------------------------

def _softmax_top(logits, k: int):
    """(the k largest logits' indices, the softmax over those k) of [tokens, experts] float64."""
    order = np.argsort(-logits, -1, kind="stable")[:, :k]
    top = np.take_along_axis(logits, order, -1)
    e = np.exp(top - top.max(-1, keepdims=True))
    return order, e / e.sum(-1, keepdims=True)


def stage_errors(choice, x, top_p, out, m, router, gate, up, down, first: int) -> dict:
    """One layer's router and held experts on the program's own tensors (see
    `ROUTER_RTOL`, `EXPERTS_RTOL`): its `choice` and `top_p` [tokens, 6] against
    the float64 router on THE LAYER'S INPUT `x` [tokens, d]; its experts' `out`
    [sample, d] against float32 numpy on their own input `m` [sample, d] under
    the program's own choice and weights, `expert_sample`'s tokens; `gate`, `up`,
    `down` hold the experts `first` on.  Beside each, the same stage a precision
    lower or with the nearest fault: the router's logits rounded to bf16, the
    experts' running sums in bf16, a SiLU for the ReLU."""
    tokens, k = choice.shape
    logits = x.astype("f8") @ router.astype("f8")
    ranked = np.sort(logits, -1)
    probs = np.exp(ranked - ranked[:, -1:])
    tie = (probs[:, -k] - probs[:, -k - 1]) < ROUTER_TIE * probs[:, -k]
    mine_choice, _ = _softmax_top(logits, k)
    differs = (np.sort(mine_choice, -1) != np.sort(choice, -1)).any(-1)

    def weights(values):   # of the program's choice: the softmax over its six logits
        top = np.take_along_axis(values, choice, -1)
        e = np.exp(top - top.max(-1, keepdims=True))
        return e / e.sum(-1, keepdims=True)

    mine = weights(logits)
    low_logits = _bf16(logits.astype("f4")).astype("f8")
    low_choice, _ = _softmax_top(low_logits, k)

    def held_of(c):  # [tokens, held]: which held experts a token chose
        return (c[..., None] == np.arange(first, first + gate.shape[0])).any(-2)

    sample = expert_sample(tokens)

    def experts(act, product=lambda a, w: a @ w, rounded=lambda t: t):
        total = np.zeros((len(sample), m.shape[1]), "f4")
        for e in range(gate.shape[0]):
            row, slot = np.nonzero(choice[sample] == first + e)
            rows_in = m[row]
            hidden = rounded(act(product(rows_in, rounded(gate[e]))) * product(rows_in, rounded(up[e]))
                             * top_p[sample[row], slot][:, None])
            total[row] += product(hidden, rounded(down[e]))
        return total

    def product_in_bf16(a, w):
        acc = np.zeros((a.shape[0], w.shape[1]), "f4")
        for i in range(0, a.shape[1], 8):
            acc = _bf16(acc + a[:, i:i + 8] @ w[i:i + 8])
        return acc

    def relu(t):
        return np.maximum(t, 0)

    want = experts(relu)
    mean_square = max(np.mean(np.square(want)), 1e-30)

    def off(got):
        return float(np.sqrt(np.mean(np.square(got - want)) / mean_square))

    return {
        "router_choice_differs": int((differs & ~tie).sum()),
        "router_ties": int((differs & tie).sum()),
        "router_prob_error": float((np.abs(top_p - mine) / mine).max()),
        "router_prob_error_bf16_logits": float((np.abs(weights(low_logits) - mine) / mine).max()),
        "held_choice_flips_bf16_logits": int((held_of(low_choice) != held_of(choice)).any(-1).sum()),
        "experts_error": off(out),
        "experts_error_bf16_sums": off(experts(relu, product_in_bf16, _bf16)),
        "experts_error_silu": off(experts(lambda t: t / (1.0 + np.exp(-t)))),
    }


def attention_errors(q, k, v, out, sample, window) -> dict:
    """The program's attention output at the sampled queries, `out` [rows,
    sample, Hq, dh], against float32 numpy on its own `q` [rows, sample, Hq, dh]
    and ALL its keys and values `k`, `v` [rows, L, Hkv, dh], each query against
    the keys `allowed` gives it under `window`; query head j reads key/value
    head j div (Hq / Hkv): largest |error| over the largest |output|; what the
    same float32 numpy reads against itself with its scores rounded to bf16,
    and with query head j reading key/value head j mod Hkv (the other grouping).

    Under a window, THE EDGE: one key more or less is one weight of ~4096 and
    moves the output by about as much as its bf16 rounding, which no largest
    error can tell.  So the error is measured ALONG what each such fault would
    add, over every sampled query past the window's length and every head: a
    rule that lacks the window's oldest key moves the output by D- = p (want -
    v_oldest) / (1 - p), p that key's weight; one that also sees the key before
    it by D+ = r (v_before - want) / (1 + r), r that key's weight over the
    allowed keys' sum.  `window_edge_missing` and `window_edge_extra` are the
    least-squares coefficients of (out - want) on D- and on D+, pooled over
    those queries and heads: 0 for the rule as stated, 1 for a window one key
    short or one key long, whatever the rounding (it is uncorrelated with D
    and averages out over ~4000 (query, head) pairs of 128 features)."""
    positions = np.asarray(sample)
    heads, kv_heads = q.shape[2], k.shape[2]
    group = heads // kv_heads
    seen = allowed(positions[:, None], np.arange(k.shape[1])[None, :], window)
    worst = rounded = regrouped = largest = 0.0
    edge = np.zeros((2, 2))                                  # [missing, extra] x [<d, D>, <D, D>]
    past = positions >= window if window is not None else np.zeros(len(positions), bool)
    for r in range(q.shape[0]):
        for h in range(heads):
            def weights(s):
                s = np.where(seen, s, -np.inf)
                e = np.exp(s - s.max(-1, keepdims=True))
                return e / e.sum(-1, keepdims=True)

            def scores_on(kv):
                return q[r, :, h] @ k[r, :, kv].T / np.sqrt(q.shape[-1])

            scores, values = scores_on(h // group), v[r, :, h // group]
            p = weights(scores)
            want = p @ values
            worst = max(worst, float(np.abs(out[r, :, h] - want).max()))
            rounded = max(rounded, float(np.abs(weights(_bf16(scores)) @ values - want).max()))
            regrouped = max(regrouped, float(np.abs(weights(scores_on(h % kv_heads)) @ v[r, :, h % kv_heads] - want).max()))
            largest = max(largest, float(np.abs(want).max()))
            if past.any():
                at, rows_ = positions[past], np.nonzero(past)[0]
                oldest, before = at - window + 1, at - window
                d = (out[r, rows_, h] - want[rows_]).astype("f8")
                weight = p[rows_, oldest][:, None].astype("f8")
                missing = weight * (want[rows_] - values[oldest]) / (1.0 - weight)
                masked = np.where(seen[rows_], scores[rows_], -np.inf).astype("f8")
                top = masked.max(-1, keepdims=True)
                ratio = np.exp(scores[rows_, before][:, None] - top) / np.exp(masked - top).sum(-1, keepdims=True)
                extra = ratio * (values[before] - want[rows_]) / (1.0 + ratio)
                edge += [[np.sum(d * missing), np.sum(missing * missing)], [np.sum(d * extra), np.sum(extra * extra)]]
    largest = max(largest, 1e-30)
    found = {"attention_error": worst / largest, "attention_error_bf16_scores": rounded / largest,
             "attention_error_other_grouping": regrouped / largest}
    if past.any():
        found["window_edge_missing"], found["window_edge_extra"] = (float(a / max(b, 1e-300)) for a, b in edge)
    return found


_PER_LAYER, _PER_STAGE = 5, 4   # the fetched variables a layer's routing stage and a staged attention take


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
    first, window, theta = (float(n) for n in np.asarray(want[8]))
    first, window, n_held = int(first), int(window) or None, np.asarray(want[5]).shape[1]
    tail = got[len(got) - _PER_STAGE * len(STAGE_LAYERS):]
    layers = [got[i:i + _PER_LAYER] for i in range(2, len(got) - len(tail), _PER_LAYER)]
    choice = np.sort(np.stack([np.asarray(layer[0]).reshape(want_choice.shape[1:]) for layer in layers]), -1)
    routed_differently = (choice != want_choice).any(axis=(0, 3))           # [rows, L]

    def held_choice(c):  # [layers, rows, L, held]: which held experts a position chose
        return (c[..., None] == np.arange(first, first + n_held)).any(-2)

    held_differs = (held_choice(choice) != held_choice(want_choice)).any(-1)          # [layers, rows, L]
    differs = held_differs.any(0)
    sampled = differs[:, logit_sample(seq)]
    err = np.abs(logits - want_logits).max(-1)
    stage_rows = np.asarray(layers[0][1]).shape[0]
    staged = stage_rows * seq
    stages = [stage_errors(np.asarray(c).reshape(tokens, k)[:staged], np.asarray(x, "f4").reshape(staged, -1),
                           np.asarray(p, "f4").reshape(staged, k), np.asarray(o, "f4"), np.asarray(m, "f4"),
                           *(np.asarray(w[i], "f4") for w in want[4:8]), first)
              for i, (c, x, p, o, m) in enumerate(layers)]
    scale = max(np.abs(want_logits).max(), 1e-9)
    summed = ("router_choice_differs", "router_ties", "held_choice_flips_bf16_logits")
    at = attention_sample(seq)
    attention, qk, qk_left_out = [], [], []
    for where, layer in enumerate(STAGE_LAYERS):
        q, key, v, out = (np.asarray(t, "f4") for t in tail[_PER_STAGE * where:_PER_STAGE * (where + 1)])
        attention.append(attention_errors(q, key, v, out, at, window if where else None))
        # the queries and keys of a position that chose other held experts in a layer BEFORE this one carry that
        # expert's output more or less, as its logits do: left out of the limit's reading as there, and read beside it
        want_q, want_k = (np.asarray(want[9 + 2 * where + j], "f4")[:stage_rows].transpose(0, 2, 1, 3) for j in (0, 1))
        off = [np.abs(mine - theirs).max(axis=(2, 3)) / np.abs(theirs).max()
               for mine, theirs in ((q, want_q), (key[:, at], want_k))]
        kept = ~held_differs[:layer, :stage_rows][..., at].any(0) if layer else np.ones((stage_rows, len(at)), bool)
        qk.append(float(max(e[kept].max(initial=0.0) for e in off)))
        qk_left_out.append(float(max(e[~kept].max(initial=0.0) for e in off)))
    # the yardstick's own precision, the first row's first layer: its queries against float64 on its own normed input,
    # and its attention's output at the sampled queries against float64 on its own q, k and v
    base = 9 + 2 * len(STAGE_LAYERS)
    first_q = np.asarray(want[base + 2], "f8").transpose(1, 0, 2)                        # [sample, H, dh]
    exact_q = (np.asarray(want[base], "f8") @ np.asarray(want[base + 1], "f8")).reshape(first_q.shape)
    own_keys, own_values = (np.asarray(want[base + j], "f8").transpose(1, 0, 2)[None] for j in (3, 4))
    near = at < own_keys.shape[1]                                                        # the queries that see those keys alone
    own = attention_errors(first_q[None, near], own_keys, own_values,
                           np.asarray(want[base + 5], "f8").transpose(1, 0, 2)[None, near], at[near], None)
    return {
        "reference_self_error": max(float(np.abs(first_q - exact_q).max() / np.abs(exact_q).max()),
                                    own["attention_error"]),
        "loss_error": abs(loss - want_loss) / max(abs(want_loss), 1e-6),
        "logit_error": float(err[~sampled].max(initial=0.0) / scale),
        "logit_error_left_out": float(err[sampled].max(initial=0.0) / scale),
        "tokens": int(tokens),
        "left_out": int(differs.sum()),
        "routed_differently": int(routed_differently.sum()),
        "under_margin": int((margin < ROUTING_MARGIN).sum()),
        "routed_differently_above_margin": int((routed_differently & (margin >= ROUTING_MARGIN)).sum()),
        **{name: (sum if name in summed else max)(stage[name] for stage in stages) for name in stages[0]},
        "router_choice_differs_share": sum(stage["router_choice_differs"] for stage in stages) / (staged * len(stages)),
        "held_rows_share": [float(held_choice(c[None]).sum() / (tokens * k)) for c in choice],
        "attention_error": max(a["attention_error"] for a in attention),
        "attention_error_bf16_scores": min(a["attention_error_bf16_scores"] for a in attention),
        "attention_error_other_grouping": min(a["attention_error_other_grouping"] for a in attention),
        "window_edge_missing": attention[-1].get("window_edge_missing", 0.0),
        "window_edge_extra": attention[-1].get("window_edge_extra", 0.0),
        "attention_errors": [a["attention_error"] for a in attention],
        "qk_error": max(qk), "qk_errors": qk, "qk_error_left_out": max(qk_left_out),
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
                      "experts_rtol": EXPERTS_RTOL, "attention_rtol": ATTENTION_RTOL, "window_edge_max": WINDOW_EDGE_MAX,
                      "qk_rtol": QK_RTOL,
                      "reference_self_rtol": REFERENCE_SELF_RTOL}),
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
        "ATTENTION_RTOL": found["attention_error"] <= ATTENTION_RTOL,
        "WINDOW_EDGE_MAX": max(abs(found["window_edge_missing"]), abs(found["window_edge_extra"])) <= WINDOW_EDGE_MAX,
        "QK_RTOL": found["qk_error"] <= QK_RTOL,
        "REFERENCE_RTOL": max(found["loss_error"], found["logit_error"]) <= REFERENCE_RTOL,
        "REFERENCE_SELF_RTOL": found["reference_self_error"] <= REFERENCE_SELF_RTOL,
    }
    return [name for name, passed in checks.items() if not passed]
