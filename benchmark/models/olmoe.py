"""OLMoE-1B-7B causal-LM pre-training: how the benchmark builds it through the
framework, a plain float32 reference of the same architecture, and the
operations one sequence needs.

Architecture: Muennighoff et al. 2024 (arXiv:2409.02060), sizes from
allenai/OLMoE-1B-7B-0125-Instruct `config.json`.  A layer, with a = rms(x),
m = rms(h) and p = softmax_f32(Wr m) over the 64 experts, not renormalised:

    h = x + Wo . Attn(rope(rms(Wq a)), rope(rms(Wk a)), Wv a; causal)
    y = h + sum_{e in top8(p)} p_e . Wdown_e(silu(Wgate_e m) * (Wup_e m))
    loss = CE(head(rms(y_L))) + 0.01 . load_balance + 0.001 . router_z

q/k-norm is over all 2048 projected features, before the heads are split;
rotary positions are rotate-half, theta 10000; load_balance = E . sum_e f_e P_e
over a batch (f_e the expert's share of the tokens x 8 assignments, P_e its
mean probability) and router_z = mean_t logsumexp(Wr m_t)^2, each the mean
over the layers.

Departures of the program under test
(`paddle_tpu.models.transformer.build_causal_lm`) from the published model,
which the reference follows so that the two compute the same function (the
configuration file's `departures` is this list, word for word):

  * one of the 16 layers (all alike: one layer is one period of the pattern);
  * 12576 of the 50304 vocabulary rows in the embedding and in the head: one chip's slice of both split four ways over a v5e four-chip host, and token ids and labels are drawn from those 12576;
  * Adam, not AdamW: the paper's decoupled weight decay of 0.1 is left out (the framework has no AdamW), its learning rate 4e-4, betas 0.9 / 0.95 and epsilon 1e-8 are kept, with no warm-up and no decay of the rate;
  * weights are random from the seed, N(0, 0.02) like the paper's truncated normal but not truncated, norm gains 1;
  * token ids are uniform random, so the cross entropy starts at ln(12576), and expert load is what N(0, 0.02) weights and then 8 memorised batches make it, not what trained weights on text would: the busiest expert has 2.9x the mean at the first step (causal attention gives the tokens a common mean, which the router reads) and 3.8x to 7.4x while the cell's ring of 8 batches is learnt by heart;
  * every position is a label (the next token), no document boundaries and no padding.
"""
from __future__ import annotations

import numpy as np

FEEDS = ("ids", "labels", "pos_ids")

#: A token may route differently in the program and in the reference where
#: the reference's 8th and 9th router probabilities lie closer than this, as
#: a share of the 8th.  Top-k is discontinuous and the program's router reads
#: a bf16 input: m = rms(h) comes out of some nine bf16 roundings (2**-9
#: relative each: the embedding, the norm, three projections, q/k-norm,
#: rotary, the attention's probabilities and output, the residual sum), so
#: each of the 2048 terms of a router logit is off by ~4e-3 of itself and the
#: gap between two logits (weights N(0, 0.02)) by ~5.5e-3, which is the
#: relative error of the probabilities' gap.  Measured at the published
#: widths (my chip runs, PR 26; 8 x 4096 tokens, three seeds): of the tokens
#: that routed differently half did so across a gap under 0.0037, 99% under
#: 0.020, the widest 0.028.  2**-4 is 2.2x the widest.  A token that routes
#: differently ABOVE this margin is a routing fault, not rounding, and fails
#: the check whatever the logits say.
ROUTING_MARGIN = 2.0 ** -4
#: The largest share of tokens that may be left out of the logit comparison
#: because they chose other experts (under the margin).  The gap between the
#: 8th and the 9th of 64 router logits of std 0.9 is 0.069 on average (1 /
#: (64 x the density at the 87.5th percentile)), so a gap error of 5.5e-3
#: flips ~0.8 x 5.5e-3 / 0.069 of the tokens: 4.61% to 4.83% measured over 9
#: seeds (my chip runs, PR 26), none in float32; the limit is a third above
#: the most seen.  ISSUE 26 asked for under 1%; that holds only where the
#: router's input carries ~5e-4 of error, which a bf16 residual stream cannot
#: (PERF.md, PR 26).  A token left out is NOT one the program got wrong: on
#: its OWN input the program's router chooses as float32 does (the router
#: stage below), and both chose their top 8 of probabilities that agree to
#: 0.6%.
LEFT_OUT_MAX = 0.065
#: ...and how far a left-out token's logits may be off, over the largest
#: |reference logit|: with N(0, 0.02) weights one expert's output is a tenth
#: of the residual stream, so another eighth expert moves the logits by 0.25
#: to 0.27 (my chip runs, PR 26, 9 seeds).  More than that is not one expert
#: swapped for its neighbour in rank.
LEFT_OUT_LOGIT_MAX = 0.4
#: Reference check on 8 seeded sequences, the larger of two errors: the
#: total loss, |program - reference| / reference, and the logits of the
#: tokens that chose the same experts in both, max |program - reference|
#: over the largest |reference logit|.  The loss holds the two auxiliary
#: losses and the cross entropy at ln(12576), so it catches a wrong
#: reduction, mask or coefficient and little else (1e-5 to 3e-5 on the
#: chip); the logits go through every product.  The program rounds
#: activations to bf16 over f32 master weights, f32 norm statistics, f32
#: router and f32 accumulation, and agrees with this reference to 1.11e-2 to
#: 1.31e-2 of the largest logit (my chip runs, PR 26, 9 seeds; the median
#: token 6.6e-3, the 99.9th percentile 9.7e-3: twice BERT's 5e-3, for the
#: router's probabilities carry the input's error into every expert's
#: weight).  End to end this is all a bf16 residual stream lets one see: a
#: router held in bf16, or experts that accumulate in bf16, move neither the
#: logits (the experts' output is a tenth of the stream) nor the left-out
#: share beyond the spread between seeds (tests/test_olmoe.py runs both
#: faults).  They are caught one stage at a time, below.
REFERENCE_RTOL = 2e-2
#: The two stages no end-to-end number resolves are checked on the
#: PROGRAM'S OWN router input m (fetched, bf16), so that upstream rounding
#: is not in the way.  The router: float32 probabilities of m under the f32
#: router weights.  The program's top 8 are those but for ties (8th and 9th
#: closer than `ROUTER_TIE` of the 8th), and its probabilities agree to
#: `ROUTER_RTOL`: 1.06e-4 to 1.08e-4 for the float32 router the issue asks
#: for on the chip (five seeds, no token routed elsewhere, no tie; the TPU's
#: float32 exp and log, for the CPU reads 1.7e-6); with the router's weights
#: in bf16 6.6e-3 and 394 tokens of 32768 routed elsewhere, with its logits
#: in bf16 1.2e-2 and 806 (my chip runs, PR 26, published widths; on the
#: CPU at hidden 512 3.6e-3 and 5.0e-3, tests/test_olmoe.py).
ROUTER_TIE = 1e-4
ROUTER_RTOL = 5e-4
#: The experts: for every 64th token, sum_k p_k Wdown(silu(Wgate m) * Wup m)
#: in float32 over the program's own m, choice and probabilities, against
#: the program's `moe_experts` output; root-mean-square error over the
#: root-mean-square output.  bf16 weights into products that accumulate in
#: float32, and gate, up, hidden and output each rounded once: 4.8e-3 (CPU,
#: hidden 512), for rounding errors average out over a contraction however
#: long.  A running sum held in bf16 does not: 1.6e-2 adding eight terms at
#: a time over 512 (tests/test_olmoe.py).
EXPERTS_RTOL = 8e-3
EXPERTS_SAMPLE = 512


def build(cfg: dict, job: dict):
    """(main, startup, feed variables by name, loss variable, the variables
    the reference is compared on: loss, logits and, layer by layer, the
    top-k expert choice, the router's input, the top-k probabilities and the
    experts' output) of the train program, as a user of the framework gets
    it by default."""
    from paddle_tpu.models import transformer

    main, startup, feeds, fetches = transformer.build_causal_lm(
        vocab_size=cfg["vocab_size"], seq_len=job["seq_len"],
        d_model=cfg["hidden_size"], n_layers=cfg["num_hidden_layers"],
        n_heads=cfg["num_attention_heads"], expert_width=cfg["intermediate_size"],
        num_experts=cfg["num_experts"], top_k=cfg["num_experts_per_tok"],
        norm_topk_prob=cfg["norm_topk_prob"], norm_eps=cfg["rms_norm_eps"],
        rope_theta=cfg["rope_theta"],
        load_balance_coef=cfg["assumed"]["load_balance_coef"],
        router_z_coef=cfg["assumed"]["router_z_coef"],
        learning_rate=job["learning_rate"], beta1=job["adam_beta1"],
        beta2=job["adam_beta2"], epsilon=job["adam_epsilon"],
        with_optimizer=True, dtype=cfg["compute_dtype"])
    ops = main.global_block().ops
    stages = [name for router, experts in zip((op for op in ops if op.type == "moe_router"),
                                              (op for op in ops if op.type == "moe_experts"))
              for name in (router.outputs["TopKIndex"][0], router.inputs["X"][0],
                           router.outputs["TopKProb"][0], experts.outputs["Out"][0])]
    return (main, startup, feeds, fetches["loss"],
            [fetches["loss"].name, fetches["logits"].name] + stages)


def make_batch(rng: np.random.RandomState, cfg: dict, job: dict,
               rows: int) -> dict:
    """One host batch as a reader yields it: int64 ids, the next token as
    the label of every position, positions 0..L-1."""
    seq = job["seq_len"]
    tokens = rng.randint(0, cfg["vocab_size"], size=(rows, seq + 1)).astype("int64")
    pos = np.tile(np.arange(seq, dtype="int64"), (rows, 1))
    return {"ids": tokens[:, :-1], "labels": tokens[:, 1:], "pos_ids": pos}


def flops_per_sample(cfg: dict, job: dict) -> float:
    """Operations the forward and backward passes of one sequence require
    (matrix multiplications only, 2 per multiply-add, backward twice the
    forward, nothing recomputed): per token and layer the four attention
    projections, the two attention products against the `seq_len` / 2 keys a
    causal mask leaves on average, the router, and three products in each
    of the token's 8 experts; once per token the head."""
    d, f = cfg["hidden_size"], cfg["intermediate_size"]
    seq, vocab = job["seq_len"], cfg["vocab_size"]
    per_layer = (2 * 4 * d * d + 2 * 2 * (seq / 2) * d + 2 * d * cfg["num_experts"]
                 + cfg["num_experts_per_tok"] * 3 * 2 * d * f)
    forward = cfg["num_hidden_layers"] * per_layer + 2 * d * vocab
    return 3.0 * forward * seq


def expert_gemm_flops(cfg: dict, tokens: int) -> float:
    """Operations of the grouped products of a training step over `tokens`
    tokens: gate, up and down for each token's 8 experts, 2 per
    multiply-add, and twice that again for the backward pass (a product
    for the rows' gradient and one for the weights').  What the algorithm
    needs: nothing for padding, nothing for experts a token did not choose."""
    rows = tokens * cfg["num_experts_per_tok"]
    forward = 3 * 2 * rows * cfg["hidden_size"] * cfg["intermediate_size"]
    return 3.0 * forward * cfg["num_hidden_layers"]


def expert_gemm_bytes(cfg: dict, tokens: int, itemsize: int = 2) -> float:
    """Bytes those products have to move at the least, at `itemsize` bytes
    an element: each of the three forward products reads its rows and every
    expert's matrix once and writes its rows; each has two products behind it
    in the backward pass, which read and write operands of the same sizes."""
    rows = tokens * cfg["num_experts_per_tok"]
    d, f, e = cfg["hidden_size"], cfg["intermediate_size"], cfg["num_experts"]
    one = rows * d + e * d * f + rows * f  # rows in, matrices, rows out (either way round)
    return 3.0 * 3 * one * itemsize * cfg["num_hidden_layers"]


def reference(params: dict, batch: dict, cfg: dict, program=None):
    """(loss, logits [rows, seq, vocab], margin [rows, seq], choice [layers,
    rows, seq, 8], and the float32 router, gate, up and down weights stacked
    by layer, for `stage_errors`) of `batch` in plain float32 jax.numpy, one
    sequence at a time; `params` maps the program's parameter names to
    arrays.  No kernel and no sort: attention is explicit scores under a
    causal mask, and every expert is applied to every token and masked by
    the top-8 choice.  `margin` is the gap between a token's 8th and 9th
    router probability as a share of the 8th, the smallest over the layers;
    `choice` the chosen experts in ascending order."""
    import jax
    import jax.numpy as jnp

    heads, layers = cfg["num_attention_heads"], cfg["num_hidden_layers"]
    n_experts, top_k = cfg["num_experts"], cfg["num_experts_per_tok"]
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    if cfg["norm_topk_prob"]:
        raise NotImplementedError("the reference follows OLMoE: probabilities not renormalised")

    def p(name):
        return jnp.asarray(params[name], jnp.float32)

    def rms(x, name):
        return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps) * p(name)

    def rope(t, pos):  # t [H, L, dh]
        half = t.shape[-1] // 2
        angle = pos.astype(jnp.float32)[:, None] * theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
        cos = jnp.concatenate([jnp.cos(angle)] * 2, -1)
        sin = jnp.concatenate([jnp.sin(angle)] * 2, -1)
        rotated = jnp.concatenate([-t[..., half:], t[..., :half]], -1)
        return t * cos + rotated * sin

    def one_sequence(row):
        ids, labels, pos = row
        x = p("lm.tok_emb")[ids]
        seq, d = x.shape
        margin = jnp.full((seq,), jnp.inf)
        counts, prob_sums, z_sums, choices = [], [], [], []
        for i in range(layers):
            pre = f"lm.l{i}"
            a = rms(x, f"{pre}.ln1.w")

            def split(t):
                return t.reshape(seq, heads, d // heads).transpose(1, 0, 2)

            q = rope(split(rms(a @ p(f"{pre}.attn.q.w"), f"{pre}.attn.q_norm.w")), pos)
            k = rope(split(rms(a @ p(f"{pre}.attn.k.w"), f"{pre}.attn.k_norm.w")), pos)
            v = split(a @ p(f"{pre}.attn.v.w"))
            scores = jnp.einsum("hqd,hkd->hqk", q, k) / np.sqrt(d // heads)
            scores = jnp.where(jnp.tril(jnp.ones((seq, seq), bool)), scores, -jnp.inf)
            ctx = jnp.einsum("hqk,hkd->hqd", jax.nn.softmax(scores, -1), v)
            h = x + ctx.transpose(1, 0, 2).reshape(seq, d) @ p(f"{pre}.attn.out.w")
            m = rms(h, f"{pre}.ln2.w")
            logits = m @ p(f"{pre}.moe.router.w")
            probs = jax.nn.softmax(logits, -1)
            ranked = jnp.sort(probs, -1)[:, ::-1]
            kth, after = ranked[:, top_k - 1], ranked[:, top_k]
            chosen = probs >= kth[:, None]
            weight = jnp.where(chosen, probs, 0.0)  # [seq, experts]

            def expert(acc, ew):
                gate, up, down, w_e = ew
                return acc + (jax.nn.silu(m @ gate) * (m @ up)) @ down * w_e[:, None], None

            moe_out, _ = jax.lax.scan(
                expert, jnp.zeros_like(h),
                (p(f"{pre}.moe.gate.w"), p(f"{pre}.moe.up.w"), p(f"{pre}.moe.down.w"), weight.T))
            x = h + moe_out
            margin = jnp.minimum(margin, (kth - after) / kth)
            counts.append(jnp.sum(chosen, 0).astype(jnp.float32))
            prob_sums.append(jnp.sum(probs, 0))
            z_sums.append(jnp.sum(jnp.square(jax.nn.logsumexp(logits, -1))))
            choices.append(jnp.sort(jax.lax.top_k(probs, top_k)[1], -1))
        out = rms(x, "lm.final_norm.w") @ p("lm.head.w")
        logp = jax.nn.log_softmax(out, -1)
        ce_sum = -jnp.take_along_axis(logp, labels[:, None], 1)[:, 0].sum()
        return (out, margin, jnp.stack(choices), ce_sum, jnp.stack(counts),
                jnp.stack(prob_sums), jnp.stack(z_sums))

    with jax.default_matmul_precision("highest"):
        rows = tuple(jnp.asarray(batch[n], jnp.int32) for n in FEEDS)
        out, margin, choice, ce_sum, counts, prob_sums, z_sums = jax.lax.map(one_sequence, rows)
        tokens = rows[0].size
        share = counts.sum(0) / (tokens * top_k)          # [layers, experts]
        balance = (n_experts * jnp.sum(share * prob_sums.sum(0) / tokens, -1)).mean()
        z_loss = (z_sums.sum(0) / tokens).mean()
        loss = (ce_sum.sum() / tokens + cfg["assumed"]["load_balance_coef"] * balance
                + cfg["assumed"]["router_z_coef"] * z_loss)
        weights = tuple(jnp.stack([p(f"lm.l{i}.moe.{n}.w") for i in range(layers)])
                        for n in ("router", "gate", "up", "down"))
        return (loss, out, margin, choice.transpose(1, 0, 2, 3)) + weights


def stage_errors(choice, m, top_p, out, router, gate, up, down) -> dict:
    """One layer's router and experts on the program's own router input
    `m` [tokens, d] (see `ROUTER_RTOL`, `EXPERTS_RTOL`): its `choice` and
    `top_p` [tokens, 8] and its experts' `out` [tokens, d] against float32
    numpy over the float32 weights."""
    tokens, k = choice.shape
    logits = (m @ router).astype("f8")
    probs = np.exp(logits - logits.max(-1, keepdims=True))
    probs /= probs.sum(-1, keepdims=True)
    ranked = np.sort(probs, -1)
    tie = (ranked[:, -k] - ranked[:, -k - 1]) < ROUTER_TIE * ranked[:, -k]
    differs = (np.sort(np.argsort(-probs, -1)[:, :k], -1) != np.sort(choice, -1)).any(-1)
    mine = np.take_along_axis(probs, choice, -1)
    sample = np.arange(0, tokens, max(tokens // EXPERTS_SAMPLE, 1))
    want = np.zeros((len(sample), m.shape[1]), "f4")
    for e in range(gate.shape[0]):
        row, slot = np.nonzero(choice[sample] == e)
        x = m[sample[row]]
        g = x @ gate[e]
        want[row] += ((g / (1.0 + np.exp(-g)) * (x @ up[e])) @ down[e]) * top_p[sample[row], slot][:, None]
    return {
        "router_choice_differs": int((differs & ~tie).sum()),
        "router_ties": int((differs & tie).sum()),
        "router_prob_error": float((np.abs(top_p - mine) / mine).max()),
        "experts_error": float(np.sqrt(np.mean(np.square(out[sample] - want))
                                       / max(np.mean(np.square(want)), 1e-30))),
    }


def compare(got, want) -> dict:
    """The program's (loss, logits and, layer by layer, top-k choice,
    router input, top-k probabilities, experts' output) against the
    reference's (loss, logits, margin, choice, weights): the two errors
    `REFERENCE_RTOL` bounds, the routing account, and the worst layer's
    stage errors."""
    loss, want_loss = float(np.asarray(got[0]).reshape(-1)[0]), float(want[0])
    logits, want_logits = np.asarray(got[1], "f4"), np.asarray(want[1], "f4")
    margin, want_choice = np.asarray(want[2]), np.asarray(want[3])
    tokens, k = margin.size, want_choice.shape[-1]
    layers = [got[i:i + 4] for i in range(2, len(got), 4)]
    choice = np.sort(np.stack([np.asarray(layer[0]).reshape(want_choice.shape[1:])
                               for layer in layers]), -1)
    differs = (choice != want_choice).any(axis=(0, 3))           # [rows, seq]
    kept = ~differs
    err = np.abs(logits.reshape(want_logits.shape) - want_logits).max(-1)
    stages = [stage_errors(np.asarray(c).reshape(tokens, k), np.asarray(m, "f4").reshape(tokens, -1),
                           np.asarray(p, "f4").reshape(tokens, k), np.asarray(o, "f4").reshape(tokens, -1),
                           *(np.asarray(w[i], "f4") for w in want[4:8]))
              for i, (c, m, p, o) in enumerate(layers)]
    return {
        "loss_error": abs(loss - want_loss) / max(abs(want_loss), 1e-6),
        "logit_error": float(err[kept].max(initial=0.0) / max(np.abs(want_logits).max(), 1e-9)),
        "logit_error_left_out": float(err[differs].max(initial=0.0)
                                      / max(np.abs(want_logits).max(), 1e-9)),
        "tokens": int(differs.size),
        "left_out": int(differs.sum()),
        "under_margin": int((margin < ROUTING_MARGIN).sum()),
        "routed_differently_above_margin": int((differs & (margin >= ROUTING_MARGIN)).sum()),
        **{name: max(stage[name] for stage in stages) for name in stages[0]},
    }


def reference_error(got, want) -> float:
    """How far the program is from the reference, as `REFERENCE_RTOL` counts
    it: the larger of the loss's and the logits' error, the logits over the
    tokens that chose the same experts in both.  Tokens that chose others
    are left out AND COUNTED (the `reference_routing` line of the run).  A
    failure (infinite error) is: more than `LEFT_OUT_MAX` of them, one whose
    8th and 9th probabilities are further apart than `ROUTING_MARGIN`, one
    whose logits are off by more than `LEFT_OUT_LOGIT_MAX`, or a router or
    experts that miss float32 on the program's own input by more than
    `ROUTER_RTOL` or `EXPERTS_RTOL`."""
    import json

    found = compare(got, want)
    print(json.dumps({"info": "reference_routing", **found,
                      "left_out_share": found["left_out"] / found["tokens"],
                      "routing_margin": ROUTING_MARGIN, "left_out_max": LEFT_OUT_MAX,
                      "left_out_logit_max": LEFT_OUT_LOGIT_MAX, "router_rtol": ROUTER_RTOL,
                      "experts_rtol": EXPERTS_RTOL}),
          flush=True)
    if (found["routed_differently_above_margin"]
            or found["left_out"] > LEFT_OUT_MAX * found["tokens"]
            or found["logit_error_left_out"] > LEFT_OUT_LOGIT_MAX
            or found["router_choice_differs"]
            or not found["router_prob_error"] <= ROUTER_RTOL
            or not found["experts_error"] <= EXPERTS_RTOL):
        return float("inf")
    return max(found["loss_error"], found["logit_error"])
