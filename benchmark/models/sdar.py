"""SDAR-30B-A3B-Chat block-diffusion training: how the benchmark builds it
through the framework, a plain float32 reference of the same architecture, and
the operations one sequence needs.

Architecture: JetLM/SDAR-30B-A3B-Chat `config.json` (`model_type: sdar_moe`,
Qwen3-MoE's block); training as a block-diffusion language model follows
BD3-LM (Arriola et al. 2025, arXiv:2503.09573): masked diffusion inside a
block, autoregressive across blocks.  With a = rms(x), m = rms(h), eps 1e-6,
L tokens of data, block length B, positions i in [0, 2L): i < L is the noised
copy, i >= L the clean one; blk(i) = (i mod L) div B; rotary position i mod L
for both copies:

    z      = [x_t ; x_0],  x_t[i] = MASK if u_i < t_blk(i) else x_0[i],  t_b ~ U(eps_t, 1] per block, u_i ~ U[0,1)
    q_j    = rope(rms_128(Wq_j a)),  j < 32;   k_g = rope(rms_128(Wk_g a)),  v_g = Wv_g a,  g < 4   (theta 1e6, rotate-half)
    h      = x + Wo . concat_j softmax(q_j k_(j div 8)^T / sqrt(128) + M) v_(j div 8)
    M[i,j] = 0 where   (i <  L, j <  L, blk(j) == blk(i))      a noised block sees itself, both ways
                    or (i <  L, j >= L, blk(j) <  blk(i))      ... and the CLEAN blocks strictly before it
                    or (i >= L, j >= L, blk(j) <= blk(i))      a clean block sees clean blocks up to itself
             -inf elsewhere (a clean query never sees a noised key)
    p      = softmax_f32(Wr m) over 128;  S = top8(p);  g_e = p_e / sum_{e' in S} p_e'
    y      = h + sum_{e in S and e in HELD} g_e . Wdown_e( silu(Wgate_e m) * (Wup_e m) ),   HELD = {0..15}
    loss   = 1/(rows . L) . sum_{i < L, x_t[i] == MASK}  (1 / t_blk(i)) . CE( head(rms(y_i)), x_0[i] )

`rms_128` is an RMSNorm over each head's 128 features with one 128-gain shared
by the heads (Qwen3-MoE's `q_norm` / `k_norm`), not OLMoE's norm over the
whole projected width.  The head and the loss run over the noised half only
(y[:, :L]).  The renormalisation is over all eight chosen experts, held or
not, so that the eight chips' shares of a layer add up to the layer.

Departures of the program under test
(`paddle_tpu.models.transformer.build_causal_lm`) from the published model,
which the reference follows so that the two compute the same function (the
configuration file's `departures` is this list, word for word):

  * four of the 48 layers (all alike, every one sparse: one layer is one period of the pattern; further layers lie on further chips as pipeline stages);
  * 16 of the 128 experts of every layer, experts 0 to 15: this chip's share of a layer whose experts are split over eight chips; the router keeps its 128 outputs, its top 8 and its renormalisation over all eight chosen, and what the 112 absent experts would have added is left out of the layer's output, in the program and in the reference alike, with no exchange standing in for the seven absent chips;
  * 18992 of the 151936 vocabulary rows in the embedding and in the head: one chip's slice of both split eight ways by row; token ids and labels are drawn from the slice, whose last id serves as the mask token, and the loss is over the slice;
  * the training recipe is assumed, for `config.json` gives none: block length 4, absorbing-state masking with t uniform on (1e-3, 1] for each block, the weight 1/t on masked positions, the sum normalised by rows x L, a masked position's logit predicting that position's own token;
  * Adam for AdamW (the framework has no AdamW), learning rate 1e-4, betas 0.9 / 0.95, epsilon 1e-8, no warm-up, no decay and no auxiliary loss;
  * weights are random, N(0, 0.02) from the run's seed, norm gains 1, but for the token embedding, N(0, 1) (the residual stream starts at unit scale, so that a token's own embedding and not the attention's average over its context is what the routers read: at N(0, 0.02) every token chooses much alike and a chip's share of the rows hangs on the seed, 4% to 22% a layer), and for the embedding and the routers' matrices together, which come from the configuration's `routing_seed` and not from the run's: they decide which experts a token meets, as a checkpoint's do, and which of the eight chips this is;
  * token ids are uniform random with no padding and no document boundaries, so the cross entropy starts near ln(18992); the masked quarter of the positions carries one embedding and chooses its eight experts alike in every layer, of which this chip holds one a layer (`routing_seed` was chosen for that: the average chip's share, 12.5% of the rows).
"""
from __future__ import annotations

import numpy as np

FEEDS = ("ids", "labels", "pos_ids", "loss_weight")
MASK = "block_diffusion"

#: Every limit below was set from two readings at the published widths (my chip
#: runs, PR 32: eight runs of the cell, eight seeds, 8 x 8192 positions each;
#: PERF.md has the table): what the program reads, and what the same stage reads a
#: precision lower, which each run prints beside it (`*_bf16_*` in its
#: `reference_routing` line).
#:
#: A token may route differently in the program and in the reference where the
#: reference's 8th and 9th router probabilities lie closer than this, as a
#: share of the 8th (benchmark/models/olmoe.py has the argument: top-k is
#: discontinuous and the program's router reads a bf16 input).  The margin is
#: OLMoE's.  With 128 outputs and four layers all but 2% of the positions have
#: some layer under it, so this says little here; the stage check of the
#: router on its own input says the rest.  A token that routes differently
#: ABOVE it is a routing fault.
ROUTING_MARGIN = 2.0 ** -4
#: Only a flip that moves a HELD expert in or out of a token's eight changes
#: what this chip adds (one among the 112 absent changes the renormalising sum
#: by the two probabilities' difference), so the logit comparison leaves out the
#: positions whose held choice differs in some layer and counts them:
#: 2.56% to 2.78% (11.4% to 12.1% routed differently at all); the limit is
#: 1.8x the most seen.
LEFT_OUT_MAX = 0.05
#: ... and how far a left-out position's logits may be off, over the largest
#: |reference logit|: one held expert's output more or less, 3.0e-2 to 3.4e-2.
LEFT_OUT_LOGIT_MAX = 0.1
#: The larger of the loss's relative error (0 to 2e-5) and the noised half's
#: logits' error over the largest |reference logit|, on the positions that
#: chose alike: 6.0e-3 to 8.3e-3 (bf16 activations over float32 masters through
#: four layers).  End to end a bf16 router or bf16 sums in the experts do not
#: show (benchmark/models/olmoe.py); they are caught a stage at a time.
REFERENCE_RTOL = 2e-2
#: The stages no end-to-end number resolves, on the PROGRAM'S OWN tensors.
#: The router on its own input m (float32 probabilities, renormalised over the
#: chosen eight): 5.3e-6 to 5.8e-6, no position routed elsewhere (0 to 1 tie);
#: with its logits rounded to bf16 1.5e-2 to 1.7e-2.
ROUTER_TIE = 1e-4
ROUTER_RTOL = 3e-4
#: The held experts on the program's own m, choice and weights: root-mean-square
#: error over the root-mean-square output, every `EXPERTS_SAMPLE`-th of the
#: tokens: 4.72e-3 to 4.82e-3 (bf16 operands into float32 accumulation); with
#: the running sums held in bf16, eight terms at a time, 3.13e-2 to 3.23e-2.
EXPERTS_RTOL = 1.2e-2
EXPERTS_SAMPLE = 512
#: The attention on the program's own q, k and v of the FIRST layer, for
#: `ATTENTION_SAMPLE` queries of every row and head against all keys under the
#: dense mask, float32 scores: largest error over the largest |output|, 2.6e-3
#: to 4.1e-3 (the kernel rounds the queries' scaling, the probabilities and
#: the output to bf16).  What it catches is a wrong mask or a wrong key head,
#: which read 0.3 and more (tests/test_sdar.py).  It does NOT tell bf16 scores
#: apart: the same reference with its scores rounded to bf16 reads 2.3e-3 to
#: 4.7e-3 against itself (`attention_error_bf16_scores`), no more than the
#: roundings the kernel makes by design, for under the per-head norm with unit
#: gains the scores are of order 1 (PERF.md, section 7).
ATTENTION_RTOL = 1e-2
ATTENTION_SAMPLE = 192
#: ... and the first layer's queries and keys themselves, at the sampled
#: positions, against the reference's (after the per-head norm and the
#: rotation): largest error over the largest |value|, 6.4e-3 to 8.5e-3.  The
#: first layer's, because only the embedding's, one norm's and one projection's
#: roundings lie before them.  A norm over the whole projected width instead of
#: each head moves a head's scale by its own spread, 1 / sqrt(2 x 128) = 6% at a
#: standard deviation, the widest of 32 heads by 12 to 15%.
QK_RTOL = 2.5e-2


def attention_sample(positions: int):
    """The positions whose queries the attention stage checks: spread over
    both halves by a multiplicative hash, the same in the reference and in
    the comparison."""
    return np.unique((np.arange(ATTENTION_SAMPLE, dtype=np.int64) * 2654435761 + 7) % positions)


def held(cfg: dict) -> tuple:
    return (cfg["experts_held_first"], cfg["num_experts"])


def build(cfg: dict, job: dict):
    """(main, startup, feed variables by name, loss variable, the variables
    the reference is compared on: loss, the noised half's logits, layer by
    layer the top-k expert choice, the router's input, the top-k weights and
    the held experts' output, and the first layer's attention: q, k, v, out) of
    the train program, as a user of the framework gets it."""
    from paddle_tpu.models import transformer

    main, startup, feeds, fetches = transformer.build_causal_lm(
        vocab_size=cfg["vocab_size"], seq_len=2 * job["seq_len"],
        d_model=cfg["hidden_size"], n_layers=cfg["num_hidden_layers"],
        n_heads=cfg["num_attention_heads"], n_kv_heads=cfg["num_key_value_heads"],
        head_dim=cfg["head_dim"], qk_norm="head", expert_width=cfg["moe_intermediate_size"],
        num_experts=cfg["num_routed_experts"], experts_held=held(cfg),
        top_k=cfg["num_experts_per_tok"], norm_topk_prob=cfg["norm_topk_prob"],
        norm_eps=cfg["rms_norm_eps"], rope_theta=cfg["rope_theta"],
        attention_mask=(MASK, cfg["block_length"]), loss_positions=job["seq_len"],
        load_balance_coef=0.0, router_z_coef=0.0,
        embedding_std=cfg["embedding_std"], routing_seed=cfg["routing_seed"],
        learning_rate=job["learning_rate"], beta1=job["adam_beta1"],
        beta2=job["adam_beta2"], epsilon=job["adam_epsilon"],
        with_optimizer=True, dtype=cfg["compute_dtype"])
    ops = main.global_block().ops
    stages = [name for router, experts in zip((op for op in ops if op.type == "moe_router"),
                                              (op for op in ops if op.type == "moe_experts"))
              for name in (router.outputs["TopKIndex"][0], router.inputs["X"][0],
                           router.outputs["TopKProb"][0], experts.outputs["Out"][0])]
    attention = next(op for op in ops if op.type == "fused_attention")
    stages += [attention.inputs[s][0] for s in ("Q", "K", "V")] + [attention.outputs["Out"][0]]
    return (main, startup, feeds, fetches["loss"],
            [fetches["loss"].name, fetches["logits"].name] + stages)


def make_batch(rng: np.random.RandomState, cfg: dict, job: dict, rows: int) -> dict:
    """One host batch as a reader yields it: L tokens of data x_0 drawn from
    the slice below its last id, which is the mask token; a noise level t for
    every block, uniform on (noise_eps, 1]; x_t masks each position with
    probability t of its block; ids = [x_t ; x_0], positions 0..L-1 twice,
    labels x_0, and the loss's weight 1/t on the masked positions."""
    seq, block = job["seq_len"], cfg["block_length"]
    mask_id = cfg["vocab_size"] - 1
    clean = rng.randint(0, mask_id, size=(rows, seq)).astype("int64")
    t = 1.0 - (1.0 - cfg["noise_eps"]) * rng.rand(rows, seq // block)
    t = np.repeat(t, block, axis=1)
    masked = rng.rand(rows, seq) < t
    noised = np.where(masked, mask_id, clean)
    pos = np.tile(np.arange(seq, dtype="int64"), (rows, 2))
    return {"ids": np.concatenate([noised, clean], axis=1), "labels": clean, "pos_ids": pos,
            "loss_weight": np.where(masked, 1.0 / t, 0.0).astype("float32")}


def allowed_pairs(seq: int, block: int) -> int:
    """(query, key) pairs M allows among the 2 x `seq` positions: a noised
    block itself, the clean blocks strictly before it, and for a clean block
    the clean blocks up to itself."""
    n = seq // block
    return block * block * (n + n * (n - 1) // 2 + n * (n + 1) // 2)


def flops_per_sample(cfg: dict, job: dict) -> float:
    """Operations the forward and backward passes of one sequence require
    (matrix multiplications only, 2 per multiply-add, backward twice the
    forward, nothing recomputed): per position (2 x seq_len of them) and layer
    the four attention projections at 32 and 4 heads of 128, the router, and
    three products in each of the position's held experts, ONE on average
    (8 chosen x 16 held of 128, a uniform router's share); per layer the two
    attention products over the pairs the mask allows; once for each of the
    seq_len noised positions the head."""
    d, f, seq = cfg["hidden_size"], cfg["moe_intermediate_size"], job["seq_len"]
    q_width = cfg["num_attention_heads"] * cfg["head_dim"]
    kv_width = cfg["num_key_value_heads"] * cfg["head_dim"]
    held_share = cfg["num_experts_per_tok"] * cfg["num_experts"] / cfg["num_routed_experts"]
    per_position = (2 * d * (2 * q_width + 2 * kv_width) + 2 * d * cfg["num_routed_experts"]
                    + held_share * 3 * 2 * d * f)
    attention = 2 * 2 * allowed_pairs(seq, cfg["block_length"]) * q_width
    forward = (cfg["num_hidden_layers"] * (2 * seq * per_position + attention)
               + seq * 2 * d * cfg["vocab_size"])
    return 3.0 * forward


def attention_flops(cfg: dict, job: dict) -> float:
    """Operations of a training step's attention products over the pairs the
    mask ALLOWS: q k^T and p v forward, four products of the same size
    backward (dv, dp, dq, dk), 2 per multiply-add, for every sequence, layer
    and query head.  Nothing for a masked pair a kernel computes anyway, and
    nothing for the scores a backward kernel computes again."""
    pairs = allowed_pairs(job["seq_len"], cfg["block_length"])
    per_head = 2 * pairs * cfg["head_dim"]
    return (6.0 * per_head * cfg["num_attention_heads"] * cfg["num_hidden_layers"]
            * job["batch_per_chip"])


def attention_bytes(cfg: dict, job: dict, itemsize: int = 2) -> float:
    """Bytes those products have to move at the least: forward reads q, k and
    v and writes the output; backward reads those four and the output's
    gradient and writes the three gradients; each once, at the heads they have."""
    positions, dh = 2 * job["seq_len"], cfg["head_dim"]
    hq, hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    forward = (2 * hq + 2 * hkv) * positions * dh
    backward = (4 * hq + 4 * hkv) * positions * dh
    return float((forward + backward) * itemsize * cfg["num_hidden_layers"] * job["batch_per_chip"])


def reference(params: dict, batch: dict, cfg: dict, program=None):
    """(loss, the noised half's logits [rows, L, vocab], margin [rows, 2L],
    choice [layers, rows, 2L, 8], and the float32 router, gate, up and down
    weights stacked by layer, for `stage_errors`) of `batch` in plain float32
    jax.numpy, one sequence and two query heads at a time; `params` maps the
    program's parameter names to arrays.  No kernel and no sort: attention is
    explicit scores under M built densely, and every held expert is applied to
    every position and weighted by the renormalised choice.  `margin` is the
    gap between a position's 8th and 9th router probability as a share of the
    8th, the smallest over the layers; `choice` the chosen experts, ascending.
    Then (first held expert, block length), which `compare` needs and the
    runner hands it nothing else, and the first layer's queries and keys at
    `attention_sample`'s positions, [rows, heads, sample, 128]."""
    import jax
    import jax.numpy as jnp

    layers, eps, theta = cfg["num_hidden_layers"], cfg["rms_norm_eps"], cfg["rope_theta"]
    hq, hkv, dh = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    top_k, block = cfg["num_experts_per_tok"], cfg["block_length"]
    first, n_held = held(cfg)
    heads_at_once = 2 if hq % 2 == 0 else 1

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
        ids, labels, pos, weight = row
        seq = labels.shape[0]
        at = jnp.arange(2 * seq)
        noised, blk = at < seq, (at % seq) // block
        qn, kn, qb, kb = noised[:, None], noised[None, :], blk[:, None], blk[None, :]
        allowed = (qn & kn & (kb == qb)) | (qn & ~kn & (kb < qb)) | (~qn & ~kn & (kb <= qb))
        x = p("lm.tok_emb")[ids]
        margin = jnp.full((2 * seq,), jnp.inf)
        choices, first_qk = [], None
        sample = attention_sample(2 * seq)
        for i in range(layers):
            pre = f"lm.l{i}"
            a = rms(x, f"{pre}.ln1.w")

            def heads(t, n, norm=None):
                t = t.reshape(2 * seq, n, dh)
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
                scores = jnp.where(allowed, scores, -jnp.inf)
                return jnp.einsum("hqk,kd->hqd", jax.nn.softmax(scores, -1), v[group])

            ctx = jax.lax.map(some_heads, jnp.arange(hq // heads_at_once)).reshape(hq, 2 * seq, dh)
            h = x + ctx.transpose(1, 0, 2).reshape(2 * seq, hq * dh) @ p(f"{pre}.attn.out.w")
            m = rms(h, f"{pre}.ln2.w")
            probs = jax.nn.softmax(m @ p(f"{pre}.moe.router.w"), -1)
            ranked = jnp.sort(probs, -1)[:, ::-1]
            kth, after = ranked[:, top_k - 1], ranked[:, top_k]
            chosen = jnp.where(probs >= kth[:, None], probs, 0.0)
            gates = chosen / jnp.sum(chosen, -1, keepdims=True)   # over all eight, held or not

            def expert(acc, ew):
                gate, up, down, g_e = ew
                return acc + (jax.nn.silu(m @ gate) * (m @ up)) @ down * g_e[:, None], None

            moe_out, _ = jax.lax.scan(
                expert, jnp.zeros_like(h),
                (p(f"{pre}.moe.gate.w"), p(f"{pre}.moe.up.w"), p(f"{pre}.moe.down.w"),
                 gates[:, first:first + n_held].T))
            x = h + moe_out
            margin = jnp.minimum(margin, (kth - after) / kth)
            choices.append(jnp.sort(jax.lax.top_k(probs, top_k)[1], -1))
        out = rms(x[:seq], "lm.final_norm.w") @ p("lm.head.w")
        logp = jax.nn.log_softmax(out, -1)
        ce = -jnp.take_along_axis(logp, labels[:, None], 1)[:, 0]
        return (out, margin, jnp.stack(choices), jnp.sum(weight * ce)) + first_qk

    with jax.default_matmul_precision("highest"):
        rows = (jnp.asarray(batch["ids"], jnp.int32), jnp.asarray(batch["labels"], jnp.int32),
                jnp.asarray(batch["pos_ids"], jnp.int32), jnp.asarray(batch["loss_weight"], jnp.float32))
        out, margin, choice, ce_sum, q_first, k_first = jax.lax.map(one_sequence, rows)
        loss = ce_sum.sum() / rows[1].size
        weights = tuple(jnp.stack([p(f"lm.l{i}.moe.{n}.w") for i in range(layers)])
                        for n in ("router", "gate", "up", "down"))
        return ((loss, out, margin, choice.transpose(1, 0, 2, 3)) + weights
                + (jnp.asarray([first, block], jnp.int32), q_first, k_first))


def _bf16(x):
    """float32 holding the nearest bf16 values (round to nearest even)."""
    bits = np.ascontiguousarray(x, "f4").view(np.uint32)
    return ((bits + 0x7FFF + ((bits >> 16) & 1)) & 0xFFFF0000).view("f4")


def stage_errors(choice, m, top_p, out, router, gate, up, down, first: int) -> dict:
    """One layer's router and held experts on the program's own router input
    `m` [tokens, d] (see `ROUTER_RTOL`, `EXPERTS_RTOL`): its `choice` and
    `top_p` [tokens, 8] and its experts' `out` [tokens, d] against float32
    numpy over the float32 weights; `gate`, `up`, `down` hold the experts
    `first` on."""
    tokens, k = choice.shape
    logits = (m @ router).astype("f8")
    probs = np.exp(logits - logits.max(-1, keepdims=True))
    probs /= probs.sum(-1, keepdims=True)
    ranked = np.sort(probs, -1)
    tie = (ranked[:, -k] - ranked[:, -k - 1]) < ROUTER_TIE * ranked[:, -k]
    differs = (np.sort(np.argsort(-probs, -1)[:, :k], -1) != np.sort(choice, -1)).any(-1)
    mine = np.take_along_axis(probs, choice, -1)
    mine /= mine.sum(-1, keepdims=True)
    sample = np.arange(0, tokens, max(tokens // EXPERTS_SAMPLE, 1))
    want = np.zeros((len(sample), m.shape[1]), "f4")
    for e in range(gate.shape[0]):
        row, slot = np.nonzero(choice[sample] == first + e)
        x = m[sample[row]]
        g = x @ gate[e]
        want[row] += ((g / (1.0 + np.exp(-g)) * (x @ up[e])) @ down[e]) * top_p[sample[row], slot][:, None]
    # the same two stages a precision lower, against this float32: a router
    # whose logits are rounded to bf16, and experts whose running sums are
    # (eight terms at a time): what the two limits have to exclude
    low = _bf16(logits.astype("f4")).astype("f8")
    low = np.exp(low - low.max(-1, keepdims=True))
    low = np.take_along_axis(low / low.sum(-1, keepdims=True), choice, -1)
    low /= low.sum(-1, keepdims=True)

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
        hidden = _bf16(g / (1.0 + np.exp(-g)) * u * top_p[sample[row], slot][:, None])
        rounded[row] += product_in_bf16(hidden, _bf16(down[e]))
    mean_square = max(np.mean(np.square(want)), 1e-30)
    return {
        "router_choice_differs": int((differs & ~tie).sum()),
        "router_ties": int((differs & tie).sum()),
        "router_prob_error": float((np.abs(top_p - mine) / mine).max()),
        "router_prob_error_bf16_logits": float((np.abs(low - mine) / mine).max()),
        "experts_error": float(np.sqrt(np.mean(np.square(out[sample] - want)) / mean_square)),
        "experts_error_bf16_sums": float(np.sqrt(np.mean(np.square(rounded - want)) / mean_square)),
    }


def attention_errors(q, k, v, out, block: int, want_q, want_k) -> dict:
    """The program's attention output [rows, Hq, 2L, dh] against float32 numpy
    on its own q, k [rows, Hkv, 2L, dh] and v, for `attention_sample`'s
    queries of every row and head, under M built densely: largest |error| over
    the largest |output|.  The same reference with its scores rounded to bf16
    against itself: what the limit must exclude.  And the program's q and k at
    those positions against the reference's `want_q`, `want_k`."""
    rows, hq, positions, dh = q.shape
    seq, group = positions // 2, hq // k.shape[1]
    sample = attention_sample(positions)
    at = np.arange(positions)
    qn, kn = (sample < seq)[:, None], (at < seq)[None, :]
    qb, kb = ((sample % seq) // block)[:, None], ((at % seq) // block)[None, :]
    allowed = (qn & kn & (kb == qb)) | (qn & ~kn & (kb < qb)) | (~qn & ~kn & (kb <= qb))
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
    """The program's (loss, noised logits, layer by layer top-k choice,
    router input, top-k weights, held experts' output, then the first layer's
    q, k, v and attention output) against the reference's (loss, logits,
    margin, choice, weights, (first held expert, block length), the first
    layer's sampled queries and keys): the two
    errors `REFERENCE_RTOL` bounds, the routing account, and the worst layer's
    stage errors."""
    loss, want_loss = float(np.asarray(got[0]).reshape(-1)[0]), float(want[0])
    logits, want_logits = np.asarray(got[1], "f4"), np.asarray(want[1], "f4")
    margin, want_choice = np.asarray(want[2]), np.asarray(want[3])
    rows, seq = want_logits.shape[:2]
    tokens, k = margin.size, want_choice.shape[-1]
    (first, block), n_held = (int(n) for n in np.asarray(want[8])), np.asarray(want[5]).shape[1]
    layers = [got[i:i + 4] for i in range(2, len(got) - 4, 4)]
    choice = np.sort(np.stack([np.asarray(layer[0]).reshape(want_choice.shape[1:])
                               for layer in layers]), -1)
    routed_differently = (choice != want_choice).any(axis=(0, 3))           # [rows, 2L]

    def held_choice(c):  # [layers, rows, 2L, held]: which held experts a position chose
        return (c[..., None] == np.arange(first, first + n_held)).any(-2)

    differs = (held_choice(choice) != held_choice(want_choice)).any(axis=(0, 3))
    noised = differs[:, :seq]                  # the logits are the noised half's
    err = np.abs(logits.reshape(want_logits.shape) - want_logits).max(-1)
    stages = [stage_errors(np.asarray(c).reshape(tokens, k), np.asarray(m, "f4").reshape(tokens, -1),
                           np.asarray(p, "f4").reshape(tokens, k), np.asarray(o, "f4").reshape(tokens, -1),
                           *(np.asarray(w[i], "f4") for w in want[4:8]), first)
              for i, (c, m, p, o) in enumerate(layers)]
    scale = max(np.abs(want_logits).max(), 1e-9)
    return {
        "loss_error": abs(loss - want_loss) / max(abs(want_loss), 1e-6),
        "logit_error": float(err[~noised].max(initial=0.0) / scale),
        "logit_error_left_out": float(err[noised].max(initial=0.0) / scale),
        "tokens": int(tokens),
        "left_out": int(differs.sum()),
        "routed_differently": int(routed_differently.sum()),
        "under_margin": int((margin < ROUTING_MARGIN).sum()),
        "routed_differently_above_margin": int((routed_differently & (margin >= ROUTING_MARGIN)).sum()),
        **{name: max(stage[name] for stage in stages) for name in stages[0]},
        **attention_errors(*got[-4:], block, want[9], want[10]),
    }


def reference_error(got, want) -> float:
    """How far the program is from the reference, as `REFERENCE_RTOL` counts
    it: the larger of the loss's and the logits' error, the logits over the
    noised positions whose held choice agrees.  Positions that chose other
    held experts are left out AND COUNTED (the `reference_routing` line of the
    run).  A failure (infinite error) is: more than `LEFT_OUT_MAX` of them,
    one that routed differently across a gap wider than `ROUTING_MARGIN`, one
    whose logits are off by more than `LEFT_OUT_LOGIT_MAX`, or a router, held
    experts or an attention that miss float32 on the program's own tensors by
    more than `ROUTER_RTOL`, `EXPERTS_RTOL` or `ATTENTION_RTOL`, or first-layer
    queries or keys that miss the reference's by more than `QK_RTOL`."""
    import json

    found = compare(got, want)
    print(json.dumps({"info": "reference_routing", **found,
                      "left_out_share": found["left_out"] / found["tokens"],
                      "routing_margin": ROUTING_MARGIN, "left_out_max": LEFT_OUT_MAX,
                      "left_out_logit_max": LEFT_OUT_LOGIT_MAX, "router_rtol": ROUTER_RTOL,
                      "experts_rtol": EXPERTS_RTOL, "attention_rtol": ATTENTION_RTOL,
                      "qk_rtol": QK_RTOL}),
          flush=True)
    if (found["routed_differently_above_margin"]
            or found["left_out"] > LEFT_OUT_MAX * found["tokens"]
            or found["logit_error_left_out"] > LEFT_OUT_LOGIT_MAX
            or found["router_choice_differs"]
            or not found["router_prob_error"] <= ROUTER_RTOL
            or not found["experts_error"] <= EXPERTS_RTOL
            or not found["attention_error"] <= ATTENTION_RTOL
            or not found["qk_error"] <= QK_RTOL):
        return float("inf")
    return max(found["loss_error"], found["logit_error"])
