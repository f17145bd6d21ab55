"""Ouro-2.6B (a looped language model) trained as a causal LM: how the
benchmark builds it through the framework, a plain float32 reference of the
same architecture, and the operations one sequence needs.

Architecture: ByteDance/Ouro-2.6B `config.json` (`model_type: ouro`; "Scaling
Latent Reasoning via Looped Language Models", arXiv:2510.25741).  With E the
embedding [V, 2048], T = `total_ut_steps` = 4 and L layers, every weight used
by all T passes, eps 1e-6:

    h_0 = E[ids]
    for t = 1..T:
        x = h_{t-1}
        for l = 1..L:
            a = Attn_l(rms(x; g1_l))          q, k, v, o 2048 x 2048, no bias; 16 heads of 128;
                                              rope(theta 1e6, rotate-half) on q, k; causal
            x = x + rms(a; g2_l)              the sandwich: a norm after the sub-layer, before the sum
            u = rms(x; g3_l)
            x = x + rms(W_down_l( silu(W_gate_l u) * (W_up_l u) ); g4_l)          width 5632
        h_t   = rms(x; g_final)               the final norm closes EVERY pass; the next pass reads h_t
        z_t   = h_t W_head                    the logits of exit t
        lam_t = sigmoid(h_t w_exit + b_exit)  one scalar a position
    p_t  = lam_t prod_{j<t} (1 - lam_j)  for t < T;    p_T = prod_{j<T} (1 - lam_j)
    loss = mean over positions of [ sum_t p_t CE(z_t, y) - beta H(p) ],   H(p) = -sum_t p_t log p_t

`config.json` gives the widths, `total_ut_steps` and `early_exit_threshold`
(1.0: a rule of inference, leave once the cumulative exit probability reaches
it, so never early; training reads it nowhere).  The sandwich norms, the final
norm's place, the exit gate and the objective are the published modelling
code's and the paper's stage-one objective; the configuration file lists them
under `assumed`.

Departures of the program under test
(`paddle_tpu.models.transformer.build_causal_lm`) from the published model,
which the reference follows so that the two compute the same function (the
configuration file's `departures` is this list, word for word):

  * eight of the 48 layers, the published layers 1 to 8 (all 48 are alike: one is a period; the floor is four): one stage of a pipeline of six stages of eight layers whose activations come round four times; the other 40 layers lie on further chips
  * 12288 of the 49152 vocabulary rows in the embedding and in the untied head: one chip's quarter of the rows; token ids and labels are drawn from the slice and the four exits' losses are over the slice
  * the exit gate is one [2048] weight and a bias read in float32, drawn N(0, 0.02) and 0: `config.json` gives no gate, the published code's is a linear layer of one output on the pass's normed output
  * the objective is the paper's stage one alone, the expected cross entropy under the learned exit distribution less beta = 0.05 times its entropy (a uniform prior): the second stage, which trains the gate alone against the measured gain of a further pass, is left out
  * Adam for AdamW (the framework has no AdamW), learning rate 1e-4 reached by a linear warm-up over the first 200 steps from 1e-6, betas 0.9 / 0.95, epsilon 1e-8, no decay
  * weights are random, N(0, 0.02) from the run's seed, norm gains 1, the gate's bias 0
  * token ids are uniform random with no padding and no document boundaries (a row is one whole sequence), every position is a label (the next token), so each exit's cross entropy starts near ln(12288)

The reference: plain `jax.numpy` in float32 at `highest` matmul precision, one
sequence and two heads at a time, explicit causal scores, no kernel, no
recomputation; the passes are a Python loop, a pass's layers a `lax.scan` over
the layers' stacked weights (32 layer bodies written out compile for minutes).
"""
import numpy as np

FEEDS = ("ids", "labels", "pos_ids")

#: The limits below are set from two readings each at the published widths (my
#: chip runs, PR 38: twenty runs of the cell and nine of the tool, a seed each,
#: 8 x 4096 positions;
#: PERF.md, section 6, has the table): what the program reads over its seeds, and
#: what it has to refuse (tools/chip_ouro_controls.py: the reference with a
#: fault put into it).
#:
#: Over the four exits, the sampled logits' largest error over the largest
#: |reference logit| of that exit: 3.7e-2 to 5.1e-2 at the LAST exit, and by exit about 1.3e-2,
#: 2.0e-2, 2.9e-2, 4.2e-2: a pass of eight bf16 layers adds ~0.9% of the
#: stream's root-mean-square (`pass2_error`) and carries what it was handed on
#: at ~1.4 times its size (random weights: the looped map is no contraction).
#: What it has to refuse: the post-norms dropped 1.22, the final norm after the
#: last pass only 1.33, a second set of weights in pass 2 1.70.  It does NOT
#: tell three passes for four (the first three exits are the same numbers:
#: `EXIT_P_ATOL` does) nor masters rounded to bf16 (3.8e-2: the program casts
#: its masters to bf16 itself; `GATE_ATOL` does).
REFERENCE_RTOL = 0.12
#: The loss's relative error, a limit of its own (the logits' 0.12 would pass a
#: loss without its entropy term): 1e-7 to 3e-5 in over thirty readings.
#: What it has to refuse: the cross entropies, the exit weighting and the mean
#: in bf16 (`reference(bf16_loss=True)`) 2.29e-3 to 2.71e-3 (the loss starts at
#: 9.79 whatever the seed and bf16's grid there is 9.75, 9.8125; the
#: per-position roundings average out), the entropy term left out 5.0e-3 to
#: 6.0e-3; three passes for four read 5.4e-4 to 5.6e-4, the post-norms dropped
#: 5.4e-4 to 1.1e-3.  It does NOT tell a second set of weights (1.9e-4, 2.2e-4)
#: nor a misplaced final norm (4.8e-5, 8.4e-5): the logits' limit does.
LOSS_RTOL = 2e-4
#: Logits are compared at this many positions of every row and exit (4 x 8 x
#: 4096 x 12288 float32 logits would be 6.4 GB): spread by a multiplicative
#: hash, the same in the program, the reference and the comparison.
LOGIT_SAMPLE = 256
#: The exit distribution p_t at EVERY position against the reference's: the
#: largest |difference| of a probability, 1.1e-2 to 1.9e-2 (a gate logit is h_t
#: . w_exit of order 1 and h_t carries the passes' bf16 roundings).  What it has
#: to refuse: a second set of weights 0.45, the final norm misplaced 0.48, the
#: post-norms dropped 0.77; three passes for four have another shape (infinite).
EXIT_P_ATOL = 0.06
#: The exit gate a stage at a time, on the PROGRAM'S OWN h_1 .. h_T as the loop
#: wrote them (float32 numpy: the logits, the sigmoids, the products): largest
#: |difference| of a probability, 1.25e-6 to 1.34e-6.  (While the gate stood IN
#: the loop's body it read 8.5e-5 to 1.6e-3: XLA hands a consumer in the same
#: fusion the un-rounded float32 product where the next pass reads its bf16;
#: PERF.md, PR 38.)  What it has to refuse: the gate's weight rounded to bf16
#: (`bf16_masters`) 1.37e-3, a distribution rounded to bf16 (`bf16_gate`)
#: 1.95e-3; `gate_error_bf16` of every run, the gate's weight, its logits and
#: every product in bf16, reads 5.2e-3 to 7.2e-3.
GATE_ATOL = 4e-5
#: Pass 2 alone, on the program's own h_1 (so that pass 1's error is not what
#: pass 4's tolerance has to hide), `STAGE_ROWS` rows, with the weights pass 2
#: reads: root-mean-square error of h_2 over its root-mean-square, 9.2e-3 to
#: 9.5e-3 (7.9e-3 against bf16 masters).  What it has to refuse: a pass that
#: reads other weights or drops its post-norms, both of order 1.
STAGE_RTOL = 3e-2
STAGE_ROWS = 2
#: The final norm's statistics on the program's own h_1 .. h_T: a row that
#: rms(x; g) wrote has mean((h / g)^2) = 1 (less eps / mean(x^2), 1e-7 here);
#: the root-mean-square, over every row of every pass, of what is missing.  The
#: bf16 that h_t is written in averages out over a row's 2048 elements, 1.276e-4
#: to 1.283e-4 in nine readings (it grows as the rows narrow: 7.4e-4 at 64-wide
#: on the CPU); a scale computed below float32 moves the whole row.  What it has
#: to refuse: THE PROGRAM with every `rms_norm`'s squares, their mean and the
#: reciprocal root at bf16 (tools/chip_ouro_controls.py swaps the op's
#: lowering) 3.27e-3 to 3.32e-3, which no comparison with the reference tells
#: (logits 3.9e-2 to 4.9e-2, `pass2_error` 9.7e-3 to 9.9e-3 for 9.2e-3 to 9.5e-3:
#: eight bf16 layers a pass hide it, and a post-norm takes a pre-norm's scale
#: error out again).
NORM_RTOL = 1e-3


def logit_sample(positions: int):
    """The positions whose logits are compared."""
    return np.unique((np.arange(LOGIT_SAMPLE, dtype=np.int64) * 2654435761 + 7) % positions)


def build(cfg: dict, job: dict):
    """(main, startup, feed variables by name, loss variable, the variables the
    reference is compared on: loss, the four exits' logits at the sampled
    positions [T, sample, rows, V], the exit distribution [T, rows, L, 1] and
    h_1 .. h_T [T, rows, L, d] as the loop wrote them) of the train program, as a user of the framework
    gets it: `build_causal_lm`, then the learning rate's warm-up and Adam from
    the traffic file."""
    import paddle_tpu as fluid
    from paddle_tpu import layers
    from paddle_tpu.models import transformer

    main, startup, feeds, fetches = transformer.build_causal_lm(
        vocab_size=cfg["vocab_size"], seq_len=job["seq_len"], d_model=cfg["hidden_size"],
        n_heads=cfg["num_attention_heads"], n_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        qk_norm=None, norm_eps=cfg["rms_norm_eps"], rope_theta=cfg["rope_theta"],
        layer_types=cfg["layer_types"], num_dense_layers=len(cfg["layer_types"]),
        dense_width=cfg["intermediate_size"], tie_embedding=cfg["tie_word_embeddings"],
        post_norm=True, loop=cfg["total_ut_steps"],
        exit_beta=cfg["exit_entropy_beta"], with_optimizer=False, dtype=cfg["compute_dtype"])
    with fluid.program_guard(main, startup):
        by_position = layers.transpose(fetches["logits"], [2, 0, 1, 3])          # [L, T, rows, V]
        sampled = layers.gather(by_position, layers.assign(logit_sample(job["seq_len"]).astype("int32")))
        rate = layers.learning_rate_scheduler.linear_lr_warmup(
            job["learning_rate"], job["lr_warmup_steps"], job["lr_warmup_start"], job["learning_rate"])
        fluid.optimizer.Adam(learning_rate=rate, beta1=job["adam_beta1"], beta2=job["adam_beta2"],
                             epsilon=job["adam_epsilon"]).minimize(fetches["loss"])
    return (main, startup, feeds, fetches["loss"],
            [fetches["loss"].name, sampled.name, fetches["exit_p"].name, fetches["hidden"].name])


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
    forward): per position and PASS a layer's four projections, its two
    attention products over the causal pairs and its three feed-forward
    products, then the head; `total_ut_steps` passes.  Nothing for the forward
    that backward computes again (a choice of the program's, not the model's
    arithmetic), the norms, the rotation or the gate."""
    d, seq, width = cfg["hidden_size"], job["seq_len"], cfg["num_attention_heads"] * cfg["head_dim"]
    kv = cfg["num_key_value_heads"] * cfg["head_dim"]
    layer = 2 * d * (2 * width + 2 * kv) + 2 * 2 * width * (seq + 1) / 2 + 3 * 2 * d * cfg["intermediate_size"]
    per_pass = len(cfg["layer_types"]) * layer + 2 * d * cfg["vocab_size"]
    return 3.0 * seq * cfg["total_ut_steps"] * per_pass


_MATRICES = ("attn.q", "attn.k", "attn.v", "attn.out", "ffn.gate", "ffn.up", "ffn.down")
_GAINS = ("ln1", "post_ln1", "ln2", "post_ln2")
#: what `one_pass` reads of a configuration: the reference hands these on, so
#: that the comparison runs its stage check at the sizes the run had
_NUMBERS = ("num_attention_heads", "num_key_value_heads", "head_dim", "rms_norm_eps", "rope_theta")


def stacked_weights(params: dict, n_layers: int, prefix: str = "lm") -> tuple:
    """The layers' float32 weights stacked by layer: the seven matrices, then the
    four gains [layers, 4, d]."""
    import jax.numpy as jnp

    def p(name):
        return jnp.asarray(params[name], jnp.float32)

    return (tuple(jnp.stack([p(f"{prefix}.l{i}.{m}.w") for i in range(n_layers)]) for m in _MATRICES)
            + (jnp.stack([jnp.stack([p(f"{prefix}.l{i}.{g}.w") for g in _GAINS]) for i in range(n_layers)]),))


def one_pass(x, pos, weights, final_gain, cfg: dict, post_norms=True, close=True):
    """One pass of the stack over one sequence x [L, d] in float32: the layers
    (a scan over their stacked `weights`), then the final norm where `close`."""
    import jax
    import jax.numpy as jnp

    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    hq, hkv, dh = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    seq = x.shape[0]
    at = jnp.arange(seq)
    heads_at_once = 2 if hq % 2 == 0 else 1

    def rms(t, gain):
        return t * jax.lax.rsqrt(jnp.mean(jnp.square(t), -1, keepdims=True) + eps) * gain

    def rope(t):  # [H, L, dh]
        half = dh // 2
        angle = pos.astype(jnp.float32)[:, None] * theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
        cos = jnp.concatenate([jnp.cos(angle)] * 2, -1)
        sin = jnp.concatenate([jnp.sin(angle)] * 2, -1)
        return t * cos + jnp.concatenate([-t[..., half:], t[..., :half]], -1) * sin

    def layer(x, w):
        wq, wk, wv, wo, gate, up, down, gains = w
        a = rms(x, gains[0])
        q = rope((a @ wq).reshape(seq, hq, dh).transpose(1, 0, 2))
        # every query head beside its own key/value head (16 on 16 here; fewer are repeated)
        k = jnp.repeat(rope((a @ wk).reshape(seq, hkv, dh).transpose(1, 0, 2)), hq // hkv, 0)
        v = jnp.repeat((a @ wv).reshape(seq, hkv, dh).transpose(1, 0, 2), hq // hkv, 0)

        def some_heads(j):
            qs, ks, vs = (jax.lax.dynamic_slice_in_dim(t, j * heads_at_once, heads_at_once, 0) for t in (q, k, v))
            scores = jnp.einsum("hqd,hkd->hqk", qs, ks) / np.sqrt(dh)
            scores = jnp.where(at[None, :] <= at[:, None], scores, -jnp.inf)
            return jnp.einsum("hqk,hkd->hqd", jax.nn.softmax(scores, -1), vs)

        ctx = jax.lax.map(some_heads, jnp.arange(hq // heads_at_once)).reshape(hq, seq, dh)
        out = ctx.transpose(1, 0, 2).reshape(seq, hq * dh) @ wo
        x = x + (rms(out, gains[1]) if post_norms else out)
        u = rms(x, gains[2])
        out = (jax.nn.silu(u @ gate) * (u @ up)) @ down
        return x + (rms(out, gains[3]) if post_norms else out), None

    x, _ = jax.lax.scan(layer, x, weights)
    return rms(x, final_gain) if close else x


def exit_distribution(gate_logits, xp=np, rounding=lambda t: t):
    """p [T, ...] of the gate logits [T, ...]: p_t = lam_t prod_{j<t} (1 -
    lam_j), the last exit the rest, by the products as written; `rounding` is
    applied to every intermediate."""
    lam = rounding(1.0 / (1.0 + xp.exp(-gate_logits)))
    left, p = xp.ones_like(lam[0]), []
    for t in range(lam.shape[0] - 1):
        p.append(rounding(lam[t] * left))
        left = rounding(left * rounding(1.0 - lam[t]))
    return xp.stack(p + [left])


def reference(params: dict, batch: dict, cfg: dict, program=None, passes=None, post_norms=True,
              final_norm_every_pass=True, second_weights=None, bf16_loss=False):
    """(loss, the sampled positions' logits [rows, T, sample, V], the exit
    distribution [rows, T, L], the four exits' mean cross entropies [T], then
    for the stage checks the layers' stacked float32 weights AS PASS 2 READS THEM
    (`stacked_weights`: eight arrays), the final norm's gain, the gate's weight
    and bias, and the configuration's `_NUMBERS` with whether a sub-layer's
    output is normed) of `batch`
    in plain float32 jax.numpy, one sequence at a time; `params` maps the
    program's parameter names to arrays.

    The keywords put a FAULT into the reference, for the controls that the
    comparison has to refuse (tests/test_ouro.py, tools/chip_ouro_controls.py):
    `passes` another number of passes, `post_norms` False drops the norms after
    the sub-layers, `final_norm_every_pass` False norms after the last pass
    only, `second_weights` (a `params` of its own) is what pass 2 reads,
    `bf16_loss` rounds to bf16 the logits the cross entropies read, the log
    softmax, each cross entropy, each product and sum of the exit weighting, the
    entropy and the mean (sums accumulate in float32, as the chip's do)."""
    import jax
    import jax.numpy as jnp

    n_layers = len(cfg["layer_types"])
    times = passes or cfg["total_ut_steps"]

    def p(name, source=params):
        return jnp.asarray(source[name], jnp.float32)

    weights = stacked_weights(params, n_layers)
    other = stacked_weights(second_weights, n_layers) if second_weights is not None else weights
    final_gain, w_exit, b_exit = p("lm.final_norm.w"), p("lm.exit_gate.w"), p("lm.exit_gate.b")
    head = p("lm.tok_emb").T if cfg["tie_word_embeddings"] else p("lm.head.w")
    beta = cfg["exit_entropy_beta"]

    def low(t):  # `reduce_precision` is an op of its own: XLA may fold a pair of casts away
        return jax.lax.reduce_precision(t, exponent_bits=8, mantissa_bits=7) if bf16_loss else t

    def one_sequence(row):
        ids, labels, pos = row
        sample = logit_sample(ids.shape[0])
        x = p("lm.tok_emb")[ids]
        logits, gates, ces = [], [], []
        for t in range(times):
            x = one_pass(x, pos, other if t == 1 else weights, final_gain, cfg, post_norms=post_norms,
                         close=final_norm_every_pass or t == times - 1)
            h = x if final_norm_every_pass or t == times - 1 else \
                x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + cfg["rms_norm_eps"]) * final_gain
            z = h @ head
            ces.append(low(-jnp.take_along_axis(low(jax.nn.log_softmax(low(z), -1)), labels[:, None], 1)[:, 0]))
            logits.append(z[sample])
            gates.append(h @ w_exit + b_exit[0])
        dist = exit_distribution(jnp.stack(gates), jnp)                       # [T, L]
        entropy = low(-jnp.sum(low(jnp.where(dist > 0, dist * jnp.log(jnp.where(dist > 0, dist, 1.0)), 0.0)), 0))
        ce = jnp.stack(ces)
        expected = low(jnp.sum(low(low(dist) * ce), 0))
        return jnp.stack(logits), dist, jnp.sum(low(expected - low(beta * entropy))), jnp.sum(ce, 1)

    with jax.default_matmul_precision("highest"):
        rows = tuple(jnp.asarray(batch[n], jnp.int32) for n in FEEDS)
        logits, dist, loss_sum, ce_sum = jax.lax.map(one_sequence, rows)
        positions = rows[1].size
        numbers = [cfg[k] for k in _NUMBERS] + [float(post_norms)]
        return ((low(loss_sum.sum() / positions), logits, dist, ce_sum.sum(0) / positions)
                + other + (final_gain, w_exit, b_exit, jnp.asarray(numbers, jnp.float32)))


def _bf16(x):
    """float32 holding the nearest bf16 values (round to nearest even)."""
    bits = np.ascontiguousarray(x, "f4").view(np.uint32)
    return ((bits + 0x7FFF + ((bits >> 16) & 1)) & 0xFFFF0000).view("f4")


def gate_errors(hidden, p, w_exit, b_exit) -> dict:
    """The program's exit distribution `p` [T, rows, L] against float32 numpy on
    its own `hidden` [T, rows, L, d]: the largest |difference| of a probability;
    and the same with the gate's weight, its logits and every product rounded to
    bf16, against the float32 one."""
    w = np.asarray(w_exit, "f4")
    logits = np.stack([np.asarray(h, "f4") @ w for h in hidden]) + float(np.asarray(b_exit).reshape(-1)[0])
    want = exit_distribution(logits)
    rounded_logits = np.stack([_bf16(np.asarray(h, "f4") @ _bf16(w)) for h in hidden])
    rounded = exit_distribution(rounded_logits + float(np.asarray(b_exit).reshape(-1)[0]), rounding=_bf16)
    return {"gate_error": float(np.abs(np.asarray(p, "f4") - want).max()),
            "gate_error_bf16": float(np.abs(rounded - want).max()),
            "exit_p_sum_error": float(np.abs(np.asarray(p, "f4").sum(0) - 1.0).max())}


def norm_error(hidden, final_gain) -> float:
    """The final norm's statistics on the program's own `hidden` [T, rows, L, d]:
    the root-mean-square over every row of every pass of mean((h / g)^2) - 1."""
    gain = np.asarray(final_gain, "f4")
    off = []
    for h in hidden:
        x = np.asarray(h, "f4") / gain
        off.append(np.mean(np.square(x, out=x), -1, dtype="f8") - 1.0)
    return float(np.sqrt(np.mean(np.square(off))))


def pass_error(h_in, h_out, weights, final_gain, cfg: dict, post_norms: bool = True) -> float:
    """Pass 2 on the program's own h_1: the float32 `one_pass` of `h_in` [rows,
    L, d] against the program's `h_out`, root-mean-square error over the
    root-mean-square of the reference's."""
    import jax
    import jax.numpy as jnp

    pos = jnp.arange(h_in.shape[1], dtype=jnp.int32)

    @jax.jit
    def run(rows, weights, final_gain):
        with jax.default_matmul_precision("highest"):
            return jax.lax.map(lambda x: one_pass(x, pos, weights, final_gain, cfg, post_norms=post_norms), rows)

    want = np.asarray(run(jnp.asarray(h_in, jnp.float32), tuple(jnp.asarray(w) for w in weights),
                          jnp.asarray(final_gain)))
    return float(np.sqrt(np.mean(np.square(np.asarray(h_out, "f4") - want)) / np.mean(np.square(want))))


def compare(got, want) -> dict:
    """The program's (loss, sampled logits [sample, T, rows, V], exit
    distribution [T, rows, L, 1], hidden [T, rows, L, d]) against the
    reference's (loss, logits [rows, T, sample, V], distribution [rows, T, L],
    exit cross entropies, the stacked weights, the final gain, the gate's weight
    and bias, the configuration's numbers): every error a limit of this module
    bounds."""
    loss, want_loss = float(np.asarray(got[0]).reshape(-1)[0]), float(want[0])
    logits = np.asarray(got[1], "f4").transpose(2, 1, 0, 3)                  # [rows, T, sample, V]
    want_logits = np.asarray(want[1], "f4")
    exits = min(logits.shape[1], want_logits.shape[1])                      # a control may run fewer passes
    by_exit = [float(np.abs(logits[:, t] - want_logits[:, t]).max() / max(np.abs(want_logits[:, t]).max(), 1e-9))
               for t in range(exits)]
    p = np.asarray(got[2], "f4")[..., 0]                                     # [T, rows, L]
    want_p = np.asarray(want[2], "f4").transpose(1, 0, 2)
    hidden = got[3]
    weights, (final_gain, w_exit, b_exit) = want[4:12], want[12:15]
    numbers = np.asarray(want[15], "f8")
    cfg = {k: (float if k in ("rms_norm_eps", "rope_theta") else int)(v) for k, v in zip(_NUMBERS, numbers)}
    return {
        "loss_error": abs(loss - want_loss) / max(abs(want_loss), 1e-6),
        "logit_error": max(by_exit), "logit_error_by_exit": by_exit,
        "exit_p_error": float(np.abs(p[:exits] - want_p[:exits]).max()) if p.shape == want_p.shape else float("inf"),
        "exit_mass": [float(m) for m in p.mean(axis=(1, 2))],
        "exit_ce_reference": [float(c) for c in np.asarray(want[3]).reshape(-1)],
        **gate_errors(hidden, p, w_exit, b_exit), "norm_error": norm_error(hidden, final_gain),
        "pass2_error": pass_error(np.asarray(hidden[0][:STAGE_ROWS], "f4"), hidden[1][:STAGE_ROWS],
                                  weights, final_gain, cfg, post_norms=bool(numbers[len(_NUMBERS)])),
    }


def reference_error(got, want) -> float:
    """How far the program is from the reference, as `REFERENCE_RTOL` counts it:
    the worst exit's sampled-logit error.  A failure (infinite error) is: a loss
    off the reference's by more than `LOSS_RTOL`, an exit distribution off the
    reference's by more than `EXIT_P_ATOL` at some position or not summing to 1,
    a gate that misses float32 on the program's own h_t by more than
    `GATE_ATOL`, a final norm whose rows miss a mean square of 1 by more than
    `NORM_RTOL`, or a pass 2 that misses float32 on the program's own h_1 by
    more than `STAGE_RTOL`."""
    import json

    found = compare(got, want)
    print(json.dumps({"info": "reference_exits", **found, "reference_rtol": REFERENCE_RTOL, "loss_rtol": LOSS_RTOL,
                      "exit_p_atol": EXIT_P_ATOL, "gate_atol": GATE_ATOL, "norm_rtol": NORM_RTOL,
                      "stage_rtol": STAGE_RTOL}), flush=True)
    if (not found["loss_error"] <= LOSS_RTOL or not found["exit_p_error"] <= EXIT_P_ATOL
            or not found["exit_p_sum_error"] <= 1e-5 or not found["gate_error"] <= GATE_ATOL
            or not found["norm_error"] <= NORM_RTOL or not found["pass2_error"] <= STAGE_RTOL):
        return float("inf")
    return found["logit_error"]
