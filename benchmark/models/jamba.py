"""AI21-Jamba2-3B causal-LM training: how the benchmark builds it through the
framework, a plain float32 reference of the same architecture, and the
operations one sequence needs.

Architecture: ai21labs/AI21-Jamba2-3B `config.json` (`model_type: jamba`);
the layer equations are those of the public `modeling_jamba.py`, and what the
config does not give is listed in the configuration file's `assumed`.  A layer,
every norm an RMSNorm with a gain and eps 1e-6, no bias but where said, with
a = rms(x; ln1) and m = rms(h; ln2):

    h = x + mixer(a),   y = h + ffn(m),   ffn(m) = (silu(m Wg) * (m Wu)) Wd at width 8192 in EVERY layer
    (`num_experts` 1: `expert_layer_period` / `_offset` choose between a dense and a one-expert form, the same here)
    layer i is attention if i % 14 == 7 (`attn_layer_period`, `attn_layer_offset`), else mamba
    mamba      [xs, z] = split(a W_in)                        [2560 -> 2 x 5120]
               xs = silu(conv_4(xs) + b_conv)                 depthwise, causal, zeros before the sequence's start
               [dt, B, C] = split(xs W_x)                     [5120 -> 160 + 16 + 16]
               dt = rms(dt; dt_norm),  B = rms(B; b_norm),  C = rms(C; c_norm)       Jamba's three inner norms
               dt = softplus(dt W_dt + b_dt)                  [160 -> 5120], float32
               A = -exp(A_log)                                [5120, 16]
               h_t = exp(dt_t[:, None] * A) * h_{t-1} + (dt_t * xs_t)[:, None] * B_t[None, :],   h_0 = 0
               y_t = h_t C_t + D * xs_t
               mixer(a) = (y * silu(z)) W_out                 [5120 -> 2560]
    attention  q = a Wq (20 heads of 128),  k = a Wk,  v = a Wv (ONE head of 128, shared by all 20)
               NO rotary or other positions, causal, scale 128^-0.5,  mixer(a) = concat(heads) Wo
    loss       mean over every position of CE( rms(y_L; final_norm) E^T, the next token ),  E the tied embedding

The reference computes the scan as the recurrence above, token by token
(`lax.scan` over t with a [5120, 16] state), never the chunked form the
program's op uses; the convolution as four shifted multiply-adds; attention as
a dense [L, L] softmax, two heads at a time; the head and the loss a block of
positions at a time.

Departures of the program under test
(`paddle_tpu.models.transformer.build_causal_lm`) from the published model,
which the reference follows so that the two compute the same function (the
configuration file's `departures` is this list, word for word):

  * 14 of the 28 layers, the published layers 0 to 13: one whole period of the pattern, 13 Mamba layers and the attention layer at index 7; the second period's 14 layers would lie on a second host as a pipeline stage;
  * Adam for AdamW (the framework has no AdamW), learning rate 3e-4 reached by a linear warm-up over the first 200 steps from 1e-6, betas 0.9 / 0.95, epsilon 1e-8, no decay;
  * weights are random, N(0, 0.02) from the run's seed, the convolution's taps too, its bias 0, norm gains 1, A_log[c, n] = ln(n + 1), D = 1, b_dt the inverse softplus of a log-uniform draw on [1e-3, 1e-1] (Mamba's defaults: `config.json` has no initialisation);
  * token ids are uniform random over the whole vocabulary with no padding and no document boundaries (a row is one whole sequence, the state starts at zero with it), every position is a label (the next token), so the cross entropy starts near ln(65536).
"""
from __future__ import annotations

import functools

import numpy as np

from benchmark.models import lfm2 as _decoder

FEEDS = ("ids", "labels")

#: The larger of the loss's relative error and the sampled logits' error over
#: the largest |reference logit| (bf16 activations over float32 masters through
#: 14 layers and a bf16 tied head).  On the chip at the published widths (my chip
#: runs, PR 47; PERF.md section 6): the sound program 2.94e-2 through 14 layers
#: (1.08e-2 through two), the loss 9e-7; the reference without Jamba's three
#: inner norms 0.874.  The logits do NOT tell a bf16 scan state or step (3.03e-2,
#: 2.93e-2: `SCAN_RTOL` does) nor the reference's products at the chip's default
#: precision (2.84e-2: the program's products ARE bf16, so that reference lies
#: nearer to it, and no limit can refuse it).
REFERENCE_RTOL = 6e-2
LOGIT_SAMPLE = _decoder.LOGIT_SAMPLE
#: The stage rows and channels: the program's own tensors of the stages below
#: are compared on the first `STAGE_ROWS` of the 8 check rows and, for the scan
#: and the convolution (both independent a channel), the first `STAGE_CHANNELS`
#: of the 5120 channels; the slices are ops of the program, so that 8 rows of
#: every stage's operands never lie in a chip's memory beside the state.
STAGE_ROWS = 2
STAGE_CHANNELS = 1024
#: THE SCAN STAGE: the op's output against the token-by-token float32
#: recurrence ON THE PROGRAM'S OWN xs, dt, B, C (first and last Mamba layer, the
#: stage rows and channels), the recurrence's output ROUNDED to bf16 as the op
#: rounds its own: root-mean-square difference over the root-mean-square
#: output.  Rounded alike, an op that computes in float32 and rounds once
#: differs from the recurrence only where its last bits cross a rounding
#: boundary; a bf16 state or a bf16 step differs everywhere.  On the chip (my
#: chip runs, PR 47), at the cell's own size on its four chips: the sound
#: program 1.02e-5 to 1.33e-5 over five seeds (the op alone on drawn inputs
#: 1.8e-5 to 2.0e-5); the PROGRAM with its state rounded to bf16 where a chunk
#: hands it on 1.109e-3, with its step rounded 1.077e-3 (`tools/
#: chip_jamba_controls.py`; on a two-layer cut on one chip 9.7e-6, 1.10e-3 and
#: 1.09e-3); the recurrence itself with a bf16 state after every token 2.05e-3,
#: with a bf16 step and decay 2.2e-2 to 2.6e-2.  (Before the op put its operands behind an `optimization_barrier` the
#: sound program read 2.78e-3: XLA handed the scan xs BEFORE its rounding.)
SCAN_RTOL = 1.5e-4
#: The first Mamba layer's convolution on the program's own in-projection
#: (bf16), float32 taps and bias: root-mean-square error over the
#: root-mean-square output; Kimi Linear's and LFM2's limit for the same op (it
#: computes in float32 and rounds once: 1.66e-3; every intermediate in bf16
#: 3.9e-3).
CONV_RTOL = _decoder.CONV_RTOL
#: The attention on the program's own q (20 heads), k and v (one head) for
#: `ATTENTION_SAMPLE` queries of the stage rows against all keys before them,
#: float32 scores: largest error over the largest |output|; the other decoders'
#: limit.  It catches a wrong mask or a key/value head read by the wrong
#: queries, not bf16 scores (PERF.md section 7, defect 13c).
ATTENTION_RTOL = _decoder.ATTENTION_RTOL
ATTENTION_SAMPLE = _decoder.ATTENTION_SAMPLE
#: ... and that layer's queries and keys at the sampled positions against the
#: reference's, over the largest |value|: seven Mamba layers' bf16 roundings lie
#: before them.  On the chip: 2.20e-2 sound (9.2e-3 behind ONE Mamba layer),
#: 0.612 without the inner norms.
QK_RTOL = 6e-2
#: The LAST Mamba layer's step projection, B and C (the scan's own operands,
#: after Jamba's three inner norms) at `attention_sample`'s positions of the
#: stage rows against the reference's, over the largest |value| of each: the
#: whole stack's bf16 roundings lie before them.  What it has to refuse: an
#: inner norm left out (B and C are then 50 times smaller and the step's
#: projection 20 times: an error near 1), which the logits do not show (at
#: N(0, 0.02) the scan's part of a mixer's output is small beside the skip's).
#: On the chip: 4.47e-2 sound behind 13 layers (8.8e-3 behind none), 27.8 without
#: the inner norms.
INNER_RTOL = 1.5e-1

logit_sample = _decoder.logit_sample
attention_sample = _decoder.attention_sample
_bf16 = _decoder._bf16


def _mamba(cfg: dict) -> dict:
    return dict(expand=cfg["mamba_expand"], state=cfg["mamba_d_state"], dt_rank=cfg["mamba_dt_rank"])


def layer_types(cfg: dict) -> list:
    """The published rule: layer i is attention where i % `attn_layer_period`
    == `attn_layer_offset`, else Mamba."""
    return ["full_attention" if i % cfg["attn_layer_period"] == cfg["attn_layer_offset"] else "mamba"
            for i in range(cfg["num_hidden_layers"])]


def build(cfg: dict, job: dict):
    """(main, startup, feed variables by name, loss variable, the variables
    the reference is compared on) of the train program, as a user of the
    framework gets it: `build_causal_lm` with every layer a recomputed segment,
    the ZeRO-3 hints over the traffic file's mesh on BOTH programs (the state is
    born split: `Executor.run(startup)` places it so), then the learning rate's
    warm-up and Adam, whose moments take the hints.  The compared variables:
    loss, the sampled positions' logits; then, on the first `STAGE_ROWS` rows,
    the first Mamba layer's convolution's input and output (`STAGE_CHANNELS`
    channels), the first and the last Mamba layer's xs, dt, B, C and output
    (the same channels), and the attention's q, k, v and output."""
    import paddle_tpu as fluid
    from paddle_tpu import layers
    from paddle_tpu.models import transformer

    kinds = cfg["layer_types"]
    assert kinds == layer_types(cfg), "layer_types is the published rule written out"
    main, startup, feeds, fetches = transformer.build_causal_lm(
        vocab_size=cfg["vocab_size"], seq_len=job["seq_len"], d_model=cfg["hidden_size"],
        n_heads=cfg["num_attention_heads"], n_kv_heads=cfg["num_key_value_heads"], qk_norm=None, rotary=False,
        norm_eps=cfg["rms_norm_eps"], layer_types=kinds, conv_kernel=cfg["mamba_d_conv"],
        mamba=_mamba(cfg), num_dense_layers=len(kinds),
        dense_width=cfg["intermediate_size"], tie_embedding=cfg["tie_word_embeddings"], recompute_layers=True,
        with_optimizer=False, dtype=cfg["compute_dtype"])
    if "mesh_shape" in job:
        mesh = fluid.parallel.make_mesh(tuple(job["mesh_shape"]), tuple(job["mesh_axes"]))
        axis = job["mesh_axes"][0]
        rules = transformer.fsdp_rules(main, axis, int(mesh.shape[axis]))
        for program in (main, startup):
            fluid.parallel.shard_parameters(program, rules, mesh=mesh, batch_axis=axis)
    block = main.global_block()

    def of(kind):
        return [op for op in block.ops if op.type == kind]

    with fluid.program_guard(main, startup):
        by_position = layers.transpose(fetches["logits"], [1, 0, 2])
        sampled = layers.gather(by_position, layers.assign(logit_sample(job["seq_len"]).astype("int32")))

        def rows(name, channels=False):   # the stage rows (and channels) of a variable, as an op of the program
            var = block.var(name)
            if channels:
                return layers.slice(var, axes=[0, 2], starts=[0, 0], ends=[STAGE_ROWS, STAGE_CHANNELS]).name
            return layers.slice(var, axes=[0], starts=[0], ends=[STAGE_ROWS]).name

        conv = next(op for op in of("short_conv") if "Bias" in op.inputs)
        stages = [rows(conv.inputs["X"][0], True), rows(conv.outputs["Out"][0], True)]
        scans = of("selective_scan")
        for scan in (scans[0], scans[-1]):
            stages += [rows(scan.inputs["X"][0], True), rows(scan.inputs["Dt"][0], True), rows(scan.inputs["B"][0]),
                       rows(scan.inputs["C"][0]), rows(scan.outputs["Out"][0], True)]
        attention = of("fused_attention")[0]
        stages += [rows(attention.inputs[s][0]) for s in ("Q", "K", "V")] + [rows(attention.outputs["Out"][0])]
        rate = layers.learning_rate_scheduler.linear_lr_warmup(
            job["learning_rate"], job["lr_warmup_steps"], job["lr_warmup_start"], job["learning_rate"])
        fluid.optimizer.Adam(learning_rate=rate, beta1=job["adam_beta1"], beta2=job["adam_beta2"],
                             epsilon=job["adam_epsilon"]).minimize(fetches["loss"])
    return (main, startup, feeds, fetches["loss"], [fetches["loss"].name, sampled.name] + stages)


def make_batch(rng: np.random.RandomState, cfg: dict, job: dict, rows: int) -> dict:
    """One host batch as a reader yields it: uniform random ids over the whole
    vocabulary, the next token as every position's label (the last position's
    is one id more)."""
    tokens = rng.randint(0, cfg["vocab_size"], size=(rows, job["seq_len"] + 1)).astype("int64")
    return {"ids": tokens[:, :-1], "labels": tokens[:, 1:]}


def _widths(cfg: dict) -> tuple:
    d = cfg["hidden_size"]
    return d, cfg["mamba_expand"] * d, cfg["mamba_d_state"], cfg["mamba_dt_rank"]


def parameters(cfg: dict) -> int:
    """The parameters the program builds, counted from the configuration."""
    d, inner, state, rank = _widths(cfg)
    heads, kv, head = cfg["num_attention_heads"], cfg["num_key_value_heads"], d // cfg["num_attention_heads"]
    mamba = (d * 2 * inner + inner * cfg["mamba_d_conv"] + inner + inner * (rank + 2 * state) + rank * inner + inner
             + inner * state + inner + inner * d + rank + 2 * state)
    attention = d * heads * head + 2 * d * kv * head + heads * head * d
    a_layer = 3 * d * cfg["intermediate_size"] + 2 * d
    kinds = cfg["layer_types"]
    return int(sum(a_layer + (mamba if kind == "mamba" else attention) for kind in kinds) + cfg["vocab_size"] * d + d)


def _mamba_layers(cfg: dict) -> int:
    return sum(kind == "mamba" for kind in cfg["layer_types"])


def flops_per_sample(cfg: dict, job: dict) -> float:
    """Operations the forward and backward passes of one sequence require
    (matrix multiplications only, 2 per multiply-add, backward twice the
    forward, nothing recomputed): per position a Mamba layer's four projections
    (2560 x 10240, 5120 x 192, 160 x 5120, 5120 x 2560), the attention layer's
    four and its two products over the causal pairs, every layer's three
    products at 8192, and the tied head.  Nothing for the scan, the
    convolution's taps and the gates, which no matrix unit computes
    (`selective_scan_flops` counts the scan's elementwise work apart)."""
    d, inner, state, rank = _widths(cfg)
    seq, heads = job["seq_len"], cfg["num_attention_heads"]
    head, kv = d // heads, cfg["num_key_value_heads"]
    part = {
        "mamba": 2 * d * 2 * inner + 2 * inner * (rank + 2 * state) + 2 * rank * inner + 2 * inner * d,
        "full_attention": (2 * d * heads * head + 2 * 2 * d * kv * head + 2 * heads * head * d
                           + 2 * heads * 2 * head * (seq + 1) / 2),
    }
    per_position = 2 * d * cfg["vocab_size"]
    for kind in cfg["layer_types"]:
        per_position += part[kind] + 3 * 2 * d * cfg["intermediate_size"]
    return 3.0 * seq * per_position


#: Elementwise operations a state element a token, forward: the decay's product
#: and its exp (counted as ONE operation: the peaks do not price it), the
#: input's product, the recurrence's multiply and add, the output's multiply
#: and add.
_SCAN_OPS = 7.0


def selective_scan_flops(cfg: dict, job: dict) -> float:
    """Elementwise operations of a training step's `selective_scan` ops on a
    chip, forward and backward (twice the forward), nothing for what backward
    makes again: `_SCAN_OPS` a state element a token and six a channel (the
    softplus, the step's product, the skip)."""
    _, inner, state, _ = _widths(cfg)
    tokens = job["batch_per_chip"] * job["seq_len"]
    return 3.0 * _mamba_layers(cfg) * tokens * inner * (_SCAN_OPS * state + 6.0)


def selective_scan_bytes(cfg: dict, job: dict) -> float:
    """Bytes those ops have to move at the least: xs, the step's projection and
    the output in bf16 and B, C in bf16 once forward (5120 x 3 x 2 + 32 x 2
    bytes a token: about 31 KB, and 50 KB with the float32 step and decay a
    kernel would keep in VMEM), the same again for the gradients backward and
    the inputs read once more there."""
    _, inner, state, _ = _widths(cfg)
    a_token = 3 * inner * 2 + 2 * state * 2
    return float(3 * a_token * job["batch_per_chip"] * job["seq_len"] * _mamba_layers(cfg))


def scan_recurrence(x, dt, b, c, a_log, d_skip, dt_bias, bf16_state=False, bf16_step=False):
    """y [rows, T, d] float32 of the recurrence in the module's docstring over
    x and the step's projection dt [rows, T, d], B, C [rows, T, N], A_log [d,
    N], D and b_dt [d]: one token at a time, a float32 [d, N] state.
    `bf16_state` rounds the state to bf16's eight bits after every token,
    `bf16_step` the step and the decay (what the scan stage's limit has to
    refuse; `reduce_precision`, which XLA may not take out as it may a pair of
    casts)."""
    import jax
    import jax.numpy as jnp

    def low(t):
        return jax.lax.reduce_precision(t, exponent_bits=8, mantissa_bits=7)

    with jax.default_matmul_precision("highest"):
        x, dt, b, c = (jnp.asarray(t, jnp.float32) for t in (x, dt, b, c))
        A = -jnp.exp(jnp.asarray(a_log, jnp.float32))
        step = jax.nn.softplus(dt + jnp.asarray(dt_bias, jnp.float32))
        if bf16_step:
            step = low(step)

        def token(h, at):
            x_t, s_t, b_t, c_t = at                                            # [rows, d], [rows, d], [rows, N] x 2
            decay = jnp.exp(s_t[..., None] * A)
            h = (low(decay) if bf16_step else decay) * h + (s_t * x_t)[..., None] * b_t[:, None, :]
            if bf16_state:
                h = low(h)
            return h, jnp.sum(h * c_t[:, None, :], -1)

        h0 = jnp.zeros(x.shape[:1] + A.shape, jnp.float32)
        _, y = jax.lax.scan(token, h0, tuple(t.swapaxes(0, 1) for t in (x, step, b, c)))
        return y.swapaxes(0, 1) + jnp.asarray(d_skip, jnp.float32) * x


def reference(params: dict, batch: dict, cfg: dict, program=None, inner_norms=True, precision="highest"):
    """(loss, the sampled positions' logits [rows, sample, vocab], the first
    Mamba layer's taps [d, 4] and convolution bias, A_log, D and b_dt of the
    first and the last Mamba layer stacked, the attention's queries [rows, 20,
    sample, 128] and keys [rows, 1, sample, 128] at `attention_sample`'s
    positions, the last Mamba layer's step projection [rows, sample, channels],
    B and C [rows, sample, 16] there) of `batch` in plain float32 jax.numpy, one sequence at a time;
    `params` maps the program's parameter names to arrays, which may lie split
    over a mesh (the runner hands them as the scope holds them: every
    operation here is one GSPMD partitions by itself).  No kernel and no chunk:
    the scan is `scan_recurrence`'s step over the tokens, the convolution four
    shifted products, attention explicit causal scores two heads at a time,
    the head and the loss 1024 positions at a time.  Two controls
    (tools/chip_jamba_controls.py): `inner_norms` False leaves Jamba's three
    inner norms out, `precision` "default" computes the products as the chip
    does unasked (bf16 operands)."""
    import jax
    import jax.numpy as jnp

    kinds, eps = cfg["layer_types"], cfg["rms_norm_eps"]
    d, inner, state, rank = _widths(cfg)
    taps, heads, kv = cfg["mamba_d_conv"], cfg["num_attention_heads"], cfg["num_key_value_heads"]
    head = d // heads
    mambas = [i for i, kind in enumerate(kinds) if kind == "mamba"]

    def p(name):
        return jnp.asarray(params[name], jnp.float32)

    def rms(x, gain):
        return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps) * p(gain)

    def mamba(a, pre, seq):
        both = a @ p(f"{pre}.in.w")
        xs, z = both[:, :inner], both[:, inner:]
        w = p(f"{pre}.conv.w")
        xs = jax.nn.silu(sum(w[:, j] * jnp.pad(xs, ((taps - 1 - j, 0), (0, 0)))[:seq] for j in range(taps))
                         + p(f"{pre}.conv.b"))
        low = xs @ p(f"{pre}.x.w")
        dt, b, c = low[:, :rank], low[:, rank:rank + state], low[:, rank + state:]
        if inner_norms:
            dt, b, c = rms(dt, f"{pre}.dt_norm.w"), rms(b, f"{pre}.b_norm.w"), rms(c, f"{pre}.c_norm.w")
        dt = dt @ p(f"{pre}.dt.w")
        y = scan_recurrence(xs[None], dt[None], b[None], c[None], p(f"{pre}.a_log"), p(f"{pre}.d"), p(f"{pre}.dt.b"))[0]
        sample = attention_sample(seq)
        return (y * jax.nn.silu(z)) @ p(f"{pre}.out.w"), (dt[sample, :STAGE_CHANNELS], b[sample], c[sample])

    def attention(a, pre, seq):
        at = jnp.arange(seq)
        q = (a @ p(f"{pre}.q.w")).reshape(seq, heads, head).transpose(1, 0, 2)
        k = (a @ p(f"{pre}.k.w")).reshape(seq, kv, head).transpose(1, 0, 2)
        v = (a @ p(f"{pre}.v.w")).reshape(seq, kv, head).transpose(1, 0, 2)

        def two_heads(j):   # NO positions: the scores are the plain products
            qs = jax.lax.dynamic_slice_in_dim(q, 2 * j, 2, 0)
            scores = jnp.einsum("hqd,kd->hqk", qs, k[0]) / np.sqrt(head)
            scores = jnp.where(at[None, :] <= at[:, None], scores, -jnp.inf)
            return jnp.einsum("hqk,kd->hqd", jax.nn.softmax(scores, -1), v[0])

        ctx = jax.lax.map(two_heads, jnp.arange(heads // 2)).reshape(heads, seq, head)
        sample = attention_sample(seq)
        return ctx.transpose(1, 0, 2).reshape(seq, heads * head) @ p(f"{pre}.out.w"), (q[:, sample], k[:, sample])

    def one_sequence(row):
        ids, labels = row
        seq = ids.shape[0]
        table = p("lm.tok_emb")
        x = table[ids]
        first_qk = None
        for i, kind in enumerate(kinds):
            pre = f"lm.l{i}"
            a = rms(x, f"{pre}.ln1.w")
            if kind == "mamba":
                out, last_operands = mamba(a, f"{pre}.mamba", seq)
                h = x + out
            else:
                out, qk = attention(a, f"{pre}.attn", seq)
                h, first_qk = x + out, first_qk or qk
            m = rms(h, f"{pre}.ln2.w")
            x = h + (jax.nn.silu(m @ p(f"{pre}.ffn.gate.w")) * (m @ p(f"{pre}.ffn.up.w"))) @ p(f"{pre}.ffn.down.w")
        x = rms(x, "lm.final_norm.w")
        block = min(1024, seq)

        def ce_of(part):   # the tied head and the cross entropy, a block of positions at a time
            hidden, target = part
            logp = jax.nn.log_softmax(hidden @ table.T, -1)
            return -jnp.take_along_axis(logp, target[:, None], 1)[:, 0].sum()

        whole = seq - seq % block
        ce = jax.lax.map(ce_of, (x[:whole].reshape(-1, block, d), labels[:whole].reshape(-1, block))).sum()
        if whole < seq:
            ce = ce + ce_of((x[whole:], labels[whole:]))
        return (x[logit_sample(seq)] @ table.T, ce) + first_qk + last_operands

    with jax.default_matmul_precision(precision):
        rows = tuple(jnp.asarray(batch[n], jnp.int32) for n in FEEDS)
        out, ce_sum, q_first, k_first, *last_operands = jax.lax.map(one_sequence, rows)
        pre = f"lm.l{mambas[0]}.mamba"
        scan_params = tuple(jnp.stack([p(f"lm.l{i}.mamba.{n}")[:STAGE_CHANNELS] for i in (mambas[0], mambas[-1])])
                            for n in ("a_log", "d", "dt.b"))
        return ((ce_sum.sum() / rows[1].size, out, p(f"{pre}.conv.w")[:STAGE_CHANNELS], p(f"{pre}.conv.b")[:STAGE_CHANNELS])
                + scan_params + (q_first, k_first) + tuple(last_operands))


def _rms(t) -> float:
    return float(np.sqrt(np.mean(np.square(t))))


def _plain_conv(x, w, bias, rounding=lambda t: t):
    """silu(conv_K(x) + bias) of x [rows, T, d] with filter w [d, K] in float32
    numpy; `rounding` is applied to every intermediate."""
    x = np.asarray(x, "f4")
    taps, acc = w.shape[1], None
    for j in range(taps):
        term = rounding(np.pad(x, ((0, 0), (taps - 1 - j, 0), (0, 0)))[:, :x.shape[1]] * w[:, j])
        acc = term if acc is None else rounding(acc + term)
    acc = rounding(acc + bias)
    return rounding(acc / (1.0 + np.exp(-acc)))


def conv_errors(x, out, w, bias) -> dict:
    """The program's convolution against float32 numpy on its own input and
    float32 taps and bias: root-mean-square error over the root-mean-square
    output; and the same convolution with every intermediate, the taps and the
    bias rounded to bf16 (what the limit has to refuse)."""
    w, bias = np.asarray(w, "f4"), np.asarray(bias, "f4")
    want = _plain_conv(x, w, bias)
    scale = max(_rms(want), 1e-30)
    return {"conv_error": _rms(np.asarray(out, "f4") - want) / scale,
            "conv_error_bf16": _rms(_plain_conv(x, _bf16(w), _bf16(bias), _bf16) - want) / scale}


@functools.lru_cache(maxsize=4)
def _recurrence_jit(bf16_state, bf16_step):
    import jax

    return jax.jit(functools.partial(scan_recurrence, bf16_state=bf16_state, bf16_step=bf16_step))


def scan_errors(layers, a_log, d_skip, dt_bias) -> dict:
    """The program's `selective_scan` output against the float32 recurrence on
    its own xs, dt, B, C, the worse of `layers` (the first and the last Mamba
    layer's five stage tensors; the parameters' rows stacked alike).
    `scan_error`: root-mean-square difference from the recurrence's output
    ROUNDED to bf16 as the op rounds its own, over the root-mean-square output.
    `scan_error_unrounded`: the same against the float32 output, which the
    output's own rounding dominates.  Beside them the recurrence with its state,
    and with its step and decay, rounded to bf16 after every token, read the
    first way: what the limit has to refuse."""
    mine, plain, state, step, decays = [], [], [], [], []
    for (x, dt, b, c, out), A, D, bias in zip(layers, a_log, d_skip, dt_bias):
        operands = tuple(np.asarray(t, "f4") for t in (x, dt, b, c, A, D, bias))
        want = np.asarray(_recurrence_jit(False, False)(*operands))
        scale = max(_rms(want), 1e-30)
        rounded = want if np.asarray(out).dtype == np.float32 else _bf16(want)    # as the op rounded its own
        out = np.asarray(out, "f4")
        mine.append(_rms(out - rounded) / scale)
        plain.append(_rms(out - want) / scale)
        state.append(_rms(_bf16(np.asarray(_recurrence_jit(True, False)(*operands))) - rounded) / scale)
        step.append(_rms(_bf16(np.asarray(_recurrence_jit(False, True)(*operands))) - rounded) / scale)
        dt_f = np.log1p(np.exp(operands[1] + operands[6]))
        decays.append(float(np.exp(-dt_f[..., None] * np.exp(operands[4])).mean()))
    return {"scan_error": max(mine), "scan_error_unrounded": max(plain), "scan_error_bf16_state": min(state),
            "scan_error_bf16_step": min(step), "scan_decay_mean": decays}


def compare(got, want) -> dict:
    """The program's fetched variables (`build`) against the reference's
    outputs (`reference`): the two errors `REFERENCE_RTOL` bounds and the stage
    errors."""
    loss, want_loss = float(np.asarray(got[0]).reshape(-1)[0]), float(want[0])
    want_logits = np.asarray(want[1], "f4")                                   # [rows, sample, vocab]
    logits = np.asarray(got[1], "f4").transpose(1, 0, 2)
    scale = max(np.abs(want_logits).max(), 1e-9)
    q, key, v, out = (np.asarray(t, "f4").transpose(0, 2, 1, 3) for t in got[14:18])      # (rows, L, H, .) as handed
    stage_rows = q.shape[0]
    attention = _decoder.attention_errors(q, key, v, out, np.asarray(want[7])[:stage_rows], np.asarray(want[8])[:stage_rows])
    at = attention_sample(q.shape[2])
    inner = [np.abs(np.asarray(mine, "f4")[:, at] - np.asarray(theirs, "f4")[:stage_rows]).max() / np.abs(theirs).max()
             for mine, theirs in zip(got[10:13], want[9:12])]               # the last Mamba layer's dt, B, C
    return {
        "loss_error": abs(loss - want_loss) / max(abs(want_loss), 1e-6),
        "logit_error": float(np.abs(logits - want_logits).max() / scale),
        "inner_error": float(max(inner)),
        **conv_errors(got[2], got[3], want[2], want[3]),
        **scan_errors([got[4:9], got[9:14]], *(np.asarray(t, "f4") for t in want[4:7])),
        **attention,
    }


def reference_error(got, want) -> float:
    """How far the program is from the reference, as `REFERENCE_RTOL` counts
    it: the larger of the loss's and the sampled logits' error.  A failure
    (infinite error) is a convolution, a scan or an attention that misses
    float32 on the program's own tensors by more than `CONV_RTOL`, `SCAN_RTOL`
    or `ATTENTION_RTOL`, queries or keys that miss the reference's by more than
    `QK_RTOL`, or a last scan's operands that miss it by more than `INNER_RTOL`."""
    import json

    found = compare(got, want)
    print(json.dumps({"info": "reference_stages", **found, "conv_rtol": CONV_RTOL, "scan_rtol": SCAN_RTOL,
                      "attention_rtol": ATTENTION_RTOL, "qk_rtol": QK_RTOL, "inner_rtol": INNER_RTOL}), flush=True)
    if (not found["conv_error"] <= CONV_RTOL or not found["scan_error"] <= SCAN_RTOL
            or not found["attention_error"] <= ATTENTION_RTOL or not found["qk_error"] <= QK_RTOL
            or not found["inner_error"] <= INNER_RTOL):
        return float("inf")
    return max(found["loss_error"], found["logit_error"])
