"""Phi-4-mini-flash-reasoning causal-LM training: how the benchmark builds it
through the framework, a plain float32 reference of the same architecture, and
the operations one sequence needs.

Architecture: microsoft/Phi-4-mini-flash-reasoning `config.json` (`model_type:
phi4flash`); the layer layout and equations are SambaY's (Ren et al. 2025,
"Decoder-Hybrid-Decoder Architecture for Efficient Reasoning with Long
Generation", arXiv:2507.06607), and what the config does not give is listed in
the configuration file's `assumed`.  Every norm is a LayerNorm with a gain and a
bias, eps 1e-5; with u = ln(x; ln1) and w = ln(h; ln2) a layer is

    h = x + op(u),   y = h + (silu(w Wg) * (w Wu)) Wd     the MLP 10240 wide in EVERY layer, no biases
    mamba            [xs, z] = split(u W_in)              [2560 -> 2 x 5120]
                     xs = silu(conv_4(xs) + b_conv)       depthwise, causal, zeros before the sequence's start
                     [dt, B, C] = split(xs W_x)           [5120 -> 160 + 16 + 16], NO inner norms
                     dt = softplus(dt W_dt + b_dt),  A = -exp(A_log)
                     h_t = exp(dt_t A) h_{t-1} + (dt_t xs_t) B_t,  m_t = h_t C_t + D xs_t     (float32 [5120, 16] state)
                     op(u) = (m * silu(z)) W_out          the layer `memory_layer` also hands on m
    sliding_attention  q = u Wq + bq (40 heads of 64), k = u Wk + bk, v = u Wv + bv (20 heads; query head j reads
                     key/value head j div 2), no positions, scale 64^-0.5, softmax over the keys j with
                     i - 512 < j <= i;  op(u) = concat(heads) Wo + bo
    full_attention   the same over all keys j <= i; the layer `kv_layer` also hands on its k and v
    gmu              op(u) = (silu(u W1) * m) W2          m the kept scan output, W1 2560 x 5120, W2 5120 x 2560
    cross_attention  q = u Wq + bq;  causal softmax attention of q on the KEPT k, v;  op(u) = ctx Wo + bo
    loss             mean over every position of CE( ln(y_L; final_norm) E^T, the next token ),  E the tied embedding

The reference computes the scan as the recurrence, token by token
(`benchmark/models/jamba.py: scan_recurrence`), never the chunked form or the
kernels the program's op uses; the convolution as four shifted multiply-adds;
each attention as explicit scores under its rule, one key/value head's two
query heads and a block of queries at a time; the head and the loss a block of
positions at a time.  No kernel, no recomputation.

Departures of the program under test
(`paddle_tpu.models.transformer.build_causal_lm`) from the published model,
which the reference follows so that the two compute the same function (the
configuration file's `departures` is this list, word for word):

  * 6 of the 32 layers, the published layers 0, 1, 16, 17, 18, 19: a Mamba layer, a sliding-window layer, the Mamba layer that keeps its scan output, the full-attention layer that keeps its keys and values, a Gated Memory Unit and a cross-attention layer, the least that holds a layer of every kind; 25008 of the 200064 rows of the tied embedding (an eighth), ids drawn from the slice and the loss over it;
  * plain softmax attention where the released checkpoint has Differential Attention (the paper's abstract): the catalog row has no key for it (no lambda initialisation, no sub-norm width), so it is not built; two softmaxes over 20 heads with 128-wide values for one over 40 heads with 64-wide ones, the same size of arithmetic;
  * Adam for AdamW (the framework has no AdamW), learning rate 3e-4 reached by a linear warm-up over the first 200 steps from 1e-6, betas 0.9 / 0.95, epsilon 1e-8, no decay;
  * weights are random from the run's seed: N(0, 0.02) for every matrix and the attention projections' biases, U(-0.5, 0.5) for the convolution's taps (Mamba's own code leaves them at nn.Conv1d's default, 4^-0.5; at N(0, 0.02) and without inner norms the state's part of a scan's output is 2e-4 of the skip's and no check can tell a wrong state), the convolution's bias 0, norm gains 1 and biases 0, A_log[c, n] = ln(n + 1), D = 1, b_dt the inverse softplus of a log-uniform draw on [1e-3, 1e-1] (`config.json` has no initialisation);
  * token ids are uniform random over the 25008-row slice with no padding and no document boundaries (a row is one whole sequence, the state starts at zero with it), every position is a label (the next token), so the cross entropy starts near ln(25008).
"""
from __future__ import annotations

import numpy as np

from benchmark.models import jamba as _jamba
from benchmark.models import lfm2 as _decoder
from benchmark.runners import train as _runner

FEEDS = ("ids", "labels")

#: The larger of the loss's relative error and the sampled logits' error over
#: the largest |reference logit| (bf16 activations over float32 masters through
#: six layers and a bf16 tied head; the logits are the head's own operands
#: multiplied again at the sampled positions: `build`).  On the chip at the
#: published widths (my chip runs, PR 50: eleven seeds; PERF.md section 6): the
#: sound program 2.19e-2 to 2.57e-2, the loss 1.9e-6 to 1.5e-5 (higher than
#: Jamba's 1.08e-2 behind two layers: the taps of U(-0.5, 0.5) make a Mamba
#: layer's output a large part of the stream).  The reference with a fault in
#: it (tools/chip_phi4flash_controls.py): the window layer computed causally
#: 0.114, the cross layer on its OWN projection of K and V 0.135, the GMU on the
#: gated y * silu(z) 0.136.  The reference's products at the chip's default
#: precision (bf16 operands) read 2.25e-2 beside 2.19e-2 sound: the program's
#: products ARE bf16, so no limit can refuse that reference (as in Jamba's cell).
REFERENCE_RTOL = 5e-2
LOGIT_SAMPLE = _decoder.LOGIT_SAMPLE
#: The stage rows and channels, as Jamba's cell has them: the program's own
#: tensors of the stages below are compared on the first `STAGE_ROWS` of the 8
#: check rows and, for the scan, the convolution and the GMU's gate (all
#: independent a channel), the first `STAGE_CHANNELS` of the 5120 channels; the
#: slices are ops of the program.
STAGE_ROWS = _jamba.STAGE_ROWS
STAGE_CHANNELS = _jamba.STAGE_CHANNELS
#: THE SCAN STAGE of both Mamba layers, and THE CONVOLUTION of both: Jamba's
#: cell's checks and limits for the same ops at the same shapes
#: (benchmark/models/jamba.py has the readings behind them).  Here, on one chip
#: with no `shard_map` round the kernels (my chip runs, PR 50, eleven seeds): the
#: scan 1.7e-6 to 6.5e-6 sound; THE PROGRAM with its state rounded to bf16 where
#: a chunk hands it on 6.16e-4; the recurrence itself with a bf16 state after
#: every token 8.6e-4 to 1.3e-3, with a bf16 step and decay 7.9e-3 to 2.6e-2.
#: (With the taps drawn N(0, 0.02) the state's part of the output lies under
#: the output's own bf16 step and a bf16 state reads 5.8e-6, a float32
#: simulation: the configuration's `assumed` has why the taps are U(-0.5, 0.5).)
#: The convolution 1.678e-3 sound, every intermediate in bf16 4.27e-3.
SCAN_RTOL = _jamba.SCAN_RTOL
CONV_RTOL = _jamba.CONV_RTOL
#: THE WINDOW STAGE: the window layer's attention on the program's own q (40
#: heads), k and v (20 heads) for `ATTENTION_SAMPLE` queries of the stage rows
#: against float32 softmax over the BAND (the 512 keys that end at the query's
#: own), largest error over the largest |output|; the other decoders' limit.  On
#: the chip (my chip runs, PR 50): 2.0e-3 to 3.3e-3 sound; the same float32
#: softmax over the whole causal triangle, which the kernels must NOT compute,
#: 0.241.  It does not tell bf16 scores apart (2.1e-3 to 4.1e-3 against itself:
#: PERF.md section 7, defect 13c).
WINDOW_RTOL = _decoder.ATTENTION_RTOL
#: THE CROSS STAGE: the cross layer's attention on its own q and the KEPT k, v
#: (the op's own operands), float32 causal softmax: the same limit; 1.7e-3 to
#: 3.4e-3 sound on the chip.  What the cross layer READ is `KEPT_KV_RTOL`'s.
CROSS_RTOL = _decoder.ATTENTION_RTOL
ATTENTION_SAMPLE = _decoder.ATTENTION_SAMPLE
#: The window layer's queries and keys at the sampled positions against the
#: reference's (biases included), over the largest |value|: one Mamba layer's
#: bf16 roundings lie before them; the limit is LFM2's for the same check
#: behind one layer.  On the chip 1.03e-2 to 1.21e-2 sound.
QK_RTOL = _decoder.QK_RTOL
#: WHAT THE CROSS LAYER READ: the K operand of its attention at the sampled
#: positions against the reference's KEPT keys (layer `kv_layer`'s projection of
#: ITS input), over the largest |value|: three layers' roundings lie before
#: them.  On the chip 1.45e-2 to 1.92e-2 sound; the reference whose cross layer
#: projects its OWN input with those weights 0.655 (two layers on, the stream
#: has moved); the window layer computed causally 8.0e-2.
KEPT_KV_RTOL = 4e-2
#: WHAT THE GMU READ: the Memory operand of its gate at the sampled positions
#: (`STAGE_CHANNELS` channels) against the reference's scan output m of layer
#: `memory_layer` BEFORE that layer's gate, over the largest |value|.  On the
#: chip 1.21e-2 to 1.75e-2 sound; the reference that hands on the gated
#: y * silu(z) 0.645; the window layer computed causally 6.8e-2.
MEMORY_RTOL = 4e-2
#: THE GMU STAGE: the gate's output against float32 silu(Gate) * Memory on the
#: op's own operands, ROUNDED to bf16 as the op rounds its own (the op reads
#: its operands behind an `optimization_barrier`, as the scan does):
#: root-mean-square difference over the root-mean-square output.  On the chip
#: 4.1e-5 to 5.5e-5 sound (the op computes in float32 and rounds once; what is
#: left is the chip's exp against numpy's where a product lies on a rounding
#: boundary); the same with the SiLU and the product each rounded to bf16
#: 2.88e-3 to 2.90e-3.  The limit is the two readings' geometric middle.
GMU_RTOL = 4e-4

logit_sample = _decoder.logit_sample
attention_sample = _decoder.attention_sample
_bf16 = _decoder._bf16
_rms = _jamba._rms

#: a fault put into the reference and the comparison, one at a time (tools/chip_phi4flash_controls.py)
FAULTS = ("window_as_causal", "cross_own_kv", "gmu_gated_memory")


def _mamba(cfg: dict) -> dict:
    return dict(expand=cfg["mamba_expand"], state=cfg["mamba_d_state"], dt_rank=cfg["mamba_dt_rank"], inner_norms=False,
                taps_bound=cfg["mamba_taps_bound"])


def layer_types(cfg: dict) -> list:
    """The kinds of the layers this cut holds, from the published layout as
    `assumed` writes it down: of the 32 layers the even ones are Mamba and the
    odd ones attention (`mb_per_layer` 2); below the boundary the attention is
    the sliding window's, layer 16 is the Mamba that keeps its scan output,
    layer 17 the full attention that keeps its keys and values, and from 18 on
    an even layer is a Gated Memory Unit and an odd one cross-attention."""
    whole, period = cfg["reduced_from"]["num_hidden_layers"], cfg["mb_per_layer"]
    boundary = whole // 2

    def kind(i):
        if i % period == 0:
            return "mamba" if i <= boundary else "gmu"
        return "sliding_attention" if i < boundary else "full_attention" if i == boundary + 1 else "cross_attention"

    return [kind(i) for i in cfg["published_layers"]]


def build(cfg: dict, job: dict):
    """(main, startup, feed variables by name, loss variable, the variables the
    reference is compared on) of the train program, as a user of the framework
    gets it: `build_causal_lm` with every layer a recomputed segment (a job
    may say `recompute_layers` false: the tests', which hold the two alike),
    then the learning rate's warm-up and Adam.  The compared variables: loss, the
    sampled positions' logits; then, on the first `STAGE_ROWS` rows, both Mamba
    layers' convolution input and output and xs, dt, B, C and scan output
    (`STAGE_CHANNELS` channels), the window layer's q, k, v and output, the
    cross layer's q, the K and V it read and its output, and the GMU's gate
    operand, the memory it read and its output (the same channels)."""
    import paddle_tpu as fluid
    from paddle_tpu import layers
    from paddle_tpu.models import transformer

    kinds = cfg["layer_types"]
    assert kinds == layer_types(cfg), "layer_types is the published layout written out"
    main, startup, feeds, fetches = transformer.build_causal_lm(
        vocab_size=cfg["vocab_size"], seq_len=job["seq_len"], d_model=cfg["hidden_size"],
        n_heads=cfg["num_attention_heads"], n_kv_heads=cfg["num_key_value_heads"], qk_norm=None, rotary=False,
        norm="layer", norm_eps=cfg["layer_norm_eps"], proj_bias=True, layer_types=kinds,
        sliding_window=cfg["sliding_window"], memory_layer=cfg["memory_layer"], kv_layer=cfg["kv_layer"],
        conv_kernel=cfg["mamba_d_conv"], mamba=_mamba(cfg), num_dense_layers=len(kinds),
        dense_width=cfg["intermediate_size"], tie_embedding=cfg["tie_word_embeddings"],
        recompute_layers=job.get("recompute_layers", True),
        with_optimizer=False, dtype=cfg["compute_dtype"])
    block = main.global_block()

    def of(kind):
        return [op for op in block.ops if op.type == kind]

    with fluid.program_guard(main, startup):
        # The sampled positions' logits [sample, rows, vocab] as the head computes them, from the head's own operands:
        # the final norm's output at the sampled (row, position) pairs times the tied table, a second op of the same
        # kind beside the head.  NOT a gather from the head's output: XLA lays 8 rows' logits out vocabulary-major for
        # the loss's reductions and copies all 3.3 GB of them position-major for a gather, and two of those beside
        # the 8.4 GB of training state the scope holds do not fit the chip (the clone compiled for the described
        # v5e planned 6.57 GB of temporaries so, 3.4 GB this way).  The head's output itself is held to the loss.
        head = next(op for op in block.ops if fetches["logits"].name in op.output_arg_names)
        assert head.type == "matmul" and head.inputs["Y"] == ["lm.tok_emb"], "the tied head"
        at = logit_sample(job["seq_len"])
        check_rows = np.arange(_runner.CHECK_ROWS)      # the rows the runner's clone is fed: the index is built for them
        pairs = np.stack(np.broadcast_arrays(check_rows[None, :], at[:, None]), -1).astype("int32")
        hidden = layers.gather_nd(block.var(head.inputs["X"][0]), layers.assign(pairs))
        sampled = layers.matmul(hidden, block.var("lm.tok_emb"), transpose_y=True)

        def rows(name, channels=False):   # the stage rows (and channels) of a variable, as an op of the program
            var = block.var(name)
            if channels:
                return layers.slice(var, axes=[0, 2], starts=[0, 0], ends=[STAGE_ROWS, STAGE_CHANNELS]).name
            return layers.slice(var, axes=[0], starts=[0], ends=[STAGE_ROWS]).name

        stages = []
        for conv in (op for op in of("short_conv") if "Bias" in op.inputs):
            stages += [rows(conv.inputs["X"][0], True), rows(conv.outputs["Out"][0], True)]
        for scan in of("selective_scan"):
            stages += [rows(scan.inputs["X"][0], True), rows(scan.inputs["Dt"][0], True), rows(scan.inputs["B"][0]),
                       rows(scan.inputs["C"][0]), rows(scan.outputs["Out"][0], True)]
        attentions = of("fused_attention")
        window = next(op for op in attentions if op.attr("mask", None) == "sliding_window")
        cross = next(op for op in attentions if op.attr("kept_kv", False))
        for attention in (window, cross):
            stages += [rows(attention.inputs[s][0]) for s in ("Q", "K", "V")] + [rows(attention.outputs["Out"][0])]
        gate = of("memory_gate")[0]
        stages += [rows(gate.inputs["Gate"][0], True), rows(gate.inputs["Memory"][0], True),
                   rows(gate.outputs["Out"][0], True)]
        rate = layers.learning_rate_scheduler.linear_lr_warmup(
            job["learning_rate"], job["lr_warmup_steps"], job["lr_warmup_start"], job["learning_rate"])
        fluid.optimizer.Adam(learning_rate=rate, beta1=job["adam_beta1"], beta2=job["adam_beta2"],
                             epsilon=job["adam_epsilon"]).minimize(fetches["loss"])
    return (main, startup, feeds, fetches["loss"], [fetches["loss"].name, sampled.name] + stages)


def make_batch(rng: np.random.RandomState, cfg: dict, job: dict, rows: int) -> dict:
    """One host batch as a reader yields it: uniform random ids over the slice
    of the vocabulary the cut holds, the next token as every position's label
    (the last position's is one id more)."""
    tokens = rng.randint(0, cfg["vocab_size"], size=(rows, job["seq_len"] + 1)).astype("int64")
    return {"ids": tokens[:, :-1], "labels": tokens[:, 1:]}


def _widths(cfg: dict) -> tuple:
    d = cfg["hidden_size"]
    return d, cfg["mamba_expand"] * d, cfg["mamba_d_state"], cfg["mamba_dt_rank"]


def _heads(cfg: dict) -> tuple:
    heads = cfg["num_attention_heads"]
    return heads, cfg["num_key_value_heads"], cfg["hidden_size"] // heads


def parameters_by_kind(cfg: dict) -> dict:
    """The parameters of a layer's operator by kind, the MLP and the two norms
    every layer has beside it (`a_layer`), and the tied embedding's rows."""
    d, inner, state, rank = _widths(cfg)
    heads, kv, head = _heads(cfg)
    q = d * heads * head + heads * head        # a projection with its bias; `out` has as many
    k = d * kv * head + kv * head
    return {
        "mamba": (d * 2 * inner + inner * cfg["mamba_d_conv"] + inner + inner * (rank + 2 * state) + rank * inner + inner
                  + inner * state + inner + inner * d),
        "sliding_attention": 2 * q - heads * head + d + 2 * k,
        "full_attention": 2 * q - heads * head + d + 2 * k,
        "gmu": 2 * d * inner,
        "cross_attention": 2 * q - heads * head + d,
        "a_layer": 3 * d * cfg["intermediate_size"] + 4 * d,
        "embedding": cfg["vocab_size"] * d,
    }


def parameters(cfg: dict) -> int:
    """The parameters the program builds, counted from the configuration."""
    part = parameters_by_kind(cfg)
    return int(sum(part["a_layer"] + part[kind] for kind in cfg["layer_types"]) + part["embedding"]
               + 2 * cfg["hidden_size"])


def _pairs(cfg: dict, seq: int) -> dict:
    """The (query, key) pairs each attention kind's rule allows over `seq` positions."""
    from paddle_tpu.ops.masked_attention import window_pairs

    triangle = seq * (seq + 1) // 2
    return {"sliding_attention": window_pairs(seq, cfg["sliding_window"]), "full_attention": triangle,
            "cross_attention": triangle}


def flops_per_sample(cfg: dict, job: dict) -> float:
    """Operations the forward and backward passes of one sequence require
    (matrix multiplications only, 2 per multiply-add, backward twice the
    forward, nothing for what backward makes again): per position every
    matrix of the layers (a Mamba layer's four projections, an attention
    layer's four, the GMU's two, the cross layer's two, every layer's three at
    10240) and the tied head (the table's lookup is no product); each
    attention's two products over the pairs its rule ALLOWS: the band for the
    window layer, the causal triangle for the full and the cross layer.
    Nothing for the scan, the taps, the gates and the norms, which no matrix
    unit computes (`selective_scan_flops` counts the scan's work apart)."""
    d, inner, state, rank = _widths(cfg)
    heads, kv, head = _heads(cfg)
    seq = job["seq_len"]
    q = d * heads * head
    matrices = {
        "mamba": d * 2 * inner + inner * (rank + 2 * state) + rank * inner + inner * d,
        "sliding_attention": 2 * q + 2 * d * kv * head, "full_attention": 2 * q + 2 * d * kv * head,
        "gmu": 2 * d * inner, "cross_attention": 2 * q,
    }
    pairs = _pairs(cfg, seq)
    forward = seq * 2.0 * d * cfg["vocab_size"]
    for kind in cfg["layer_types"]:
        forward += seq * 2.0 * (matrices[kind] + 3 * d * cfg["intermediate_size"])
        forward += 2 * 2.0 * heads * head * pairs.get(kind, 0)
    return 3.0 * forward


def window_attention_flops(cfg: dict, job: dict) -> float:
    """Operations of a step's window attentions on a chip: the two products
    forward and the four backward over the pairs the rule ALLOWS (the band),
    nothing for a masked pair a kernel computes anyway and nothing for the
    scores backward computes again."""
    heads, _, head = _heads(cfg)
    layers_ = sum(kind == "sliding_attention" for kind in cfg["layer_types"])
    return 6 * 2.0 * heads * head * _pairs(cfg, job["seq_len"])["sliding_attention"] * job["batch_per_chip"] * layers_


def window_attention_bytes(cfg: dict, job: dict) -> float:
    """Bytes those attentions have to move at the least: q, k, v and the
    output once forward and their four gradients once backward, bf16."""
    heads, kv, head = _heads(cfg)
    layers_ = sum(kind == "sliding_attention" for kind in cfg["layer_types"])
    return float(2 * 2 * (2 * heads + 2 * kv) * head * job["seq_len"] * job["batch_per_chip"] * layers_)


#: Jamba's module's counts for this cut's two Mamba layers on one chip: the same op at the same channels and state,
#: read from the same keys (`mamba_expand`, `mamba_d_state`, `mamba_dt_rank`, the "mamba" entries of `layer_types`)
selective_scan_flops = _jamba.selective_scan_flops
selective_scan_bytes = _jamba.selective_scan_bytes


def reference(params: dict, batch: dict, cfg: dict, program=None, fault=None, precision="highest"):
    """(loss, the sampled positions' logits [rows, sample, vocab], both Mamba
    layers' taps [2, channels, 4] and convolution biases, their A_log, D and b_dt
    stacked, the window layer's queries [rows, 40, sample, 64] and keys [rows,
    20, sample, 64] at `attention_sample`'s positions, the keys the cross layer
    READ there [rows, 20, sample, 64], the memory the GMU READ there [rows,
    sample, channels], the window) of `batch` in plain float32 jax.numpy, one sequence at a
    time; `params` maps the program's parameter names to arrays.  No kernel and
    no chunk: the scan is `scan_recurrence`'s step over the tokens, the
    convolution four shifted products, each attention explicit scores under
    its rule, a key/value head's query heads and 2048 queries at a time, the
    head and the loss 1024 positions at a time.  `fault` (one of `FAULTS`) puts
    one fault in: the window layer computed under the causal rule, the cross
    layer on its OWN input projected with the kept layer's key and value
    weights, the GMU on the gated y * silu(z).  `precision` "default" computes
    the products as the chip does unasked (bf16 operands)."""
    import jax
    import jax.numpy as jnp

    assert fault is None or fault in FAULTS, fault
    kinds, eps = cfg["layer_types"], cfg["layer_norm_eps"]
    d, inner, state, rank = _widths(cfg)
    heads, kv, head = _heads(cfg)
    group, taps, window = heads // kv, cfg["mamba_d_conv"], cfg["sliding_window"]
    mambas = [i for i, kind in enumerate(kinds) if kind == "mamba"]

    def p(name):
        return jnp.asarray(params[name], jnp.float32)

    def ln(x, pre):
        mean = jnp.mean(x, -1, keepdims=True)
        var = jnp.mean(jnp.square(x - mean), -1, keepdims=True)
        return (x - mean) * jax.lax.rsqrt(var + eps) * p(f"{pre}.w") + p(f"{pre}.b")

    def mamba(u, pre, seq):
        both = u @ p(f"{pre}.in.w")
        xs, z = both[:, :inner], both[:, inner:]
        w = p(f"{pre}.conv.w")
        xs = jax.nn.silu(sum(w[:, j] * jnp.pad(xs, ((taps - 1 - j, 0), (0, 0)))[:seq] for j in range(taps))
                         + p(f"{pre}.conv.b"))
        low = xs @ p(f"{pre}.x.w")
        dt, b, c = low[:, :rank] @ p(f"{pre}.dt.w"), low[:, rank:rank + state], low[:, rank + state:]
        m = _jamba.scan_recurrence(xs[None], dt[None], b[None], c[None], p(f"{pre}.a_log"), p(f"{pre}.d"),
                                   p(f"{pre}.dt.b"))[0]
        gated = m * jax.nn.silu(z)
        return gated @ p(f"{pre}.out.w"), (gated if fault == "gmu_gated_memory" else m)

    def project(u, pre, name, n):
        return (u @ p(f"{pre}.{name}.w") + p(f"{pre}.{name}.b")).reshape(u.shape[0], n, head).transpose(1, 0, 2)

    def attend(q, k, v, seq, band):
        """softmax(q k^T / sqrt(head) under the rule) v over [40, seq, 64] queries and [20, seq, 64] keys, values."""
        rows = min(2048, seq) if seq % min(2048, seq) == 0 else seq
        at = jnp.arange(seq)

        def a_kv_head(operands):
            qs, keys, values = operands                                       # [group, seq, head], [seq, head] x 2

            def a_block(first):
                mine = jax.lax.dynamic_slice_in_dim(qs, first, rows, 1)
                scores = jnp.einsum("hqd,kd->hqk", mine, keys) / np.sqrt(head)
                q_at = first + jnp.arange(rows)
                allowed = at[None, :] <= q_at[:, None]
                if band is not None:
                    allowed = allowed & (at[None, :] > q_at[:, None] - band)
                return jnp.einsum("hqk,kd->hqd", jax.nn.softmax(jnp.where(allowed, scores, -jnp.inf), -1), values)

            blocks = jax.lax.map(a_block, jnp.arange(0, seq, rows))            # [seq / rows, group, rows, head]
            return blocks.transpose(1, 0, 2, 3).reshape(group, seq, head)

        ctx = jax.lax.map(a_kv_head, (q.reshape(kv, group, seq, head), k, v))  # [kv, group, seq, head]
        return ctx.reshape(heads, seq, head).transpose(1, 0, 2).reshape(seq, heads * head)

    def one_sequence(row):
        ids, labels = row
        seq = ids.shape[0]
        table = p("lm.tok_emb")
        x = table[ids]
        sample = attention_sample(seq)
        kept, found = {}, {}
        for i, kind in enumerate(kinds):
            pre = f"lm.l{i}"
            u = ln(x, f"{pre}.ln1")
            if kind == "mamba":
                out, memory = mamba(u, f"{pre}.mamba", seq)
                if i == cfg["memory_layer"]:
                    kept["memory"] = memory
            elif kind == "gmu":
                found["memory"] = kept["memory"][sample, :STAGE_CHANNELS]
                out = (jax.nn.silu(u @ p(f"{pre}.gmu.in.w")) * kept["memory"]) @ p(f"{pre}.gmu.out.w")
            else:
                at = f"{pre}.attn"
                q = project(u, at, "q", heads)
                if kind == "cross_attention":
                    k, v = kept["kv"]
                    if fault == "cross_own_kv":
                        theirs = f"lm.l{cfg['kv_layer']}.attn"
                        k, v = project(u, theirs, "k", kv), project(u, theirs, "v", kv)
                    found["kept_k"] = k[:, sample]
                else:
                    k, v = project(u, at, "k", kv), project(u, at, "v", kv)
                if i == cfg["kv_layer"]:
                    kept["kv"] = (k, v)
                band = window if kind == "sliding_attention" and fault != "window_as_causal" else None
                if kind == "sliding_attention" and "q" not in found:
                    found["q"], found["k"] = q[:, sample], k[:, sample]
                out = attend(q, k, v, seq, band) @ p(f"{at}.out.w") + p(f"{at}.out.b")
            h = x + out
            w = ln(h, f"{pre}.ln2")
            x = h + (jax.nn.silu(w @ p(f"{pre}.ffn.gate.w")) * (w @ p(f"{pre}.ffn.up.w"))) @ p(f"{pre}.ffn.down.w")
        x = ln(x, "lm.final_norm")
        block = min(1024, seq)

        def ce_of(part):   # the tied head and the cross entropy, a block of positions at a time
            hidden, target = part
            logp = jax.nn.log_softmax(hidden @ table.T, -1)
            return -jnp.take_along_axis(logp, target[:, None], 1)[:, 0].sum()

        whole = seq - seq % block
        ce = jax.lax.map(ce_of, (x[:whole].reshape(-1, block, d), labels[:whole].reshape(-1, block))).sum()
        if whole < seq:
            ce = ce + ce_of((x[whole:], labels[whole:]))
        return x[logit_sample(seq)] @ table.T, ce, found["q"], found["k"], found["kept_k"], found["memory"]

    with jax.default_matmul_precision(precision):
        rows = tuple(jnp.asarray(batch[n], jnp.int32) for n in FEEDS)
        out, ce_sum, q_window, k_window, kept_k, memory = jax.lax.map(one_sequence, rows)

        def stacked(name):
            return jnp.stack([p(f"lm.l{i}.mamba.{name}")[:STAGE_CHANNELS] for i in mambas])

        return (ce_sum.sum() / rows[1].size, out, stacked("conv.w"), stacked("conv.b"), stacked("a_log"), stacked("d"),
                stacked("dt.b"), q_window, k_window, kept_k, memory, jnp.int32(window))


def attention_stage(q, k, v, out, band=None) -> dict:
    """The program's attention output [rows, Hq, L, dh] against float32 numpy
    on its own q, k [rows, Hkv, L, dh] and v, for `attention_sample`'s queries
    of every row and head under the rule (`band`: the window; None: every key
    up to the query's own): largest |error| over the largest |output|.  And
    the same reference with its scores rounded to bf16 against itself."""
    rows, hq, positions, dh = q.shape
    group = hq // k.shape[1]
    sample = attention_sample(positions)
    at = np.arange(positions)
    allowed = at[None, :] <= sample[:, None]
    if band is not None:
        allowed &= at[None, :] > sample[:, None] - band
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
    return {"error": worst / max(largest, 1e-30), "error_bf16_scores": rounded / max(largest, 1e-30)}


def _silu(x):
    return x / (1.0 + np.exp(-x))


def gmu_errors(gate, memory, out) -> dict:
    """The program's `memory_gate` output against float32 silu(Gate) * Memory
    on its own operands, rounded to bf16 as the op rounds its own:
    root-mean-square difference over the root-mean-square output; beside it the
    same with the SiLU and the product each rounded to bf16 (what the limit has
    to refuse)."""
    gate, memory = np.asarray(gate, "f4"), np.asarray(memory, "f4")
    want = _silu(gate) * memory
    scale = max(_rms(want), 1e-30)
    rounded = want if np.asarray(out).dtype == np.float32 else _bf16(want)
    return {"gmu_error": _rms(np.asarray(out, "f4") - rounded) / scale,
            "gmu_error_bf16": _rms(_bf16(_bf16(_silu(gate)) * memory) - rounded) / scale}


def _over_largest(mine, theirs) -> float:
    theirs = np.asarray(theirs, "f4")
    return float(np.abs(np.asarray(mine, "f4") - theirs).max() / max(np.abs(theirs).max(), 1e-30))


def compare(got, want, fault=None) -> dict:
    """The program's fetched variables (`build`) against the reference's
    outputs (`reference`): the two errors `REFERENCE_RTOL` bounds and the stage
    errors.  `fault` "window_as_causal" also computes the window stage's float32
    softmax over the whole causal triangle (what that stage has to refuse)."""
    loss, want_loss = float(np.asarray(got[0]).reshape(-1)[0]), float(want[0])
    want_logits = np.asarray(want[1], "f4")                                   # [rows, sample, vocab]
    logits = np.asarray(got[1], "f4").transpose(1, 0, 2)
    scale = max(np.abs(want_logits).max(), 1e-9)
    convs = [_jamba.conv_errors(got[2 + 2 * n], got[3 + 2 * n], np.asarray(want[2])[n], np.asarray(want[3])[n])
             for n in range(2)]
    scans = _jamba.scan_errors([got[6:11], got[11:16]], *(np.asarray(t, "f4") for t in want[4:7]))
    heads_major = [np.asarray(t, "f4").transpose(0, 2, 1, 3) for t in got[16:24]]       # (rows, L, H, .) as handed
    stage_rows = heads_major[0].shape[0]
    at = attention_sample(heads_major[0].shape[2])
    window = attention_stage(*heads_major[:4], band=None if fault == "window_as_causal" else int(want[11]))
    cross = attention_stage(*heads_major[4:])
    qk = max(_over_largest(mine[:, :, at], np.asarray(theirs)[:stage_rows])
             for mine, theirs in ((heads_major[0], want[7]), (heads_major[1], want[8])))
    return {
        "loss_error": abs(loss - want_loss) / max(abs(want_loss), 1e-6),
        "logit_error": float(np.abs(logits - want_logits).max() / scale),
        "conv_error": max(c["conv_error"] for c in convs),
        "conv_error_bf16": min(c["conv_error_bf16"] for c in convs),
        **scans,
        "window_error": window["error"], "window_error_bf16_scores": window["error_bf16_scores"],
        "cross_error": cross["error"], "cross_error_bf16_scores": cross["error_bf16_scores"],
        "qk_error": qk,
        "kept_kv_error": _over_largest(heads_major[5][:, :, at], np.asarray(want[9])[:stage_rows]),
        "memory_error": _over_largest(np.asarray(got[25], "f4")[:, at], np.asarray(want[10])[:stage_rows]),
        **gmu_errors(got[24], got[25], got[26]),
    }


LIMITS = (("conv_error", "CONV_RTOL"), ("scan_error", "SCAN_RTOL"), ("window_error", "WINDOW_RTOL"),
          ("cross_error", "CROSS_RTOL"), ("qk_error", "QK_RTOL"), ("kept_kv_error", "KEPT_KV_RTOL"),
          ("memory_error", "MEMORY_RTOL"), ("gmu_error", "GMU_RTOL"))


def failed_limits(found: dict) -> list:
    """The stage limits `found` (a `compare`) misses, and `REFERENCE_RTOL`
    where the loss or the logits miss it."""
    missed = [limit for key, limit in LIMITS if not found[key] <= globals()[limit]]
    if not max(found["loss_error"], found["logit_error"]) <= REFERENCE_RTOL:
        missed.append("REFERENCE_RTOL")
    return missed


def reference_error(got, want) -> float:
    """How far the program is from the reference, as `REFERENCE_RTOL` counts
    it: the larger of the loss's and the sampled logits' error.  A failure
    (infinite error) is a stage that misses its limit (`LIMITS`): a convolution,
    a scan, the window or the cross attention or the GMU's gate on the
    program's own tensors, the window layer's queries and keys, or what the
    cross layer or the GMU READ against what the reference says was kept."""
    import json

    found = compare(got, want)
    print(json.dumps({"info": "reference_stages", **found,
                      **{limit.lower(): globals()[limit] for _, limit in LIMITS}}), flush=True)
    if [limit for limit in failed_limits(found) if limit != "REFERENCE_RTOL"]:
        return float("inf")
    return max(found["loss_error"], found["logit_error"])
