"""BERT masked-LM pre-training: how the benchmark builds it through the
framework, a plain float32 reference of the same architecture, and the
operations one sequence needs.

Architecture: Devlin et al. 2018 (arXiv:1810.04805), sizes from
google-research/bert `bert_config.json`.  Departures of the program under
test (`paddle_tpu.models.transformer.build_bert`) from the paper, which the
reference follows so that the two compute the same function:

  * no token-type (segment) embedding and no next-sentence head;
  * in training, dropout on the attention output in place of dropout on the
    attention probabilities (the reference runs the `for_test` clone, which
    has no dropout at all);
  * logits over every position, not only the masked ones; the loss is the
    mean over ALL positions of the cross entropy, 0 where the label is -100;
  * layer-norm epsilon 1e-5 (the paper's code uses 1e-12);
  * token ids are uniform random, so the loss starts near ln(vocab) * 0.15.
"""
from __future__ import annotations

import numpy as np

FEEDS = ("ids", "labels", "pos_ids")
#: Reference check on 8 seeded sequences, the larger of two errors: the
#: loss, |program - reference| / reference, and the logits, max |program -
#: reference| over the largest |reference logit|.  Both are needed.  The
#: zoo draws EVERY parameter N(0, 0.02), the layer-norm gains too, so
#: activations are ~0.02, the logits ~1e-2 and the loss is 0.15 ln(vocab)
#: to 1e-6 whatever the precision: the loss alone cannot fail (1.7e-7 on
#: the chip, PR 22) and only catches a wrong reduction or mask.  The logits
#: go through every layer: the program rounds activations to bf16 (2**-9
#: relative each) over f32 master weights, layer-norm statistics and
#: accumulation, and agrees with the float32 reference to 4.6e-3 to 5.2e-3
#: of the largest logit (my chip runs, PR 22).  Parameters themselves held
#: in bf16, or bf16 accumulation over 768 or 3072 terms, is off by several
#: 1e-2, and so fails this.
REFERENCE_RTOL = 1.5e-2


def build(cfg: dict, job: dict):
    """(main, startup, feed variables by name, loss variable, the variables
    the reference is compared on: loss and logits) of the train program, as
    a user of the framework gets it by default."""
    from paddle_tpu.models import transformer

    main, startup, feeds, fetches = transformer.build_bert(
        vocab_size=cfg["vocab_size"], seq_len=job["seq_len"],
        d_model=cfg["hidden_size"], n_layers=cfg["num_hidden_layers"],
        n_heads=cfg["num_attention_heads"], d_ff=cfg["intermediate_size"],
        dropout_prob=cfg["hidden_dropout_prob"],
        learning_rate=job["learning_rate"], with_optimizer=True,
        dtype=cfg["compute_dtype"], use_fused_attention=True)
    ops = main.global_block().ops
    head = next(i for i, op in enumerate(ops) if op.type == "mul"
                and op.inputs["Y"] == ["bert.lm_head.w"])
    logits = ops[head + 1].outputs["Out"][0]  # the bias add after it
    return main, startup, feeds, fetches["loss"], [fetches["loss"].name, logits]


def make_batch(rng: np.random.RandomState, cfg: dict, job: dict,
               rows: int) -> dict:
    """One host batch as a reader yields it: int64 ids, labels with -100
    where the position is not masked (15% are), positions 0..L-1."""
    seq, vocab = job["seq_len"], cfg["vocab_size"]
    ids = rng.randint(0, vocab, size=(rows, seq)).astype("int64")
    labels = np.where(rng.rand(rows, seq) < job["mask_fraction"], ids, -100)
    pos = np.tile(np.arange(seq, dtype="int64"), (rows, 1))
    return {"ids": ids, "labels": labels.astype("int64"), "pos_ids": pos}


def flops_per_sample(cfg: dict, job: dict) -> float:
    """Operations the forward and backward passes of one sequence require
    (matrix multiplications only, 2 per multiply-add, backward twice the
    forward, nothing recomputed): per token and layer the four attention
    projections, the two feed-forward products and the two attention
    products against `seq_len` keys; once per token the LM head."""
    d, ff = cfg["hidden_size"], cfg["intermediate_size"]
    seq, vocab = job["seq_len"], cfg["vocab_size"]
    per_layer = 2 * (4 * d * d + 2 * d * ff) + 2 * 2 * seq * d
    forward = cfg["num_hidden_layers"] * per_layer + 2 * d * vocab
    return 3.0 * forward * seq


def reference(params: dict, batch: dict, cfg: dict, program=None):
    """(masked-LM loss, logits [rows, seq, vocab]) of `batch` in plain
    float32 jax.numpy; `params` maps the program's parameter names to
    arrays."""
    import jax
    import jax.numpy as jnp

    heads = cfg["num_attention_heads"]

    def p(name):
        return jnp.asarray(params[name], jnp.float32)

    def layer_norm(x, prefix):
        mean = x.mean(-1, keepdims=True)
        var = jnp.square(x - mean).mean(-1, keepdims=True)
        return (x - mean) / jnp.sqrt(var + 1e-5) * p(prefix + ".w") + p(prefix + ".b")

    def dense(x, prefix):
        return x @ p(prefix + ".w") + p(prefix + ".b")

    with jax.default_matmul_precision("highest"):
        ids = jnp.asarray(batch["ids"], jnp.int32)
        labels = jnp.asarray(batch["labels"], jnp.int32)
        x = p("bert.tok_emb")[ids] + p("bert.pos_emb")[jnp.asarray(batch["pos_ids"], jnp.int32)]
        x = layer_norm(x, "bert.emb_ln")
        rows, seq, d = x.shape
        for i in range(cfg["num_hidden_layers"]):
            pre = f"bert.l{i}"

            def split(t):
                return t.reshape(rows, seq, heads, d // heads).transpose(0, 2, 1, 3)

            q, k, v = (split(dense(x, f"{pre}.attn.{n}")) for n in "qkv")
            scores = jnp.einsum("bhqd,bhkd->bhqk", q, k) / np.sqrt(d // heads)
            ctx = jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(scores, -1), v)
            ctx = ctx.transpose(0, 2, 1, 3).reshape(rows, seq, d)
            x = layer_norm(x + dense(ctx, f"{pre}.attn.out"), f"{pre}.ln1")
            h = jax.nn.gelu(dense(x, f"{pre}.ffn1"), approximate=False)
            x = layer_norm(x + dense(h, f"{pre}.ffn2"), f"{pre}.ln2")
        logits = dense(x, "bert.lm_head")
        labels = labels.reshape(-1)
        logp = jax.nn.log_softmax(logits.reshape(rows * seq, -1), -1)
        picked = jnp.take_along_axis(logp, jnp.maximum(labels, 0)[:, None], 1)[:, 0]
        return jnp.where(labels == -100, 0.0, -picked).mean(), logits


def reference_error(got, want) -> float:
    """How far the program's (loss, logits) are from the reference's, as
    `REFERENCE_RTOL` counts it."""
    loss, want_loss = float(np.asarray(got[0]).reshape(-1)[0]), float(want[0])
    logits, want_logits = np.asarray(got[1], "f4"), np.asarray(want[1], "f4")
    return max(abs(loss - want_loss) / max(abs(want_loss), 1e-6),
               float(np.abs(logits - want_logits).max()
                     / max(np.abs(want_logits).max(), 1e-9)))
