"""Keye-VL-2.0's cell on the chip, what its comparison can and cannot tell: the
harness's own `benchmark.models.keye.compare` / `failed_limits` on the program's
check rows against the float32 reference, sound and then with a fault put in,
one at a time, so that each limit this PR brings has a reading it must refuse
beside the sound one (PERF.md, section 6, PRs 56, 57 and 59).  Nine faults go into THE
PROGRAM (a lowering or a seam of `ops/sparse_index_ops.py` is wrapped and the
check rows run again through a new executor), three are read on the program's own
fetched tensors of the stage row, one is the reference a precision lower:

  * `half_the_picks`: 1024 picks a query for 2048: `picks_count`;
  * `a_key_after_the_query`: every query of a chunk holds its band's last key,
    before it or after: `picks_count`;
  * `no_relu`, `no_weights`: the index scores without the ReLU, without w (the
    CHOICE alone: the alignment term keeps the sound scores): `PICKS_DIFFER_MAX`,
    `PICKS_GAP_MAX`;
  * `index_scores_in_bf16`: each index head's products rounded to bf16 before
    the ReLU, the weights and the sum: `PICKS_DIFFER_MAX`, `PICKS_GAP_MAX`;
  * `threshold_from_16_bits` (PR 57): the select made WRONG: a row's threshold
    and `last` from scores whose low 16 bits are dropped (bf16's order; the
    picks then compare the float32 scores with it): `PICKS_DIFFER_MAX`,
    `PICKS_GAP_MAX`, `picks_count`;
  * `dense_attention`: dense causal attention where the selected one belongs:
    `ATTENTION_RTOL`.  At the cell's size its 8-row clone holds BOTH attentions
    (the selected one still makes the log-sum-exp) and does not load beside the
    state (11.7 GB of temporaries: my chip runs, PR 56), so name it only under
    `DRY=1`; every run's `attention_error_dense` is the same fault in numpy on
    the program's own q, k, v;
  * `alignment_scores_in_bf16`: the alignment term's own index scores rounded
    to bf16 a head (the choice keeps the sound ones): `ALIGNMENT_RTOL`;
  * `target_of_one_head`: the alignment target from the first query head alone
    for the mean of all 32: `ALIGNMENT_RTOL`;
  * `choice_again_from_bf16_scores`: what a recomputed forward that chose AGAIN,
    from scores rounded otherwise, would hand backward: on the first layer's own
    qI, kI, w, q, k, v of the stage row, the share of (query, pick) pairs that
    differ between the choice from float32 scores and from scores rounded to
    bf16, and how far the attention's dq under the second mask lies from dq
    under the first (`gradient_change`).  The sound program reads 0 and 0.0: the
    choice is KEPT (`registry.set_kept`, must), and tests/test_keye.py holds the
    traced step to one select a layer;
  * `target_not_detached`: the alignment term's gradient to the attention's
    queries, on the stage row's first chunk: the op's own is 0.0 exactly (its
    backward rule returns none), the same term differentiated WITHOUT the
    stop_gradient reads `alignment_gradient_to_q` > 0;
  * `alignment_gradient_without_relu_mask` (PR 59): the benchmark's `correct`
    reads the alignment TERM and not its gradients, so the gradients have a
    comparison of their own (`tools/chip_index_alignment.py: differences`,
    `GRADIENT_RTOL`): on the stage row's first band's last chunk, the form the
    platform and the shape choose (`index_alignment_kernels`) against `jax.vjp`
    through `index_scores` (`alignment_gradient_error`, the largest of d_qI,
    d_kI and d_w), sound, and with G formed without `[P_h > 0]`;
  * `reference_default_precision`: the reference's float32 products at the
    chip's default precision (bf16 operands): `REFERENCE_SELF_RTOL`.

    chiprun --timeout 3000 -- python3 tools/chip_keye_controls.py 3560000501      (PERF.md, PR 56)

Names after the seed run those controls alone, beside `sound`.
`DRY=1` rehearses it tiny on the CPU; no number of that means anything.
"""
import contextlib
import gc
import json
import os
import sys
from types import SimpleNamespace

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

DRY = os.environ.get("DRY") == "1"

import jax
import jax.numpy as jnp
import numpy as np

import paddle_tpu as fluid
from benchmark import manifest as mf
from benchmark.models import keye, lfm2
from benchmark.runners.train import CHECK_ROWS
from paddle_tpu.core.registry import get_op_def
from paddle_tpu.ops import index_alignment_kernels as iak
from paddle_tpu.ops import sparse_index_ops as sio

import chip_index_alignment as alone      # tools/ is this script's directory

TINY = (dict(hidden_size=64, num_attention_heads=4, num_key_value_heads=2, head_dim=16, moe_intermediate_size=32, num_experts=2,
             num_routed_experts=8, experts_held_first=2, num_experts_per_tok=2, vocab_size=96, num_hidden_layers=2,
             rope_scaling=dict(mrope_section=[2, 3, 3]),
             sa_config=dict(indexer_head_dim=16, indexer_num_heads=4, indexer_num_kv_heads=1, kv_chunk_size=512,
                            q_chunk_size=512, topk=8)),
        dict(seq_len=32, batch_per_chip=1, ring=4))


@contextlib.contextmanager
def lowered_as(op_type, wrong):
    """The registered lowering of `op_type` replaced by `wrong(real, ctx, op, ins)` for the length of the block."""
    definition = get_op_def(op_type)
    real = definition.lower
    definition.lower = lambda ctx, op, ins: wrong(real, ctx, op, ins)
    try:
        yield
    finally:
        definition.lower = real


@contextlib.contextmanager
def seam(name, wrong):
    """`sparse_index_ops.<name>` replaced by `wrong(real, ...)` for the length of the block."""
    real = getattr(sio, name)
    setattr(sio, name, lambda *a, **k: wrong(real, *a, **k))
    try:
        yield
    finally:
        setattr(sio, name, real)


def with_attrs(op, **attrs):
    return SimpleNamespace(type=op.type, attr=lambda n, d=None: attrs.get(n, op.attr(n, d)), input=op.input, output=op.output)


def scores_of(fault, where="_select_row"):
    """`index_scores` with `fault` put in, for the CHOICE alone (`_select_row`)
    or for the alignment term alone (`_alignment_row`)."""
    def wrong(qi, ki, w):
        products = jnp.einsum("chd,kd->hck", qi, ki, preferred_element_type=jnp.float32)
        if fault == "index_scores_in_bf16":
            products = jax.lax.reduce_precision(products, 8, 7)
        if fault != "no_relu":
            products = jax.nn.relu(products)
        return jnp.sum(products * (1.0 if fault == "no_weights" else jnp.transpose(w)[:, :, None]), axis=0)

    def under(real, *args):
        with seam("index_scores", lambda _, *a: wrong(*a)):
            return real(*args)

    return lambda: seam(where, under)


def faults(cfg):
    def halved(real, ctx, op, ins):
        return real(ctx, with_attrs(op, topk=cfg["sa_config"]["topk"] // 2), ins)

    def after(real, scores, first_query, topk, select):
        return real(scores, first_query, topk, select).at[:, -1].set(True)

    def from_16_bits(real, scores, first_query, topk, select):
        def upper_half(masked, topk):
            bits = jax.lax.bitcast_convert_type(masked, jnp.int32) & jnp.int32(-65536)
            return select(jax.lax.bitcast_convert_type(bits, jnp.float32), topk)
        return real(scores, first_query, topk, upper_half)

    def dense(real, ctx, op, ins):
        return {**real(ctx, op, ins), "Out": real(ctx, op, {k: v for k, v in ins.items() if k != "Picks"})["Out"]}

    def one_head(real, q, k, lse, allowed, scale, *kernel):      # through the seam both forms pass: the chip's kernel takes the one head
        return real(q[:1], k[:1], lse[:1], allowed, scale, *kernel)

    return {
        "half_the_picks": lambda: lowered_as("sparse_index", halved),
        "a_key_after_the_query": lambda: seam("choose", after),
        "no_relu": scores_of("no_relu"),
        "no_weights": scores_of("no_weights"),
        "index_scores_in_bf16": scores_of("index_scores_in_bf16"),
        "threshold_from_16_bits": lambda: seam("choose", from_16_bits),
        "dense_attention": lambda: lowered_as("fused_attention", dense),
        "alignment_scores_in_bf16": scores_of("index_scores_in_bf16", "_alignment_row"),
        "target_of_one_head": lambda: seam("attention_target", one_head),
    }


def on_the_stage_row(got, cfg, depth):
    """The first layer's own operands of the stage row, as `keye.build` orders them."""
    staged = got[keye._HEAD + keye._LAYER * depth:][:keye._STAGE]
    return tuple(jnp.asarray(np.asarray(t)[0]) for t in staged[:7])        # qI, kI, w, picks, q, k, v


def choice_again(got, cfg):
    """See `choice_again_from_bf16_scores` above."""
    from paddle_tpu.ops.nn_ops import _xla_attention

    qi, ki, w, picks, q, k, v = on_the_stage_row(got, cfg, cfg["num_hidden_layers"])
    topk = cfg["sa_config"]["topk"]
    weights = sio.scaled_weights(w, qi.shape[1], qi.shape[2])

    def again(rounded):
        real = sio.index_scores
        with seam("index_scores", lambda _, *a: jax.lax.reduce_precision(real(*a), 8, 7) if rounded else real(*a)):
            return jax.jit(lambda: sio._select_row(qi, ki[:, 0], weights, topk)[0])()

    first, second = again(False), again(True)
    assert bool((first == picks).all()), "the op alone on the fetched operands makes the program's own choice"

    def dq(chosen):
        def loss(q):
            if jax.default_backend() == "tpu":
                from paddle_tpu.ops.masked_attention import selected_attention

                out = selected_attention(q[None], k[None], v[None], chosen[None], q.shape[-1] ** -0.5, True)[0][0]
            else:
                keys, values = (jnp.repeat(t, q.shape[0] // k.shape[0], 0)[None] for t in (k, v))
                out = _xla_attention(q[None], keys, values, None, True, q.shape[-1] ** -0.5, None,
                                     sio.unpack_bits(chosen[None], q.shape[1]))[0][0]
            return jnp.sum(out.astype(jnp.float32) ** 2)
        return jax.jit(jax.grad(loss))(q).astype(jnp.float32)

    one, other = dq(first), dq(second)
    differ = keye.unpack(np.asarray(first), q.shape[1]) != keye.unpack(np.asarray(second), q.shape[1])
    return {"picks_differ_choice_again": float(differ.sum() / 2 / keye.chosen_pairs(q.shape[1], topk)),
            "gradient_change": float(jnp.abs(one - other).max() / jnp.abs(one).max()),
            "gradient_change_same_choice": float(jnp.abs(one - dq(first)).max() / jnp.abs(one).max())}


def chunk_of_the_stage_row(got, cfg, last=False):
    """(qI, kI, the scaled w, q, k, allowed, `lse_of`, the attention's scale) of
    the first chunk of the stage row's first band, or the band's `last`: its
    queries against the band's keys."""
    qi, ki, w, picks, q, k, _ = on_the_stage_row(got, cfg, cfg["num_hidden_layers"])
    chunk = min(sio.CHUNK, q.shape[1])
    keys = min(sio.BAND, q.shape[1])
    rows = slice(keys - chunk, keys) if last else slice(0, chunk)
    allowed = sio.unpack_bits(picks[rows], q.shape[1])[:, :keys]
    weights = sio.scaled_weights(w, qi.shape[1], qi.shape[2])[rows]
    scale = q.shape[-1] ** -0.5

    def lse_of(q):
        s = jnp.einsum("hcd,hkd->hck", q, jnp.repeat(k[:, :keys], q.shape[0] // k.shape[0], 0),
                       preferred_element_type=jnp.float32) * scale
        return jax.nn.logsumexp(jnp.where(allowed, s, -jnp.inf), -1)

    return qi[rows], ki[:keys, 0], weights, q[:, rows], k[:, :keys], allowed, lse_of, scale


def target_not_detached(got, cfg):
    """See `target_not_detached` above."""
    qi, ki, weights, q, k, allowed, lse_of, scale = chunk_of_the_stage_row(got, cfg)

    def term(q, detached):
        target = sio.attention_target(q, k, jax.lax.stop_gradient(lse_of(q)), allowed, scale)
        target = jax.lax.stop_gradient(target) if detached else target
        return sio.chunk_divergence(qi, ki, weights, target, allowed) / q.shape[1]

    sound = jax.jit(jax.grad(lambda q: term(q, True)))(q)
    faulty = jax.jit(jax.grad(lambda q: term(q, False)))(q)
    return {"alignment_gradient_to_q": float(jnp.abs(sound.astype(jnp.float32)).max()),
            "alignment_gradient_to_q_not_detached": float(jnp.abs(faulty.astype(jnp.float32)).max())}


def gradient_without_relu_mask(got, cfg):
    """See `alignment_gradient_without_relu_mask` above."""
    qi, ki, weights, q, k, allowed, lse_of, scale = chunk_of_the_stage_row(got, cfg, last=True)
    operands = (qi, ki, weights, sio.attention_target(q, k, lse_of(q), allowed, scale), allowed)
    name = "kernel" if jax.default_backend() == "tpu" and iak.fits(qi.shape[0], ki.shape[0], *qi.shape[1:]) else "plain"
    want = jax.jit(alone.by_vjp)(*operands)
    sound = alone.differences(jax.jit(alone.FORMS[name])(*operands), want)
    with alone.without_relu_mask():
        faulty = alone.differences(jax.jit(alone.FORMS[name])(*operands), want)
    return {"form": name, "limit": alone.GRADIENT_RTOL,
            "alignment_gradient_error": max(sound[n] for n in alone.NAMES[1:]), "sound": sound,
            "alignment_gradient_error_without_relu_mask": max(faulty[n] for n in alone.NAMES[1:]), "faulty": faulty}


def main(seed: int, only=()):
    cfg = mf.read_json("benchmark/configs/keye-vl-2.0-30b-a3b.json")
    job = mf.read_json("benchmark/traffic/train-dsa-s16384.json")
    if DRY:
        cfg.update(TINY[0])
        job.update(TINY[1])
        lfm2.LOGIT_SAMPLE = lfm2.ATTENTION_SAMPLE = 8
    program, startup, _, _, check_names = keye.build(cfg, job)
    program.random_seed = startup.random_seed = seed
    scope = fluid.Scope()
    fluid.Executor(fluid.TPUPlace(0)).run(startup, scope=scope)
    rows = keye.make_batch(np.random.RandomState(seed % 2**32), cfg, job, CHECK_ROWS)
    params = {p.name: scope.find_var(p.name) for p in program.all_parameters()}
    batch = {k: np.asarray(v) for k, v in rows.items()}

    def reference(**kw):   # to the host at once: its float32 copies of the experts do not stay on the chip beside a clone
        return [np.asarray(w) for w in jax.jit(lambda p, b: keye.reference(p, b, cfg, program, **kw))(params, batch)]

    def check_rows():   # a new executor and a new clone: nothing compiled under another fault is met again
        got = fluid.Executor(fluid.TPUPlace(0)).run(program.clone(for_test=True), feed=rows,
                                                    fetch_list=list(check_names), scope=scope)
        got = [np.asarray(t) for t in got]
        jax.clear_caches()      # ... and the clone's program leaves the chip before the next one loads
        gc.collect()
        return got

    def report(name, mine, theirs, **more):
        found = keye.compare(mine, theirs)
        refused = keye.failed_limits(found)
        print(json.dumps({"control": name, "seed": seed, "correct": not refused, "refused_by": refused, **found, **more}),
              flush=True)

    want, sound = reference(), check_rows()
    report("sound", sound, want)
    if not only or "choice_again_from_bf16_scores" in only:
        print(json.dumps({"control": "choice_again_from_bf16_scores", "seed": seed, **choice_again(sound, cfg)}), flush=True)
    if not only or "target_not_detached" in only:
        print(json.dumps({"control": "target_not_detached", "seed": seed, **target_not_detached(sound, cfg)}), flush=True)
    if not only or "alignment_gradient_without_relu_mask" in only:
        print(json.dumps({"control": "alignment_gradient_without_relu_mask", "seed": seed, **gradient_without_relu_mask(sound, cfg)}),
              flush=True)
    if not only or "reference_default_precision" in only:
        report("reference_default_precision", sound, reference(precision="default"))
    for name, fault in faults(cfg).items():
        if not only or name in only:
            with fault():
                report(name, check_rows(), want)


if __name__ == "__main__":
    main(int(sys.argv[1]) if len(sys.argv) > 1 else 3560000501, tuple(sys.argv[2:]))
