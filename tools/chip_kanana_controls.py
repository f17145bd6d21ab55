"""Kanana-2's cell on the chip, what its comparison can and cannot tell: the
harness's own `benchmark.models.kanana.compare` / `failed_limits` on the
program's check rows against the float32 reference, sound and then with a
fault put in, one at a time, so that each limit this PR brings has a reading it
must refuse beside the sound one (PERF.md, section 6, PR 54).  Five faults go
into THE PROGRAM (the op's registered lowering is wrapped and the check rows run
again through a new executor), one into the reference's weights, one is the
reference a precision lower:

  * `angles_in_bf16`: the rotary angle position x theta^(-2i/64) rounded to
    bf16 before its sine and cosine (at position 16383 a bf16 angle is off by
    whole turns): `ROTARY_RTOL`, `QK_RTOL`;
  * `k_r_not_rotated`: the one shared 64-wide key part handed on unrotated,
    q_r rotated: `ROTARY_RTOL`, `QK_RTOL`, `REFERENCE_RTOL`;
  * `q_r_deinterleaved_k_r_not`: q_r put into the halves' order of the family's
    public code before its rotation, k_r left in pairs: `ROTARY_RTOL`, `QK_RTOL`;
  * `scale_of_the_nope_width`: scores at 128^-0.5 for (128 + 64)^-0.5:
    `ATTENTION_RTOL`;
  * `router_in_bf16`: the router's float32 matrix rounded to bf16 before the
    logits' product: `ROUTER_RTOL`;
  * `shared_experts_missing`: the reference without the first sparse layer's
    shared experts (the errors are differences): `SHARED_RTOL` (the stage on the
    program's own m) and, end to end, `REFERENCE_RTOL`;
  * `reference_default_precision`: the reference's float32 products at the
    chip's default precision (bf16 operands), the nearest precision below the
    one the reference states: `REFERENCE_SELF_RTOL`, the reference's own first
    product against float64 (end to end nothing tells it: the program rounds
    as much itself).

    chiprun -- python3 tools/chip_kanana_controls.py 3900000017      (PERF.md, PR 54)

Names after the seed run those controls alone, beside `sound`.
`DRY=1` rehearses it tiny on the CPU; no number of that means anything.
"""
import contextlib
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
from benchmark.models import kanana, lfm2
from benchmark.runners.train import CHECK_ROWS
from paddle_tpu.core.registry import get_op_def

TINY = (dict(hidden_size=48, num_attention_heads=2, intermediate_size=96, moe_intermediate_size=16, n_routed_experts=4,
             num_routed_experts=32, num_experts_per_tok=4, vocab_size=96, kv_lora_rank=24, qk_nope_head_dim=16,
             qk_rope_head_dim=8, v_head_dim=16, num_hidden_layers=3, layer_types=["latent_attention"] * 3),
        dict(seq_len=256, batch_per_chip=1, ring=4))


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


def with_attrs(op, **attrs):
    return SimpleNamespace(type=op.type, attr=lambda n, d=None: attrs.get(n, op.attr(n, d)), input=op.input, output=op.output)


def rotation(fault):
    def wrong(real, ctx, op, ins):
        x, positions = ins["X"][0], ins["Positions"][0]
        one_head = x.shape[2] == 1                     # the shared key part; each head's q_r has them all
        if fault == "k_r_not_rotated" and one_head:
            return {"Out": x}
        if fault == "q_r_deinterleaved_k_r_not" and not one_head:
            return real(ctx, op, {**ins, "X": [jnp.concatenate([x[..., 0::2], x[..., 1::2]], -1)]})
        if fault == "angles_in_bf16":                  # the op's arithmetic, its angle rounded to bf16's eight bits
            half = x.shape[-1] // 2
            inv_freq = op.attr("theta") ** (-np.arange(half, dtype=np.float32) / half)
            angle = jax.lax.reduce_precision(positions.astype(jnp.float32)[:, :, None, None] * inv_freq, 8, 7)
            cos, sin = jnp.cos(angle), jnp.sin(angle)
            even, odd = x[..., 0::2].astype(jnp.float32), x[..., 1::2].astype(jnp.float32)
            out = jnp.stack([even * cos - odd * sin, odd * cos + even * sin], -1).reshape(x.shape)
            return {"Out": out.astype(x.dtype)}
        return real(ctx, op, ins)

    return lambda: lowered_as("rotary_embedding", wrong)


def faults(cfg):
    def scaled(real, ctx, op, ins):
        return real(ctx, with_attrs(op, scale=float(cfg["qk_nope_head_dim"]) ** -0.5), ins)

    def router(real, ctx, op, ins):
        return real(ctx, op, {**ins, "W": [jax.lax.reduce_precision(ins["W"][0], 8, 7)]})

    return {
        "angles_in_bf16": rotation("angles_in_bf16"),
        "k_r_not_rotated": rotation("k_r_not_rotated"),
        "q_r_deinterleaved_k_r_not": rotation("q_r_deinterleaved_k_r_not"),
        "scale_of_the_nope_width": lambda: lowered_as("fused_attention", scaled),
        "router_in_bf16": lambda: lowered_as("moe_router", router),
    }


def main(seed: int, only=()):
    cfg = mf.read_json("benchmark/configs/kanana-2-30b-a3b.json")
    job = mf.read_json("benchmark/traffic/train-mla-s16384.json")
    if DRY:
        cfg.update(TINY[0])
        job.update(TINY[1])
        lfm2.LOGIT_SAMPLE = lfm2.ATTENTION_SAMPLE = 8
    program, startup, _, _, check_names = kanana.build(cfg, job)
    program.random_seed = startup.random_seed = seed
    scope = fluid.Scope()
    fluid.Executor(fluid.TPUPlace(0)).run(startup, scope=scope)
    rows = kanana.make_batch(np.random.RandomState(seed % 2**32), cfg, job, CHECK_ROWS)
    params = {p.name: scope.find_var(p.name) for p in program.all_parameters()}
    batch = {k: np.asarray(v) for k, v in rows.items()}

    def reference(**kw):   # to the host at once: its float32 copies of the experts do not stay on the chip beside a clone
        return [np.asarray(w) for w in jax.jit(lambda p, b: kanana.reference(p, b, cfg, program, **kw))(params, batch)]

    def check_rows():   # a new executor and a new clone: nothing compiled under another fault is met again
        return fluid.Executor(fluid.TPUPlace(0)).run(program.clone(for_test=True), feed=rows,
                                                     fetch_list=list(check_names), scope=scope)

    def report(name, mine, theirs):
        found = kanana.compare(mine, theirs)
        refused = kanana.failed_limits(found)
        print(json.dumps({"control": name, "seed": seed, "correct": not refused, "refused_by": refused, **found}), flush=True)

    want, sound = reference(), check_rows()
    report("sound", sound, want)
    for name, fault in faults(cfg).items():
        if not only or name in only:
            with fault():
                report(name, check_rows(), want)
    if not only or "shared_experts_missing" in only:
        down = f"lm.l{cfg['first_k_dense_replace']}.moe.shared.down.w"
        params[down], kept = 0 * params[down], params[down]
        report("shared_experts_missing", sound, reference())
        params[down] = kept
    if not only or "reference_default_precision" in only:
        report("reference_default_precision", sound, reference(precision="default"))


if __name__ == "__main__":
    main(int(sys.argv[1]) if len(sys.argv) > 1 else 3900000017, tuple(sys.argv[2:]))
