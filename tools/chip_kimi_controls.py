"""Kimi Linear's cell on the chip, what its comparison can and cannot tell: the
harness's own `benchmark.models.kimi_linear.compare` / `reference_error` on the
program's check rows against the float32 reference, sound and then with a
fault put in, one at a time, so that each limit this PR brings has a reading
it must refuse beside the sound one (PERF.md, section 6, PR 42).  The faults
are put into THE PROGRAM (the op's module is patched and the check rows run
again through a new executor; since PR 44 the scan's faults go into BOTH its
lowerings, the kernels of `ops/kda_kernels.py` that the chip runs, through the
seams their `jax.jit`s take as static arguments, and the `jax.numpy` form that
`DRY=1` runs), but for the last, which is a weight zeroed in the reference
(the errors are differences):

  * `kda_bf16_state`: the KDA state rounded to bf16 at every chunk boundary:
    `KDA_RTOL`;
  * `kda_bf16_cumulative_decay`: the cumulative log decay inside a chunk rounded
    to bf16: `KDA_RTOL`;
  * `kda_no_decay`: Diag(alpha) dropped (g = 0): `KDA_RTOL`, and everything
    after it;
  * `kda_default_precision`: the op's float32 products at the chip's default
    precision (bf16 operands), the nearest precision below the one the
    configuration states for the state and the decay: `KDA_RTOL`;
  * `conv_in_bf16`: the plain short convolution's taps, products and running
    sum rounded to bf16: `CONV_RTOL`;
  * `shared_expert_missing`: the reference without the first sparse layer's
    shared expert: `SHARED_RTOL` (the stage on the program's own m) and, end to
    end, `REFERENCE_RTOL`.

    chiprun -- python3 tools/chip_kimi_controls.py 3900000017      (PERF.md, PR 42)

Names after the seed run those controls alone, beside `sound`.
`DRY=1` rehearses it tiny on the CPU; no number of that means anything.
"""
import contextlib
import io
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

import paddle_tpu as fluid
from benchmark import manifest as mf
from benchmark.models import kimi_linear, lfm2
from paddle_tpu.ops import kda_kernels
from paddle_tpu.ops import linear_attention_ops as lao
from paddle_tpu.ops import moe_ops

from chip_kimi_kernels import bf16, bf16_cumulative_in_kernel, bf16_states, in_kernel_bf16   # beside this script

CHECK_ROWS = 8  # as benchmark/runners/train.py
DRY = os.environ.get("DRY") == "1"
TINY = (dict(hidden_size=48, num_attention_heads=2, intermediate_size=96, moe_intermediate_size=16, num_experts=4,
             num_routed_experts=32, num_experts_per_token=4, vocab_size=96, kv_lora_rank=24, qk_nope_head_dim=16,
             qk_rope_head_dim=8, v_head_dim=16,
             linear_attn_config=dict(num_heads=2, head_dim=16, short_conv_kernel_size=4, kda_layers=[1, 2, 3, 5],
                                     full_attn_layers=[4])),
        dict(seq_len=256, batch_per_chip=2, ring=4))
LIMITS = {"logit_error": "REFERENCE_RTOL", "loss_error": "REFERENCE_RTOL", "kda_error": "KDA_RTOL",
          "conv_error": "CONV_RTOL", "shared_error": "SHARED_RTOL", "experts_error": "EXPERTS_RTOL",
          "router_prob_error": "ROUTER_RTOL", "attention_error": "ATTENTION_RTOL", "qk_error": "QK_RTOL"}


@contextlib.contextmanager
def patched(*seams):
    """`seams`: (module, name, value), each set for the length of the block."""
    sound = [getattr(module, name) for module, name, _ in seams]
    for module, name, value in seams:
        setattr(module, name, value)
    try:
        yield
    finally:
        for (module, name, _), value in zip(seams, sound):
            setattr(module, name, value)


def conv_in_bf16(x, w):
    taps, acc = w.shape[1], None
    for j in range(taps):
        term = bf16(moe_ops._shift_rows(x, taps - 1 - j).astype(jnp.float32) * bf16(w[:, j].astype(jnp.float32)))
        acc = term if acc is None else bf16(acc + term)
    return bf16(jax.nn.silu(acc)).astype(x.dtype)


def faults():
    cumulative, scan = lao._cumulative, lao.chunked_kda
    return {
        "kda_bf16_state": lambda: patched((lao, "_states", bf16_states), (kda_kernels, "carried", in_kernel_bf16)),
        "kda_bf16_cumulative_decay": lambda: patched(
            (lao, "_cumulative", lambda g: bf16(cumulative(g))),
            (kda_kernels, "cumulative", bf16_cumulative_in_kernel)),
        "kda_no_decay": lambda: patched((lao, "chunked_kda", lambda q, k, v, g, *rest: scan(q, k, v, 0 * g, *rest))),
        "kda_default_precision": lambda: patched((lao, "_KDA_PRECISION", jax.lax.Precision.DEFAULT)),   # the kernels' too
        "conv_in_bf16": lambda: patched((moe_ops, "_plain_short_conv", conv_in_bf16)),
    }


def main(seed: int, only=()):
    cfg = mf.read_json("benchmark/configs/kimi-linear-48b-a3b.json")
    job = mf.read_json("benchmark/traffic/train-kda-s4096.json")
    if DRY:
        cfg.update(TINY[0])
        job.update(TINY[1])
        lfm2.LOGIT_SAMPLE = lfm2.ATTENTION_SAMPLE = 8
    program, startup, _, _, check_names = kimi_linear.build(cfg, job)
    program.random_seed = startup.random_seed = seed
    scope = fluid.Scope()
    fluid.Executor(fluid.TPUPlace(0)).run(startup, scope=scope)
    rows = kimi_linear.make_batch(np.random.RandomState(seed % 2**32), cfg, job, CHECK_ROWS)
    params = {p.name: np.asarray(scope.find_var(p.name)) for p in program.all_parameters()}
    batch = {k: np.asarray(v) for k, v in rows.items()}
    reference = jax.jit(lambda p, b: kimi_linear.reference(p, b, cfg, program))
    want = [np.asarray(w) for w in reference(params, batch)]

    def check_rows():   # a new executor and a new clone: nothing compiled under another fault is met again
        return fluid.Executor(fluid.TPUPlace(0)).run(program.clone(for_test=True), feed=rows,
                                                     fetch_list=list(check_names), scope=scope)

    def report(name, mine, theirs):
        with contextlib.redirect_stdout(io.StringIO()) as said:
            error = kimi_linear.reference_error(mine, theirs)
        found = json.loads(said.getvalue())
        print(json.dumps({"control": name, "seed": seed, "correct": bool(error <= kimi_linear.REFERENCE_RTOL),
                          "refused_by": sorted({LIMITS[k] for k in LIMITS if not found[k] <= getattr(kimi_linear, LIMITS[k])}),
                          **{k: found[k] for k in LIMITS}, "kda_error_unrounded": found["kda_error_unrounded"],
                          "kda_error_bf16_state": found["kda_error_bf16_state"], "conv_error_bf16": found["conv_error_bf16"],
                          "left_out_share": found["left_out"] / found["tokens"], "kda_decay_mean": found["kda_decay_mean"]}),
              flush=True)

    report("sound", check_rows(), want)
    for name, fault in faults().items():
        if only and name not in only:
            continue
        with fault():
            report(name, check_rows(), want)
    if not only or "shared_expert_missing" in only:
        first_sparse = cfg["first_k_dense_replace"]
        zeroed = dict(params, **{f"lm.l{first_sparse}.moe.shared.down.w": 0 * params[f"lm.l{first_sparse}.moe.shared.down.w"]})
        report("shared_expert_missing", check_rows(), [np.asarray(w) for w in reference(zeroed, batch)])


if __name__ == "__main__":
    main(int(sys.argv[1]) if len(sys.argv) > 1 else 3900000017, sys.argv[2:])
