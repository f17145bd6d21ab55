"""The looped cell on the chip, what its comparison can and cannot tell: the
harness's own `benchmark.models.ouro.compare` / `reference_error` on the
program's check rows against the float32 reference, and then against the SAME
reference with a fault put into it, one at a time, so that each limit that
decides `correct` has a reading it must refuse beside the sound one (PERF.md,
section 6, PR 38):

  * `three_passes`: the reference runs three passes for four: `REFERENCE_RTOL`
    cannot see it on the first three exits (they are the same numbers), the
    exit distribution can: `EXIT_P_ATOL`;
  * `no_post_norms`: the norms after the sub-layers dropped: `REFERENCE_RTOL`,
    `EXIT_P_ATOL`, `STAGE_RTOL`;
  * `final_norm_last_only`: the final norm after the last pass only (the next
    pass reads the un-normed stream): `REFERENCE_RTOL`, `EXIT_P_ATOL`;
  * `second_weights`: pass 2 reads a second, differently drawn set of layer
    weights (the loop does NOT share its weights): `REFERENCE_RTOL`,
    `EXIT_P_ATOL`, `STAGE_RTOL`;
  * `bf16_masters`: every weight rounded to bf16, the precision below the
    float32 masters the configuration states: `GATE_ATOL` (the gate reads its
    weight in float32; the layers' masters are cast to bf16 by the program
    itself, so nothing else tells);
  * `bf16_gate`: the program's exit distribution rounded to bf16, a program
    whose gate computes its products in bf16: `GATE_ATOL`;
  * `bf16_loss`: the cross entropies, the exit weighting and the mean rounded
    to bf16 (`reference(bf16_loss=True)`): `LOSS_RTOL`, which mostly reads how
    far the loss lies from bf16's grid (steps of 1/16 at 9.8: up to 3.2e-3, and
    nothing for a loss that happens to lie on it);
  * `no_entropy_term`: `beta` 0, the expected cross entropy alone: `LOSS_RTOL`
    (the logits and the distribution are the same numbers);
  * `bf16_norm_statistics`: THE PROGRAM with the fault, every `rms_norm` it
    lowers taking its squares, their mean and the reciprocal root at bf16,
    against the sound reference: `NORM_RTOL` (eight bf16 layers a pass hide it
    from every comparison with the reference).

A fault in the reference reads as the same fault in the program would: the
errors are differences.

    chiprun -- python3 tools/chip_ouro_controls.py 3900000017      (PERF.md, PR 38)

Names after the seed run those controls alone, beside `sound`.

`DRY=1` rehearses it tiny on the CPU; no number of that means anything.
"""
import contextlib
import functools
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
from benchmark.models import ouro

CHECK_ROWS = 8  # as benchmark/runners/train.py
DRY = os.environ.get("DRY") == "1"
TINY = (dict(hidden_size=64, num_attention_heads=4, num_key_value_heads=4, head_dim=16, intermediate_size=96,
             vocab_size=96, layer_types=["full_attention"] * 2, num_hidden_layers=2),
        dict(seq_len=32, batch_per_chip=4, ring=4))
LIMITS = {"logit_error": "REFERENCE_RTOL", "loss_error": "LOSS_RTOL", "exit_p_error": "EXIT_P_ATOL",
          "gate_error": "GATE_ATOL", "norm_error": "NORM_RTOL", "pass2_error": "STAGE_RTOL"}


def redrawn(params: dict, seed: int) -> dict:
    """The layers' matrices drawn again, N(0, 0.02) from another seed: the weights pass 2 would read if
    the loop did not share them."""
    rng = np.random.RandomState(seed % 2**31)
    return {n: (0.02 * rng.randn(*np.shape(v))).astype("f4") if ".l" in n and np.ndim(v) == 2 else v
            for n, v in params.items()}


@contextlib.contextmanager
def norm_statistics_in_bf16():
    """While open, a program lowers every `rms_norm` with its squares, their mean and the reciprocal root
    rounded to bf16 (`ops/moe_ops.py: _rms_norm` keeps them float32)."""
    from paddle_tpu.core.registry import get_op_def
    from paddle_tpu.ops.common import first, match_dtype

    low = functools.partial(jax.lax.reduce_precision, exponent_bits=8, mantissa_bits=7)

    def lowered(ctx, op, ins):
        x, begin = first(ins, "X"), op.attr("begin_norm_axis", 1)
        xf = x.astype(jnp.float32)
        mean = low(jnp.mean(low(jnp.square(xf)), axis=tuple(range(begin, x.ndim)), keepdims=True))
        y = (xf * low(jax.lax.rsqrt(mean + op.attr("epsilon", 1e-5)))).astype(x.dtype)
        return {"Y": y * match_dtype(y, first(ins, "Scale")).reshape((1,) * begin + tuple(x.shape[begin:]))}

    rms_norm = get_op_def("rms_norm")
    sound, rms_norm.lower = rms_norm.lower, lowered
    try:
        yield
    finally:
        rms_norm.lower = sound


def main(seed: int, only=()):
    cfg = mf.read_json("benchmark/configs/ouro-2.6b.json")
    job = mf.read_json("benchmark/traffic/train-ut4-s4096.json")
    if DRY:
        cfg.update(TINY[0])
        job.update(TINY[1])
        ouro.LOGIT_SAMPLE = 8
    program, startup, _, _, check_names = ouro.build(cfg, job)
    program.random_seed = startup.random_seed = seed
    scope = fluid.Scope()
    exe = fluid.Executor(fluid.TPUPlace(0))
    exe.run(startup, scope=scope)
    rows = ouro.make_batch(np.random.RandomState(seed % 2**32), cfg, job, CHECK_ROWS)
    got = exe.run(program.clone(for_test=True), feed=rows, fetch_list=list(check_names), scope=scope)
    params = {p.name: np.asarray(scope.find_var(p.name)) for p in program.all_parameters()}
    batch = {k: np.asarray(v) for k, v in rows.items()}
    rounded = list(got)
    rounded[2] = ouro._bf16(np.asarray(got[2], "f4"))
    low_norms = got
    if not only or "bf16_norm_statistics" in only:
        with norm_statistics_in_bf16():
            low_norms = fluid.Executor(fluid.TPUPlace(0)).run(
                program.clone(for_test=True), feed=rows, fetch_list=list(check_names), scope=scope)
    controls = {
        "sound": (got, params, {}),
        "three_passes": (got, params, dict(passes=3)),
        "no_post_norms": (got, params, dict(post_norms=False)),
        "final_norm_last_only": (got, params, dict(final_norm_every_pass=False)),
        "second_weights": (got, params, dict(second_weights=redrawn(params, seed + 1))),
        "bf16_masters": (got, {n: ouro._bf16(np.asarray(v, "f4")) for n, v in params.items()}, {}),
        "bf16_gate": (rounded, params, {}),
        "bf16_loss": (got, params, dict(bf16_loss=True)),
        "no_entropy_term": (got, params, dict(cfg=dict(cfg, exit_entropy_beta=0.0))),
        "bf16_norm_statistics": (low_norms, params, {}),
    }
    for name, (mine, weights, fault) in controls.items():
        if only and name not in ("sound",) + tuple(only):
            continue
        second, model = fault.pop("second_weights", None), fault.pop("cfg", cfg)
        reference = jax.jit(lambda p, b, q: ouro.reference(p, b, model, program, second_weights=q, **fault))
        want = [np.asarray(w) for w in reference(weights, batch, second)]
        with contextlib.redirect_stdout(io.StringIO()) as said:
            error = ouro.reference_error(mine, want)
        found = json.loads(said.getvalue())
        print(json.dumps({"control": name, "seed": seed, "correct": bool(error <= ouro.REFERENCE_RTOL),
                          "refused_by": sorted({LIMITS[k] for k in LIMITS if not found[k] <= getattr(ouro, LIMITS[k])}),
                          **{k: found[k] for k in LIMITS}, "gate_error_bf16": found["gate_error_bf16"],
                          "logit_error_by_exit": found["logit_error_by_exit"], "exit_mass": found["exit_mass"]}),
              flush=True)


if __name__ == "__main__":
    main(int(sys.argv[1]) if len(sys.argv) > 1 else 3900000017, sys.argv[2:])
