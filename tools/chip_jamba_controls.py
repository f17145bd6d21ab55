"""Jamba's cell on the chips, what its comparison can and cannot tell: the
harness's own `benchmark.models.jamba.compare` / `reference_error` on the
program's check rows against the float32 reference, sound and then with a
fault put in, one at a time, so that each limit this PR brings has a reading
it must refuse beside the sound one (PERF.md, section 6, PR 47).  Two faults
go into THE PROGRAM (the op's module is patched and the check rows run again
through a new executor), two into THE REFERENCE (the errors are differences):

  * `scan_bf16_state`: the scan's state rounded to bf16 where eight tokens
    hand it to the next (`ssm_ops._carried` in the XLA form, `ssm_kernels.carried`
    in the kernels: both are patched, the cell's path reads its own): `SCAN_RTOL`;
  * `scan_bf16_step`: the step softplus(dt + b_dt), and with it the decay's
    exponent, rounded to bf16 (`ssm_ops._step_of`, `ssm_kernels.step_of`): `SCAN_RTOL`;
  * `reference_default_precision`: the reference's float32 products at the
    chip's default precision (bf16 operands), the nearest precision below the
    one the reference states: `REFERENCE_RTOL` / `QK_RTOL` / `INNER_RTOL`;
  * `no_inner_norms`: the reference without Jamba's three inner norms:
    `INNER_RTOL`.

    chiprun --chips 4 -- python3 tools/chip_jamba_controls.py 3900000017      (PERF.md, PR 47)

Names after the seed run those controls alone, beside `sound`.
`DRY=1` rehearses it tiny on the CPU's virtual mesh; no number of that means anything.
"""
import contextlib
import io
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

DRY = os.environ.get("DRY") == "1"
if DRY:
    os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import jax
import numpy as np

import paddle_tpu as fluid
from benchmark import manifest as mf
from benchmark.models import jamba, lfm2
from paddle_tpu.ops import ssm_kernels, ssm_ops

CHECK_ROWS = 8  # as benchmark/runners/train.py
TINY = (dict(hidden_size=64, intermediate_size=96, mamba_dt_rank=4, num_attention_heads=4, vocab_size=96,
             num_hidden_layers=4, attn_layer_period=4, attn_layer_offset=2,
             layer_types=["mamba", "mamba", "full_attention", "mamba"]),
        dict(seq_len=64, batch_per_chip=1, ring=4))
LIMITS = {"logit_error": "REFERENCE_RTOL", "loss_error": "REFERENCE_RTOL", "scan_error": "SCAN_RTOL",
          "conv_error": "CONV_RTOL", "attention_error": "ATTENTION_RTOL", "qk_error": "QK_RTOL",
          "inner_error": "INNER_RTOL"}


@contextlib.contextmanager
def patched(*seams):
    """Each (module, name, value) set for the block."""
    sound = [getattr(module, name) for module, name, _ in seams]
    for module, name, value in seams:
        setattr(module, name, value)
    try:
        yield
    finally:
        for (module, name, _), value in zip(seams, sound):
            setattr(module, name, value)


def low(t):
    return jax.lax.reduce_precision(t, exponent_bits=8, mantissa_bits=7)   # XLA takes a pair of casts out


def low_in_kernel(t):
    return t.astype(jax.numpy.bfloat16).astype(jax.numpy.float32)          # Mosaic takes none out, and has no `reduce_precision`


def main(seed: int, only=()):
    cfg = mf.read_json("benchmark/configs/ai21-jamba2-3b.json")
    job = mf.read_json("benchmark/traffic/train-ssm-fsdp4.json")
    if DRY:
        cfg.update(TINY[0])
        job.update(TINY[1])
        lfm2.LOGIT_SAMPLE = lfm2.ATTENTION_SAMPLE = 8
        jamba.STAGE_CHANNELS = 64
    program, startup, _, _, check_names = jamba.build(cfg, job)
    program.random_seed = startup.random_seed = seed
    scope = fluid.Scope()
    fluid.monitor.enable()    # the placement's gauges are set only then
    fluid.Executor(fluid.TPUPlace(0)).run(startup, scope=scope)
    fluid.monitor.disable()
    # no chip ever holds the whole state: what each device holds after the start-up program, and the placement's gauges
    gauges, held = fluid.monitor.MONITOR.gauge_values(), [d.memory_stats() or {} for d in jax.devices()]
    print(json.dumps({"placed": "startup", "seed": seed, "bytes_in_use": [int(m.get("bytes_in_use", 0)) for m in held],
                      "peak_bytes_in_use": [int(m.get("peak_bytes_in_use", 0)) for m in held],
                      **{k: gauges.get(f"executor.{k}") for k in ("state_bytes_sharded", "state_bytes_replicated")}}),
          flush=True)
    rows = jamba.make_batch(np.random.RandomState(seed % 2**32), cfg, job, CHECK_ROWS)
    params = {p.name: scope.find_var(p.name) for p in program.all_parameters()}   # as they lie: split over the mesh
    batch = {k: np.asarray(v) for k, v in rows.items()}

    def reference(**kw):
        return [np.asarray(w) for w in jax.jit(lambda p, b: jamba.reference(p, b, cfg, program, **kw))(params, batch)]

    def check_rows():   # a new executor and a new clone: nothing compiled under another fault is met again
        return fluid.Executor(fluid.TPUPlace(0)).run(program.clone(for_test=True), feed=rows,
                                                     fetch_list=list(check_names), scope=scope)

    def report(name, mine, theirs):
        with contextlib.redirect_stdout(io.StringIO()) as said:
            error = jamba.reference_error(mine, theirs)
        found = json.loads(said.getvalue())
        print(json.dumps({"control": name, "seed": seed, "correct": bool(error <= jamba.REFERENCE_RTOL),
                          "refused_by": sorted({LIMITS[k] for k in LIMITS if not found[k] <= getattr(jamba, LIMITS[k])}),
                          **{k: found[k] for k in LIMITS}, "scan_error_unrounded": found["scan_error_unrounded"],
                          "scan_error_bf16_state": found["scan_error_bf16_state"],
                          "scan_error_bf16_step": found["scan_error_bf16_step"], "conv_error_bf16": found["conv_error_bf16"],
                          "scan_decay_mean": found["scan_decay_mean"]}), flush=True)

    want, sound = reference(), check_rows()
    report("sound", sound, want)
    step, step_in_kernel = ssm_ops._step_of, ssm_kernels.step_of
    program_faults = {"scan_bf16_state": ((ssm_ops, "_carried", low), (ssm_kernels, "carried", low_in_kernel)),
                      "scan_bf16_step": ((ssm_ops, "_step_of", lambda dt, bias: low(step(dt, bias))),
                                         (ssm_kernels, "step_of", lambda dt, bias: low_in_kernel(step_in_kernel(dt, bias))))}
    for name, seams in program_faults.items():
        if only and name not in only:
            continue
        with patched(*seams):
            report(name, check_rows(), want)
    if not only or "no_inner_norms" in only:
        report("no_inner_norms", sound, reference(inner_norms=False))
    if not only or "reference_default_precision" in only:
        report("reference_default_precision", sound, reference(precision="default"))


if __name__ == "__main__":
    main(int(sys.argv[1]) if len(sys.argv) > 1 else 1, tuple(sys.argv[2:]))
