"""Phi-4-mini-flash's cell on the chip, what its comparison can and cannot
tell: the harness's own `benchmark.models.phi4flash.compare` on the program's
check rows against the float32 reference, sound and then with a fault put in,
one at a time, so that each limit this PR brings has a reading it must refuse
beside the sound one (PERF.md, section 6, PR 50).  Three faults go into THE
REFERENCE and the comparison (`phi4flash.FAULTS`: the errors are differences),
one into THE PROGRAM (the op's module is patched and the check rows run again
through a new executor), one is the reference a precision lower:

  * `window_as_causal`: the window layer computed under the causal rule, in the
    reference's forward pass and in the window stage's float32 softmax on the
    program's own q, k, v: `WINDOW_RTOL`;
  * `cross_own_kv`: the cross layer on its OWN input projected with the kept
    layer's key and value weights, not on the kept tensors: `KEPT_KV_RTOL`,
    `REFERENCE_RTOL`;
  * `gmu_gated_memory`: the GMU reading the gated y * silu(z) for the scan
    output y: `MEMORY_RTOL`, `REFERENCE_RTOL`;
  * `scan_bf16_state`: the scan's state rounded to bf16 where a chunk hands it
    on (`ssm_ops._carried`, `ssm_kernels.carried`, as tools/chip_jamba_controls.py
    does): `SCAN_RTOL` where the state's part of the output stands over the
    output's own bf16 step;
  * `reference_default_precision`: the reference's float32 products at the
    chip's default precision (bf16 operands).

    chiprun -- python3 tools/chip_phi4flash_controls.py 3900000017      (PERF.md, PR 50)

Names after the seed run those controls alone, beside `sound`.
`DRY=1` rehearses it tiny on the CPU; no number of that means anything.
"""
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

DRY = os.environ.get("DRY") == "1"

import jax
import numpy as np

import paddle_tpu as fluid
from benchmark import manifest as mf
from benchmark.models import jamba, lfm2, phi4flash
from paddle_tpu.ops import ssm_kernels, ssm_ops
from tools.chip_jamba_controls import low, low_in_kernel, patched

from benchmark.runners.train import CHECK_ROWS  # noqa: E402
TINY = (dict(hidden_size=64, intermediate_size=96, mamba_dt_rank=4, num_attention_heads=4, num_key_value_heads=2,
             vocab_size=96, sliding_window=8),
        dict(seq_len=64, batch_per_chip=1, ring=4))


def main(seed: int, only=()):
    cfg = mf.read_json("benchmark/configs/phi-4-mini-flash-reasoning.json")
    job = mf.read_json("benchmark/traffic/train-sambay-s8192.json")
    if DRY:
        cfg.update(TINY[0])
        job.update(TINY[1])
        lfm2.LOGIT_SAMPLE = lfm2.ATTENTION_SAMPLE = 8
        jamba.STAGE_CHANNELS = phi4flash.STAGE_CHANNELS = 64
    program, startup, _, _, check_names = phi4flash.build(cfg, job)
    program.random_seed = startup.random_seed = seed
    scope = fluid.Scope()
    fluid.Executor(fluid.TPUPlace(0)).run(startup, scope=scope)
    rows = phi4flash.make_batch(np.random.RandomState(seed % 2**32), cfg, job, CHECK_ROWS)
    params = {p.name: scope.find_var(p.name) for p in program.all_parameters()}
    batch = {k: np.asarray(v) for k, v in rows.items()}

    def reference(**kw):
        return [np.asarray(w) for w in jax.jit(lambda p, b: phi4flash.reference(p, b, cfg, program, **kw))(params, batch)]

    def check_rows():   # a new executor and a new clone: nothing compiled under another fault is met again
        return fluid.Executor(fluid.TPUPlace(0)).run(program.clone(for_test=True), feed=rows,
                                                     fetch_list=list(check_names), scope=scope)

    def report(name, mine, theirs, fault=None):
        found = phi4flash.compare(mine, theirs, fault=fault)
        refused = phi4flash.failed_limits(found)
        print(json.dumps({"control": name, "seed": seed, "correct": not refused, "refused_by": refused, **found}), flush=True)

    want, sound = reference(), check_rows()
    report("sound", sound, want)
    for fault in phi4flash.FAULTS:
        if not only or fault in only:
            report(fault, sound, reference(fault=fault), fault=fault)
    if not only or "scan_bf16_state" in only:
        with patched((ssm_ops, "_carried", low), (ssm_kernels, "carried", low_in_kernel)):
            report("scan_bf16_state", check_rows(), want)
    if not only or "reference_default_precision" in only:
        report("reference_default_precision", sound, reference(precision="default"))


if __name__ == "__main__":
    main(int(sys.argv[1]) if len(sys.argv) > 1 else 1, tuple(sys.argv[2:]))
