"""The alignment TARGET of `index_alignment` ALONE on the chip: a chunk's
`sparse_index_ops.attention_target` at a chunk of Keye-VL-2.0's cell, 512
queries of 32 heads of 128 in bf16 over 4 key/value heads against 4096 / 8192 /
16384 keys, 2048 of them allowed a query (the causal edge as a band's last
chunk has it), the log-sum-exp the allowed scores' own:

  * `plain`: the op's form until PR 62 and off the TPU since, a `lax.map` over
    the key/value groups whose [8, 512, keys] float32 scores and exponentials
    pass through HBM;
  * `kernel`: `alignment_target_kernels.target` (two sweeps over the keys, a
    group's scores and exponentials for a block of keys in VMEM only), what the
    chip runs, then the same by keys a grid step.

ms a call, the median of five timings of eight calls in one program, and each
form's largest difference from the plain form's over the largest value of the
target, with how far its rows' sums lie from 1 (PERF.md, section 6, PR 62).
`VARIANTS=<file.py>[,<file.py>]` prices other forms beside them without an edit
of the tree: each file is loaded by path and gives `FORMS`, a dict of name ->
`f(q, k, lse, allowed, scale, interpret)` (how the shape of the kernel that
was NOT kept, one sweep with e_h in a VMEM scratch, was priced: PERF.md has its
table).

    chiprun -- python3 tools/chip_alignment_target.py            DRY=1 rehearses it tiny on the CPU
"""
import importlib.util
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

DRY = os.environ.get("DRY") == "1"

import jax
import jax.numpy as jnp
import numpy as np

from paddle_tpu.ops import alignment_target_kernels as atk
from paddle_tpu.ops import sparse_index_ops as sio

ROWS, HEADS, KV_HEADS, WIDTH, TOPK, CALLS = (64, 8, 2, 128, 48, 2) if DRY else (512, 32, 4, 128, 2048, 8)
WIDTHS = (256, 512) if DRY else (4096, 8192, 16384)
SCALE = WIDTH ** -0.5


def forms():
    found = {"plain": lambda *o, interpret: sio.attention_target(*o),
             "kernel": lambda *o, interpret: atk.target(*o, interpret=interpret)}
    for block in ((128,) if DRY else (256, 512, 2048)):
        found[f"kernel, {block} keys a step"] = lambda *o, interpret, block=block: atk.target(*o, block=block, interpret=interpret)
    for path in filter(None, os.environ.get("VARIANTS", "").split(",")):
        spec = importlib.util.spec_from_file_location(os.path.splitext(os.path.basename(path))[0], path)
        module = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(module)
        found.update(module.FORMS)
    return found


def operands(seed, keys):
    """CALLS chunks' (q, k, lse, allowed): query r of a chunk sees keys 0 .. keys - ROWS + r and holds TOPK of them."""
    rng = np.random.RandomState(seed)
    dtype = jnp.float32 if DRY else jnp.bfloat16
    q = jnp.asarray(rng.randn(CALLS, HEADS, ROWS, WIDTH).astype("f4"), dtype)
    k = jnp.asarray(rng.randn(CALLS, KV_HEADS, keys, WIDTH).astype("f4"), dtype)
    causal = np.arange(keys) <= keys - ROWS + np.arange(ROWS)[:, None]
    chosen = np.where(causal, rng.rand(CALLS, ROWS, keys), 2.0)
    allowed = chosen <= np.sort(chosen, axis=-1)[..., TOPK - 1:TOPK]
    allowed[:, np.arange(ROWS), keys - ROWS + np.arange(ROWS)] = True
    allowed = jnp.asarray(allowed)

    def log_sum_exp(q, k, allowed):
        s = jnp.einsum("ghcd,gkd->ghck", q.reshape(KV_HEADS, -1, ROWS, WIDTH), k, preferred_element_type=jnp.float32) * SCALE
        return jax.nn.logsumexp(jnp.where(allowed, s, -jnp.inf), axis=-1).reshape(HEADS, ROWS)

    return q, k, jax.lax.map(lambda chunk: log_sum_exp(*chunk), (q, k, allowed)), allowed


def ms_a_call(form, chunks):
    run = jax.jit(lambda chunks: jax.lax.map(lambda chunk: form(*chunk, SCALE, interpret=DRY), chunks))
    jax.block_until_ready(run(chunks))
    timings = []
    for _ in range(5):
        start = time.perf_counter()
        jax.block_until_ready(run(chunks))
        timings.append((time.perf_counter() - start) * 1e3 / CALLS)
    return float(np.median(timings)), run(chunks)


def main():
    print(json.dumps({"info": "device", "platform": jax.devices()[0].platform, "kind": jax.devices()[0].device_kind}), flush=True)
    priced = forms()
    for keys in WIDTHS:
        chunks = operands(62 + keys, keys)
        line = {"rows": ROWS, "keys": keys, "heads": HEADS, "kv_heads": KV_HEADS, "held": TOPK}
        want = None
        for name, form in priced.items():
            try:
                ms, got = ms_a_call(form, chunks)
            except Exception as e:       # a tile that does not fit the chip's VMEM says so and the others go on
                line[f"{name}: refused"] = str(e)[:300]
                continue
            want = got if want is None else want
            line[f"{name}: ms a call"] = ms
            line[f"{name}: against plain"] = float(jnp.abs(got - want).max() / want.max())
            line[f"{name}: rows' sums from 1"] = float(jnp.abs(jnp.sum(got, axis=-1) - 1.0).max())
        print(json.dumps({k: round(v, 4) if isinstance(v, float) and v > 1e-3 else v for k, v in line.items()}), flush=True)


if __name__ == "__main__":
    main()
