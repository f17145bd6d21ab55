"""The indexer's select ALONE on the chip: `lax.top_k`'s last column (what
`sparse_index` read until PR 57) beside the two forms of
`paddle_tpu/ops/sparse_index_kernels.py` that count, the kernel `select` (what
the chip runs) and the plain `kth_and_last`, at a chunk of Keye-VL-2.0's cell:
[512, 4096 / 8192 / 16384] float32 scores, `topk` 2048, the causal edge as a
band's last chunk has it.  Each form's (kth, last) is held to `lax.top_k`'s on
the chip, on random scores and on scores that tie (a third of them 0.0, a
seventh of the rows quarters); ms a call, the median of five timings of eight
calls in one program (PERF.md, section 6, PR 57).

    chiprun -- python3 tools/chip_index_select.py            DRY=1 rehearses it tiny on the CPU
"""
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

DRY = os.environ.get("DRY") == "1"

import jax
import jax.numpy as jnp
import numpy as np

from paddle_tpu.ops import sparse_index_kernels as sik

ROWS, TOPK, CALLS = (16, 48, 2) if DRY else (512, 2048, 8)
WIDTHS = (128, 256) if DRY else (4096, 8192, 16384)


def top_k(masked, topk):
    values, indices = jax.lax.top_k(masked, topk)
    return values[:, -1:], indices[:, -1:]


def scores(seed, width, tied):
    """[CALLS, ROWS, width] as `choose` masks them: chunk c's query r sees keys 0 .. width - ROWS + r."""
    rng = np.random.RandomState(seed)
    x = rng.randn(CALLS, ROWS, width).astype("f4")
    if tied:
        x[rng.rand(*x.shape) < 1 / 3] = 0.0
        x[:, ::7] = np.round(x[:, ::7] * 4) / 4          # a few hundred equals at every value, the threshold's among them
        x += np.float32(0.0)                             # no -0.0: `lax.top_k` orders it UNDER +0.0, `>` and `==` do not
    return jnp.asarray(np.where(np.arange(width) <= width - ROWS + np.arange(ROWS)[:, None], x, -np.inf).astype("f4"))


def ms_a_call(form, x):
    run = jax.jit(lambda x: jax.lax.map(lambda m: form(m, TOPK), x))
    jax.block_until_ready(run(x))
    timings = []
    for _ in range(5):
        start = time.perf_counter()
        jax.block_until_ready(run(x))
        timings.append((time.perf_counter() - start) * 1e3 / CALLS)
    return float(np.median(timings)), run


def main():
    forms = {"select": lambda masked, topk: sik.select(masked, topk, DRY), "kth_and_last": sik.kth_and_last}
    print(json.dumps({"info": "device", "platform": jax.devices()[0].platform, "kind": jax.devices()[0].device_kind}), flush=True)
    for width in WIDTHS:
        line = {"rows": ROWS, "keys": width, "topk": TOPK, "kernel_rows_a_block": sik._rows(ROWS, width)}
        for tied in (False, True):
            x, kind = scores(57 + tied, width, tied), ", ties" if tied else ""
            line[f"lax.top_k: ms a call{kind}"], by_sort = ms_a_call(top_k, x)
            for name, form in forms.items():
                line[f"{name}: ms a call{kind}"], by_count = ms_a_call(form, x)
                line[f"{name}: equal to lax.top_k{kind}"] = all((np.asarray(g) == np.asarray(w)).all() for g, w in zip(by_count(x), by_sort(x)))
        print(json.dumps({k: round(v, 4) if isinstance(v, float) else v for k, v in line.items()}), flush=True)


if __name__ == "__main__":
    main()
