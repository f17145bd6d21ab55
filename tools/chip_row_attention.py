"""On the chip: the whole-row attention kernel (`ops/pallas_attention.py`) alone,
forward + backward of a layer at BERT-base's heads and ~16k tokens, at 128, 256,
384 and 512 keys: today's heads-major call over (B, H, L, dh); the same call
with the program's four transposes round it (what a `[B, L, H, dh]` program paid
until PR 39), and those transposes round a sum, for their own price; the call
over the projections' own layout (B, L, H, dh) at each number of heads a grid
step, g in {2, 4, 6, 12}.  Before the times, how far the two layouts' outputs
and gradients lie apart, and each one's distance from float32
(tests/test_pallas_attention.py: attention_errors).

    chiprun -- python3 tools/chip_row_attention.py       (PERF.md, PR 39)

A microbenchmark: a time here is a kernel's alone, not the cell's.
"""
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

from paddle_tpu.ops import pallas_attention as pa
from tests.test_pallas_attention import attention_errors

DRY = os.environ.get("DRY") == "1"  # a rehearsal on the CPU: interpreted, tiny, no time printed
assert DRY or jax.devices()[0].platform == "tpu", jax.devices()
HEADS, WIDTH = (4, 64) if DRY else (12, 64)
GROUPS = (2, 4) if DRY else (2, 4, 6, 12)
SCALE = WIDTH ** -0.5
RULE = pa._pick_heads


def swap(t):
    return jnp.swapaxes(t, 1, 2)


def heads_major(q, k, v):
    return pa.fused_sdpa(q, k, v, None, False, SCALE, DRY)


def transposed(q, k, v):
    """(B, L, H, dh) in and out through the heads-major call: the program's transposes at the kernel's edges."""
    return swap(heads_major(swap(q), swap(k), swap(v)))


def native(q, k, v):
    return pa.fused_sdpa(q, k, v, None, False, SCALE, DRY, "blhd")


def with_heads(g):
    """`native` at `g` heads a grid step, whatever the rule would choose."""
    def f(q, k, v):
        pa._pick_heads = lambda *a: g
        try:
            return native(q, k, v)
        finally:
            pa._pick_heads = RULE
    return f


def ms(fn, q, k, v, w, runs=20):
    """Forward + backward of sum(fn * w), the value kept so that the forward call runs: the mean of `runs` after one that compiles."""
    step = jax.jit(jax.value_and_grad(lambda q, k, v: (fn(q, k, v) * w).astype(jnp.float32).sum(), argnums=(0, 1, 2)))
    try:
        jax.block_until_ready(step(q, k, v))
        t = time.perf_counter()
        for _ in range(runs):
            out = step(q, k, v)
        jax.block_until_ready(out)
        return None if DRY else round((time.perf_counter() - t) / runs * 1e3, 4)
    except Exception as e:  # a block that overruns the scoped VMEM is a finding, not a failure
        return f"{type(e).__name__}: {str(e)[:200]}"


def report(what, **fields):
    print(json.dumps({"what": what, "device": jax.devices()[0].device_kind, **fields}), flush=True)


def apart(got, want):
    """Largest difference over the largest magnitude, in float32."""
    got, want = got.astype(jnp.float32), want.astype(jnp.float32)
    return float(jnp.abs(got - want).max() / jnp.abs(want).max())


shape = (2, HEADS, 128, WIDTH) if DRY else (32, HEADS, 512, WIDTH)
# each layout's distance from float32, the projections' layout through the heads-major reference's axes
errors = {"bhld": heads_major, **{f"blhd_g{g}": (lambda q, k, v, g=g: swap(with_heads(g)(swap(q), swap(k), swap(v)))) for g in GROUPS}}
for seed in (0, 1):
    report("attention_errors", shape=shape, seed=seed, errors=attention_errors(shape, errors, seed=seed))

for seq, batch in ((128, 2),) if DRY else ((128, 128), (256, 64), (384, 48), (512, 32)):
    q, k, v, w = (jax.random.normal(kk, (batch, seq, HEADS, WIDTH), jnp.bfloat16) for kk in jax.random.split(jax.random.PRNGKey(seq), 4))
    results = lambda f: jax.jit(lambda *a: (f(*a),) + jax.grad(  # noqa: E731
        lambda *b: (f(*b) * w).astype(jnp.float32).sum(), argnums=(0, 1, 2))(*a))(q, k, v)
    want = results(transposed)
    for g in GROUPS:
        report("layouts_apart", shape=(batch, seq, HEADS, WIDTH), heads_a_step=g,
               apart=dict(zip(("out", "dq", "dk", "dv"), (apart(a, b) for a, b in zip(results(with_heads(g)), want)))))
    row = {"bhld": ms(heads_major, swap(q), swap(k), swap(v), swap(w)), "bhld_and_transposes": ms(transposed, q, k, v, w),
           "transposes_alone": ms(lambda q, k, v: swap(swap(q) + swap(k) * swap(v)), q, k, v, w),
           "blhd_rule": ms(native, q, k, v, w), **{f"blhd_g{g}": ms(with_heads(g), q, k, v, w) for g in GROUPS}}
    rule = {direction: RULE(HEADS, seq, WIDTH, 2, bufs, "blhd") for direction, bufs in (("fwd", (6, 2)), ("bwd", (10, 3)))}
    report("fwd_bwd_ms", shape=(batch, seq, HEADS, WIDTH), rule_heads_a_step=rule, ms=row)
