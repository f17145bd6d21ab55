"""On the chip: the three attentions of `fused_attention` against float32 on
unit-variance q, k, v (tests/test_pallas_attention.py: attention_errors), and
each attention's time alone at BERT-base's heads and ~16k tokens; then (PR 37)
the causal attentions at long keys, the stock flash kernel and the splash
kernels under the causal rule (`ops/masked_attention.py: causal_attention`),
at 128-wide heads and at 64-wide heads on grouped key/value heads, which an
interpreted run cannot vouch for (PERF.md, defect 15).  CAUSAL=1 runs those alone.
Since PR 64 `causal_attention`'s backward is the one kernel that sums dq in VMEM and
rounds it ONCE (`ops/attention_backward_kernels.py`); beside it the stock fused
backward, whose dq is a partial a block of keys rounded to bf16 before XLA sums
them (2 partials at 2048 keys, 8 at 8192: the third shape).

    chiprun -- python3 tools/chip_attention_errors.py     (PERF.md, PRs 30, 37 and 64)
"""
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp

from tests.test_pallas_attention import attention_errors, flash_causal, xla_attention
from paddle_tpu.ops.masked_attention import causal_attention
from paddle_tpu.ops.nn_ops import _flash_attention_tpu
from paddle_tpu.ops.pallas_attention import fused_sdpa

DRY = os.environ.get("DRY") == "1"  # a rehearsal on the CPU: XLA's attention only, tiny
assert DRY or jax.devices()[0].platform == "tpu", jax.devices()


def stock_fused(q, k, v):
    """The stock splash kernels under the causal rule with the stock FUSED
    backward: what `causal_attention` ran until PR 64."""
    from jax.experimental.pallas.ops.tpu.splash_attention import splash_attention_kernel as splash
    from jax.experimental.pallas.ops.tpu.splash_attention import splash_attention_mask as mask_lib

    b = 128 if DRY else 1024
    sizes = splash.BlockSizes(block_q=b, block_kv=b, block_kv_compute=min(b, 512), block_q_dkv=b, block_kv_dkv=b,
                              block_kv_dkv_compute=min(b, 512), use_fused_bwd_kernel=True)
    mask = mask_lib.MultiHeadMask([mask_lib.CausalMask((q.shape[2], q.shape[2]))] * q.shape[1])
    kernel = splash.make_splash_mha(mask, block_sizes=sizes, head_shards=1, q_seq_shards=1, interpret=DRY)
    return jax.vmap(kernel)((q.astype(jnp.float32) * q.shape[-1] ** -0.5).astype(q.dtype), k, v)


# the float32 reference holds [B, H, L, L] scores: 2048 keys, a few heads; 8192 keys on 4 heads (1 GB an array)
for causal_shape, kv_heads in (((1, 4, 256, 128), 4), ((1, 8, 256, 64), 2)) if DRY else (
        ((2, 8, 2048, 128), 8), ((2, 16, 2048, 64), 4), ((1, 4, 8192, 128), 4)):
    causal = {"block_causal": lambda q, k, v: causal_attention(q, k, v, q.shape[-1] ** -0.5, interpret=DRY),
              "stock_fused_backward": stock_fused}
    if not DRY:
        causal["flash"] = flash_causal
    for seed in (0, 1):
        print(json.dumps({"causal_attention_errors": causal_shape, "kv_heads": kv_heads, "seed": seed,
                          "device": jax.devices()[0].device_kind,
                          "errors": attention_errors(causal_shape, causal, seed=seed, causal=True, kv_heads=kv_heads)}),
              flush=True)
if os.environ.get("CAUSAL") == "1":
    sys.exit(0)
shape = (2, 12, 512, 64) if DRY else (32, 12, 512, 64)
scale = shape[-1] ** -0.5
attentions = {
    "xla": xla_attention,
    "row_kernel": lambda q, k, v: fused_sdpa(q, k, v, None, False, scale),
    # the same kernel over the projections' own layout, (B, L, H, dh), through the reference's axes (PR 39)
    "row_kernel_blhd": lambda q, k, v: jnp.swapaxes(fused_sdpa(*(jnp.swapaxes(t, 1, 2) for t in (q, k, v)), None, False, scale,
                                                               False, "blhd"), 1, 2),
    "flash": lambda q, k, v: _flash_attention_tpu(q, k, v, None, False, scale),
}
if DRY:
    attentions = {"xla": xla_attention}
for seed in (0, 1):
    print(json.dumps({"attention_errors": shape, "seed": seed, "device": jax.devices()[0].device_kind,
                      "errors": attention_errors(shape, attentions, seed=seed)}), flush=True)

if DRY:
    sys.exit(0)  # a time taken on the CPU is no device number
# each attention alone, forward + backward, ms (a microbenchmark: not the cell)
key = jax.random.PRNGKey(0)
for seq, batch in ((128, 128), (256, 64), (384, 48), (512, 32)):
    q, k, v, w = (jax.random.normal(kk, (batch, 12, seq, 64), jnp.bfloat16) for kk in jax.random.split(key, 4))
    row = {}
    for name, f in attentions.items():
        g = jax.jit(jax.grad(lambda q, k, v: (f(q, k, v) * w).astype(jnp.float32).sum(), argnums=(0, 1, 2)))
        try:
            jax.block_until_ready(g(q, k, v))
            t = time.perf_counter()
            for _ in range(20):
                out = g(q, k, v)
            jax.block_until_ready(out)
            row[name] = round((time.perf_counter() - t) / 20 * 1e3, 3)
        except Exception as e:  # a shape a kernel refuses
            row[name] = repr(e)[:120]
    print(json.dumps({"fwd_bwd_ms": row, "shape": (batch, 12, seq, 64)}), flush=True)
