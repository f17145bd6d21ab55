"""Interleaved A/B of BERT-base train-step variants on the real chip.

Variants: f32 (round-3 config), bf16, bf16+fused(flash) attention.
Protocol from the r3 chip round: interleave variants round-robin, best-of-N
windows each, report per-variant best — single measurements on the shared
chip are not evidence.
"""
import sys
import time

import numpy as np
import jax
import jax.numpy as jnp

import paddle_tpu as fluid
from paddle_tpu.models import transformer

B, L = 256, 128


def make(name, **kw):
    main, startup, feeds, fetches = transformer.build_bert(
        vocab_size=30522, seq_len=L, d_model=768, n_layers=12, n_heads=12,
        d_ff=3072, dropout_prob=0.1, with_optimizer=True, **kw)
    scope = fluid.Scope()
    exe = fluid.Executor(fluid.TPUPlace(0))
    exe.run(startup, scope=scope)
    batch = transformer.make_fake_batch(B, L, 30522)
    dev = fluid.TPUPlace(0).jax_device()
    batch = {k: jax.device_put(jnp.asarray(v), dev) for k, v in batch.items()}
    loss_name = fetches["loss"].name

    def dispatch():
        return exe.run(main, feed=batch, fetch_list=[loss_name], scope=scope,
                       return_numpy=False)

    # warm
    for _ in range(3):
        out = dispatch()
    np.asarray(out[0])
    return name, dispatch


def window(dispatch, iters=4):
    t0 = time.perf_counter()
    for _ in range(iters):
        out = dispatch()
    np.asarray(out[0])
    return (time.perf_counter() - t0) / iters


VARIANTS = {
    "f32": dict(dtype="float32"),
    "bf16": dict(dtype="bfloat16"),
    "bf16+flash": dict(dtype="bfloat16", use_fused_attention=True),
}


def main():
    # three full BERT+Adam states don't fit HBM together: A/B one PAIR per
    # invocation (pass two variant names), interleaved round-robin
    names = [a for a in sys.argv[1:] if a in VARIANTS] or ["bf16", "bf16+flash"]
    variants = [make(n, **VARIANTS[n]) for n in names]
    best = {n: float("inf") for n, _ in variants}
    for rnd in range(4):
        for n, d in variants:
            dt = window(d)
            best[n] = min(best[n], dt)
            print(f"round {rnd} {n}: {dt*1e3:.1f} ms", file=sys.stderr)
    flops_per_seq = 6 * 110e6 * L
    for n, _ in variants:
        dt = best[n]
        seqs = B / dt
        mfu = seqs * flops_per_seq / 197e12
        print(f"{n}: best {dt*1e3:.1f} ms  {seqs:.0f} seqs/s  mfu {mfu:.3f}")


if __name__ == "__main__":
    main()
