"""Interleaved A/B: BERT-base @ seq 512, bf16, plain vs flash(Pallas)
attention — validates the _FLASH_MIN_SEQ=512 routing threshold on a full
train step (the microbench sweep was unreliable on the r4 machine)."""
import sys
import time

import numpy as np
import jax
import jax.numpy as jnp

import paddle_tpu as fluid
from paddle_tpu.models import transformer

B, L = 32, 512


def make(name, **kw):
    main, startup, feeds, fetches = transformer.build_bert(
        vocab_size=30522, seq_len=L, d_model=768, n_layers=12, n_heads=12,
        d_ff=3072, dropout_prob=0.1, with_optimizer=True, dtype="bfloat16", **kw)
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


def main():
    variants = [make("plain", use_fused_attention=False),
                make("flash", use_fused_attention=True)]
    best = {n: float("inf") for n, _ in variants}
    for rnd in range(4):
        for n, d in variants:
            dt = window(d)
            best[n] = min(best[n], dt)
            print(f"round {rnd} {n}: {dt*1e3:.1f} ms", file=sys.stderr)
    for n, _ in variants:
        dt = best[n]
        seqs = B / dt
        # attention flops matter at 512: 6*(110e6 params)*L + attn term
        print(f"{n}: best {dt*1e3:.1f} ms  {seqs:.1f} seqs/s")


if __name__ == "__main__":
    main()
