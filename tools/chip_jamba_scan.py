"""Prices, on one chip, the selective scan of Jamba's cell as the step runs it
(run through `chiprun -- python3 tools/chip_jamba_scan.py`, ~3 min): the op
alone at a chip's share of the cell, (1, 8192, 5120) with a state of 16, bf16,
forward and forward + backward (all seven gradients), in BOTH forms: the XLA
form `ops/ssm_ops.py: chunked_selective_scan` at each chunk length of `CHUNKS`
(the op's is `ssm_ops._SSM_CHUNK`) and the Pallas kernels of
`ops/ssm_kernels.py` at `CHUNK` tokens and `BLOCK` channels a grid step (this
tool's arguments: the op has one value of each, `ssm_kernels.CHUNK` / `BLOCK`,
stated there with the run that chose it; several `CHUNK:BLOCK` pairs in
`KERNELS`).  Then how far each form lies from the token-by-token float32
recurrence (`benchmark/models/jamba.py: scan_recurrence`) on the first
`STAGE_CHANNELS` channels: the output rounded as the op rounds, as the cell's
stage reads it, beside the recurrence with a bf16 state and with a bf16 step;
and, on the same bf16 VALUES held in float32 so that no output's rounding
hides the arithmetic, the output and each of the seven gradients.  Prints one
JSON line a reading.  `ROWS=2 LENGTH=4096` is the cell's other plan."""
import json
import os
import sys
import time

sys.path.insert(0, os.getcwd())

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.models import jamba
from paddle_tpu.ops import ssm_kernels, ssm_ops

RUNS = 3
CHUNKS = tuple(int(c) for c in os.environ.get("CHUNKS", str(ssm_ops._SSM_CHUNK)).split(",") if c)
KERNELS = tuple(tuple(int(v) for v in pair.split(":")) for pair in
                os.environ.get("KERNELS", f"{ssm_kernels.CHUNK}:{ssm_kernels.BLOCK}").split(",") if pair)
ROWS, LENGTH = int(os.environ.get("ROWS", 1)), int(os.environ.get("LENGTH", 8192))
CHANNELS, STATE = int(os.environ.get("CHANNELS", 5120)), 16
NAMES = ("x", "dt", "b", "c", "a_log", "d", "dt_bias")


def say(**fields):
    print(json.dumps(fields, default=float), flush=True)


def timed(fn, *args):
    out = jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(RUNS):
        out = fn(*args)
    jax.block_until_ready(out)
    return 1e3 * (time.perf_counter() - t0) / RUNS, out


def inputs(seed):
    """x, dt, B, C bf16 and ALog, D, DtBias float32, in `NAMES`' order."""
    r = np.random.RandomState(seed)
    x, dt = r.randn(ROWS, LENGTH, CHANNELS), 0.3 * r.randn(ROWS, LENGTH, CHANNELS)
    b, c = r.randn(ROWS, LENGTH, STATE), r.randn(ROWS, LENGTH, STATE)
    a_log = np.log(np.tile(np.arange(1, STATE + 1, dtype="f4"), (CHANNELS, 1)))
    step = np.exp(r.uniform(np.log(1e-3), np.log(1e-1), CHANNELS))
    bias = (step + np.log(-np.expm1(-step))).astype("f4")
    return (tuple(jnp.asarray(t, jnp.bfloat16) for t in (x, dt, b, c))
            + (jnp.asarray(a_log), jnp.ones(CHANNELS, jnp.float32), jnp.asarray(bias)))


def forms():
    """{name: the op's output as a function of `NAMES`' seven arrays}."""
    found = {}
    for chunk in CHUNKS:
        found[f"xla_{chunk}"] = lambda x, dt, b, c, al, ds, bias, chunk=chunk: ssm_ops.chunked_selective_scan(
            x, dt, al, b, c, ds, bias, chunk)[0]
    for chunk, block in KERNELS:
        found[f"kernels_{chunk}_{block}"] = lambda x, dt, b, c, al, ds, bias, chunk=chunk, block=block: ssm_ops.kernel_selective_scan(
            x, dt, al, b, c, ds, bias, "tpu", chunk, block if x.shape[-1] % block == 0 else ssm_kernels.UNIT)[0]    # the errors' few channels
    return found


def relative(got, want):
    got, want = np.asarray(got, "f4"), np.asarray(want, "f4")
    return float(np.sqrt(np.mean(np.square(got - want))) / np.sqrt(np.mean(np.square(want))))


def main():
    say(device=jax.devices()[0].device_kind, rows=ROWS, length=LENGTH, channels=CHANNELS, state=STATE)
    full = inputs(1)
    few = min(jamba.STAGE_CHANNELS, CHANNELS)
    sliced = tuple(t[..., :few] if t.shape[-1] == CHANNELS else t[:few] if t.shape[0] == CHANNELS else t for t in full)
    held = tuple(t.astype(jnp.float32) for t in sliced)                      # the same values, no output rounded
    weight = jnp.asarray(np.random.RandomState(2).randn(ROWS, LENGTH, few), jnp.float32)

    def gradients(fn):
        return jax.jit(jax.grad(lambda *a: jnp.sum(fn(*a).astype(jnp.float32) * weight), argnums=tuple(range(7))))

    ms, want = timed(jax.jit(jamba.scan_recurrence), *sliced)
    say(what="recurrence", channels=few, ms=ms)
    want = np.asarray(want)
    rounded = jamba._bf16(want)
    for name, kw in (("bf16_state", dict(bf16_state=True)), ("bf16_step", dict(bf16_step=True))):
        low = np.asarray(jax.jit(lambda *a: jamba.scan_recurrence(*a, **kw))(*sliced))
        say(what=f"recurrence_{name}", error=relative(jamba._bf16(low), rounded))
    want_grads = [np.asarray(g) for g in gradients(jamba.scan_recurrence)(*held)]
    for name, fn in forms().items():
        def both(*a):
            return jax.grad(lambda *a: jnp.sum(fn(*a).astype(jnp.float32) * a[0].astype(jnp.float32)), argnums=tuple(range(7)))(*a)

        try:
            fwd_ms, _ = timed(jax.jit(fn), *full)
            both_ms, _ = timed(jax.jit(both), *full)
            got = jax.jit(fn)(*sliced)
            exact, grads = jax.jit(fn)(*held), gradients(fn)(*held)
        except Exception as e:   # a chunk whose arrays do not fit, a kernel Mosaic refuses
            say(what=name, failed=str(e)[:400])
            continue
        say(what=name, forward_ms=fwd_ms, forward_and_backward_ms=both_ms, error=relative(got, rounded),
            error_float32=relative(exact, want), **{f"d_{n}_error": relative(g, w) for n, g, w in zip(NAMES, grads, want_grads)},
            peak_gb=(jax.devices()[0].memory_stats() or {}).get("peak_bytes_in_use", 0) / 1e9)


if __name__ == "__main__":
    main()
