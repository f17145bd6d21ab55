"""Prices, on one chip, the selective scan of Jamba's cell as the step runs it
(run through `chiprun -- python3 tools/chip_jamba_scan.py`, ~3 min): the op
`ops/ssm_ops.py: chunked_selective_scan` alone at a chip's share of the cell,
(1, 8192, 5120) with a state of 16, forward and forward + backward, for each
chunk length of `CHUNKS` (the op's default is `ssm_ops._SSM_CHUNK`); how far
each lies from the token-by-token float32 recurrence rounded as the op rounds
(`benchmark/models/jamba.py: scan_recurrence`, on the first `STAGE_CHANNELS`
channels, as the cell's stage reads it), beside the recurrence with a bf16
state and with a bf16 step; and what the recurrence itself takes a row (the
reference's cost).  Prints one JSON line a reading.  `ROWS=2 LENGTH=4096` is
the cell's other plan."""
import json
import os
import sys
import time

sys.path.insert(0, os.getcwd())

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.models import jamba
from paddle_tpu.ops import ssm_ops

RUNS = 3
CHUNKS = tuple(int(c) for c in os.environ.get("CHUNKS", "32,64,128,256").split(","))
ROWS, LENGTH = int(os.environ.get("ROWS", 1)), int(os.environ.get("LENGTH", 8192))
CHANNELS, STATE = int(os.environ.get("CHANNELS", 5120)), 16


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
    r = np.random.RandomState(seed)
    x, dt = r.randn(ROWS, LENGTH, CHANNELS), 0.3 * r.randn(ROWS, LENGTH, CHANNELS)
    b, c = r.randn(ROWS, LENGTH, STATE), r.randn(ROWS, LENGTH, STATE)
    a_log = np.log(np.tile(np.arange(1, STATE + 1, dtype="f4"), (CHANNELS, 1)))
    step = np.exp(r.uniform(np.log(1e-3), np.log(1e-1), CHANNELS))
    bias = (step + np.log(-np.expm1(-step))).astype("f4")
    return (tuple(jnp.asarray(t, jnp.bfloat16) for t in (x, dt, b, c))
            + (jnp.asarray(a_log), jnp.ones(CHANNELS, jnp.float32), jnp.asarray(bias)))


def main():
    say(device=jax.devices()[0].device_kind, rows=ROWS, length=LENGTH, channels=CHANNELS, state=STATE)
    x, dt, b, c, a_log, d_skip, bias = inputs(1)
    few = min(jamba.STAGE_CHANNELS, CHANNELS)
    sliced = (x[..., :few], dt[..., :few], b, c, a_log[:few], d_skip[:few], bias[:few])
    ms, want = timed(jax.jit(jamba.scan_recurrence), *sliced)
    say(what="recurrence", channels=few, ms=ms)
    want = np.asarray(want)
    scale = float(np.sqrt(np.mean(np.square(want))))
    rounded = jamba._bf16(want)
    for name, kw in (("bf16_state", dict(bf16_state=True)), ("bf16_step", dict(bf16_step=True))):
        low = np.asarray(jax.jit(lambda *a: jamba.scan_recurrence(*a, **kw))(*sliced))
        say(what=f"recurrence_{name}", error=float(np.sqrt(np.mean(np.square(jamba._bf16(low) - rounded)))) / scale)
    for chunk in CHUNKS:
        def forward(x, dt, b, c):
            return ssm_ops.chunked_selective_scan(x, dt, a_log, b, c, d_skip, bias, chunk)[0]

        def both(x, dt, b, c):
            return jax.grad(lambda *a: jnp.sum(forward(*a).astype(jnp.float32) * x.astype(jnp.float32)), argnums=(0, 1, 2, 3))(x, dt, b, c)

        try:
            fwd_ms, y = timed(jax.jit(forward), x, dt, b, c)
            both_ms, _ = timed(jax.jit(both), x, dt, b, c)
        except Exception as e:   # a chunk whose arrays do not fit
            say(what="scan", chunk=chunk, failed=str(e)[:200])
            continue
        got = np.asarray(y[..., :few], "f4")
        say(what="scan", chunk=chunk, forward_ms=fwd_ms, forward_and_backward_ms=both_ms,
            error=float(np.sqrt(np.mean(np.square(got - rounded)))) / scale,
            error_unrounded=float(np.sqrt(np.mean(np.square(got - want)))) / scale,
            peak_gb=(jax.devices()[0].memory_stats() or {}).get("peak_bytes_in_use", 0) / 1e9)


if __name__ == "__main__":
    main()
