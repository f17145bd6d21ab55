"""ICI collective micro-benchmark (BASELINE.md last row: achieved allreduce
bandwidth vs roofline; reference shape: benchmark/fluid/fluid_benchmark.py
multi-GPU modes measuring NCCL throughput).

Sweeps psum / all_gather / reduce_scatter / ppermute over a jax.sharding
Mesh across a range of payload sizes, timing K chained collectives per
dispatch (one device sync at the end), and reports achieved algorithmic
bandwidth per chip:

  allreduce:      algo_bytes = 2 * (n-1)/n * payload   (ring)
  all_gather:     algo_bytes = (n-1)/n * result
  reduce_scatter: algo_bytes = (n-1)/n * payload
  ppermute:       algo_bytes = payload                 (one hop)

vs_roofline uses --ici-gbps (per-direction per-link; v5e ICI ~ 186 GB/s
bidirectional over 2 links -> pass the datasheet number for the target
topology).  On the 8-device virtual CPU mesh the absolute numbers are
host-memcpy speeds — the point there is validating the harness end to end
(tests/test_collective_bench.py + the dryrun), so the day multi-chip
hardware exists this file is the measurement, not a TODO.

  JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
      python tools/collective_bench.py --sizes-mb 1,8 --iters 3

`--overlap` (ISSUE 7) A/Bs the backward-overlapped bucketed gradient
all-reduce against the serial single-flat-psum baseline through the
production bucketing code (parallel.distributed.make_grad_sync):

  python tools/collective_bench.py --overlap --layers 12 --grad-mb 4
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

if __name__ == "__main__":
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np


def _mesh(n=None):
    import jax
    from jax.sharding import Mesh

    devs = jax.devices()
    n = n or len(devs)
    return Mesh(np.array(devs[:n]), ("x",))


def bench_collective(kind, size_mb, mesh, iters=4, chain=8, dtype="float32"):
    """One (collective, size) point: per-chip payload `size_mb`, `chain`
    dependent collectives per dispatch."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    n = mesh.devices.size
    elems = int(size_mb * 1e6) // np.dtype(dtype).itemsize
    elems -= elems % n  # reduce_scatter needs n | elems
    x = jnp.ones((n, elems), dtype)
    x = jax.device_put(x, NamedSharding(mesh, P("x", None)))

    def body(v):
        if kind == "allreduce":
            return jax.lax.psum(v, "x") * (1.0 / n)  # keep values bounded
        if kind == "all_gather":
            g = jax.lax.all_gather(v, "x")           # [n, elems]
            return g[0]                               # keep carry shape
        if kind == "reduce_scatter":
            g = jax.lax.psum_scatter(v, "x", tiled=True)
            return jnp.tile(g, n)[:v.shape[0]]
        if kind == "ppermute":
            return jax.lax.ppermute(v, "x", [(i, (i + 1) % n) for i in range(n)])
        raise ValueError(kind)

    @jax.jit
    @lambda f: jax.shard_map(f, mesh=mesh, in_specs=P("x", None),
                             out_specs=P("x", None), check_vma=False)
    def step(v):
        row = v[0]
        for _ in range(chain):
            row = body(row) + 1e-9  # data dependence between collectives
        return row[None, :]

    out = step(x)
    np.asarray(jax.device_get(out[0, :1]))
    best = 1e9
    for _ in range(iters):
        t0 = time.perf_counter()
        out = step(x)
        np.asarray(jax.device_get(out[0, :1]))
        best = min(best, (time.perf_counter() - t0) / chain)

    payload = elems * np.dtype(dtype).itemsize
    if kind == "allreduce":
        algo = 2 * (n - 1) / n * payload
    elif kind in ("all_gather", "reduce_scatter"):
        algo = (n - 1) / n * payload
    else:
        algo = payload
    return {"collective": kind, "payload_mb": round(payload / 1e6, 3),
            "devices": n, "time_us": round(best * 1e6, 1),
            "achieved_gbps": round(algo / best / 1e9, 3)}


def bench_overlap(mesh, layers=8, grad_mb=1.0, bucket_mb=4.0, iters=4,
                  width=256, dtype="float32"):
    """Backward-overlapped vs serial gradient all-reduce A/B through the
    PRODUCTION bucketing code (parallel.distributed.make_grad_sync — the
    same callable CompiledProgram.with_grad_overlap installs on the
    lowering).

    Emulates a backward pass as `layers` dependent matmul segments, each
    yielding a `grad_mb`-sized gradient as it completes.  The bucketed arm
    psums size-capped buckets whose dataflow depends only on their member
    grads — XLA's latency-hiding scheduler can issue each bucket while
    later segments still compute; the serial arm's ONE flat psum depends
    on every grad, so it cannot start until the whole chain is done (the
    fetch-barrier-at-optimizer-boundary shape DDP replaced).  Both arms
    are element-wise identical; the A/B isolates scheduling.

    On the virtual CPU mesh the numbers validate the harness (like the
    raw-collective sweep above); on real multi-chip hardware the
    overlap_gain is the measurement."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from paddle_tpu.parallel.distributed import make_grad_sync

    elems = max(int(grad_mb * 1e6) // np.dtype(dtype).itemsize, 1)
    rng = np.random.RandomState(0)
    ws = jnp.asarray(rng.randn(layers, width, width) * (width ** -0.5), dtype)
    x0 = jnp.asarray(rng.randn(width, width), dtype)

    def make_step(mode):
        sync = make_grad_sync("x", int(bucket_mb * 1e6), mode=mode)

        def worker(x, w_stack):
            grads = []
            h = x
            for i in range(layers):
                h = jnp.tanh(h @ w_stack[i])
                # grad_i's dataflow hangs off segment i's output: the
                # payload becomes available exactly when this "layer"
                # finishes, like a real backward
                g = jnp.full((elems,), 0.0, dtype) + h[0, 0]
                grads.append((f"g{i}", g))
            synced = sync(grads)
            acc = jnp.zeros((), jnp.float32)
            for v in synced.values():
                acc = acc + jnp.mean(v).astype(jnp.float32)
            return h, acc

        return jax.jit(jax.shard_map(worker, mesh=mesh,
                                     in_specs=(P(), P()),
                                     out_specs=(P(), P()), check_vma=False))

    out = {}
    parity = {}
    for mode in ("serial", "bucketed"):
        step = make_step(mode)
        h, acc = step(x0, ws)
        np.asarray(jax.device_get(acc))
        best = 1e9
        for _ in range(iters):
            t0 = time.perf_counter()
            h, acc = step(x0, ws)
            np.asarray(jax.device_get(acc))
            best = min(best, time.perf_counter() - t0)
        out[mode] = best
        parity[mode] = float(np.asarray(jax.device_get(acc)))

    # the schedule actually measured: make_grad_sync plans greedy buckets
    # over f32 comm sizes (g.size * 4), not a flat ceil over total bytes
    from paddle_tpu.parallel.distributed import plan_buckets
    n_buckets = len(plan_buckets([(f"g{i}", elems * 4)
                                  for i in range(layers)],
                                 int(bucket_mb * 1e6)))
    return {"metric": "grad_allreduce_overlap_ab",
            "devices": int(mesh.devices.size),
            "layers": layers, "grad_mb": grad_mb, "bucket_mb": bucket_mb,
            "n_buckets": n_buckets,
            "serial_ms": round(out["serial"] * 1e3, 3),
            "bucketed_ms": round(out["bucketed"] * 1e3, 3),
            "overlap_gain": round(out["serial"] / out["bucketed"], 4)
            if out["bucketed"] else None,
            "parity": bool(np.isclose(parity["serial"], parity["bucketed"],
                                      rtol=1e-6))}


def main(argv=None):
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--sizes-mb", default="0.25,1,4,16,64")
    p.add_argument("--collectives",
                   default="allreduce,all_gather,reduce_scatter,ppermute")
    p.add_argument("--iters", type=int, default=4)
    p.add_argument("--chain", type=int, default=8)
    p.add_argument("--devices", type=int, default=None)
    p.add_argument("--ici-gbps", type=float, default=None,
                   help="per-chip ICI roofline for vs_roofline (e.g. 186 "
                        "for v5e bidirectional)")
    p.add_argument("--cpu-mesh", type=int, default=None, metavar="N",
                   help="run on an N-device virtual CPU mesh (sets the "
                        "device count and the platform before jax starts)")
    p.add_argument("--overlap", action="store_true",
                   help="backward-overlapped vs serial gradient all-reduce "
                        "A/B through parallel.distributed.make_grad_sync "
                        "(the ISSUE-7 measurement); prints one JSON line "
                        "with both walls + overlap_gain")
    p.add_argument("--layers", type=int, default=8,
                   help="--overlap: emulated backward segments")
    p.add_argument("--grad-mb", type=float, default=1.0,
                   help="--overlap: per-segment gradient payload (MB)")
    p.add_argument("--bucket-mb", type=float, default=4.0,
                   help="--overlap: bucket size cap (MB), as "
                        "FLAGS_dp_bucket_mb")
    args = p.parse_args(argv)

    if args.cpu_mesh:
        os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                                   + f" --xla_force_host_platform_device_count={args.cpu_mesh}").strip()
        import jax

        jax.config.update("jax_platforms", "cpu")

    mesh = _mesh(args.devices)
    if args.overlap:
        print(json.dumps(bench_overlap(mesh, layers=args.layers,
                                       grad_mb=args.grad_mb,
                                       bucket_mb=args.bucket_mb,
                                       iters=args.iters)))
        return
    for kind in args.collectives.split(","):
        for size in args.sizes_mb.split(","):
            rec = bench_collective(kind, float(size), mesh,
                                   iters=args.iters, chain=args.chain)
            if args.ici_gbps:
                rec["vs_roofline"] = round(rec["achieved_gbps"] / args.ici_gbps, 4)
            print(json.dumps(rec))


if __name__ == "__main__":
    main()
