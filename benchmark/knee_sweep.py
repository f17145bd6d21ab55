"""The sweep that finds a serving cell's knee, made once when the cell is
defined (and again by a later benchmark PR once an optimisation has moved it).

    python3 -m benchmark.knee_sweep --workload resnet50.serve-steady --seed 1 \
        --rates 100,200,400 --seconds 8

One process, one loaded server; for each rate in turn `--seconds` of the
cell's traffic after its warming traffic, then a line of JSON: p50, p99,
failed, whether the queue was deeper at the end than at the start.  The knee
is the highest rate at which p99 meets the limit, nothing is shed and the
queue does not grow; the cell then runs at a fixed share of it, written into
its traffic file as a number.  The benchmark itself never searches.
"""
from __future__ import annotations

import argparse
import json

import numpy as np

from benchmark import manifest as mf
from benchmark.run import CompileCounter, Run, require_chips
from benchmark.runners import serve


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--rates", required=True, help="requests/s, comma-separated")
    ap.add_argument("--seconds", default="8", help="window length, or one per rate, comma-separated")
    args = ap.parse_args(argv)

    manifest = mf.load_with_planned()
    cell = mf.cell(manifest, args.workload)
    config = mf.config_of(manifest, cell)
    job = mf.read_json(mf.traffic_path(cell["traffic"]))
    devices = require_chips(cell["chips"])
    from paddle_tpu.flags import apply_compile_cache

    apply_compile_cache(mf.ROOT + "/.jax_cache")
    run = Run(cell=cell, config=config, traffic=job, model=mf.model_module(config),
              seed=args.seed, seconds=0.0, trace=False, devices=devices,
              compiles=CompileCounter(), trace_dir="")
    served = serve.Served(run)
    try:
        rates = [float(r) for r in args.rates.split(",")]
        lengths = [float(x) for x in args.seconds.split(",")]
        lengths = lengths * len(rates) if len(lengths) == 1 else lengths
        for k, (rate, seconds) in enumerate(zip(rates, lengths)):
            due, rows, offset, picked = serve.schedule(args.seed + 2 * k, job, rate, seconds)
            depth0 = served.server.stats()["queue_depth"]
            shed0 = served.server.stats()["shed"]
            res = serve.drive(served, due, rows, offset)
            late = res["t_done"][picked][-50:] - res["t_due"][picked][-50:]
            try:
                out = serve.summarise(res, rows, seconds, picked)
            except ValueError as e:  # too few came back for a percentile
                out = {"error": str(e)}
            stats = served.server.stats()
            print(json.dumps({"rate_per_s": rate, "seconds": seconds, **out,
                              "shed": stats["shed"] - shed0,
                              "queue_depth_start": depth0,
                              "last_50_mean_ms": float(np.nanmean(late) * 1e3),
                              "buckets": {b: a["batches"] for b, a in
                                          served.server.bucket_attribution().items()}}),
                  flush=True)
    finally:
        served.close()


if __name__ == "__main__":
    main()
