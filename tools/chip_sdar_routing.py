"""SDAR's cell a step at a time on the chip: each layer's share of held rows
beside the step's time, so that a run that reads low can be told from its
routing (`PERF.md` section 6, PR 32, second session; defect 14).

    chiprun -- python3 tools/chip_sdar_routing.py 3100000039,41 70     (PERF.md, PR 32)

Builds the cell as `benchmark.runners.train` does (weights and batches from
the seed, the ring after the runner's eight check rows), then runs the train
program synchronously, fetching every `moe_experts` op's `Held` and `Load`:
one JSON line a step.  The fetches make the step a few ms longer than the
benchmark's; the shares are the benchmark's own.  `DRY=1` rehearses it
tiny on the CPU.
"""
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

import paddle_tpu as fluid
from benchmark import manifest as mf
from benchmark.models import sdar

CHECK_ROWS = 8  # benchmark/runners/train.py draws these from the seed's stream first
DRY = os.environ.get("DRY") == "1"  # a rehearsal on the CPU: tiny, and no number of it means anything
TINY = (dict(hidden_size=64, num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2, head_dim=32,
             moe_intermediate_size=32, num_experts=4, num_routed_experts=16, num_experts_per_tok=2, vocab_size=96,
             routing_seed=0), dict(seq_len=32, batch_per_chip=4, ring=4))


def main(seeds, steps):
    cfg = mf.read_json("benchmark/configs/sdar-30b-a3b-chat.json")
    job = mf.read_json("benchmark/traffic/train-blockdiff-s4096.json")
    if DRY:
        cfg.update(TINY[0])
        job.update(TINY[1])
    for seed in seeds:
        program, startup, _, loss, _ = sdar.build(cfg, job)
        program.random_seed = startup.random_seed = seed
        scope = fluid.Scope()
        exe = fluid.Executor(fluid.TPUPlace(0))
        exe.run(startup, scope=scope)
        experts = [op for op in program.global_block().ops if op.type == "moe_experts"]
        fetches = [loss] + [op.outputs["Held"][0] for op in experts] + [op.inputs["Load"][0] for op in experts]
        rng = np.random.RandomState(seed)
        sdar.make_batch(rng, cfg, job, CHECK_ROWS)
        ring = [sdar.make_batch(rng, cfg, job, job["batch_per_chip"]) for _ in range(job["ring"])]
        n = len(experts)
        for step in range(steps):
            start = time.perf_counter()
            got = [np.asarray(g) for g in exe.run(program, feed=ring[step % len(ring)], fetch_list=fetches, scope=scope)]
            ms = (time.perf_counter() - start) * 1e3
            held, load = got[1:1 + n], got[1 + n:]
            print(json.dumps({
                "seed": seed, "step": step, "ms": round(ms, 2), "loss": float(got[0].reshape(-1)[0]),
                "held_share": [round(100.0 * float(h.sum() / l.sum()), 2) for h, l in zip(held, load)],
                "load_max_over_mean": [round(float(l.max() / l.mean()), 2) for l in load]}), flush=True)


if __name__ == "__main__":
    main([int(s) for s in sys.argv[1].split(",")], int(sys.argv[2]))
