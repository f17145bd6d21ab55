"""Round-5 interleaved A/B: unfuse BN stats reductions from convolutions.

The r5 profile (its script since replaced by benchmark/trace_reduce.py)
showed conv fusions carrying
BN-stat reduce epilogues running at 9-43 TF/s vs ~90-190 for clean convs —
but the step is bandwidth-bound, so what matters is total HBM bytes, not
in-fusion MXU rate.  Variants (result: r5 chip round):

  base        : round-4 lowering (two-pass stats, fused into convs)   115.4 ms
  barrier     : two-pass stats behind an optimization_barrier         130.8 ms
  single      : one fused E[x]/E[x^2] pass, no barrier                103.9 ms  <- shipped
  barrier1    : barrier + single fused stats pass                     120.9 ms

  python experiments/resnet_bn_unfuse_ab.py [rounds] [iters]
"""
from __future__ import annotations

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

K = 4


def make_dispatch(unfuse, fused_pass):
    from paddle_tpu.ops import nn_ops
    from tools.bench_kit import make_resnet_dispatch

    def with_flags(fn):
        # the lowering flags participate in the executor compile-cache key,
        # so they must hold BOTH at compile time and at every dispatch (a
        # dispatch under different flags would recompile the default config
        # and silently time the wrong variant)
        saved = (nn_ops._BN_UNFUSE_CONV, nn_ops._BN_STATS_FUSED_PASS,
                 nn_ops._BN_BF16_FUSED_DEFAULT)
        # base/barrier variants must explicitly restore the r4 two-pass
        # lowering (bf16 models take the fused pass by default)
        nn_ops._BN_UNFUSE_CONV = unfuse
        nn_ops._BN_STATS_FUSED_PASS = fused_pass
        nn_ops._BN_BF16_FUSED_DEFAULT = fused_pass
        try:
            return fn()
        finally:
            (nn_ops._BN_UNFUSE_CONV, nn_ops._BN_STATS_FUSED_PASS,
             nn_ops._BN_BF16_FUSED_DEFAULT) = saved

    inner, _ = with_flags(lambda: make_resnet_dispatch(K=K))
    return lambda: with_flags(inner)


def main():
    rounds = int(sys.argv[1]) if len(sys.argv) > 1 else 4
    iters = int(sys.argv[2]) if len(sys.argv) > 2 else 4
    from tools.opbench import interleave

    variants = {
        "base": make_dispatch(False, False),
        "barrier": make_dispatch(True, False),
        "single": make_dispatch(False, True),
        "barrier1": make_dispatch(True, True),
    }
    stats = interleave(variants, rounds=rounds, iters=iters, warmup=1)
    for name, s in stats.items():
        per_step = s["best_ms"] / K
        print(f"{name:9s} best {per_step:7.2f} ms/step  "
              f"({256/per_step*1e3:6.0f} imgs/s)  spread {s['spread_pct']}%  "
              f"windows {[round(w/K,2) for w in s['windows_ms']]}")
    print(json.dumps(stats))


if __name__ == "__main__":
    main()
