"""NMT f32 vs bf16 A/B, round 5: the r4 A/B measured bf16 a no-op (652 vs
629 seqs/s) on a ONE-DISPATCH-PER-STEP harness that was mostly host dispatch
latency; with the steps=K scan the bench now measures compute (20.7
ms/step), so the precision lever deserves a re-measure.

Result (r5 chip round): 20.92 vs 21.38 ms/step — ~2%; at bs32/seq<=64/
d512 the per-step matmuls are latency-bound, not precision-bound, so the
bench keeps f32 (better numerics at no cost).

  python experiments/nmt_bf16_ab_r05.py [rounds] [iters]
"""
from __future__ import annotations

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

K = 8
B = 32


def main():
    rounds = int(sys.argv[1]) if len(sys.argv) > 1 else 3
    iters = int(sys.argv[2]) if len(sys.argv) > 2 else 3
    from tools.bench_kit import make_nmt_dispatch
    from tools.opbench import interleave

    variants = {
        "f32": make_nmt_dispatch(K=K, b=B, dtype="float32")[0],
        "bf16": make_nmt_dispatch(K=K, b=B, dtype="bfloat16")[0],
    }
    stats = interleave(variants, rounds=rounds, iters=iters, warmup=1)
    for name, s in stats.items():
        per_step = s["best_ms"] / K
        print(f"{name:5s} best {per_step:7.2f} ms/step  "
              f"({B/per_step*1e3:6.0f} seqs/s)  spread {s['spread_pct']}%")


if __name__ == "__main__":
    main()
