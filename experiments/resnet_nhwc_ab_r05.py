"""Round-5 NHWC vs NCHW whole-model A/B under the fused single-pass BN.

r3 measured whole-model NHWC neutral (2351 vs 2337 imgs/s) on the two-pass
BN lowering; r4 review asks the layout question to be closed on the current
config.  NHWC requires the conv7 stem (s2d rearrangement is NCHW-only), so
conv7 NCHW is included to separate stem effect from layout effect.

Result (r5 chip round): NCHW+s2d 104.07, NCHW+conv7 105.00, NHWC+conv7
104.35 ms/step — NHWC neutral for the third round; question closed.

  python experiments/resnet_nhwc_ab_r05.py [rounds] [iters]
"""
from __future__ import annotations

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

K = 4


def main():
    rounds = int(sys.argv[1]) if len(sys.argv) > 1 else 3
    iters = int(sys.argv[2]) if len(sys.argv) > 2 else 4
    from tools.bench_kit import make_resnet_dispatch
    from tools.opbench import interleave

    variants = {
        "nchw_s2d": make_resnet_dispatch(K=K, stem="space_to_depth")[0],
        "nchw_conv7": make_resnet_dispatch(K=K, stem="conv7")[0],
        "nhwc_conv7": make_resnet_dispatch(K=K, stem="conv7", data_format="NHWC")[0],
    }
    stats = interleave(variants, rounds=rounds, iters=iters, warmup=1)
    for name, s in stats.items():
        per_step = s["best_ms"] / K
        print(f"{name:11s} best {per_step:7.2f} ms/step  ({256/per_step*1e3:6.0f} imgs/s)  "
              f"spread {s['spread_pct']}%")


if __name__ == "__main__":
    main()
