"""Analytic single-chip roofline for the ResNet-50 bs256 bf16 train step.

Question (r5 review path): is the measured ~104 ms step near the memory
roofline, i.e. is the ≥20% MFU floor reachable by software at all on one
v5e?  Model: per conv layer, fwd+bwd cost = max(FLOP/peak, bytes/BW) with
the fusion structure the r5 profile shows XLA already achieving:

  fwd:  conv reads x_raw (normalize fused in) + weights, writes y_raw
        (stats fused as output reduction)  -> bytes = in + out
  bwd:  dgrad  reads dy, writes dx         -> in + out
        wgrad  reads x, dy                 -> 2 tensors
        BN/relu backward elementwise passes fused into the above reduce
        fusions (observed), but dy itself is produced by a residual/relu
        chain pass: counted via the elementwise section.

Elementwise extras: residual adds (read a,b, write out) fwd and the mirror
adds in bwd; optimizer update on 25.6M f32 params (read p,m,g, write p,m).

  python experiments/resnet_roofline.py [peak_TFs] [bw_GBs]
"""
from __future__ import annotations

import sys

PEAK = float(sys.argv[1]) * 1e12 if len(sys.argv) > 1 else 197e12
BW = float(sys.argv[2]) * 1e9 if len(sys.argv) > 2 else 750e9  # achieved stream BW
B = 256
BPE = 2  # bf16


def conv_layers():
    """(Cin, H, W, Cout, k, stride) for ResNet-50 with the s2d stem."""
    layers = [(12, 112, 112, 64, 4, 1)]  # s2d stem
    stages = [(64, 256, 56, 3), (128, 512, 28, 4), (256, 1024, 14, 6), (512, 2048, 7, 3)]
    cin = 64
    for cmid, cout, hw, blocks in stages:
        for b in range(blocks):
            stride = 2 if (b == 0 and hw != 56) else 1
            hin = hw * stride
            if b == 0:
                layers.append((cin, hin, hin, cout, 1, stride))  # shortcut
            layers.append((cin if b == 0 else cout, hin, hin, cmid, 1, stride))
            layers.append((cmid, hw, hw, cmid, 3, 1))
            layers.append((cmid, hw, hw, cout, 1, 1))
            cin = cout
    return layers


def main():
    tot_ms = 0.0
    tot_flop = 0.0
    rows = []
    for (cin, hin, win, cout, k, s) in conv_layers():
        hout, wout = hin // s, win // s
        flop = 2.0 * B * hout * wout * cin * cout * k * k
        x_bytes = B * cin * hin * win * BPE
        y_bytes = B * cout * hout * wout * BPE
        w_bytes = cin * cout * k * k * 4  # f32 master read (+bf16 convert, small)
        fwd = max(flop / PEAK, (x_bytes + y_bytes + w_bytes) / BW)
        dgrad = max(flop / PEAK, (y_bytes + x_bytes + w_bytes) / BW)
        wgrad = max(flop / PEAK, (x_bytes + y_bytes + w_bytes) / BW)
        ms = (fwd + dgrad + wgrad) * 1e3
        tot_ms += ms
        tot_flop += 3 * flop
        rows.append((f"{cin:4d}->{cout:4d} {k}x{k}/{s} @{hout:3d}", flop, ms))
    # residual adds: 16 adds over the block-output tensors, fwd (2r+1w) and
    # bwd relu'+split (~2 passes each over the same size)
    res_elems = B * (3 * 56 * 56 * 256 + 4 * 28 * 28 * 512 + 6 * 14 * 14 * 1024 + 3 * 7 * 7 * 2048)
    res_ms = (res_elems * BPE * (3 + 2)) / BW * 1e3
    # optimizer: momentum on 25.6M f32 params: read p,v,g write p,v
    opt_ms = (25.6e6 * 4 * 5) / BW * 1e3
    # loss/fc/pool tail ~1 ms (measured)
    tail_ms = 1.0
    total = tot_ms + res_ms + opt_ms + tail_ms
    print(f"conv fwd+bwd roofline: {tot_ms:7.2f} ms  ({tot_flop/1e12:.2f} TFLOP)")
    print(f"residual/relu elementwise: {res_ms:5.2f} ms")
    print(f"optimizer: {opt_ms:5.2f} ms   tail: {tail_ms:.1f} ms")
    print(f"TOTAL roofline step: {total:7.2f} ms -> {B/total*1e3:6.0f} imgs/s "
          f"-> MFU {B/total*1e3*3*4.089e9/PEAK*100:.1f}%")
    worst = sorted(rows, key=lambda r: -r[2])[:8]
    print("\nworst layers (ms fwd+bwd roofline):")
    for name, flop, ms in worst:
        print(f"  {name}  {ms:6.2f} ms  ({flop/1e9:6.1f} GF, "
              f"{flop/ms*1e3/1e12:5.1f} TF/s at roofline)")


if __name__ == "__main__":
    main()
