"""Aggregate a JAX trace.json.gz by HLO category and by source line, with
achieved FLOP/s and bytes/s per bucket (the trace events carry
model_flops, bytes_accessed, device_duration_ps and my python `source`).

  python experiments/trace_summary.py <trace.json.gz> <n_steps> [top]
"""
from __future__ import annotations

import gzip
import json
import re
import sys
from collections import defaultdict


def load(path):
    with gzip.open(path, "rt") as f:
        return json.load(f)["traceEvents"]


def summarize(path, n_steps, top=30):
    ev = load(path)
    by_cat = defaultdict(lambda: [0.0, 0.0, 0.0, 0])   # ms, flops, bytes, n
    by_src = defaultdict(lambda: [0.0, 0.0, 0.0, 0])
    by_name = defaultdict(lambda: [0.0, 0.0, 0.0, 0])
    total = 0.0
    for e in ev:
        if e.get("ph") != "X":
            continue
        a = e.get("args") or {}
        cat = a.get("hlo_category")
        if cat is None:
            continue  # not an XLA-op event
        dur_ms = float(a.get("device_duration_ps", 0)) / 1e9
        if cat in ("while",):  # outer loop double-counts its body
            continue
        flops = float(a.get("model_flops", 0) or 0)
        byt = float(a.get("bytes_accessed", 0) or 0)
        src = (a.get("source") or "?").split("/")[-1]
        name = re.sub(r"\.\d+", "", e.get("name", "?"))
        for d, key in ((by_cat, cat), (by_src, src), (by_name, name)):
            d[key][0] += dur_ms
            d[key][1] += flops
            d[key][2] += byt
            d[key][3] += 1
        total += dur_ms
    print(f"device time/step (excl. outer while): {total/n_steps:.3f} ms")

    def dump(d, title, k=top):
        print(f"\n== by {title} ==")
        print(f"{'ms/step':>9} {'%':>5} {'n/step':>7} {'TF/s':>7} {'GB/s':>7}  {title}")
        for key, (ms, fl, byt, n) in sorted(d.items(), key=lambda kv: -kv[1][0])[:k]:
            tfs = fl / (ms / 1e3) / 1e12 if ms else 0
            gbs = byt / (ms / 1e3) / 1e9 if ms else 0
            print(f"{ms/n_steps:9.3f} {ms/total*100:5.1f} {n/n_steps:7.1f} "
                  f"{tfs:7.1f} {gbs:7.1f}  {str(key)[:100]}")

    dump(by_cat, "hlo_category")
    dump(by_name, "op name")
    dump(by_src, "source line")


if __name__ == "__main__":
    path = sys.argv[1]
    n_steps = int(sys.argv[2])
    top = int(sys.argv[3]) if len(sys.argv) > 3 else 30
    summarize(path, n_steps, top)
