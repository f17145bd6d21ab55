"""Profile a bench-model train step on the real chip and print the top HLO
ops by self time (parsed from the trace.json.gz the JAX profiler emits).

Round-5 discovery: jax.profiler.trace gives per-op device times (earlier
rounds worked from cost_analysis alone).  This
replaces the framework-variant decomposition (r3 chip round) with ground
truth.  Dispatch construction is shared with bench.py via tools/bench_kit.

  python experiments/profile_model.py resnet50
  python experiments/profile_model.py bert
"""
from __future__ import annotations

import glob
import gzip
import json
import os
import re
import sys
import tempfile
from collections import defaultdict

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np


def profile_dispatch(dispatch, n_iters=6, label="model"):
    import jax

    for _ in range(2):
        out = dispatch()
    np.asarray(out[0])

    d = tempfile.mkdtemp(prefix=f"prof_{label}_")
    with jax.profiler.trace(d):
        for _ in range(n_iters):
            out = dispatch()
        np.asarray(out[0])
    traces = glob.glob(os.path.join(d, "**", "*.trace.json.gz"), recursive=True)
    if not traces:
        print("no trace produced; files:", glob.glob(d + "/**/*", recursive=True))
        return None
    return traces[0]


def summarize(trace_path, n_iters, steps_per_dispatch, top=40, merge_reps=True):
    """Aggregate device-lane event durations by (cleaned) op name."""
    with gzip.open(trace_path, "rt") as f:
        data = json.load(f)
    events = data.get("traceEvents", [])
    pid_names = {}
    tid_names = {}
    for e in events:
        if e.get("ph") == "M" and e.get("name") == "process_name":
            pid_names[e["pid"]] = e["args"].get("name", "")
        if e.get("ph") == "M" and e.get("name") == "thread_name":
            tid_names[(e["pid"], e["tid"])] = e["args"].get("name", "")
    device_pids = {pid for pid, n in pid_names.items()
                   if "TPU" in n or "/device" in n.lower()}
    if not device_pids:
        device_pids = set(pid_names)
    agg = defaultdict(float)
    count = defaultdict(int)
    total = 0.0
    for e in events:
        if e.get("ph") != "X" or e.get("pid") not in device_pids:
            continue
        lane = tid_names.get((e["pid"], e["tid"]), "")
        if "XLA Modules" in lane or "Steps" in lane:
            continue
        if "XLA Ops" not in lane and "TensorFlow Ops" not in lane and lane:
            continue
        name = e.get("name", "?")
        dur = e.get("dur", 0) / 1e3  # us -> ms
        if merge_reps:
            # strip .N suffixes and fusion numbering so repeated layers merge
            name = re.sub(r"\.\d+", "", name)
        agg[name] += dur
        count[name] += 1
        total += dur
    denom = n_iters * steps_per_dispatch
    rows = sorted(agg.items(), key=lambda kv: -kv[1])
    print(f"total device time/step: {total/denom:.3f} ms  ({len(agg)} distinct ops)")
    print(f"{'ms/step':>9}  {'%':>5}  {'n':>5}  name")
    for name, ms in rows[:top]:
        print(f"{ms/denom:9.3f}  {ms/total*100:5.1f}  {count[name]:5d}  {name[:110]}")
    return agg, total, denom


if __name__ == "__main__":
    from tools.bench_kit import make_bert_dispatch, make_resnet_dispatch

    which = sys.argv[1] if len(sys.argv) > 1 else "resnet50"
    n_iters = int(sys.argv[2]) if len(sys.argv) > 2 else 6
    if which == "resnet50":
        K = 4
        dispatch, _ = make_resnet_dispatch(K=K)
    elif which == "bert":
        K = 2
        dispatch, _ = make_bert_dispatch(K=K)
    else:
        raise SystemExit(f"unknown model {which}")
    path = profile_dispatch(dispatch, n_iters=n_iters, label=which)
    if path:
        print("trace:", path)
        summarize(path, n_iters, K)
