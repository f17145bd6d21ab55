"""Op/model micro-benchmark driver (reference role:
paddle/fluid/operators/benchmark/op_tester.cc:1 — a standalone per-op timing
tool fed by config files).

The TPU rebuild's version packages the interleaved-A/B methodology from
the r3 chip round into a reusable library + CLI:

  * variants are timed round-robin (A,B,A,B,...) so throughput drift hits
    every variant equally — single measurements showed +/-20% run-to-run
    variance on the r5 machine and are not evidence;
  * each round times a window of `iters` dispatches ended by one device
    sync; per-variant stats report best / median / spread over rounds.

Library use:

    from tools.opbench import interleave
    stats = interleave({"conv7": dispatch_a, "s2d": dispatch_b}, rounds=5)

CLI use (single-op timing through the real program/executor path):

    python tools/opbench.py --op relu --input X=256x1024 --grad
    python tools/opbench.py --op conv2d --input Input=64x64x56x56 \
        --input Filter=64x64x3x3 --attr strides=1,1 --attr paddings=1,1
"""
from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import time
import zlib

if __name__ == "__main__":  # `python tools/opbench.py` from the repo root
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from typing import Callable, Dict

import numpy as np


# --------------------------------------------------------------------------
# core: interleaved A/B timing
# --------------------------------------------------------------------------

def _sync(x):
    """Block until the dispatch's result is real (device->host copy)."""
    if isinstance(x, (list, tuple)):
        for v in x:
            _sync(v)
        return
    np.asarray(x)


def interleave(variants: Dict[str, Callable], rounds: int = 4, iters: int = 8,
               warmup: int = 2) -> Dict[str, dict]:
    """Time each zero-arg dispatch callable round-robin.

    Returns {name: {best_ms, median_ms, spread_pct, windows_ms}} where each
    window is (wall time of `iters` dispatches + one sync) / iters and
    spread_pct = (max-min)/median over windows.
    """
    order = list(variants.items())
    for name, fn in order:  # compile + warm every variant before timing any
        out = None
        for _ in range(warmup):
            out = fn()
        if out is not None:
            _sync(out)
    windows: Dict[str, list] = {name: [] for name, _ in order}
    for _ in range(rounds):
        for name, fn in order:
            t0 = time.perf_counter()
            for _ in range(iters):
                out = fn()
            _sync(out)
            windows[name].append((time.perf_counter() - t0) / iters)
    stats = {}
    for name, ws in windows.items():
        med = statistics.median(ws)
        stats[name] = {
            "best_ms": round(min(ws) * 1e3, 4),
            "median_ms": round(med * 1e3, 4),
            "spread_pct": round((max(ws) - min(ws)) / med * 100, 1),
            "windows_ms": [round(w * 1e3, 4) for w in ws],
        }
    return stats


# --------------------------------------------------------------------------
# per-op timing through the program/executor path
# --------------------------------------------------------------------------

def build_op_dispatch(op_type: str, inputs: Dict[str, np.ndarray],
                      attrs: dict | None = None, grad: bool = False,
                      place=None) -> Callable:
    """One-op program -> executor dispatch closure.

    With grad=True the op's (mean-reduced) first output is differentiated
    w.r.t. every floating input via append_backward, so the window times
    fwd+bwd — the shape that matters for training-path ops.
    """
    import paddle_tpu as fluid
    from paddle_tpu.core.program import Program, program_guard

    attrs = dict(attrs or {})
    prog, startup = Program(), Program()
    with program_guard(prog, startup):
        block = prog.global_block()
        in_io, feed = {}, {}
        for slot, arr in inputs.items():
            arr = np.asarray(arr)
            name = f"in_{slot}"
            block.create_var(name, shape=arr.shape, dtype=str(arr.dtype),
                             is_data=True)
            feed[name] = arr
            in_io[slot] = [name]
        fluid.core.registry.get_op_def(op_type)  # fail early on unknown op
        out_slots = _probe_output_slots(op_type)
        out_io = {}
        for slot in out_slots:
            v = block.create_var(f"out_{slot}")
            out_io[slot] = [v.name]
        block.append_op(op_type, inputs=in_io, outputs=out_io, attrs=attrs)
        fetch_name = out_io[out_slots[0]][0]
        if grad:
            loss = fluid.layers.mean(block.var(fetch_name))
            float_ins = [n for n, a in feed.items()
                         if np.issubdtype(a.dtype, np.floating)]
            grads = fluid.calc_gradient(loss, [block.var(n) for n in float_ins])
            fetch_list = [loss.name] + [g.name for g in grads if g is not None]
        else:
            fetch_list = [fetch_name]

    exe = fluid.Executor(place or fluid.TPUPlace(0))
    scope = fluid.Scope()
    exe.run(startup, scope=scope)

    def dispatch():
        return exe.run(prog, feed=feed, fetch_list=fetch_list, scope=scope,
                       return_numpy=False)

    return dispatch


_KNOWN_OUT_SLOTS = {
    # ops whose primary output slot is not "Out"
    "conv2d": ["Output"], "conv3d": ["Output"], "conv2d_transpose": ["Output"],
    "batch_norm": ["Y", "MeanOut", "VarianceOut", "SavedMean", "SavedVariance"],
    "layer_norm": ["Y", "Mean", "Variance"],
    "softmax_with_cross_entropy": ["Loss", "Softmax"],
    "cross_entropy": ["Y"], "matmul": ["Out"], "mul": ["Out"],
    "pool2d": ["Out"], "pool3d": ["Out"], "dropout": ["Out", "Mask"],
    "lrn": ["Out", "MidOut"], "maxout": ["Out"],
    "hinge_loss": ["Loss"], "log_loss": ["Loss"], "rank_loss": ["Out"],
    "huber_loss": ["Out", "Residual"], "kldiv_loss": ["Loss"],
    "warpctc": ["Loss", "WarpCTCGrad"], "topk": ["Out", "Indices"],
    "linear_chain_crf": ["TransitionExps", "Alpha", "EmissionExps",
                         "LogLikelihood"],
}


def _probe_output_slots(op_type: str):
    return _KNOWN_OUT_SLOTS.get(op_type, ["Out"])


# --------------------------------------------------------------------------
# CLI
# --------------------------------------------------------------------------

def _parse_input(spec: str):
    """X=64x3x224x224[:float32] -> (slot, random ndarray)."""
    slot, shape = spec.split("=", 1)
    dtype = "float32"
    if ":" in shape:
        shape, dtype = shape.rsplit(":", 1)
    dims = tuple(int(d) for d in shape.split("x"))
    rng = np.random.RandomState(zlib.crc32(slot.encode()) % (2**31))
    if np.issubdtype(np.dtype(dtype), np.integer):
        arr = rng.randint(0, 10, dims).astype(dtype)
    else:
        arr = rng.rand(*dims).astype(dtype)
    return slot, arr


def _parse_attr(spec: str):
    """k=v with v parsed as bool/int/float/int-list/str."""
    k, v = spec.split("=", 1)
    if v in ("true", "True"):
        return k, True
    if v in ("false", "False"):
        return k, False
    if "," in v:
        parts = v.split(",")
        try:
            return k, [int(x) for x in parts]
        except ValueError:
            try:
                return k, [float(x) for x in parts]
            except ValueError:
                return k, v
    try:
        return k, int(v)
    except ValueError:
        pass
    try:
        return k, float(v)
    except ValueError:
        return k, v


def main(argv=None):
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--op", default=None, help="registered op type")
    p.add_argument("--input", action="append", default=[],
                   metavar="SLOT=DIMxDIM[:dtype]")
    p.add_argument("--attr", action="append", default=[], metavar="K=V")
    p.add_argument("--grad", action="store_true",
                   help="time fwd+bwd (append_backward over mean of output)")
    p.add_argument("--cpu", action="store_true", help="run on CPUPlace")
    p.add_argument("--rounds", type=int, default=4)
    p.add_argument("--iters", type=int, default=8)
    args = p.parse_args(argv)

    if not args.op:
        p.error("--op is required")

    import paddle_tpu as fluid

    inputs = dict(_parse_input(s) for s in args.input)
    attrs = dict(_parse_attr(s) for s in args.attr)
    place = fluid.CPUPlace() if args.cpu else fluid.TPUPlace(0)
    dispatch = build_op_dispatch(args.op, inputs, attrs, grad=args.grad,
                                 place=place)
    stats = interleave({args.op: dispatch}, rounds=args.rounds,
                       iters=args.iters)
    rec = {"op": args.op, "grad": args.grad,
           "inputs": {k: list(v.shape) for k, v in inputs.items()},
           "attrs": attrs, **stats[args.op]}
    print(json.dumps(rec))


if __name__ == "__main__":
    main()
