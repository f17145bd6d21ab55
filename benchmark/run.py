"""One run of one cell of BENCHMARK.json, in a new process, on the cell's chips.

    python3 -m benchmark.run --workload <name> --seed <n> --seconds <s> --trace <0|1>

The last line of the standard output is one JSON object with `correct`,
`attempted`, `failed`, `metrics` and `device`: with `--trace 0` the cell's
end-to-end metrics, with `--trace 1` (monitor on, a profiler trace over the
end of the window) its per-layer metrics and a `breakdown`.  Earlier lines,
each a JSON object with an `info` key, carry what else is worth a number.

There is no mode for a machine without the chips: the run exits non-zero
and prints no result when JAX finds another platform than `PLATFORM` or
fewer chips than the cell asks for.  tests/benchmark rehearses the cells
tiny on the CPU by overriding `PLATFORM` and the sizes FROM THE TEST.

The harness is driven by data: the cell names a configuration file (which
names its model module under benchmark/models) and a traffic file (which
names its `kind`, the runner under benchmark/runners); every per-layer
metric is a reader under benchmark/metrics found by the metric's name.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()  # set-up is counted from here

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
from dataclasses import dataclass, field  # noqa: E402
from typing import Optional  # noqa: E402

from benchmark import manifest as mf  # noqa: E402
from benchmark import trace_reduce  # noqa: E402
from benchmark.peaks import peaks_for  # noqa: E402

PLATFORM = "tpu"
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def info(what: str, **fields) -> None:
    print(json.dumps({"info": what, **fields}, default=float), flush=True)


class CompileCounter:
    """Backend compiles as `jax.monitoring` reports them, with their time:
    the benchmark's own count, so that "no compile inside the window" holds
    with the program's monitor off.  A program loaded from the persistent
    cache fires the same event."""

    def __init__(self):
        import jax.monitoring

        self.times: list = []
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_):
        if event == COMPILE_EVENT:
            self.times.append(time.perf_counter())

    def count_between(self, t0: float, t1: float) -> int:
        return sum(t0 <= t <= t1 for t in self.times)


@dataclass
class Run:
    """What a runner is given."""
    cell: dict
    config: dict
    traffic: dict
    model: object
    seed: int
    seconds: float
    trace: bool
    devices: list
    compiles: CompileCounter
    trace_dir: str
    t_process: float = T_PROCESS


@dataclass
class Outcome:
    """What a runner hands back."""
    correct: bool
    attempted: int
    failed: int
    end_to_end: dict                  # metric name -> value
    stats: dict                       # the runner's own numbers, for readers
    window: tuple                     # (t0, t1) on time.perf_counter
    executables: list = field(default_factory=list)  # compiled steps that ran in the window
    scope_of: dict = field(default_factory=dict)     # HLO instruction -> op scope
    monitor_delta: dict = field(default_factory=dict)
    reasons: list = field(default_factory=list)      # why `correct` is false


def require_chips(n_chips: int) -> list:
    import jax

    devs = jax.devices()
    if devs[0].platform != PLATFORM or len(devs) < n_chips:
        raise SystemExit(
            f"benchmark: needs {n_chips} {PLATFORM} chip(s); JAX found "
            f"{len(devs)} {devs[0].platform!r} device(s) "
            f"({devs[0].device_kind}).  Nothing was run: there is no "
            f"fallback to another backend.")
    return devs[:n_chips]


def executable_bytes(executable) -> int:
    """Peak device bytes of one compiled program as XLA planned it:
    arguments + outputs - aliased + temporaries.  The runtime's
    `peak_bytes_in_use` does not see the temporaries (PR 21)."""
    m = executable.memory_analysis()
    return int(m.argument_size_in_bytes + m.output_size_in_bytes
               - m.alias_size_in_bytes + m.temp_size_in_bytes)


def memory_peak_bytes(devices, executables) -> int:
    """The peak on the fullest chip: the larger of what the runtime counted
    and what the largest program that ran was planned to take."""
    seen = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in devices]
    planned = [executable_bytes(e) for e in executables]
    return int(max(seen + planned + [0]))


def newest_xplane(trace_dir: str) -> Optional[str]:
    found = []
    for base, _, files in os.walk(trace_dir):
        found += [os.path.join(base, f) for f in files if f.endswith(".xplane.pb")]
    return max(found, key=os.path.getmtime) if found else None


def per_layer_metrics(manifest: dict, run: Run, out: Outcome, reduced: dict,
                      peaks: dict) -> dict:
    """Every per-layer metric of the cell whose reader finds something to
    read; a reader that returns None leaves its metric out of the line."""
    ctx = {"trace": reduced, "stats": out.stats, "monitor": out.monitor_delta,
           "end_to_end": out.end_to_end, "executables": out.executables,
           "config": run.config, "traffic": run.traffic, "cell": run.cell,
           "model": run.model, "peaks": peaks}
    metrics = {}
    for m in mf.metrics_of(manifest, run.cell["name"], "per_layer"):
        value = mf.reader_module(m["name"]).read(ctx)
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return metrics


def main(argv=None, root: str = mf.ROOT) -> dict:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    manifest = mf.load(root)
    cell = mf.cell(manifest, args.workload)
    config = mf.config_of(manifest, cell, root)
    traffic = mf.read_json(mf.traffic_path(cell["traffic"]), root)
    devices = require_chips(cell["chips"])
    peaks = peaks_for(devices[0].device_kind)  # an unknown device ends here

    from paddle_tpu import monitor
    from paddle_tpu.flags import apply_compile_cache

    # JAX_COMPILATION_CACHE_DIR where it is set, else a fixed directory in
    # the checkout: only a cell's first run there compiles
    cache = apply_compile_cache(os.path.join(root, ".jax_cache"))
    compiles = CompileCounter()
    if args.trace:
        monitor.reset()
        monitor.enable()
    info("start", workload=cell["name"], seed=args.seed, seconds=args.seconds,
         trace=args.trace, device_kind=devices[0].device_kind,
         chips=len(devices), compile_cache=cache)

    trace_dir = os.path.join(root, ".bench_trace", cell["name"])
    run = Run(cell=cell, config=config, traffic=traffic,
              model=mf.model_module(config), seed=args.seed,
              seconds=args.seconds, trace=bool(args.trace), devices=devices,
              compiles=compiles, trace_dir=trace_dir)
    out: Outcome = mf.runner_module(traffic).run(run)

    in_window = compiles.count_between(*out.window)
    if in_window:
        out.correct = False
        out.reasons.append(f"{in_window} backend compile(s) inside the window")
    out.stats["compiles_in_window"] = in_window
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices),
              "memory_peak_bytes": memory_peak_bytes(devices, out.executables)}
    result = {"correct": bool(out.correct), "attempted": int(out.attempted),
              "failed": int(out.failed)}
    if args.trace:
        pb = newest_xplane(trace_dir)
        reduced = (trace_reduce.reduce_trace(trace_reduce.load_xplane(pb),
                                             out.scope_of) if pb else {})
        if reduced.get("devices"):
            device["busy_s"] = reduced["busy_s"]
            device["window_s"] = reduced["window_s"]
        result["metrics"] = per_layer_metrics(manifest, run, out, reduced, peaks)
        result["breakdown"] = trace_reduce.breakdown(reduced)
    else:
        result["metrics"] = {m["name"]: {"value": float(out.end_to_end[m["name"]]),
                                         "unit": m["unit"]}
                             for m in mf.metrics_of(manifest, cell["name"], "end_to_end")}
    result["device"] = device
    if out.reasons:
        info("not_correct", reasons=out.reasons)
    print(json.dumps(result), flush=True)
    return result


if __name__ == "__main__":
    main()
