"""The view behind PERF.md's "where the time goes", from one traced run:

    python3 -m benchmark.timeline_report --workload <cell> --seed <n> --seconds <s>

runs the cell in this process exactly as `benchmark.run --trace 1` does (its
lines come first, its result line included), then prints one more line,
`{"info": "timeline", ...}`, read from the program's monitor and the run's
trace through `benchmark.program_trace`:

  * `setup_by_module`: per program the executor built, seconds in
    `executor.build`, `.lower` and `.compile`, and whether JAX's persistent
    cache served the compile;
  * `host_ms_per_step`: per span name of the loop's thread, mean total and
    self ms a step of the window (how `pipeline.dispatch` splits into
    `executor.feed_place` and `executor.enqueue`);
  * `reader_stage`: the producer thread's batches in the window;
  * `idle_by_span_s`: the device's idle time in the traced window by the
    program span the host was in;
  * `slow_steps`: the `pipeline_step` records over 1.5 times the median, each
    with the wall time of the step after it.

Like `benchmark.run` it has no mode for a machine without the chips.
"""
from __future__ import annotations

import json
import sys
from collections import defaultdict
from statistics import median

from benchmark import manifest as mf
from benchmark import program_trace as pt

SETUP_SPANS = ("executor.build", "executor.lower", "executor.compile")


def setup_by_module(events) -> dict:
    out: dict = defaultdict(dict)
    for s in pt.spans_of(events):
        if s.name in SETUP_SPANS and "module" in s.args:
            mod = out[s.args["module"]]
            key = s.name.split(".")[1] + "_s"
            mod[key] = mod.get(key, 0.0) + s.end - s.start
            if "cache_hit" in s.args:
                mod["cache_hit"] = bool(s.args["cache_hit"])
    return dict(out)


def host_ms_per_step(events, first_step: int) -> dict:
    """{span name: [mean ms a step, mean self ms a step]} over the steps of
    the window, for the spans of the loop's thread."""
    spans = pt.spans_of(events)
    own = pt.self_times(spans)
    mine = pt.from_step(spans, first_step)
    n_steps = len({s.args["step"] for s in mine if s.name == pt.DISPATCH})
    total: dict = defaultdict(lambda: [0.0, 0.0])
    for s in mine:
        total[s.name][0] += s.end - s.start
        total[s.name][1] += own[s.id]
    return {k: [1e3 * a / n_steps, 1e3 * b / n_steps]
            for k, (a, b) in sorted(total.items())} if n_steps else {}


def reader_stage(events, first_step: int) -> dict:
    spans = pt.spans_of(events)
    window = pt.loop_window(spans, first_step)
    staged = [s for s in spans if s.name == pt.STAGE and window
              and window[0] <= s.start <= window[1]]
    if not staged:
        return {}
    ms = sorted(1e3 * (s.end - s.start) for s in staged)
    return {"batches": len(ms), "ms_p50": median(ms), "ms_max": ms[-1],
            "bytes": staged[0].args.get("bytes")}


def slow_steps(records, first_step: int, n: int = 5) -> list:
    mine = [r for r in records if r.get("kind") == "pipeline_step"
            and r["pipeline_step"] >= first_step]
    if not mine:
        return []
    mid = median(r["t_step_wall_s"] for r in mine)
    keys = ("pipeline_step", "t_step_wall_s", "t_next_batch_s", "t_dispatch_s",
            "t_host_blocked_s", "inflight", "logged")
    # a completion the host noticed late is followed by a short step; a
    # step the device took long over is not
    after = {r["pipeline_step"] - 1: r["t_step_wall_s"] for r in mine}
    slow = [r for r in mine if r["t_step_wall_s"] > pt.SLOW_STEP * mid]
    return [{**{k: r.get(k) for k in keys},
             "t_next_step_wall_s": after.get(r["pipeline_step"])} for r in slow[:n]]


def timeline(cell: dict, traffic: dict) -> dict:
    mon = pt.program_monitor()
    events, first = mon.events(), traffic.get("warmup_steps", 0)
    planes = pt.traced_planes({"cell": cell})
    idle = pt.idle_attribution(planes) if planes else None
    return {"setup_by_module": setup_by_module(events),
            "host_ms_per_step": host_ms_per_step(events, first),
            "reader_stage": reader_stage(events, first),
            "idle_by_span_s": idle["by_span_s"] if idle else None,
            "slow_steps": slow_steps(mon.step_records(), first),
            "counters": {k: v for k, v in mon.counter_values().items()
                         if k.startswith(("executor.", "reader.", "lowering."))}}


def main(argv=None) -> dict:
    from benchmark import run

    argv = list(sys.argv[1:] if argv is None else argv) + ["--trace", "1"]
    run.main(argv)
    manifest = mf.load()
    cell = mf.cell(manifest, argv[argv.index("--workload") + 1])
    found = timeline(cell, mf.read_json(mf.traffic_path(cell["traffic"])))
    print(json.dumps({"info": "timeline", **found}, default=float), flush=True)
    return found


if __name__ == "__main__":
    main()
