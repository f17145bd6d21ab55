"""Set-up as a timeline (PR 36): what the caller's thread did from process start
to the window's first step, from the program's own spans and the JAX compiles
its monitor observed.

    python3 -m benchmark.setup_timeline --workload <cell> --seed <n> --seconds <s>

runs the cell in this process as `benchmark.run --trace 1` does (its lines come
first, its result line included), then prints one more line, `{"info":
"setup_timeline", ...}`:

  * `timeline`: the caller's thread in order, `[what, offset_s, seconds,
    detail]`: every ROOT span (a span with no span above it: `program.build`,
    `program.clone`, `executor.prepare`, `executor.run`, `pipeline.dispatch`,
    ...) with the program it belongs to and how its time splits into the parts
    below, and every GAP between them (`gap`; the stretch before
    `monitor.enable()` is `gap:before_enable`) with the observed `jax.*` events
    that fell in it: seconds by kind and the longest few by function.  A gap
    with `jax.backend_compile` in it is a `jax.jit` of the caller's; a gap with
    nothing in it is Python or numpy of the caller's;
  * `parts`: the six numbers below, which add up to `setup_s`;
  * `by_program`: seconds by program id and part;
  * `inside_executor`: seconds of `executor.lower` and of `executor.compile`
    by the kind of observed event over them: a lowering's trace and its way
    to StableHLO; a compile's cache load inside its `jax.backend_compile`.

ONE partition of the interval, used by the five readers under
benchmark/metrics (`setup_program_build_s`, `setup_lower_s`, `setup_run_s`,
`setup_foreign_compile_s`, `setup_unattributed_share`).  Every instant of the
caller's thread belongs to the innermost program span open over it, and by
that span's name to a part:

  * `program_build_s`: `program.build`, `.backward`, `.optimize`, `.clone`;
  * `lower_s`: `executor.prepare`, `analysis.verify`, `analysis.plan`,
    `executor.build`, `executor.lower`: Python that no cache serves;
  * `compile_s`: `executor.compile`: XLA, or a load from the compile cache;
  * `run_s`: every other program span (`executor.execute`, `.fetch`,
    `.enqueue`, `pipeline.*`, ...): a self time, since `executor.lower` and
    `executor.compile` open under `executor.enqueue` at a step's first call;
  * `foreign_compile_s`: where an observed `jax.trace`, `jax.lower` or
    `jax.backend_compile` event (`jax.cache_load` lies inside the last) covers
    the instant and neither `executor.lower` nor `executor.compile` encloses
    it, wherever it fell: under another span (an eager `jnp` call under
    `executor.fetch`) or under none (the benchmark's reference and probe);
  * `unattributed_s`: under no span and no observed event: imports, the
    runtime coming up, numpy in the caller.

Set-up starts at `benchmark.run.T_PROCESS` and ends at the start of the
`pipeline.next_batch` span whose `step` is the traffic's `warmup_steps`; the
caller's thread is that span's.  Events carry `time.time()`, `T_PROCESS` is
`time.perf_counter()`: the monitor's `enabled_at` holds both clocks at
`monitor.enable()`.  A program without it (the parent of PR 36) gives None
everywhere and the metrics are left out of the line.

The arithmetic works on plain tuples, `program_trace`'s: a monitor event is
(name, ts_s, dur_s, tid, depth, args, id, parent's id).
"""
from __future__ import annotations

from benchmark import run as bench_run  # first: its import stamps T_PROCESS

import json
import sys
from collections import defaultdict
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

from benchmark import manifest as mf
from benchmark import program_trace as pt
from benchmark import trace_reduce as tr

BUILD = "program_build_s"
LOWER = "lower_s"
COMPILE = "compile_s"
RUN = "run_s"
FOREIGN = "foreign_compile_s"
UNATTRIBUTED = "unattributed_s"
PARTS = (BUILD, LOWER, COMPILE, RUN, FOREIGN, UNATTRIBUTED)

PART_OF = {
    "program.build": BUILD, "program.backward": BUILD,
    "program.optimize": BUILD, "program.clone": BUILD,
    "executor.prepare": LOWER, "analysis.verify": LOWER,
    "analysis.plan": LOWER, "executor.build": LOWER, "executor.lower": LOWER,
    "executor.compile": COMPILE,
}
# an observed JAX event inside one of these is the executor's own
ENCLOSING = ("executor.lower", "executor.compile")
OBSERVED = "jax."
GAP = "gap"
BEFORE_ENABLE = "gap:before_enable"
SHORT_GAP_S = 1e-3  # a shorter gap between two roots is left out of the list

# start, end, part, the root span over it, the innermost span over it
Piece = Tuple[float, float, str, Optional[pt.Span], Optional[pt.Span]]


def process_start() -> float:
    """`T_PROCESS` of the `benchmark.run` that is running: under `python3 -m
    benchmark.run` that module is `__main__`, and the one the runners import
    by name is a second, later copy."""
    main = sys.modules.get("__main__")
    if getattr(getattr(main, "__spec__", None), "name", None) == "benchmark.run":
        return main.T_PROCESS
    return bench_run.T_PROCESS


def setup_interval(spans: Sequence[pt.Span], first_step: int,
                   enabled_at: Tuple[float, float], t_process: float
                   ) -> Optional[Tuple[float, float, int]]:
    """(process start, the window's first pull from the loader, the caller's
    thread), the times on the events' clock; None without that pull."""
    pulls = [s for s in spans
             if s.name == pt.NEXT_BATCH and s.args.get("step") == first_step]
    if not pulls:
        return None
    wall, perf = enabled_at
    return (wall - (perf - t_process), pulls[0].start, pulls[0].tid)


def observed(spans: Iterable[pt.Span]) -> List[pt.Span]:
    return [s for s in spans if s.name.startswith(OBSERVED)]


def partition(spans: Sequence[pt.Span], lo: float, hi: float) -> List[Piece]:
    """[lo, hi] of ONE thread cut into pieces in order, each with its part and
    the root and the innermost span it lies under (None in a gap).  A child
    is held to its parent's interval and to the end of the sibling before it,
    so the pieces cover the interval once whatever the clocks' rounding did."""
    real = [s for s in spans if not s.name.startswith(OBSERVED)]
    ids = {s.id for s in real}
    below: Dict[int, list] = defaultdict(list)
    for s in real:
        below[s.parent if s.parent in ids else 0].append(s)
    compiling = tr.union(tr.clip(
        [(s.start, s.end) for s in observed(spans)], lo, hi))
    out: List[Piece] = []

    def own(a: float, b: float, part: str, root, span, sealed: bool) -> None:
        """[a, b] is `span`'s own (or a gap's): what an observed JAX event
        covers of it is foreign unless the executor's span encloses it."""
        if b <= a:
            return
        if sealed:
            out.append((a, b, part, root, span))
            return
        at = a
        for s, e in tr.clip(compiling, a, b):
            if s > at:
                out.append((at, s, part, root, span))
            out.append((s, e, FOREIGN, root, span))
            at = e
        if at < b:
            out.append((at, b, part, root, span))

    def walk(span, a: float, b: float, part: str, root, sealed: bool) -> None:
        at = a
        for c in sorted(below[span.id if span else 0],
                        key=lambda s: (s.start, -s.end)):
            cs, ce = max(c.start, at), min(c.end, b)
            if ce <= cs:
                continue
            own(at, cs, part, root, span, sealed)
            walk(c, cs, ce, part if sealed else PART_OF.get(c.name, RUN),
                 root or c, sealed or c.name in ENCLOSING)
            at = ce
        own(at, b, part, root, span, sealed)

    walk(None, lo, hi, UNATTRIBUTED, None, False)
    return out


def parts_of(pieces: Iterable[Piece]) -> Dict[str, float]:
    out = dict.fromkeys(PARTS, 0.0)
    for s, e, part, _, _ in pieces:
        out[part] += e - s
    return out


def _merged_by_kind(events: Iterable[pt.Span]) -> Dict[str, list]:
    """{kind of observed event: its intervals merged}: a kind's nested
    events (a `jit` traced inside a `jit`) count once."""
    by_kind: Dict[str, list] = defaultdict(list)
    for s in events:
        by_kind[s.name].append((s.start, s.end))
    return {k: tr.union(v) for k, v in sorted(by_kind.items())}


def _jax_in(events: Sequence[pt.Span], a: float, b: float, top: int = 4) -> dict:
    """The observed events that overlap [a, b]: seconds by kind and the
    longest few."""
    inside = [s for s in events if s.end > a and s.start < b]
    longest = sorted(inside, key=lambda s: s.start - s.end)[:top]
    return {"seconds": {k: tr.total(tr.clip(v, a, b))
                        for k, v in _merged_by_kind(inside).items()},
            "longest": [[s.name, s.args.get("fun_name"), s.end - s.start]
                        for s in longest]}


def timeline_of(pieces: Sequence[Piece], events: Sequence[pt.Span], lo: float,
                enabled_wall: float) -> list:
    """The pieces as the ordered list of root spans and gaps."""
    rows: list = []
    for s, e, part, root, _ in pieces:
        key = root.id if root is not None else None
        if rows and rows[-1]["key"] == key:
            rows[-1]["end"] = e
        else:
            rows.append({"key": key, "root": root, "start": s, "end": e,
                         "parts": defaultdict(float)})
        rows[-1]["parts"][part] += e - s
    out = []
    for r in rows:
        s, e, root = r["start"], r["end"], r["root"]
        if root is not None:
            detail = {k: root.args[k] for k in ("program", "source", "module", "step")
                      if k in root.args}
            detail["parts"] = dict(r["parts"])
            out.append([root.name, s - lo, e - s, detail])
            continue
        if e - s < SHORT_GAP_S:
            continue
        for a, b, what in ((s, min(e, enabled_wall), BEFORE_ENABLE),
                           (max(s, enabled_wall), e, GAP)):
            if b > a:
                out.append([what, a - lo, b - a, _jax_in(events, a, b)])
    return out


def inside_executor(pieces: Iterable[Piece], events: Sequence[pt.Span]) -> dict:
    """{`executor.lower` | `executor.compile`: {kind of observed event:
    seconds of the span's pieces it covers}}: how much of a lowering was
    JAX's trace and how much the way to StableHLO; how much of a compile was
    a load from the cache (`jax.cache_load` lies INSIDE `jax.backend_compile`:
    the difference is XLA)."""
    merged = _merged_by_kind(events)
    out: dict = {name: defaultdict(float) for name in ENCLOSING}
    for a, b, _, _, span in pieces:
        if span is not None and span.name in ENCLOSING:
            for kind, intervals in merged.items():
                out[span.name][kind] += tr.total(tr.clip(intervals, a, b))
    return {name: {k: v for k, v in kinds.items() if v}
            for name, kinds in out.items()}


def by_program(pieces: Iterable[Piece]) -> dict:
    """{program id: {part: seconds}} by the innermost span's `program`: a
    step's first call lowers and compiles under `pipeline.dispatch`, which
    carries a step and no program."""
    out: dict = defaultdict(lambda: defaultdict(float))
    for s, e, part, _, span in pieces:
        if span is not None and "program" in span.args:
            out[span.args["program"]][part] += e - s
    return {k: dict(v) for k, v in out.items()}


def setup_pieces(events: Iterable[tuple], first_step: int,
                 enabled_at: Tuple[float, float], t_process: float):
    """(pieces, the caller's observed events, lo, hi) of the set-up, or None
    where the window's first step cannot be found."""
    spans = pt.spans_of(events)
    found = setup_interval(spans, first_step, enabled_at, t_process)
    if found is None:
        return None
    lo, hi, tid = found
    mine = [s for s in spans if s.tid == tid and s.end > lo and s.start < hi]
    return partition(mine, lo, hi), observed(mine), lo, hi


def this_runs_setup(traffic: dict):
    """`setup_pieces` of the run in this process, with the number of events
    its monitor holds and the wall time of `enable()`; None where the program
    has no stamp, the traffic no warm-up steps or the window no first step."""
    first = traffic.get("warmup_steps")
    mon = pt.program_monitor()
    stamp = getattr(mon, "enabled_at", None)
    if first is None or stamp is None:
        return None
    seen = mon.events()
    found = setup_pieces(seen, first, stamp, process_start())
    return found + (len(seen), stamp[0]) if found else None


def read_metric(ctx: dict, name: str) -> Optional[float]:
    """What a reader under benchmark/metrics calls: one of PARTS in seconds,
    or `unattributed_share`, % of the line's `setup_s` that no part but the
    last holds (so the parts and it add up to `setup_s`)."""
    found = this_runs_setup(ctx["traffic"])
    if found is None:
        return None
    parts = parts_of(found[0])
    if name != "unattributed_share":
        return parts[name]
    setup_s = ctx["end_to_end"].get("setup_s")
    if not setup_s:
        return None
    held = sum(v for k, v in parts.items() if k != UNATTRIBUTED)
    return 100.0 * (setup_s - held) / setup_s


def report(traffic: dict) -> Optional[dict]:
    found = this_runs_setup(traffic)
    if found is None:
        return None
    pieces, events, lo, hi, n_events, enabled_wall = found
    # `monitor_events` at the monitor's EVENT_CAP (200000) means later events
    # were dropped and the timeline's end is not to be trusted
    return {"interval_s": hi - lo, "monitor_events": n_events,
            "parts": parts_of(pieces),
            "by_program": by_program(pieces),
            "inside_executor": inside_executor(pieces, events),
            "timeline": timeline_of(pieces, events, lo, enabled_wall)}


def main(argv=None) -> Optional[dict]:
    argv = list(sys.argv[1:] if argv is None else argv) + ["--trace", "1"]
    bench_run.main(argv)
    manifest = mf.load()
    cell = mf.cell(manifest, argv[argv.index("--workload") + 1])
    found = report(mf.read_json(mf.traffic_path(cell["traffic"])))
    print(json.dumps({"info": "setup_timeline", **(found or {})}, default=float),
          flush=True)
    return found


if __name__ == "__main__":
    main()
