"""The program's own spans, read for the per-layer metrics that look at the
train path from inside (PR 23): what the loop's thread and the loader's
producer thread did, and what the host was doing while the device idled.

Two sources, both the program's:

  * the monitor after the run (`monitor.get_monitor()`): span events with an
    id, their parent's id and the `step` their work belongs to, the
    `pipeline_step` records and the counters.  The window is cut by step:
    a span belongs to it when its `step` is `warmup_steps` or later, and the
    producer thread (whose spans carry `batch`, not `step`) from the start
    of that step's `pipeline.next_batch`.
  * the run's profiler trace itself, where every program span is also a
    `TraceAnnotation` on the `/host:CPU` plane, on the clock of the
    device's `XLA Ops`.  `benchmark.run` hands the readers the trace
    already reduced and without the events' `stats`, so this module finds
    the `.xplane.pb` under `.bench_trace/<cell>` of the checkout and reads
    it again; one written before this process's first `pipeline.dispatch`
    is another run's and is not used (nor is there one where a rehearsal
    keeps its traces in a scratch root this module cannot know).

Every function that reads a program without these spans (the parent of the
PR that added them) returns None and the metric is left out of the line.

The arithmetic works on plain tuples so that it is tested on events built by
hand: a monitor event is the monitor's own tuple (name, ts_s, dur_s, tid,
depth, args, id, parent's id); a plane is (name, [(line name, [(event name,
start_ns, duration_ns, stats), ...]), ...]), `trace_reduce`'s with the
stats added.
"""
from __future__ import annotations

import functools
import os
import re
from collections import defaultdict, namedtuple
from statistics import median
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

from benchmark import manifest as mf
from benchmark import trace_reduce as tr

Span = namedtuple("Span", "name start end tid args id parent")

NEXT_BATCH = "pipeline.next_batch"
DISPATCH = "pipeline.dispatch"
HOST_BLOCKED = "pipeline.host_blocked"
STAGE = "reader.stage"
UNATTRIBUTED = "unattributed"
SLOW_STEP = 1.5  # times the median step

Interval = Tuple[float, float]


# -- the monitor's events ----------------------------------------------------

def spans_of(events: Iterable[tuple]) -> List[Span]:
    """The monitor's events as spans; an event of a program whose spans have
    no id yet (six fields) gets 0 for its own and its parent's."""
    return [Span(e[0], e[1], e[1] + e[2], e[3], e[5] or {}, *(e[6:8] or (0, 0)))
            for e in events]


def self_times(spans: Sequence[Span]) -> Dict[int, float]:
    """{span id: its duration less what its children cover}."""
    covered: Dict[int, list] = defaultdict(list)
    for s in spans:
        covered[s.parent].append((s.start, s.end))
    return {s.id: (s.end - s.start)
            - tr.total(tr.clip(tr.union(covered[s.id]), s.start, s.end))
            for s in spans}


def from_step(spans: Sequence[Span], first_step: int) -> List[Span]:
    """The spans of the steps from `first_step` on, whatever their thread."""
    return [s for s in spans if s.args.get("step", -1) >= first_step]


def loop_window(spans: Sequence[Span], first_step: int) -> Optional[Interval]:
    """From the start of `first_step`'s pull from the loader to the end of
    the last thing the loop's thread did for a step: the loop's time in
    the window.  None where the loop has no `pipeline.next_batch` span."""
    mine = from_step(spans, first_step)
    pulls = [s for s in mine if s.name == NEXT_BATCH]
    if not pulls:
        return None
    tid = pulls[0].tid
    return (min(s.start for s in pulls),
            max(s.end for s in mine if s.tid == tid))


def loop_metrics(events: Iterable[tuple], first_step: int) -> dict:
    """`next_batch_wait_share` and `reader_stage_share` (% of the loop's
    time in the window) and `dispatch_ms_per_step`; a key is missing where
    its span is."""
    spans = spans_of(events)
    window = loop_window(spans, first_step)
    if window is None:
        return {}
    lo, hi = window
    mine = from_step(spans, first_step)
    out = {"next_batch_wait_share": 100.0 * sum(
        s.end - s.start for s in mine if s.name == NEXT_BATCH) / (hi - lo)}
    staged = [(s.start, s.end) for s in spans
              if s.name == STAGE and s.start >= lo]
    if staged:
        out["reader_stage_share"] = (
            100.0 * tr.total(tr.clip(tr.union(staged), lo, hi)) / (hi - lo))
    sent = [s.end - s.start for s in mine if s.name == DISPATCH]
    if sent:
        out["dispatch_ms_per_step"] = 1e3 * sum(sent) / len(sent)
    return out


def slow_step_share(records: Iterable[dict], first_step: int) -> Optional[float]:
    """% of the window's `pipeline_step` records whose wall time is over
    SLOW_STEP times their median."""
    walls = [r["t_step_wall_s"] for r in records
             if r.get("kind") == "pipeline_step"
             and r["pipeline_step"] >= first_step]
    if not walls:
        return None
    return 100.0 * sum(w > SLOW_STEP * median(walls) for w in walls) / len(walls)


def program_monitor():
    from paddle_tpu import monitor

    return monitor.get_monitor()


def read_loop_metric(ctx: dict, name: str) -> Optional[float]:
    """What a reader under benchmark/metrics calls."""
    first = ctx["traffic"].get("warmup_steps")
    if first is None:
        return None
    return loop_metrics(program_monitor().events(), first).get(name)


# -- the profiler trace -------------------------------------------------------

def planes_of(profile_data, names: frozenset) -> list:
    """The device planes' `XLA Ops` and `XLA Modules` lines, and of every
    other plane the events called one of `names`, these with their stats."""
    planes = []
    for plane in profile_data.planes:
        device = bool(tr.DEVICE_PLANE.match(plane.name))
        lines = []
        for line in plane.lines:
            if device:
                if line.name in ("XLA Ops", "XLA Modules"):
                    lines.append((line.name, [
                        (e.name, float(e.start_ns), float(e.duration_ns), {})
                        for e in line.events]))
                continue
            kept = [(e.name, float(e.start_ns), float(e.duration_ns),
                     dict(e.stats)) for e in line.events if e.name in names]
            if kept:
                lines.append((line.name, kept))
        planes.append((plane.name, lines))
    return planes


@functools.lru_cache(maxsize=1)
def _load(path: str, mtime: float, names: frozenset) -> list:
    import jax

    return planes_of(jax.profiler.ProfileData.from_file(path), names)


def find_trace(trace_dir: str, not_before: float) -> Optional[str]:
    """The newest `.xplane.pb` under `trace_dir` if it was written at or
    after `not_before` (seconds on `time.time`), else None."""
    found = [os.path.join(base, f) for base, _, files in os.walk(trace_dir)
             for f in files if f.endswith(".xplane.pb")]
    if not found:
        return None
    newest = max(found, key=os.path.getmtime)
    return newest if os.path.getmtime(newest) >= not_before else None


def traced_planes(ctx: dict) -> Optional[list]:
    """This run's trace with the program's spans in it, or None."""
    events = program_monitor().events()
    sent = [e[1] for e in events if e[0] == DISPATCH]
    if not sent or "name" not in ctx["cell"]:
        return None
    path = find_trace(os.path.join(mf.ROOT, ".bench_trace", ctx["cell"]["name"]),
                      min(sent))
    if path is None:
        return None
    names = frozenset({e[0] for e in events} | {tr.WINDOW_ANNOTATION})
    return _load(path, os.path.getmtime(path), names)


def host_lines(planes) -> List[list]:
    """One list of (name, start, end, stats) per host thread that has any;
    the loop's thread (the one that dispatches) first."""
    lines = [[(n, s, s + d, st) for n, s, d, st in events
              if n != tr.WINDOW_ANNOTATION]
             for pname, plines in planes if not tr.DEVICE_PLANE.match(pname)
             for _, events in plines]
    lines = [ln for ln in lines if ln]
    lines.sort(key=lambda ln: not any(e[0] == DISPATCH for e in ln))
    return lines


def traced_window(planes) -> Optional[Interval]:
    for pname, plines in planes:
        if tr.DEVICE_PLANE.match(pname):
            continue
        for _, events in plines:
            for n, s, d, _ in events:
                if n == tr.WINDOW_ANNOTATION:
                    return (s, s + d)
    return None


def device_ops(planes) -> List[Tuple[str, dict]]:
    """[(device plane, {line name: events})] of the planes that ran ops."""
    out = []
    for pname, plines in planes:
        if tr.DEVICE_PLANE.match(pname):
            by_line = dict(plines)
            if by_line.get("XLA Ops"):
                out.append((pname, by_line))
    return out


def idle_gaps(ops: list, window: Interval) -> List[Interval]:
    lo, hi = window
    busy = tr.union(tr.clip([(s, s + d) for _, s, d, _ in ops], lo, hi))
    return tr.subtract([(lo, hi)], busy)


def innermost(lines: List[list]) -> List[Tuple[float, float, str]]:
    """Time cut at every span boundary, each piece named for the span that
    wins it: a span of an earlier line (the loop's thread is the first)
    before one of a later line, and on a line the one that started last,
    which is the innermost.  Pieces no span covers are left out."""
    cuts = sorted({t for ln in lines for _, s, e, _ in ln for t in (s, e)})
    pieces = []
    for a, b in zip(cuts, cuts[1:]):
        for ln in lines:
            over = [(s, n) for n, s, e, _ in ln if s <= a and e >= b]
            if over:
                pieces.append((a, b, max(over)[1]))
                break
    return pieces


def attribute_gaps(gaps: List[Interval], lines: List[list]) -> Dict[str, float]:
    """{span name: time of the idle gaps that fell under it}; what fell
    under no span goes to UNATTRIBUTED."""
    by_name: Dict[str, float] = defaultdict(float)
    pieces = innermost(lines)
    j = 0
    for gs, ge in gaps:
        left = ge - gs
        while j < len(pieces) and pieces[j][1] <= gs:
            j += 1
        k = j
        while k < len(pieces) and pieces[k][0] < ge:
            over = min(ge, pieces[k][1]) - max(gs, pieces[k][0])
            if over > 0:
                by_name[pieces[k][2]] += over
                left -= over
            k += 1
        if left > 0:
            by_name[UNATTRIBUTED] += left
    return dict(by_name)


def loops_part(window: Interval, lines: List[list]) -> Interval:
    """`window` cut to where the loop's thread has spans.  The benchmark's
    `bench.traced_window` closes after `train_loop` has returned: the
    device's idle time in that tail (about a millisecond, most of what
    `device_idle_share` reads in a busy cell) is no span's and nobody's to
    repair.  A trace without a dispatching thread keeps the whole window."""
    loop = lines[0]
    if not any(e[0] == DISPATCH for e in loop):
        return window
    return (max(window[0], min(e[1] for e in loop)),
            min(window[1], max(e[2] for e in loop)))


def idle_attribution(planes) -> Optional[dict]:
    """Per device the idle time of the loop's part of the traced window by
    the program span the host was in, and from it the two shares, the
    median device's: `idle_host_active_share` (% of that time: idle outside
    the loop's `pipeline.host_blocked`) and `idle_unattributed_share` (% of
    the idle time under no program span).  None without a traced window, a
    device that ran ops or a program span in the trace."""
    window = traced_window(planes)
    lines = host_lines(planes)
    devices = device_ops(planes)
    if window is None or not lines or not devices:
        return None
    window = loops_part(window, lines)
    # what runs inside `host_blocked` (the fetch of a logged step) is the
    # innermost span of its gap and still the host waiting for the device
    blocked = tr.union((s, e) for n, s, e, _ in lines[0] if n == HOST_BLOCKED)
    per_device = []
    for _, by_line in devices:
        gaps = idle_gaps(by_line["XLA Ops"], window)
        by_name = attribute_gaps(gaps, lines)
        idle = tr.total(gaps)
        per_device.append({
            "by_span": by_name,
            "idle_host_active_share":
                100.0 * tr.total(tr.subtract(gaps, blocked)) / (window[1] - window[0]),
            "idle_unattributed_share":
                100.0 * by_name.get(UNATTRIBUTED, 0.0) / idle if idle else 0.0})
    out = {k: median(d[k] for d in per_device)
           for k in ("idle_host_active_share", "idle_unattributed_share")}
    out["by_span_s"] = {k: v / 1e9 for k, v in per_device[0]["by_span"].items()}
    out["window_s"] = (window[1] - window[0]) / 1e9
    return out


def read_idle_metric(ctx: dict, name: str) -> Optional[float]:
    planes = traced_planes(ctx)
    found = idle_attribution(planes) if planes else None
    return found[name] if found else None


# -- the step's phases ---------------------------------------------------------

_UPDATE = re.compile(r"(?:^|/)update/")
_FWD = re.compile(r"(?:^|/)(?:jvp\()?fwd\)?/")


def phase_of(op_name: str) -> Optional[str]:
    """`bwd` for a transpose JAX derived (recomputation under
    `memory_optimize` included), else `update` under the lowering's
    `update` scope, else `fwd` under its `fwd` scope, else None."""
    if "transpose(" in op_name:
        return "bwd"
    if _UPDATE.search(op_name):
        return "update"
    if _FWD.search(op_name):
        return "fwd"
    return None


def phases_from_hlo_text(text: str) -> Dict[str, str]:
    """{instruction name: phase} of a compiled program's text; empty for a
    program whose lowering marks no phase (no `fwd` scope anywhere), so
    that its transposes are not read as a backward phase on their own."""
    out = {}
    for line in text.splitlines():
        m = tr._HLO_LINE.match(line)  # (instruction name, op_name)
        phase = phase_of(m.group(2)) if m else None
        if phase:
            out[m.group(1)] = phase
    return out if "fwd" in out.values() else {}


def phase_ms_per_step(planes, phase_by_instruction: Dict[str, str]) -> Optional[dict]:
    """{`fwd`|`bwd`|`update`: device ms a run of the main module}, the
    median device: the time of the `XLA Ops` events in the traced window
    by their instruction's phase, over the runs of the module that ran
    most in it."""
    window = traced_window(planes)
    devices = device_ops(planes)
    if window is None or not devices or not phase_by_instruction:
        return None
    lo, hi = window
    per_device = []
    for _, by_line in devices:
        ns: Dict[str, float] = dict.fromkeys(("fwd", "bwd", "update"), 0.0)
        for name, s, d, _ in by_line["XLA Ops"]:
            phase = phase_by_instruction.get(tr.instruction_of(name))
            if phase:
                ns[phase] += max(0.0, min(s + d, hi) - max(s, lo))
        runs: Dict[str, float] = defaultdict(float)
        for name, s, d, _ in by_line.get("XLA Modules", []):
            if d > 0:
                runs[name] += max(0.0, min(s + d, hi) - max(s, lo)) / d
        most = max(runs.values(), default=0.0)
        if most > 0:
            per_device.append({k: v / 1e6 / most for k, v in ns.items()})
    if not per_device:
        return None
    return {k: median(d[k] for d in per_device) for k in per_device[0]}


@functools.lru_cache(maxsize=1)
def _phases(executables: tuple) -> Dict[str, str]:
    found: Dict[str, str] = {}
    for e in executables:
        found.update(phases_from_hlo_text(e.as_text()))
    return found


def read_phase_metric(ctx: dict, phase: str) -> Optional[float]:
    planes = traced_planes(ctx) if ctx["executables"] else None
    found = (phase_ms_per_step(planes, _phases(tuple(ctx["executables"])))
             if planes else None)
    return found[phase] if found else None
