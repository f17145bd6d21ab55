"""From a profiler trace to the numbers the per-layer metrics read.

What a TPU trace of this JAX (0.9.0, libtpu 0.0.34) holds, looked at by hand
in PR 22: one plane per chip named `/device:TPU:<n>` with the lines `Steps`,
`XLA Modules` (one event per executed program, named `jit_step(<hash>)`),
`XLA Ops` (one event per executed HLO instruction; the event's name is the
instruction's text, `%fusion.172 = ... fusion(...), kind=kOutput, calls=...`,
WITHOUT its metadata) and `Async XLA Ops` (copies, slices and collectives in
flight, from `-start` to `-done`); and one plane `/host:CPU` whose lines are
threads, where `jax.profiler.TraceAnnotation` events appear under their own
name.  Events carry `device_offset_ps` and `device_duration_ps` and NO
per-op flops or bytes, so a roofline share cannot come from the trace
alone, and no `op<idx>:<type>` scope reaches the trace: the scope of an
instruction is looked up by its name in the compiled program's text
(`scopes_from_hlo_text`).  Host and device events are on one clock.

The reduction works on plain tuples so that it can be tested on a trace
built by hand: a plane is (name, [(line name, [(event name, start_ns,
duration_ns), ...]), ...]).
"""
from __future__ import annotations

import re
from collections import defaultdict
from statistics import median
from typing import Dict, Iterable, List, Optional, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
WINDOW_ANNOTATION = "bench.traced_window"
ANNOTATION_PREFIX = "bench."
COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter",
               "collective-permute", "all-to-all", "collective-broadcast")
_OPCODE = re.compile(r"\b([a-z][a-z0-9\-]*)\(")
_SCOPE = re.compile(r"op\d+:([\w.]+)")
_HLO_LINE = re.compile(r'^\s*(?:ROOT )?%(\S+) = .*?metadata=\{[^}]*op_name="([^"]*)"')

Interval = Tuple[float, float]


def load_xplane(path: str) -> list:
    """The planes of an `.xplane.pb` file as plain tuples."""
    import jax

    return planes_of(jax.profiler.ProfileData.from_file(path))


def planes_of(profile_data) -> list:
    return [(plane.name,
             [(line.name, [(e.name, float(e.start_ns), float(e.duration_ns))
                           for e in line.events]) for line in plane.lines])
            for plane in profile_data.planes]


def scopes_from_hlo_text(text: str) -> Dict[str, str]:
    """{instruction name: `<op type>.fwd|bwd`} for every instruction of a
    compiled program whose metadata carries the lowering's
    `op<idx>:<type>` named scope."""
    out = {}
    for line in text.splitlines():
        m = _HLO_LINE.match(line)
        if not m:
            continue
        found = _SCOPE.findall(m.group(2))
        if found:
            way = "bwd" if "transpose(" in m.group(2) else "fwd"
            out[m.group(1)] = f"{found[-1]}.{way}"
    return out


def instruction_of(event_name: str) -> str:
    return event_name.split(" = ", 1)[0].strip().lstrip("%")


def opcode_of(event_name: str) -> str:
    """`fusion`, `convolution`, `all-reduce-start`, ... of an event named by
    its instruction's text; the name itself where it is not HLO text."""
    head, sep, rest = event_name.partition(" = ")
    m = _OPCODE.search(rest) if sep else None
    return m.group(1) if m else head.strip().lstrip("%")


def is_collective(opcode: str) -> bool:
    return opcode.startswith(COLLECTIVES)


def union(intervals: Iterable[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for s, e in sorted(i for i in intervals if i[1] > i[0]):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def clip(intervals: Iterable[Interval], lo: float, hi: float) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def total(intervals: Iterable[Interval]) -> float:
    return sum(e - s for s, e in intervals)


def subtract(a: List[Interval], b: List[Interval]) -> List[Interval]:
    """The parts of the merged intervals `a` that the merged `b` leaves
    uncovered."""
    out, j = [], 0
    for s, e in a:
        at = s
        while j < len(b) and b[j][1] <= at:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > at:
                out.append((at, b[k][0]))
            at = max(at, b[k][1])
            k += 1
        if at < e:
            out.append((at, e))
    return out


def _annotations(planes) -> List[Tuple[str, float, float]]:
    return [(name, s, s + d) for pname, lines in planes
            if not DEVICE_PLANE.match(pname)
            for _, events in lines for name, s, d in events
            if name.startswith(ANNOTATION_PREFIX)]


def _label_gaps(gaps: List[Interval], notes) -> Dict[str, float]:
    """Each idle gap goes to the benchmark's host annotation that overlaps
    it most (the shortest one on a tie, which is the innermost)."""
    notes = [n for n in notes if n[0] != WINDOW_ANNOTATION]
    by_label: Dict[str, float] = defaultdict(float)
    for s, e in gaps:
        best, best_key = "host:unannotated", (0.0, 0.0)
        for name, ns, ne in notes:
            over = min(e, ne) - max(s, ns)
            if over > 0 and (over, ns - ne) > best_key:
                best, best_key = name, (over, ns - ne)
        by_label[best] += e - s
    return by_label


def reduce_trace(planes, scope_of: Optional[Dict[str, str]] = None,
                 max_gaps: int = 4000) -> dict:
    """Busy and idle time, device time by label, collective time and the
    idle gaps by what the host was doing, per device and summed up.

    The window is the `bench.traced_window` annotation where the trace has
    one, else the extent of the device events.  All seconds.  `devices` is
    empty where the trace has no TPU plane (a CPU rehearsal): the readers
    then return nothing.
    """
    scope_of = scope_of or {}
    notes = _annotations(planes)
    window = next(((s, e) for n, s, e in notes if n == WINDOW_ANNOTATION), None)
    devices = []
    for pname, lines in planes:
        if not DEVICE_PLANE.match(pname):
            continue
        by_line = dict(lines)
        ops = by_line.get("XLA Ops") or by_line.get("XLA Modules") or []
        if not ops:
            continue
        lo, hi = window or (min(s for _, s, _ in ops),
                            max(s + d for _, s, d in ops))
        label_s: Dict[str, float] = defaultdict(float)
        scoped = 0.0
        work, waits = [], []
        for name, s, d in ops:
            cs, ce = max(s, lo), min(s + d, hi)
            if ce <= cs:
                continue
            opcode = opcode_of(name)
            scope = scope_of.get(instruction_of(name))
            if scope:
                scoped += ce - cs
            stem = re.sub(r"[.\d]+$", "", instruction_of(name))
            label_s[scope or f"{opcode}:{stem}"] += ce - cs
            (waits if is_collective(opcode) else work).append((cs, ce))
        in_flight = clip([(s, s + d) for name, s, d in by_line.get("Async XLA Ops", [])
                          if is_collective(opcode_of(name))], lo, hi)
        work_u = union(work)
        coll_u = union(waits + in_flight)
        busy_u = union(work + waits)
        gaps = subtract([(lo, hi)], busy_u)
        modules: Dict[str, float] = defaultdict(float)
        for name, s, d in by_line.get("XLA Modules", []):
            if d > 0:
                modules[name] += max(0.0, min(s + d, hi) - max(s, lo)) / d
        longest = sorted(gaps, key=lambda g: g[0] - g[1])[:max_gaps]
        devices.append({
            "name": pname, "window_s": (hi - lo) / 1e9,
            "busy_s": total(busy_u) / 1e9,
            "op_time_s": sum(label_s.values()) / 1e9,
            "scoped_s": scoped / 1e9,
            "collective_s": total(coll_u) / 1e9,
            "collective_exposed_s": total(subtract(coll_u, work_u)) / 1e9,
            "by_label": {k: v / 1e9 for k, v in label_s.items()},
            "idle_gaps": {k: v / 1e9 for k, v in
                          _label_gaps(longest, notes).items()},
            "module_runs": dict(modules),
        })
    out = {"devices": devices, "n_annotations": len(notes)}
    if devices:
        for key in ("window_s", "busy_s"):
            out[key] = sum(d[key] for d in devices) / len(devices)
        for key, part, whole in (
                ("idle_share", "busy_s", "window_s"),
                ("scoped_share", "scoped_s", "op_time_s"),
                ("collective_share", "collective_s", "window_s"),
                ("collective_exposed_share", "collective_exposed_s", "window_s")):
            out[key] = median(d[part] / d[whole] if d[whole] else 0.0
                              for d in devices)
        out["idle_share"] = 1.0 - out["idle_share"]
        labels: Dict[str, float] = defaultdict(float)
        for d in devices:
            for k, v in d["by_label"].items():
                labels[k] += v / len(devices)
        out["by_label"] = dict(labels)
        out["idle_gaps"] = devices[0]["idle_gaps"]
        runs = max(devices[0]["module_runs"].items(), key=lambda kv: kv[1],
                   default=(None, 0.0))
        out["main_module"], out["main_module_runs"] = runs
    return out


def breakdown(reduced: dict, n: int = 10) -> dict:
    """The `breakdown` of a traced run's last line: the device operations
    that took most time and the longest idle gaps by what the host was
    doing, seconds, at most `n` each."""
    def top(d):
        return [[k, v] for k, v in
                sorted(d.items(), key=lambda kv: -kv[1])[:n]]

    return {"device_ops": top(reduced.get("by_label", {})),
            "idle_gaps": top(reduced.get("idle_gaps", {}))}
