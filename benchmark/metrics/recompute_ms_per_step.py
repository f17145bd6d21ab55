"""Device ms a step spends computing again, in backward, a forward it had
already computed: the instructions of the compiled step that JAX's
`checkpoint` put into its rematerialised computation (`rematted_computation`
in their `op_name` in `compiled.as_text()`), which is where the lowering of a
`layers.Repeat(recompute=True)` runs each pass's forward a second time.  Over
the main module's runs in the traced window, the median device.

Each event's OWN time: on the trace's `XLA Ops` line a `while` or
`conditional` event encloses the events of its body, and a loop lowered as a
scan is a `while`, so a sum of every event's duration counts the body twice
(PERF.md, section 7, first item).  `own_ms_per_run` takes from each event the
time of the events directly inside it.  The reader also prints, on an `info`
line `loop_device_time`, the step's whole own time and its split by the
lowering's op scope, forward, backward and recomputed apart: the hand sum the
cell's numbers are checked against.  Nothing where no instruction was
rematerialised."""
import json
import re
from collections import defaultdict
from statistics import median

from benchmark import program_trace
from benchmark import trace_reduce as tr
from benchmark.metrics import attention_roofline_share

LAYER = 'lowering (core/lowering.py)'
UNIT = 'ms'
BETTER = 'lower'
SOURCE = 'device_trace'
MOVES = 'train_samples_per_s'

SCOPE = "/rematted_computation/"
_OP_SCOPE = re.compile(r"op\d+:([\w.]+)")


def own_times(events, window) -> list:
    """[(instruction, own ns)] of one `XLA Ops` line's events (name, start,
    duration, ...) inside `window`: an event's duration less that of the events
    directly inside it."""
    lo, hi = window
    inside = sorted(((max(s, lo), min(s + d, hi), name) for name, s, d, *_ in events
                     if min(s + d, hi) > max(s, lo)), key=lambda e: (e[0], -e[1]))
    own, open_ = [], []          # open_: indices into `own` of the events that enclose the next one
    for start, end, name in inside:
        while open_ and own[open_[-1]][2] <= start:
            open_.pop()
        if open_:
            own[open_[-1]][1] -= end - start
        own.append([tr.instruction_of(name), end - start, end])
        open_.append(len(own) - 1)
    return [(name, max(ns, 0.0)) for name, ns, _ in own]


def own_ms_by_instruction(planes) -> dict:
    """{instruction: own device ms a run of the module that ran most}, the
    median device; empty without a traced window or device ops."""
    window = program_trace.traced_window(planes)
    devices = program_trace.device_ops(planes)
    if window is None or not devices:
        return {}
    lo, hi = window
    per_device = []
    for _, by_line in devices:
        ns = defaultdict(float)
        for name, own in own_times(by_line["XLA Ops"], window):
            ns[name] += own
        runs = defaultdict(float)
        for name, s, d, *_ in by_line.get("XLA Modules", []):
            if d > 0:
                runs[name] += max(0.0, min(s + d, hi) - max(s, lo)) / d
        most = max(runs.values(), default=0.0)
        if most > 0:
            per_device.append({k: v / 1e6 / most for k, v in ns.items()})
    if not per_device:
        return {}
    return {k: median(d.get(k, 0.0) for d in per_device) for k in set().union(*per_device)}


def op_names(text: str) -> dict:
    """{instruction: its `op_name`} of a compiled program's text (an instruction
    that runs over several lines carries it on its last: the splash kernels')."""
    found, name = {}, None
    for line in text.splitlines():
        first = attention_roofline_share._FIRST_LINE.match(line)
        if first:
            name = first.group(1)
        for op_name in attention_roofline_share._OP_NAME.findall(line) if name else ():
            found.setdefault(name, op_name)
    return found


def own_ms(ctx: dict):
    """({instruction: own ms a run}, {instruction: op_name}) of the run's trace
    and executables, or None without either."""
    if not ctx.get("executables"):
        return None
    planes = program_trace.traced_planes(ctx)
    spent = own_ms_by_instruction(planes) if planes else {}
    if not spent:
        return None
    names = {}
    for e in ctx["executables"]:
        names.update(op_names(e.as_text()))
    return spent, names


def split(spent: dict, names: dict) -> dict:
    """The step's own time by the lowering's innermost op scope, `.fwd` /
    `.bwd` / `.again` (recomputed) apart; what carries no scope under ``."""
    by_scope = defaultdict(float)
    for instruction, ms in spent.items():
        op_name = names.get(instruction, "")
        scopes = _OP_SCOPE.findall(op_name)
        way = "again" if SCOPE in op_name else "bwd" if "transpose(" in op_name else "fwd"
        by_scope[f"{scopes[-1]}.{way}" if scopes else ""] += ms
    return dict(by_scope)


def read(ctx: dict):
    found = own_ms(ctx)
    if found is None:
        return None
    spent, names = found
    again = {instruction for instruction, op_name in names.items() if SCOPE in op_name}
    if not again:
        return None
    by_scope = split(spent, names)
    print(json.dumps({"info": "loop_device_time", "own_ms_per_step": sum(spent.values()),
                      "recomputed_ms": sum(ms for i, ms in spent.items() if i in again),
                      "backward_ms": sum(ms for k, ms in by_scope.items() if k.endswith(".bwd")),
                      "by_scope": dict(sorted(by_scope.items(), key=lambda kv: -kv[1])[:40])}), flush=True)
    return sum(ms for i, ms in spent.items() if i in again)
