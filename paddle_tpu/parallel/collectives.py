"""What a compiled step communicates: the collectives of a mesh program's
executable, read once from its text while the monitor is on.

GSPMD's gathers and reduces are in no program op and in no `lowering.*`
counter: the partitioner puts them in after the lowering, and the TPU's
compiler then hides most of them inside fusions.  `collectives_of` walks the
compiled module's text (`compiled.as_text()`: `runtime_executable()
.hlo_modules()[0].to_string()` is the same serialisation at the same cost,
~1 s for Jamba's 19 MB, and the Python bindings offer no walk of the module
itself) from its ENTRY computation down and finds every collective the step
runs; `publish` makes a span, two counters and one step record of them
(docs/observability.md, "What a step communicates").

What the walk takes for ONE collective: every instruction that carries one
`channel_id` (the TPU's compiler clones an `all-gather` into each fusion that
carries it: the start, the fusions that compute while it is in flight, the
done), or a `-start` and the `-done` that names it.  What it records of it:

  * `kind`: the opcode less `-start` / `-done`;
  * `participants` and `axis`: the size of a replica group and the mesh axes
    whose groups the replica groups are, `dp` or `dp,tp`; ids are positions
    in the executable's device assignment, which is the mesh's devices in
    order (`use_global_device_ids`); `?` where they match no axes;
  * `bytes`, BY ONE RULE FOR EVERY KIND: the whole array as one chip holds it
    at the collective's wide end, which is the instruction's result (after a
    gather, after an all-reduce, an all-to-all, a permute or a broadcast)
    and, for a `reduce-scatter`, its result times the participants (the array
    before the reduce).  A tuple's elements are summed.  It is what the
    program moves, not what the links carry: of a gather over n chips a chip
    receives (n - 1) / n of it;
  * `dtype`: the element type that holds most of those bytes;
  * `op`: the program op it serves as `<phase>:<op type>`, from the `op_name`
    of the collective's own instruction (the carrier's where it has none):
    the lowering's innermost `op<idx>:<type>` scope, `again` under a
    `rematted_computation`, `bwd` under a `transpose(`, `update` under the
    `update` scope, else `fwd`; `partitioner` where the metadata names no op;
  * `in_while` and `passes`: whether it stands in a `while` body, and the
    product of the enclosing loops' `known_trip_count`s (1 where the compiler
    states none): counts and bytes are a pass's times `passes`;
  * `instructions`: {name: role} of the instructions of the ENTRY computation
    and of the `while` bodies and `conditional` branches under it, which are
    what the device's `XLA Ops` line shows as events:
      `sync`    the collective itself, scheduled as one instruction;
      `start`   an `-start`, or a fusion rooted in `AsyncCollectiveStart`;
      `done`    its `-done`, or a fusion rooted in `AsyncCollectiveDone`: the
                core waits here for what is still in flight;
      `fused`   a fusion, call or custom call whose computation (followed to
                the end) holds the collective and IS it (the TPU's
                `all-reduce-scatter` fusions);
      `overlap` such an instruction between a start and its done (an
                `async_collective_fusion`): it computes while the collective
                goes on, and its time is the computation's.
"""
from __future__ import annotations

import itertools
import re
from collections import defaultdict
from typing import Dict, List, Optional

import numpy as np

from ..core.lowering import BY_OP_ROWS
from ..monitor import MONITOR as _MON

KINDS = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all", "collective-permute", "collective-broadcast")

_HEADER = re.compile(r"^(ENTRY )?%?([\w.\-]+) \(.*\{$")
_INSTRUCTION = re.compile(r"^  (ROOT )?%?([\w.\-]+) = ")
_OPCODE = re.compile(r"\b([a-z][a-z0-9\-]*)\(")
_CALLEE = re.compile(r"\b(calls|body|to_apply|called_computations|branch_computations|true_computation|false_computation)"
                     r"=\{?(%?[\w.\-]+(?:, ?%?[\w.\-]+)*)\}?")
_ARRAY = re.compile(r"\b([a-z]+\d*[a-z0-9]*)\[([\d,]*)\]")
_CHANNEL = re.compile(r"\bchannel_id=(\d+)")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_OP_SCOPE = re.compile(r"op\d+:([\w.]+)")
_UPDATE = re.compile(r"(?:^|/)update/")
_TRIPS = re.compile(r'"known_trip_count":\{"n":"(\d+)"\}')
_ASYNC_ROOT = re.compile(r'custom_call_target="\w*Collective(Start|Done)"')
_FIRST_OPERAND = re.compile(r"\(%?([\w.\-]+)")
_GROUPS_LIST = re.compile(r"\b(?:replica_groups|source_target_pairs)=\{((?:\{[\d,]*\},?)*)\}")
_GROUPS_IOTA = re.compile(r"\breplica_groups=\[(\d+),(\d+)\]<=\[([\d,]+)\](?:T\(([\d,]+)\))?")
_ITEMSIZE = {"pred": 1, "s4": 1, "u4": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "f16": 2, "bf16": 2, "s32": 4, "u32": 4,
             "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8, "c128": 16}


def kind_of(opcode: str) -> Optional[str]:
    """`all-gather` of `all-gather`, `all-gather-start`, `all-gather-done`; None of any other opcode."""
    for kind in KINDS:
        if opcode.startswith(kind) and opcode[len(kind):] in ("", "-start", "-done"):
            return kind
    return None


def op_of(op_name: str) -> str:
    """`<phase>:<op type>` of an instruction's `op_name`, `partitioner` where it names no op of the program."""
    scopes = _OP_SCOPE.findall(op_name)
    if not scopes:
        return "partitioner"
    phase = ("again" if "rematted_computation" in op_name else "bwd" if "transpose(" in op_name
             else "update" if _UPDATE.search(op_name) else "fwd")
    return f"{phase}:{scopes[-1]}"


def shape_bytes(shape: str, largest: bool = False) -> Dict[str, int]:
    """{element type: bytes} of an instruction's shape as the text has it, a tuple's elements summed; with
    `largest` its largest element alone."""
    elements = [(dtype, _ITEMSIZE.get(dtype, 1 if dtype.startswith("f8") else 0)
                 * int(np.prod([int(d) for d in dims.split(",") if d], dtype=np.int64)))
                for dtype, dims in _ARRAY.findall(shape)]
    if largest and elements:
        elements = [max(elements, key=lambda e: e[1])]
    held: Dict[str, int] = defaultdict(int)
    for dtype, size in elements:
        if size:
            held[dtype] += size
    return dict(held)


def _groups(line: str, devices: int) -> List[tuple]:
    """The replica groups of a collective's line (a permute's pairs) as tuples of ids; one group of every device
    where the line states none."""
    iota = _GROUPS_IOTA.search(line)
    if iota:
        n, size, dims, perm = iota.groups()
        ids = np.arange(int(np.prod([int(d) for d in dims.split(",")]))).reshape([int(d) for d in dims.split(",")])
        if perm:
            ids = ids.transpose([int(p) for p in perm.split(",")])
        return [tuple(int(i) for i in row) for row in ids.reshape(int(n), int(size))]
    listed = _GROUPS_LIST.search(line)
    found = [tuple(int(i) for i in g.split(",") if i) for g in re.findall(r"\{([\d,]*)\}", listed.group(1))] if listed else []
    return [g for g in found if g] or [tuple(range(devices))]


class _Axes:
    """The mesh's axes as partitions of the device assignment's positions, to name a collective's groups by."""

    def __init__(self, mesh):
        names, shape = tuple(mesh.axis_names), tuple(mesh.devices.shape)
        ids = np.arange(int(np.prod(shape))).reshape(shape)
        self.devices = ids.size
        self.by_label = []      # (label, {id: the group's number}), fewer axes first
        for n in range(1, len(names) + 1):
            for subset in itertools.combinations(range(len(names)), n):
                rest = [a for a in range(len(names)) if a not in subset]
                rows = ids.transpose(rest + list(subset)).reshape(-1, int(np.prod([shape[a] for a in subset])))
                self.by_label.append((",".join(names[a] for a in subset), rows.shape[1],
                                      {int(i): g for g, row in enumerate(rows) for i in row}))

    def name(self, groups: List[tuple], pairs: bool):
        """(participants, axis) of replica groups, or of a permute's source-target pairs: the fewest axes whose
        groups hold every pair, and the devices that send or receive."""
        size = len({i for g in groups for i in g}) if pairs else len(groups[0])
        for label, width, group_of in self.by_label:
            try:
                within = all(len({group_of[i] for i in g}) == 1 for g in groups)
            except KeyError:
                break
            if within and (pairs or (width == size and all(len(g) == size for g in groups))):
                return size, label
        return size, "?"


class _Computation:
    __slots__ = ("collectives", "calls", "role", "done_of")

    def __init__(self):
        self.collectives = []   # its own collective instructions, parsed: dicts
        self.calls = []         # (instruction, opcode, [called computations], op_name, trips)
        self.role = None        # `start` / `done`: it is rooted in the TPU's AsyncCollectiveStart / Done custom call
        self.done_of = {}       # a `-done` or `async-done` instruction -> the start it names


def _parse(text: str, axes: _Axes):
    """({computation: _Computation}, the ENTRY's name) of a compiled module's text.  An instruction whose text
    runs over several lines (a Mosaic kernel's) is read from its first."""
    computations: Dict[str, _Computation] = {}
    entry, at = None, None
    for line in text.split("\n"):
        if not line.startswith("  "):
            header = _HEADER.match(line)
            if header:
                at = computations[header.group(2)] = _Computation()
                if header.group(1):
                    entry = header.group(2)
            continue
        found = _INSTRUCTION.match(line) if at is not None else None
        if not found:
            continue
        rest = line[found.end():]
        code = _OPCODE.search(rest)
        if not code:
            continue
        name, opcode = found.group(2), code.group(1)
        kind = kind_of(opcode)
        if kind is not None:
            if opcode.endswith("-done"):
                at.done_of[name] = _FIRST_OPERAND.match(rest[code.end() - 1:]).group(1)
            # (a start's shape holds its operands beside its results, and a permute's two counters: its largest
            # element stands in until its done, whose shape is the result's, is met)
            held = shape_bytes(rest[:code.start()], largest=opcode.endswith("-start") and rest.startswith("("))
            channel = _CHANNEL.search(rest)
            meta = _OP_NAME.search(rest)
            groups = _groups(rest, axes.devices) if not opcode.endswith("-done") else None
            at.collectives.append({"name": name, "opcode": opcode, "kind": kind, "held": held,
                                   "channel": int(channel.group(1)) if channel else None,
                                   "op_name": meta.group(1) if meta else "", "groups": groups})
            continue
        if opcode == "async-done":
            at.done_of[name] = _FIRST_OPERAND.match(rest[code.end() - 1:]).group(1)
        if opcode == "custom-call":
            root = _ASYNC_ROOT.search(rest)
            if root:
                at.role = root.group(1).lower()
        # (a reduce's or a sort's `to_apply` is a scalar function: only a `call` runs a computation through it)
        callees = [c.strip().lstrip("%") for m in _CALLEE.finditer(rest) if m.group(1) != "to_apply" or opcode == "call"
                   for c in m.group(2).split(",")]
        if callees:
            meta = _OP_NAME.search(rest)
            trips = _TRIPS.search(rest) if opcode == "while" else None
            at.calls.append((name, opcode, callees, meta.group(1) if meta else "", int(trips.group(1)) if trips else 1))
    return computations, entry


def collectives_of(text: str, mesh) -> List[dict]:
    """Every collective a run of the compiled module makes, as the module's docstring lists them, in the order
    the walk from ENTRY meets them."""
    axes = _Axes(mesh)
    computations, entry = _parse(text, axes)
    inside_memo: Dict[str, list] = {}

    def inside(name: str) -> list:
        """The collective instructions of a computation and of everything it calls."""
        if name not in inside_memo:
            inside_memo[name] = []      # (a cycle cannot be, but a second visit must not recurse)
            at = computations.get(name)
            if at is not None:
                inside_memo[name] = at.collectives + [c for _, _, callees, _, _ in at.calls for callee in callees
                                                      for c in inside(callee)]
        return inside_memo[name]

    found: Dict[object, dict] = {}

    def meet(c: dict, where: str, instruction: str, role: str, op_name: str, in_while: bool, passes: int):
        key = ("channel", c["channel"]) if c["channel"] is not None else ("name", where, c["name"])
        one = found.get(key)
        if one is None:
            participants, axis = axes.name(c["groups"], pairs=c["kind"] == "collective-permute")
            one = found[key] = {"kind": c["kind"], "participants": participants, "axis": axis, "held": c["held"],
                                "op": op_of(c["op_name"] or op_name), "in_while": in_while, "passes": passes,
                                "instructions": {}}
        one["instructions"].setdefault(instruction, role)
        return one

    def walk(name: str, in_while: bool, passes: int):
        at = computations[name]
        started = {}
        for c in at.collectives:
            if c["opcode"].endswith("-done"):
                one = started.get(at.done_of[c["name"]])
                if one is not None:
                    one["instructions"][c["name"]] = "done"
                    one["held"] = c["held"]
                continue
            role = "start" if c["opcode"].endswith("-start") else "sync"
            started[c["name"]] = meet(c, name, c["name"], role, "", in_while, passes)
        for instruction, opcode, callees, op_name, trips in at.calls:
            if opcode in ("while", "conditional"):
                for callee in callees:
                    if callee in computations:
                        walk(callee, in_while or opcode == "while", passes * trips)
                continue
            for callee in callees:
                role = "start" if opcode == "async-start" else (computations[callee].role if callee in computations else None)
                for c in inside(callee):
                    if not c["opcode"].endswith("-done"):
                        started[instruction] = meet(c, callee, instruction, role or "fused", op_name, in_while, passes)
        for instruction, start in at.done_of.items():       # an `async-done` of a wrapped collective
            if start in started and instruction not in started[start]["instructions"]:
                started[start]["instructions"][instruction] = "done"

    if entry is not None:
        walk(entry, False, 1)
    out = []
    for one in found.values():
        roles = set(one["instructions"].values())
        if {"start", "done"} <= roles:      # what carries it between its two ends computes meanwhile
            one["instructions"] = {i: "overlap" if r == "fused" else r for i, r in one["instructions"].items()}
        held = one.pop("held")
        one["bytes"] = sum(held.values()) * (one["participants"] if one["kind"] == "reduce-scatter" else 1)
        one["dtype"] = max(held, key=held.get) if held else "?"
        out.append(one)
    return out


def _table(rows: Dict[str, list]) -> Dict[str, list]:
    """{row: [count, bytes]} cut to the `BY_OP_ROWS` dearest by bytes and `other`, as `TraceProfile.by_op`."""
    dearest = sorted(rows.items(), key=lambda kv: -kv[1][1])
    table = {name: list(row) for name, row in dearest[:BY_OP_ROWS]}
    rest = [row for _, row in dearest[BY_OP_ROWS:]]
    table["other"] = [sum(row[0] for row in rest), sum(row[1] for row in rest)]
    return table


def record_of(found: List[dict], mesh, program: str, module: str) -> dict:
    """The `kind="collectives"` step record of one compiled module."""
    tables = {by: defaultdict(lambda: [0, 0]) for by in ("kind", "axis", "dtype", "op")}     # row -> [count, bytes]
    instructions = {}
    for number, one in enumerate(found):
        for by, table in tables.items():
            table[one[by]][0] += one["passes"]
            table[one[by]][1] += one["passes"] * one["bytes"]
        for name, role in one["instructions"].items():
            # (an instruction that carries two collectives is the first's: its time is not split)
            instructions.setdefault(name, [one["kind"], role, one["op"], number])
    return {"kind": "collectives", "program": program, "module": module, "devices": int(mesh.size),
            "mesh": {str(a): int(n) for a, n in mesh.shape.items()},
            "ops": sum(row[0] for row in tables["kind"].values()), "bytes": sum(row[1] for row in tables["kind"].values()),
            "in_while": sum(one["in_while"] for one in found),
            **{f"by_{by}": {row: list(n) for row, n in tables[by].items()} for by in ("kind", "axis", "dtype")},
            "by_op": _table(tables["op"]), "instructions": instructions}


def publish(compiled, mesh, program: str, module: str) -> dict:
    """Walk `compiled`'s text and say what was found: the span `executor.collectives` round the walk (`ops=`,
    `bytes=`: what the record costs is on the set-up timeline), the counters `executor.collective_ops` and
    `executor.collective_bytes` (a step of every mesh module this process compiled, summed as the `lowering.*`
    counters are) and the step record (`record_of`).  The caller has checked that the monitor is on."""
    with _MON.span("executor.collectives", program=program, module=module) as walking:
        record = record_of(collectives_of(compiled.as_text(), mesh), mesh, program, module)
        walking.annotate(ops=record["ops"], bytes=record["bytes"])
    _MON.counter("executor.collective_ops").inc(record["ops"])
    _MON.counter("executor.collective_bytes").inc(record["bytes"])
    _MON.record_step(record)
    return record
