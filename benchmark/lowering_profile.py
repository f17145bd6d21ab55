"""Inside `executor.lower` (PR 52): where a cell's set-up spends the seconds of
its traces, from the spans the lowering opens under the executor's
`executor.lower` and the table that span carries.

    python3 -m benchmark.lowering_profile --workload <cell> [--seed <n>] [--seconds <s>]

runs the cell in this process as `benchmark.run --trace 1` does (its lines come
first, its result line included; what `python3 -m benchmark.setup_timeline`
runs, with a short window where none is asked for), then prints one line a
program the executor lowered before the window, `{"info": "lowering_program",
...}`, the start-up program, the step and the `for_test` clone apart (`kind`,
from the module's name):

  * `lower_s`: the program's `executor.lower` spans; `phases`: of them, the
    SELF seconds of `lowering.forward`, `.transpose`, `.update`, `.to_hlo`,
    `.sparse_probe` and `.plan_kept`, and `unattributed`: what is left of
    `executor.lower` and of `lowering.trace` outside them (the step function's
    own glue, JAX closing the trace, the walk that counts the jaxpr);
  * `jax_own_s`: by phase, its seconds less those of its rows in the table
    (the span's `ops_s`): JAX's linearisation in `forward`, its
    `backward_pass` in `transpose`;
  * `jaxpr_eqns`, `pallas_calls`: of the traced jaxpr, on `lowering.to_hlo`;
  * `by_op`: the span's table, `[phase, op type, self seconds, calls]` by
    seconds, the twelve dearest rows of the trace and `other`;

and one last line `{"info": "lowering_profile", ...}` with the cell's sums:
`lower_s`, `prepare_s` (the `executor.prepare` spans) and `setup_lower_s`
(`setup_timeline`'s part, which is the two together), the six `parts` that the
per-layer metrics report, and the programs' tables merged.

ONE reading of the set-up, used by the six readers under benchmark/metrics
(`setup_trace_forward_s`, `setup_trace_transpose_s`, `setup_trace_update_s`,
`setup_trace_probe_s`, `setup_to_hlo_s`, `setup_lower_unattributed_share`):
`setup_timeline.this_runs_setup` cuts the caller's thread from process start to
the window's first step into pieces, each with the innermost span over it; a
piece belongs to the part its innermost span names, so the five parts and the
unattributed seconds add up to the `executor.lower` spans' seconds by
construction.  A program without the spans (the parent of PR 52) gives None
everywhere and the metrics are left out of the line.

The arithmetic works on plain tuples, `setup_timeline`'s pieces and
`program_trace`'s spans, so that it is tested on events built by hand.
"""
from __future__ import annotations

from benchmark import setup_timeline as st  # first: its import stamps T_PROCESS

import json
import sys
from collections import defaultdict
from typing import Dict, Iterable, List, Optional, Sequence

from benchmark import manifest as mf
from benchmark import program_trace as pt

LOWER = "executor.lower"
TRACE = "lowering.trace"
TO_HLO = "lowering.to_hlo"
SPANS = "lowering."
FORWARD, TRANSPOSE, UPDATE, PROBE, HLO = "forward_s", "transpose_s", "update_s", "probe_s", "to_hlo_s"
UNATTRIBUTED = "unattributed_s"
PART_OF = {
    "lowering.forward": FORWARD, "lowering.transpose": TRANSPOSE,
    "lowering.update": UPDATE, TO_HLO: HLO,
    # traces that make no code
    "lowering.sparse_probe": PROBE, "lowering.plan_kept": PROBE,
    LOWER: UNATTRIBUTED, TRACE: UNATTRIBUTED,
}
PARTS = (FORWARD, TRANSPOSE, UPDATE, PROBE, HLO, UNATTRIBUTED)
OTHER = "other"
# the phase of a row outside the five (an op lowered under `lowering.trace` alone): attributed all the same
OUTSIDE = "trace"
DEFAULTS = (("--seed", "1"), ("--seconds", "5"))


def under_lower(pieces: Iterable[st.Piece]) -> List[st.Piece]:
    """The pieces whose innermost span is `executor.lower` or one of the
    lowering's, which open under it alone."""
    return [p for p in pieces if p[4] is not None and (p[4].name == LOWER or p[4].name.startswith(SPANS))]


def seconds_by_span(pieces: Iterable[st.Piece]) -> Dict[str, Dict[str, float]]:
    """{program: {span name: SELF seconds}} of the pieces under `executor.lower`."""
    out: dict = defaultdict(lambda: defaultdict(float))
    for s, e, _, _, span in under_lower(pieces):
        out[span.args.get("program")][span.name] += e - s
    return {k: dict(v) for k, v in out.items()}


def parts_of(pieces: Iterable[st.Piece]) -> Optional[Dict[str, float]]:
    """The six parts in seconds; None where no `executor.lower` lies before the
    window or the program opens no span under it."""
    by_span: dict = defaultdict(float)
    for spans in seconds_by_span(pieces).values():
        for name, seconds in spans.items():
            by_span[name] += seconds
    if not any(name.startswith(SPANS) for name in by_span):
        return None
    out = dict.fromkeys(PARTS, 0.0)
    for name, seconds in by_span.items():
        out[PART_OF.get(name, UNATTRIBUTED)] += seconds
    return out


def merged(tables: Iterable[Optional[dict]]) -> List[list]:
    """The spans' `by_op` tables as one, `[phase, op type, self seconds,
    calls]` by seconds, `other` last."""
    rows: dict = defaultdict(lambda: [0.0, 0])
    for table in tables:
        for key, (seconds, calls) in (table or {}).items():
            rows[key][0] += seconds
            rows[key][1] += calls
    rest = rows.pop(OTHER, [0.0, 0])
    out = [[*key.split(":", 1), *row] for key, row in sorted(rows.items(), key=lambda kv: -kv[1][0])]
    return out + [[OTHER, "", *rest]]


def unattributed_share(parts: Dict[str, float], table: Sequence[list]) -> Optional[float]:
    """% of the `executor.lower` seconds in none of the five parts nor in a
    row of the table outside the five phases."""
    total = sum(parts.values())
    if not total:
        return None
    outside = sum(row[2] for row in table if row[0] == OUTSIDE)
    return 100.0 * max(parts[UNATTRIBUTED] - outside, 0.0) / total


def lowering_spans(spans: Sequence[pt.Span], pieces: Sequence[st.Piece]) -> List[pt.Span]:
    """The `executor.lower` spans the pieces lie under and the lowering's spans
    inside them, in order."""
    mine = under_lower(pieces)
    if not mine:
        return []
    lo, hi, tid = mine[0][0], mine[-1][1], mine[0][4].tid
    return sorted((s for s in spans if s.tid == tid and s.start < hi and s.end > lo
                   and (s.name == LOWER or s.name.startswith(SPANS))), key=lambda s: s.start)


def by_program(inside: Sequence[pt.Span], pieces: Sequence[st.Piece]) -> List[dict]:
    """One entry a program lowered before the window, in order: the start-up
    program, the step and the `for_test` clone apart."""
    seconds = seconds_by_span(pieces)
    found: dict = {}
    for s in inside:
        entry = found.setdefault(s.args.get("program"), {"tables": [], "held": defaultdict(float), "size": {}})
        if s.name == LOWER:
            entry["module"] = s.args.get("module", "")
            entry["tables"].append(s.args.get("by_op"))
        else:
            entry["held"][s.name[len(SPANS):]] += s.args.get("ops_s", 0.0)
            entry["size"].update({k: s.args[k] for k in ("jaxpr_eqns", "pallas_calls") if k in s.args})
    out = []
    for program, entry in found.items():
        took = seconds.get(program, {})
        phases: dict = defaultdict(float)
        for name, s in took.items():
            phases["unattributed" if PART_OF.get(name, UNATTRIBUTED) == UNATTRIBUTED else name[len(SPANS):]] += s
        module = entry.get("module", "")
        out.append({"program": program, "module": module, "kind": module.split("_")[0],
                    "lower_s": sum(took.values()), "phases": dict(phases),
                    "jax_own_s": {k: phases[k] - held for k, held in entry["held"].items() if held and k in phases},
                    **entry["size"], "by_op": merged(entry["tables"])})
    return out


def this_runs_profile(traffic: dict) -> Optional[dict]:
    """The profile of the run in this process: the cell's sums, the merged
    `by_op` and the programs; None where `setup_timeline` finds no set-up or
    the program opens no span under `executor.lower`."""
    found = st.this_runs_setup(traffic)
    if found is None:
        return None
    pieces = found[0]
    parts = parts_of(pieces)
    if parts is None:
        return None
    inside = lowering_spans(pt.spans_of(pt.program_monitor().events()), pieces)
    lower_s, setup_lower_s = sum(parts.values()), st.parts_of(pieces)[st.LOWER]
    return {"lower_s": lower_s, "prepare_s": setup_lower_s - lower_s, "setup_lower_s": setup_lower_s, "parts": parts,
            "by_op": merged(s.args.get("by_op") for s in inside if s.name == LOWER),
            "programs": by_program(inside, pieces)}


def _sums(found: Optional[dict]) -> str:
    return json.dumps({"info": "lowering_profile", **{k: v for k, v in (found or {}).items() if k != "programs"}},
                      default=float)


def read_metric(ctx: dict, name: str) -> Optional[float]:
    """What a reader under benchmark/metrics calls: one of PARTS in seconds, or
    `unattributed_share` in %, which also prints the `lowering_profile` line."""
    found = this_runs_profile(ctx["traffic"])
    if found is None:
        return None
    if name != "unattributed_share":
        return found["parts"][name]
    print(_sums(found), flush=True)
    return unattributed_share(found["parts"], found["by_op"])


def main(argv=None, root: str = mf.ROOT) -> Optional[dict]:
    argv = list(sys.argv[1:] if argv is None else argv)
    for option, value in DEFAULTS:
        if option not in argv:
            argv += [option, value]
    st.bench_run.main(argv + ["--trace", "1"], root=root)
    cell = mf.cell(mf.load(root), argv[argv.index("--workload") + 1])
    found = this_runs_profile(mf.read_json(mf.traffic_path(cell["traffic"]), root))
    for program in (found or {}).get("programs", ()):
        print(json.dumps({"info": "lowering_program", **program}, default=float), flush=True)
    print(_sums(found), flush=True)
    return found


if __name__ == "__main__":
    main()
