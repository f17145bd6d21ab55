"""Share of a step that the core spends IN a collective or waiting for one:
the device's OWN time (an event's duration less the events directly inside it:
`recompute_ms_per_step.own_ms`, a run of the step, the median device) under the
instructions that the program's `kind="collectives"` record names with a role
whose time is the collective's (`sync`, a collective scheduled as one
instruction; `start` and `done`, the two ends of an asynchronous one, the
`done` being where the core waits for what is still in flight; `fused`, a
fusion that IS the collective), over the mean time a run of the step takes in
the traced window (`window_s` over `main_module_runs` of the reduced trace).
It is what a perfect overlap would give back.  An `overlap` instruction (an
`async_collective_fusion` that computes while a gather goes on) is NOT counted:
its time is its product's.

`trace_reduce.is_collective`, which `collective_time_share` reads, sees an
event's opcode and so none of the TPU's fused collectives (`PERF.md`, defect
20a); this reader is told the names by the program, whose compiled text says
which fusion holds what.  Where every collective is synchronous and stands as
an instruction of its own (the dp4 cell) the two agree.

The own-time table is the one Jamba's and Nemotron-3-Super's readers make
(`ctx["ssm_own_ms"]`, as `ssm_ms_per_step.own_ms_under` keeps it): no second
sort of the trace.  The reader also prints an `info` line `collectives`: the
record's `by_kind` and `by_op` rows as `[count, bytes, own device ms a step]`,
the ms summed over the row's instructions, and the ms by role.  Nothing where
the program wrote no record or the run has no device trace."""
import json
from collections import defaultdict

from benchmark.metrics import collective_bytes_per_step, recompute_ms_per_step

LAYER = 'multi-chip (parallel/*)'
UNIT = '%'
BETTER = 'lower'
SOURCE = 'device_trace'
MOVES = 'train_samples_per_s'

#: the roles (`paddle_tpu/parallel/collectives.py` has the five) whose time is the collective's own
OWN_ROLES = ("sync", "start", "done", "fused")


def own_ms(ctx: dict):
    """({instruction: own ms a run of the step}, {instruction: op_name}) of the run, made once for every reader
    of it; None without a trace."""
    if "ssm_own_ms" not in ctx:
        ctx["ssm_own_ms"] = recompute_ms_per_step.own_ms(ctx)
    return ctx["ssm_own_ms"]


def step_ms(ctx: dict):
    """Mean ms a run of the step takes in the traced window."""
    t = ctx.get("trace") or {}
    return 1e3 * t["window_s"] / t["main_module_runs"] if t.get("main_module_runs") else None


def split(record: dict, spent: dict) -> dict:
    """The record's tables with the device's own ms beside count and bytes: `by_kind`, `by_op` (an op the record
    summed under `other` stays there), `by_role` (ms alone), `own_ms` (the roles that are the collective's)."""
    by_kind, by_op, by_role = defaultdict(float), defaultdict(float), defaultdict(float)
    for name, (kind, role, op, _) in record["instructions"].items():
        ms = spent.get(name, 0.0)
        by_role[role] += ms
        if role in OWN_ROLES:
            by_kind[kind] += ms
            by_op[op if op in record["by_op"] else "other"] += ms
    return {"own_ms": sum(by_kind.values()), "by_role": dict(by_role),
            "by_kind": {k: [*row, by_kind.get(k, 0.0)] for k, row in record["by_kind"].items()},
            "by_op": {k: [*row, by_op.get(k, 0.0)] for k, row in record["by_op"].items()}}


def read(ctx: dict):
    record = collective_bytes_per_step.step_record(ctx)
    step = step_ms(ctx)
    found = own_ms(ctx) if record and step else None
    if found is None:
        return None
    table = split(record, found[0])
    print(json.dumps({"info": "collectives", "module": record["module"], "step_ms": step, "ops": record["ops"],
                      "bytes": record["bytes"], **table}), flush=True)
    return 100.0 * table["own_ms"] / step
