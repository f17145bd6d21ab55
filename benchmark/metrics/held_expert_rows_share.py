"""The share of a step's (token, slot) assignments that landed on the experts
this chip holds, the worst (largest) layer's, median over the window's logged
steps: the `held_rows_share` of the program's `moe_routing` step records
(`pipeline.train_loop` publishes one per logged step for a layer built with
`layers.moe(held=...)`, with the gauge `moe.held_rows_share`).  12.5% is a
uniform router over 128 experts of which 16 are held; the lowering passes over
twice that share of the rows at no extra cost and over all of them, by a
second path, beyond it (ops/moe_ops.py).  The cell also asserts here what the
program promises: no logged step left an assignment to a held expert out.
Nothing where the program has no such record."""
from statistics import median

from benchmark import program_trace

LAYER = 'lowering (core/lowering.py)'
UNIT = '%'
BETTER = 'lower'
SOURCE = 'program_counter'
MOVES = 'train_samples_per_s'


def read(ctx: dict):
    first = (ctx.get("traffic") or {}).get("warmup_steps")
    if first is None:
        return None
    return held_rows_share(program_trace.program_monitor().step_records(), first)


def held_rows_share(records, first_step: int):
    found = [r for r in records if r.get("kind") == "moe_routing" and "held_rows_share" in r
             and r["pipeline_step"] >= first_step]
    if not found:
        return None
    dropped = [(r["pipeline_step"], r["dropped_tokens"]) for r in found if r["dropped_tokens"]]
    assert not dropped, f"moe.dropped_tokens is not 0 at steps {dropped[:4]}"
    return 100.0 * median(max(r["held_rows_share"]) for r in found)
