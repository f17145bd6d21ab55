"""Tokens of the busiest expert over the mean, the worst layer's, median over
the window's logged steps: the program's `moe_routing` step records
(`pipeline.train_loop` publishes one per logged step, with the gauges
`moe.load_max_over_mean`, `moe.load_min_over_mean`, `moe.dropped_tokens`).
1.0 is perfect balance: what later says whether a slow step is the kernel or
the routing.  The cell also asserts here what the program promises: no
logged step dropped a token.  Nothing where the program has no such record."""
from statistics import median

from benchmark import program_trace

LAYER = 'lowering (core/lowering.py)'
UNIT = 'ratio'
BETTER = 'lower'
SOURCE = 'program_counter'
MOVES = 'train_samples_per_s'


def read(ctx: dict):
    first = ctx["traffic"].get("warmup_steps")
    if first is None:
        return None
    return load_max_over_mean(program_trace.program_monitor().step_records(), first)


def load_max_over_mean(records, first_step: int):
    found = [r for r in records if r.get("kind") == "moe_routing"
             and r["pipeline_step"] >= first_step]
    if not found:
        return None
    dropped = [(r["pipeline_step"], r["dropped_tokens"]) for r in found if r["dropped_tokens"]]
    assert not dropped, f"moe.dropped_tokens is not 0 at steps {dropped[:4]}"
    return median(max(r["load_max_over_mean"]) for r in found)
