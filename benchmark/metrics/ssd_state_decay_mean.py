"""The mean decay a token and head, exp(dt A), of the Mamba-2 layers: the
`decay_mean` of the program's `ssd_state` step records (`pipeline.train_loop`
publishes one per logged step for a program with an `ssd_scan` op: per layer
the mean decay, the mean step dt and the largest |h| of the state after the
last token), mean over the layers, median over the window's logged steps.  **A
health check, not a lever**: the step does the same arithmetic whatever the
decay is; at 1 a layer forgets nothing and its state grows with the sequence, at
0 it reads one token, and neither is a test of a scan.  The cell also asserts
here what the program promises: every logged step's state is finite.  Nothing
where the program has no such record."""
import math
from statistics import mean, median

from benchmark import program_trace

LAYER = 'lowering (core/lowering.py)'
UNIT = 'ratio'
BETTER = 'higher'
SOURCE = 'program_counter'
MOVES = 'train_samples_per_s'


def read(ctx: dict):
    first = (ctx.get("traffic") or {}).get("warmup_steps")
    if first is None:
        return None
    return decay_mean(program_trace.program_monitor().step_records(), first)


def decay_mean(records, first_step: int):
    found = [r for r in records if r.get("kind") == "ssd_state" and r["pipeline_step"] >= first_step]
    if not found:
        return None
    for r in found:
        assert all(math.isfinite(s) for s in r["state_abs_max"]), \
            f"step {r['pipeline_step']}: the scans' largest |h| {r['state_abs_max']}"
    return median(mean(r["decay_mean"]) for r in found)
