"""The share of the exit distribution that lies on the LAST pass of a looped
model, the mean over a step's positions, median over the window's logged
steps: `exit_mass[-1]` of the program's `loop_exit` step records
(`pipeline.train_loop` publishes one per logged step for a program with a
`layers.exit_loss`, with the gauge `loop.exit_mass_last_pass`).  A gate at its
start (weights N(0, 0.02), bias 0) leaves each pass half of what is left:
12.5% on the fourth of four.  The share falling towards 0 is a gate that
learns to leave early, so the later passes' arithmetic buys less and less of
the loss.  A health check of the objective, not a lever on the step: the
step does the same arithmetic whatever the gate says (training reads every
exit), and the number moves with the seed that draws the gate (12 to 26% at
the start).  The cell also asserts here what the program promises: every logged
step's distribution sums to 1 to 1e-5 and its exits' cross entropies are
finite.  Nothing where the program has no such record."""
import math
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
    return last_pass_share(program_trace.program_monitor().step_records(), first)


def last_pass_share(records, first_step: int):
    found = [r for r in records if r.get("kind") == "loop_exit" and r["pipeline_step"] >= first_step]
    if not found:
        return None
    for r in found:
        assert abs(sum(r["exit_mass"]) - 1.0) <= 1e-5, f"step {r['pipeline_step']}: the exit masses sum to {sum(r['exit_mass'])}"
        assert all(math.isfinite(c) for c in r["exit_ce"]), f"step {r['pipeline_step']}: exit cross entropies {r['exit_ce']}"
    return 100.0 * median(r["exit_mass"][-1] for r in found)
