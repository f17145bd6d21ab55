"""Device ms a step spends on the exits of a looped model: the instructions
under the scopes `exit_head` (the four exits' head and exit gate: one product
and one pass over the loop's stacked outputs, AFTER the loop and outside its
`while`) and `exit_loss` (the four cross entropies and the exit-weighted
loss), which `paddle_tpu.models.transformer.build_causal_lm(loop=...)` opens
with `fluid.name_scope`, forward and backward, each event's OWN time
(`recompute_ms_per_step.own_times`, as that reader sums it).  Nothing of them
is computed again in backward (only the loop's body is), so this metric and
`recompute_ms_per_step` do not overlap.  The part of the step that a cut in depth
leaves over-weighted: four heads over the vocabulary beside 8 layers, not 48.
Nothing where the program has no such scope."""
import re

from benchmark.metrics import recompute_ms_per_step

LAYER = 'ops: kernels (ops/*.py)'
UNIT = 'ms'
BETTER = 'lower'
SOURCE = 'device_trace'
MOVES = 'train_samples_per_s'

#: a process that builds a second looped model gets `exit_head_1`: sibling `name_scope`s of one name are numbered
SCOPE = re.compile(r"/(exit_head|exit_loss)(_\d+)?/")


def exit_ms(spent: dict, names: dict):
    """Own ms of the instructions under the exits' scopes that are not
    recomputed; None without any."""
    mine = [ms for instruction, ms in spent.items()
            if SCOPE.search(names.get(instruction, "")) and recompute_ms_per_step.SCOPE not in names[instruction]]
    return sum(mine) if mine else None


def read(ctx: dict):
    found = recompute_ms_per_step.own_ms(ctx)
    return exit_ms(*found) if found else None
