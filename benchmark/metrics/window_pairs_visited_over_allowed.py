"""The (query, key) pairs inside the blocks that the window rule's block maps
visit, over the pairs the rule allows: the program's trace-time counters
`lowering.window_pairs_visited` over `lowering.window_pairs_allowed`
(ops/masked_attention.py: `window_attention` counts both for every op that took
the rule, whichever program of the process lowered it: a ratio of sums over the
same ops).  1.0 is a kernel that computes no masked pair; a block of b keys
under a window of w reads about (b + w) / w.  Nothing where no op took the
rule (a program without a window, a parent that has no such counter, or the
rule lowered to XLA's attention off the TPU)."""
from benchmark import program_trace

LAYER = 'lowering (core/lowering.py)'
UNIT = 'ratio'
BETTER = 'lower'
SOURCE = 'program_counter'
MOVES = 'train_samples_per_s'


def read(ctx: dict):
    if "traffic" not in ctx:
        return None
    counted = program_trace.program_monitor().counter_values()
    allowed = counted.get("lowering.window_pairs_allowed", 0)
    return counted.get("lowering.window_pairs_visited", 0) / allowed if allowed else None
