"""Of the bytes a step's `recompute_scope` segments COULD keep for backward
(every product's output, kernel's residuals, expert products' outputs and
router's logits that a `registry.set_kept` rule names), the share `plan_kept`
found room for: the program's trace-time counters
`lowering.recomputed_kept_bytes` over `lowering.recomputed_candidates_bytes`
(core/lowering.py: counted once a trace of a program with a backward pass,
whichever program of the process lowered it: a ratio of sums over the same
segments).  100 is a step that makes nothing dear again; 0 a chip so full that
every segment is the plain `jax.checkpoint`'s.  Nothing where no segment was
planned (a program without a `recompute_scope`, a parent without the counters)."""
from benchmark import program_trace

LAYER = 'lowering (core/lowering.py)'
UNIT = '%'
BETTER = 'higher'
SOURCE = 'program_counter'
MOVES = 'train_samples_per_s'


def read(ctx: dict):
    if "traffic" not in ctx:
        return None
    counted = program_trace.program_monitor().counter_values()
    candidates = counted.get("lowering.recomputed_candidates_bytes", 0)
    return 100.0 * counted.get("lowering.recomputed_kept_bytes", 0) / candidates if candidates else None
